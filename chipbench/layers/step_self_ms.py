"""Self time of the program's `executor.step` span, per step of the
window: what no child span covers, `executor.fetch` among them: the
writes of the new persistables into the scope, the step's bookkeeping and
the spans' own cost. With `prepare_ms`, `feed_place_ms`, `rng_ms`,
`dispatch_ms` and the `executor.placement` time (`placement_ms`) it adds
up to `host_dispatch_ms` of the same window."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.step', own=True)
