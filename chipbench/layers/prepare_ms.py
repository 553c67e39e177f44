"""Self time of the program's `executor.prepare` span, per step of the
window: `_prepare` without its `executor.placement` and `executor.feed`
children, which leaves the derivation of the cache key (the scan of the
Program's variables against the scope, the shardings, the hash), the
verifier's lookup and `pin_state`."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.prepare', own=True)
