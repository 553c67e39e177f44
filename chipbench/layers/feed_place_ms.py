"""Host time of the program's `executor.feed` span, per step of the
window: `_place_feed`, which converts each fed array and starts its
transfer. The transfer itself is asynchronous and has no host span: what
of it is not hidden shows as device idle under `executor.fetch`."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.feed')
