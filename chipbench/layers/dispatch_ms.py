"""Host time of the program's `executor.dispatch` span, per step of the
window: the steady call of the jitted step, which is jax's own dispatch
(flattening some 600 arrays, the C++ fast path, the enqueue)."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.dispatch')
