"""Host time of the program's `executor.rng` span, per step of the
window: the `jax.random.key` of the step, two small device programs."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.rng')
