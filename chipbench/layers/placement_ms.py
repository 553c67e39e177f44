"""Host time of the program's `executor.placement` span, per step of the
window: `_ensure_dist_placement`, which under a mesh walks the Program's
persistables to see that each lies where its sharding says."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.per_step_ms(reading, 'executor.placement')
