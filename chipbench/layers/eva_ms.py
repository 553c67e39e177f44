"""Device time per step in the EVA mixers: every event whose Fluid op
scope lies under the name scope `eva_mixer` (the mixers' four projections,
the rotary, the chunk pooling, the attention op's kernels of both
geometries and their merge), forward and backward and what the backward
pass runs again, from the trace. Nothing to read where the program names
no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'eva_mixer')
    return None if s is None else 1e3 * s
