"""Device time per step in the global mixers without positions: every
event whose Fluid op scope lies under the name scope `global_attention`
(the four projections, the transposes and the flash kernels on the whole
triangle), forward and backward and what a region runs again, from the
trace. Nothing to read where the program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'global_attention')
    return None if s is None else 1e3 * s
