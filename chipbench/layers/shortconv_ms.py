"""Device time per step in the short-convolution mixers: every event whose
Fluid op scope lies under the name scope `short_conv_mixer` (the two
projections, `split`, the gates and the convolution), forward and backward
and what a region runs again, from the trace. Nothing to read where the
program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'short_conv_mixer')
    return None if s is None else 1e3 * s
