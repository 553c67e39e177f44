"""Device time per step in the norms on the two branches' OUTPUTS: every
event whose Fluid op scope lies under the name scope `sandwich_norm` (the
`rms_norm` between a mixer or a feed-forward and the residual stream: two
a layer), forward and backward and what a region runs again, from the
trace: what the changed residual path costs a step. Nothing to read where
the program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'sandwich_norm')
    return None if s is None else 1e3 * s
