"""Device time per step in the dense gated feed-forwards: every event whose
Fluid op scope lies under the name scope `dense_mlp` (the two `mul`, the
split, the SiLU and the product between them), forward and backward and
what a region runs again, from the trace. Nothing to read where the
program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'dense_mlp')
    return None if s is None else 1e3 * s
