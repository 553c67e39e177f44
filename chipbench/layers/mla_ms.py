"""Device time per step in latent attention: every event whose Fluid op
scope lies under the name scope `latent_attention` (the mixers' norms,
projections, rotary, concatenations and their flash kernels), forward and
backward and what the backward pass runs again, from the trace. Nothing
to read where the program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'latent_attention')
    return None if s is None else 1e3 * s
