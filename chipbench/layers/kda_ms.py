"""Device time per step in the Kimi Delta Attention mixers: every event
whose Fluid op scope lies under the name scope `kda_mixer` (the mixers'
norms, six projections, three convolutions, the decay's elementwise chain,
the delta rule's kernels, the gated norm), forward and backward and what
the backward pass runs again, from the trace. Nothing to read where the
program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'kda_mixer')
    return None if s is None else 1e3 * s
