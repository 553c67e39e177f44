"""Device time per step in the multi-token prediction module: every event
whose Fluid op scope lies under the name scope `mtp` (its two norms and
projection, its layer, its own latent attention among it, its use of the
shared head and its cross entropy), forward and backward and what the
backward pass runs again, from the trace. Nothing to read where the
program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'mtp')
    return None if s is None else 1e3 * s
