"""Device time per step of what the SUMMARIES cost beside the exact part:
every event whose Fluid op scope lies under the name scope `eva_summary`
(the chunk pooling, forward and backward and what the backward pass runs
again), plus the Mosaic events of the attention op that were called in
the staircase's own jitted functions (`staircase_fwd`, `staircase_bwd`:
the summary part's forward, dq and dk/dv kernels), from the trace. The
attention is ONE Fluid op, so the merge's elementwise fusions lie in its
scope with the exact part's and are `eva_ms`'s, not this metric's.
Nothing to read where the program names no such scope."""
from chipbench.harness import kernels

STAIRCASE = ('staircase_fwd', 'staircase_bwd')


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'eva_summary')
    if s is None:
        return None
    stairs = kernels.ms(reading, 'flash_attention', STAIRCASE)
    return 1e3 * s + (stairs or 0.0)
