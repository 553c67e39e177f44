"""Device time per step in the selective state-space scan: every event
whose Fluid scope is an `ssd_scan` op, forward and backward (the stages
`ssd_intra`, `ssd_scan` and `ssd_inter` of both, the backward's run twice),
from the trace. Nothing to read where the program has no such op."""


def read(reading):
    red = reading['trace']
    if red is None or not red['fluid_op_s'].get('ssd_scan'):
        return None
    return 1e3 * red['fluid_op_s']['ssd_scan'] / red['steps']
