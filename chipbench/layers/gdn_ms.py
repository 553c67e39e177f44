"""Device time per step in the gated delta rule: every event whose Fluid
scope is a `gated_delta_rule` op, forward and backward (the stages
`gdn_intra` and `gdn_scan` of both), from the trace. Nothing to read where
the program has no such op."""


def read(reading):
    red = reading['trace']
    if red is None or not red['fluid_op_s'].get('gated_delta_rule'):
        return None
    return 1e3 * red['fluid_op_s']['gated_delta_rule'] / red['steps']
