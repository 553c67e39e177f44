"""Device time per step in the expert layer: every event whose Fluid scope
is a `moe_mlp` op, forward and backward (router, sort, gathers, the
grouped matmuls, the weighted sum), from the trace."""


def read(reading):
    red = reading['trace']
    if red is None or not red['fluid_op_s'].get('moe_mlp'):
        return None
    return 1e3 * red['fluid_op_s']['moe_mlp'] / red['steps']
