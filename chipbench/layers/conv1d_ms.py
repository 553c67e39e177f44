"""Device time per step in the depthwise causal convolutions: every event
whose Fluid scope is a `causal_conv1d` op, forward and backward, from the
trace. Nothing to read where the program has no such op."""


def read(reading):
    red = reading['trace']
    if red is None or not red['fluid_op_s'].get('causal_conv1d'):
        return None
    return 1e3 * red['fluid_op_s']['causal_conv1d'] / red['steps']
