"""Device time per step in the loss head, from the trace: the output
projection (the `mul` scope with the highest index: the last fully
connected layer the builder makes), `one_hot`, `label_smooth`,
`softmax_with_cross_entropy` and the masked mean after it, forward and
backward."""

HEAD_OPS = ('one_hot', 'label_smooth', 'softmax_with_cross_entropy')


def read(reading):
    red = reading['trace']
    if red is None:
        return None
    muls = [k for k in red['fluid_scope_s'] if k.rsplit('_', 1)[0] == 'mul']
    s = sum(v for k, v in red['fluid_op_s'].items() if k in HEAD_OPS)
    if muls:
        s += red['fluid_scope_s'][max(muls, key=lambda k: int(
            k.rsplit('_', 1)[1]))]
    return 1e3 * s / red['steps'] if s else None
