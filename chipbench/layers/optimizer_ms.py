"""Device time per step of the events whose root lies in an optimizer
scope (`adam_*`, `momentum_*`), from the trace. A LOWER BOUND on the
optimizer's cost: a fusion is one event and goes to the scope of its
root, and XLA fuses most parameter updates into the weight-gradient
matmul or convolution that feeds them, where their time cannot be told
from the root's. An optimizer change is read from `mfu_pct` (the
device's busy time per step) and the rate; this metric shows only how
much of the update still runs as events of its own."""

OPTIMIZER_OPS = ('adam', 'momentum')


def read(reading):
    red = reading['trace']
    if red is None:
        return None
    s = sum(v for k, v in red['fluid_op_s'].items() if k in OPTIMIZER_OPS)
    return 1e3 * s / red['steps']
