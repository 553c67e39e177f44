"""One Pallas kernel's time and roofline share, told from the others'.

The trace's reduction keeps the Mosaic events' time by the Fluid op type of
the scope they lie in (`kernel_by_op_s`, harness/trace.py), and a
configuration's flops/<config>.py `kernel_cost()` gives what each kernel
requires under the same key: {op type: (FLOPs, bytes)}. A per-layer metric
of one kernel is a reader that names its op type (layers/README.txt).
Where an op's scopes hold kernels of several kinds, the reader names the
jitted functions its kernel is called in besides (`callees`:
`kernel_by_callee_s`, scopes.callee_of), and the others' time stays out.
"""
from chipbench.harness import peaks

# the functions jax's megablox kernels are called in (ops/kernels/
# grouped_matmul.py: `gmm` forward and for d_lhs, `tgmm` for d_rhs)
MEGABLOX = ('gmm', 'tgmm')


def _window(reading, op, callees=None):
    """(device seconds of the Mosaic events in `op`'s scopes over the
    traced window, its steps), or None where the trace has none; with
    `callees`, of the events called in those functions alone."""
    red = reading['trace']
    if red is None:
        return None
    if callees is None:
        seconds = red['kernel_by_op_s'].get(op)
    else:
        by = red['kernel_by_callee_s'].get(op, {})
        seconds = sum(by.get(c, 0.0) for c in callees)
    return (seconds, red['steps']) if seconds else None


def ms(reading, op, callees=None):
    """The kernel's device milliseconds a step."""
    got = _window(reading, op, callees)
    return None if got is None else 1e3 * got[0] / got[1]


def roofline_pct(reading, op, callees=None):
    """The least time the chip could take for what the kernel requires of
    one step (the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s) over its measured time, in percent."""
    got = _window(reading, op, callees)
    cost = (reading['kernel_cost'] or {}).get(op)
    if got is None or cost is None or reading['peaks'] is None:
        return None
    least_s, _ = peaks.roofline(cost, reading['peaks'])
    return 100.0 * least_s / (got[0] / got[1])
