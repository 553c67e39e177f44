"""One Pallas kernel's time and roofline share, told from the others'.

The trace's reduction keeps the Mosaic events' time by the Fluid op type of
the scope they lie in (`kernel_by_op_s`, harness/trace.py), and a
configuration's flops/<config>.py `kernel_cost()` gives what each kernel
requires under the same key: {op type: (FLOPs, bytes)}. A per-layer metric
of one kernel is a reader that names its op type (layers/README.txt).
"""
from chipbench.harness import peaks


def _window(reading, op):
    """(device seconds of the Mosaic events in `op`'s scopes over the
    traced window, its steps), or None where the trace has none."""
    red = reading['trace']
    if red is None or not red['kernel_by_op_s'].get(op):
        return None
    return red['kernel_by_op_s'][op], red['steps']


def ms(reading, op):
    """The kernel's device milliseconds a step."""
    got = _window(reading, op)
    return None if got is None else 1e3 * got[0] / got[1]


def roofline_pct(reading, op):
    """The least time the chip could take for what the kernel requires of
    one step (the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s) over its measured time, in percent."""
    got = _window(reading, op)
    cost = (reading['kernel_cost'] or {}).get(op)
    if got is None or cost is None or reading['peaks'] is None:
        return None
    least_s, _ = peaks.roofline(cost, reading['peaks'])
    return 100.0 * least_s / (got[0] / got[1])
