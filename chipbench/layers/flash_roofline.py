"""The flash kernels' share of their roofline: the least time the chip
could take for what the kernels of one step require (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, harness/peaks.py and
flops/<config>.py) over the kernels' measured time. Which bound applies
goes on the summary line (`kernel_bound`)."""
from chipbench.harness import peaks


def read(reading):
    red, cost, peak = (reading['trace'], reading['kernel_cost'],
                       reading['peaks'])
    if red is None or not red['kernel_s'] or cost is None or peak is None:
        return None
    least_s, _ = peaks.roofline(cost, peak)
    return 100.0 * least_s / (red['kernel_s'] / red['steps'])
