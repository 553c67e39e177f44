"""The flash kernels' share of their roofline: the least time the chip
could take for what the kernels of one step require (harness/peaks.py and
flops/<config>.py `kernel_cost()['flash_attention']`) over the kernels'
measured time. Which bound applies goes on the summary line
(`kernel_bound`)."""
from chipbench.harness import kernels


def read(reading):
    return kernels.roofline_pct(reading, 'flash_attention')
