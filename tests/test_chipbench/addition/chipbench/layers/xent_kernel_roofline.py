"""The cross-entropy kernel's share of its roofline (flops/tiny_lm.py
`kernel_cost()['softmax_with_cross_entropy']` over its measured time)."""
from chipbench.harness import kernels


def read(reading):
    return kernels.roofline_pct(reading, 'softmax_with_cross_entropy')
