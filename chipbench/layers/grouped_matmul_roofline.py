"""The grouped-matmul kernels' share of their roofline (flops/olmoe.py
`kernel_cost()['moe_mlp']` over their measured time)."""
from chipbench.harness import kernels


def read(reading):
    return kernels.roofline_pct(reading, 'moe_mlp')
