"""The grouped-matmul kernels' share of their roofline (flops/olmoe.py
`kernel_cost()['moe_mlp']` over the measured time of the megablox pair's
Mosaic events: grouped_matmul_ms.py)."""
from chipbench.harness import kernels


def read(reading):
    return kernels.roofline_pct(reading, 'moe_mlp', kernels.MEGABLOX)
