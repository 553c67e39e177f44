"""Device time per step of the grouped-matmul kernels: the Mosaic events
that lie in `moe_mlp` scopes."""
from chipbench.harness import kernels


def read(reading):
    return kernels.ms(reading, 'moe_mlp')
