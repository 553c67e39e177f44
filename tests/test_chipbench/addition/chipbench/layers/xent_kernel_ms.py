"""Device time per step of the cross-entropy kernel: the Mosaic events that
lie in `softmax_with_cross_entropy` scopes."""
from chipbench.harness import kernels


def read(reading):
    return kernels.ms(reading, 'softmax_with_cross_entropy')
