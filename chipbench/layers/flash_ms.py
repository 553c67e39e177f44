"""Device time per step of the flash kernels: the Mosaic (Pallas) events
that lie in `flash_attention` scopes."""
from chipbench.harness import kernels


def read(reading):
    return kernels.ms(reading, 'flash_attention')
