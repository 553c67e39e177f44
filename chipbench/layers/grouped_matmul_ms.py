"""Device time per step of the grouped-matmul kernels: the Mosaic events
in `moe_mlp` scopes that jax's megablox pair makes (called in `gmm` and
`tgmm`: kernels.MEGABLOX). Another kernel of the expert layer, such as one
that adds the experts' rows to their tokens, is `moe_ms`'s and not this
metric's."""
from chipbench.harness import kernels


def read(reading):
    return kernels.ms(reading, 'moe_mlp', kernels.MEGABLOX)
