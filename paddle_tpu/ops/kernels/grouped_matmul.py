"""Grouped matmul: rows [m, k], sorted into contiguous groups, times a stack
of matrices [groups, k, n], group g's rows by rhs[g]. The matmul of a
dropless expert layer (fluid/ops_impl/moe_ops.py): m is always tokens x
top_k, whatever the router's imbalance.

The Pallas kernels are jax's own megablox pair
(jax.experimental.pallas.ops.tpu.megablox.gmm: `gmm`, and `tgmm` for the
gradient of the stack); this module is the differentiable wrapper, with a
tile for each of the three calls instead of megablox's one for all
(its `tgmm` keeps a [tk, tn] float32 accumulator and runs out of VMEM at
the tile its `gmm` wants), tuned on the v5e at OLMoE's shapes with
`tools/bench_grouped_matmul.py --sweep` (docs/perf.md has the rows).

Not under the PADDLE_TPU_KERNELS knob: like the flash kernels it is what
the op lowers to on the TPU, and `lax.ragged_dot` elsewhere (on the chip
ragged_dot took 2.5 to 4.6 times a dense batched matmul of the same FLOPs,
forward plus backward; my chip run, PR 26).
"""
import functools
import importlib

import jax

# the package re-exports its `gmm` function under the module's name
_megablox = importlib.import_module(
    'jax.experimental.pallas.ops.tpu.megablox.gmm')

__all__ = ['grouped_matmul', 'usable', 'TILES']

# (tm, tk, tn) of the three calls at 2-byte operands, in this order: the
# forward [m, k] x [g, k, n]; the gradient of the rows, the same kernel on
# the transposed stack (its k is the forward's n); the gradient of the
# stack, tgmm, whose m is the contraction. `_fit` cuts a tile to its
# problem and to VMEM.
TILES = ((256, 2048, 2048), (256, 2048, 2048), (256, 1024, 1024))


def usable(m):
    """The kernels take whole row tiles: m a multiple of 128."""
    return m % 128 == 0


def _fit(tiles, m, k, n, itemsize, budget=4 << 20):
    """The tile cut to the problem: tm to a divisor of m, tk and tn to
    their dimensions, and the [tk, tn] block (the stack's tile in gmm, the
    output's in tgmm) halved along its longer side until it is within
    `budget` bytes at 2-byte operands, which is what double-buffered fits
    the 16 MiB of scoped VMEM beside the rows' tiles (4-byte operands: the
    float32 check of chipbench's olmoe configuration). A halved side stays
    whole lane tiles (a width of 1856 halves to 896, not 928: Mosaic takes
    a block's last dimension in 128s or whole); the kernels mask what the
    last tile of a side overhangs."""
    tm, tk, tn = tiles
    while m % tm:
        tm //= 2
    tk, tn = min(tk, k), min(tn, n)

    def half(side):
        return max(128, side // 2 // 128 * 128)

    # 4-byte operands: half the block again (the rows' tiles double too)
    while tk * tn * itemsize > budget // max(1, itemsize // 2):
        if tk >= tn:
            tk = half(tk)
        else:
            tn = half(tn)
    return tm, tk, tn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, interpret, tiles=None):
    """[m, k] x [groups, k, n] -> [m, n] in lhs's dtype, float32
    accumulation. `group_sizes` [groups] int32 sums to m. `interpret` as
    every kernel here: True for the Pallas interpreter, False for Mosaic.
    `tiles` overrides TILES (the sweep's door)."""
    tiles = tiles or TILES
    m, k = lhs.shape
    return _megablox.gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        _fit(tiles[0], m, k, rhs.shape[2], lhs.dtype.itemsize),
        interpret=interpret)


def _fwd(lhs, rhs, group_sizes, interpret, tiles):
    return (grouped_matmul(lhs, rhs, group_sizes, interpret, tiles),
            (lhs, rhs, group_sizes))


def _bwd(interpret, tiles, res, g):
    lhs, rhs, group_sizes = res
    tiles = tiles or TILES
    m, k = lhs.shape
    n = rhs.shape[2]
    g = g.astype(lhs.dtype)
    size = lhs.dtype.itemsize
    d_lhs = _megablox.gmm(g, rhs, group_sizes, lhs.dtype,
                          _fit(tiles[1], m, n, k, size),
                          transpose_rhs=True, interpret=interpret)
    d_rhs = _megablox.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                           _fit(tiles[2], m, k, n, size, budget=2 << 20),
                           num_actual_groups=rhs.shape[0],
                           interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_fwd, _bwd)
