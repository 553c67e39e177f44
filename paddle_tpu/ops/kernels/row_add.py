"""The add of a layout's rows to the tokens they belong to, as one Pallas
kernel that walks the LIVE rows only: what a held share's compact path
(fluid/ops_impl/moe_ops.py `_add_up`) does twice a layer, forward under
`moe_combine` and, as the transpose of the row gather, in the backward
pass.

    out[t] = sum of gate[j] x rows[j] over the j with token[j] == t  float32

`rows` [cap, d] come as the grouped matmuls leave them: sorted by expert,
each expert's rows by token (the sort that laid them out is stable), the
rows no assignment fills after them all. No copy in token order is made:
XLA's gather of `cap` rows into that order costs a row the same whatever
the order, and its scatter-add walks every one of them at 40 to 67 ns
whatever it holds (docs/perf.md "A held share's rows, by index"). Here the
trip count follows the live rows on the device.

How. The tokens are cut into tiles of `T` and the rows into chunks of `R`
(`tiles(d)`). A tile's rows are ONE contiguous range in each expert's
group, so what a tile needs is, an expert, the few chunks its range
touches: `plan` finds the ranges (a count of each tile's keys by expert,
in XLA, once a layer for both adds) and lists the (tile, chunk) steps with
each step's range, a tile after the other, every tile at least once. The
grid walks that list (scalar prefetch; its static length is tiles x
experts + chunks, what the steps can never pass; the steps past the last
name the last step's blocks again, so nothing is fetched for them and
nothing runs). A step builds the matrix [T, R] "row j is token t's, and
inside the step's range" from the chunk's tokens and adds its product with
the chunk [R, d] to the tile's float32 block [T, d], which stays in VMEM
from the tile's first step (zeroed there) to its last (written once: a
token with no held expert gets zeros). Chunks need no alignment to ranges:
a row outside the range meets a column of zeros.

The product is the MXU's and the add float32 whatever the rows are. A 0/1
matrix times bf16 rows is exact under float32 accumulation; with gates the
matrix carries them, cut into three bf16 parts that add up to the float32
gate, so each product is exact again (three passes); float32 rows (a
cell's float32 check) multiply at full precision. Against the scatter-add
of float32(row) x gate the result differs in the order of a token's few
adds and in one rounding a product.

A row no assignment fills is read only where it shares a chunk with a live
one (or, with no live row at all, chunk 0) and must hold zeros there, as
`_add_up` asks: 0 x NaN is NaN.

Within the default 16 MiB of scoped VMEM (`usable` counts the blocks) and
with no `vmem_limit_bytes`: a Mosaic call that states one makes XLA plan
the whole step anew (PR 42).

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the grouped matmul
it is what the rule lowers to on the TPU, and the scatter-add elsewhere.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta_intra import _dot

__all__ = ['row_add', 'plan', 'tiles', 'usable']

_LANES = 128
# of the 16 MiB of scoped VMEM, what the call's blocks may take
_VMEM = 12 << 20
_BF16, _F32 = jnp.bfloat16, jnp.float32


def tiles(d):
    """(T, R): the tokens of a tile and the rows of a chunk
    (tools/bench_row_add.py --sweep; docs/perf.md has the rows), the tile
    halved until its float32 block fits VMEM at width `d`."""
    t, r = 256, 128
    while t > 8 and _held(t, r, d) > _VMEM:
        t //= 2
    return t, r


def _held(t, r, d):
    """Bytes of VMEM a call holds at float32 rows: the chunk and the
    tile's block twice (the pipeline's two buffers), the product before it
    is added, the matrix and its parts."""
    return 2 * r * d * 4 + 3 * t * d * 4 + 4 * t * r * 4


def usable(cap, n, d, dtype, tile=None):
    """Rows of whole lane tiles, a layout of whole chunks, tokens of whole
    tiles, bf16 or float32 rows, the blocks within VMEM; at `tile` (T, R)
    where one is given (the sweep's door), else at `tiles(d)`."""
    t, r = tile or tiles(d)
    return (d % _LANES == 0 and cap % r == 0 and n % t == 0
            and _held(t, r, d) <= _VMEM
            and jnp.dtype(dtype) in (jnp.dtype(_BF16), jnp.dtype(_F32)))


def plan(key, groups, cap, d, tile=None):
    """The steps a call walks, from the layer's keys: `key` [n, k] int32
    is each assignment's group, or `groups` where it has no row; the rows
    are the assignments with one, sorted by group and a group's in the
    order of `key` (token-major), `cap` of them laid out. Returns (tile,
    chunk, lo, hi [W], steps [1]), int32, W = tiles x groups + chunks:
    step w adds the rows lo[w] <= j < hi[w] of chunk[w] to tile[w]. All of
    it dense arithmetic over tiles x groups and W (a search or a gather of
    scalars is a loop to XLA). One plan serves every add over the same
    rows at the same width."""
    t, r = tile or tiles(d)
    n, k = key.shape
    nt = n // t
    group = lax.iota(jnp.int32, groups)[None, :, None]
    # a (tile, group) pair's rows, and where they lie in the layout
    rows = jnp.sum(key.reshape(nt, 1, t * k) == group, axis=-1,
                   dtype=jnp.int32)
    total = jnp.sum(rows, axis=0)
    hi = (jnp.cumsum(total) - total)[None, :] + jnp.cumsum(rows, axis=0)
    lo = hi - rows
    count = jnp.where(rows > 0, -(-hi // r) - lo // r, 0)
    # every tile at least once (with an empty range): its block is written
    count = jnp.maximum(count, (group[:, :, 0] == 0).astype(jnp.int32)
                        ).reshape(-1)
    end = jnp.cumsum(count)
    w = jnp.minimum(lax.iota(jnp.int32, nt * groups + cap // r), end[-1] - 1)
    mine = ((end - count)[None, :] <= w[:, None]) & (w[:, None] < end)

    def of(x):
        """x of each step's pair [W], of x a pair"""
        return jnp.sum(jnp.where(mine, x.reshape(-1), 0), axis=1,
                       dtype=jnp.int32)

    lo, hi = of(lo), of(hi)
    last = jnp.maximum(jnp.sum(total) - 1, 0) // r
    chunk = jnp.minimum(lo // r + w - of(end - count), last)
    pair = of(lax.iota(jnp.int32, nt * groups))
    return pair // groups, chunk, lo, hi, end[-1:]


def _parts(x):
    """float32 -> three bf16 that add up to it."""
    out = []
    for _ in range(3):
        out.append(x.astype(_BF16))
        x = x - out[-1].astype(_F32)
    return out


def _kernel(tile_ref, chunk_ref, lo_ref, hi_ref, steps_ref, token_ref,
            *refs):
    """One (tile, chunk) step; `refs` is ([gate,] rows, out)."""
    rows_ref, out_ref = refs[-2:]
    w = pl.program_id(0)
    tile = tile_ref[w]

    @pl.when((w == 0) | (tile != tile_ref[jnp.maximum(w - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(w < steps_ref[0])
    def _():
        rows = rows_ref[...]
        t, r = out_ref.shape[0], rows.shape[0]
        row = lax.broadcasted_iota(jnp.int32, (1, r), 1) + chunk_ref[w] * r
        # each row's token within the tile; -1, no token's, out of range
        slot = jnp.where((row >= lo_ref[w]) & (row < hi_ref[w]),
                         token_ref[...] - tile * t, -1)
        hit = lax.broadcasted_iota(jnp.int32, (t, r), 0) == slot
        if len(refs) == 2:
            product = _dot(jnp.where(hit, 1.0, 0.0), rows, 'nn', rows.dtype)
        else:
            gate = jnp.where(hit, refs[0][...], 0.0)
            product = (_dot(gate, rows, 'nn', _F32) if rows.dtype == _F32
                       else sum(_dot(part, rows, 'nn', _BF16)
                                for part in _parts(gate)))
        out_ref[...] += product


@functools.partial(jax.jit, static_argnames=('n', 'interpret', 'tile'))
def row_add(rows, token, gate, steps, *, n, interpret, tile=None):
    """rows [cap, d] (bf16 or float32), token [cap] int32 the token of
    each, gate [cap] float32 or None (ones): [n, d] float32. `steps` is
    `plan` of the layer's keys; `tile` overrides `tiles(d)`, in the plan
    and here alike (the sweep's door). A jitted function of this name, so
    that a profile names the kernel's time by it."""
    cap, d = rows.shape
    t, r = tile or tiles(d)

    def chunk(w, tile, chunk, *_):
        return chunk[w], 0, 0

    small = [token] if gate is None else [token, gate]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps[0].shape[0],),
            in_specs=[pl.BlockSpec((None, 1, r), chunk)] * len(small)
            + [pl.BlockSpec((r, d), lambda w, tile, chunk, *_:
                            (chunk[w], 0))],
            out_specs=pl.BlockSpec((t, d), lambda w, tile, *_:
                                   (tile[w], 0))),
        out_shape=jax.ShapeDtypeStruct((n, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='row_add',
    )(*steps, *(s.reshape(cap // r, 1, r) for s in small), rows)
