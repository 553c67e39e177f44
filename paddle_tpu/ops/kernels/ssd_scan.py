"""Mamba-2's selective state-space scan as one Pallas kernel forward and one
backward: a grid step owns one chunk of some heads of one group, everything
[chunk, chunk] of it (the decays, C B^T, M) lives in VMEM for that step, and
the state between chunks is a float32 VMEM scratch that the grid's last,
sequential axis walks.

The mathematics, its precisions and the names are those of
fluid/ops_impl/linear_attention_ops.py `_ssd_stages`, which stays as the
composition this is tested against and as every other platform's path. Per
head, with L the running sum of dt A inside the chunk and S the state at
the chunk's start (kept here as S^T [N, P], the heads of a step side by
side along the lanes):

    M_ij = (C_i . B_j) exp(L_i - L_j) dt_j for i >= j, 0 above
    Y    = M X + diag(exp L) C S + D X
    S'   = exp(L_C) S + (diag(exp(L_C - L) dt) X)^T B

Operands of the matmuls in `dtype` (bf16 under AMP, float32 accumulation;
float32 operands multiply at full precision, written in the body); M, the
weighted X and S rounded once, as `_mm` rounds them; dt, A, D, every decay,
every elementwise product and the carried state in float32. C B^T is one
product a step, shared by its heads; the reads and writes of the state are
one product each for all heads of the step.

The chunk is a static argument of both kernels, 128 or 256 tokens (whole
lane tiles; `usable`): at 256 the decays, C B^T and M are [256, 256]
float32, 256 KiB each, and a step's x block [256, 512]. Neither call states
a VMEM limit, so a step lives within Mosaic's default scoped 16 MiB.
Compiled for a v5e at 64 heads of 64, state 128, one row of 8192 (Mosaic's
own count, forward / backward, MiB): chunk 128 in 8 groups at 8 heads a step
under 5 / 5.62 in bf16 and under 5 / 10.18 in float32; chunk 256 in one
group at 8 heads 4.28 / 10.01 in bf16 and 7.09 / 16.62 in float32, which
is over, so float32 at 256 takes 4 heads a step (4.15 / 9.94; `_heads`).
A group whose heads take several grid steps (ONE group for 64 heads: eight
steps a chunk, sixteen of four) forms the same C B^T in each of them: it
is shared among a step's heads, not across steps.

Operands in the layout the model hands over: x [B, T, H P] and B, C
[B, T, G N] are cut into (chunk, heads P) and (chunk, N) blocks by the
index maps. What a head needs a token (dt and L) is 2 MB a layer: XLA sums
L and lays both out twice, tokens along the sublanes [.., T, heads] and
along the lanes [.., heads, T], so that exp(L_i - L_j) is a column minus a
row and nothing is transposed in VMEM. Heads narrower than a lane tile
(P = 64) are taken two a tile: a head's M multiplies the tile and a select
keeps its lanes, which costs the MXU nothing (a product of 64 columns
takes a pass of 128).

Backward (`ssd_scan_bwd`): ONE kernel over the chunks in reverse that
carries dS in a float32 scratch, computes the chunk's decays again
(transposed, so that M^T and dCB^T come without a transposition a head)
and pulls dy back to dx, dB, dC (summed over the step's heads), dD and, a
head a token, the cotangents of dt and L, which XLA sums back along the
chunk outside (2 MB again). What it reads of the forward is S at each
chunk's start, float32 [B, steps, Z, N, heads P]: the forward writes it
beside y wherever something reads it (`_forward_kept`), and the op's
backward launches no forward of its own.

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the flash kernels,
the grouped matmul, stage `gdn_intra` and the causal convolution it is
what the op lowers to on the TPU.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import custom_dce, pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta_intra import _dot, _iotas

__all__ = ['ssd_scan_fwd', 'ssd_scan_bwd', 'usable', 'HEADS']

# heads a grid step (tools/bench_ssd_scan.py --sweep; docs/perf.md has the
# rows): the largest divisor of a group's heads within it
HEADS = 8
# tokens a chunk: one or two lane tiles ([chunk, chunk] is one MXU pass or
# four); a static argument of both kernels, `usable` says which it takes
CHUNKS = (128, 256)
_LANES = 128

_F32 = jnp.float32


def usable(chunk, p, n, r, dtype):
    """A chunk of 128 or 256 (whole lane tiles of tokens, and what the
    scoped VMEM holds of [chunk, chunk] float32: the module docstring has
    the count), heads of 64 or 128, a group's `r` heads filling whole lane
    tiles, a state of whole lane tiles, bf16 or float32 operands."""
    return (chunk in CHUNKS and p in (64, 128) and (r * p) % _LANES == 0
            and n % _LANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _heads(r, p, chunk=128, itemsize=2):
    """Heads a grid step: the largest divisor of a group's `r` heads
    within HEADS that fills whole lane tiles; within half of HEADS where a
    chunk's column of operands passes 512 bytes (256 tokens of float32:
    the backward at 8 heads would ask 16.6 MiB of scoped VMEM)."""
    n = min(r, HEADS if chunk * itemsize <= 512 else max(HEADS // 2, 1))
    while r % n or (n * p) % _LANES:
        n -= 1
    return n


def _spread(cols, p):
    """[rows, heads] -> [rows, heads P]: a head's column over its P lanes
    (rows 1 or the chunk; a broadcast along the lanes and, where two heads
    share a lane tile, a select)."""
    rows, hs = cols.shape
    pack = max(_LANES // p, 1)
    lane = lax.broadcasted_iota(jnp.int32, (rows, pack * p), 1) // p
    tiles = []
    for first in range(0, hs, pack):
        tile = jnp.broadcast_to(cols[:, first:first + 1], lane.shape)
        for k in range(1, pack):
            tile = jnp.where(lane == k, cols[:, first + k:first + k + 1],
                             tile)
        tiles.append(tile)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _gather(full, p):
    """[rows, heads P] float32 -> [rows, 128]: lane h the sum over head
    h's P lanes (a product with a 0/1 matrix at full precision: the MXU
    adds what the lanes' unit would rotate)."""
    rows, width = full.shape
    lane, head = (lax.broadcasted_iota(jnp.int32, (width, _LANES), i)
                  for i in (0, 1))
    ones = (lane // p == head).astype(_F32)
    if rows == 1:       # a row alone is no matmul: eight of it
        return _dot(jnp.broadcast_to(full, (8, width)), ones, 'nn',
                    _F32)[0:1]
    return _dot(full, ones, 'nn', _F32)


def _tile_heads(hs, p):
    """(first lane of a lane tile, the heads in it) of a step's heads."""
    pack = max(_LANES // p, 1)
    return [(first * p, range(first, first + pack))
            for first in range(0, hs, pack)]


def _decay(diff):
    """exp(L_i - L_j) where i >= j: there the difference is no more than 0
    (A < 0, dt >= 0), so the clamp changes nothing; above the diagonal it
    keeps what is exponentiated finite, for a mask on C B^T (or on the sum
    over the heads) to take out with one select a step, not two a head."""
    return jnp.exp(jnp.minimum(diff, 0.0))


def _fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, y_ref, *rest,
                p, dtype):
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    chunk, width = x_ref.shape[1:]
    hs = width // p
    x, b, c = x_ref[0], b_ref[0], c_ref[0]
    dt_c, run_c = col_ref[0, 0, 0], col_ref[0, 0, 1]         # [chunk, hs]
    dt_r, run_r = row_ref[0, 0, 0], row_ref[0, 0, 1]         # [hs, chunk]
    xf = x.astype(_F32)
    row, col = _iotas(chunk)
    lower = row >= col
    s0 = s_ref[...]
    if len(rest) == 2:           # the backward's residual
        rest[0][0, 0, 0] = s0
    last = run_c[chunk - 1:chunk, :]                         # L_C [1, hs]
    # the state's part, all heads of the step at once
    read = _spread(jnp.exp(run_c), p)
    write = _spread(jnp.exp(last - run_c) * dt_c, p)
    keep = _spread(jnp.exp(last), p)
    y = _dot(c, s0, 'nn', dtype) * read + d_ref[...] * xf
    s_ref[...] = s0 * keep + _dot(b, xf * write, 'tn', dtype)
    # the chunk's own tokens, a head at a time
    cb = jnp.where(lower, _dot(c, b, 'nt', dtype), 0.0)
    tile_w = max(p, _LANES)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, tile_w), 1) // p
    for lo, heads in _tile_heads(hs, p):
        tile = x[:, lo:lo + tile_w]
        own = None
        for k, h in enumerate(heads):
            decay = _decay(run_c[:, h:h + 1] - run_r[h:h + 1, :])
            m = cb * decay * dt_r[h:h + 1, :]
            part = _dot(m, tile, 'nn', dtype)
            own = part if own is None else jnp.where(lane == k, part, own)
        y_ref[0, :, lo:lo + tile_w] = y[:, lo:lo + tile_w] + own


def _bwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcol_ref, drow_ref, dd_ref, ds_ref,
                *, p, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    chunk, width = x_ref.shape[1:]
    hs = width // p
    x, b, c = x_ref[0], b_ref[0], c_ref[0]
    dt_c, run_c = col_ref[0, 0, 0], col_ref[0, 0, 1]
    dt_r, run_r = row_ref[0, 0, 0], row_ref[0, 0, 1]
    xf, dy = x.astype(_F32), dy_ref[0]
    row, col = _iotas(chunk)
    upper = col >= row
    s0, ds1 = s_ref[0, 0, 0], ds_ref[...]
    last = run_c[chunk - 1:chunk, :]
    read_c, keep_c = jnp.exp(run_c), jnp.exp(last)
    tail_c = jnp.exp(last - run_c)
    write_c = tail_c * dt_c
    read, write, keep = (_spread(v, p) for v in (read_c, write_c, keep_c))
    # the state's part, all heads at once: Y += read (C S), S' = keep S +
    # B^T (write X)
    dyr = dy * read
    cs = _dot(c, s0, 'nn', dtype)
    bds = _dot(b, ds1, 'nn', dtype)
    dc = _dot(dyr, s0, 'nt', dtype)
    db = _dot(xf * write, ds1, 'nt', dtype)
    ds_ref[...] = ds1 * keep + _dot(c, dyr, 'tn', dtype)
    d_row = d_ref[...]
    dx = write * bds + d_row * dy
    dd_ref[0] += jnp.sum(dy * xf, axis=0, keepdims=True)
    # a head's scalars a token, lane h the head's: what the reads, the
    # writes and the chunk's decay hand back to dt and L
    d_read = _gather(dyr * cs, p)[:, :hs]             # d read x read
    d_write = _gather(xf * bds, p)[:, :hs]
    d_keep = _gather(jnp.sum(ds1 * s0, axis=0, keepdims=True), p)[:, :hs]
    # the chunk's own tokens, transposed: [j, i] for M_ij
    cbt = jnp.where(upper, _dot(b, c, 'nt', dtype), 0.0)
    dcbt = jnp.zeros((chunk, chunk), _F32)
    direct = jnp.zeros((chunk, hs), _F32)
    head_c = lax.broadcasted_iota(jnp.int32, (chunk, hs), 1)
    tile_w = max(p, _LANES)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, tile_w), 1) // p
    for lo, heads in _tile_heads(hs, p):
        tile, dy_tile = x[:, lo:lo + tile_w], dy[:, lo:lo + tile_w]
        own = None
        for k, h in enumerate(heads):
            dt_h = dt_c[:, h:h + 1]
            decay = _decay(run_r[h:h + 1, :] - run_c[:, h:h + 1])
            scores = cbt * decay                       # M^T before dt_j
            dy_h = dy_tile if len(heads) == 1 else jnp.where(
                lane == k, dy_tile, 0.0)
            dmt = _dot(tile, dy_h, 'nt', dtype)        # dM^T
            part = _dot(scores * dt_h, dy_tile, 'nn', dtype)
            own = part if own is None else jnp.where(lane == k, part, own)
            pulled = dmt * scores
            to_dt = jnp.sum(pulled, axis=1, keepdims=True)   # over i
            direct = jnp.where(head_c == h, to_dt, direct)
            # L_i of exp(L_i - L_j): the sums over j, tokens along lanes
            drow_ref[0, 0, h:h + 1, :] = jnp.sum(pulled * dt_h, axis=0,
                                                 keepdims=True)
            dcbt = dcbt + dmt * (decay * dt_h)
        dx_ref[0, :, lo:lo + tile_w] = (
            dx[:, lo:lo + tile_w] + own).astype(dx_ref.dtype)
    dcbt = jnp.where(upper, dcbt, 0.0)
    db_ref[0] = (db + _dot(dcbt, c, 'nn', dtype)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dcbt, b, 'tn', dtype)).astype(dc_ref.dtype)
    # dt_j of M and of the write; L_j of both and L_i of the read; L_C of
    # the write and of the chunk's decay at the chunk's last token
    d_last = jnp.sum(d_write * write_c, axis=0, keepdims=True) \
        + d_keep * keep_c
    at_last = lax.broadcasted_iota(jnp.int32, (chunk, hs), 0) == chunk - 1
    dcol_ref[0, 0, 0] = direct + d_write * tail_c
    dcol_ref[0, 0, 1] = d_read - d_write * write_c \
        - direct * dt_c + jnp.where(at_last, d_last, 0.0)


def _small(dt, a, hs, chunk):
    """What a head needs a token, both ways up: (dt, L) as
    [B, steps, 2, T, hs] and [B, steps, 2, hs, T], L the running sum of
    dt A inside each chunk. dt [B, T, H] float32, T whole chunks."""
    bsz, t, h = dt.shape
    run = jnp.cumsum((dt * a).reshape(bsz, -1, chunk, h), axis=2)
    both = jnp.stack([dt, run.reshape(dt.shape)], axis=1)
    both = both.reshape(bsz, 2, t, h // hs, hs)
    return both.transpose(0, 3, 1, 2, 4), both.transpose(0, 3, 1, 4, 2)


def _dims(x, b, p, groups, hs, chunk):
    """(B, T, H, N, grid steps a group, grid steps a row, chunks, lanes a
    step) of x [B, T, H P] and b [B, T, G N] at `hs` heads a step."""
    bsz, t, width = x.shape
    h = width // p
    return (bsz, t, h, b.shape[2] // groups, h // groups // hs, h // hs,
            t // chunk, hs * p)


@functools.partial(jax.jit, static_argnames=('p', 'groups', 'hs', 'chunk',
                                             'save', 'interpret'))
def _forward(x, dt, a, b, c, d, *, p, groups, hs, chunk, save, interpret):
    bsz, t, h, n, per, steps, z, width = _dims(x, b, p, groups, hs, chunk)
    cols, rows = _small(dt, a, hs, chunk)
    like = jax.ShapeDtypeStruct
    tokens = pl.BlockSpec((1, chunk, width), lambda i, j, k: (i, k, j))
    outs, out_specs = [like(x.shape, _F32)], [tokens]
    if save:
        outs.append(like((bsz, steps, z, n, width), _F32))
        out_specs.append(pl.BlockSpec((1, 1, 1, n, width),
                                      lambda i, j, k: (i, j, k, 0, 0)))
    group = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, j // per))
    got = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, dtype=x.dtype),
        grid=(bsz, steps, z),
        in_specs=[
            tokens, group, group,
            pl.BlockSpec((1, 1, 2, chunk, hs),
                         lambda i, j, k: (i, j, 0, k, 0)),
            pl.BlockSpec((1, 1, 2, hs, chunk),
                         lambda i, j, k: (i, j, 0, 0, k)),
            pl.BlockSpec((1, width), lambda i, j, k: (0, j))],
        out_specs=out_specs, out_shape=outs,
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))(
        x, b, c, cols, rows, d)
    return tuple(got)


@functools.partial(custom_dce.custom_dce, static_argnums=(6, 7, 8, 9, 10))
def _forward_kept(x, dt, a, b, c, d, p, groups, hs, chunk, interpret):
    """(y, S at each chunk's start). Where nothing reads the starts (a
    forward that no backward follows; a recompute region's first pass,
    whose backward runs the forward again), the call that is left is the
    forward that does not write them."""
    return _forward(x, dt, a, b, c, d, p=p, groups=groups, hs=hs,
                    chunk=chunk, save=True, interpret=interpret)


@_forward_kept.def_dce
def _forward_used(p, groups, hs, chunk, interpret, used, x, dt, a, b, c, d):
    got = _forward(x, dt, a, b, c, d, p=p, groups=groups, hs=hs,
                   chunk=chunk, save=used[1], interpret=interpret)
    return (got[0] if used[0] else None, got[1] if used[1] else None)


@functools.partial(jax.jit, static_argnames=('p', 'groups', 'hs', 'chunk',
                                             'interpret'))
def _backward(x, dt, a, b, c, d, starts, dy, *, p, groups, hs, chunk,
              interpret):
    bsz, t, h, n, per, steps, z, width = _dims(x, b, p, groups, hs, chunk)
    cols, rows = _small(dt, a, hs, chunk)
    like = jax.ShapeDtypeStruct
    # a group's dB and dC: the step's own where it takes the whole group,
    # else float32 parts a step, summed below
    part = x.dtype if per == 1 else _F32
    tokens = pl.BlockSpec((1, chunk, width),
                          lambda i, j, k: (i, z - 1 - k, j))
    group = pl.BlockSpec((1, chunk, n),
                         lambda i, j, k: (i, z - 1 - k, j // per))
    parts = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, z - 1 - k, j))
    col = pl.BlockSpec((1, 1, 2, chunk, hs),
                       lambda i, j, k: (i, j, 0, z - 1 - k, 0))
    dx, db, dc, dcols, drows, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, dtype=x.dtype),
        grid=(bsz, steps, z),
        in_specs=[
            tokens, group, group, col,
            pl.BlockSpec((1, 1, 2, hs, chunk),
                         lambda i, j, k: (i, j, 0, 0, z - 1 - k)),
            pl.BlockSpec((1, width), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1, 1, n, width),
                         lambda i, j, k: (i, j, z - 1 - k, 0, 0)),
            tokens],
        out_specs=[
            tokens, parts, parts, col,
            pl.BlockSpec((1, 1, hs, chunk),
                         lambda i, j, k: (i, j, 0, z - 1 - k)),
            pl.BlockSpec((1, 1, width), lambda i, j, k: (i, 0, j))],
        out_shape=[
            like(x.shape, x.dtype), like((bsz, t, steps * n), part),
            like((bsz, t, steps * n), part),
            like((bsz, steps, 2, t, hs), _F32),
            like((bsz, steps, hs, t), _F32), like((bsz, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))(
        x, b, c, cols, rows, d, starts, dy)
    if per > 1:
        db, dc = (v.reshape(bsz, t, groups, per, n).sum(3).reshape(b.shape)
                  .astype(b.dtype) for v in (db, dc))
    # [B, steps, T, hs] and [B, steps, hs, T] -> [B, T, H]
    dcols = dcols.transpose(0, 2, 3, 1, 4).reshape(bsz, 2, t, h)
    dl = dcols[:, 1] + drows.transpose(0, 3, 1, 2).reshape(dt.shape)
    # L is the running sum of dt A inside a chunk: its cotangent runs back
    dl = dl.reshape(bsz, z, chunk, h)
    back = jnp.cumsum(dl[:, :, ::-1], axis=2)[:, :, ::-1].reshape(dt.shape)
    return (dx, dcols[:, 0] + back * a, jnp.sum(back * dt, axis=(0, 1)),
            db, dc, dd.reshape(bsz, h, p).sum((0, 2)))


def _flat(x, dt, b, c, d):
    """The op's arguments as the kernels take them: heads and groups side
    by side along the last axis, the skip a lane (zeros without one)."""
    bsz, t, h, p = x.shape
    skip = jnp.zeros((h,), _F32) if d is None else d.astype(_F32)
    return (x.reshape(bsz, t, h * p), dt.astype(_F32),
            b.reshape(bsz, t, -1), c.reshape(bsz, t, -1),
            jnp.repeat(skip, p)[None])


def ssd_scan_fwd(x, dt, a, b, c, d, *, chunk, interpret):
    """x [B, T, H, P], b, c [B, T, G, N] in the matmuls' dtype, dt
    [B, T, H], a [H], d [H] or None, T whole chunks of `chunk`. Returns
    (y [B, T, H, P] float32 with the skip, S at each chunk's start for
    `ssd_scan_bwd`); a caller that drops the starts pays nothing for them
    once jitted (`_forward_kept`)."""
    p, groups = x.shape[3], b.shape[2]
    x2, dt, b2, c2, skip = _flat(x, dt, b, c, d)
    y, starts = _forward_kept(x2, dt, a.astype(_F32), b2, c2, skip, p,
                              groups, _heads(x.shape[2] // groups, p, chunk,
                                             x.dtype.itemsize), chunk,
                              interpret)
    return y.reshape(x.shape), starts


def ssd_scan_bwd(x, dt, a, b, c, d, starts, dy, *, chunk, interpret):
    """The cotangents of (x, dt, a, b, c, d) for dy [B, T, H, P] float32;
    `starts` is what `ssd_scan_fwd` kept (its shape says how many heads a
    grid step took)."""
    p, groups = x.shape[3], b.shape[2]
    flat = _flat(x, dt, b, c, d)
    dx, ddt, da, db, dc, dd = _backward(
        flat[0], flat[1], a.astype(_F32), *flat[2:], starts,
        dy.astype(_F32).reshape(flat[0].shape), p=p, groups=groups,
        hs=starts.shape[4] // p, chunk=chunk, interpret=interpret)
    return (dx.reshape(x.shape), ddt, da.astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            None if d is None else dd.astype(d.dtype))
