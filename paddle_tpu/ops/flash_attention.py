"""Pallas TPU flash attention (forward + backward kernels).

TPU-first replacement for the reference's attention chain
(benchmark/fluid/models/machine_translation.py + nets.py
scaled_dot_product_attention: QK^T -> softmax -> PV as separate ops, which
materializes the [B,H,Tq,Tk] score matrix in HBM). FlashAttention-2 style:
K/V are tiled through the innermost grid dimension, so VMEM only ever holds
[block_q, D] + [block_k, D] tiles plus the online-softmax state — sequence
length is bounded by HBM, not VMEM. The forward keeps a running
(max, sum, acc) in VMEM scratch across the k-grid; the backward recomputes
probabilities from the saved logsumexp. HBM traffic drops from O(T^2) to
O(T*D).

The backward is one algorithm with three schedules. Where a head's whole
score matrix is one tile (every call at T <= 1024 with rows of up to 512
bytes, causal or not) nothing has to be accumulated across grid steps, and
ONE kernel on the grid (B, H) gives dq, dk and dv from one s, p, dp and ds
(_bwd_fused_kernel, 'tile'). A causal self-attention head of more tiles,
on the triangular grid or its band, still takes one pass (_bwd_head_kernel,
'head') where VMEM holds the head's float32 dq beside a grid step's blocks
(_head_vmem_limit against _HEAD_VMEM_LIMIT_BYTES: 16384 x 128 and
8192 x 256 are 8 MiB of dq; bf16 heads of D = 128 fit up to 32768
positions): dk and dv accumulate over a k-block's q-blocks, dq into the
head's accumulator, each pair's s, p, dp and ds computed once. Everything
else (the rectangular grid: not causal, Tq != Tk, oblong tiles; a head
too long or too wide for that budget) takes two kernels, dq over a
q-row's k-blocks and dk/dv over a k-column's q-blocks, each recomputing
s, p, dp and ds for itself: 7 dots and two exp a pair where one pass
spends 5 and one. _prep decides from the shapes and counts
`flash.backward{passes=one, span=tile|head}` or `{passes=two}`.

Supports an additive per-key bias [B, Tk] (padding mask; treated as a
constant — stop_gradient'd by the op lowering) and causal masking —
together these cover every mask the Transformer model builds
(models/transformer.py _pad_mask_bias). Arbitrary [B,H,Tq,Tk] biases fall
back to the XLA path in the op lowering (ops_impl/nn_ops.py).

Causal self-attention (Tq == Tk, square blocks) runs on a LINEARIZED
LOWER-TRIANGLE grid: scalar-prefetch index arrays enumerate only the
(q-block, k-block) pairs on or below the diagonal, so blocks above it are
never computed — causal forward+backward costs ~half the rectangular
FLOPs. See the strategy note above _tri_maps for why this (and not
compute predication) is the safe way to skip blocks under Mosaic. A
sliding `window` (causal only: query i sees keys i - window + 1 .. i)
cuts the same enumeration on its other side: the forward and the
backward's grids (one pass over a head, or dq and dk/dv) list only the
BAND of blocks a window touches, and the mask gets its second edge. Where
the triangular grid does not apply (or the backward is one tile) a window
is the mask alone.

What is masked where. On the rectangular grid and in the one-tile backward
every pair of a causal call COMPUTES its mask (_mask_causal: two iotas, a
subtraction, one or two compares, a select on the score tile). On the
triangular grid and its band no pair does: because the tiles are square,
every pair on one block diagonal d = i - j has the same mask, and only the
diagonal itself and the one or two diagonals a window's far edge crosses
hold a position the mask removes (_mask_diffs; 32 of the 528 pairs of
16384 positions in 512-blocks, 56 of the 252 under a window of 4096). So
the masks are two to four additive float32 tiles, 0 where a key is seen
and NEG_BIG where not, filled into VMEM scratch once a head
(_fill_mask_tiles), and every pair adds the tile of its KIND, tile 0 being
zeros: s = dot * scale + tile[kind]. The kind is scalar arithmetic on the
prefetched block coordinates and picks an operand; every pair runs the
same instructions, so nothing is predicated (the strategy note above
_tri_maps). An interior pair's scores are what a computed mask left to
the bit; a masked position reads s + NEG_BIG and not NEG_BIG, and exp of
either minus any live row's maximum is exactly 0 in float32, so p, l, the
accumulators and every gradient are unchanged. The staircase grid admits
or leaves out whole blocks and masks nothing. A key bias is an operand
only where there is one to add: a call on the triangle or the band
without a `key_bias` and without padded keys hands in none and its bodies
add none, and the staircase never has one. `flash.tiles_masked{grid=}`
counts, beside `flash.tiles{grid=}`, the pairs that still pay for a mask.

What is float32 and what follows the input. The q, k, v and do tiles go
into the MXU in the dtype of their refs (bf16 under AMP, float32 in a
Program without it), and p and ds are cast to that dtype only as operands
of their dots; every dot accumulates in float32. Float32 whatever comes
in: the scores s, the bias add, the causal mask, exp, the running max m
and sum l, the accumulators (acc, dq, dk, dv scratch), lse and delta.
Outputs and gradients leave in the input's dtype. So float32 in computes
what it always did, and bf16 in rounds p and ds once more than the
float32 reference does, as every matmul under AMP rounds its operands
(tests/test_flash_attention.py has the arithmetic of the tolerance).
Row statistics never become 1-D vectors inside a body: they stay
lane-broadcast [rows, LANES] tiles from scratch or HBM to the score tile
(_lanes). On the v5e that, not the operand dtype, was what a forward
block step waited for (PERF.md, PR 24). The counters
`flash.lowered{operands=<dtype>, grid=band|triangle|rect}` and
`flash.backward{passes=one, span=tile|head}` / `{passes=two}` count
attention calls per lowering; `flash.tiles{grid=}` adds up the (q-block,
k-block) pairs a head that a call's grids visit (forward + dq + dk/dv;
forward + the one pass under 'head') and `flash.tiles_masked{grid=}` those
of them that add a mask tile which is not zeros (triangle, band) or
compute a mask (every pair of a causal call elsewhere), so a lowering says
off the chip whether the band was taken, what it spared, which backward
ran, and what share of the pairs still pays for a mask.

`interpret` is the CALLER's decision, never read off the process's
default backend: the op lowering passes interpret=False on a TPU place
(and takes the XLA reference chain elsewhere), tests pass interpret=True
to run the kernel bodies under the pallas interpreter at tiny shapes. A
TPU process whose backend quietly fell back to the host therefore cannot
interpret a kernel without somebody having said so.

Degenerate rows whose every key is masked (key_bias=-1e9 on all causally
visible positions) produce garbage outputs/grads in BOTH this kernel and
the XLA oracle — the -1e9 offsets cancel in exp(s - lse), amplifying
rounding noise. Real pad masks never do this (the first key of a sequence
is live); such rows are pad queries whose loss contribution is masked.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs

NEG_BIG = -1e9   # finite mask value: keeps fully-masked rows NaN-free
LANES = 128      # stats scratch is lane-broadcast to keep stores tiled


def _round_up(x, m):
    return (x + m - 1) // m * m


# The nine dots of a forward and backward, by their contracting dimensions:
# a @ b and a @ b^T. Operands go in as they are (the tiles in their refs'
# dtype, p and ds cast to it by the caller); the result is float32.
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _mask_causal(s, q0, k0, q_axis, window=None):
    """NEG_BIG where a key lies after its query or, under a `window`, that
    many positions or more before it (a query sees its own position and
    the window - 1 before it), for a score tile whose first query is q0
    and first key k0, queries running along q_axis. The positions'
    difference within the tile does not depend on the grid step; only the
    scalars it is compared with do."""
    rel = (lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
           - lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    keep = rel >= k0 - q0
    if window is not None:
        keep = keep & (rel < k0 - q0 + window)
    return jnp.where(keep, s, NEG_BIG)


def _lanes(x, n):
    """A lane-broadcast [rows, LANES] statistic (every lane of a row holds
    the row's value) as [rows, n]: the same registers n / LANES times
    over, so a row statistic meets a [rows, n] tile without ever becoming
    a 1-D vector and being laid out again."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return pltpu.repeat(x, n // LANES, axis=1)


# ---------------------------------------------------------------------------
# grid shapes. Two causal strategies:
#   rectangular  — grid (B, H, nq, nk), every block computed, upper-triangle
#                  blocks masked to NEG_BIG. Predicating the COMPUTE on the
#                  grid position is NOT safe: it desynchronizes Mosaic's
#                  block pipelining when a revisited input block's index map
#                  depends on an outer grid dim (observed: batch>1 +
#                  key-bias blocks read stale data).
#   triangular   — grid (B, H, n_tri) where n_tri enumerates ONLY the
#                  lower-triangle (q-block, k-block) pairs; the (i, j)
#                  coordinates come from scalar-prefetch index arrays
#                  (pltpu.PrefetchScalarGridSpec). Upper blocks are never in
#                  the grid, so causal pays ~half the FLOPs, and every block
#                  is visited exactly once — no predication, so the Mosaic
#                  hazard above never arises. Used when Tq == Tk and
#                  bq == bk (decoder self-attention); anything else falls
#                  back to rectangular.
#   band           — the triangular grid cut on its other side too: under a
#                  sliding `window` a q-block i sees the k-blocks
#                  i - nb .. i, nb = ceil((window - 1) / bk) (_band), and
#                  the same index arrays list only those pairs. Nothing
#                  else changes: still one visit a pair, still no
#                  predication; the accumulators start at a row's (a
#                  column's) first pair IN THE BAND and end at its last.
#                  16384 positions in 512-blocks under a window of 4096:
#                  252 of the triangle's 528 pairs a head. A windowed call
#                  outside _use_tri's conditions takes the rectangular
#                  grid with the same mask and skips nothing.
#   the mask       — on the rectangular grid every causal pair computes it
#                  (_mask_causal). On the triangle and the band only the
#                  pairs ON the diagonal and on the diagonals a window's
#                  far edge crosses hold a position it removes
#                  (_mask_diffs), and all pairs of one diagonal share one
#                  mask; so the kernels hold the masks as additive tiles
#                  in VMEM scratch (_fill_mask_tiles, once a head) and
#                  EVERY pair adds the tile of its kind (_pair_kind), zeros
#                  for the interior. A conditional round the mask, taken
#                  by those pairs only, cost a quarter (docs/perf.md, PR
#                  42): choosing an operand costs nothing of the kind,
#                  because all pairs still run one instruction stream.
#                  `flash.tiles_masked{grid=}` counts the pairs whose tile
#                  is not zeros.
# ---------------------------------------------------------------------------


def _band(window, bk, n):
    """How many k-blocks BELOW the diagonal a q-block still sees under
    `window` (a query sees the keys i - window + 1 .. i): None where that
    is every one of the n - 1 there are, the plain triangle."""
    if window is None:
        return None
    nb = -(-(window - 1) // bk)
    return None if nb >= n - 1 else nb


def _tile_pairs(n, nb=None):
    """(q-block, k-block) pairs of the triangle of n blocks, or of its
    band of nb blocks below the diagonal."""
    if nb is None:
        return n * (n + 1) // 2
    return (nb + 1) * (nb + 2) // 2 + (n - nb - 1) * (nb + 1)


def _tri_maps(n, nb=None):
    """Row-major lower-triangle enumeration: (0,0),(1,0),(1,1),(2,0),...
    Returns int32 (i_map, j_map) with j <= i, length n*(n+1)//2. With
    `nb`, only the pairs of the band i - nb <= j <= i, in the same
    order."""
    import numpy as np
    rows = np.arange(n)
    first = np.zeros(n, int) if nb is None else np.maximum(rows - nb, 0)
    i = np.repeat(rows, rows - first + 1)
    j = np.concatenate([np.arange(f, r + 1) for r, f in zip(rows, first)])
    return i.astype(np.int32), j.astype(np.int32)


def _tri_maps_kv(n, nb=None):
    """Lower-triangle enumeration ordered for the dk/dv kernel: k-block j
    outer (visited last-to-first), its contributing q-blocks i = j..n-1
    (with `nb`: j..min(n - 1, j + nb)) inner, so the (dk, dv) accumulator
    runs over consecutive steps."""
    import numpy as np
    ii, jj = [], []
    for j in range(n - 1, -1, -1):
        last = n - 1 if nb is None else min(n - 1, j + nb)
        ii.append(np.arange(j, last + 1))
        jj.append(np.full(last + 1 - j, j))
    return (np.concatenate(ii).astype(np.int32),
            np.concatenate(jj).astype(np.int32))


def _mask_diffs(window, bk, n):
    """The block diagonals d = i - j of the triangle of n blocks (or its
    band) whose pairs hold a position the mask removes: the diagonal
    itself (keys after their query, and under a window shorter than a
    block the keys too far back as well) and, under a window, the one or
    two diagonals its far edge crosses: floor(window / bk) up to
    ceil((window - 1) / bk), the band's lower edge. They are the same
    one where the window is a whole number of blocks or one key more
    (4096 under 512-blocks: 0 and 8), two where it ends inside a block.
    Every pair of one diagonal has the SAME mask, because the tiles are
    square, and every other pair of the grid has none."""
    if window is None:
        return (0,)
    return (0,) + tuple(range(max(1, window // bk),
                              min(-(-(window - 1) // bk), n - 1) + 1))


def _masked_pairs(n, diffs):
    """How many pairs of the triangle of n blocks, or of its band, lie on
    the masked diagonals `diffs`: n - d on diagonal d."""
    return sum(n - d for d in diffs)


def _pair_kind(d, diffs):
    """Which mask tile a pair on block diagonal d = i - j takes: 1 + its
    place in `diffs`, or 0, the tile of zeros, for a pair that holds
    nothing to mask. Scalar arithmetic on the pair's prefetched
    coordinates (or numpy's, in the tests); it chooses an OPERAND of the
    body's one add, never an instruction."""
    kind = 0
    for n, masked in enumerate(diffs):
        kind = jnp.where(d == masked, n + 1, kind)
    return kind


def _mask_tile(tiles, i, j):
    """The tile pair (i, j) adds to its scores, of `tiles` = (the scratch
    _fill_mask_tiles filled, its diagonals)."""
    tiles_s, diffs = tiles
    return tiles_s[_pair_kind(i - j, diffs)]


def _fill_mask_tiles(tiles_s, t, diffs, block, q_axis, window):
    """At a head's first pair, the mask of every kind of pair as an
    additive float32 tile in VMEM scratch: tile 0 zeros, tile 1 + n
    _mask_causal's own arithmetic for a pair on diagonal diffs[n] (0
    where a position is seen, NEG_BIG where not), queries along q_axis.
    Two to four tiles serve the whole call; no pair computes a mask."""
    @pl.when(t == 0)
    def _fill():
        zeros = jnp.zeros(tiles_s.shape[1:], tiles_s.dtype)
        tiles_s[0] = zeros
        for n, d in enumerate(diffs):
            tiles_s[n + 1] = _mask_causal(zeros, d * block, 0, q_axis,
                                          window)


def _stair_maps(nq, qpw, spw):
    """Row-major enumeration of a STAIRCASE: q-block i, of window i // qpw
    (qpw q-blocks a window), sees the summary blocks 0 .. (i // qpw + 1) *
    spw - 1 (spw summary blocks a window): whole blocks admitted or left
    out, so no tile is masked and none above the stairs is in the grid.
    The queries handed in start at the row's SECOND window and the
    summaries end before its last (flash_attention_summary), so every
    q-block and every summary block has a pair."""
    import numpy as np
    seen = (np.arange(nq) // qpw + 1) * spw
    i = np.repeat(np.arange(nq), seen)
    j = np.concatenate([np.arange(n) for n in seen])
    return i.astype(np.int32), j.astype(np.int32)


def _stair_maps_kv(nq, qpw, spw):
    """The same pairs ordered for the dk/dv kernel: summary block j outer,
    the q-blocks that see it, (j // spw) * qpw .. nq - 1, inner."""
    import numpy as np
    first = (np.arange(nq // qpw * spw) // spw) * qpw
    i = np.concatenate([np.arange(f, nq) for f in first])
    j = np.repeat(np.arange(len(first)), nq - first)
    return i.astype(np.int32), j.astype(np.int32)


# ---------------------------------------------------------------------------
# forward kernel body + rectangular/triangular wrappers
# ---------------------------------------------------------------------------

def _fwd_body(q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref,
              m_s, l_s, acc_s, i, j, is_first, is_last, *,
              scale, causal, block_q, block_k, window=None, tiles=None):
    """One pair of the forward. `tiles` (the triangular grid and its
    band): the mask is the tile of the pair's kind, added; without them a
    causal pair computes its mask. kb_ref None: no key bias to add."""
    @pl.when(is_first)
    def _init():
        m_s[:] = jnp.full_like(m_s, -1e30)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _compute():
        q = q_ref[0, 0]                                        # [bq, D]
        kb = k_ref[0, 0]                                       # [bk, D]
        vb = v_ref[0, 0]
        s = _dot(q, kb, _NT) * scale
        if kb_ref is not None:
            s = s + kb_ref[0]
        if tiles is not None:
            s = s + _mask_tile(tiles, i, j)
        elif causal:
            s = _mask_causal(s, i * block_q, j * block_k, 0, window)
        m_prev = m_s[:]                                        # [bq, LANES]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = l_s[:] * alpha + p.sum(axis=-1, keepdims=True)
        m_s[:] = m_new
        acc_s[:] = acc_s[:] * _lanes(alpha, acc_s.shape[1]) + _dot(
            p.astype(vb.dtype), vb, _NN)

    _compute()

    @pl.when(is_last)
    def _finish():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0, 0] = (acc_s[:] / _lanes(l, acc_s.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0, 0] = m_s[:] + jnp.log(l)


def _row_start(i, j, nb):
    """Is k-block j the first that q-block i visits (triangle: block 0;
    band of nb: block i - nb, or 0 in the first rows)?"""
    return j == 0 if nb is None else j == jnp.maximum(i - nb, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, scale, causal, block_q, block_k,
                window=None):
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    _fwd_body(q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref, m_s, l_s, acc_s,
              i, j, j == 0, j == nk - 1,
              scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              window=window)


def _fwd_kernel_tri(im_ref, jm_ref, q_ref, k_ref, v_ref, kb_ref,
                    o_ref, lse_ref, m_s, l_s, acc_s, tiles_s, *,
                    scale, block_q, block_k, diffs, window=None, nb=None):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    _fill_mask_tiles(tiles_s, t, diffs, block_q, 0, window)
    # j == 0 (in a band: the row's first block in it) starts row i;
    # j == i is the diagonal block, last for row i
    _fwd_body(q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref, m_s, l_s, acc_s,
              i, j, _row_start(i, j, nb), j == i,
              scale=scale, causal=True, block_q=block_q, block_k=block_k,
              tiles=(tiles_s, diffs))


def _stair_row_end(i, qpw, spw):
    """The last summary block q-block i visits: the last of the window
    before its own (the queries handed in start at the second window)."""
    return (i // qpw + 1) * spw - 1


def _fwd_kernel_stair(im_ref, jm_ref, q_ref, k_ref, v_ref,
                      o_ref, lse_ref, m_s, l_s, acc_s, *,
                      scale, block_q, block_k, qpw, spw):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    # a q-block's row runs from summary block 0; nothing inside a tile is
    # masked and no summary has a bias
    _fwd_body(q_ref, k_ref, v_ref, None, o_ref, lse_ref, m_s, l_s, acc_s,
              i, j, j == 0, j == _stair_row_end(i, qpw, spw),
              scale=scale, causal=False, block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dq_s, i, j, is_first, is_last, *,
                 scale, causal, block_q, block_k, window=None, tiles=None):
    @pl.when(is_first)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    def _compute():
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = _lanes(lse_ref[0, 0], block_k)                   # [bq, bk]
        delta = _lanes(delta_ref[0, 0], block_k)
        s = _dot(q, kb, _NT) * scale
        if kb_ref is not None:
            s = s + kb_ref[0]
        if tiles is not None:
            s = s + _mask_tile(tiles, i, j)
        elif causal:
            s = _mask_causal(s, i * block_q, j * block_k, 0, window)
        p = jnp.exp(s - lse)
        dp = _dot(do, vb, _NT)
        ds = p * (dp - delta) * scale
        dq_s[:] = dq_s[:] + _dot(ds.astype(kb.dtype), kb, _NN)

    _compute()

    @pl.when(is_last)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_s, *, scale, causal, block_q, block_k,
                   window=None):
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    _bwd_dq_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dq_s, i, j, j == 0, j == nk - 1,
                 scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                 window=window)


def _bwd_dq_kernel_tri(im_ref, jm_ref, q_ref, k_ref, v_ref, kb_ref, do_ref,
                       lse_ref, delta_ref, dq_ref, dq_s, tiles_s, *,
                       scale, block_q, block_k, diffs, window=None, nb=None):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    _fill_mask_tiles(tiles_s, t, diffs, block_q, 0, window)
    _bwd_dq_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dq_s, i, j, _row_start(i, j, nb), j == i,
                 scale=scale, causal=True, block_q=block_q, block_k=block_k,
                 tiles=(tiles_s, diffs))


def _bwd_dkv_pair(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                  dk_s, dv_s, i, j, *, scale, causal, block_q, block_k,
                  window=None, tiles=None):
    """One (q-block i, k-block j) pair's s^T, p^T, dp^T and ds^T, added
    into the dk and dv accumulators. Returns the k tile and ds^T as a
    dot's operand, which is all that dq needs besides. `tiles` and a
    kb_ref of None as in _fwd_body, the tiles transposed as the scores
    are."""
    # the scores TRANSPOSED, [bk, bq]: both accumulators then take
    # their p^T and ds^T as computed, and no [bq, bk] tile is turned
    # round. What it costs is the three small vectors below.
    k = k_ref[0, 0]                                            # [bk, D]
    v = v_ref[0, 0]
    qb = q_ref[0, 0]                                           # [bq, D]
    dob = do_ref[0, 0]
    lse_b = lse_ref[0, 0].T[:1]                                # [1, bq]
    delta_b = delta_ref[0, 0].T[:1]
    st = _dot(k, qb, _NT) * scale                              # [bk, bq]
    if kb_ref is not None:
        st = st + jnp.broadcast_to(kb_ref[0], (LANES, block_k)).T[:, :1]
    if tiles is not None:
        st = st + _mask_tile(tiles, i, j)
    elif causal:
        st = _mask_causal(st, i * block_q, j * block_k, 1, window)
    pt = jnp.exp(st - lse_b)
    dv_s[:] = dv_s[:] + _dot(pt.astype(dob.dtype), dob, _NN)
    dpt = _dot(v, dob, _NT)
    dsc = (pt * (dpt - delta_b) * scale).astype(qb.dtype)
    dk_s[:] = dk_s[:] + _dot(dsc, qb, _NN)
    return k, dsc


def _bwd_dkv_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                  dk_ref, dv_ref, dk_s, dv_s, i, j, is_first, is_last, *,
                  scale, causal, block_q, block_k, window=None, tiles=None):
    @pl.when(is_first)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    _bwd_dkv_pair(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                  dk_s, dv_s, i, j, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, window=window,
                  tiles=tiles)

    @pl.when(is_last)
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, scale, causal, block_q,
                    block_k, window=None):
    j, i = pl.program_id(2), pl.program_id(3)   # k block outer, q block inner
    nq = pl.num_programs(3)
    _bwd_dkv_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                  dk_ref, dv_ref, dk_s, dv_s, i, j, i == 0, i == nq - 1,
                  scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, window=window)


def _bwd_dkv_kernel_tri(im_ref, jm_ref, q_ref, k_ref, v_ref, kb_ref, do_ref,
                        lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s,
                        tiles_s, *, scale, block_q, block_k, nq, diffs,
                        window=None, nb=None):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    _fill_mask_tiles(tiles_s, t, diffs, block_q, 1, window)
    # contributing q-blocks for k-block j run i = j..nq-1 (tri_maps_kv
    # order): the accumulator starts at the diagonal and ends at the last
    # q-block, in a band of nb at the last q-block that still sees j
    last = nq - 1 if nb is None else jnp.minimum(j + nb, nq - 1)
    _bwd_dkv_body(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                  dk_ref, dv_ref, dk_s, dv_s, i, j, i == j, i == last,
                  scale=scale, causal=True,
                  block_q=block_q, block_k=block_k, tiles=(tiles_s, diffs))


def _bwd_dq_kernel_stair(im_ref, jm_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dq_s, *,
                         scale, block_q, block_k, qpw, spw):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    _bwd_dq_body(q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref,
                 dq_ref, dq_s, i, j, j == 0, j == _stair_row_end(i, qpw, spw),
                 scale=scale, causal=False, block_q=block_q, block_k=block_k)


def _bwd_dkv_kernel_stair(im_ref, jm_ref, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                          dk_s, dv_s, *, scale, block_q, block_k, nq, qpw,
                          spw):
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    # summary block j's q-blocks run from the first of the window after
    # its own to the row's last (_stair_maps_kv order)
    _bwd_dkv_body(q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref,
                  dk_ref, dv_ref, dk_s, dv_s, i, j, i == (j // spw) * qpw,
                  i == nq - 1, scale=scale, causal=False,
                  block_q=block_q, block_k=block_k)


def _bwd_head_kernel(im_ref, jm_ref, q_ref, k_ref, v_ref, kb_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                     dq_s, dk_s, dv_s, tiles_s, *, scale, block_q, block_k,
                     nq, diffs, window=None, nb=None):
    """The whole backward of a causal head of MORE than one tile in one
    pass over its triangle or band, the pairs in _tri_maps_kv order: a
    pair's s^T, p^T, dp^T and ds^T are computed once (_bwd_dkv_pair) and
    feed all three gradients, 5 dots and one exp where the two kernels
    spend 7 and two, and q, k, v, do, lse and delta are read once a pair.
    dk and dv accumulate over a k-block's consecutive steps as in
    _bwd_dkv_kernel_tri. dq cannot: a q-block's pairs lie a k-block's
    whole run apart, so the HEAD's dq stays in VMEM, float32
    [nq, bq, D], and pair (i, j) adds into block i, the dot taken as
    _bwd_fused_kernel takes it, (k^T ds^T)^T. k-blocks run last to
    first, so q-block i meets its diagonal pair first (its block is
    zeroed there, with k-block i's dk and dv) and its row's first
    k-block last: there block i leaves for the head's dq output block,
    which stays in VMEM until the head's last pair. Only those inits and
    stores are predicated on the grid position, never the compute (the
    strategy note above _tri_maps)."""
    t = pl.program_id(2)
    i, j = im_ref[t], jm_ref[t]
    last = nq - 1 if nb is None else jnp.minimum(j + nb, nq - 1)
    _fill_mask_tiles(tiles_s, t, diffs, block_q, 1, window)

    @pl.when(i == j)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)
        dq_s[i] = jnp.zeros(dq_s.shape[1:], dq_s.dtype)

    k, dsc = _bwd_dkv_pair(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref,
                           delta_ref, dk_s, dv_s, i, j, scale=scale,
                           causal=True, block_q=block_q, block_k=block_k,
                           tiles=(tiles_s, diffs))
    kt = k.astype(jnp.float32).T.astype(k.dtype)               # [D, bk]
    dq_s[i] = dq_s[i] + _dot(kt, dsc, _NN).T

    @pl.when(i == last)
    def _finish_kv():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)

    @pl.when(_row_start(i, j, nb))
    def _finish_q():
        dq_ref[0, 0, i] = dq_s[i].astype(dq_ref.dtype)


def _add_rows(acc, x):
    """acc + x for an x that holds acc's first rows only (a causal query
    sub-tile sees no key after its last query)."""
    if acc is None:
        return x
    n = x.shape[0]
    if n == acc.shape[0]:
        return acc + x
    return jnp.concatenate([acc[:n] + x, acc[n:]], axis=0)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, kb_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, causal, sub_q,
                      window=None):
    """The whole backward of one (batch, head) in one grid step: nothing
    crosses a grid step, so s^T, p^T, dp^T and ds^T are computed once and
    feed all three gradients (5 dots and one exp where the two kernels
    spend 7 and two), every operand is read once, and the results leave
    in one store each. The arithmetic is _bwd_dkv_body's, scores
    transposed [keys, queries]. The one new dot is dq = ds k, taken as
    (k^T ds^T)^T: that turns round two [rows, D] tiles where ds k would
    turn round the score tile (7 % of the kernel at 1024 x 1024 on the
    v5e; docs/perf.md, PR 27). The query axis is walked in static
    sub-tiles of sub_q (the forward's tile), last to first; a causal
    self-attention sub-tile takes only the keys up to its last query, so
    the blocks above the diagonal cost nothing here either. A `window`
    is the mask's second edge here and skips nothing: a call short enough
    for one pass is a few windows long at most."""
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    kb = jnp.broadcast_to(kb_ref[0], (LANES, bk)).T[:, :1]     # [bk, 1]
    dk = dv = None
    for q0 in reversed(range(0, bq, sub_q)):
        nk = min(bk, q0 + sub_q) if causal and bq == bk else bk
        rows = pl.ds(q0, sub_q)
        k = k_ref[0, 0, :nk]                                   # [nk, D]
        v = v_ref[0, 0, :nk]
        qb = q_ref[0, 0, rows]                                 # [sub_q, D]
        dob = do_ref[0, 0, rows]
        lse_b = lse_ref[0, 0, rows].T[:1]                      # [1, sub_q]
        delta_b = delta_ref[0, 0, rows].T[:1]
        st = _dot(k, qb, _NT) * scale + kb[:nk]                # [nk, sub_q]
        if causal:
            st = _mask_causal(st, q0, 0, 1, window)
        pt = jnp.exp(st - lse_b)
        dv = _add_rows(dv, _dot(pt.astype(dob.dtype), dob, _NN))
        dpt = _dot(v, dob, _NT)
        dst = pt * (dpt - delta_b) * scale
        dsc = dst.astype(qb.dtype)
        dk = _add_rows(dk, _dot(dsc, qb, _NN))
        kt = k.astype(jnp.float32).T.astype(k.dtype)           # [D, nk]
        dq_ref[0, 0, rows] = _dot(kt, dsc, _NN).T.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _use_tri(causal, Tq, Tk, bq, bk):
    """Triangular (block-skipping) causal grid applies to the aligned
    self-attention case; nq == 1 has no upper blocks to skip."""
    return causal and Tq == Tk and bq == bk and Tq // bq > 1


def _tri_specs(bq, bk, D, Dv):
    """Shared BlockSpecs for the triangular grids: q-row-indexed [bq, D]
    blocks (q/dq) and [bq, Dv] blocks (o/do), k-col-indexed [bk, D] blocks
    (k/dk) and [bk, Dv] blocks (v/dv), the [1, bk] key-bias block, and the
    q-row [bq, LANES] stats block (lse/delta). One definition keeps the
    three pallas_calls in sync. Returns (qrow, kcol, kbias, stats, orow,
    vcol); the last two ARE the first two where values are as wide as
    keys."""
    def row(d):
        return pl.BlockSpec((1, 1, bq, d),
                            lambda b, h, t, im, jm: (b, h, im[t], 0))

    def col(d):
        return pl.BlockSpec((1, 1, bk, d),
                            lambda b, h, t, im, jm: (b, h, jm[t], 0))

    qrow, kcol = row(D), col(D)
    kbias = pl.BlockSpec((1, 1, bk), lambda b, h, t, im, jm: (b, 0, jm[t]))
    stats = pl.BlockSpec((1, 1, bq, LANES),
                         lambda b, h, t, im, jm: (b, h, im[t], 0))
    if Dv == D:
        return qrow, kcol, kbias, stats, qrow, kcol
    return qrow, kcol, kbias, stats, row(Dv), col(Dv)


def _bias_args(kernel, kb, kbias, **static):
    """(kernel(**static), in_specs, operands) of a triangular or band
    call's key bias. With no bias (kb None) the call has no such operand,
    and the kernel's kb_ref, the sixth ref of every such kernel, is None:
    the bodies then add none. The kernel keeps its name."""
    if kb is not None:
        return functools.partial(kernel, **static), [kbias], [kb]

    @functools.wraps(kernel)
    def without(*refs, **static):
        return kernel(*refs[:5], None, *refs[5:], **static)
    return functools.partial(without, **static), [], []


def _mask_tiles(diffs, bq, bk):
    """The VMEM scratch of a triangular or band call's mask tiles: the
    tile of zeros and one a masked diagonal (_fill_mask_tiles)."""
    return pltpu.VMEM((len(diffs) + 1, bq, bk), jnp.float32)


def _fwd_call(q, k, v, kb, causal, scale, bq, bk, interpret, window=None):
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype),
        jax.ShapeDtypeStruct((B, H, Tq, LANES), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bq, Dv), jnp.float32),
    ]
    if _use_tri(causal, Tq, Tk, bq, bk):
        nb = _band(window, bk, Tq // bq)
        diffs = _mask_diffs(window, bk, Tq // bq)
        im, jm = _tri_maps(Tq // bq, nb)
        qrow, kcol, kbias, stats, orow, vcol = _tri_specs(bq, bk, D, Dv)
        kern, kb_spec, kb_arg = _bias_args(
            _fwd_kernel_tri, kb, kbias, scale=scale, block_q=bq, block_k=bk,
            diffs=diffs, window=window, nb=nb)
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, H, len(im)),
                in_specs=[qrow, kcol, vcol] + kb_spec,
                out_specs=[orow, stats],
                scratch_shapes=scratch_shapes + [_mask_tiles(diffs, bq, bk)],
            ),
            out_shape=out_shape,
            interpret=interpret,
        )(jnp.asarray(im), jnp.asarray(jm), q, k, v, *kb_arg)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, window=window)
    return pl.pallas_call(
        kern,
        grid=(B, H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(q, k, v, kb)


def _bwd_call_tri(q, k, v, kb, do, lse, delta, scale, bq, bk, interpret,
                  window=None):
    """Causal backward over the linearized lower-triangle grid or its band
    (see the strategy note at the top): dq accumulates over a q-row's
    k-blocks, then dk/dv re-walk the same pairs k-block-major
    (_tri_maps_kv order)."""
    B, H, Tq, D = q.shape
    Dv = v.shape[3]
    nq = Tq // bq
    nb = _band(window, bk, nq)
    diffs = _mask_diffs(window, bk, nq)
    qrow, kcol, kbias, stats, orow, vcol = _tri_specs(bq, bk, D, Dv)
    im, jm = _tri_maps(nq, nb)
    kern, kb_spec, kb_arg = _bias_args(
        _bwd_dq_kernel_tri, kb, kbias, scale=scale, block_q=bq, block_k=bk,
        diffs=diffs, window=window, nb=nb)
    bwd_in_specs = [qrow, kcol, vcol] + kb_spec + [orow, stats, stats]
    dq = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im)),
            in_specs=bwd_in_specs,
            out_specs=qrow,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            _mask_tiles(diffs, bq, bk)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(im), jnp.asarray(jm), q, k, v, *kb_arg, do, lse, delta)
    im2, jm2 = _tri_maps_kv(nq, nb)
    kern, _, _ = _bias_args(
        _bwd_dkv_kernel_tri, kb, kbias, scale=scale, block_q=bq, block_k=bk,
        nq=nq, diffs=diffs, window=window, nb=nb)
    dk, dv = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im2)),
            in_specs=bwd_in_specs,
            out_specs=[kcol, vcol],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
                _mask_tiles(diffs, bk, bq),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(jnp.asarray(im2), jnp.asarray(jm2), q, k, v, *kb_arg, do, lse, delta)
    return dq, dk, dv


def _head_vmem_limit(T, D, bq, bk, itemsize):
    """The VMEM limit the one-pass head kernel's call states, in bytes:
    what it holds (a head's dq in float32 and the two buffers of its
    output block, a grid step's blocks twice over: q, do, k, v in, dk, dv
    out, lse and delta, the bias in its sublane tile; the dk and dv
    accumulators; the mask's tiles, counted as the four a window that
    ends inside a block takes) plus Mosaic's default scope for the body's
    score tiles, as the two kernels have it at the same tiles. A head
    narrower than a
    lane tile takes a whole one in VMEM: D = 64 is counted as 128 (float32
    operands of D = 64 over 16384 positions, traced under highest
    precision, were refused by 1.75 MiB at the narrow count; compiled for
    a described v5e, PR 44), and keys of 192 two. D is the keys' width:
    values narrower than the keys are counted as wide as they."""
    D = _round_up(D, LANES)
    dq = T * D * (4 + 2 * itemsize)
    blocks = 2 * ((2 * bq + 4 * bk) * D * itemsize + 2 * bq * LANES * 4
                  + 8 * bk * 4)
    return (dq + blocks + 2 * bk * D * 4 + 4 * bq * bk * 4
            + _MOSAIC_SCOPE_BYTES)


def _bwd_call_head(q, k, v, kb, do, lse, delta, scale, bq, bk, interpret,
                   window=None):
    """One pass over a head's triangle or band: grid (B, H, pairs), one
    kernel for dq, dk and dv (_bwd_head_kernel). The call states the VMEM
    it needs (_head_vmem_limit): Mosaic's default does not cover a head's
    dq."""
    B, H, Tq, D = q.shape
    Dv = v.shape[3]
    nq = Tq // bq
    nb = _band(window, bk, nq)
    diffs = _mask_diffs(window, bk, nq)
    qrow, kcol, kbias, stats, orow, vcol = _tri_specs(bq, bk, D, Dv)
    head = pl.BlockSpec((1, 1, nq, bq, D),
                        lambda b, h, t, im, jm: (b, h, 0, 0, 0))
    im, jm = _tri_maps_kv(nq, nb)
    kern, kb_spec, kb_arg = _bias_args(
        _bwd_head_kernel, kb, kbias, scale=scale, block_q=bq, block_k=bk,
        nq=nq, diffs=diffs, window=window, nb=nb)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im)),
            in_specs=[qrow, kcol, vcol] + kb_spec + [orow, stats, stats],
            out_specs=[head, kcol, vcol],
            scratch_shapes=[
                pltpu.VMEM((nq, bq, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
                _mask_tiles(diffs, bk, bq),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq, bq, D), q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_head_vmem_limit(Tq, D, bq, bk,
                                              q.dtype.itemsize)),
        interpret=interpret,
    )(jnp.asarray(im), jnp.asarray(jm), q, k, v, *kb_arg, do, lse, delta)
    return dq.reshape(q.shape), dk, dv


def _bwd_call_fused(q, k, v, kb, do, lse, delta, causal, scale, sub_q,
                    interpret, window=None):
    """One pass over a head's whole score matrix: grid (B, H), one kernel
    for dq, dk and dv (_bwd_fused_kernel)."""
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    if kb is None:
        # a forward on the triangular grid that took no bias
        kb = jnp.zeros((B, 1, Tk), jnp.float32)
    qrow = pl.BlockSpec((1, 1, Tq, D), lambda b, h: (b, h, 0, 0))
    kcol = pl.BlockSpec((1, 1, Tk, D), lambda b, h: (b, h, 0, 0))
    orow, vcol = qrow, kcol
    if Dv != D:
        orow = pl.BlockSpec((1, 1, Tq, Dv), lambda b, h: (b, h, 0, 0))
        vcol = pl.BlockSpec((1, 1, Tk, Dv), lambda b, h: (b, h, 0, 0))
    kbias = pl.BlockSpec((1, 1, Tk), lambda b, h: (b, 0, 0))
    stats = pl.BlockSpec((1, 1, Tq, LANES), lambda b, h: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          sub_q=sub_q, window=window),
        grid=(B, H),
        in_specs=[qrow, kcol, vcol, kbias, orow, stats, stats],
        out_specs=[qrow, kcol, vcol],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, kb, do, lse, delta)


def _bwd_call(q, k, v, kb, do, lse, delta, causal, scale, bq, bk, schedule,
              interpret, window=None):
    """One algorithm, scheduled by what has to be kept across grid steps
    and whether VMEM holds it (_prep's rule): 'tile', one pass where a
    head's scores are one tile; 'head', one pass over the triangle or
    band with the head's dq in VMEM; None, dq and dk/dv in a pass each
    over the triangular or the rectangular grid."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if schedule == 'tile':
        return _bwd_call_fused(q, k, v, kb, do, lse, delta, causal, scale,
                               bq, interpret, window)
    if schedule == 'head':
        return _bwd_call_head(q, k, v, kb, do, lse, delta, scale, bq, bk,
                              interpret, window)
    if _use_tri(causal, Tq, Tk, bq, bk):
        return _bwd_call_tri(q, k, v, kb, do, lse, delta, scale, bq, bk,
                             interpret, window)
    return _bwd_call_rect(q, k, v, kb, do, lse, delta, causal, scale, bq, bk,
                          interpret, window)


def _bwd_call_rect(q, k, v, kb, do, lse, delta, causal, scale, bq, bk,
                   interpret, window=None):
    """Two passes over the rectangular grid: dq accumulates over a q-row's
    k-blocks, dk/dv over a k-column's q-blocks."""
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window),
        grid=(B, H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, kb, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window),
        grid=(B, H, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kb, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, kb, causal, window, scale, bq, bk, schedule,
               interpret):
    o, lse = _fwd_call(q, k, v, kb, causal, scale, bq, bk, interpret, window)
    return o, lse[..., 0]


def _flash_lse_fwd(q, k, v, kb, causal, window, scale, bq, bk, schedule,
                   interpret):
    o, lse = _fwd_call(q, k, v, kb, causal, scale, bq, bk, interpret, window)
    # named for a recompute region's policy (step_artifact._run_region):
    # kept, the backward pass rebuilds q, k and v and not this call
    o, lse = checkpoint_name(o, 'flash_out'), checkpoint_name(lse, 'flash_lse')
    return (o, lse[..., 0]), (q, k, v, kb, o, lse)


def _folded_delta(do, o, dlse):
    """delta = sum(do * o) - dlse, lane-broadcast as the kernels read it:
    lse = logsumexp(S) gives dS|lse = P * dlse, and the kernels compute
    dS = P * (dP - delta), so folding delta' = delta - dlse routes the lse
    gradient through the same pallas calls, whatever the schedule (the
    FlashAttention D-trick extended one term)."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta - dlse.astype(jnp.float32)
    return jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))


def _flash_lse_bwd(causal, window, scale, bq, bk, schedule, interpret, res,
                   cot):
    """Backward with an lse cotangent, sharing the kernels unchanged
    (_folded_delta)."""
    do, dlse = cot
    q, k, v, kb, o, lse = res
    delta = _folded_delta(do, o, dlse)
    dq, dk, dv = _bwd_call(q, k, v, kb, do, lse, delta, causal, scale,
                           bq, bk, schedule, interpret, window)
    # kb is a mask constant (see module docstring): zero cotangent
    return dq, dk, dv, None if kb is None else jnp.zeros_like(kb)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# The two staircase calls are jitted functions of their own: a trace tells
# their Mosaic events from the aligned part's by the function they were
# called in (chipbench/harness/scopes.py callee_of), as jax's megablox
# kernels are told apart.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def staircase_fwd(q, k, v, scale, bq, bk, qpw, spw, interpret):
    """The forward over the staircase grid (_stair_maps): grid (B, H,
    pairs), the forward body as it is, unmasked and with no bias."""
    B, H, Tq, D = q.shape
    Dv = v.shape[3]
    im, jm = _stair_maps(Tq // bq, qpw, spw)
    qrow, kcol, _, stats, orow, vcol = _tri_specs(bq, bk, D, Dv)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_stair, scale=scale, block_q=bq,
                          block_k=bk, qpw=qpw, spw=spw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im)),
            in_specs=[qrow, kcol, vcol],
            out_specs=[orow, stats],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(im), jnp.asarray(jm), q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def staircase_bwd(q, k, v, do, lse, delta, scale, bq, bk, qpw, spw,
                  interpret):
    """The backward over the same pairs in two passes: dq over a q-row's
    summary blocks, dk/dv over a summary block's q-blocks
    (_stair_maps_kv order), as _bwd_call_tri walks its triangle."""
    B, H, Tq, D = q.shape
    Dv = v.shape[3]
    nq = Tq // bq
    qrow, kcol, _, stats, orow, vcol = _tri_specs(bq, bk, D, Dv)
    bwd_in_specs = [qrow, kcol, vcol, orow, stats, stats]
    im, jm = _stair_maps(nq, qpw, spw)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_stair, scale=scale, block_q=bq,
                          block_k=bk, qpw=qpw, spw=spw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im)),
            in_specs=bwd_in_specs,
            out_specs=qrow,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(im), jnp.asarray(jm), q, k, v, do, lse, delta)
    im2, jm2 = _stair_maps_kv(nq, qpw, spw)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_stair, scale=scale, block_q=bq,
                          block_k=bk, nq=nq, qpw=qpw, spw=spw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, len(im2)),
            in_specs=bwd_in_specs,
            out_specs=[kcol, vcol],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(jnp.asarray(im2), jnp.asarray(jm2), q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _stair_lse(q, k, v, scale, bq, bk, qpw, spw, interpret):
    o, lse = staircase_fwd(q, k, v, scale, bq, bk, qpw, spw, interpret)
    return o, lse[..., 0]


def _stair_lse_fwd(q, k, v, scale, bq, bk, qpw, spw, interpret):
    o, lse = staircase_fwd(q, k, v, scale, bq, bk, qpw, spw, interpret)
    # kept by a recompute region's policy under the names _flash_lse_fwd
    # gives its own
    o, lse = checkpoint_name(o, 'flash_out'), checkpoint_name(lse, 'flash_lse')
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _stair_lse_bwd(scale, bq, bk, qpw, spw, interpret, res, cot):
    """_flash_lse_bwd's arithmetic over the staircase's pairs."""
    do, dlse = cot
    q, k, v, o, lse = res
    return staircase_bwd(q, k, v, do, lse, _folded_delta(do, o, dlse),
                         scale, bq, bk, qpw, spw, interpret)


_stair_lse.defvjp(_stair_lse_fwd, _stair_lse_bwd)


def merge_lse(o, lse, o_s, lse_s):
    """Two partial attentions over DISJOINT key sets as one softmax over
    their union, from each part's output and log-sum-exp:
        lse' = logaddexp(lse, lse_s)
        o'   = o * e^(lse - lse') + o_s * e^(lse_s - lse')
    in float32. Ring attention merges its ring steps so
    (parallel/ring_attention.py), flash_attention_summary its exact and
    its summary part. Differentiable through all four."""
    lse_new = jnp.logaddexp(lse, lse_s)
    w = jnp.exp(lse - lse_new)[..., None]
    w_s = jnp.exp(lse_s - lse_new)[..., None]
    return (o.astype(jnp.float32) * w + o_s.astype(jnp.float32) * w_s,
            lse_new)


# Tile defaults from the tools/tune_flash.py sweep of PR 24 on a v5e (bf16,
# D = 64, 16 x 8 x 1024 and 64 x 8 x 256, forward plus backward, square and
# oblong tiles; docs/perf.md has every row and the commands). Not causal:
# 1024 x 1024 beats 512 x 512 by 12% at T = 1024. Causal: 512 x 512 on the
# triangular grid beats one masked 1024 x 1024 block by 1.5%. Equal
# bq == bk keeps the triangular grid eligible (_use_tri). Shorter sequences
# clip the tiles in _prep, which is all that T = 256 ever sees.
# The backward has three schedules (_prep's rule). 'tile' (PR 27): ONE
# pass wherever a head's scores fit the table's largest tile, masked or
# not: the residual lse is per row, so nothing ties the backward to the
# forward's tile, and a causal call walks that tile in the forward's 512
# sub-tiles (_bwd_fused_kernel; at 16 x 8 x 1024 x 64 causal 0.87 ms a
# call against 1.59 ms for the two kernels on the triangular grid): every
# call of the Transformer cells. 'head' (PR 42): one pass over the
# triangular grid or its band at the forward's tiles, the head's dq in
# VMEM (_bwd_head_kernel): causal self-attention of more than one tile,
# which is every call of the language-model cells (16384 x 128 with and
# without a window, 8192 x 256, 8192 x 128, 4096 x 128, and their float32
# checks at 512- and 256-tiles). Two passes: the rectangular grid (not
# causal over 1024, Tq != Tk, oblong tiles) and a head whose dq VMEM does
# not hold. Tiles a caller forces are the backward's too.
_TUNED_BQ_BK = {True: (512, 512), False: (1024, 1024)}
# Beside 1024 x 1024 float32 score tiles Mosaic's VMEM budget holds operand
# rows of up to this many bytes (compiled for a described v5e, PR 24:
# float32 at D = 128 and bf16 at D = 256 fit, float32 at D = 256 is
# refused).
_WIDE_ROW_BYTES = 512
# The one-pass backward holds a head's whole score tile, its operands and
# its three results in VMEM at once, and fits Mosaic's default 16 MiB at
# every shape this rule lets into one tile (compiled for a described v5e,
# PR 27, tests/test_flash_aot.py: 1024 x 1024 at bf16 D = 256 and float32
# D = 128, 512 x 512 at float32 D = 512; what fills VMEM first is the
# double-buffered q, k, v, do, dq, dk, dv blocks, 14 x T x row bytes,
# where the two kernels hold 10 and 12).
_MOSAIC_SCOPE_BYTES = 16 * 2 ** 20
# The one-pass head kernel holds a head's dq besides, which that default
# does not cover, so its call states a limit of its own
# (_head_vmem_limit). The rule lets a head in where that limit is at most
# half the 128 MiB of a v5e's VMEM (16384 x 128 bf16: 19.0 MiB held, 35.0
# stated; float32: 28.5 and 44.5).
_HEAD_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _default_tile(tuned, T, row_bytes):
    """The table's tile for a sequence of T, or the 512 it was before PR 24
    where the larger one cannot be had for nothing: operand rows too wide
    for VMEM, or a length it would pad further than 512 does (1536 keys
    in 1024-tiles are 2048)."""
    if row_bytes > _WIDE_ROW_BYTES and T > 512:
        # rows wider than the table was tuned for, over more than one
        # tile: float32 operands at D = 256, which only a float32 check
        # of a bf16 cell runs. Traced under jax's highest matmul precision
        # (each float32 dot then splits its operands into bf16 parts in
        # VMEM) the two-pass backward at 512 x 512 is refused at T = 8192
        # (compiled for a described v5e, PR 30); 256 x 256 fits.
        return min(tuned, 256)
    if row_bytes > _WIDE_ROW_BYTES or (T > tuned and T % tuned):
        return min(tuned, 512)
    return tuned


def _window_of(window, causal, Tq):
    """The window as the kernels take it: None where there is none or
    where it reaches every earlier position (`window >= Tq` IS plain
    causal attention, on the same grid)."""
    if window is None:
        return None
    if not causal or int(window) != window or window < 1:
        raise ValueError('flash attention: window=%r is a whole number of '
                         'positions, at least 1, of a causal call (a query '
                         'sees its own position and the window - 1 before '
                         'it)' % (window,))
    return None if window >= Tq else int(window)


def _prep(q, k, v, key_bias, sm_scale, block_q, block_k, interpret,
          causal=False, window=None):
    """Shared block-size/padding/bias plumbing for the public wrappers."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if sm_scale is None:
        sm_scale = D ** -0.5
    if not isinstance(interpret, bool):
        raise TypeError(
            'flash attention needs interpret=True (pallas interpreter) or '
            'interpret=False (Mosaic, TPU only) from its caller, got %r'
            % (interpret,))
    # the dots take their operands as given, so the three agree on a dtype
    operands = jnp.result_type(q, k, v)
    q, k, v = (x.astype(operands) for x in (q, k, v))
    tuned_bq, tuned_bk = _TUNED_BQ_BK[bool(causal)]
    whole_q, whole_k = _TUNED_BQ_BK[False]
    row_bytes = D * operands.itemsize
    forced = block_q is not None or block_k is not None
    if block_q is None:
        block_q = _default_tile(tuned_bq, Tq, row_bytes)
    if block_k is None:
        block_k = _default_tile(tuned_bk, Tk, row_bytes)
    bq = min(block_q, _round_up(Tq, 128))
    bk = min(block_k, _round_up(Tk, 128))
    Tq_p = _round_up(Tq, bq)
    Tk_p = _round_up(Tk, bk)
    # trace time: once per attention call per lowering (a forward and one
    # or two backward kernels each), never per step. `grid` is the
    # forward's; `flash.tiles` the (q-block, k-block) pairs a head that the
    # call's grids visit: forward + dq + dk/dv, forward + one pass over
    # the same pairs ('head'), or forward + one step ('tile').
    nq, nk = Tq_p // bq, Tk_p // bk
    tri = _use_tri(causal, Tq_p, Tk_p, bq, bk)
    if tri:
        nb = _band(window, bk, nq)
        grid, pairs = 'triangle' if nb is None else 'band', _tile_pairs(nq, nb)
        masked = _masked_pairs(nq, _mask_diffs(window, bk, nq))
    else:
        grid, pairs = 'rect', nq * nk
        masked = pairs if causal else 0
    # the backward's schedule, read off the shapes: one pass where a head's
    # scores are one tile, the forward's or (tiles not forced) the table's
    # largest for rows this wide; one pass over the triangle or band where
    # VMEM holds the head's dq beside a step's blocks; else two passes
    if (Tq_p, Tk_p) == (bq, bk) or (
            not forced and Tq_p <= _default_tile(whole_q, Tq, row_bytes)
            and Tk_p <= _default_tile(whole_k, Tk, row_bytes)):
        schedule, bwd_pairs, bwd_masked = 'tile', 1, int(bool(causal))
    elif tri and _head_vmem_limit(
            Tq_p, D, bq, bk, operands.itemsize) <= _HEAD_VMEM_LIMIT_BYTES:
        schedule, bwd_pairs, bwd_masked = 'head', pairs, masked
    else:
        schedule, bwd_pairs, bwd_masked = None, 2 * pairs, 2 * masked
    # values narrower (or wider) than the keys say so; equal widths count
    # under the labels they always had
    obs.counter('flash.lowered', operands=operands.name, grid=grid,
                **({'dv': int(v.shape[3])} if v.shape[3] != D else {})).inc()
    if schedule:
        obs.counter('flash.backward', passes='one', span=schedule).inc()
    else:
        obs.counter('flash.backward', passes='two').inc()
    obs.counter('flash.tiles', grid=grid).inc(pairs + bwd_pairs)
    # of those, the pairs that pay for a mask: on the triangle and the band
    # the ones on a masked diagonal (_mask_diffs), which add a tile that
    # is not zeros; on the other grids every pair of a causal call, which
    # computes its mask
    obs.counter('flash.tiles_masked', grid=grid).inc(masked + bwd_masked)
    if key_bias is not None:
        key_bias = lax.stop_gradient(
            key_bias.reshape(B, Tk).astype(jnp.float32))
    elif not tri or Tk_p != Tk:
        # the rectangular grid's bodies add a bias whatever it holds, and
        # padded keys are removed through one; a triangle or band with
        # neither hands in none and adds none
        key_bias = jnp.zeros((B, Tk), jnp.float32)
    if Tq_p != Tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tq_p - Tq), (0, 0)))
    if Tk_p != Tk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))
        key_bias = jnp.pad(key_bias, ((0, 0), (0, Tk_p - Tk)),
                           constant_values=NEG_BIG)
    if key_bias is not None:
        # (B, 1, Tk): Mosaic block shapes need the sublane dim to equal
        # the array dim, so the bias carries an explicit singleton sublane
        key_bias = key_bias.reshape(B, 1, Tk_p)
    return (q, k, v, key_bias, float(sm_scale), int(bq), int(bk),
            schedule, bool(interpret), Tq, Tq_p)


def flash_attention_lse(q, k, v, key_bias=None, causal=False, sm_scale=None,
                        block_q=None, block_k=None, window=None, *,
                        interpret):
    """flash_attention that ALSO returns the per-query logsumexp
    ([B, H, Tq], f32) — the combine statistic ring attention needs to merge
    partial attention over key shards. Differentiable in q/k/v through BOTH
    outputs (see _flash_lse_bwd)."""
    window = _window_of(window, causal, q.shape[2])
    (q, k, v, kb, scale, bq, bk, schedule, interp, Tq, Tq_p) = _prep(
        q, k, v, key_bias, sm_scale, block_q, block_k, interpret,
        causal=causal, window=window)
    o, lse = _flash_lse(q, k, v, kb, bool(causal), window, scale, bq, bk,
                        schedule, interp)
    if Tq_p != Tq:
        o = o[:, :, :Tq, :]
        lse = lse[:, :, :Tq]
    return o, lse


def flash_attention(q, k, v, key_bias=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, window=None, *, interpret):
    """Flash attention over [B, H, T, D] tensors.

    key_bias: optional additive [B, Tk] bias (e.g. -1e9 on padded keys);
              treated as a non-differentiable mask.
    causal:   lower-triangular masking (decoder self-attention).
    window:   with causal, a sliding window: query i sees the keys
              i - window + 1 .. i (its own position counts). None, or a
              window that reaches the whole sequence: plain causal. On
              the triangular grid's conditions the kernels visit only the
              band of blocks the window touches.
    block_q/block_k: kernel tile sizes (defaults from the _TUNED_BQ_BK
              table; tools/tune_flash.py sweeps them).
    interpret: required. False compiles through Mosaic (TPU only); True
              runs the kernel bodies under the pallas interpreter.
    v may be [B, H, Tk, Dv] with Dv another width than q's and k's D (the
    v, o, do and dv blocks are then [block, Dv], the scores untouched).
    Returns [B, H, Tq, Dv] in q's dtype; differentiable w.r.t. q/k/v.
    """
    # one custom_vjp serves both wrappers: the unused lse output gets a
    # zero cotangent, making _flash_lse_bwd exactly the classic backward
    o, _ = flash_attention_lse(q, k, v, key_bias=key_bias, causal=causal,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, window=window,
                               interpret=interpret)
    return o


# The staircase's tiles: q-blocks of 512 (the causal table's) inside a
# window, summary blocks of the largest of these that divides a window's
# summaries (2048 / 16 = 128 at EvaByte's sizes: one lane tile of keys).
_STAIR_BQ = 512
_STAIR_BK = (512, 256, 128)


def summary_blocks(window, every):
    """(block_q, block_k) the staircase kernels take for aligned windows
    of `window` positions summarised once every `every`, or None where
    Mosaic's tiling does not take them (the XLA chain then, as off the
    TPU)."""
    per = window // every
    bk = next((b for b in _STAIR_BK if per % b == 0), None)
    if window % _STAIR_BQ or bk is None:
        return None
    return _STAIR_BQ, bk


def _summary_shapes(q, kbar, window, every):
    T, window = q.shape[2], int(window)
    if window < 1 or T % window:
        raise ValueError('flash attention: aligned windows of %r do not '
                         'divide a row of %d' % (window, T))
    if kbar is None:
        return T // window, None
    every = int(every)
    if every < 1 or window % every or kbar.shape[2] * every != T:
        raise ValueError(
            'flash attention: one summary every %r positions, %d summaries '
            'for a row of %d in windows of %d: the summaries divide a '
            'window and cover the row' % (every, kbar.shape[2], T, window))
    return T // window, window // every


def flash_attention_summary(q, k, v, kbar=None, vbar=None, *, window,
                            every=None, sm_scale=None, block_q=None,
                            block_k=None, interpret):
    """Causal attention that is EXACT inside aligned windows and sees
    what lies before a query's window through SUMMARIES, under one
    softmax (EVA, Zheng et al. 2023, arXiv:2302.04542, as EvaByte sizes
    it). q, k, v [B, H, T, D] (v may be [.., Dv]); the row is cut into
    aligned windows of `window` positions; kbar [B, H, T / every, D] and
    vbar [B, H, T / every, Dv] hold one summary key and value for every
    `every` consecutive positions. Query t of window w sees

        the keys m of window w with m <= t                     (exact)
        the summaries n whose positions lie in a window before w

    and its output is one softmax over both sets. Two geometries of the
    flash kernels and a merge:

      aligned    the causal kernels over rows of `window`: the operands
                 viewed [B, H x T / window, window, D], which costs
                 nothing, so the triangular grid and the one-pass
                 backward run as for any causal call of that length;
      staircase  the forward, dq and dk/dv kernels unmasked on a grid of
                 the (q-block, summary-block) pairs whose summaries lie in
                 an earlier window (_stair_maps): the queries from the
                 second window on against the summaries before the last;
      merge_lse  joins the two (o, lse) pairs in float32.

    Neither set's scores reach HBM. Without kbar and vbar (or in a row of
    one window) the exact part is the answer. `block_q`/`block_k` are the
    STAIRCASE's tiles (summary_blocks() by default); the aligned part
    takes the causal table's. Counters `flash.forward{geometry=aligned|
    staircase}` a call beside `flash.lowered` and `flash.backward` of
    the aligned part. Returns [B, H, T, Dv] in q's dtype."""
    B, H, T, D = q.shape
    windows, per = _summary_shapes(q, kbar, window, every)
    window = int(window)
    if sm_scale is None:
        sm_scale = D ** -0.5

    def rows(x):
        return x.reshape(B, H * windows, window, x.shape[3])

    obs.counter('flash.forward', geometry='aligned').inc()     # trace time
    o_e, lse_e = flash_attention_lse(rows(q), rows(k), rows(v), causal=True,
                                     sm_scale=sm_scale, interpret=interpret)
    o_e, lse_e = o_e.reshape(B, H, T, -1), lse_e.reshape(B, H, T)
    if kbar is None or windows == 1:
        return o_e
    if block_q is None or block_k is None:
        blocks = summary_blocks(window, every)
        if blocks is None:
            raise ValueError('flash attention: no staircase tiles for '
                             'windows of %d summarised every %d; pass '
                             'block_q and block_k' % (window, every))
        block_q, block_k = blocks
    if window % block_q or per % block_k:
        raise ValueError('flash attention: staircase tiles %d x %d do not '
                         'divide a window of %d positions and %d summaries'
                         % (block_q, block_k, window, per))
    operands = jnp.result_type(q, kbar, vbar)
    # the first window sees no summary and the last window's summaries
    # are seen by nobody: neither is handed to the kernels
    seen = kbar.shape[2] - per
    q_s, kbar, vbar = (x.astype(operands) for x in (
        q[:, :, window:], kbar[:, :, :seen], vbar[:, :, :seen]))
    obs.counter('flash.forward', geometry='staircase').inc()   # trace time
    o_s, lse_s = _stair_lse(
        q_s, kbar, vbar, float(sm_scale), int(block_q), int(block_k),
        window // block_q, per // block_k, bool(interpret))
    o_t, _ = merge_lse(o_e[:, :, window:], lse_e[:, :, window:], o_s, lse_s)
    return jnp.concatenate([o_e[:, :, :window], o_t.astype(q.dtype)], axis=2)


def reference_attention_summary(q, k, v, kbar=None, vbar=None, *, window,
                                every=None, sm_scale=None):
    """flash_attention_summary in plain XLA with the masks written out
    densely: ONE softmax over [T + T / every] scores a query (the
    fallback off the TPU, and the tests' oracle)."""
    B, H, T, D = q.shape
    _summary_shapes(q, kbar, window, every)
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32)
    pos = jnp.arange(T)
    seen = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] // window == pos[None, :] // window)
    keys, values = k, v
    if kbar is not None:
        first = jnp.arange(kbar.shape[2]) * every      # a summary's first
        seen = jnp.concatenate(
            [seen, pos[:, None] // window > first[None, :] // window], axis=1)
        keys = jnp.concatenate([k, kbar.astype(k.dtype)], axis=2)
        values = jnp.concatenate([v, vbar.astype(v.dtype)], axis=2)
    s = jnp.einsum('bhqd,bhkd->bhqk', qf, keys.astype(jnp.float32)) \
        * sm_scale
    p = jax.nn.softmax(jnp.where(seen, s, NEG_BIG), axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p,
                      values.astype(jnp.float32)).astype(q.dtype)


def flash_attention_sharded(mesh, q, k, v, key_bias=None, causal=False,
                            sm_scale=None, window=None, *, interpret):
    """flash_attention inside a GSPMD-partitioned step: the kernel runs
    PER SHARD, batch split over the mesh's 'dp' axis and heads over 'tp'
    (the layout DistributeTranspiler feeds and the Megatron column-parallel
    q/k/v projections already produce). Nothing partitions a bare
    pallas_call: with sharded operands jax refuses to lower it
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map" — tests/test_flash_attention.py pins the refusal,
    chip_smoke.py's mesh leg checks the compiled step's calls are per
    shard). Attention is independent per (batch, head), so the per-shard
    call needs no collective. An axis the mesh lacks, or that does not
    divide its dim, is left unsplit: its operands are gathered and every
    device along it computes the same attention."""
    B, H = q.shape[0], q.shape[1]

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    bdim, hdim = axis('dp', B), axis('tp', H)
    if bdim is None and hdim is None:
        return flash_attention(q, k, v, key_bias=key_bias, causal=causal,
                               sm_scale=sm_scale, window=window,
                               interpret=interpret)
    from jax.sharding import PartitionSpec as P
    qkv = P(bdim, hdim, None, None)
    bias = () if key_bias is None else (key_bias,)

    def body(q, k, v, *kb):
        return flash_attention(q, k, v, key_bias=kb[0] if kb else None,
                               causal=causal, sm_scale=sm_scale,
                               window=window, interpret=interpret)

    # check_vma off: pallas out_shapes carry no varying-mesh-axes info
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qkv, qkv, qkv) + (P(bdim, None),) * len(bias),
        out_specs=qkv, check_vma=False)(q, k, v, *bias)


def reference_attention(q, k, v, key_bias=None, causal=False, sm_scale=None,
                        window=None):
    """Plain-XLA attention with the same signature (fallback + test oracle).
    key_bias is stop_gradient'd to match the kernel's semantics."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    window = _window_of(window, causal, Tq)
    if sm_scale is None:
        sm_scale = D ** -0.5
    s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if key_bias is not None:
        s = s + lax.stop_gradient(
            key_bias.reshape(B, 1, 1, Tk).astype(jnp.float32))
    if causal:
        qpos = jnp.arange(Tq)[:, None]
        kpos = jnp.arange(Tk)[None, :]
        seen = qpos >= kpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p,
                      v.astype(jnp.float32)).astype(q.dtype)
