"""Stage `gdn_intra` of the gated delta rule as one Pallas kernel forward
and one backward: everything C x C of a chunk (the decays D, K K^T, A, the
unit lower triangular solve T = (I + A)^-1, Q K^T) lives in VMEM for the
grid step that needs it, and only what the scan reads leaves.

The mathematics, its precisions and the names are those of
fluid/ops_impl/linear_attention_ops.py `_intra`, which stays as the
composition this is tested against and as every other platform's path:

    D   = exp(G_i - G_j) for i >= j, 0 above     G the in-chunk running sum
    A   = strict_lower(diag(beta) (K K^T) * D)
    T   = (I + A)^-1           float32: diagonal blocks of 16 by forward
                               substitution, then two levels of merges
    U   = T diag(beta) V       W = T diag(beta exp G) K
    P   = (Q K^T) * D          Qg = diag(exp G) Q
    Kd  = diag(exp(G_C - G)) K

Operands of the matmuls in `dtype` (bf16 under AMP, float32 accumulation;
float32 operands multiply at full precision, written in the body: a
`jax.default_matmul_precision` context does not reach a kernel); decays,
solve and every elementwise product in float32. W, Qg, Kd, P leave in
`dtype`, which is what the scan's matmuls round them to anyway, U in
float32.

The solve in the form Mosaic lowers. A head's four diagonal blocks are
eliminated side by side, column by column (row i of a block's inverse minus
a[i, k] times row k, k = 0..15: the substitution's sums, accumulated in
the order of k); a level of merges is two 64 x 64 products
X - X M X over the block diagonal X so far, M the blocks of A one level
off it (what `_inverse` computes a pair of blocks at a time: the terms
added are exact zeros).

The backward kernel computes the chunk again in VMEM, but for the solve,
and pulls the five cotangents back in the same grid step; the solve's part
is -T^T dT T^T on the strict lower triangle. T is the one C x C array of
the chunk it reads (beside P's cotangent): the op's backward runs the forward kernel again for what its scans
read, and that run writes T out (float32, 67 MB a layer at 4096
chunk-heads, alive from there to the backward kernel of the same layer):
a third of a step's solves for 0.16 ms of traffic a layer.

What the two kernel pairs read, by the rank of g. With a decay a HEAD
(`_fwd_kernel`, `_bwd_kernel`) the operands are prepared by XLA: q and k
normalised, q scaled, both rounded to `dtype`, G summed. With a decay a
CHANNEL (`_fwd_kernel_channel`, `_bwd_kernel_channel`; G is [C, Dk] and D
stays inside the products, linear_attention_ops `_intra_channel`) the
kernels read the OP's own operands (ISSUE 56: on a [8192, 32, 128] array
each pass XLA makes to prepare an operand is 0.2 to 0.4 GB, and they were
29 GB a step) WHERE THE OP HOLDS THEM (ISSUE 58: `_call_channel`): q, k, v
and g viewed [B, T, H x D], which is how a layer's projections and
convolutions leave them, a block (1, 64, heads x 128) at (row, chunk,
step of heads) whose lanes the bodies slice by head (`_head`: whole lane
tiles). The DMA's strided read is what `_to_chunks` did as a transposing
copy through HBM, and the backward writes dq, dk, dv and dg through the
same blocks, so no chunked copy of a tokens' operand exists (they were
19.2 GB a step; the kernels alone run as before, 2.44 ms forward and 5.24
backward a layer of `ling3flash_s8192` in bf16: my chip run, PR 58). q and
k come as the op holds them, g NOT summed and, where `floor` says so, not
yet held to its floor (the bodies hold it as they load it and hand back
no gradient where the raw g lay under it), beta, the one operand XLA
still lays out by chunk ([B, T, H] float32). What the scan reads (W, U,
Qg, Kd, P, T, G's last row) stays [N x B, H, C, .]. In VMEM, a head at
a time: the l2 norm in float32 (`_unit`), q's scale, the rounding to
`dtype` where `_stage_intra` rounds on the other paths, and G as the
product of the
lower triangle of ones with g at float32 precision (`_ones_below`: the
ones are exact in bf16 and the MXU adds in float32, so each of the 64 sums
is rounded about once; a second product with the triangle transposed turns
G's cotangent into g's in the backward). The forward also writes G's last
row [1, Dk], the log of the chunk's decay, and the backward takes that
row's cotangent as an operand and hands back the cotangents of the RAW q
and k, through the scale and the norm in float32 (`_unit_pull`).
`_unit` and `_unit_pull` take a head's [C, Dk] rows and nothing of the
per-channel form: the per-head body can take them (ROADMAP.md Speed 4 (f)).

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the flash kernels
and the grouped matmul it is what the op lowers to on the TPU.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['gated_delta_intra', 'gated_delta_intra_tokens', 'usable',
           'HEADS']

# heads a grid step at 2-byte operands (tools/bench_gated_delta_intra.py
# --sweep; docs/perf.md has the rows); 4-byte operands take half
HEADS = 8
_CHUNK = 64
_BLOCK = 16          # linear_attention_ops._SOLVE_BLOCK
_MIDDLE = (_BLOCK - 1) // 2     # the row of a block its decays refer to


def usable(chunk, dk, dv, dtype):
    """A chunk of 64 (the solve's four blocks of 16 and two merges are
    written out), heads of whole lane tiles, bf16 or float32 operands."""
    return (chunk == _CHUNK and dk % 128 == 0 and dv % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _dot(a, b, dims, dtype):
    """a x b on the MXU, operands in `dtype`, float32 out. `dims`: 'nn'
    a b, 'nt' a b^T, 'tn' a^T b."""
    contract = {'nn': ((1,), (0,)), 'nt': ((1,), (1,)),
                'tn': ((0,), (0,))}[dims]
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype),
                           (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _iotas(c, rows=None):
    """Row and column numbers of a [rows, c] array (rows: c). Each made at
    its own shape: Mosaic does not slice an iota."""
    shape = (c if rows is None else rows, c)
    return (lax.broadcasted_iota(jnp.int32, shape, 0),
            lax.broadcasted_iota(jnp.int32, shape, 1))


def _column(x_row, eye):
    """[1, c] -> [c, 1]: the diagonal of the row spread over c rows."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _as_row(x_col, eye):
    """[c, 1] -> [1, c]"""
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _solve(mats):
    """(I + a)^-1 of each strictly lower triangular a [64, 64] float32 of
    the list: the heads of a grid step side by side, step by step, so that
    one head's chain of dependent steps runs between the others'."""
    c = mats[0].shape[0]
    nb, half = c // _BLOCK, _BLOCK // 2
    # the diagonal blocks, each as its 16 rows of the whole width, in two
    # halves of eight rows: the upper is final after its eight columns
    row8, col8 = _iotas(c, half)
    eyes = [[(row8 + (b * _BLOCK + r) == col8).astype(jnp.float32)
             for r in (0, half)] for b in range(nb)]
    xs = [[list(e) for e in eyes] for _ in mats]
    for k in range(_BLOCK - 1):
        for a, x in zip(mats, xs):
            for b in range(nb):
                lo = b * _BLOCK
                up, low = x[b]
                pivot = up[k:k + 1] if k < half else low[k - half:k - half + 1]
                coef = a[lo:lo + _BLOCK, lo + k:lo + k + 1]       # [16, 1]
                if k < half:
                    up = up - coef[:half] * pivot
                x[b] = [up, low - coef[half:] * pivot]
    xs = [jnp.concatenate([r for blk in x for r in blk], axis=0) for x in xs]
    row, col = _iotas(c)
    size = _BLOCK
    while size < c:
        # the blocks one level off the diagonal: odd block row, the even
        # block column before it
        br, bc = row // size, col // size
        off = (br % 2 == 1) & (bc == br - 1)
        ps = [_dot(x, jnp.where(off, a, 0.0), 'nn', jnp.float32)
              for x, a in zip(xs, mats)]
        xs = [x - _dot(p, x, 'nn', jnp.float32) for x, p in zip(xs, ps)]
        size *= 2
    return xs


def _chunks(q_ref, k_ref, v_ref, gb_ref, dtype):
    """What forward and backward both need of a grid step's chunk-heads:
    a dict a value head. q, k [C, Dk] (its key head's), v [C, Dv] in
    `dtype`, gb [2, C] float32 (G, beta)."""
    c = q_ref.shape[2]
    row, col = _iotas(c)
    eye, lower = row == col, row >= col
    rep = v_ref.shape[1] // q_ref.shape[1]    # value heads a key head
    heads = []
    for h in range(v_ref.shape[1]):
        if h % rep == 0:            # a key head's products, once
            q, k = q_ref[0, h // rep], k_ref[0, h // rep]
            kk, qk = _dot(k, k, 'nt', dtype), _dot(q, k, 'nt', dtype)
            qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        g_row = gb_ref[0, h, 0:1, :]
        g_col = _column(g_row, eye)
        beta = _column(gb_ref[0, h, 1:2, :], eye)
        decay = jnp.where(
            lower, jnp.exp(jnp.where(lower, g_col - g_row, 0.0)), 0.0)
        a0 = jnp.where(row > col, kk * decay, 0.0)
        last = jnp.sum(jnp.where(col == c - 1, g_row, 0.0), axis=1,
                       keepdims=True)                 # G_C in every row
        heads.append(dict(
            q=q, k=k, beta=beta, decay=decay, kk=kk, qk=qk, a0=a0,
            e_g=jnp.exp(g_col), e_last=jnp.exp(last - g_col), qf=qf,
            kf=kf, vf=v_ref[0, h].astype(jnp.float32)))
    return heads


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, w_ref, u_ref, qg_ref, kd_ref,
                p_ref, *rest, dtype):
    heads = _chunks(q_ref, k_ref, v_ref, gb_ref, dtype)
    solved = _solve([x['a0'] * x['beta'] for x in heads])
    for h, (x, t) in enumerate(zip(heads, solved)):
        kf = x['kf']
        if rest:                 # the backward's residual
            rest[0][0, h] = t
        u_ref[0, h] = _dot(t, x['vf'] * x['beta'], 'nn', dtype)
        w_ref[0, h] = _dot(t, kf * (x['beta'] * x['e_g']), 'nn',
                           dtype).astype(dtype)
        p_ref[0, h] = (x['qk'] * x['decay']).astype(dtype)
        qg_ref[0, h] = (x['qf'] * x['e_g']).astype(dtype)
        kd_ref[0, h] = (kf * x['e_last']).astype(dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, t_ref, dw_ref, du_ref, dqg_ref,
                dkd_ref, dp_ref, dq_ref, dk_ref, dv_ref, dgb_ref, *, dtype):
    f32 = jnp.float32
    c = q_ref.shape[2]
    row, col = _iotas(c)
    eye, strict, last = row == col, row > col, _iotas(c, 1)[1] == c - 1
    heads = _chunks(q_ref, k_ref, v_ref, gb_ref, dtype)
    # stage by stage over the heads, as `_solve`: the chains of dependent
    # matmuls of one head between the others'
    for h, x in enumerate(heads):
        x['t'] = t_ref[0, h]
        x['g_w'], x['g_u'] = dw_ref[0, h], du_ref[0, h]
        x['scale_k'] = x['beta'] * x['e_g']
        # U = T (beta V), W = T (beta exp(G) K)
        x['d_t'] = _dot(x['g_u'], x['vf'] * x['beta'], 'nt', dtype) \
            + _dot(x['g_w'], x['kf'] * x['scale_k'], 'nt', dtype)
    # T = (I + A)^-1: dA = -T^T dT T^T on the strict lower triangle
    for x in heads:
        x['tt_dt'] = _dot(x['t'], x['d_t'], 'tn', f32)
    for x in heads:
        x['d_a'] = jnp.where(strict, -_dot(x['tt_dt'], x['t'], 'nt', f32),
                             0.0)
    for h, x in enumerate(heads):
        q, k, beta, decay, kk, qk, a0, t, d_a, scale_k = (x[n] for n in (
            'q', 'k', 'beta', 'decay', 'kk', 'qk', 'a0', 't', 'd_a',
            'scale_k'))
        e_g, e_last, qf, kf, vf = (x[n] for n in
                                   ('e_g', 'e_last', 'qf', 'kf', 'vf'))
        g_qg, g_kd, g_p = (r[0, h].astype(f32) for r in (
            dqg_ref, dkd_ref, dp_ref))
        d_vb = _dot(t, x['g_u'], 'tn', dtype)
        d_kb = _dot(t, x['g_w'], 'tn', dtype)
        d_a0 = d_a * beta
        d_kk = d_a0 * decay
        d_qk = g_p * decay
        x['dq'] = _dot(d_qk, k, 'nn', dtype) + g_qg * e_g
        x['dk'] = _dot(d_qk, q, 'tn', dtype) + _dot(d_kk, k, 'nn', dtype) \
            + _dot(d_kk, k, 'tn', dtype) + d_kb * scale_k + g_kd * e_last
        dv_ref[0, h] = (d_vb * beta).astype(dtype)
        kb_sum = jnp.sum(d_kb * kf, axis=1, keepdims=True)
        d_beta = jnp.sum(d_a * a0, axis=1, keepdims=True) \
            + jnp.sum(d_vb * vf, axis=1, keepdims=True) + kb_sum * e_g
        d_eg = kb_sum * beta + jnp.sum(g_qg * qf, axis=1, keepdims=True)
        d_last = jnp.sum(g_kd * kf, axis=1, keepdims=True) * e_last
        d_diff = (d_a0 * kk + g_p * qk) * decay
        d_g = d_eg * e_g - d_last + jnp.sum(d_diff, axis=1, keepdims=True)
        d_g = _as_row(d_g, eye) - jnp.sum(d_diff, axis=0, keepdims=True) \
            + jnp.where(last, jnp.sum(d_last, axis=0, keepdims=True), 0.0)
        dgb_ref[0, h, 0:1, :] = d_g
        dgb_ref[0, h, 1:2, :] = _as_row(d_beta, eye)
    # a key head's gradient: the sum over the value heads it serves
    rep = v_ref.shape[1] // q_ref.shape[1]
    for kh in range(q_ref.shape[1]):
        served = heads[kh * rep:(kh + 1) * rep]
        dq_ref[0, kh] = sum(x['dq'] for x in served).astype(dtype)
        dk_ref[0, kh] = sum(x['dk'] for x in served).astype(dtype)


def _channel_scores(qf, kf, g, dtype):
    """The scores of a head whose decay is a channel's: qf, kf, g [C, Dk]
    float32, g the running sum G. By row block a of 16, with r_a = G at
    the block's middle row: the block's rows times exp(G_i - r_a) against
    every row of its own and the earlier blocks times exp(r_a - G_j) (at
    most 1 before the block; inside it either exponent within 8 x
    |gate_floor| of 0: the rule has bounded that, and
    linear_attention_ops `_intra_channel` says why the middle), keys and
    queries of a block in ONE product. Returns (K K^T and Q K^T [C, C]
    with their decays, the rows' factors, what the backward reads again of
    each block)."""
    c, dk = g.shape
    rowi = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    starts = [g[lo + _MIDDLE:lo + _MIDDLE + 1] for lo in range(0, c, _BLOCK)]
    rows = jnp.exp(g - jnp.concatenate(
        [jnp.broadcast_to(r, (_BLOCK, dk)) for r in starts], axis=0))
    ke, qe = kf * rows, qf * rows
    kk, qk, kept = [], [], []
    for a, start in enumerate(starts):
        lo = a * _BLOCK
        seen = rowi < lo + _BLOCK
        cols = jnp.where(seen, jnp.exp(jnp.where(seen, start - g, 0.0)), 0.0)
        kc = kf * cols
        lhs = jnp.concatenate([ke[lo:lo + _BLOCK], qe[lo:lo + _BLOCK]],
                              axis=0)
        got = _dot(lhs, kc, 'nt', dtype)
        kk.append(got[:_BLOCK])
        qk.append(got[_BLOCK:])
        kept.append((lhs, kc, cols))
    return (jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0), rows,
            kept)


def _unit(x, l2norm, eps, scale=1.0):
    """A chunk-head's rows x [C, Dk] as the op holds them, widened to
    float32 and, where `l2norm`, divided by their norm over Dk,
    x * rsqrt(sum x^2 + eps), then scaled (what `_stage_intra` does in XLA
    on the other paths). Returns (the rows, rsqrt's column [C, 1] or
    None). A padded row is exactly 0 and stays so: 0 * rsqrt(eps); the
    floor under the sum is float32's smallest normal number and moves no
    row that has a norm or an eps."""
    x = x.astype(jnp.float32)
    inv = None
    if l2norm:
        inv = lax.rsqrt(jnp.maximum(
            jnp.sum(x * x, axis=1, keepdims=True) + eps,
            float(jnp.finfo(jnp.float32).tiny)))
        x = x * inv
    return (x if scale == 1.0 else x * scale), inv


def _unit_pull(d, x, inv, scale=1.0):
    """The cotangent d [C, Dk] float32 of `_unit(x, ..)`'s rows pulled back
    to x, from the float32 sum that `_unit` took (inv; None without a
    norm): with n = x inv, inv (d - n sum(d n)) over Dk."""
    if scale != 1.0:
        d = d * scale
    if inv is None:
        return d
    n = x.astype(jnp.float32) * inv
    return inv * (d - n * jnp.sum(d * n, axis=1, keepdims=True))


def _ones_below(c):
    """The lower triangle of ones [C, C] float32, diagonal included: times
    g, the running sum of its rows; transposed times a cotangent, the sum
    from each row to the last."""
    row, col = _iotas(c)
    return (row >= col).astype(jnp.float32)


def _head(ref, h, heads):
    """Where head h of a grid step's `heads` lies in a block
    (1, C, heads x D) of an array the op holds, [B, T, H x D]: whole lane
    tiles of the chunk's rows, so that the slice moves nothing (ssd_scan.py
    takes a head's lanes so)."""
    d = ref.shape[2] // heads
    return (0, slice(None), slice(h * d, (h + 1) * d))


def _channel_chunks(q_ref, k_ref, v_ref, g_ref, beta_ref, dtype, norm,
                    floor):
    """`_chunks` for a decay a channel, from the OP's operands where the op
    holds them: a head's q, k [C, Dk] not normalised, v [C, Dv] and g
    [C, Dk] float32 not summed are the head's lanes of a (1, C, heads x D)
    block (`_head`), beta [1, C]; a key head a value head. `norm`
    (qk_l2norm, eps, q's scale); `floor`: the bound g is held to here
    (None: the caller has held it). q and k are normalised in float32, q
    scaled, both rounded to `dtype` where `_stage_intra` rounds them on
    the other paths and widened again;
    G is the running sum of g's rows as a product with the
    triangle of ones at float32 precision (the MXU takes g in three bf16
    pieces, the ones are exact and the accumulator is float32: each sum
    is rounded about once, where a chain of 64 adds rounds 63 times)."""
    l2norm, eps, scale = norm
    c, n = q_ref.shape[1], beta_ref.shape[1]
    row, col = _iotas(c)
    eye = row == col
    ones = _ones_below(c)
    heads = []
    for h in range(n):
        qn, q_inv = _unit(q_ref[_head(q_ref, h, n)], l2norm, eps, scale)
        kn, k_inv = _unit(k_ref[_head(k_ref, h, n)], l2norm, eps)
        qf, kf = (x.astype(dtype).astype(jnp.float32) for x in (qn, kn))
        g = g_ref[_head(g_ref, h, n)]
        if floor is not None:       # held to its floor as it is loaded
            g = jnp.where(g < floor, floor, g)
        g = _dot(ones, g, 'nn', jnp.float32)                  # G
        kk, qk, rows, kept = _channel_scores(qf, kf, g, dtype)
        heads.append(dict(
            qf=qf, kf=kf,
            vf=v_ref[_head(v_ref, h, n)].astype(jnp.float32), g=g, kk=kk,
            qk=qk, rows=rows, kept=kept, a0=jnp.where(row > col, kk, 0.0),
            beta=_column(beta_ref[0, h], eye), e_g=jnp.exp(g),
            e_last=jnp.exp(g[c - 1:c] - g), q_inv=q_inv, k_inv=k_inv))
    return heads


def _fwd_kernel_channel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref,
                        qg_ref, kd_ref, p_ref, last_ref, *rest, dtype, norm,
                        floor):
    c = q_ref.shape[1]
    row, col = _iotas(c)
    heads = _channel_chunks(q_ref, k_ref, v_ref, g_ref, beta_ref, dtype,
                            norm, floor)
    solved = _solve([x['a0'] * x['beta'] for x in heads])
    for h, (x, t) in enumerate(zip(heads, solved)):
        kf = x['kf']
        if rest:                 # the backward's residual
            rest[0][0, h] = t
        u_ref[0, h] = _dot(t, x['vf'] * x['beta'], 'nn', dtype)
        w_ref[0, h] = _dot(t, kf * (x['beta'] * x['e_g']), 'nn',
                           dtype).astype(dtype)
        # a token's own pair decays by exp(0): q . k as it is
        own = jnp.sum(x['qf'] * kf, axis=1, keepdims=True)
        p_ref[0, h] = (jnp.where(row > col, x['qk'], 0.0)
                       + jnp.where(row == col, own, 0.0)).astype(dtype)
        qg_ref[0, h] = (x['qf'] * x['e_g']).astype(dtype)
        kd_ref[0, h] = (kf * x['e_last']).astype(dtype)
        last_ref[0, h] = x['g'][c - 1:c]       # G_C: the chunk's decay, log


def _bwd_kernel_channel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, dw_ref,
                        du_ref, dqg_ref, dkd_ref, dp_ref, dlast_ref, dq_ref,
                        dk_ref, dv_ref, dg_ref, dbeta_ref, *, dtype, norm,
                        floor):
    f32 = jnp.float32
    c, n = q_ref.shape[1], beta_ref.shape[1]
    dk = q_ref.shape[2] // n
    row, col = _iotas(c)
    eye, strict = row == col, row > col
    rowi = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    ones = _ones_below(c)
    heads = _channel_chunks(q_ref, k_ref, v_ref, g_ref, beta_ref, dtype,
                            norm, floor)
    for h, x in enumerate(heads):
        x['t'] = t_ref[0, h]
        x['g_w'], x['g_u'] = dw_ref[0, h], du_ref[0, h]
        x['scale_k'] = x['beta'] * x['e_g']
        # U = T (beta V), W = T (beta exp(G) K)
        x['d_t'] = _dot(x['g_u'], x['vf'] * x['beta'], 'nt', dtype) \
            + _dot(x['g_w'], x['kf'] * x['scale_k'], 'nt', dtype)
    # T = (I + A)^-1: dA = -T^T dT T^T on the strict lower triangle
    for x in heads:
        x['tt_dt'] = _dot(x['t'], x['d_t'], 'tn', f32)
    for x in heads:
        x['d_a'] = jnp.where(strict, -_dot(x['tt_dt'], x['t'], 'nt', f32),
                             0.0)
    for h, x in enumerate(heads):
        beta, t, d_a, scale_k, e_g, e_last, qf, kf, vf, rows = (
            x[n] for n in ('beta', 't', 'd_a', 'scale_k', 'e_g', 'e_last',
                           'qf', 'kf', 'vf', 'rows'))
        g_qg, g_kd, g_p = (r[0, h].astype(f32) for r in (
            dqg_ref, dkd_ref, dp_ref))
        d_vb = _dot(t, x['g_u'], 'tn', dtype)
        d_kb = _dot(t, x['g_w'], 'tn', dtype)
        dv_ref[_head(dv_ref, h, n)] = (d_vb * beta).astype(dtype)
        d_beta = jnp.sum(d_a * x['a0'], axis=1, keepdims=True) \
            + jnp.sum(d_vb * vf, axis=1, keepdims=True) \
            + jnp.sum(d_kb * kf * e_g, axis=1, keepdims=True)
        dbeta_ref[0, h] = _as_row(d_beta, eye)
        # the scores' cotangents, keys over queries as the forward stacks
        # a block's rows
        d_kk = d_a * beta
        d_qk = jnp.where(strict, g_p, 0.0)
        d_own = jnp.sum(jnp.where(eye, g_p, 0.0), axis=1, keepdims=True)
        to_last = g_kd * kf * e_last
        dq = g_qg * e_g + d_own * kf
        dkey = d_kb * scale_k + g_kd * e_last + d_own * qf
        # G's cotangent: through exp(G), exp(G_C - G) and G_C itself (the
        # chunk's decay, whose cotangent is an operand), and below through
        # each block's two factors and its reference r_a
        d_g = (d_kb * kf * beta + g_qg * qf) * e_g - to_last \
            + jnp.where(rowi == c - 1, dlast_ref[0, h]
                        + jnp.sum(to_last, axis=0, keepdims=True), 0.0)
        d_lhs, d_starts = [], []
        for a, (lhs, kc, cols) in enumerate(x['kept']):
            lo = a * _BLOCK
            d_got = jnp.concatenate([d_kk[lo:lo + _BLOCK],
                                     d_qk[lo:lo + _BLOCK]], axis=0)
            d_lhs.append(_dot(d_got, kc, 'nn', dtype))       # [32, Dk]
            d_kc = _dot(d_got, lhs, 'tn', dtype)             # [C, Dk]
            dkey = dkey + d_kc * cols
            d_cols = d_kc * kf * cols
            d_g = d_g - d_cols
            d_starts.append(jnp.sum(d_cols, axis=0, keepdims=True))
        d_ke = jnp.concatenate([d[:_BLOCK] for d in d_lhs], axis=0)
        d_qe = jnp.concatenate([d[_BLOCK:] for d in d_lhs], axis=0)
        d_rows = (d_qe * qf + d_ke * kf) * rows
        d_g = d_g + d_rows
        for a, d_start in enumerate(d_starts):  # r_a: G's row in the middle
            lo = a * _BLOCK
            d_start = d_start - jnp.sum(
                d_rows[lo:lo + _BLOCK], axis=0, keepdims=True)
            d_g = d_g + jnp.where(rowi == lo + _MIDDLE, d_start, 0.0)
        # g's: the sum of G's from each row to the chunk's last; none
        # where the raw g lay UNDER its floor (one AT it keeps the whole)
        d_g = _dot(ones, d_g, 'tn', f32)
        if floor is not None:
            d_g = jnp.where(g_ref[_head(g_ref, h, n)] < floor, 0.0, d_g)
        dg_ref[_head(dg_ref, h, n)] = d_g
        # q's and k's as the op holds them and where: through the scale and
        # the norm in float32 (the rounding to `dtype` passes a cotangent
        # as it is)
        at = _head(q_ref, h, n)
        dq_ref[at] = _unit_pull(dq + d_qe * rows, q_ref[at], x['q_inv'],
                                norm[2]).astype(dq_ref.dtype)
        dk_ref[at] = _unit_pull(dkey + d_ke * rows, k_ref[at],
                                x['k_inv']).astype(dk_ref.dtype)


def _heads(hv, rep, dtype):
    """Value heads a grid step: the largest divisor of hv within HEADS
    (half of it at 4-byte operands, whose blocks are twice the bytes)
    that holds whole groups of the `rep` value heads a key head serves."""
    n = max(rep, HEADS * 2 // jnp.dtype(dtype).itemsize)
    while hv % n or n % rep:
        n -= 1
    return n


def _call(kernel, ins, outs, heads, interpret):
    """One grid step a row of the chunks and `heads` value heads; of the
    arrays over key heads (q, k and their gradients) the heads those take
    their keys from."""
    rows, hv = ins[2].shape[:2]

    def specs(shapes):
        return [pl.BlockSpec(
            (1, heads * s[1] // hv) + s[2:],
            lambda i, j, n=len(s): (i, j) + (0,) * (n - 2)) for s in shapes]

    return pl.pallas_call(
        kernel, grid=(rows, hv // heads),
        in_specs=specs([a.shape for a in ins]),
        out_specs=specs([o.shape for o in outs]),
        out_shape=outs, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')))(*ins)


def _call_channel(kernel, ins, outs, heads, interpret):
    """One grid step a row, a chunk and `heads` heads, for the per-channel
    kernels. An array of rank 3 is the OP's, [B, T, H x D], T whole chunks:
    its block is the chunk's tokens by the step's heads' lanes, which the
    index map cuts out where they lie (the DMA's strided read or write;
    no chunked copy is made of it). One of rank 4 is the scan's or beta's,
    [N x B, H, ., .], chunk n of row b at n x B + b."""
    bsz = ins[0].shape[0]
    rows, h, _, c = ins[4].shape                # beta [N x B, H, 1, C]

    def specs(shapes):
        return [pl.BlockSpec((1, c, heads * s[2] // h),
                             lambda b, n, j: (b, n, j)) if len(s) == 3 else
                pl.BlockSpec((1, heads) + s[2:],
                             lambda b, n, j: (n * bsz + b, j, 0, 0))
                for s in shapes]

    return pl.pallas_call(
        kernel, grid=(bsz, rows // bsz, h // heads),
        in_specs=specs([a.shape for a in ins]),
        out_specs=specs([o.shape for o in outs]),
        out_shape=outs, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel')))(*ins)


def _flat(x):
    """[N, B, H, ...] -> [N x B, H, ...]"""
    return x.reshape((-1,) + x.shape[2:])


# Both calls are jitted functions of their own: an op is traced several
# times a Program (custom_vjp traces the primal and its forward rule, the
# backward runs the forward again) and a model has several such ops, and a
# kernel body is thousands of operations to trace and to lower. jit keeps
# one trace a shape and emits one function a module, called from each
# place under that place's scopes.
@functools.partial(jax.jit, static_argnames=('interpret', 'heads', 'solved'))
def _forward(q, k, v, gb, *, interpret, heads, solved):
    """The forward kernel; `solved` adds T [rows, H, C, C] float32 to what
    it writes (what the backward keeps of the chunk it computed again)."""
    dtype = v.dtype
    like = jax.ShapeDtypeStruct
    keys = v.shape[:3] + q.shape[3:]              # a row a value head
    scores = v.shape[:3] + (v.shape[2],)
    outs = [like(keys, dtype), like(v.shape, jnp.float32),
            like(keys, dtype), like(keys, dtype), like(scores, dtype)]
    if solved:
        outs.append(like(scores, jnp.float32))
    return tuple(_call(functools.partial(_fwd_kernel, dtype=dtype),
                       (q, k, v, gb), outs, heads, interpret))


@functools.partial(jax.jit, static_argnames=('interpret', 'heads'))
def _backward(res, cts, *, interpret, heads):
    outs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in res[:4]]
    return tuple(_call(functools.partial(_bwd_kernel, dtype=res[2].dtype),
                       tuple(res) + tuple(cts), outs, heads, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _intra(q, k, v, gb, interpret, heads):
    return _forward(q, k, v, gb, interpret=interpret, heads=heads,
                    solved=False)


def _intra_fwd(q, k, v, gb, interpret, heads):
    outs = _forward(q, k, v, gb, interpret=interpret, heads=heads,
                    solved=True)
    return outs[:5], (q, k, v, gb, outs[5])


def _intra_bwd(interpret, heads, res, g):
    return _backward(res, g, interpret=interpret, heads=heads)


_intra.defvjp(_intra_fwd, _intra_bwd)


@functools.partial(jax.jit, static_argnames=('interpret', 'heads', 'solved',
                                             'norm', 'floor'))
def _forward_channel(q, k, v, g, beta, *, interpret, heads, solved, norm,
                     floor):
    """`_forward` for a decay a channel, from the op's operands where the
    op holds them: q, k [B, T, H x Dk] and v [B, T, H x Dv] as they are, g
    [B, T, H x Dk] float32 not summed, T whole chunks, beta
    [rows, H, 1, C] float32, rows = N x B. Beside W, U, Qg, Kd, P (and T),
    [rows, H, C, .] for the scan, it writes G's last row [rows, H, 1, Dk]
    float32, the log of the chunk's decay."""
    dtype = v.dtype
    like = jax.ShapeDtypeStruct
    rows, h, _, c = beta.shape
    keys, scores = (rows, h, c, q.shape[2] // h), (rows, h, c, c)
    outs = [like(keys, dtype), like((rows, h, c, v.shape[2] // h),
                                    jnp.float32),
            like(keys, dtype), like(keys, dtype), like(scores, dtype),
            like((rows, h, 1, keys[3]), jnp.float32)]
    if solved:
        outs.append(like(scores, jnp.float32))
    return tuple(_call_channel(
        functools.partial(_fwd_kernel_channel, dtype=dtype, norm=norm,
                          floor=floor),
        (q, k, v, g, beta), outs, heads, interpret))


@functools.partial(jax.jit, static_argnames=('interpret', 'heads', 'norm',
                                             'floor'))
def _backward_channel(res, cts, *, interpret, heads, norm, floor):
    outs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in res[:5]]
    return tuple(_call_channel(
        functools.partial(_bwd_kernel_channel, dtype=res[2].dtype,
                          norm=norm, floor=floor),
        tuple(res) + tuple(cts), outs, heads, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _intra_channel(q, k, v, g, beta, interpret, heads, norm, floor):
    return _forward_channel(q, k, v, g, beta, interpret=interpret,
                            heads=heads, solved=False, norm=norm, floor=floor)


def _intra_channel_fwd(q, k, v, g, beta, interpret, heads, norm, floor):
    outs = _forward_channel(q, k, v, g, beta, interpret=interpret,
                            heads=heads, solved=True, norm=norm, floor=floor)
    return outs[:6], (q, k, v, g, beta, outs[6])


def _intra_channel_bwd(interpret, heads, norm, floor, res, cts):
    return _backward_channel(res, cts, interpret=interpret, heads=heads,
                             norm=norm, floor=floor)


_intra_channel.defvjp(_intra_channel_fwd, _intra_channel_bwd)


def gated_delta_intra(q, k, v, g_sum, beta, interpret, heads=None):
    """q, k [N, B, Hk, C, Dk] (normalised, q scaled), v [N, B, Hv, C, Dv]
    in the matmuls' dtype, g_sum, beta [N, B, Hv, C] float32, g_sum the
    running sum of g inside each chunk. Hk divides Hv and key head h
    serves the value heads h * Hv / Hk and following: a grid step reads a
    key head once for the value heads it serves, and the backward adds
    their gradients up in VMEM. Returns (W, U, Qg, Kd, P, decay of the
    chunk) a VALUE head, as `_intra` of linear_attention_ops does on
    repeated key heads, W, Qg, Kd, P in the matmuls' dtype and U in
    float32. Differentiable in all five. `heads` overrides the value
    heads a grid step takes (the sweep's door: a multiple of Hv / Hk that
    divides Hv). A decay a channel is `gated_delta_intra_tokens`."""
    lead, hv = q.shape[:2], v.shape[2]
    heads = heads or _heads(hv, hv // q.shape[2], v.dtype)
    gb = jnp.stack([g_sum, beta], axis=-2).astype(jnp.float32)
    outs = _intra(_flat(q), _flat(k), _flat(v), _flat(gb), interpret, heads)
    return tuple(o.reshape(lead + o.shape[1:]) for o in outs) \
        + (jnp.exp(g_sum[..., -1]),)


def gated_delta_intra_tokens(q, k, v, g, beta, interpret, heads=None,
                             norm=None, floor=None):
    """The stage with a decay a CHANNEL, from the OP's operands where the
    op holds them: q, k [B, T, H, Dk] NOT normalised, v [B, T, H, Dv] in
    the matmuls' dtype, g [B, T, H, Dk] NOT summed, beta [B, T, H]; a key
    head a value head. `norm` (qk_l2norm, eps, q's scale; None: no norm, a
    scale of 1) says what the kernels do to q and k in VMEM before they
    round them to the matmuls' dtype; `floor` the bound the kernels hold g
    to as they load it (None: the caller has), handing back no gradient
    where the raw g lay under it and the whole of it AT it. The kernels'
    index maps cut the chunks of 64 out of the [B, T, H x D] views, so a T
    of whole chunks is read in place and the four large cotangents are
    written in place; any other T is padded first (tokens that change
    nothing: zeros), one copy an array. Only beta ([B, T, H] float32) is
    laid out by chunk in XLA. Returns (W, U, Qg, Kd, P, decay of the
    chunk [N, B, H, Dk]) as `gated_delta_intra`, [N, B, H, C, .] for the
    scan; differentiable in the five raw operands."""
    bsz, t, h = beta.shape
    pad = -t % _CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // _CHUNK
    l2norm, eps, scale = norm or (False, 0.0, 1.0)
    outs = _intra_channel(
        *(x.reshape(bsz, t + pad, -1)
          for x in (q, k, v, g.astype(jnp.float32))),
        beta.astype(jnp.float32).reshape(bsz, n, _CHUNK, h).transpose(
            1, 0, 3, 2).reshape(n * bsz, h, 1, _CHUNK),
        interpret, heads or _heads(h, 1, v.dtype),
        (bool(l2norm), float(eps), float(scale)),
        None if floor is None else float(floor))
    outs = tuple(o.reshape((n, bsz) + o.shape[1:]) for o in outs)
    return outs[:5] + (jnp.exp(outs[5][..., 0, :]),)
