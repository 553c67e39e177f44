"""Token mixers with a recurrent state: the gated delta rule, Mamba-2's
selective state-space scan, the depthwise causal convolution that feeds
either and the gated norm after them.

TPU-first extension (no reference counterpart: the reference's recurrent
layers, operators/lstm_op.cc and gru_op.cc, carry a vector a step; this
carries a matrix a head). `gated_delta_rule` is Gated DeltaNet's token
mixer (Yang, Kautz and Hatamizadeh 2024, arXiv:2412.06464; the chunked
form of Yang et al. 2024, arXiv:2406.06484). Per head, with S a
[Dk, Dv] float32 state, S_0 = 0, for t = 1..T:

    S   = exp(g_t) S                                 g_t <= 0: the decay
    S   = S + k_t (beta_t (v_t - S^T k_t))^T         the delta rule
    o_t = S^T q_t

ONE implementation, whatever the platform: the chunked (WY) form. A row of
T tokens is cut into chunks of C (`chunk_size`, padded with tokens that
change nothing: k = 0, beta = 0, g = 0). With G the running sum of g
inside a chunk and D_ij = exp(G_i - G_j) for i >= j, 0 above the diagonal
(only such differences are ever exponentiated, so nothing overflows):

  stage `gdn_intra`, every chunk at once (matmuls and one C x C solve):
    A   = strict_lower(diag(beta) (K K^T) * D)
    T   = (I + A)^-1                      unit lower triangular, float32
    U   = T diag(beta) V                  what each token writes, before
    W   = T diag(beta) diag(exp G) K      the state it reads is known
    P   = lower((Q K^T) * D)              the chunk's own attention
  stage `gdn_scan`, a lax.scan over the chunks carrying S in float32:
    Vn  = U - W S                         the values written
    O   = diag(exp G) Q S + P Vn
    S   = exp(G_C) S + (diag(exp(G_C - G)) K)^T Vn

A DECAY A CHANNEL (Kimi Delta Attention, arXiv:2510.26692): g [B, T, H,
Dk] in place of [B, T, H] (the rank of G says which; no attribute
chooses), S = diag(exp(g_t)) S: each ROW of the state at its own rate. G is
then [C, Dk] and D no longer factors out of K K^T and Q K^T:

    A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)        i > j
    P_ij = sum_d q_id k_jd exp(G_id - G_jd)      i > j;  P_ii = q_i . k_i
    T = (I + A)^-1,  U = T diag(beta) V,  W = T diag(beta) (K * exp G)
    Vn = U - W S,  O = (Q * exp G) S + P Vn
    S  = diag(exp G_C) S + (K * exp(G_C - G))^T Vn        exp G_C a [Dk] vector

A and P are formed by row blocks of 16 as matmuls of K_i * exp(G_i - r_a)
with K_j * exp(r_a - G_j), r_a the running sum at the block's MIDDLE row:
for j before the block the second exponent is <= 0, inside the block both
lie within 8 x |gate_floor| of 0. That bound is the op's attribute
`gate_floor` (the layer's producer keeps g >= gate_floor: -5 for
-5 * sigmoid(.)): the rule holds g to it (a g AT the floor keeps its whole
gradient) and refuses a floor whose half block passes exp(44), so that a
factor and the operand it scales stay normal float32 numbers both ways
(referred to the block's edge, exp(-80) times a small cotangent is flushed
to zero and its partner exp(80) makes the loss all of g's gradient). A
token's own pair (the diagonal of P) decays by exp(0) and is q . k as it
is, outside the product: inside it, its two cotangents to G_i cancel only
to the matmuls' rounding, which under bf16 is more than the whole of g's
gradient where the decay is strong. What is still exponentiated from
running sums is rounded as they are: at g near -5 G reaches -320 a chunk
and a factor is off by 3e-5, which is what a float32 comparison reads on
the decay's own parameters. The per-channel form has its own composition
(`_intra_channel`), its own `gdn_intra` kernels (a key head a value head),
which read the op's OWN operands and take q and k's l2 norms, q's scale,
the rounding to the matmuls' dtype, g's floor and G's running sum in
VMEM, forward and backward, so that XLA prepares nothing for them and
finishes no cotangent behind them (ISSUE 56), WHERE THE OP HOLDS THEM
(ISSUE 58): q, k, v and g viewed [B, T, H x D], which is how the layer's
projections leave them, the chunks cut by the kernels' index maps and the
four large cotangents written the same way, so that neither `_to_chunks`
nor `_from_chunks` runs on that path and the backward keeps q, k, v and g
in that view (a grid step of 8 heads in bf16 asks 6.50 MiB of scoped VMEM
forward and 9.79 backward, 4.58 and 9.66 at 4 heads in float32, where the
per-head kernel asks 4.62 and 6.76: compiled for a described v5e, PR 58)
and the same `gdn_scan` kernels, which scale S's rows by the chunk's [Dk] decay (a
head's row of lanes turned to a column in VMEM) where they multiply by a
scalar (2.52, 2.74 and 5.74 MiB for the three walks in bf16, 12.99 the
reverse walk in float32): all within Mosaic's default of 16 MiB, no call
states a limit. The per-head path is what it was.

The backward is the op's own (`jax.custom_vjp`) and keeps the op's inputs
alone: no state a token, and not even a state a chunk (S at the starts of
128 chunks is [128, B, 32, 128, 128] float32, 256 MiB a layer at B = 1,
which the cell of 8192 tokens has no room for beside three layers'
activations). It recomputes stage `gdn_intra` for all chunks at once,
scans the chunks forward again for S at each chunk's start, walks them
backwards with the transposed step (jax.vjp of the same step function
that the forward scans, so the two cannot drift) and pulls the chunks'
cotangents back through the recomputed stage (the l2 norm of q and k and
the repeat of the key heads included; with a decay a channel on the TPU
the norm's and the running sum's pull-backs are the backward kernel's).

On the TPU, for a chunk of 64 and heads of whole lane tiles (`usable` of
ops/kernels/gated_delta_intra.py), stage `gdn_intra` is a Pallas kernel
forward and one backward that keep everything C x C of a chunk in VMEM:
the same arithmetic in the same precisions, W, Qg, Kd and P handed over in
the matmuls' dtype (what the scan's matmuls round them to anyway), the
backward's recomputed and transposed passes one grid step. Every other
platform and shape takes `_intra` below, the composition the kernel is
tested against. Stage `gdn_scan` reads there what that kernel hands over
and is three calls of two Pallas kernels (`usable` of
ops/kernels/gated_delta_scan.py: the chunks the grid's last, sequential
axis, S a float32 VMEM scratch): the forward walk for O, in the backward
the forward walk again for S at each chunk's start (a temporary of the
backward, never a residual) and the reverse walk with dS in the scratch,
the transposition of `_chunk_step`. Every other platform and shape takes
the `lax.scan`s of `_chunk_step` below, which the kernels are tested
against. The rule chooses on what it can see (platform, shapes, dtype);
nothing else selects it.

Under AMP the rule is one of the MXU's: q, k, v are cast to bf16
(`lowering.amp_cast`; they are what the backward keeps) and every matmul
of the two stages takes bf16 operands with float32 accumulation; the
norm of q and k, g, beta, the decays, the solve and the state are
float32. The output is float32.

`ssd_scan` is Mamba-2's selective state-space scan (SSD: Dao and Gu 2024,
arXiv:2405.21060). Per head h of group g = h // (H / G), with S a [P, N]
float32 state, S_0 = 0, for t = 1..T:

    S   = exp(dt_t A_h) S + dt_t x_t B_t^T        A_h < 0, dt_t > 0
    y_t = S C_t + D_h x_t                         B_t, C_t the group's

ONE implementation, whatever the platform: the chunked form. A row is cut
into chunks of C (`chunk_size`, padded with tokens of dt = 0, which decay
nothing and write nothing). With L the running sum of dt A inside a chunk
(only differences L_i - L_j, i >= j, and L_C - L_j are exponentiated:
every factor is at most 1):

  stage `ssd_intra`, every chunk at once:
    M_ij = (C_i . B_j) exp(L_i - L_j) dt_j for i >= j, 0 above   [C, C]
    Y    = M X                                the chunk's own tokens
    dS   = (diag(exp(L_C - L) dt) X)^T B      what the chunk writes
  stage `ssd_scan`, a lax.scan over the chunks carrying S in float32:
    S_start of the next chunk = exp(L_C) S_start + dS
  stage `ssd_inter`, every chunk at once:
    Y   += diag(exp L) C S_start              what the earlier chunks left

C . B is a group's and is computed once for its H / G heads; the decay is
a head's. The backward is the op's own (`jax.custom_vjp`) and keeps the
op's inputs alone: it runs the three stages again behind one
`optimization_barrier` and transposes them (jax.vjp of the function the
forward runs, so the two cannot drift); S at the chunks' starts (a
[T / C, B, H, P, N] float32 array) lives only inside a pass. Under AMP
the rule is one of the MXU's: x, B, C are cast to bf16 (they are what the
backward keeps) and the matmuls of `ssd_intra` and `ssd_inter` take bf16
operands (M, the weighted X and S_start rounded once) with float32
accumulation; dt, A, D, every decay and the carried state are float32.
The output is float32.
On the TPU, for chunks of 128 or 256 (the op's `chunk_size`, handed to the
kernels as their static argument), heads of 64 or 128 and states of whole
lane tiles (`usable` of ops/kernels/ssd_scan.py), the whole scan is one Pallas
kernel forward and one backward: the chunks are the grid's last,
sequential axis, S a float32 VMEM scratch, nothing [C, C] reaches HBM,
and the backward reads S_start of each chunk, which the rule's forward
kept ([T / C, B, H, P, N] float32, alive from a block's recomputation to
its backward), so it runs no stage again. The same arithmetic in the same
precisions; every other platform and shape takes `_ssd_stages` below, the
composition the kernels are tested against. The rule chooses as it does
for the delta rule's stage.

`causal_conv1d`: y[b, t, c] = act(sum_j w[j, c] x[b, t - (K - 1) + j, c]
+ bias[c]), x = 0 before the row's first token; depthwise (a filter a
channel), the bias optional (input `Bias`). Float32 elementwise work (K
shifted multiply-adds), never the MXU. Two more optional inputs of x's
shape make it LFM2's double-gated short convolution,
y = OutGate * conv(InGate * x): each product is taken in float32 and
rounded to x's dtype, around the convolution (the rule's own
multiplications, under the op's scope; the kernels are what they were).
Under AMP it reads its input rounded to bf16 and gives its result in
bf16, as attention does: the input is what its backward keeps, and a
[B, T, C] float32 array twice a layer is what the cell cannot hold.
On the TPU, for whole lane tiles of channels and whole tiles of tokens
(`usable` of ops/kernels/causal_conv1d.py), it is one Pallas kernel
forward and one backward that shift a tile's rows in VMEM and move each
array once: `_conv`'s arithmetic in its order and precisions, the
backward's recomputed sum, dx and dw one grid step. Every other platform
and shape takes `_conv` below (a padded float32 copy and K slices of it,
which on the TPU are K misaligned copies through HBM), the composition
the kernel is tested against. The rule chooses as it does for the delta
rule's stage.

`gated_rms_norm`: y = w * x * rsqrt(mean(x^2) + eps) * silu(gate) over the
last axis, statistics in float32; its backward keeps x, the gate (bf16
under AMP) and w and computes the rest again. `gate_act` `sigmoid` puts
sigmoid(gate) where silu(gate) stands (Kimi Delta Attention's output
gate), in the composition and in both kernels. Two attributes give
Mamba-2's form: `norm_before_gate` false gates FIRST,
y = w * rmsnorm(x * silu(gate)), and `groups` G takes the mean over each
of G equal parts of the last axis by itself.
On the TPU, for groups of whole lane tiles and rows that merge without a
copy (`usable` of ops/kernels/gated_norm.py), it is one Pallas kernel
forward and one backward whose block holds a group's columns: the mean is
taken in VMEM along the lanes, each pass moves its arrays once, the
backward gives dx, dgate and dw from one read and computes `inv` again in
VMEM, so it runs no forward again and holds nothing behind a barrier. A
float32 x [.., heads, 128] with one group is read BY HEAD, where its
producer's heads left it, beside the gate and the result as the matmuls
round the op hold them, so nothing moves to meet the kernel's view. The
same arithmetic in the same precisions, the result in x's dtype. Every
other platform and shape takes `_gated_norm` below (with G > 1 a reshape
to [.., G, width], which on the TPU puts the groups where the tiles keep
eight rows and moves the array round the sum), the composition the kernel
is tested against. The rule chooses as it does for the delta rule's stage.

Trace-time counters: `gdn.lowered{chunk=, gate=head|channel}` once per op
per trace,
`gdn.intra{way=kernel|composed}` and `gdn.scan{way=kernel|composed}`
beside it (which way each stage went), `gdn.prologue{where=kernel|xla}`
(where q and k's norm and g's running sum are taken: in the per-channel
kernels, or by XLA on every other path), `gdn.chunks{where=kernel|xla}`
(who cuts q, k, v and g into chunks and joins their cotangents: the
per-channel kernels' index maps on the arrays the op holds, or
`_to_chunks` and `_from_chunks`, transposing copies of XLA's, on every
other path), `gdn.tokens` the B x T of the traced shape,
`ssd.lowered{chunk=, heads=, groups=}` and `ssd.tokens` likewise and
`ssd.way{way=kernel|composed}` beside them,
`conv1d.lowered{taps=K, act=silu|none}` (and the labels `bias=true` and
`gates=1|2` where the op has them; `obs.REGISTRY.total('conv1d.lowered')`
is every form's), `shortconv.tokens` the B x T of an op with both gates
and `conv1d.way{way=kernel|composed}` beside it,
`gated_rms_norm.lowered` and `gated_rms_norm.way{way=kernel|composed}`
beside it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ... import obs
from ...ops.kernels import causal_conv1d as conv_kernel
from ...ops.kernels import gated_delta_intra as intra_kernel
from ...ops.kernels import gated_delta_scan as delta_scan
from ...ops.kernels import gated_norm as norm_kernel
from ...ops.kernels import ssd_scan as ssd_kernel
from ..lowering import register, data_of, amp_cast

_SOLVE_BLOCK = 16
_HIGHEST = lax.Precision.HIGHEST


def _mm(spec, a, b, dtype):
    """einsum on the MXU: operands in `dtype`, float32 accumulation."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _forward_substitution(a):
    """(I + a)^-1 for strictly lower triangular a [..., n, n], row by row:
    row i of the inverse is e_i - a[i, :] X with the rows above it known.
    n unrolled steps of elementwise work; backward stable."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    x = jnp.zeros_like(a)
    for i in range(n):
        row = eye[i] - jnp.sum(a[..., i, :, None] * x, axis=-2)
        x = x.at[..., i, :].set(row)
    return x


def _inverse(a):
    """(I + a)^-1 for strictly lower triangular a [..., C, C] in float32.
    Where C is 16 times a power of two: the diagonal blocks of 16 by
    forward substitution, then neighbouring blocks merged,
        [[L1, 0], [M, L2]]^-1 = [[X1, 0], [-X2 M X1, X2]],
    up to the whole chunk (two small matmuls a merge, at full precision).
    Any other C: forward substitution over the whole chunk."""
    c = a.shape[-1]
    nb = c // _SOLVE_BLOCK
    if c % _SOLVE_BLOCK or nb & (nb - 1) or nb < 2:
        return _forward_substitution(a)

    def block(i, j, size):
        return a[..., i * size:(i + 1) * size, j * size:(j + 1) * size]

    size = _SOLVE_BLOCK
    x = _forward_substitution(
        jnp.stack([block(i, i, size) for i in range(nb)], axis=-3))
    while size < c:
        x1, x2 = x[..., 0::2, :, :], x[..., 1::2, :, :]
        m = jnp.stack([block(2 * j + 1, 2 * j, size)
                       for j in range(x1.shape[-3])], axis=-3)
        low = -jnp.matmul(jnp.matmul(x2, m, precision=_HIGHEST), x1,
                          precision=_HIGHEST)
        x = jnp.concatenate(
            [jnp.concatenate([x1, jnp.zeros_like(x1)], axis=-1),
             jnp.concatenate([low, x2], axis=-1)], axis=-2)
        size *= 2
    return x[..., 0, :, :]


@jax.custom_vjp
def _unit_lower_inverse(a):
    return _inverse(a)


def _unit_lower_inverse_fwd(a):
    x = _inverse(a)
    return x, x


def _unit_lower_inverse_bwd(x, g):
    # d(L^-1) = -L^-1 dL L^-1, and only the strict lower part of a is free
    xt = jnp.swapaxes(x, -1, -2)
    d = -jnp.matmul(jnp.matmul(xt, g, precision=_HIGHEST), xt,
                    precision=_HIGHEST)
    return (jnp.tril(d, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _intra(q, k, v, g, beta):
    """Stage `gdn_intra`. q, k [N, B, H, C, Dk], v [N, B, H, C, Dv] in the
    matmuls' dtype, g, beta [N, B, H, C] float32, N chunks. Returns what
    the scan reads of each chunk: (W, U, Qg, Kd, P, decay of the chunk)."""
    dtype = v.dtype
    c = q.shape[-2]
    gc = jnp.cumsum(g, axis=-1)                              # G
    diff = gc[..., :, None] - gc[..., None, :]
    lower = np.tril(np.ones((c, c), bool))
    strict = np.tril(lower, -1)
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))        # D, 0 above
    kk = _mm('...ik,...jk->...ij', k, k, dtype)
    a = jnp.where(strict, kk * decay, 0.0) * beta[..., :, None]
    t = _unit_lower_inverse(a)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    u = _mm('...ij,...jd->...id', t, vf * beta[..., None], dtype)
    w = _mm('...ij,...jd->...id', t,
            kf * (beta * jnp.exp(gc))[..., None], dtype)
    p = _mm('...ik,...jk->...ij', q, k, dtype) * decay
    qg = q.astype(jnp.float32) * jnp.exp(gc)[..., None]
    last = gc[..., -1:]
    kd = kf * jnp.exp(last - gc)[..., None]
    return w, u, qg, kd, p, jnp.exp(last[..., 0])


def _channel_block(c):
    """Rows of a chunk of `c` whose decays by channel share one reference:
    the solve's block where the chunk is whole blocks, else the chunk's
    largest divisor within one."""
    return max(b for b in range(1, _SOLVE_BLOCK + 1) if c % b == 0)


def _intra_channel(q, k, v, g, beta):
    """Stage `gdn_intra` with a decay a CHANNEL: g [N, B, H, C, Dk]
    float32, the rest as `_intra`. The chunk's decay is [N, B, H, Dk]. The
    scores by row block a (`_channel_block` rows) with r_a the running sum
    G at the block's MIDDLE row: rows times exp(G_i - r_a), columns of the
    blocks up to a times exp(r_a - G_j) (at most 1 before the block), one
    matmul a block. Inside the block either exponent is within half a
    block x |gate_floor| of 0 (40 here: the rule has bounded it), so that
    a factor and what it multiplies stay normal float32 numbers both ways:
    with the reference at the block's edge a factor of exp(-80) times a
    small cotangent is flushed to zero, and its partner exp(80) makes that
    loss the whole of g's gradient."""
    dtype = v.dtype
    c, dk = q.shape[-2:]
    blk = _channel_block(c)
    nb = c // blk
    gc = jnp.cumsum(g, axis=-2)                              # G
    starts = gc[..., (blk - 1) // 2::blk, :][..., :, None, :]    # r_a
    blocks = gc.shape[:-2] + (nb, blk, dk)
    rows = jnp.exp(gc.reshape(blocks) - starts)
    # the columns a row block sees: its own block's and the earlier ones'
    seen = (np.arange(c) < (np.arange(nb)[:, None] + 1) * blk)[..., None]
    cols = jnp.where(seen, jnp.exp(jnp.where(
        seen, starts - gc[..., None, :, :], 0.0)), 0.0)      # [.., nb, C, Dk]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kcols = kf[..., None, :, :] * cols
    kk, qk = (_mm('...aik,...ajk->...aij', x.reshape(blocks) * rows, kcols,
                  dtype).reshape(gc.shape[:-1] + (c,)) for x in (kf, qf))
    strict = np.tril(np.ones((c, c), bool), -1)
    a = jnp.where(strict, kk, 0.0) * beta[..., :, None]
    t = _unit_lower_inverse(a)
    e_g = jnp.exp(gc)
    u = _mm('...ij,...jd->...id', t, vf * beta[..., None], dtype)
    w = _mm('...ij,...jd->...id', t, kf * (beta[..., None] * e_g), dtype)
    # a token's own pair decays by exp(0): q . k as it is, so that G's
    # gradient is not what two roundings leave of a term that cancels
    p = jnp.where(strict, qk, 0.0) + jnp.eye(c, dtype=jnp.float32) \
        * jnp.sum(qf * kf, axis=-1)[..., None]
    last = gc[..., -1:, :]
    return (w, u, qf * e_g, kf * jnp.exp(last - gc), p,
            jnp.exp(last[..., 0, :]))


def _chunk_step(s, x, dtype):
    """One chunk of stage `gdn_scan`: the state S [B, H, Dk, Dv] float32 in,
    (the state after the chunk, the chunk's outputs) out. The chunk's
    decay is [B, H], or [B, H, Dk] where the state's rows decay each at
    its own rate."""
    w, u, qg, kd, p, decay = x
    vn = u - _mm('...ck,...kv->...cv', w, s, dtype)
    o = _mm('...ck,...kv->...cv', qg, s, dtype) \
        + _mm('...ij,...jv->...iv', p, vn, dtype)
    s = s * decay[(Ellipsis,) + (None,) * (s.ndim - decay.ndim)] \
        + _mm('...ck,...cv->...kv', kd, vn, dtype)
    return s, o


def _to_chunks(x, c):
    """[B, T, H, ...] -> [N, B, H, C, ...], T padded with zeros to N x C."""
    b, t = x.shape[:2]
    x = jnp.pad(x, [(0, 0), (0, -t % c)] + [(0, 0)] * (x.ndim - 2))
    x = x.reshape((b, -1, c) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _from_chunks(x, t):
    """[N, B, H, C, D] -> [B, T, H, D]"""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 1), 2, 3)         # [B, N, C, H, D]
    return x.reshape((x.shape[0], -1) + x.shape[3:])[:, :t]


def _stage_intra(q, k, v, g, beta, cfg):
    """From the op's inputs to what the scan reads: q and k normalised
    (float32), q scaled, each key head repeated for its value heads
    (`_intra`; the kernel reads a key head for each of them), all cut
    into chunks (the padding tokens change nothing: k = 0, beta = 0,
    g = 0), then stage `gdn_intra`: the kernel where `cfg` says so, else
    `_intra`. The per-head kernel reads what this prepares (q and k
    normalised and rounded, G summed, all cut into chunks: passes of XLA's
    over each array). The per-channel kernels read the op's OWN operands
    where they lie, [B, T, H, D], and do the rest in VMEM (the chunks by
    their index maps, g's floor where `cfg` carries it, the norm, the
    scale, the rounding to the matmuls' dtype, G's running sum, and in
    the backward their pull-backs), so that XLA's part of that path is
    beta's layout (and one padding copy an operand where T is no whole
    number of chunks)."""
    chunk, scale, l2norm, eps, kernel = cfg[:5]
    dtype = v.dtype
    if kernel and g.ndim == 4:      # a decay a channel: the raw operands,
        # where they lie (the kernels' index maps cut the chunks)
        return intra_kernel.gated_delta_intra_tokens(
            q, k, v, g, beta, False, norm=(l2norm, eps, scale),
            floor=cfg[6] if len(cfg) > 6 else None)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    if l2norm:
        qf, kf = (x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
                  for x in (qf, kf))
    qf = qf * scale
    rep = v.shape[2] // q.shape[2]
    if rep > 1 and not kernel:      # the kernel reads a key head in place
        qf, kf = (jnp.repeat(x, rep, axis=2) for x in (qf, kf))
    q, k, v, g, beta = (
        _to_chunks(x, chunk) for x in (
            qf.astype(dtype), kf.astype(dtype), v, g.astype(jnp.float32),
            beta.astype(jnp.float32)))
    if g.ndim == 5:                 # a decay a channel
        return _intra_channel(q, k, v, g, beta)
    if kernel:
        return intra_kernel.gated_delta_intra(
            q, k, v, jnp.cumsum(g, axis=-1), beta, False)
    return _intra(q, k, v, g, beta)


def _zero_state(xs):
    """S = 0 [B, H, Dk, Dv] for the chunks `xs` of stage `gdn_intra`."""
    w, u = xs[:2]
    return jnp.zeros(w.shape[1:3] + (w.shape[4], u.shape[4]), jnp.float32)


def _scan(xs, dtype, kernel):
    """Stage `gdn_scan` from S = 0 over `xs`, what stage `gdn_intra` hands
    over of every chunk: the tokens' outputs [B, N x C, H, Dv] float32. The
    Pallas kernels where `kernel` says so (they write that layout
    themselves), else a `lax.scan` of `_chunk_step`."""
    if kernel:
        return delta_scan.gated_delta_scan(xs, dtype, False)
    o = lax.scan(functools.partial(_chunk_step, dtype=dtype),
                 _zero_state(xs), xs)[1]
    return _from_chunks(o, o.shape[0] * o.shape[3])


def _scan_bwd(xs, do, dtype, kernel):
    """The tokens' cotangents `do` [B, N x C, H, Dv] pulled back through
    the scan to `xs`, in two walks: forward again for S at each chunk's
    start (a temporary), then the chunks in reverse with the transposed
    step."""
    if kernel:      # the kernels' own backward is those two walks; the
        # forward that jax.vjp runs first has no reader and leaves no call
        return jax.vjp(lambda *x: _scan(x, dtype, True), *xs)[1](do)
    step = functools.partial(_chunk_step, dtype=dtype)
    _, starts = lax.scan(lambda s, x: (step(s, x)[0], s), _zero_state(xs),
                         xs)

    def body(ds, inp):
        s, x, do_c = inp
        _, back = jax.vjp(step, s, x)
        return back((ds, do_c))

    return lax.scan(body, jnp.zeros_like(starts[0]),
                    (starts, xs, _to_chunks(do, xs[0].shape[3])),
                    reverse=True)[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, cfg):
    with jax.named_scope('gdn_intra'):
        xs = _stage_intra(q, k, v, g, beta, cfg)
    with jax.named_scope('gdn_scan'):
        o = _scan(xs, v.dtype, cfg[5])
    return o[:, :q.shape[1]]


def _chunked_fwd(q, k, v, g, beta, cfg):
    res = (q, k, v, g)
    if cfg[4] and g.ndim == 4:
        # kept as the per-channel kernels read them, [B, T, H x D], which
        # is how the projections left them: a [B, T, H, D] array behind
        # the backward's barrier is tiled by (H, D) on the TPU, a copy to
        # make and one more to read by token
        res = tuple(x.reshape(x.shape[:2] + (-1,)) for x in res)
    return _chunked(q, k, v, g, beta, cfg), res + (beta,)


def _recompute_after(res, g):
    """The kept inputs and the cotangent behind one optimization barrier,
    as jax.checkpoint places it: without it XLA finds the backward's
    recomputation equal to the forward's computation, keeps the forward's
    arrays for the backward instead, and nothing is saved."""
    return lax.optimization_barrier((res, g))


def _chunked_bwd(cfg, res, do):
    (q, k, v, g, beta), do = _recompute_after(res, do)
    if q.ndim == 3:                 # kept [B, T, H x D]
        q, k, v, g = (x.reshape(beta.shape + (-1,)) for x in (q, k, v, g))
    with jax.named_scope('gdn_intra'):
        xs, pull = jax.vjp(
            lambda *a: _stage_intra(*a, cfg), q, k, v, g, beta)
    with jax.named_scope('gdn_scan'):
        do = jnp.pad(do, [(0, 0), (0, -do.shape[1] % cfg[0]), (0, 0),
                          (0, 0)])
        dxs = _scan_bwd(xs, do, v.dtype, cfg[5])
    with jax.named_scope('gdn_intra'):
        return pull(dxs)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _chunk_of(chunk_size, t):
    """`chunk_size`, or for a shorter row the power of two that holds it."""
    return min(int(chunk_size), 1 << max(t - 1, 0).bit_length())


# the largest exponent stage `gdn_intra` may take of a decay a channel:
# exp(44) squared is finite in float32 (and in bf16, which has its range),
# so a factor leaves its operand half the range
_MAX_EXPONENT = 44.0


def gated_delta_rule(q, k, v, g, beta, chunk_size=64, scale=None,
                     qk_l2norm=False, l2norm_eps=1e-6, kernel=False,
                     scan_kernel=False, gate_floor=None):
    """q, k [B, T, Hk, Dk], v [B, T, Hv, Dv] (float32, or bf16 for bf16
    matmuls), beta [B, T, Hv], g [B, T, Hv] (a decay a head) or
    [B, T, Hv, Dk] (a decay a channel: the rank says which); Hk divides Hv
    and key head h serves the value heads h * Hv / Hk and following.
    Returns o [B, T, Hv, Dv] float32. `qk_l2norm`: q and k are first
    divided by their norm over Dk, x * rsqrt(sum x^2 + eps), in float32;
    then q is scaled (`scale`, default Dk^-0.5). `kernel`: stage
    `gdn_intra` as the Pallas kernel, `scan_kernel`: stage `gdn_scan` as
    the Pallas kernels (the rule's choices; the caller has asked each
    one's `usable`). `gate_floor`: the bound g >= gate_floor that a decay
    a channel needs (a block of rows exponentiates up to half a block x
    |gate_floor| either way); the rule holds g to it and refuses one too
    low for a float32."""
    dk = q.shape[3]
    scale = dk ** -0.5 if scale is None else float(scale)
    chunk = _chunk_of(chunk_size, q.shape[1])
    floor = None        # what stage `gdn_intra` has still to hold g to
    if g.ndim == 4:
        reach = (_channel_block(chunk) + 1) // 2     # from a block's middle
        if gate_floor is None or not \
                -_MAX_EXPONENT <= reach * float(gate_floor) <= 0.0:
            raise ValueError(
                'gated_delta_rule: a decay a channel needs gate_floor with '
                '%d x gate_floor >= %g (half a block of a chunk of %d is '
                'exponentiated at once), got %r'
                % (reach, -_MAX_EXPONENT, chunk, gate_floor))
        # held to its floor; a g AT the floor (a saturated gate) keeps its
        # whole gradient, which `jnp.maximum` would halve. The per-channel
        # kernels hold it as they load it (a pass over g less each way)
        g = g.astype(jnp.float32)
        if kernel:
            floor = float(gate_floor)
        else:
            g = jnp.where(g < float(gate_floor), float(gate_floor), g)
    return _chunked(q, k, v, g, beta,
                    (chunk, scale, bool(qk_l2norm), float(l2norm_eps),
                     bool(kernel), bool(scan_kernel), floor))


@register('gated_delta_rule')
def _gated_delta_rule(ins, attrs, ctx):
    q, k, v, g, beta = (data_of(ins[s][0])
                        for s in ('Q', 'K', 'V', 'G', 'Beta'))
    chunk = int(attrs.get('chunk_size', 64))
    channel = g.ndim == 4           # a decay a channel: the rank says so
    obs.counter('gdn.lowered', chunk=chunk,                  # trace time
                gate='channel' if channel else 'head').inc()
    obs.counter('gdn.tokens').inc(int(v.shape[0]) * int(v.shape[1]))
    q, k, v = amp_cast(ctx, q, k, v)
    cut = _chunk_of(chunk, q.shape[1])
    # stage `gdn_intra`: the Pallas kernel on the TPU for a shape it takes,
    # as the expert layer takes its grouped matmul there (by channel: a
    # key head a value head)
    kernel = ctx.platform == 'tpu' and intra_kernel.usable(
        cut, q.shape[3], v.shape[3], v.dtype) and not (
            channel and q.shape[2] != v.shape[2])
    obs.counter('gdn.intra',                                 # trace time
                way='kernel' if kernel else 'composed').inc()
    # where q and k's norm and g's running sum are taken: in the
    # per-channel kernels' VMEM, or by XLA ahead of the stage; and who cuts
    # the tokens' operands into chunks: those kernels' index maps, where
    # the op holds them, or `_to_chunks` (copies of XLA's). One answer
    # today; the per-head kernels may take the second before the first
    where = 'kernel' if kernel and channel else 'xla'
    obs.counter('gdn.prologue', where=where).inc()           # trace time
    obs.counter('gdn.chunks', where=where).inc()             # trace time
    # stage `gdn_scan`: the Pallas kernels read what that kernel hands over
    scan = kernel and delta_scan.usable(
        cut, q.shape[3], v.shape[3], v.shape[2], v.dtype)
    obs.counter('gdn.scan',                                  # trace time
                way='kernel' if scan else 'composed').inc()
    scale = attrs.get('scale', -1.0)
    o = gated_delta_rule(
        q, k, v, g, beta, chunk_size=chunk,
        scale=None if scale is None or scale < 0 else float(scale),
        qk_l2norm=bool(attrs.get('qk_l2norm', False)),
        l2norm_eps=float(attrs.get('l2norm_eps', 1e-6)),
        kernel=kernel, scan_kernel=scan,
        gate_floor=attrs.get('gate_floor'))
    return {'Out': o}


def _ssd_stages(x, dt, a, b, c, chunk):
    """The three stages on whole chunks. x [B, T, H, P], b, c [B, T, G, N]
    in the matmuls' dtype, dt [B, T, H] and a [H] float32, T a multiple of
    `chunk`. Returns y [B, T, H, P] float32 (without the skip D x)."""
    dtype = x.dtype
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r, z = h // g, t // chunk
    x = x.reshape(bsz, z, chunk, g, r, p)
    b, c = (v.reshape(bsz, z, chunk, g, n) for v in (b, c))
    # a head's arrays with the chunk's tokens last: [B, Z, G, R, C]
    dt = jnp.moveaxis(dt.reshape(bsz, z, chunk, g, r), 2, -1)
    run = jnp.cumsum(dt * a.reshape(g, r, 1), axis=-1)        # L
    with jax.named_scope('ssd_intra'):
        lower = np.tril(np.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            lower, run[..., :, None] - run[..., None, :], -jnp.inf))
        cb = _mm('bzign,bzjgn->bzgij', c, b, dtype)
        m = cb[:, :, :, None] * decay * dt[..., None, :]
        y = _mm('bzgrij,bzjgrp->bzigrp', m, x, dtype)
        last = run[..., -1:]
        write = jnp.moveaxis(jnp.exp(last - run) * dt, -1, 2)  # [B,Z,C,G,R]
        ds = _mm('bzcgrp,bzcgn->zbgrpn',
                 x.astype(jnp.float32) * write[..., None], b, dtype)
        keep = jnp.moveaxis(jnp.exp(last[..., 0]), 1, 0)       # [Z,B,G,R]
    with jax.named_scope('ssd_scan'):
        # S at each chunk's start; elementwise, the state in float32
        _, starts = lax.scan(
            lambda s, inp: (s * inp[0][..., None, None] + inp[1], s),
            jnp.zeros(ds.shape[1:], jnp.float32), (keep, ds))
    with jax.named_scope('ssd_inter'):
        read = jnp.moveaxis(jnp.exp(run), -1, 2)               # [B,Z,C,G,R]
        y = y + read[..., None] * _mm('bzcgn,zbgrpn->bzcgrp', c, starts,
                                      dtype)
    return y.reshape(bsz, t, h, p)


def _ssd_padded(x, dt, b, c, chunk):
    """x, dt, b, c with T padded to whole chunks: tokens of dt = 0, which
    decay nothing and write nothing."""
    pad = [(0, 0), (0, -x.shape[1] % chunk), (0, 0)]
    xp, bp, cp = (jnp.pad(v, pad + [(0, 0)]) for v in (x, b, c))
    return xp, jnp.pad(dt.astype(jnp.float32), pad), bp, cp


def _ssd(x, dt, a, b, c, d, chunk):
    t = x.shape[1]
    xp, dtp, bp, cp = _ssd_padded(x, dt, b, c, chunk)
    y = _ssd_stages(xp, dtp, a.astype(jnp.float32), bp, cp, chunk)[:, :t]
    if d is not None:
        y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y


def _ssd_kernel(x, dt, a, b, c, d, chunk):
    """The Pallas forward: (y, S at each chunk's start, which only a
    caller that reads it pays for)."""
    xp, dtp, bp, cp = _ssd_padded(x, dt, b, c, chunk)
    y, starts = ssd_kernel.ssd_scan_fwd(xp, dtp, a, bp, cp, d, chunk=chunk,
                                        interpret=False)
    return y[:, :x.shape[1]], starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_chunked(x, dt, a, b, c, d, chunk, kernel):
    if kernel:
        return _ssd_kernel(x, dt, a, b, c, d, chunk)[0]
    return _ssd(x, dt, a, b, c, d, chunk)


def _ssd_fwd(x, dt, a, b, c, d, chunk, kernel):
    if kernel:      # S at the chunks' starts is all the backward asks of it
        y, starts = _ssd_kernel(x, dt, a, b, c, d, chunk)
        return y, (x, dt, a, b, c, d, starts)
    return _ssd(x, dt, a, b, c, d, chunk), (x, dt, a, b, c, d)


def _ssd_bwd(chunk, kernel, res, dy):
    if kernel:      # one kernel, no forward of its own
        x, dt, a, b, c, d, starts = res
        t = x.shape[1]
        xp, dtp, bp, cp = _ssd_padded(x, dt, b, c, chunk)
        dyp = jnp.pad(dy, [(0, 0), (0, -t % chunk), (0, 0), (0, 0)])
        dx, ddt, da, db, dc, dd = ssd_kernel.ssd_scan_bwd(
            xp, dtp, a, bp, cp, d, starts, dyp, chunk=chunk,
            interpret=False)
        return (dx[:, :t], ddt[:, :t].astype(dt.dtype), da, db[:, :t],
                dc[:, :t], dd)
    res, dy = _recompute_after(res, dy)
    return jax.vjp(lambda *v: _ssd(*v, chunk), *res)[1](dy)


_ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, d=None, chunk_size=128, kernel=False):
    """x [B, T, H, P], b, c [B, T, G, N] (float32, or bf16 for bf16
    matmuls), dt [B, T, H] (the step, > 0), a [H] (< 0), d [H] or None; G
    divides H and group g serves heads g * H / G and following. Returns
    y [B, T, H, P] float32. `kernel`: the Pallas kernels, forward and
    backward (the rule's choice; the caller has asked their `usable`),
    else `_ssd_stages`."""
    return _ssd_chunked(x, dt, a, b, c, d,
                        _chunk_of(chunk_size, x.shape[1]), bool(kernel))


@register('ssd_scan')
def _ssd_scan(ins, attrs, ctx):
    x, dt, a, b, c = (data_of(ins[s][0]) for s in ('X', 'Dt', 'A', 'B', 'C'))
    d = data_of(ins['D'][0]) if ins.get('D') else None
    chunk = int(attrs.get('chunk_size', 128))
    obs.counter('ssd.lowered', chunk=chunk, heads=int(x.shape[2]),
                groups=int(b.shape[2])).inc()                # trace time
    obs.counter('ssd.tokens').inc(int(x.shape[0]) * int(x.shape[1]))
    x, b, c = amp_cast(ctx, x, b, c)
    # on the TPU, for a shape they take, one Pallas kernel each way
    kernel = ctx.platform == 'tpu' and ssd_kernel.usable(
        _chunk_of(chunk, x.shape[1]), x.shape[3], b.shape[3],
        x.shape[2] // b.shape[2], x.dtype)
    obs.counter('ssd.way',                                   # trace time
                way='kernel' if kernel else 'composed').inc()
    return {'Out': ssd_scan(x, dt, a, b, c, d, chunk_size=chunk,
                            kernel=kernel)}


_CONV_ACTS = {'': lambda x: x, 'silu': jax.nn.silu, 'swish': jax.nn.silu}


def _conv(x, w, act, b=None):
    taps, t = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + t] * wf[j] for j in range(taps))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return _CONV_ACTS[act](y).astype(x.dtype)


def _conv_forward(x, w, act, kernel, b):
    if kernel:
        return conv_kernel.causal_conv1d_fwd(x, w, b, act=act,
                                             interpret=False)
    return _conv(x, w, act, b)


# The backward keeps the input and the filter and computes the sum again:
# K shifted multiply-adds of a memory-bound op, against a second
# [B, T, C] float32 array (the sum before its activation) kept a layer.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def causal_conv1d(x, w, act='', kernel=False, b=None):
    """x [B, T, C], w [K, C], b [C] or None:
    y[t] = act(sum_j w[j] x[t - (K - 1) + j] + b).
    `kernel`: the Pallas kernels, forward and backward (the rule's choice;
    the caller has asked their `usable`), else `_conv`."""
    return _conv_forward(x, w, act, kernel, b)


def _conv_fwd(x, w, act, kernel, b):
    return _conv_forward(x, w, act, kernel, b), (x, w, b)


def _conv_bwd(act, kernel, res, g):
    if kernel:      # the sum again in VMEM: nothing XLA could keep instead
        x, w, b = res
        # (dx, dw) and, with a bias, its gradient
        return (conv_kernel.causal_conv1d_bwd(x, w, g, b, act=act,
                                              interpret=False)
                + (None,))[:3]
    res, g = _recompute_after(res, g)
    return jax.vjp(lambda x, w, b: _conv(x, w, act, b), *res)[1](g)


causal_conv1d.defvjp(_conv_fwd, _conv_bwd)


def _gate(x, gate):
    """x * gate in float32, rounded to x's dtype; x where there is none."""
    if gate is None:
        return x
    return (x.astype(jnp.float32) * gate.astype(jnp.float32)).astype(x.dtype)


@register('causal_conv1d')
def _causal_conv1d(ins, attrs, ctx):
    b = data_of(ins['Bias'][0]) if ins.get('Bias') else None
    x = amp_cast(ctx, data_of(ins['X'][0]))
    w = data_of(ins['Filter'][0])
    # the gates of a double-gated short convolution: y = OutGate *
    # conv(InGate * X), each product taken here, around the kernel
    gates = [amp_cast(ctx, data_of(ins[slot][0])) if ins.get(slot) else None
             for slot in ('InGate', 'OutGate')]
    n_gates = sum(g is not None for g in gates)
    act = attrs.get('act') or ''
    labels = {'taps': int(w.shape[0]), 'act': act or 'none'}
    if b is not None:
        labels['bias'] = 'true'
    if n_gates:
        labels['gates'] = n_gates
    obs.counter('conv1d.lowered', **labels).inc()            # trace time
    if n_gates == 2:
        obs.counter('shortconv.tokens').inc(int(x.shape[0])
                                            * int(x.shape[1]))
    # on the TPU, for a shape they take, one Pallas kernel each way
    kernel = ctx.platform == 'tpu' and conv_kernel.usable(
        x.shape[1], x.shape[2], w.shape[0], x.dtype)
    obs.counter('conv1d.way',                                # trace time
                way='kernel' if kernel else 'composed').inc()
    y = causal_conv1d(_gate(x, gates[0]), w, act, kernel, b)
    return {'Out': _gate(y, gates[1])}


_GATE_ACTS = {'silu': jax.nn.silu, 'sigmoid': jax.nn.sigmoid}


def _norm_cfg(cfg):
    """(eps, norm_before_gate, groups, the gate's activation: `silu` where
    `cfg` names none)"""
    return tuple(cfg) + ('silu',) * (4 - len(cfg))


def _gated_norm(x, gate, w, cfg):
    eps, norm_first, groups, act = _norm_cfg(cfg)
    act = _GATE_ACTS[act]
    xf = x.astype(jnp.float32)
    if not norm_first:
        xf = xf * act(gate.astype(jnp.float32))
    parts = xf.reshape(xf.shape[:-1] + (groups, -1)) if groups > 1 else xf
    inv = lax.rsqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    y = (parts * inv).reshape(xf.shape) if groups > 1 else xf * inv
    y = y * w.astype(jnp.float32)
    return y * act(gate.astype(jnp.float32)) if norm_first else y


def _kernel_args(cfg):
    eps, norm_first, groups, act = _norm_cfg(cfg)
    args = dict(eps=eps, norm_first=norm_first, groups=groups,
                interpret=False)
    if act != 'silu':
        args['gate_act'] = act
    return args


def _gated_norm_forward(x, gate, w, cfg, kernel):
    if kernel:
        return norm_kernel.gated_norm_fwd(x, gate, w, **_kernel_args(cfg))
    return _gated_norm(x, gate, w, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_rms_norm(x, gate, w, cfg, kernel=False):
    """`cfg` = (eps, norm_before_gate, groups) and optionally the gate's
    activation (`silu`, or `sigmoid`), float32:
    w * x * rsqrt(mean(x^2) + eps) * silu(gate), or with
    norm_before_gate false w * u * rsqrt(mean(u^2) + eps), u = x *
    silu(gate); the mean over each of `groups` parts of the last axis.
    `kernel`: the Pallas kernels, forward and backward, which give the
    result in x's dtype (the rule's choice; the caller has asked their
    `usable`), else `_gated_norm`."""
    return _gated_norm_forward(x, gate, w, cfg, kernel)


def _gated_norm_fwd(x, gate, w, cfg, kernel):
    return _gated_norm_forward(x, gate, w, cfg, kernel), (x, gate, w)


def _gated_norm_bwd(cfg, kernel, res, g):
    if kernel:      # `inv` again in VMEM: nothing XLA could keep instead
        return norm_kernel.gated_norm_bwd(*res, g, **_kernel_args(cfg))
    res, g = _recompute_after(res, g)
    return jax.vjp(lambda *a: _gated_norm(*a, cfg), *res)[1](g)


gated_rms_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


@register('gated_rms_norm')
def _gated_rms_norm(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    gate = amp_cast(ctx, data_of(ins['Gate'][0]))
    groups = int(attrs.get('groups', 1))
    obs.counter('gated_rms_norm.lowered').inc()              # trace time
    # on the TPU, for a shape they take, one Pallas kernel each way
    kernel = ctx.platform == 'tpu' and norm_kernel.usable(
        x.shape, groups, x.dtype, gate.dtype)
    obs.counter('gated_rms_norm.way',                        # trace time
                way='kernel' if kernel else 'composed').inc()
    cfg = (float(attrs.get('epsilon', 1e-5)),
           bool(attrs.get('norm_before_gate', True)), groups)
    if attrs.get('gate_act', 'silu') != 'silu':     # as the op was, else
        cfg += (attrs['gate_act'],)
    y = gated_rms_norm(x, gate, data_of(ins['Scale'][0]), cfg, kernel)
    return {'Y': y.astype(x.dtype)}


# ---------------------------------------------------------------------------
# chunk_softmax_pool: one learned summary key and value a chunk (EVA)
# ---------------------------------------------------------------------------

def _pool_weights(k, vec, chunk, scale):
    """softmax over each chunk's positions of scale * (vec . k): k
    [B, H, T, D], vec [H, D] -> [B, H, T / chunk, chunk] float32. The dot
    is a product and a sum in float32, never a matmul: the chip would
    take a matmul's float32 operands in bf16 passes."""
    b, h, t, _ = k.shape
    logits = jnp.sum(k.astype(jnp.float32)
                     * vec.astype(jnp.float32)[None, :, None, :], axis=-1)
    return jax.nn.softmax(
        (logits * scale).reshape(b, h, t // chunk, chunk), axis=-1)


def _chunks(x, chunk):
    b, h, t, d = x.shape
    return x.astype(jnp.float32).reshape(b, h, t // chunk, chunk, d)


def _pool_forward(k, v, mu, phi, chunk, scale):
    a = _pool_weights(k, mu, chunk, 1.0)
    b = _pool_weights(k, phi, chunk, scale)
    kbar = jnp.sum(_chunks(k, chunk) * a[..., None], axis=3)
    vbar = jnp.sum(_chunks(v, chunk) * b[..., None], axis=3)
    return (kbar.astype(k.dtype), vbar.astype(v.dtype)), (k, v, mu, phi, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def chunk_softmax_pool(k, v, mu, phi, chunk, scale):
    """One summary key and one summary value for every `chunk` consecutive
    positions, per head, each a softmax-weighted sum whose logits come
    from the KEYS against a learned vector (EVA, arXiv:2302.04542, with
    EvaByte's two learned vectors in place of the sampled feature):

        a_m = softmax over the chunk of (mu . k_m)            kbar = sum a_m k_m
        b_m = softmax over the chunk of (scale * phi . k_m)   vbar = sum b_m v_m

    k, v [B, H, T, D] (v may be [.., Dv]), mu, phi [H, D], T a multiple
    of `chunk`; returns kbar [B, H, T / chunk, D] and vbar [.., Dv] in k's
    and v's dtype. Logits, weights and sums are float32. The backward
    keeps k, v and the two weight arrays ([B, H, T] float32 each) and
    nothing else of the forward."""
    return _pool_forward(k, v, mu, phi, chunk, scale)[0]


def _pool_bwd(chunk, scale, res, cot):
    k, v, mu, phi, a, b = res
    dkbar, dvbar = (c.astype(jnp.float32)[:, :, :, None, :] for c in cot)
    kc, vc = _chunks(k, chunk), _chunks(v, chunk)

    def through_softmax(w, dw):
        return w * (dw - jnp.sum(w * dw, axis=-1, keepdims=True))

    dla = through_softmax(a, jnp.sum(kc * dkbar, axis=-1))[..., None]
    dlb = through_softmax(b, jnp.sum(vc * dvbar, axis=-1))[..., None] * scale
    muf, phif = (x.astype(jnp.float32)[None, :, None, None, :]
                 for x in (mu, phi))
    dk = a[..., None] * dkbar + dla * muf + dlb * phif
    dv = b[..., None] * dvbar
    dmu, dphi = (jnp.sum(d * kc, axis=(0, 2, 3)) for d in (dla, dlb))
    return (dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype),
            dmu.astype(mu.dtype), dphi.astype(phi.dtype))


chunk_softmax_pool.defvjp(
    lambda k, v, mu, phi, chunk, scale: _pool_forward(k, v, mu, phi, chunk,
                                                      scale),
    _pool_bwd)


@register('chunk_softmax_pool')
def _chunk_softmax_pool(ins, attrs, ctx):
    k, v, mu, phi = (data_of(ins[s][0]) for s in ('K', 'V', 'Mu', 'Phi'))
    chunk = int(attrs['chunk'])
    obs.counter('chunk_pool.lowered', chunk=chunk).inc()     # trace time
    # the keys and values as the attention call beside it reads them
    kc, vc = amp_cast(ctx, k, v)
    scale = attrs.get('scale', -1.0)
    scale = k.shape[-1] ** -0.5 if scale is None or scale < 0 \
        else float(scale)
    kbar, vbar = chunk_softmax_pool(kc, vc, mu, phi, chunk, scale)
    return {'KBar': kbar.astype(k.dtype), 'VBar': vbar.astype(v.dtype)}
