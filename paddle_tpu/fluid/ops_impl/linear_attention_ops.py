"""Linear attention with a recurrent state: the gated delta rule, the
depthwise causal convolution that feeds it and the gated norm after it.

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

The backward is the op's own (`jax.custom_vjp`) and keeps the op's inputs
alone: no state a token, and not even a state a chunk (S at the starts of
128 chunks is [128, B, 32, 128, 128] float32, 256 MiB a layer at B = 1,
which the cell of 8192 tokens has no room for beside three layers'
activations). It recomputes stage `gdn_intra` for all chunks at once,
scans the chunks forward again for S at each chunk's start, walks them
backwards with the transposed step (jax.vjp of the same step function
that the forward scans, so the two cannot drift) and pulls the chunks'
cotangents back through the recomputed stage (the l2 norm of q and k and
the repeat of the key heads included).

On the TPU, for a chunk of 64 and heads of whole lane tiles (`usable` of
ops/kernels/gated_delta_intra.py), stage `gdn_intra` is a Pallas kernel
forward and one backward that keep everything C x C of a chunk in VMEM:
the same arithmetic in the same precisions, W, Qg, Kd and P handed over in
the matmuls' dtype (what the scan's matmuls round them to anyway), the
backward's recomputed and transposed passes one grid step. Every other
platform and shape takes `_intra` below, the composition the kernel is
tested against. The rule chooses on what it can see (platform, shapes,
dtype); nothing else selects it.

Under AMP the rule is one of the MXU's: q, k, v are cast to bf16
(`lowering.amp_cast`; they are what the backward keeps) and every matmul
of the two stages takes bf16 operands with float32 accumulation; the
norm of q and k, g, beta, the decays, the solve and the state are
float32. The output is float32.

`causal_conv1d`: y[b, t, c] = act(sum_j w[j, c] x[b, t - (K - 1) + j, c]),
x = 0 before the row's first token; depthwise (a filter a channel), no
bias. Float32 elementwise work (K shifted multiply-adds), never the MXU.
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
last axis, statistics in float32; its backward keeps x and the gate
(bf16 under AMP) and computes the rest again.

Trace-time counters: `gdn.lowered{chunk=}` once per op per trace,
`gdn.intra{way=kernel|composed}` beside it (which way stage `gdn_intra`
went), `gdn.tokens` the B x T of the traced shape, `conv1d.lowered` and
`conv1d.way{way=kernel|composed}` beside it, `gated_rms_norm.lowered`.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ... import obs
from ...ops.kernels import causal_conv1d as conv_kernel
from ...ops.kernels import gated_delta_intra as intra_kernel
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


def _chunk_step(s, x, dtype):
    """One chunk of stage `gdn_scan`: the state S [B, H, Dk, Dv] float32 in,
    (the state after the chunk, the chunk's outputs) out."""
    w, u, qg, kd, p, decay = x
    vn = u - _mm('...ck,...kv->...cv', w, s, dtype)
    o = _mm('...ck,...kv->...cv', qg, s, dtype) \
        + _mm('...ij,...jv->...iv', p, vn, dtype)
    s = s * decay[..., None, None] + _mm('...ck,...cv->...kv', kd, vn, dtype)
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
    g = 0), then stage `gdn_intra`: the kernel where `cfg` says so (with G
    summed here: a scan over 64 that XLA does in passing), else `_intra`."""
    chunk, scale, l2norm, eps, kernel = cfg
    dtype = v.dtype
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
    if kernel:
        return intra_kernel.gated_delta_intra(
            q, k, v, jnp.cumsum(g, axis=-1), beta, False)
    return _intra(q, k, v, g, beta)


def _zero_state(q, v):
    return jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]),
                     jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, cfg):
    step = functools.partial(_chunk_step, dtype=v.dtype)
    with jax.named_scope('gdn_intra'):
        xs = _stage_intra(q, k, v, g, beta, cfg)
    with jax.named_scope('gdn_scan'):
        _, o = lax.scan(step, _zero_state(q, v), xs)
    return _from_chunks(o, q.shape[1])


def _chunked_fwd(q, k, v, g, beta, cfg):
    return _chunked(q, k, v, g, beta, cfg), (q, k, v, g, beta)


def _recompute_after(res, g):
    """The kept inputs and the cotangent behind one optimization barrier,
    as jax.checkpoint places it: without it XLA finds the backward's
    recomputation equal to the forward's computation, keeps the forward's
    arrays for the backward instead, and nothing is saved."""
    return lax.optimization_barrier((res, g))


def _chunked_bwd(cfg, res, do):
    (q, k, v, g, beta), do = _recompute_after(res, do)
    step = functools.partial(_chunk_step, dtype=v.dtype)
    with jax.named_scope('gdn_intra'):
        xs, pull = jax.vjp(
            lambda *a: _stage_intra(*a, cfg), q, k, v, g, beta)
    with jax.named_scope('gdn_scan'):
        # S at each chunk's start, by the forward's scan over again
        _, starts = lax.scan(lambda s, x: (step(s, x)[0], s),
                             _zero_state(q, v), xs)

        def body(ds, inp):
            s, x, do_c = inp
            _, back = jax.vjp(step, s, x)
            return back((ds, do_c))

        _, dxs = lax.scan(body, jnp.zeros_like(starts[0]),
                          (starts, xs, _to_chunks(do, cfg[0])),
                          reverse=True)
    with jax.named_scope('gdn_intra'):
        return pull(dxs)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _chunk_of(chunk_size, t):
    """`chunk_size`, or for a shorter row the power of two that holds it."""
    return min(int(chunk_size), 1 << max(t - 1, 0).bit_length())


def gated_delta_rule(q, k, v, g, beta, chunk_size=64, scale=None,
                     qk_l2norm=False, l2norm_eps=1e-6, kernel=False):
    """q, k [B, T, Hk, Dk], v [B, T, Hv, Dv] (float32, or bf16 for bf16
    matmuls), g, beta [B, T, Hv]; Hk divides Hv and key head h serves the
    value heads h * Hv / Hk and following. Returns o [B, T, Hv, Dv]
    float32. `qk_l2norm`: q and k are first divided by their norm over
    Dk, x * rsqrt(sum x^2 + eps), in float32; then q is scaled (`scale`,
    default Dk^-0.5). `kernel`: stage `gdn_intra` as the Pallas kernel
    (the rule's choice; the caller has asked its `usable`)."""
    dk = q.shape[3]
    scale = dk ** -0.5 if scale is None else float(scale)
    return _chunked(q, k, v, g, beta,
                    (_chunk_of(chunk_size, q.shape[1]), scale,
                     bool(qk_l2norm), float(l2norm_eps), bool(kernel)))


@register('gated_delta_rule')
def _gated_delta_rule(ins, attrs, ctx):
    q, k, v, g, beta = (data_of(ins[s][0])
                        for s in ('Q', 'K', 'V', 'G', 'Beta'))
    chunk = int(attrs.get('chunk_size', 64))
    obs.counter('gdn.lowered', chunk=chunk).inc()            # trace time
    obs.counter('gdn.tokens').inc(int(v.shape[0]) * int(v.shape[1]))
    q, k, v = amp_cast(ctx, q, k, v)
    # stage `gdn_intra`: the Pallas kernel on the TPU for a shape it takes,
    # as the expert layer takes its grouped matmul there
    kernel = ctx.platform == 'tpu' and intra_kernel.usable(
        _chunk_of(chunk, q.shape[1]), q.shape[3], v.shape[3], v.dtype)
    obs.counter('gdn.intra',                                 # trace time
                way='kernel' if kernel else 'composed').inc()
    scale = attrs.get('scale', -1.0)
    o = gated_delta_rule(
        q, k, v, g, beta, chunk_size=chunk,
        scale=None if scale is None or scale < 0 else float(scale),
        qk_l2norm=bool(attrs.get('qk_l2norm', False)),
        l2norm_eps=float(attrs.get('l2norm_eps', 1e-6)),
        kernel=kernel)
    return {'Out': o}


_CONV_ACTS = {'': lambda x: x, 'silu': jax.nn.silu, 'swish': jax.nn.silu}


def _conv(x, w, act):
    taps, t = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + t] * wf[j] for j in range(taps))
    return _CONV_ACTS[act](y).astype(x.dtype)


def _conv_forward(x, w, act, kernel):
    if kernel:
        return conv_kernel.causal_conv1d_fwd(x, w, act=act, interpret=False)
    return _conv(x, w, act)


# The backward keeps the input and the filter and computes the sum again:
# K shifted multiply-adds of a memory-bound op, against a second
# [B, T, C] float32 array (the sum before its activation) kept a layer.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def causal_conv1d(x, w, act='', kernel=False):
    """x [B, T, C], w [K, C]: y[t] = act(sum_j w[j] x[t - (K - 1) + j]).
    `kernel`: the Pallas kernels, forward and backward (the rule's choice;
    the caller has asked their `usable`), else `_conv`."""
    return _conv_forward(x, w, act, kernel)


def _conv_fwd(x, w, act, kernel):
    return _conv_forward(x, w, act, kernel), (x, w)


def _conv_bwd(act, kernel, res, g):
    if kernel:      # the sum again in VMEM: nothing XLA could keep instead
        return conv_kernel.causal_conv1d_bwd(*res, g, act=act,
                                             interpret=False)
    res, g = _recompute_after(res, g)
    return jax.vjp(lambda x, w: _conv(x, w, act), *res)[1](g)


causal_conv1d.defvjp(_conv_fwd, _conv_bwd)


@register('causal_conv1d')
def _causal_conv1d(ins, attrs, ctx):
    obs.counter('conv1d.lowered').inc()                      # trace time
    x = amp_cast(ctx, data_of(ins['X'][0]))
    w = data_of(ins['Filter'][0])
    # on the TPU, for a shape they take, one Pallas kernel each way
    kernel = ctx.platform == 'tpu' and conv_kernel.usable(
        x.shape[1], x.shape[2], w.shape[0], x.dtype)
    obs.counter('conv1d.way',                                # trace time
                way='kernel' if kernel else 'composed').inc()
    return {'Out': causal_conv1d(x, w, attrs.get('act') or '', kernel)}


def _gated_norm(x, gate, w, eps):
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return xf * inv * w.astype(jnp.float32) \
        * jax.nn.silu(gate.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gated_rms_norm(x, gate, w, eps):
    """w * x * rsqrt(mean(x^2) + eps) * silu(gate), float32."""
    return _gated_norm(x, gate, w, eps)


def _gated_norm_fwd(x, gate, w, eps):
    return _gated_norm(x, gate, w, eps), (x, gate, w)


def _gated_norm_bwd(eps, res, g):
    res, g = _recompute_after(res, g)
    return jax.vjp(lambda *a: _gated_norm(*a, eps), *res)[1](g)


gated_rms_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


@register('gated_rms_norm')
def _gated_rms_norm(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    obs.counter('gated_rms_norm.lowered').inc()              # trace time
    y = gated_rms_norm(x, amp_cast(ctx, data_of(ins['Gate'][0])),
                       data_of(ins['Scale'][0]),
                       float(attrs.get('epsilon', 1e-5)))
    return {'Y': y.astype(x.dtype)}
