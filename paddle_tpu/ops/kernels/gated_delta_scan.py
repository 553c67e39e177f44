"""Stage `gdn_scan` of the gated delta rule as Pallas kernels: the walk over
a row's chunks is the grid's last, sequential axis, and the state S (in the
reverse walk its cotangent dS) is a float32 VMEM scratch that never leaves
the chip between two chunks.

The mathematics, its precisions and the names are those of
fluid/ops_impl/linear_attention_ops.py `_chunk_step`, which stays as the
composition this is tested against and as every other platform's path. Per
value head, with S [Dk, Dv] the state at the chunk's start and
(W, U, Qg, Kd, P, decay) what stage `gdn_intra` left of the chunk:

    Vn  = U - W S                         the values written
    O   = Qg S + P Vn
    S'  = decay S + Kd^T Vn

Operands of every product in `dtype` (bf16 under AMP, float32 accumulation;
float32 operands multiply at full precision, written in the body), S and
Vn rounded once for the products that read them, as `_mm` rounds them; the
carry, the decay and every sum in float32.

A grid step owns one chunk of `HEADS` value heads: their products are
batched `dot_general`s (one body whatever the number of heads, and
independent chains for the scheduler to run side by side). The operands are
read where the `gdn_intra` kernel left them, [N, B, H, C, D], by the index
maps; the chunk's decay comes a head a row of lanes ([N, B, H, 1, Dv]: a
[1, 1] block broadcasts neither way in Mosaic), 2 MB a layer that XLA
spreads outside. O is written, and its cotangent read, where the op's
neighbours hold them: [B, T, H, Dv], a head a sublane of a token's tile
(a block [C, HEADS, Dv], a strided store a head), so that no transpose
of XLA's stands between the walk and the gated norm either way.

Three calls a layer a step, which `_scan` and `_forward_kept` below make of
one forward kernel and one reverse kernel:

  forward        O alone (the op's forward; the reads of S at the chunks'
                 starts are dropped through `custom_dce`)
  forward again  S at each chunk's start alone, [N, B, H, Dk, Dv] float32
                 (the op's backward; it reads neither Qg nor P and writes
                 no O)
  reverse        from the last chunk, dS in the scratch: a step reads S at
                 the chunk's start, the chunk's operands and dO, forms Vn
                 again and gives dW, dU, dQg, dKd, dP, the decay's
                 cotangent and the new dS: the transposition of
                 `_chunk_step`, cotangents rounded to `dtype` where a
                 product reads them

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the flash kernels,
the grouped matmul and stage `gdn_intra` it is what the op lowers to on the
TPU. No call states a `vmem_limit_bytes`: at the cell's shapes the reverse
walk's blocks, twice over, and its scratch are under Mosaic's default
(tests/test_flash_aot.py holds that).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import custom_dce, pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['gated_delta_scan', 'usable', 'HEADS']

# value heads a grid step (tools/bench_gated_delta_scan.py --sweep;
# docs/perf.md has the rows): a token's tile of O [B, T, H, Dv] is eight
# heads of 128 lanes
HEADS = 8
_CHUNK = 64
_LANES = 128

_F32 = jnp.float32


def usable(chunk, dk, dv, heads, dtype):
    """A chunk of 64 (what the `gdn_intra` kernel hands over), heads of
    whole lane tiles whose state is within what the reverse walk's blocks
    leave of VMEM (128 x 256 at 2 bytes, 128 x 128 at 4: AOT,
    tests/test_flash_aot.py), bf16 or float32 operands, and value heads in
    whole steps of HEADS (or fewer than HEADS in all)."""
    dtype = jnp.dtype(dtype)
    return (chunk == _CHUNK and dk % _LANES == 0 and dv % _LANES == 0
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and dk * dv * dtype.itemsize <= 4 * _LANES * _LANES
            and (heads % HEADS == 0 or heads < HEADS))


def _heads(heads):
    """Value heads a grid step: HEADS, or all of fewer (a block's heads
    are whole sublane tiles of O, or O's whole axis)."""
    return min(heads, HEADS)


def _dot(a, b, dims, dtype):
    """A head's a x b for every head of the step, on the MXU: [h, ., .]
    operands in `dtype`, float32 out. `dims`: 'nn' a b, 'nt' a b^T, 'tn'
    a^T b."""
    contract = {'nn': ((2,), (1,)), 'nt': ((2,), (2,)),
                'tn': ((1,), (1,))}[dims]
    precision = lax.Precision.HIGHEST if dtype == _F32 else None
    return lax.dot_general(a.astype(dtype), b.astype(dtype),
                           (contract, ((0,), (0,))), precision=precision,
                           preferred_element_type=_F32)


def _eye(n):
    """The unit diagonal of [1, n, n], each iota made at its shape."""
    return (lax.broadcasted_iota(jnp.int32, (1, n, n), 1)
            == lax.broadcasted_iota(jnp.int32, (1, n, n), 2))


def _as_rows(x):
    """[h, 1, n] -> [h, n, 1]: a head's lanes spread over n rows (a decay
    a channel scales the state's ROWS)."""
    return jnp.sum(jnp.where(_eye(x.shape[2]), x, 0.0), axis=2,
                   keepdims=True)


def _as_lanes(x):
    """[h, n, 1] -> [h, 1, n]"""
    return jnp.sum(jnp.where(_eye(x.shape[1]), x, 0.0), axis=1,
                   keepdims=True)


def _fwd_kernel(*refs, dtype, out, save, channel=False):
    """`out`: O is written (and Qg, P are read); `save`: S at the chunk's
    start is; `channel`: the chunk's decay is a head's [1, Dk] row, one a
    row of the state. refs: the operands read, the outputs written, the
    scratch."""
    outs, s_ref = list(refs[6 if out else 4:-1]), refs[-1]
    if out:
        w_ref, u_ref, qg_ref, kd_ref, p_ref, decay_ref = refs[:6]
        o_ref = outs.pop(0)
    else:
        w_ref, u_ref, kd_ref, decay_ref = refs[:4]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    if save:
        outs.pop(0)[0, 0] = s
    sr = s.astype(dtype)
    vn = (u_ref[0, 0] - _dot(w_ref[0, 0], sr, 'nn', dtype)).astype(dtype)
    if out:
        o = _dot(qg_ref[0, 0], sr, 'nn', dtype) \
            + _dot(p_ref[0, 0], vn, 'nn', dtype)
        for h in range(o.shape[0]):      # a head a sublane of a token's tile
            o_ref[0, :, h, :] = o[h]
    decay = decay_ref[0, 0]
    s_ref[...] = s * (_as_rows(decay) if channel else decay) \
        + _dot(kd_ref[0, 0], vn, 'tn', dtype)


def _bwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, decay_ref, s_ref,
                do_ref, dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref,
                ddecay_ref, ds_ref, *, dtype, channel=False):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    w, qg, kd, p = w_ref[0, 0], qg_ref[0, 0], kd_ref[0, 0], p_ref[0, 0]
    s, ds1 = s_ref[0, 0], ds_ref[...]
    do = jnp.stack([do_ref[0, :, h, :] for h in range(s.shape[0])])
    sr, do, ds1r = s.astype(dtype), do.astype(dtype), ds1.astype(dtype)
    vn = (u_ref[0, 0] - _dot(w, sr, 'nn', dtype)).astype(dtype)
    # O = Qg S + P Vn and S' = decay S + Kd^T Vn hand Vn its cotangent
    dvn = _dot(p, do, 'tn', dtype) + _dot(kd, ds1r, 'nn', dtype)
    du_ref[0, 0] = dvn
    dvn = dvn.astype(dtype)
    dp_ref[0, 0] = _dot(do, vn, 'nt', dtype).astype(dp_ref.dtype)
    dkd_ref[0, 0] = _dot(vn, ds1r, 'nt', dtype).astype(dkd_ref.dtype)
    dqg_ref[0, 0] = _dot(do, sr, 'nt', dtype).astype(dqg_ref.dtype)
    # Vn = U - W S
    dw_ref[0, 0] = (-_dot(dvn, sr, 'nt', dtype)).astype(dw_ref.dtype)
    decay = decay_ref[0, 0]
    if channel:
        ddecay_ref[0, 0] = _as_lanes(jnp.sum(ds1 * s, axis=2, keepdims=True))
        decay = _as_rows(decay)
    else:
        ddecay_ref[0, 0] = jnp.sum(ds1 * s, axis=1, keepdims=True)
    ds_ref[...] = ds1 * decay + _dot(qg, do, 'tn', dtype) \
        - _dot(w, dvn, 'tn', dtype)


def _specs(arrays, heads, chunk_of):
    """A block of `heads` value heads of one chunk of [N, B, H, ...]
    arrays; grid (row, step of heads, chunk), `chunk_of` the chunk a grid
    step walks."""
    return [pl.BlockSpec(
        (1, 1, heads) + a.shape[3:],
        lambda i, j, k, n=len(a.shape): (chunk_of(k), i, j) + (0,) * (n - 3))
        for a in arrays]


def _spread(decay, dv):
    """[N, B, H] -> [N, B, H, 1, Dv]: a head's decay along a row of lanes;
    [N, B, H, Dk], a decay a channel, is that row already."""
    if decay.ndim == 4:
        return decay.astype(_F32)[..., None, :]
    return jnp.broadcast_to(decay.astype(_F32)[..., None, None],
                            decay.shape + (1, dv))


def _token_spec(c, heads, dv, chunk_of):
    """A chunk's tokens of the step's heads in [B, T, H, Dv]."""
    return pl.BlockSpec((1, c, heads, dv),
                        lambda i, j, k: (i, chunk_of(k), j, 0))


@functools.partial(jax.jit, static_argnames=('dtype', 'heads', 'out', 'save',
                                             'interpret'))
def _forward(w, u, qg, kd, p, decay, *, dtype, heads, out, save, interpret):
    n, bsz, h, c, dk = w.shape
    dv = u.shape[-1]
    like = jax.ShapeDtypeStruct
    ins = (w, u, qg, kd, p) if out else (w, u, kd)
    ins += (_spread(decay, dv),)
    outs, out_specs = [], []
    if out:
        outs.append(like((bsz, n * c, h, dv), _F32))
        out_specs.append(_token_spec(c, heads, dv, lambda k: k))
    if save:
        outs.append(like((n, bsz, h, dk, dv), _F32))
        out_specs += _specs(outs[-1:], heads, lambda k: k)
    got = pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype, out=out, save=save,
                          **({'channel': True} if decay.ndim == 4 else {})),
        grid=(bsz, h // heads, n),
        in_specs=_specs(ins, heads, lambda k: k),
        out_specs=out_specs, out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))(*ins)
    got = list(got)
    return (got.pop(0) if out else None, got.pop(0) if save else None)


@functools.partial(custom_dce.custom_dce, static_argnums=(6, 7, 8))
def _forward_kept(w, u, qg, kd, p, decay, dtype, heads, interpret):
    """(O, S at each chunk's start). A caller reads one of the two: the
    op's forward O, its backward the starts, and the call that is left
    computes and moves nothing for the other."""
    return _forward(w, u, qg, kd, p, decay, dtype=dtype, heads=heads,
                    out=True, save=True, interpret=interpret)


@_forward_kept.def_dce
def _forward_used(dtype, heads, interpret, used, w, u, qg, kd, p, decay):
    return _forward(w, u, qg, kd, p, decay, dtype=dtype, heads=heads,
                    out=used[0], save=used[1], interpret=interpret)


@functools.partial(jax.jit, static_argnames=('dtype', 'heads', 'interpret'))
def _backward(w, u, qg, kd, p, decay, starts, do, *, dtype, heads,
              interpret):
    n, bsz, h, c = w.shape[:4]
    dv = u.shape[-1]
    like = jax.ShapeDtypeStruct
    ins = (w, u, qg, kd, p, _spread(decay, dv), starts, do)
    channel = decay.ndim == 4
    outs = [like(a.shape, a.dtype) for a in (w, u, qg, kd, p)] \
        + [like(ins[5].shape, _F32)]
    dw, du, dqg, dkd, dp, ddecay = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype,
                          **({'channel': True} if channel else {})),
        grid=(bsz, h // heads, n),
        in_specs=_specs(ins[:-1], heads, lambda k: n - 1 - k)
        + [_token_spec(c, heads, dv, lambda k: n - 1 - k)],
        out_specs=_specs(outs, heads, lambda k: n - 1 - k), out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads,) + starts.shape[3:], _F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')))(*ins)
    if channel:
        return dw, du, dqg, dkd, dp, ddecay[..., 0, :].astype(decay.dtype)
    return dw, du, dqg, dkd, dp, \
        jnp.sum(ddecay, axis=(3, 4)).astype(decay.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(w, u, qg, kd, p, decay, dtype, heads, interpret):
    return _forward_kept(w, u, qg, kd, p, decay, dtype, heads, interpret)[0]


def _scan_fwd(w, u, qg, kd, p, decay, dtype, heads, interpret):
    return _scan(w, u, qg, kd, p, decay, dtype, heads, interpret), \
        (w, u, qg, kd, p, decay)


def _scan_bwd(dtype, heads, interpret, xs, do):
    starts = _forward_kept(*xs, dtype, heads, interpret)[1]
    return _backward(*xs, starts, do.astype(_F32), dtype=dtype, heads=heads,
                     interpret=interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def gated_delta_scan(xs, dtype, interpret):
    """`xs` = (W, U, Qg, Kd, P, decay) of every chunk as stage `gdn_intra`
    hands them over: W, Qg, Kd [N, B, H, C, Dk], P [N, B, H, C, C], U
    [N, B, H, C, Dv] float32, decay [N, B, H] float32, or [N, B, H, Dk]
    where the state's rows decay each at its own rate (the rank says
    which); `dtype` the matmuls'. Returns O [B, N x C, H, Dv] float32, the
    tokens' outputs of
    the scan from S = 0. Differentiable in all six: the backward keeps
    `xs` alone, walks the chunks forward again for S at each chunk's
    start (a temporary, [N, B, H, Dk, Dv] float32) and then in reverse."""
    return _scan(*xs, jnp.dtype(dtype), _heads(xs[0].shape[2]), interpret)
