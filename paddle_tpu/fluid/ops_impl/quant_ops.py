"""Quantization op rules: explicit int8 <-> float boundaries in the IR.

Parity: the reference grew fake_quantize/fake_dequantize operators
(paddle/fluid/operators/fake_quantize_op.*) for its slim/quant-aware
tooling — scales computed per tensor or per channel, int8 storage for
inference. Here the same boundaries are three PURE rules the quant pass
(fluid/passes/quant_pass.py) inserts, so `analysis`, provenance and
`program_lint` see every precision change as a real op, and constant
folding can evaluate a `quantize` of a frozen weight at optimization time through
the rule itself (one definition of the rounding semantics).

Scheme (docs/perf.md#quantized-inference carries the tolerance table):
symmetric linear int8, per-channel absmax scales — `scale[ch] =
max|x[ch]| / 127` (floored so all-zero channels stay finite), `q =
clip(round(x / scale), -127, 127)`. Scales keep their reduced axes
(`[V, 1]` for a row-quantized table), so dequantize is a plain
broadcast multiply and the scales ship as ordinary persistables.

All three rules are deterministic, context-free functions of their
inputs — foldable by fluid.passes (is_foldable) by construction.
"""
import jax
import jax.numpy as jnp

from ..lowering import register, data_of, like

# absmax floor: keeps all-zero channels' scales finite; round-trips of
# genuinely zero rows stay exactly zero because q is 0 there anyway
SCALE_FLOOR = 1e-12
QMAX = 127.0


def quantize_array(x, axis=0):
    """(q int8, scale f32 keepdims) for per-channel symmetric absmax
    quantization along `axis`. Shared by the lowering rule, the offline
    weight quantizer (passes.quant_pass.quantize_weights) and the
    embedding row store (embedding.quant_rows) — ONE definition of the
    rounding semantics."""
    x = jnp.asarray(x, jnp.float32)
    axes = tuple(a for a in range(x.ndim) if a != axis % max(x.ndim, 1))
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax / QMAX, SCALE_FLOOR)
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@register('quantize')
def _quantize(ins, attrs, ctx):
    q, scale = quantize_array(data_of(ins['X'][0]),
                              axis=int(attrs.get('axis', 0)))
    return {'Out': q, 'Scale': scale}


@register('dequantize')
def _dequantize(ins, attrs, ctx):
    q = data_of(ins['X'][0])
    scale = data_of(ins['Scale'][0])
    return {'Out': q.astype(jnp.float32) * scale}


@register('quant_lookup_table')
def _quant_lookup_table(ins, attrs, ctx):
    """lookup_table over an int8 row-quantized table: gather the int8
    rows AND their [V, 1] scales by id, dequantize AFTER the gather — the
    fp32 [V, D] table never materializes, so serving HBM for the
    embedding is the int8 bytes + one f32 scale per row (the vocab-per-
    HBM-byte doubling docs/perf.md claims). Semantics match
    sequence_ops._lookup_table_dense exactly: dequant-then-gather and
    gather-then-dequant are the same elementwise math, and padding_idx
    zeroes the row via its SCALE (0 * q == 0.0, the dense rule's
    `w.at[pad].set(0)`)."""
    w = data_of(ins['W'][0])                         # int8 [V, D]
    scale = data_of(ins['Scale'][0])                 # f32 [V, 1]
    ids_v = ins['Ids'][0]
    ids = data_of(ids_v).astype(jnp.int32)
    if ids.shape and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, -1)
    if attrs.get('padding_idx') is not None and attrs['padding_idx'] >= 0:
        scale = scale.at[attrs['padding_idx']].set(0.0)
    rows = jnp.take(w, ids, axis=0).astype(jnp.float32)
    row_scale = jnp.take(scale, ids, axis=0)
    # scale keepdims [V, 1] gathers to [..., 1]: broadcasts over the
    # embedding dim whatever the id rank
    out = rows * row_scale
    from .lod_beam import is_beam_form
    if is_beam_form(ids_v) and out.ndim == ids.ndim + 1:
        out = out[:, None]
    return {'Out': like(ids_v, out)}
