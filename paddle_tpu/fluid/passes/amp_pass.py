"""AMP as an IR rewrite: explicit casts instead of trace-time casting.

Runtime AMP (`lowering.amp_cast`, armed by `ctx.amp`) silently casts the
float32 operands of matmul/conv/attention ops to bfloat16 inside the
rule — invisible to the Program IR, to `fluid.analysis`, to provenance,
and to `program_lint`. This pass makes the same decision VISIBLE: for
each AMP-eligible op it inserts `cast` ops (f32 -> bf16) in front of the
op's float operands, repoints the op at the casted temps, and — when the
rule's inferred output is bf16 where the var declared f32 — routes the
op through a bf16 temp and casts back to f32, so downstream ops see
exactly the dtype runtime AMP produced. The rewritten program then runs
with `ctx.amp` OFF (`program._amp_ir` marks it); `ctx.amp` remains only
as the compatibility flag for unoptimized programs.

Numerics vs runtime AMP (the documented tolerance, docs/passes.md): the
op's result passes through one extra f32->bf16 rounding at the region
boundary (runtime AMP casts the result straight back to f32 inside the
rule; here the boundary is a real bf16 value a cast op widens). Relative
error is bounded by one bf16 ulp (~2^-8) of the op output; everything
outside the rewritten regions is bit-identical.

Eligibility is decided per op: when the rule cannot abstract-eval on the
hypothetical bf16 operand specs, the op is left on f32 (MORE precise
than runtime AMP, still within the documented tolerance) and counted in
the report.
"""
import jax

from ... import obs
from .. import lowering
from ..framework import Operator
from . import OP_SEQ_ATTR

__all__ = ['run', 'AMP_SLOTS']

_C_CASTS = obs.counter('passes.amp.casts_inserted')
_C_REWRITTEN = obs.counter('passes.amp.ops_rewritten')

# op type -> input slots runtime amp_cast covers. The moe rule casts its
# experts' weights and, inside the rule, the rows it gives them; X stays as
# it comes because the router reads it in float32, and GateW is the
# router's. rms_norm and rotary_embedding are not here: their statistics
# and angles are float32 whatever they are fed.
AMP_SLOTS = {
    'mul': ('X', 'Y'),
    'matmul': ('X', 'Y'),
    'conv2d': ('Input', 'Filter'),
    'flash_attention': ('Q', 'K', 'V'),
    'moe_mlp': ('W1', 'B1', 'W2', 'B2', 'W3'),
}


def _bf16_spec(spec):
    if isinstance(spec, lowering.SeqValue):
        return lowering.SeqValue(_bf16_spec(spec.data), spec.lengths,
                                 spec.outer_lengths)
    return jax.ShapeDtypeStruct(spec.shape, 'bfloat16')


def _cast_op(block, src, dst, dtype, seq_attr):
    return Operator(block, type='cast', inputs={'X': [src]},
                    outputs={'Out': [dst]},
                    attrs={'out_dtype': dtype, OP_SEQ_ATTR: seq_attr},
                    callsite=getattr(src.op, 'callsite', None))


def run(program, report):
    """Rewrite AMP regions in place (program is optimize()'s clone).
    Returns the number of ops rewritten."""
    from . import written_names
    block = program.global_block()
    version = {}            # name -> write version (the block is not SSA)
    cast_cache = {}         # (name, version) -> casted Variable
    new_ops = []
    inserted = rewritten = skipped = 0
    bw_cache = {}

    def bump(op):
        # written_names, not output_arg_names: an undeclared sub-block
        # write (while body updating an outer f32 var) must invalidate
        # the cast_cache entry for that name
        for n in written_names(program, op, cache=bw_cache):
            version[n] = version.get(n, 0) + 1

    for op in block.ops:
        if op.type not in AMP_SLOTS:
            new_ops.append(op)
            bump(op)
            continue
        slots = AMP_SLOTS[op.type]
        targets = []
        in_specs, specs_ok = {}, True
        for slot, vs in op.inputs.items():
            row = []
            for j, v in enumerate(vs):
                s = lowering.spec_of(v)
                if s is None:
                    specs_ok = False
                row.append(s)
                if v.dtype == 'float32' and slot in slots:
                    targets.append((slot, j, v))
            in_specs[slot] = row
        if not targets:
            new_ops.append(op)
            bump(op)
            continue
        outs = None
        if specs_ok:
            for slot, j, v in targets:
                in_specs[slot][j] = _bf16_spec(in_specs[slot][j])
            try:
                outs = lowering.abstract_eval(op, in_specs)
            except Exception:
                outs = None
        if outs is None:
            # cannot prove the rewrite's dtypes: leave the op on f32
            # (more precise than runtime amp; documented tolerance)
            skipped += 1
            new_ops.append(op)
            bump(op)
            continue
        seq = op.attrs.get(OP_SEQ_ATTR, 0)
        orig_out_names = list(op.output_arg_names)
        for slot, j, v in targets:
            ck = (v.name, version.get(v.name, 0))
            cv = cast_cache.get(ck)
            if cv is None:
                cv = block.create_var(
                    name='%s@amp.v%d.bf16' % (v.name, ck[1]),
                    shape=list(v.shape) if v.shape is not None else None,
                    dtype='bfloat16', lod_level=v.lod_level)
                new_ops.append(_cast_op(block, v, cv, 'bfloat16', seq))
                cast_cache[ck] = cv
                inserted += 1
            op.inputs[slot][j] = cv
        new_ops.append(op)
        # bf16 outputs where f32 was declared: route through a bf16 temp
        # and cast back, so downstream dtypes match runtime amp exactly
        for slot, vs in op.outputs.items():
            vals = outs.get(slot) if hasattr(outs, 'get') else None
            if vals is None:
                continue
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            for j, (var, val) in enumerate(zip(vs, vals)):
                if val is None:
                    continue
                spec = val.data if isinstance(val, lowering.SeqValue) \
                    else val
                if str(spec.dtype) == 'bfloat16' and var.dtype == 'float32':
                    ov = block.create_var(
                        name=var.name + '@amp.out.bf16',
                        shape=(list(var.shape) if var.shape is not None
                               else None),
                        dtype='bfloat16', lod_level=var.lod_level)
                    ov.op = op
                    op.outputs[slot][j] = ov
                    new_ops.append(_cast_op(block, ov, var, 'float32',
                                            seq))
                    inserted += 1
        for n in orig_out_names:
            version[n] = version.get(n, 0) + 1
        rewritten += 1

    if rewritten or inserted:
        block.ops = new_ops
        program._bump_version()
        _C_CASTS.inc(inserted)
        _C_REWRITTEN.inc(rewritten)
    # the rewritten program must NOT also runtime-cast: amp becomes an
    # IR property; _amp_ir tells the executor to force ctx.amp off even
    # when the global amp_guard armed it
    program._amp = False
    program._amp_ir = True
    report.note('amp', ops_rewritten=rewritten, casts_inserted=inserted,
                ops_skipped=skipped)
    return rewritten
