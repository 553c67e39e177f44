"""Optimizer update rules.

Parity: reference paddle/fluid/operators/{sgd,momentum,adam,adagrad,adamax,
decayed_adagrad,rmsprop,ftrl,adadelta}_op.* — each lowers to a pure update
fused into the same XLA module as forward+backward, so the whole train step
is one device launch (the reference dispatches one CUDA kernel per param per
optimizer op).
"""
import jax
import jax.numpy as jnp

from ..lowering import register, data_of, SparseRows, use_kernel


def _lr(ins):
    return data_of(ins['LearningRate'][0]).reshape(())


def _merge_sparse(g, ctx=None):
    """Merge duplicate ids of a SparseRows grad (reference MergeAdd,
    operators/math/selected_rows_functor.cc): nonlinear updates (adagrad's
    g^2, adam's moments) must see each touched row ONCE with its summed
    gradient. Static shapes: sort the N occurrences, segment-sum into at
    most N merged rows, and return (uids int32[N], merged [N, D],
    valid bool[N]) where invalid slots carry zero rows and id 0 — callers
    mask their update deltas with `valid` so the padding rows are no-ops.

    Sharded case (docs/embedding.md): when the step is compiled against a
    mesh (ctx.mesh) the merge's [N, *] intermediates are PINNED replicated
    — N is batch-sized, and without the pin GSPMD has to invent layouts
    for the argsort/segment-sum chain from the (axis-sharded) cotangents
    feeding it, which is exactly the replicate-then-repartition class the
    remat detector flags. The row scatter the CALLER then does against the
    row-sharded table partitions per shard (each shard applies the deltas
    for rows it owns), and the step's out-sharding constraint keeps the
    table's layout a fixed point — the dense [vocab, dim] gradient never
    exists under either layout.

    The sort/segment/unsort core is embedding.lookup.dedup_plan — ONE
    definition of the static-shape dedup invariant serves both the
    lookup wire's query side and this merge."""
    from ...embedding.lookup import dedup_plan
    ids, rows = g.ids, g.rows
    if ctx is not None and getattr(ctx, 'mesh', None) is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(ctx.mesh, PartitionSpec())
        ids = jax.lax.with_sharding_constraint(ids, rep)
        rows = jax.lax.with_sharding_constraint(rows, rep)
    n = ids.shape[0]
    uids, seg, order, n_unique = dedup_plan(ids.astype(jnp.int32))
    merged = jax.ops.segment_sum(rows[order], seg, num_segments=n)
    valid = jnp.arange(n) < n_unique
    # invalid slots carry dedup_plan's sentinel id: clamp to 0 so the
    # callers' moment GATHERS at uids stay in-bounds (their scattered
    # deltas are already masked with `valid`)
    uids = jnp.where(valid, uids, 0)
    return uids, merged, valid


@register('sgd')
def _sgd(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = ins['Grad'][0]
    if isinstance(g, SparseRows):
        # index-based row update (reference sgd_op.h SelectedRows branch):
        # scatter-add handles duplicate ids exactly like the dense path
        # (SGD is linear in the gradient), and the vocab-sized dense grad
        # buffer never exists
        return {'ParamOut': p.at[g.ids].add(-_lr(ins) * g.rows)}
    return {'ParamOut': p - _lr(ins) * data_of(g)}


@register('momentum')
def _momentum(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    v = data_of(ins['Velocity'][0])
    mu = attrs['mu']
    lr = _lr(ins)
    v_out = mu * v + g
    if attrs.get('use_nesterov', False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {'ParamOut': p_out, 'VelocityOut': v_out}


@register('adagrad')
def _adagrad(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = ins['Grad'][0]
    m = data_of(ins['Moment'][0])
    eps = attrs.get('epsilon', 1e-6)
    lr = _lr(ins)
    if isinstance(g, SparseRows):
        # touched-rows-only update on merged duplicates
        from ...ops import kernels
        uids, gm, valid = _merge_sparse(g, ctx)
        # fused pallas path: gather + moment math + scatter in ONE call,
        # tables aliased in place (per-shard-local — sharded steps keep
        # the XLA path, whose scatter partitions under the mesh)
        if getattr(ctx, 'mesh', None) is None and \
                use_kernel(ctx, 'sparse_adagrad'):
            p_out, m_out = kernels.fused_sparse_adagrad(
                p, m, uids, gm, valid, lr, eps,
                interpret=ctx.pallas_interpret)
        else:
            p_out, m_out = kernels.sparse_adagrad_reference(
                p, m, uids, gm, valid, lr, eps)
        return {'ParamOut': p_out, 'MomentOut': m_out}
    g = data_of(g)
    m_out = m + g * g
    p_out = p - lr * g / (jnp.sqrt(m_out) + eps)
    return {'ParamOut': p_out, 'MomentOut': m_out}


@register('adam')
def _adam(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = ins['Grad'][0]
    m1 = data_of(ins['Moment1'][0])
    m2 = data_of(ins['Moment2'][0])
    b1p = data_of(ins['Beta1Pow'][0]).reshape(())
    b2p = data_of(ins['Beta2Pow'][0]).reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    lr = _lr(ins) * jnp.sqrt(1 - b2p) / (1 - b1p)
    if isinstance(g, SparseRows):
        # duplicates are merged first so the nonlinear moment math sees
        # each row's summed grad once; lr is already bias-corrected,
        # exactly what both paths apply per row (see adagrad above)
        from ...ops import kernels
        uids, gm, valid = _merge_sparse(g, ctx)
        if getattr(ctx, 'mesh', None) is None and \
                use_kernel(ctx, 'sparse_adam'):
            p_out, m1_out, m2_out = kernels.fused_sparse_adam(
                p, m1, m2, uids, gm, valid, lr, b1, b2, eps,
                interpret=ctx.pallas_interpret)
        else:
            p_out, m1_out, m2_out = kernels.sparse_adam_reference(
                p, m1, m2, uids, gm, valid, lr, b1, b2, eps)
        return {'ParamOut': p_out, 'Moment1Out': m1_out,
                'Moment2Out': m2_out}
    g = data_of(g)
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * g * g
    p_out = p - lr * m1_out / (jnp.sqrt(m2_out) + eps)
    return {'ParamOut': p_out, 'Moment1Out': m1_out, 'Moment2Out': m2_out}


@register('adam_beta_pow_update')
def _adam_beta_pow_update(ins, attrs, ctx):
    b1p = data_of(ins['Beta1Pow'][0])
    b2p = data_of(ins['Beta2Pow'][0])
    return {'Beta1PowOut': b1p * attrs.get('beta1', 0.9),
            'Beta2PowOut': b2p * attrs.get('beta2', 0.999)}


@register('adamax')
def _adamax(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    m = data_of(ins['Moment'][0])
    inf_norm = data_of(ins['InfNorm'][0])
    b1p = data_of(ins['Beta1Pow'][0]).reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf_norm, jnp.abs(g))
    lr = _lr(ins) / (1 - b1p)
    p_out = p - lr * m_out / (inf_out + eps)
    return {'ParamOut': p_out, 'MomentOut': m_out, 'InfNormOut': inf_out}


@register('decayed_adagrad')
def _decayed_adagrad(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    m = data_of(ins['Moment'][0])
    decay = attrs.get('decay', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    m_out = decay * m + (1 - decay) * g * g
    p_out = p - _lr(ins) * g / (jnp.sqrt(m_out) + eps)
    return {'ParamOut': p_out, 'MomentOut': m_out}


@register('rmsprop')
def _rmsprop(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    ms = data_of(ins['MeanSquare'][0])
    mom = data_of(ins['Moment'][0])
    rho = attrs.get('decay', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    momentum = attrs.get('momentum', 0.0)
    ms_out = rho * ms + (1 - rho) * g * g
    mom_out = momentum * mom + _lr(ins) * g / jnp.sqrt(ms_out + eps)
    return {'ParamOut': p - mom_out, 'MomentOut': mom_out, 'MeanSquareOut': ms_out}


@register('adadelta')
def _adadelta(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    avg_sq_g = data_of(ins['AvgSquaredGrad'][0])
    avg_sq_u = data_of(ins['AvgSquaredUpdate'][0])
    rho = attrs.get('rho', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * g * g
    update = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * update * update
    return {'ParamOut': p + update, 'AvgSquaredGradOut': g2,
            'AvgSquaredUpdateOut': u2}


@register('ftrl')
def _ftrl(ins, attrs, ctx):
    p = data_of(ins['Param'][0])
    g = data_of(ins['Grad'][0])
    sq = data_of(ins['SquaredAccumulator'][0])
    lin = data_of(ins['LinearAccumulator'][0])
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    lr_power = attrs.get('lr_power', -0.5)
    lr = _lr(ins)
    new_sq = sq + g * g
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    pre = jnp.clip(new_lin, -l1, l1) - new_lin
    p_out = pre / denom
    return {'ParamOut': p_out, 'SquaredAccumOut': new_sq, 'LinearAccumOut': new_lin}
