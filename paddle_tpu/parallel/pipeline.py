"""Pipeline parallelism: microbatch streaming over a mesh axis — GPipe and
the circular (interleaved / virtual-stage) schedule.

TPU-first design (the reference's closest notion is device placement of
ops; it has no pipeline engine): stage parameters are STACKED on a leading
[n_stages, ...] axis sharded over the `pp` mesh axis, so each device holds
exactly its stages' weights. Inside shard_map, a lax.scan runs the classic
collective-permute pipeline: every tick each device applies one stage to
the activation it holds, then the ring `ppermute` hands the result to the
next device while the first device ingests the next microbatch.

With n_virtual == 1 this is GPipe: n_micro + S - 1 ticks, bubble fraction
(S-1)/(n_micro+S-1) — raise n_micro to amortize.

With n_virtual == v > 1 it is the circular schedule (Megatron/praxis
"interleaved 1F1B" loop placement): the model is cut into v*S chunks,
device d holding chunks {p*S + d : p < v}, and each microbatch rides the
ring v times. Microbatches are injected in rounds of S (n_micro must be a
multiple of S); the schedule position u = t - d decomposes uniquely as
u = ((r*v + p)*S + j), so every device applies exactly one chunk per tick
with no collisions. Total ticks v*n_micro + S - 1, each 1/v the cost of a
GPipe stage — the fill/drain bubble shrinks by v while per-device weight
memory stays the same. The backward schedule falls out of XLA transposing
the scan, exactly as for GPipe.

`extras` are per-call tensors every stage reads but none produce (pad-mask
biases, encoder output for a pipelined decoder stack): replicated over the
pp axis and passed to stage_fn after the activation. This is what lets a
full Fluid transformer stack — not just a toy closure — run through the
pipeline (see fluid/transpiler/pipeline_transpiler.py).
"""
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ._sp import stack_unit_params

__all__ = ['pipeline_apply', 'pipeline_manual_axes', 'stack_stage_params']


def pipeline_manual_axes(mesh, axis='pp'):
    """The mesh axes pipeline_apply's shard_map goes MANUAL over: dp, sp
    and the pipeline axis (tp stays automatic for GSPMD). Single source of
    truth — the Executor passes this same set into the stage Ctx so the
    attention lowering's per-shard routing always agrees with the actual
    shard_map axis_names."""
    return frozenset(a for a in ('dp', 'sp', axis) if a in mesh.shape)

# [{param pytree} per stage] -> pytree with leading [n_stages, ...] axis
stack_stage_params = stack_unit_params


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, axis='pp',
                   extras=(), extras_streamed=(), n_virtual=1,
                   param_specs=None):
    """Run the pipeline.

    stage_fn(params, x, *extras_streamed_mb, *extras) -> y
                    same signature for every stage; all stages must map
                    [mb, ...] -> same shape/dtype (equal widths — pad if
                    needed)
    stacked_params: pytree, leaves [n_virtual * S, ...] (S = pp axis size)
                    in sequential stage order — chunk g runs as phase
                    g // S on device g % S
    microbatches:   [n_micro, mb, ...] (replicated or batch-sharded on dp)
    extras:         global tensors every stage reads whole (tied weights,
                    precomputed tables) — replicated over `axis`
    extras_streamed: batch-aligned tensors ([n_micro, mb, ...], microbatched
                    like x: pad-mask biases, a pipelined decoder's encoder
                    output). Each device dynamic-indexes its OWN in-flight
                    microbatch slice — the tensors do not ride the ring.
                    CONTRACT under an 'sp' mesh axis: every streamed extra
                    must be sequence-shaped [batch, seq, ...] (seq % sp
                    == 0) — dim 2 post-microbatching is sharded over sp
                    like the activation's. A per-row feature extra
                    [batch, d] would have its FEATURE dim sharded;
                    restructure it as a replicated `extras` entry or fold
                    it into the activation when composing with sp.
    n_virtual:      chunks per device (circular schedule); > 1 requires
                    n_micro to be a multiple of S.
    Returns [n_micro, mb, ...]: the final chunk's output per microbatch.
    """
    S = mesh.shape[axis]
    v = int(n_virtual)
    n_micro = microbatches.shape[0]
    if v < 1:
        raise ValueError('n_virtual must be >= 1, got %d' % v)
    # an empty pytree (activation-only stages) is valid: nothing to shard
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != v * S:
            raise ValueError(
                'pipeline stage: stacked leading dim %d (leaf shape %r) '
                'must equal mesh axis %r size %d times n_virtual=%d (one '
                'chunk per device per phase)'
                % (leaf.shape[0], tuple(leaf.shape), axis, S, v))
    if v > 1 and n_micro % S:
        raise ValueError(
            'circular pipeline (n_virtual=%d) injects microbatches in '
            'rounds of S=%d; n_micro=%d is not a multiple' % (v, S, n_micro))
    from jax import shard_map
    n_stream = len(extras_streamed)

    # [v*S, ...] sequential chunk order -> [v, S, ...]: row p column d is
    # chunk p*S + d, so sharding dim 1 over the pp axis gives device d its
    # phase-indexed chunk block [v, 1, ...]
    stacked_params = jax.tree_util.tree_map(
        lambda w: w.reshape((v, S) + w.shape[1:]), stacked_params)
    if param_specs is not None:
        # pin the reshaped stack's layout: phase dim replicated, stage dim
        # over the pipeline axis, trailing dims keeping each weight's own
        # (tp) spec — GSPMD otherwise invents the transition from the
        # per-stage persisted shardings and falls back to full remat
        stacked_params = jax.tree_util.tree_map(
            lambda w, sp: lax.with_sharding_constraint(
                w, jax.sharding.NamedSharding(
                    mesh, P(None, axis, *sp))),
            stacked_params, param_specs)

    # Axes left AUTOMATIC inside the shard_map (tp): the per-tick
    # dynamic-slice of the microbatch stack, the scan carry, and the ring
    # ppermute output carry no natural tp sharding, so GSPMD used to
    # invent transitions for them — "Involuntary full rematerialization"
    # (replicate-then-repartition every tick; MULTICHIP_r04 tail). The
    # Megatron layout is unambiguous: ACTIVATIONS are replicated over tp,
    # only weights are tp-sharded (the column-split matmul consumes a
    # replicated x; the row-split one psums back to replicated). Pin that
    # with explicit constraints — specs mention no manual axis, so they
    # are legal inside the manual shard_map.
    manual_set = pipeline_manual_axes(mesh, axis)
    auto_axes = [a for a in mesh.shape if a not in manual_set]
    if auto_axes:
        # NamedSharding over a mesh whose axis types MATCH the shard_map
        # context (dp/pp/sp Manual, tp Auto): the raw all-Auto mesh fails
        # the context-mesh check when jax transposes the constraint in the
        # backward pass, and a bare PartitionSpec is too weak to stop the
        # partitioner's replicate-then-repartition on the matmul cotangent
        from jax.sharding import AxisType, Mesh as _Mesh, NamedSharding
        pin_mesh = _Mesh(
            mesh.devices, mesh.axis_names,
            axis_types=tuple(AxisType.Manual if n in manual_set
                             else AxisType.Auto for n in mesh.axis_names))
        _tp_replicated = lambda t: lax.with_sharding_constraint(
            t, NamedSharding(pin_mesh, P()))
    else:
        _tp_replicated = lambda t: t

    def body(params, mbs, *ex):
        stream, glob = ex[:n_stream], ex[n_stream:]
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == S - 1
        T = v * n_micro + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            held = carry  # [mb, ...] activation each device currently holds
            # schedule position: u = ((r*v + p)*S + j) uniquely — device
            # idx works round r, phase p, round-slot j at tick t
            u = t - idx
            j = u % S
            q = u // S
            if v > 1:
                p = q % v
                mb = (q // v) * S + j
            else:
                p = 0
                mb = u
            mb_c = jnp.clip(mb, 0, n_micro - 1)
            # first device ingests a fresh microbatch on phase 0; on later
            # phases it consumes the wrap-around activation from the ring
            # slice with keepdims and pin the 4-D [1, mb, ...] slice
            # BEFORE dropping the unit dim: the transpose of this chain is
            # a dynamic-update-slice of exactly that [1, mb, ...] cotangent
            # chunk, so the pin sits next to the scatter input. (One
            # degenerate cotangent transition in the dp x pp x tp segment
            # still draws a partitioner warning — docs/distributed.md,
            # "Known partitioner residue".)
            def slice_mb(t):
                s = _tp_replicated(
                    lax.dynamic_slice_in_dim(t, mb_c, 1, axis=0))
                return s[0]
            fresh = slice_mb(mbs)
            ingest = is_first if v == 1 else (is_first & (p == 0))
            # constraining x (not just fresh) matters for the BACKWARD
            # too: dx, the stage matmul's input cotangent, inherits the pin
            x = _tp_replicated(jnp.where(ingest, fresh, held))
            sex = [slice_mb(e) for e in stream]
            if v > 1:
                chunk = jax.tree_util.tree_map(
                    lambda w: lax.dynamic_index_in_dim(
                        w, p, axis=0, keepdims=False)[0], params)
            else:
                chunk = jax.tree_util.tree_map(lambda w: w[0, 0], params)
            y = stage_fn(chunk, x, *sex, *glob)
            # the last device completes microbatch mb on the final phase
            emit = (u >= 0) & (mb < n_micro) & (p == v - 1)
            emit_idx = jnp.where(emit, mb_c, -1)
            # everyone passes its output to the next device; the wraparound
            # (last -> first) either advances the phase or is ignored by
            # the first device's ingest above
            y = _tp_replicated(y)
            handed = lax.ppermute(y, axis, perm)
            return handed, (y, emit_idx)

        init = jnp.zeros(mbs.shape[1:], mbs.dtype)
        _, (ys, emit_idxs) = lax.scan(tick, init, jnp.arange(T))
        # gather the last device's completed outputs in microbatch order
        out = jnp.zeros((n_micro,) + ys.shape[1:], ys.dtype)
        valid = emit_idxs >= 0
        valid_b = valid.reshape(valid.shape + (1,) * (ys.ndim - 1))
        out = out.at[jnp.where(valid, emit_idxs, 0)].add(
            jnp.where(valid_b, ys, 0.0))
        # only the last device holds real outputs; broadcast them to all
        # shards so the result is replicated over the pp axis
        out = jnp.where(is_last, out, 0.0)
        out = lax.psum(out, axis)
        return out

    # compose with data parallel: when the mesh also carries 'dp', the
    # microbatch dim (dim 1 of [n_micro, mb, ...]) stays dp-sharded and
    # every dp slice runs its own pipeline; global extras stay replicated
    dp_axis = 'dp' if ('dp' in mesh.shape and 'dp' != axis) else None
    if dp_axis and microbatches.shape[1] % mesh.shape['dp']:
        raise ValueError(
            'per-microbatch size %d does not divide the dp mesh axis '
            '%d — lower n_micro or the dp size so every dp shard gets '
            'whole microbatch rows' % (microbatches.shape[1],
                                       mesh.shape['dp']))
    # compose with sequence parallel: an 'sp' mesh axis shards the
    # SEQUENCE dim (dim 2 of [n_micro, mb, T, ...]) of the activation and
    # every streamed extra; stage bodies then run sequence-local and the
    # attention lowering rides the sp ring via its per-shard collective
    # body (ops_impl/nn_ops.py routes on ctx.manual_axes)
    sp_axis = 'sp' if ('sp' in mesh.shape and 'sp' != axis) else None
    if sp_axis:
        sp = mesh.shape['sp']
        for t, name in [(microbatches, 'activation')] + \
                [(e, 'streamed extra') for e in extras_streamed]:
            if t.ndim < 3 or t.shape[2] % sp:
                raise ValueError(
                    'pp x sp: the %s (shape %r) needs a sequence dim at '
                    'index 2 divisible by the sp mesh axis size %d — '
                    'under sp every streamed extra must be sequence-shaped '
                    '[batch, seq, ...]; pass per-row features as a '
                    'replicated extra instead (see pipeline_apply '
                    'docstring)' % (name, tuple(t.shape), sp))

    def mbspec(ndim):
        spec = [None, dp_axis, sp_axis] + [None] * (ndim - 3)
        return P(*spec[:ndim])

    mb_spec = mbspec(microbatches.ndim)
    # manual ONLY over dp + sp + the pipeline axis: any other mesh axis
    # (tp) stays automatic, so GSPMD partitions the matmuls INSIDE each
    # stage by the stacked params' Megatron shardings and inserts the tp
    # all-reduces — the Megatron-style dp x pp x tp layout with no
    # hand-written tensor-parallel collectives
    manual = pipeline_manual_axes(mesh, axis)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(None, axis),
                                         stacked_params),
                  mb_spec)
                 + tuple(mbspec(e.ndim) for e in extras_streamed)
                 + tuple(P() for _ in extras),
        out_specs=mb_spec, axis_names=manual, check_vma=False)
    return fn(stacked_params, microbatches, *extras_streamed, *extras)
