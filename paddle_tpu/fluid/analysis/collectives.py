"""Pass 7 — collective-safety lint (docs/analysis.md#pass-7).

Which ops lower to collectives is statically knowable from the lowering
rules plus the program's annotations: a `lookup_table` with
`is_distributed` and a `dist_axis` the mesh declares takes the
all_to_all wire (ops_impl/embedding_ops.dist_lookup_applies), a
fixed-capacity `moe_mlp` rides two all_to_alls when the dp axis divides
num_experts and a dropless one moves nothing (ops_impl/moe_ops), `flash_attention` ppermutes K/V around the ring
when an 'sp' axis exists (ops_impl/nn_ops), and `autodiff` under a mesh
with a data axis implies the GSPMD gradient all-reduce. This pass
derives each block's collective sequence from exactly those conditions
— no jax import, no device — and flags the two hazard classes the
runtime today only survives, not prevents:

  * CollectiveDivergence — a collective under divergent control flow.
    A cond/switch body issuing a collective on one branch only is the
    rendezvous-hang class: devices that take different branches never
    meet at the rendezvous (error). A collective inside a While body is
    the same hazard one remove away — safe only while every device
    runs the same trip count (warning).
  * ConcurrentCollectives — a program declared `concurrent=True`
    (the serving posture: ShardedPredictor verifies with it) that
    issues collectives. Two co-hosted modules interleaving collectives
    on shared devices would pair rendezvous participants across
    modules and deadlock; today that is survived only by the silent
    process-wide `_MESH_DISPATCH_LOCK` in serving/pod.py — the finding
    names the hazard and points at the lock (warning: the lock DOES
    serialize, so the program runs; the lint makes the dependence on
    it visible).

`collective_sequence(program, mesh_axes=)` is the shared derivation the
cost model (analysis/costmodel.py) prices for wire bytes.
"""
from .dataflow import sub_block_indices
from .findings import (COLLECTIVE_DIVERGENCE, CONCURRENT_COLLECTIVES,
                       Finding, SEV_ERROR, SEV_WARNING)

__all__ = ['run_pass', 'collective_sequence', 'op_collectives']


def resolve_axes(program, mesh_axes=None):
    """The mesh spec the pass judges against: the override (program_lint
    --mesh) or the program's own set_mesh() spec, as a plain dict or
    None."""
    if mesh_axes is not None:
        return dict(mesh_axes)
    items = getattr(program, '_mesh_axes', None)
    return dict(items) if items else None


def _data_axis(program, axes):
    """The axis feed batches (and therefore dp gradients) shard over:
    the program's declared data_axis when it is in `axes`, else the
    'dp'/'data' default set_mesh would derive."""
    da = getattr(program, '_mesh_data_axis', None)
    if da and da in axes:
        return da
    for cand in ('dp', 'data'):
        if cand in axes:
            return cand
    return None


def op_collectives(op, program, axes):
    """[(kind, axis)] collectives this op's lowering issues under mesh
    `axes` — the static mirror of the per-op mesh conditions in
    ops_impl/. Empty for ops that lower collective-free."""
    if not axes:
        return []
    t = op.type
    if t in ('lookup_table', 'quant_lookup_table'):
        ax = op.attrs.get('dist_axis')
        if op.attrs.get('is_distributed') and ax in axes:
            # the two-direction exchange: ids out, rows back
            return [('all_to_all', ax), ('all_to_all', ax)]
        return []
    if t == 'moe_mlp':
        try:
            n_exp = int(op.attrs.get('num_experts', 0))
        except (TypeError, ValueError):
            return []
        if op.attrs.get('dropless'):
            # one device only: nothing over the wire (on a mesh that
            # would shard the experts the rule refuses to lower). A held
            # share (`experts_held`) is dropless too: it computes its own
            # experts' part and runs WITHOUT the exchange that would sum
            # the shares on a pod, so it moves nothing either
            return []
        if 'dp' in axes and n_exp and n_exp % axes['dp'] == 0:
            # dispatch + combine
            return [('all_to_all', 'dp'), ('all_to_all', 'dp')]
        return []
    if t == 'flash_attention':
        if 'sp' in axes:
            return [('ppermute', 'sp')]
        return []
    if t == 'autodiff':
        ax = _data_axis(program, axes)
        if ax is not None:
            return [('all_reduce', ax)]
        return []
    return []


def collective_sequence(program, mesh_axes=None, block=None, _seen=None):
    """The statically-derived collective sequence of `block` (default:
    the global block), sub-blocks included, in program order:
    [(block_idx, op_index, op, kind, axis)]."""
    axes = resolve_axes(program, mesh_axes)
    if not axes:
        return []
    if block is None:
        block = program.global_block()
    if _seen is None:
        _seen = set()
    if block.idx in _seen:
        return []
    _seen = _seen | {block.idx}
    seq = []
    for i, op in enumerate(block.ops):
        for kind, ax in op_collectives(op, program, axes):
            seq.append((block.idx, i, op, kind, ax))
        for bi in sub_block_indices(op, program):
            if bi not in _seen:
                seq += collective_sequence(program, mesh_axes,
                                           program.block(bi), _seen)
    return seq


def _block_collectives(program, block, axes, _seen=None):
    """[(op, kind, axis)] issued anywhere under `block` (recursive)."""
    if _seen is None:
        _seen = set()
    if block.idx in _seen:
        return []
    _seen = _seen | {block.idx}
    out = []
    for op in block.ops:
        for kind, ax in op_collectives(op, program, axes):
            out.append((op, kind, ax))
        for bi in sub_block_indices(op, program):
            out += _block_collectives(program, program.block(bi), axes,
                                      _seen)
    return out


def _describe(colls):
    return ', '.join(sorted({'%s(%s) by %s' % (kind, ax, op.type)
                             for op, kind, ax in colls}))


def run_pass(program, concurrent=False, mesh_axes=None):
    """See analysis.analyze for concurrent/mesh_axes. Returns
    [Finding]; empty when the program declares no mesh — without one
    every op lowers collective-free."""
    axes = resolve_axes(program, mesh_axes)
    if not axes:
        return []
    findings = []

    # divergence: collectives inside control-flow bodies
    for blk in program.blocks:
        for op in blk.ops:
            sub_idxs = sub_block_indices(op, program)
            if not sub_idxs:
                continue
            per_branch = [_block_collectives(program, program.block(bi),
                                             axes) for bi in sub_idxs]
            if not any(per_branch):
                continue
            if op.type == 'while':
                colls = [c for branch in per_branch for c in branch]
                findings.append(Finding.for_op(
                    COLLECTIVE_DIVERGENCE, SEV_WARNING,
                    'While body issues collective(s) [%s]: safe only '
                    'while every device runs the SAME trip count — a '
                    'divergent condition strands part of the mesh at '
                    'the rendezvous (hang, not error)'
                    % _describe(colls), op,
                    var_names=sorted({o.input_arg_names[0]
                                      for o, _, _ in colls
                                      if o.input_arg_names})))
            else:
                # ifelse/switch: a branch-only collective is the
                # rendezvous-hang class even with every branch listed —
                # branches are mutually exclusive per device, and an
                # implicit else (fewer collectives on one path) is the
                # same divergence
                if not all(per_branch) or len(per_branch) < 2 or \
                        len({tuple((k, a) for _, k, a in b)
                             for b in per_branch}) > 1:
                    colls = [c for branch in per_branch for c in branch]
                    findings.append(Finding.for_op(
                        COLLECTIVE_DIVERGENCE, SEV_ERROR,
                        '%s issues collective(s) [%s] on one branch '
                        'only: devices taking the other branch never '
                        'reach the rendezvous and the mesh hangs — '
                        'hoist the collective out of the conditional '
                        'or issue a matching collective on every '
                        'branch' % (op.type, _describe(colls)), op))

    # concurrency: a concurrent-declared program issuing collectives at
    # all leans on serving/pod.py's process-wide _MESH_DISPATCH_LOCK
    if concurrent:
        top = [(op, kind, ax)
               for _, _, op, kind, ax in collective_sequence(
                   program, mesh_axes)]
        if top:
            findings.append(Finding(
                CONCURRENT_COLLECTIVES, SEV_WARNING,
                'program is declared to run CONCURRENTLY and issues '
                'collective(s) [%s]: two modules interleaving '
                'collectives on shared devices pair rendezvous '
                'participants across modules and deadlock — today this '
                'is survived only by the process-wide '
                '_MESH_DISPATCH_LOCK in paddle_tpu/serving/pod.py '
                '(co-hosted sharded replicas serialize their '
                'dispatches); keep dispatches behind that lock, or '
                'give each program its own devices' % _describe(top),
                var_names=sorted({op.inputs.get('W', [None])[0].name
                                  for op, _, _ in top
                                  if op.inputs.get('W')})))
    return findings
