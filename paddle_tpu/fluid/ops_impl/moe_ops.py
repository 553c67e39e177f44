"""Mixture-of-experts op lowering.

TPU-first extension (no reference counterpart — the reference predates MoE
layers; closest ancestor is its conditional-computation machinery,
fluid/layers/control_flow.py Switch). The `moe_mlp` op is a top-k gated
expert FFN:

  gate_logits = x @ gate_w                       [N, E], float32
  expert e:  y = act(x @ w1[e] + b1[e]) @ w2[e] + b2[e]
  gated (W3 given):  y = (act(x @ w1[e]) * (x @ w3[e])) @ w2[e]

The biases are optional inputs. Gates are the softmax probabilities of the
k chosen experts: raw for k=1 (Switch) and for `norm_topk_prob` false
(OLMoE), renormalised over the chosen k otherwise (GShard). The router's
logits and softmax are float32 at full matmul precision whatever the
experts run in: under AMP the expert matmuls take bf16 operands, the
router does not (a top-k choice is discrete; rounding upstream of it
changes which experts a token gets). The op also emits the Switch/GShard
load-balancing auxiliary loss (E * sum_e f_e * P_e) as a scalar `AuxLoss`
and the step's assignments per expert as `ExpertCount` ([E] int32).

WHICH PATH DROPS. `dropless` false (a `capacity_factor`): the
Switch/GShard fixed-capacity packing of paddle_tpu.parallel.moe — tokens
packed into [E, capacity] slots, overflow DROPPED (first choices before
second), static shapes. Two executions of it, same math:

- mesh path: when the step is compiled against a mesh (DistributeTranspiler
  or ParallelExecutor) whose dp axis size divides num_experts, experts are
  sharded num_experts/dp-per-device over dp and tokens ride TWO
  all_to_alls (parallel/moe.py moe_apply) — true expert parallelism on
  the ICI.
- dense path: identical pack/transform/unpack with the experts vmapped
  locally (single device, or expert count not a multiple of mesh size).

The two agree exactly when capacity is not exceeded; under overflow
the drop PATTERN differs (per-shard vs global cumsum order) — the standard
TPU MoE trade, tested in tests/test_pipeline_moe.py.

`dropless` true (`layers.moe_mlp(capacity_factor=None)`): NOTHING is
dropped. The tokens x k assignments are sorted by expert and the experts
run as grouped matmuls over the E ragged groups (the Pallas kernel of
ops/kernels/grouped_matmul.py on the TPU, `lax.ragged_dot` elsewhere): the
row count is always tokens x k, so the work does not depend on how uneven
the router is. One device only: on a mesh whose dp divides num_experts the
rule raises `DroplessOnMeshError` rather than fall back to a path that
drops.

Inside the op's `moe_mlp_<index>` scope the stages are named `moe_route`
(logits, top-k, the sort and the gather of rows), `moe_experts` (the
matmuls) and `moe_combine` (un-sort, gate weights, sum over k). Trace-time
counters: `moe.lowered{path=grouped|capacity}` once per op per trace of
the rule (a lowering, or build-time shape inference), `moe.assignments`
the tokens x k of the traced shape.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ... import obs
from ..lowering import register, data_of, amp_cast

_ACTS = {
    'relu': jax.nn.relu,
    'gelu': jax.nn.gelu,
    'tanh': jnp.tanh,
    'sigmoid': jax.nn.sigmoid,
    'swish': jax.nn.silu,
    None: lambda x: x,
    '': lambda x: x,
}


def supported_acts():
    """Expert activations the rule can lower (layers.moe_mlp validates
    against this at construction time)."""
    return set(_ACTS)


def _expert_mlp(p, t, act):
    """One expert on its rows `t`; `p` holds w1, w2 and, where the op was
    given them, b1, b2 (biases) and w3 (the gated form's up-projection)."""
    h = t @ p['w1']
    if 'b1' in p:
        h = h + p['b1']
    h = _ACTS[act](h)
    if 'w3' in p:
        h = h * (t @ p['w3'])
    out = h @ p['w2']
    return out + p['b2'] if 'b2' in p else out


def _dense_moe(params, x, logits, capacity_factor, act, top_k,
               norm_topk_prob):
    """Local pack/transform/unpack with the same fixed-capacity semantics
    as parallel.moe.moe_apply (minus the all_to_all exchanges) — routing
    math is shared via pack_topk/combine_topk so the paths cannot drift.
    Overflow is dropped."""
    from ...parallel.moe import pack_topk, combine_topk
    nt = x.shape[0]
    n_exp = logits.shape[-1]
    cap = int(max(1, capacity_factor * top_k * nt / n_exp))
    send, route = pack_topk(x, logits, n_exp, cap, top_k, norm_topk_prob)
    out = jax.vmap(lambda p, t: _expert_mlp(p, t, act))(params, send)
    return combine_topk(out, route, x.dtype)


def _rows(x, idx):
    """x[idx] along axis 0 for indices known to be in bounds (permutations
    and expert ids): no clamp, no fill."""
    return x.at[idx].get(mode='promise_in_bounds')


# A gather of rows by a permutation, with the inverse gather as its
# gradient: jax's own transpose of a gather is a scatter-add, which the TPU
# runs row by row; the inverse permutation is known here, so both ways are
# gathers.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Rows of x [nt, d] for the sorted assignments: x[order // k]
    ([nt * k, d]); `order` sorts the token-major assignments by expert and
    `inv` undoes it."""
    return _rows(x, order // k)


def _dispatch_fwd(x, order, inv, k):
    return _dispatch(x, order, inv, k), inv


def _dispatch_bwd(k, inv, g):
    g = _rows(g, inv).reshape(-1, k, g.shape[-1])
    return jnp.sum(g.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(rows, order, inv):
    """rows [nt * k, d] back from expert order to token-major order."""
    return _rows(rows, inv)


_unsort.defvjp(lambda rows, order, inv: (_unsort(rows, order, inv), order),
               lambda order, g: (_rows(g, order), None, None))


def _grouped_matmul(rows, w, sizes, ctx):
    """rows [A, k] (sorted by group) times w [E, k, n], group e's rows by
    w[e]; float32 accumulation, the result in the operands' dtype. On the
    TPU the Pallas grouped matmul (ops/kernels/grouped_matmul.py), as the
    attention op takes its flash kernels there; `lax.ragged_dot` elsewhere
    and for a row count the kernel's tiles do not divide."""
    from ...ops.kernels import grouped_matmul as gm
    if ctx.platform == 'tpu' and gm.usable(rows.shape[0]):
        return gm.grouped_matmul(rows, w, sizes, False)
    return lax.ragged_dot(rows, w, sizes,
                          preferred_element_type=jnp.float32
                          ).astype(rows.dtype)


def _dropless_moe(params, x, expert, gate, sizes, act, ctx):
    """Every one of the nt x k assignments is computed. `expert`, `gate`
    are [nt, k]; `sizes` [E] counts the assignments per expert. `x` is in
    the experts' dtype; returns float32 [nt, d_out]."""
    nt, k = expert.shape
    with jax.named_scope('moe_route'):
        flat = expert.reshape(-1)                      # token-major
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        rows = _dispatch(x, order, inv, k)             # [nt * k, d]
        if 'b1' in params:
            group = _rows(flat, order)
    with jax.named_scope('moe_experts'):
        h = _grouped_matmul(rows, params['w1'], sizes, ctx)
        if 'b1' in params:
            h = h + _rows(params['b1'], group)
        h = _ACTS[act](h.astype(jnp.float32))
        if 'w3' in params:
            h = h * _grouped_matmul(rows, params['w3'], sizes,
                                    ctx).astype(jnp.float32)
        out = _grouped_matmul(h.astype(rows.dtype), params['w2'], sizes,
                              ctx)
        if 'b2' in params:
            out = out + _rows(params['b2'], group)
    with jax.named_scope('moe_combine'):
        out = _unsort(out, order, inv).reshape(nt, k, out.shape[-1])
        return jnp.sum(out.astype(jnp.float32) * gate[..., None], axis=1)


_SLOTS = {'W1': 'w1', 'B1': 'b1', 'W2': 'w2', 'B2': 'b2', 'W3': 'w3'}


@register('moe_mlp')
def _moe_mlp(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    gate_w = data_of(ins['GateW'][0])
    params = {key: data_of(ins[slot][0]) for slot, key in _SLOTS.items()
              if ins.get(slot)}
    act = attrs.get('act') or None
    n_exp = int(attrs.get('num_experts'))
    top_k = int(attrs.get('top_k', 1))
    norm = bool(attrs.get('norm_topk_prob', True))
    dropless = bool(attrs.get('dropless', False))
    cf = float(attrs.get('capacity_factor', 2.0))   # unused when dropless

    shape_in, dtype_in = x.shape, x.dtype
    if x.ndim > 2:
        x = x.reshape(-1, x.shape[-1])
    nt = x.shape[0]
    obs.counter('moe.lowered',
                path='grouped' if dropless else 'capacity').inc()
    obs.counter('moe.assignments').inc(nt * top_k)

    from ...parallel.moe import (DroplessOnMeshError, load_balancing_loss,
                                 moe_apply, router_topk)
    with jax.named_scope('moe_route'):
        # the router is not the MXU's: float32 operands at full precision
        logits = jnp.matmul(x.astype(jnp.float32),
                            gate_w.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        aux = load_balancing_loss(logits, top_k)
        expert, gate = router_topk(logits, top_k, norm)        # [k, nt]
        sizes = jnp.bincount(expert.reshape(-1), length=n_exp
                             ).astype(jnp.int32)
    params = dict(zip(params, amp_cast(ctx, *params.values())))
    # the experts' rows in the experts' dtype (AMP cast the weights just
    # above; X stayed float32 for the router)
    x = x.astype(params['w1'].dtype)

    mesh = ctx.mesh
    shards = (mesh is not None and 'dp' in getattr(mesh, 'shape', {})
              and n_exp % mesh.shape['dp'] == 0)
    if dropless and shards:
        raise DroplessOnMeshError(
            'moe_mlp(capacity_factor=None) is dropless and runs on one '
            'device; this step is compiled against a mesh whose dp=%d '
            'divides its %d experts, where the experts would ride '
            'moe_apply\'s fixed-capacity all_to_all and overflow would be '
            'dropped. The expert-parallel twin (dropless under moe_apply, '
            'the four-chip cell of PERF.md section 7) is not built; give a '
            'capacity_factor, or run the layer on one device.'
            % (mesh.shape['dp'], n_exp))
    if dropless:
        y = _dropless_moe(params, x, expert.T, gate.T, sizes, act, ctx)
    elif shards:
        from jax.sharding import NamedSharding, PartitionSpec as P
        # experts block-sharded over dp (n_exp/dp per device); tokens
        # already batch-sharded over dp
        params = jax.tree_util.tree_map(
            lambda p: jax.lax.with_sharding_constraint(
                p, NamedSharding(mesh, P('dp'))), params)
        y = moe_apply(lambda p, t: _expert_mlp(p, t, act), params, x,
                      logits, mesh, axis='dp', capacity_factor=cf,
                      top_k=top_k, norm_topk_prob=norm)
    else:
        y = _dense_moe(params, x, logits, cf, act, top_k, norm)
    return {'Out': y.astype(dtype_in).reshape(shape_in[:-1] + y.shape[-1:]),
            'AuxLoss': aux, 'ExpertCount': sizes}
