"""Mixture-of-experts op lowering.

TPU-first extension (no reference counterpart — the reference predates MoE
layers; closest ancestor is its conditional-computation machinery,
fluid/layers/control_flow.py Switch). The `moe_mlp` op is a top-k gated
expert FFN:

  gate_logits = x @ gate_w                       [N, E], float32
  expert e:  y = act(x @ w1[e] + b1[e]) @ w2[e] + b2[e]
  gated (W3 given):  y = (act(x @ w1[e]) * (x @ w3[e])) @ w2[e]
  act 'relu2' is relu(h)^2 (Nemotron-H's experts: two matrices, no W3)

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

A SHARE (`experts_held` = (first, count), dropless only): this device
holds `count` of the `num_experts` experts, as one of the devices of an
expert-parallel layer does. The router's logits, the softmax, the top-k,
the renormalisation of the gates, `AuxLoss` and `ExpertCount` are over all
`num_experts`; the stacks are [count, ...]. What the op computes is the
sum, over each token's chosen experts THAT ARE HELD, of gate x expert:
the part of the layer's output that this device's experts give. What it
leaves out: the absent experts' part (on the pod the other devices
compute theirs and the exchange adds them up; one device runs without
the exchange and nothing stands in for it), so the outputs of all
num_experts / count shares add up to the whole layer's, and anything
every device computes alike (a shared expert) is counted once. Still
dropless: no assignment to a held expert is lost at any imbalance, and
an assignment to an absent expert costs no matmul tile. How the held
experts get their rows (`_held_moe`): BY INDEX, once a layer. One sort of
the tokens x k assignments puts the held ones first in expert order; a
layout of `_HELD_SLACK` times the expected number of held rows, and never
more than half the layer's rows (`_held_layout`: a share of an eighth of
the experts lays out 4 times its expected rows, one of a 32nd all 10), is
gathered from the tokens, goes through the grouped matmuls, and is
weighted by the gates and added back to the tokens (`_compact_moe`,
`_lay_out` / `_add_up`; the cost follows the rows laid out, not rows x
tokens). That add runs twice a layer, forward and as the gather's
transpose. On the TPU it is one Pallas kernel (ops/kernels/row_add.py)
that reads the rows where the matmuls left them, sorted by expert and an
expert's by token, and walks the live ones only, a tile of tokens at a
time; on the host, and at a shape the kernel does not take (`usable`), it
is the scatter-add the kernel is tested against, which walks every row of
the layout after a gather of them all into token order
(`moe.add{way=kernel|scatter}` counts the choice at trace time, once an
add). A router that sends more than that to
the held experts is answered, on the device (one `lax.cond` a layer), by
the static tokens x k rows a block at a time (`_held_blocks`), which is
also all a layer has where half its rows are under one 256-row tile. The
layer from its keys on (`_held_paths`: the conditional and both paths) is
one `jax.jit` function a step, shared by the step's layers of equal
shapes (`lowering.traced_once`). One device, as the whole dropless layer:
the same refusal on a mesh.

GROUP-LIMITED ROUTING (the sigmoid router's; `n_group`, `topk_group`): the
choice confined to the best `topk_group` of `n_group` groups of consecutive
experts (parallel/moe.py router_topk), counted at trace time as
`moe.router{groups=, kept=}`; the held share and `ExpertCount` are what
they are without groups.
ANOTHER ROUTER (dropless only; `scoring`, `SelectionBias`, `gate_scale`):
`scoring` 'sigmoid' scores every expert by itself, sigmoid(logit), where
the default takes a softmax over all; an input `SelectionBias` [E]
(float32, a persistable that is no trainable parameter) is added to the
scores for the CHOICE of the top k and for nothing else: the gates are
the chosen experts' scores without it, renormalised over the chosen
under `norm_topk_prob` (the attribute `norm_eps`, where the op has it,
is what that sum carries in place of the scoring's own), times
`gate_scale`; no gradient reaches the bias
(parallel/moe.py router_topk: one code path for both scorings). Its
owner moves it after the step from `ExpertCount`
(layers.router_bias_update). `AuxLoss` stays the softmax form. An
optional input `RouterX` of `X`'s shape is what the router reads in
place of `X` (`layers.moe_mlp(router_input=)`): the logits are RouterX @
GateW, float32 at full precision as ever, and `X` feeds the experts
alone, so the router's gradient flows to one tensor and the experts' to
another. Everything after the logits (either scoring, the top k,
`AuxLoss`, `ExpertCount`, a held share and its device counter) is
indifferent to where they came from. Without it the op is what it was.

Inside the op's `moe_mlp_<index>` scope the stages are named `moe_route`
(logits, top-k, the sort and the gather of rows), `moe_experts` (the
matmuls) and `moe_combine` (un-sort, gate weights, sum over k). Trace-time
counters: `moe.lowered{path=grouped|capacity}` once per op per trace of
the rule (a lowering, or build-time shape inference; a share adds the
labels `held=<count>of<num_experts>` and `dispatch=index`, a sigmoid
router the label `scoring=sigmoid`, a router with an input of its own
the label `router=own`, squared-ReLU experts the label `act=relu2`, and
dropless experts of two matrices without biases the label `gated=false`:
the form whose grouped matmuls are two a pass).

ONCE A STEP IN A RECOMPUTE REGION. What the route stage DECIDES (the
router's choice, the auxiliary loss's `f`, `ExpertCount`, and on a share's
compact path the index, the add's steps and `live`: `_routed`,
`_compact_route`) is named `region_keep`, the name a region's policy saves
(step_artifact.py REGION_KEEP): the region's second forward reads the
integers and runs no top-k, no count, no sort and no plan again; the
logits, the gates, the row gather, the matmuls and the add it runs as
before. Outside a region the names do nothing. `moe.route_kept{held=}`
and `moe.route_kept_bytes{held=}` count what a lowering named
(`_count_routed`).

What a share's step did with its DATA leaves the device as the op's
device counter (`_held_counter`, lowering.register_device_counter): the
step's assignments to the held experts, one int32 an op, reduced from
`ExpertCount` by the function whose result the `lax.cond` compares
(`_held_rows`). On the host, while observability is on, it becomes the
step record's `fields['device']` entry (`rows`, `expected`, `cap`, `way`)
and the registry's `moe.held.rows{op=}` and
`moe.held.layer_steps{op=, way=compact|blocks}` (`_held_record`).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ... import obs
from ..lowering import (DeviceCounter, amp_cast, data_of, register,
                        register_device_counter, traced_once)
from ..step_artifact import REGION_KEEP
from ...ops.kernels import row_add

_ACTS = {
    'relu': jax.nn.relu,
    'gelu': jax.nn.gelu,
    'tanh': jnp.tanh,
    'sigmoid': jax.nn.sigmoid,
    'swish': jax.nn.silu,
    'relu2': lambda x: jnp.square(jax.nn.relu(x)),
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


def _keep(live):
    """Rows before `live` as they are, zeros after: a select, which stops
    whatever an unwritten row holds, forward and backward. `live` None:
    every row is live."""
    if live is None:
        return lambda rows: rows

    def keep(rows):
        row = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0)
        return jnp.where(row < live, rows, jnp.zeros((), rows.dtype))
    return keep


def _experts(params, rows, sizes, group, act, ctx, keep):
    """The experts on their sorted rows: group e's rows by expert e's
    matrices. `group` [rows] names each row's expert where there are
    biases."""
    h = keep(_grouped_matmul(rows, params['w1'], sizes, ctx))
    if 'b1' in params:
        h = h + _rows(params['b1'], group)
    h = _ACTS[act](h.astype(jnp.float32))
    if 'w3' in params:
        h = h * keep(_grouped_matmul(rows, params['w3'], sizes,
                                     ctx)).astype(jnp.float32)
    out = _grouped_matmul(keep(h.astype(rows.dtype)), params['w2'],
                          sizes, ctx)
    if 'b2' in params:
        out = out + _rows(params['b2'], group)
    return keep(out)


def _dropless_moe(params, x, expert, gate, sizes, act, ctx, live=None):
    """Every one of the nt x k assignments is computed. `expert`, `gate`
    are [nt, k]; `sizes` [E] counts the assignments per expert. `x` is in
    the experts' dtype; returns float32 [nt, d_out].

    A held share (`_held_moe`) passes `live`, the number of sorted rows
    that belong to a held expert: `sizes` then sums to `live`, the rows
    after them are the absent experts' tail group, which no matmul tile
    visits and whose rows a kernel leaves unwritten, so every buffer of
    rows is set to zero past `live` (`_keep`)."""
    nt, k = expert.shape
    keep = _keep(live)
    group = None
    with jax.named_scope('moe_route'):
        flat = expert.reshape(-1)                      # token-major
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        rows = keep(_dispatch(x, order, inv, k))       # [nt * k, d]
        if 'b1' in params:
            group = _rows(flat, order)
            if live is not None:
                # the tail group has no bias row: any row in bounds, masked
                group = jnp.minimum(group, sizes.shape[0] - 1)
    with jax.named_scope('moe_experts'):
        out = _experts(params, rows, sizes, group, act, ctx, keep)
    with jax.named_scope('moe_combine'):
        out = _unsort(out, order, inv).reshape(nt, k, out.shape[-1])
        return jnp.sum(out.astype(jnp.float32) * gate[..., None], axis=1)


def _argsort(keys, bound):
    """jnp.argsort(keys, stable=True) of int32 keys in [0, bound): where
    they fit, a key and its position are packed into ONE int32 and sorted
    as one operand, unstably (the packed keys are distinct, so the order is
    the stable one). The TPU's compiler takes 2 s over such a sort of
    98304 keys and 14 s over the stable sort of two operands that argsort
    lowers to (11 s from 20480 keys on; AOT, PR 39): a start's time, in the
    step and in every check Program."""
    n = keys.shape[0]
    span = 1 << (n - 1).bit_length()
    if bound * span > 1 << 31:
        return jnp.argsort(keys, stable=True).astype(jnp.int32)
    packed = lax.sort(keys * span + lax.iota(jnp.int32, n), is_stable=False)
    return packed & (span - 1)


# The two row moves of a held share's compact path, each the other's
# transpose, both by index. `at` is (token, steps) (`_index`): token [cap]
# is the row of x that a laid-out row takes; `steps` is what the add goes
# by. Written as a pair so that the add is float32 whatever the rows are
# (jax's own transpose of a bf16 gather adds in bf16) and neither way
# clamps an index. The add, two ways (`interpret` says which):
#
# - the kernel (ops/kernels/row_add.py; `interpret` True or False, as the
#   kernel takes it): `steps` is its plan, the rows are read where the
#   grouped matmuls left them, the live ones only, and the gates are its
#   second operand, so no float32 product of the layout is written;
# - the scatter-add (`interpret` None): `steps` is (order, token[order]),
#   order [cap] the laid-out rows by token, so that the add is given its
#   rows in the order of the rows they are added to: left to itself XLA
#   sorts a scatter's indices anew in every scatter, forward and backward,
#   and its compiler takes 11 s over each such sort of 49152 (2 s over the
#   scatter told its indices are sorted; AOT, PR 39).
#
# The zero-size residuals carry a shape and a dtype.
def _index(src, key, groups, width=None):
    """`at` of a layout: `src` [cap] are the laid-out assignments'
    positions among the token-major `key` [n, k] (each assignment's group,
    or `groups` for one without a row). `width`: the rows' width where the
    add is the kernel's, None where it is the scatter's."""
    n, k = key.shape
    token = src // k
    if width is not None:
        return token, row_add.plan(key, groups, src.shape[0], width)
    order = _argsort(token, n)
    return token, (order, _rows(token, order))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lay_out(x, at, interpret):
    """x[token]: the laid-out rows [cap, d] of x [n, d]."""
    return _rows(x, at[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _add_up(rows, gate, at, n, interpret):
    """The laid-out rows [cap, d], each times its gate ([cap, 1] float32;
    None: as they are), added to the rows of x they came from: [n, d]
    float32. A row that no assignment fills must come as zeros."""
    token, steps = at
    if interpret is not None:
        return row_add.row_add(
            rows, token, None if gate is None else gate.reshape(-1), steps,
            n=n, interpret=interpret)
    order, by_token = steps
    if gate is not None:
        rows = rows.astype(jnp.float32) * gate
    zero = jnp.zeros((n,) + rows.shape[1:], jnp.float32)
    return zero.at[by_token].add(
        _rows(rows, order).astype(jnp.float32), indices_are_sorted=True,
        mode='promise_in_bounds')


def _add_up_fwd(rows, gate, at, n, interpret):
    return (_add_up(rows, gate, at, n, interpret),
            (at, rows[:0], gate) if gate is None else (at, rows, gate))


def _add_up_bwd(n, interpret, res, g):
    at, rows, gate = res
    g = _lay_out(g, at, interpret)
    if gate is None:
        return g.astype(rows.dtype), None, None
    return ((g * gate).astype(rows.dtype),
            jnp.sum(g * rows.astype(jnp.float32), axis=-1, keepdims=True),
            None)


_lay_out.defvjp(
    lambda x, at, interpret: (_lay_out(x, at, interpret), (at, x[:, :0])),
    lambda interpret, res, g: (
        _add_up(g, None, res[0], res[1].shape[0], interpret
                ).astype(res[1].dtype), None))
_add_up.defvjp(_add_up_fwd, _add_up_bwd)


def _add_kernel(ctx, cap, n, d, dtype):
    """Is a layout's add the Pallas kernel? On the TPU, at the shapes it
    takes; the scatter-add on every other platform and shape."""
    return ctx.platform == 'tpu' and row_add.usable(cap, n, d, dtype)


def _routed(tree):
    """What the route stage DECIDED, every array of `tree` named
    REGION_KEEP for a recompute region's policy (step_artifact.py): the
    region's second forward reads the integers its first made and runs
    neither the choice, nor the counts, nor the sort, nor the plan again.
    Nothing that takes a gradient goes through here. Outside a region the
    name is inert."""
    return jax.tree_util.tree_map(
        lambda v: checkpoint_name(v, REGION_KEEP), tree)


def _count_routed(tree, held):
    """Trace-time counters of what a lowering named (`_routed`, and the two
    router functions of parallel/moe.py): `moe.route_kept{held=}`, the
    arrays of `tree`, and `moe.route_kept_bytes{held=}`, their bytes from
    shape and dtype (`jax.eval_shape`'s do), under the label `moe.lowered`
    carries (`held` None: none). They count the names, not what
    a step kept: a lowering outside any region (`olmoe_s4096`,
    `qwen3next_s8192`) counts the same and keeps nothing; that a region
    used them is read from the step's jaxpr (tests/test_held_experts.py).
    Apart from `recompute.kept_values`, the models' marks."""
    leaves = jax.tree_util.tree_leaves(tree)
    labels = {} if held is None else {'held': held}
    obs.counter('moe.route_kept', **labels).inc(len(leaves))
    obs.counter('moe.route_kept_bytes', **labels).inc(
        sum(v.size * v.dtype.itemsize for v in leaves))


def _compact_route(key, sizes, *, cap, width, biased):
    """What the compact path decides from its keys, named (`_routed`):
    `src` [cap], the laid-out assignments' positions among the token-major
    keys (one packed sort of them all); `at` (`_index`; `width` as there);
    `group` [cap], each row's expert, where there are biases; `live`, the
    rows that some assignment fills."""
    flat = key.reshape(-1)                             # token-major
    src = _argsort(flat, sizes.shape[0] + 1)[:cap]
    group = None
    if biased:
        # a row past `live` has no bias row: any row in bounds, masked
        group = jnp.minimum(_rows(flat, src), sizes.shape[0] - 1)
    return _routed((src, _index(src, key, sizes.shape[0], width), group,
                    jnp.sum(sizes)))


def _compact_moe(params, x, key, gate, sizes, cap, act, ctx):
    """A held share whose held assignments fit `cap` rows: they alone are
    laid out, sorted by expert, once for the whole layer, and nothing of
    tokens x k rows is built. One stable sort of the tokens x k keys puts
    the held assignments first, in expert order; its first `cap` entries
    are the assignments that get a row. The rows are gathered by that
    index (`_lay_out`), go through the experts, and are added to their
    tokens, each times its gate (`_add_up`). `key` [nt, k] is the held
    expert's index or, for an absent one, the number of held experts;
    `sizes` are the held experts' counts; `x` is in the experts' dtype."""
    nt, k = key.shape
    kernel = _add_kernel(ctx, cap, nt, x.shape[1], x.dtype)
    interpret = ctx.pallas_interpret if kernel else None
    with jax.named_scope('moe_route'):
        src, at, group, live = _compact_route(
            key, sizes, cap=cap, width=x.shape[1] if kernel else None,
            biased='b1' in params)
        # `keep`: a row past `live` is some absent assignment's token, and
        # the kernels' gradient of the rows is unwritten there
        keep = _keep(live)

    # the index is kept for the backward pass, the rows are laid out again
    # there: kept, a layer's rows and the experts' hidden rows are 670 MB
    # at 25600 rows of 2048. Kept twice over: by this checkpoint, which
    # closes over it, and, where a recompute region encloses the layer, by
    # the region's policy under the names `_compact_route` gave it (the
    # route stage's values are the third kind a region keeps,
    # step_artifact.py REGION_KEEP), so the region's second forward makes
    # no index either. The row gather, the grouped matmuls and the add
    # still run again there, and once more here.
    @jax.checkpoint
    def rows_of(params, x, gate):
        with jax.named_scope('moe_route'):
            rows = keep(_lay_out(x, at, interpret))
            row_gate = _rows(gate.reshape(-1, 1), src)
        with jax.named_scope('moe_experts'):
            out = _experts(params, rows, sizes, group, act, ctx, keep)
        with jax.named_scope('moe_combine'):
            return _add_up(out, row_gate, at, nt, interpret)

    return rows_of(params, x, gate)


# tokens a block of a held share that keeps all its rows: its buffers of
# rows are this x top_k
_HELD_BLOCK = 2048
# the compact path holds this many times the layer's expected held rows
_HELD_SLACK = 10


def _held_cap(assignments, count, n_exp):
    """Rows of the compact path's layout for a layer of `assignments`
    (tokens x k) that holds `count` of `n_exp` experts: the slack times
    the expected held rows, rounded up to the kernels' 256 rows."""
    return -(-_HELD_SLACK * assignments * count // n_exp // 256) * 256


def _held_layout(assignments, count, n_exp):
    """Rows of the compact path's layout: `_held_cap`, or half the layer's
    rows (rounded down to the kernels' 256) where the slack's layout would
    hold more; a layout of more than half the rows gains too little on
    keeping them all. None where half the rows are under one such tile:
    the layer keeps every row whatever the router does."""
    return min(_held_cap(assignments, count, n_exp),
               assignments // 2 // 256 * 256) or None


def _held_blocks(params, x, key, gate, act, ctx):
    """Every tokens x k row of a held share, whatever the router did:
    `_dropless_moe(live=)` over blocks of `_HELD_BLOCK` tokens, one after
    the other (a lax.scan), each block by itself and recomputed in the
    backward pass (jax.checkpoint), so a buffer of rows is a block's in
    both passes. A block's assignments are sorted held experts first, the
    absent experts' after them in ONE tail group, which the grouped
    matmuls never visit (their `sizes` are the held experts' alone)."""
    nt = key.shape[0]
    count = params['w1'].shape[0]

    @jax.checkpoint
    def block(params, x, key, gate):
        sizes = jnp.bincount(key.reshape(-1), length=count + 1
                             )[:count].astype(jnp.int32)
        return _dropless_moe(params, x, key, gate, sizes, act, ctx,
                             live=jnp.sum(sizes))

    if nt % _HELD_BLOCK or nt == _HELD_BLOCK:
        return block(params, x, key, gate)
    x, key, gate = (t.reshape((-1, _HELD_BLOCK) + t.shape[1:])
                    for t in (x, key, gate))
    _, y = lax.scan(lambda _, b: (None, block(params, *b)), None,
                    (x, key, gate))
    return y.reshape(nt, y.shape[-1])


def _held_moe(params, x, expert, gate, sizes, held, act, ctx):
    """This device's share of the layer: experts first .. first + count - 1
    are here (the stacks are [count, ...]), the router chose among all
    num_experts and `sizes` [num_experts] counts what each got. The layer
    takes one of two paths, chosen ONCE on the device (lax.cond) from how
    many of its tokens x k assignments are held:

    - `_compact_moe`, where they fit the layout (`_held_layout`):
      `_HELD_SLACK` times the expected number (tokens x k x count /
      num_experts), and at most half the layer's rows. Only those rows
      exist, reached by index. A row of the layout that no assignment
      fills costs a gathered row and a tile the kernels skip. The slack
      is wide on purpose: a router in training leans towards or away from
      the held experts within tens of steps (read on the chip, PR 30: 1.2
      times the expected rows at the first step and 5.8 times at the
      64th), and a step's time should not follow it;
    - `_held_blocks`, at any imbalance beyond that: the static tokens x k
      rows a block at a time, so no assignment to a held expert is ever
      lost. The only path where the layer has no layout (toy widths).

    Either way an absent expert's assignment costs no matmul tile.
    Everything after the keys is `_held_paths`, one function a step for
    all its layers of these shapes (`traced_once`). `params` are in the
    experts' dtype already (one cast a layer: at 16 experts of 2048 x 512
    it moves 300 MB)."""
    first, count = held
    nt, k = expert.shape
    with jax.named_scope('moe_route'):
        local = expert - first
        key = jnp.where((local >= 0) & (local < count), local, count)
    cap = _held_layout(nt * k, count, sizes.shape[0])
    held_sizes = sizes[first:first + count]
    if cap is not None:
        # the layout's two adds, forward and the row gather's transpose
        kernel = _add_kernel(ctx, cap, nt, x.shape[1], x.dtype)
        obs.counter('moe.add', way='kernel' if kernel else 'scatter').inc(2)
        # what the shared body's compact path names, counted a lowering
        _count_routed(jax.eval_shape(
            functools.partial(_compact_route, cap=cap, biased='b1' in params,
                              width=x.shape[1] if kernel else None),
            key, held_sizes), '%dof%d' % (count, sizes.shape[0]))
    paths = traced_once(ctx, _held_paths, cap=cap, act=act)
    return paths(params, x, key, gate, held_sizes, _held_rows(sizes, held))


def _held_paths(ctx, params, x, key, gate, sizes, rows, *, cap, act):
    """`_held_moe` from its keys on: `key` [nt, k] is the held expert's
    index or, for an absent one, the number of held experts; `sizes` are
    the held experts' counts and `rows` their sum (`_held_rows`)."""
    if cap is None:
        return _held_blocks(params, x, key, gate, act, ctx)
    return lax.cond(
        rows <= cap,
        lambda *a: _compact_moe(*a, sizes, cap, act, ctx),
        lambda *a: _held_blocks(*a, act, ctx),
        params, x, key, gate)


def _held_rows(sizes, held):
    """The step's assignments to the held experts, of `sizes`
    [num_experts] (`ExpertCount`): what `_held_moe` compares with its
    capacity, and the op's device counter."""
    first, count = held
    return jnp.sum(sizes[first:first + count])


def _held_record(label, rows, facts):
    """The host's side of a held op's device counter, a step: `rows` is
    the integer the device compared, `facts` what the rule noted when it
    was traced, so the way read here is the way the layer took."""
    cap = facts['cap']
    way = 'compact' if cap is not None and rows <= cap else 'blocks'
    obs.counter('moe.held.rows', op=label).inc(rows)
    obs.counter('moe.held.layer_steps', op=label, way=way).inc()
    return {'op': label, 'rows': rows, 'expected': facts['expected'],
            'cap': cap, 'way': way}


@register_device_counter('moe_mlp')
def _held_counter(op):
    """A share's held rows, a step; where the op was built with its
    expert count (`return_expert_count`), which is what it is read from."""
    held = op.attrs.get('experts_held')
    if not held or not op.output('ExpertCount'):
        return None
    held = tuple(int(i) for i in held)
    return DeviceCounter(op.output('ExpertCount')[0],
                         lambda sizes: _held_rows(sizes, held), _held_record)


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
    held = attrs.get('experts_held')
    held = tuple(int(i) for i in held) if held else None
    scoring = attrs.get('scoring') or 'softmax'
    bias = data_of(ins['SelectionBias'][0]) if ins.get('SelectionBias') \
        else None
    labels = {'held': '%dof%d' % (held[1], n_exp), 'dispatch': 'index'} \
        if held else {}
    if scoring != 'softmax':
        labels['scoring'] = scoring
    # the router's input: its own tensor where the op was given one
    routed = x
    if ins.get('RouterX'):
        if not dropless:
            raise ValueError("moe_mlp: RouterX is the dropless layer's "
                             '(capacity_factor=None)')
        routed = data_of(ins['RouterX'][0]).reshape(x.shape)
        labels['router'] = 'own'
    if act == 'relu2':
        labels['act'] = act
    if dropless and not set(params) - {'w1', 'w2'}:
        labels['gated'] = 'false'
    obs.counter('moe.lowered', path='grouped' if dropless else 'capacity',
                **labels).inc()

    from ...parallel.moe import (DroplessOnMeshError, load_balancing_loss,
                                 moe_apply, router_topk)
    with jax.named_scope('moe_route'):
        # the router is not the MXU's: float32 operands at full precision
        logits = jnp.matmul(routed.astype(jnp.float32),
                            gate_w.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        aux = load_balancing_loss(logits, top_k)
        n_group = int(attrs.get('n_group', 1))
        if n_group > 1:                                      # trace time
            obs.counter('moe.router', groups=n_group,
                        kept=int(attrs['topk_group'])).inc()
        expert, gate = router_topk(
            logits, top_k, norm, scoring, bias,
            float(attrs.get('gate_scale', 1.0)),
            attrs.get('norm_eps'), n_group,
            int(attrs.get('topk_group', 1)))                   # [k, nt]
        sizes = _routed(jnp.bincount(expert.reshape(-1), length=n_exp
                                     ).astype(jnp.int32))
        # the rule's three: the choice and the auxiliary loss's f, which
        # `router_topk` and `load_balancing_loss` name, and the counts
        _count_routed(
            (expert, jax.ShapeDtypeStruct((n_exp,), jnp.float32), sizes),
            labels.get('held'))
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
    if held:
        # what the host reads the op's device counter by (`_held_record`)
        rows = x.shape[0] * top_k
        ctx.note(expected=rows * held[1] / n_exp,
                 cap=_held_layout(rows, held[1], n_exp))
        y = _held_moe(params, x, expert.T, gate.T, sizes, held, act, ctx)
    elif dropless:
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
