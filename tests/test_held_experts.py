"""An expert layer that holds a SHARE of its experts (ops_impl/moe_ops.py,
ISSUEs 30, 31 and 39): the shares add up to the whole layer; the layout is
the slack or half the rows; absent rows cost nothing and poison nothing;
the held rows are reached by index, once a layer, on the compact path or,
over the layout, on the blocks; the counters of the lowering. Small sizes,
on the CPU."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name
from paddle_tpu.fluid.ops_impl import moe_ops
from util import input_parameter as _input, nan_path

N, D, E, H, K = 64, 16, 32, 12, 4
HELD = 8


def build_share(held, n=N, amp=False):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, aux, count = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_aux_loss=True, return_expert_count=True,
            experts_held=held)
        if amp:
            fluid.amp.decorate_program(main)
    return main, startup, out, aux, count


def _weights(scope):
    return [np.asarray(scope.find_var('moe_mlp_0.w_%d' % i).get_tensor())
            for i in range(4)]       # router, gate (W1), up (W3), down (W2)


def _set_weights(scope, weights, first=0, count=E):
    place = fluid.CPUPlace()
    scope.var('moe_mlp_0.w_0').get_tensor().set(weights[0], place)
    for i in (1, 2, 3):
        scope.var('moe_mlp_0.w_%d' % i).get_tensor().set(
            weights[i][first:first + count], place)


def run_share(held, xs, weights=None):
    main, startup, out, aux, count = build_share(held, n=len(xs))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        if weights is None:
            exe.run(startup)
        else:
            # every parameter is given: no start-up program (a compile a
            # share) is run
            _set_weights(fluid.global_scope(), weights,
                         *(held or (0, E)))
        got = exe.run(main, feed={'x': xs}, fetch_list=[out, aux, count])
        return got, _weights(fluid.global_scope())


@pytest.mark.parametrize('tokens', [N, 4096], ids=['one_block', 'two_blocks'])
def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer(tokens):
    """THE SHARE TEST of the model-configs guide, section 4: the routed
    parts that all E / held shares give, with what every chip computes
    alike (the shared expert) counted once, add up to what the UNCUT
    plain reference gives for the whole expert block; the router's loss
    and the assignments per expert are the whole layer's in every share."""
    from chipbench.harness import catalog
    reference = catalog.load_module(catalog.ROOT, 'references', 'qwen3_next')
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(tokens, D)).astype('float32')
    (whole, aux, count), weights = run_share(None, xs)
    parts = []
    for first in range(0, E, HELD):
        (part, aux_s, count_s), _ = run_share((first, HELD), xs, weights)
        np.testing.assert_array_equal(count_s, count)
        np.testing.assert_allclose(aux_s, aux, rtol=1e-6)
        held_rows = count[first:first + HELD].sum()
        assert np.abs(part).max() > 0 and held_rows > 0
        parts.append(part)
    assert count.sum() == tokens * K
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-7)
    # the uncut reference: every expert held, the shared expert beside them
    shared = [rng.normal(size=s).astype('float32') * 0.3
              for s in ((D, H), (D, H), (H, D))]
    shared_gate = rng.normal(size=(D, 1)).astype('float32')
    model = {'num_experts_per_tok': K, 'rms_norm_eps': 1e-6,
             'norm_topk_prob': True}
    w = {'norm_post': np.ones(D, 'float32'), 'router': weights[0],
         'experts_in': [weights[1], weights[2]], 'experts_down': weights[3],
         'shared': shared, 'shared_gate': shared_gate}
    m = np.asarray(reference.rms(xs, w['norm_post'], 1e-6))
    with jax.default_matmul_precision('highest'):
        want, ref_aux = reference.experts(w, jnp.asarray(xs)[None], model)
        # the program's shares run on the normed input, as the block does
        got_parts = [run_share((first, HELD), m, weights)[0][0]
                     for first in range(0, E, HELD)]
        once = np.asarray(jax.nn.sigmoid(m @ shared_gate) * (
            (jax.nn.silu(m @ shared[0]) * (m @ shared[1])) @ shared[2]))
    np.testing.assert_allclose(sum(got_parts) + once, np.asarray(want)[0],
                               rtol=2e-4, atol=2e-6)


def _forced_router(order):
    """Router weights that send a token of x > 0 to `order`, in order."""
    router = np.zeros((D, E), 'float32')
    for j, e in enumerate(order):
        router[:, e] = 4.0 - j
    return router


# tokens x k, held, routed -> rows of the layout (`_held_layout`)
LAYOUTS = {
    # an eighth held: ten times the expected rows are more than all of
    # them, so half the rows, 4 x the expected
    'smallthinker_s16384': (16384 * 6, 8, 64, 49152),
    'glm47flash_s8192': (8192 * 4, 8, 64, 16384),
    # a 32nd held: the slack's ten times, under half the rows
    'qwen3next_s8192': (8192 * 10, 16, 512, 25600),
    # half the rows under one 256-row tile: no layout, every row is kept
    'toy_cells': (160 * 3, 4, 16, None),
    'one_row_short_of_a_tile': (511, 8, 64, None),
    'one_tile': (512, 8, 64, 256),
    # half the rows are rounded DOWN to whole tiles
    'tiles_round_down': (1534, 8, 64, 512),
}


@pytest.mark.parametrize('case', list(LAYOUTS))
def test_the_layout_is_the_slack_or_half_the_rows(case):
    """From shapes alone: `_HELD_SLACK` times the expected held rows, at
    most half the layer's rows in whole tiles, None under one tile."""
    rows, count, routed, cap = LAYOUTS[case]
    assert moe_ops._HELD_SLACK == 10
    assert moe_ops._held_layout(rows, count, routed) == cap
    if cap is not None:
        assert cap % 256 == 0 and 2 * cap <= rows
        assert cap <= moe_ops._held_cap(rows, count, routed)


@pytest.mark.parametrize('bound', [9, 1 << 20], ids=['packed', 'pairs'])
def test_the_one_operand_sort_is_the_stable_argsort(bound):
    """`_argsort` packs a key and its position into one int32 where they
    fit 31 bits and is `jnp.argsort(stable=True)` where they do not: the
    same order either way, ties in the order of their positions."""
    keys = np.random.default_rng(0).integers(0, 9, size=3000).astype('int32')
    span = 1 << (len(keys) - 1).bit_length()
    assert (bound * span <= 1 << 31) == (bound == 9)
    np.testing.assert_array_equal(
        moe_ops._argsort(jnp.asarray(keys), bound),
        np.argsort(keys, kind='stable'))


def test_a_layer_compacts_its_held_rows_or_keeps_them_all(monkeypatch):
    """Two experts of 32 held, 4096 tokens: the expected held rows are a
    sixteenth of the layer's 16384 assignments, so the layer lays out
    only 4 x that many rows, once (the compact path: a sort and row
    gathers); a router forced onto the held experts overflows them and
    the same layer keeps all its rows instead, a block at a time. Both are
    the uncut layer's part."""
    paths = []
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', 4)    # a sixteenth is held
    compact, blocks = moe_ops._compact_moe, moe_ops._held_blocks
    monkeypatch.setattr(moe_ops, '_compact_moe', lambda *a: (
        paths.append(('compact', a[5])), compact(*a))[1])
    monkeypatch.setattr(moe_ops, '_held_blocks', lambda *a: (
        paths.append(('blocks', a[2].shape)), blocks(*a))[1])
    rng = np.random.default_rng(4)
    xs = np.abs(rng.normal(size=(4096, D))).astype('float32') + 0.1
    (whole, _, count), weights = run_share(None, xs)
    parts = [run_share((first, 2), xs, weights)[0][0]
             for first in range(0, E, 2)]
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-7)
    # 4096 tokens x 4 = 16384 assignments, 1024 expected, 4096 rows; both
    # paths are traced ONCE a layer (a lax.cond), the device takes one
    assert ('compact', 4096) in paths and ('blocks', (4096, K)) in paths
    del paths[:]
    run_share((6, 2), xs, weights)
    names = [name for name, _ in paths]    # each, once a trace of the rule
    assert names.count('compact') == names.count('blocks') > 0
    # ... and it took the compact one: the other gives NaN here
    monkeypatch.setattr(moe_ops, '_held_blocks', nan_path)
    (part, _, _), _ = run_share((6, 2), xs, weights)
    np.testing.assert_allclose(part, parts[3], rtol=1e-6, atol=1e-8)
    monkeypatch.setattr(moe_ops, '_held_blocks', blocks)
    forced = [w.copy() for w in weights]
    forced[0] = _forced_router((6, 7, 0, 1))
    (whole, _, count), _ = run_share(None, xs, forced)
    (rest, _, _), _ = run_share((0, 2), xs, forced)
    # 8192 held rows, twice what the compact path lays out: had the layer
    # taken it (NaN here), half of them would be missing from `part`
    monkeypatch.setattr(moe_ops, '_compact_moe', nan_path)
    (part, _, _), _ = run_share((6, 2), xs, forced)
    assert count[6] == count[7] == 4096
    np.testing.assert_allclose(part + rest, whole, rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize(
    'tokens,held,forced', [(N, (8, HELD), False), (4096, (6, 2), False),
                           (4096, (6, 2), True)],
    ids=['all_rows', 'compact_rows', 'overflow_rows'])
def test_rows_of_absent_experts_cost_no_tile_and_poison_nothing(
        tokens, held, forced, monkeypatch):
    """The grouped matmuls are given the held experts' group sizes alone,
    and whatever lies in the rows after them (a kernel leaves them
    unwritten, in its results and in the gradient of its rows: NaN here)
    reaches neither the output nor a gradient: in a layer that always
    keeps all its rows, in one that lays out the held rows only, and in
    one whose held rows overflow that layout."""
    rng = np.random.default_rng(1)
    xs = np.abs(rng.normal(size=(tokens, D))).astype('float32') + 0.1
    seen = []
    plain = moe_ops._grouped_matmul
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', 4)    # a sixteenth is held

    @jax.custom_vjp
    def poison(out, live):
        return jnp.where(live, out, jnp.nan)

    poison.defvjp(lambda out, live: (poison(out, live), live),
                  lambda live, g: (jnp.where(live, g, jnp.nan), None))

    def poisoned(rows, w, sizes, ctx):
        seen.append((rows.shape[0], w.shape[0], sizes))
        live = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
        rows = poison(rows, live)         # the gradient of the rows
        return poison(plain(rows, w, sizes, ctx), live)

    (_, _, _), weights = run_share(None, xs)
    if forced:
        weights[0] = _forced_router((6, 7, 0, 1))
    (want, _, count), _ = run_share(held, xs, weights)
    monkeypatch.setattr(moe_ops, '_grouped_matmul', poisoned)
    main, startup, out, _, _ = build_share(held)
    with unique_name.guard(), framework.program_guard(main, startup):
        grads = fluid.backward.append_backward(layers.mean(out))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _set_weights(fluid.global_scope(), weights, *held)    # held ones
        got = exe.run(main, feed={'x': xs},
                      fetch_list=[out] + [g for _, g in grads])
    assert all(np.isfinite(g).all() for g in got)
    assert all(np.abs(g).max() > 0 for g in got)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-7)
    # every call: the held experts' groups and no tail group; the rows are
    # a block's tokens x K, or the compact path's few of the whole layer
    assert seen and all(groups == held[1] and sizes.shape == (held[1],)
                        for _, groups, sizes in seen)
    sizes = {rows for rows, _, _ in seen}
    assert min(tokens, moe_ops._HELD_BLOCK) * K in sizes
    if tokens > N:
        assert min(sizes) == moe_ops._HELD_SLACK * tokens * K * held[1] \
            // E < moe_ops._HELD_BLOCK * K
    live = count[held[0]:held[0] + held[1]].sum()
    assert live == tokens * 2 if forced else live < min(sizes)


# kinds of token by the experts a forced router gives them, of 32 with
# experts 4..7 or 6..7 held: how many of a token's K = 4 assignments are held
KINDS = {'all_k': (4, 5, 6, 7), 'two': (6, 7, 0, 1), 'one': (6, 0, 1, 2),
         'none': (0, 1, 2, 3)}
#        held, slack -> cap, tokens of each kind, the path the device takes
BOUNDARY = {
    # 256 x 2 = 512 held rows = cap: the last row of the layout is used
    'live_is_cap': ((6, 2), 2, {'two': 256, 'none': 768}, 'compact'),
    # one more: no layout of `cap` rows holds them, every row is kept
    'live_is_cap_plus_one': ((6, 2), 2, {'two': 256, 'one': 1, 'none': 767},
                             'blocks'),
    # tokens with K, two, one and no held assignment side by side: a
    # token's rows are added, its gradient is the sum
    'two_and_k_held_slots': ((4, 4), 2, {'all_k': 100, 'two': 200,
                                         'one': 50, 'none': 674}, 'compact'),
    # an EIGHTH held at the slack the module has: the layout is half the
    # 4096 rows, 4 x the expected 512. 512 x 4 = 2048 held rows = cap
    'eighth_live_is_cap': ((4, 4), 10, {'all_k': 512, 'none': 512},
                           'compact'),
    'eighth_live_is_cap_plus_one': ((4, 4), 10, {'all_k': 512, 'one': 1,
                                                 'none': 511}, 'blocks'),
    # one row under: 511 x 4 + 2 + 1 = 2047
    'eighth_live_is_cap_less_one': ((4, 4), 10, {'all_k': 511, 'two': 1,
                                                 'one': 1, 'none': 511},
                                    'compact'),
}


def plain_part(x, router, w1, w3, w2, held):
    """The held experts' part of the layer, every held expert on every
    token: no sort, no gather, no ragged op. Stacks of all E experts."""
    with jax.default_matmul_precision('highest'):
        gate, index = jax.lax.top_k(jax.nn.softmax(x @ router, -1), K)
        gate = gate / jnp.sum(gate, -1, keepdims=True)
        y = 0.0
        for e in range(held[0], held[0] + held[1]):
            mine = jnp.sum(jnp.where(index == e, gate, 0.0), -1)
            y = y + mine[:, None] * (
                (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
    return y


@pytest.mark.parametrize('case', list(BOUNDARY))
def test_the_boundary_of_the_layout_and_tokens_with_many_held_slots(
        case, monkeypatch):
    """`live == cap` takes the compact path and `live == cap + 1` the
    rows-kept one (the path NOT expected gives NaN here); a token with
    two and with K held assignments has its rows added up. Each equals
    the plain part of the uncut layer in value and in every gradient:
    the input's, the router's, the three stacks'."""
    held, slack, kinds, path = BOUNDARY[case]
    tokens = sum(kinds.values())
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', slack)
    cap = min(-(-slack * tokens * K * held[1] // E // 256) * 256,
              tokens * K // 2 // 256 * 256)
    live = sum(n * len([e for e in KINDS[kind]
                        if held[0] <= e < held[0] + held[1]])
               for kind, n in kinds.items())
    assert (live <= cap) == (path == 'compact') and 2 * cap <= tokens * K
    if 'live_is_cap' in case:
        assert live - cap == {'': 0, '_plus_one': 1, '_less_one': -1}[
            case.split('live_is_cap')[1]]
    monkeypatch.setattr(moe_ops, '_held_blocks' if path == 'compact'
                        else '_compact_moe', nan_path)
    rng = np.random.default_rng(7)
    # a token's kind is one of its first features; the router reads those
    kind_of = rng.permutation(np.repeat(np.arange(len(kinds)),
                                        list(kinds.values())))
    xs = rng.normal(size=(tokens, D)).astype('float32')
    xs[:, :len(kinds)] = np.eye(len(kinds), dtype='float32')[kind_of]
    router = np.zeros((D, E), 'float32')
    for i, kind in enumerate(kinds):
        router[i, list(KINDS[kind])] = 4.0 - np.arange(K)
    stacks = [rng.normal(size=s).astype('float32') * 0.3
              for s in ((E, D, H), (E, D, H), (E, H, D))]
    weights = [router] + stacks
    w = rng.normal(size=(tokens, D)).astype('float32')

    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out = layers.moe_mlp(
            _input('x', xs), num_experts=E, hidden_size=H, act='swish',
            gated=True, top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, experts_held=held)
        loss = layers.reduce_sum(layers.elementwise_mul(out, layers.data(
            name='w', shape=[tokens, D], dtype='float32',
            append_batch_size=False)))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    names = ['x'] + ['moe_mlp_0.w_%d' % i for i in range(4)]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _set_weights(fluid.global_scope(), weights, *held)
        got = exe.run(main, feed={'w': w},
                      fetch_list=[out] + [grads[n] for n in names])
    want = plain_part(xs, *weights, held)
    g_want = jax.grad(lambda *a: jnp.sum(plain_part(*a, held) * w),
                      argnums=range(5))(xs, *weights)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5)
    assert np.abs(got[0]).max() > 0.1
    for name, a, b in zip(names, got[1:], g_want):
        if b.shape != a.shape:            # a stack: the held experts' slice
            rest = np.delete(np.asarray(b), np.s_[held[0]:sum(held)], axis=0)
            assert np.abs(rest).max() == 0
            b = b[held[0]:held[0] + held[1]]
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * np.abs(b).max(), err_msg=name)


def _loops_and_dots(jaxpr, found):
    """Every loop and every dot_general's operand shapes of a jaxpr and of
    what it calls, a `cond`'s branches apart (they are returned)."""
    conds = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == 'cond':
            conds.append(eqn)
            continue
        if name in ('scan', 'while'):
            found['loops'].append(name)
        if name == 'dot_general':
            found['dots'].append(tuple(v.aval.shape for v in eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            conds += _loops_and_dots(sub, found)
    return conds


@pytest.mark.parametrize('way', ['forward', 'backward'])
def test_the_compact_path_is_indices_once_a_layer(way, monkeypatch):
    """From the jaxpr at toy widths: one `cond` a layer (a second in the
    backward pass, its transpose) and no loop outside it; the branch
    without a loop (the compact path) moves its rows with no dot_general
    at all, so none by a 0/1 matrix [cap, tokens]; the other branch (all
    the rows kept) is the one that walks blocks."""
    import types
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', 2)
    tokens, held = 4096, (6, 2)
    cap = 2 * tokens * K * held[1] // E
    rng = np.random.default_rng(5)
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32) for k, s in
              (('w1', (2, D, H)), ('w3', (2, D, H)), ('w2', (2, H, D)))}
    x = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    expert = jnp.asarray(np.argsort(rng.normal(size=(tokens, E)))[:, :K],
                         jnp.int32)
    gate = jnp.full((tokens, K), 0.25, jnp.float32)
    sizes = jnp.bincount(expert.reshape(-1), length=E).astype(jnp.int32)
    ctx = types.SimpleNamespace(platform='cpu')

    def part(params, x, gate):
        return jnp.sum(moe_ops._held_moe(params, x, expert, gate, sizes,
                                         held, 'swish', ctx))

    fn = part if way == 'forward' else jax.grad(part, argnums=(0, 1, 2))
    outside = {'loops': [], 'dots': []}
    conds = _loops_and_dots(jax.make_jaxpr(fn)(params, x, gate).jaxpr,
                            outside)
    assert len(conds) == (1 if way == 'forward' else 2)
    assert outside == {'loops': [], 'dots': []}
    for cond in conds:
        inside = []
        for branch in cond.params['branches']:
            found = {'loops': [], 'dots': []}
            assert _loops_and_dots(branch.jaxpr, found) == []
            inside.append(found)
        blocks, compact = inside           # lax.cond: (false, true)
        assert blocks['loops'] and not compact['loops']
        assert compact['dots'] == []
        assert not [shapes for shapes in blocks['dots']
                    if any({cap, tokens} <= set(s) for s in shapes)]


def test_biases_ride_the_compact_path_as_they_do_the_kept_rows(monkeypatch):
    """The Fluid layer's biased, ungated form of the experts (no cell
    runs it held): a laid-out row takes its expert's bias rows by the same
    index, and a row that no assignment fills gives nothing. The compact
    path against all rows kept, values and every gradient."""
    import types
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', 2)
    tokens, held = 4096, (6, 2)
    rng = np.random.default_rng(9)
    params = {k: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
              for k, s in (('w1', (2, D, H)), ('w2', (2, H, D)),
                           ('b1', (2, H)), ('b2', (2, D)))}
    x = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    expert = jnp.asarray(np.argsort(rng.normal(size=(tokens, E)))[:, :K],
                         jnp.int32)
    gate = jnp.asarray(rng.uniform(size=(tokens, K)), jnp.float32)
    sizes = jnp.bincount(expert.reshape(-1), length=E).astype(jnp.int32)
    w = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    ctx = types.SimpleNamespace(platform='cpu')

    def part(params, x, gate):
        return jnp.sum(w * moe_ops._held_moe(params, x, expert, gate, sizes,
                                             held, 'relu', ctx))

    both = []
    for path in ('compact', 'blocks'):
        both.append(jax.jit(jax.value_and_grad(part, argnums=(0, 1, 2)))(
            params, x, gate))
        blocks = moe_ops._held_blocks       # the second time: rows kept
        monkeypatch.setattr(moe_ops, '_compact_moe',
                            lambda p, x, key, gate, sizes, cap, act, ctx:
                            blocks(p, x, key, gate, act, ctx))
    for a, b in zip(*(jax.tree_util.tree_leaves(t) for t in both)):
        assert np.abs(b).max() > 0
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max())


def test_a_router_forced_onto_the_held_experts_loses_nothing():
    """Every token to held experts 8..11: all N x K assignments are
    computed, at an imbalance no fixed capacity would hold."""
    rng = np.random.default_rng(2)
    xs = np.abs(rng.normal(size=(N, D))).astype('float32') + 0.1
    router = np.zeros((D, E), 'float32')
    for j, e in enumerate((8, 9, 10, 11)):
        router[:, e] = 4.0 - j                      # x > 0: 8, 9, 10, 11
    (_, _, _), weights = run_share(None, xs)
    weights[0] = router
    (whole, _, count), _ = run_share(None, xs, weights)
    (part, _, count_s), _ = run_share((8, HELD), xs, weights)
    assert count_s[8:12].tolist() == [N] * 4
    assert count_s[8:8 + HELD].sum() == N * K == count_s.sum()
    np.testing.assert_allclose(part, whole, rtol=2e-5, atol=2e-7)
    # and a share that holds none of the chosen computes exactly nothing
    (none, _, _), _ = run_share((16, HELD), xs, weights)
    assert np.abs(none).max() == 0


def test_no_share_is_the_op_as_it_was():
    """experts_held=None adds nothing to the op: the same attributes, the
    same lowered module as a call that does not name it, and no select on
    the rows; a share that holds every expert computes the same values."""
    xs = np.random.default_rng(3).normal(size=(N, D)).astype('float32')
    texts = []
    for kwargs in ({}, {'experts_held': None}):
        main, startup = framework.Program(), framework.Program()
        main.random_seed = startup.random_seed = 3
        with unique_name.guard(), framework.program_guard(main, startup):
            x = layers.data(name='x', shape=[D], dtype='float32')
            out = layers.moe_mlp(x, num_experts=E, hidden_size=H,
                                 act='swish', gated=True, top_k=K,
                                 capacity_factor=None, bias_attr=False,
                                 **kwargs)
        op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
        assert 'experts_held' not in op.attrs
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            texts.append(exe.lowered_hlo(main, {'x': xs}, [out]))
    assert texts[0] == texts[1]
    (whole, _, _), weights = run_share(None, xs)
    (every, _, _), _ = run_share((0, E), xs, weights)
    np.testing.assert_allclose(every, whole, rtol=1e-6, atol=1e-8)


def test_a_share_is_dropless_only_and_a_range_of_the_experts():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[D], dtype='float32')
        with pytest.raises(ValueError, match='capacity_factor=None'):
            layers.moe_mlp(x, num_experts=E, hidden_size=H, gated=True,
                           bias_attr=False, experts_held=(0, 8))
        with pytest.raises(ValueError, match='not a range'):
            layers.moe_mlp(x, num_experts=E, hidden_size=H, gated=True,
                           bias_attr=False, capacity_factor=None,
                           experts_held=(28, 8))


def test_a_share_counts_its_lowering_and_moves_nothing_over_the_wire():
    from paddle_tpu.fluid.analysis import collectives
    label = {'path': 'grouped', 'held': '%dof%d' % (HELD, E),
             'dispatch': 'index'}
    before = obs.counter('moe.lowered', **label).value
    xs = np.ones((N, D), 'float32')
    main, startup, out, _, _ = build_share((0, HELD), amp=True)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={'x': xs}, fetch_list=[out])
        text = exe.lowered_hlo(main, {'x': xs}, [out])
    assert obs.counter('moe.lowered', **label).value > before
    op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
    assert op.attrs['experts_held'] == [0, HELD]
    assert collectives.op_collectives(op, main, {'dp': 4}) == []
    # under AMP the held experts multiply bf16, the router float32
    dots = [l for l in text.splitlines() if 'dot_general' in l]
    assert [l for l in dots if 'HIGHEST' in l and 'bf16' not in l]
    assert [l for l in dots if 'xbf16>, tensor' in l]


def test_a_held_lowering_counts_once_under_dispatch_index():
    """`moe.lowered{path=grouped, held=8of32, dispatch=index}`: one count a
    trace of the rule, so one for the step's lowering of a Program with
    one held layer; a layer that holds every expert names no dispatch."""
    def counts():
        return [obs.counter('moe.lowered', path='grouped', **more).value
                for more in ({'held': '%dof%d' % (HELD, E),
                              'dispatch': 'index'},
                             {'held': '%dof%d' % (HELD, E)}, {})]

    xs = np.ones((N, D), 'float32')
    for held, moved in (((8, HELD), [1, 0, 0]), (None, [0, 0, 1])):
        main, startup, out, _, _ = build_share(held)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            before = counts()
            exe.run(main, feed={'x': xs}, fetch_list=[out])
            first = counts()
            exe.run(main, feed={'x': xs}, fetch_list=[out])   # no new trace
            assert counts() == first
        assert [b - a for a, b in zip(before, first)] == moved


# An expert layer in a recompute region routes ONCE a step (ISSUE 62): what
# the route stage decides is named for the region's policy, the region's
# second forward reads it, and no number changes.
RT, RD = 256, 128        # tokens; a width the row-add kernel takes
ROUTED = {
    # way of the layout's add, attributes of the router, `top_k` a forward
    'scatter': (False, {}, 2),                  # the router's and AuxLoss's
    'kernel': (True, {}, 2),
    'scatter_grouped': (False, {'scoring': 'sigmoid', 'n_group': 4,
                                'topk_group': 2}, 4),    # two more by group
}
ROUTE_KEPT = ('moe.route_kept', 'moe.route_kept_bytes')


def _strip_names(monkeypatch):
    """The parent's lowering: no value of the route stage is named."""
    from paddle_tpu.parallel import moe
    for module in (moe, moe_ops):
        monkeypatch.setattr(module, 'checkpoint_name', lambda v, name: v)


def _held_step(case, region=True, held=(8, HELD)):
    """A toy Program with one held `moe_mlp` between two `fc`, in a
    recompute region or not, its add forced the `case`'s way: (loss and
    every gradient and the expert counts on one batch, the step's jaxpr,
    what its one trace counted under ROUTE_KEPT by the `held=` label and
    under `recompute.kept_values`)."""
    kernel, router, _ = ROUTED[case]
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[RD], dtype='float32')
        h = layers.fc(x, RD)
        with fluid.recompute_guard() if region else contextlib.nullcontext():
            out, aux, count = layers.moe_mlp(
                h, num_experts=E, hidden_size=H, act='swish', gated=True,
                top_k=K, capacity_factor=None, bias_attr=False,
                return_aux_loss=True, return_expert_count=True,
                experts_held=held, **router)
            y = layers.fc(out, RD)
        loss = layers.mean(y) + 0.01 * aux
        grads = fluid.backward.append_backward(loss)
    feed = {'x': np.random.default_rng(0).normal(size=(RT, RD)
                                                 ).astype('float32')}
    label = {'held': '%dof%d' % (held[1], E)} if held else {}
    counters = [obs.counter(n, **label) for n in ROUTE_KEPT] \
        + [obs.counter('recompute.kept_values')]
    with pytest.MonkeyPatch.context() as patch, \
            fluid.scope_guard(fluid.Scope()):
        patch.setattr(moe_ops, '_add_kernel', lambda *a: kernel)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetch = [loss, count] + [g for _, g in grads]
        before = [c.value for c in counters]
        values = exe.run(main, feed=feed, fetch_list=fetch)
        counted = [c.value - b for c, b in zip(counters, before)]
        scope = fluid.global_scope()
        compiled = exe.step_artifact(main, feed, fetch, scope)
        donated, readonly = compiled.plan.split(compiled.state_dict(scope))
        jaxpr = compiled._jitted.trace(
            donated, readonly, {n: jnp.asarray(v) for n, v in feed.items()},
            jax.random.key(0)).jaxpr
    return values, jaxpr, counted


def _primitives(jaxpr, counts=None):
    """How often each primitive stands in `jaxpr` and in what it calls,
    the FALSE branch of a two-way `cond` apart: of the held layer's
    `lax.cond` that is `_held_blocks`, whose blocks sort for themselves
    under a checkpoint of their own."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if name == 'cond' and len(subs) == 2:
            subs = subs[1:]
        for sub in subs:
            _primitives(sub, counts)
    return counts


@pytest.mark.parametrize('case', list(ROUTED))
def test_a_region_makes_the_choice_and_the_index_once(case, monkeypatch):
    """In a region the step's jaxpr holds each `top_k` of the router and
    each `sort` of the compact path ONCE, where the same Program with the
    names stripped (the parent's lowering) holds them twice, forward and
    the region's second forward; the loss, every gradient and the counts
    are the stripped Program's TO THE BIT."""
    kernel, _, top_ks = ROUTED[case]
    sorts = 1 if kernel else 2    # the keys'; the scatter's: tokens' too
    named, jaxpr, _ = _held_step(case)
    found = _primitives(jaxpr)
    assert (found['top_k'], found['sort']) == (top_ks, sorts)
    assert found['name'] == 3 + (8 if kernel else 5)
    _strip_names(monkeypatch)
    plain, jaxpr, _ = _held_step(case)
    found = _primitives(jaxpr)
    assert (found['top_k'], found['sort']) == (2 * top_ks, 2 * sorts)
    assert 'name' not in found
    # the layer was on the compact path: the held rows fit the layout
    cap = moe_ops._held_layout(RT * K, HELD, E)
    assert 0 < plain[1][8:8 + HELD].sum() <= cap == 512
    assert len(named) == len(plain) > 6
    for a, b in zip(named, plain):
        assert np.abs(b).max() > 0
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize('case', ['scatter', 'scatter_grouped'])
def test_outside_a_region_the_names_are_inert(case, monkeypatch):
    """No region, no `jax.checkpoint` to read a name: the step's jaxpr is
    the stripped Program's with the `name` equations between, and so are
    its numbers."""
    named, jaxpr, _ = _held_step(case, region=False)
    with_names = _primitives(jaxpr)
    assert with_names.pop('name') == 3 + 5
    _strip_names(monkeypatch)
    plain, jaxpr, _ = _held_step(case, region=False)
    assert _primitives(jaxpr) == with_names
    for a, b in zip(named, plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize('case,held', [
    ('scatter', (8, HELD)), ('kernel', (8, HELD)), ('scatter', None)])
def test_a_lowering_counts_what_it_named_and_its_bytes(case, held):
    """`moe.route_kept{held=}` and `moe.route_kept_bytes{held=}`, a trace
    of the rule: the rule's three for every path (the choice [k, tokens],
    `f` and the counts [E]), and beside them, where the layer has a
    layout, what the compact path decides at (tokens, k, cap): `src` and
    `token` [cap], the scalar `live`, and the scatter's `order` and
    `token[order]` [cap] or the kernel's plan, four of tiles x held +
    chunks steps and their number. Not the models' marks."""
    from paddle_tpu.ops.kernels import row_add
    _, _, (arrays, nbytes, marks) = _held_step(case, held=held)
    rule = [RT * K, E, E]
    cap = moe_ops._held_layout(RT * K, HELD, E)
    t, r = row_add.tiles(RD)
    steps = RT // t * HELD + cap // r
    layout = [] if held is None else [cap, cap, 1] + (
        [steps] * 4 + [1] if case == 'kernel' else [cap, cap])
    assert arrays == len(rule + layout) == (3 if held is None
                                            else 11 if case == 'kernel'
                                            else 8)
    assert nbytes == 4 * sum(rule + layout)
    assert marks == 0
