"""Trinity-Mini (`afmoe`) on the normal path (ISSUE 49): a layer of each
kind against the benchmark's plain reference on every gradient (a rule
moved in the reference FAILS the comparison: the window's edge, which
layers turn, the per-head norms' place, the gate, the sandwich order, the
muP scale, the shared expert; the five layers together run the cell's own
checks in tests/test_chipbench/test_chipbench_cells.py), the global layer's
blindness to order, a post-branch norm weight of 0 silencing its branch,
an expert layer's eight SHARES plus the shared expert counted once adding
up to the uncut reference's layer, the bias update, the name scopes, the
regions, the warm-up, the configuration's file, its FLOPs and its
readers. Small sizes, on the CPU."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name

import decoder_toy
from decoder_toy import REPO, build_toy

CELL = 'trinitymini_s8192'
CONFIG = 'trinity_mini_26b_a3b'
# the layers the cell runs, by their index in the source's layer_types
DENSE_WINDOWED, EXPERT_WINDOWED, EXPERT_GLOBAL = 1, 2, 3


reference_module = functools.partial(decoder_toy.reference_module,
                                     'afmoe')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


def _one_layer(index):
    """The toy cell cut to the ONE layer `index` of layer_types, at a
    window of 5 keys over rows of 80."""
    return _toy_cell(num_hidden_layers=1, first_layer=index,
                     num_dense_layers=int(index < 2), sliding_window=5)


@functools.lru_cache(maxsize=None)
def _layer_program(index):
    """The check Program of the toy cell cut to layer `index`, run ONCE a
    kind, as harness/check.py run_check runs it (float32, highest matmul
    precision, a seeded sample of 2 rows): its loss, the gradient of every
    trainable parameter, and the scope's weights in the reference's tree.
    The moved rules below are compared with this one run."""
    from chipbench.harness import check
    cell = _one_layer(index)
    config = dict(cell['config'], amp='none')
    traffic = dict(cell['traffic'], pool=1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        train = cell['builder'].build(config, traffic)
        exe.run(train['startup'])
        every = check.parameter_names(train['main'])
        built = cell['builder'].build(dict(config, check={'grads': every}),
                                      traffic, train=False)
        names = sorted(built['grads'])
        pool, _ = cell['generator'].make_pool(traffic, config, 6)
        with jax.default_matmul_precision('highest'):
            out = exe.run(built['main'], feed=pool[0],
                          fetch_list=[built['loss']]
                          + [built['grads'][n] for n in names])
        scope = fluid.global_scope()
        params, tree = cell['builder'].reference_params(
            config, built['main'],
            lambda n: np.asarray(scope.find_var(n).get_tensor()))
    return {'model': config['model'], 'batch': pool[0], 'params': params,
            'every': every, 'paths': check.grad_paths(tree, set(names)),
            'loss': float(np.asarray(out[0]).reshape(-1)[0]),
            'grads': dict(zip(names, out[1:])),
            'main': built['main']}


def _against(side, reference):
    """(relative error of the loss, {parameter: its gradient's}) of
    `_layer_program`'s run against `reference`."""
    from chipbench.harness import check
    paths = side['paths']
    loss, grads = reference.loss_and_grads(
        side['params'], side['model'], side['batch'],
        sorted({path for path, _ in paths.values()}))
    rel = {}
    for name, (path, i) in paths.items():
        want = grads[path] if i is None else grads[path][i]
        rel[name] = check.rel_norm(side['grads'][name], np.asarray(want))
    return abs(side['loss'] - loss) / abs(loss), rel


# ------------------------------------------------------------------ the model

# parameters a layer: 4 norms of the stream, 2 of the heads, 5 matrices of
# the mixer; 3 of a dense feed-forward; router, 3 stacks, bias, 3 shared
_MIXER, _DENSE, _EXPERTS = 11, 3, 8


@pytest.mark.parametrize('index, n_params', [
    (DENSE_WINDOWED, _MIXER + _DENSE), (EXPERT_WINDOWED, _MIXER + _EXPERTS),
    (EXPERT_GLOBAL, _MIXER + _EXPERTS)],
    ids=['dense_windowed', 'expert_windowed', 'expert_global'])
def test_a_layer_of_each_kind_agrees_with_the_plain_reference(index,
                                                               n_params):
    """One layer of models/afmoe.py (with the scaled embedding before it
    and the head after it) through the Executor against
    chipbench/references/afmoe.py in float32 to 1e-5: the loss and the
    gradient of every parameter, at a window of 5 keys over rows of 80,
    8 query heads over 2, experts 4..7 of 16 held beside the shared
    one."""
    side = _layer_program(index)
    assert len(side['every']) == 1 + n_params + 2
    assert len(side['grads']) == 1 + n_params + 2 - (index >= 2)  # no bias
    loss_rel, grad_rel = _against(side, reference_module())
    assert loss_rel < 1e-5 and max(grad_rel.values()) < 1e-5, grad_rel
    flash, = [op for op in side['main'].global_block().ops
              if op.type == 'flash_attention']
    assert flash.attrs.get('window') == (None if index == EXPERT_GLOBAL
                                         else 5)


def _window_one_key(by):
    def move(ref):
        seen = ref.seen
        ref.seen = lambda rows, keys, window: seen(
            rows, keys, None if window is None else window + by)
    return move


def _no_rotary(ref):
    ref.rotary = lambda x, theta: x


def _rotary_on_the_global_layer(ref):
    """The one layer of the cell it is told on is the global one: turned,
    with a window that covers every row."""
    attention = ref.attention
    ref.attention = lambda w, g, model, kind: attention(
        w, g, dict(model, sliding_window=1 << 20), 'sliding_attention')


def _norms_after_rotary(ref):
    rms, rotary = ref.rms, ref.rotary
    # the per-head norms have weights of head_dim; the stream's of hidden
    ref.rotary = lambda x, theta: x

    def moved(t, w, eps):
        out = rms(t, w, eps)
        return out if t.ndim != 4 else rms(rotary(t, 10000), w, eps)
    ref.rms = moved


def _gate_halved(ref):
    """sigmoid(g Wg / 2) for sigmoid(g Wg). (A gate of 0.5 throughout, or
    none, would be a uniform scale of the branch, which the norm on the
    branch's output takes out again: the sandwich order hides it from
    every value, and only the gate's own gradient would tell.)"""
    attention = ref.attention
    ref.attention = lambda w, g, model, kind: attention(
        dict(w, gate=0.5 * w['gate']), g, model, kind)


def _pre_norm_only(ref):
    """x + branch(norm(x)): the repo's other models' residual path."""
    rms = ref.rms

    def layer(w, x, model, index):
        eps = model['rms_norm_eps']
        kind, is_dense = ref.layer_kind(model, index)
        h = x + ref.attention(w, rms(x, w['norm_in'], eps), model, kind)
        m = rms(h, w['norm_pre_mlp'], eps)
        f = ref.dense(m, *w['ffn']) if is_dense \
            else ref.experts(w, m, model) + ref.dense(m, *w['shared'])
        return h + f
    ref.layer = layer


def _no_mup(ref):
    forward = ref.forward_loss
    ref.forward_loss = lambda params, model, ids, labels: forward(
        params, dict(model, mup_enabled=False), ids, labels)


def _shared_expert_gated(ref):
    dense = ref.dense
    ref.dense = lambda m, w1, w3, w2: 0.5 * dense(m, w1, w3, w2)


# {rule: (the layer it is told on, the move)}
_MOVED = {
    'window_one_key_wider': (EXPERT_WINDOWED, _window_one_key(1)),
    'window_one_key_narrower': (EXPERT_WINDOWED, _window_one_key(-1)),
    'rotary_on_the_global_layer': (EXPERT_GLOBAL,
                                   _rotary_on_the_global_layer),
    'no_rotary_on_a_windowed_layer': (EXPERT_WINDOWED, _no_rotary),
    'gate_of_half_the_projection': (DENSE_WINDOWED, _gate_halved),
    'pre_norm_only': (DENSE_WINDOWED, _pre_norm_only),
    'embedding_not_scaled': (DENSE_WINDOWED, _no_mup),
    'shared_expert_halved': (EXPERT_WINDOWED, _shared_expert_gated),
}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference with ONE rule moved, the same one-layer Program's run FAILS
    the same tolerance, by a hundred times and more. The reference is a
    fresh copy of the module with one function moved (its callers look it
    up in the module)."""
    index, move = _MOVED[rule]
    reference = reference_module()
    move(reference)
    _, grad_rel = _against(_layer_program(index), reference)
    assert max(grad_rel.values()) > 1e-3, grad_rel


def test_norms_after_rotary_show_in_the_norms_own_gradients_alone():
    """A rotation keeps a head's mean square, so while the per-head norm
    weights are 1 the order of norm and rotary changes no VALUE: only the
    gradients of those two weights of head_dim numbers tell it, which is
    why the configuration's float32 check names a q-norm weight."""
    reference = reference_module()
    _norms_after_rotary(reference)
    loss_rel, grad_rel = _against(_layer_program(EXPERT_WINDOWED), reference)
    assert loss_rel < 1e-5
    told = {n for n, r in grad_rel.items() if r > 1e-3}
    assert told == {'rms_norm_1.w_0', 'rms_norm_2.w_0'}, grad_rel
    assert max(r for n, r in grad_rel.items() if n not in told) < 1e-5


# ----------------------------------------------------- one layer on an input

T, HIDDEN = 24, 32


def _sizes(**over):
    c = dict(layer_types=('sliding_attention', 'full_attention'), n_dense=0,
             hidden=HIDDEN, n_head=4, n_kv_head=2, d_head=8, window=T,
             dense_width=48, n_expert=16, top_k=2, expert_width=16,
             shared_width=16, experts_held=None, eps=1e-5, rope_theta=1e4,
             norm_topk_prob=True, gate_scale=2.826, norm_eps=1e-20, std=0.3)
    c.update(over)
    return c


def _run_layer(index, c, x, zero=()):
    """decoder_layer(x, index, c) on the fed [B, T, hidden] `x`, the
    parameters named in `zero` set to 0. Returns (output, {name:
    value})."""
    from paddle_tpu.models import afmoe as A
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with unique_name.guard(), framework.program_guard(main, startup):
        data = layers.data(name='x', shape=[T, HIDDEN], dtype='float32')
        out, _, _ = A.decoder_layer(data, index, c)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        params = {}
        for p in main.global_block().all_parameters():
            tensor = scope.find_var(p.name).get_tensor()
            if p.name in zero:
                tensor.set(np.zeros(p.shape, 'float32'), fluid.CPUPlace())
            params[p.name] = np.asarray(tensor).copy()
        got, = exe.run(main, feed={'x': x}, fetch_list=[out])
    return np.asarray(got), params


def test_the_global_layer_sees_no_order_and_a_windowed_layer_does():
    """No positional encoding on a `full_attention` layer: with the
    earlier positions of a row SHUFFLED the last position's output does
    not move (its keys are the same set); on a `sliding_attention` layer
    whose window covers the row, so that the keys are the same set too,
    rotary tells the order and it does."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, T, HIDDEN)).astype('float32')
    order = np.concatenate([rng.permutation(T - 1), [T - 1]])
    for index, moves in ((1, False), (0, True)):
        both, _ = _run_layer(index, _sizes(), np.concatenate(
            [x, x[:, order]]))
        a, b = both[:2], both[2:]
        gap = np.abs(a[:, -1] - b[:, -1]).max()
        assert (gap > 1e-2) if moves else (gap < 1e-5), (index, gap)
        # every other position's keys changed: the layer is not blind
        assert np.abs(a[:, 3] - b[:, 3]).max() > 1e-2


# the post-branch norms of a layer built alone: the mixer's, the
# feed-forward's (creation order, models/afmoe.py decoder_layer)
_POST_ATTN, _POST_MLP = 'rms_norm_3.w_0', 'rms_norm_5.w_0'


@pytest.mark.parametrize('silenced', ['attention', 'feed_forward', 'both'])
def test_a_post_branch_norm_weight_of_zero_silences_its_branch(silenced):
    """The sandwich order: h = x + rms(branch, w), so w = 0 takes the
    branch out of the stream whatever it computed. With both at 0 the
    layer is the identity; with one, what is left is the other branch on
    a stream that never saw the first, as the plain reference gives it."""
    reference = reference_module()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, T, HIDDEN)).astype('float32')
    zero = {'attention': (_POST_ATTN,), 'feed_forward': (_POST_MLP,),
            'both': (_POST_ATTN, _POST_MLP)}[silenced]
    c = _sizes(n_dense=1)                  # layer 0: windowed, dense
    got, p = _run_layer(0, c, x, zero=zero)
    if silenced == 'both':
        np.testing.assert_array_equal(got, x)
        return
    assert np.abs(got - x).max() > 1e-2
    w = {'norm_in': p['rms_norm_0.w_0'], 'q': p['fc_0.w_0'],
         'k': p['fc_1.w_0'], 'v': p['fc_2.w_0'],
         'q_norm': p['rms_norm_1.w_0'], 'k_norm': p['rms_norm_2.w_0'],
         'gate': p['fc_3.w_0'], 'out': p['fc_4.w_0'],
         'norm_post_attn': p[_POST_ATTN], 'norm_pre_mlp': p['rms_norm_4.w_0'],
         'ffn': [p['fc_%d.w_0' % i] for i in (5, 6, 7)],
         'norm_post_mlp': p[_POST_MLP]}
    model = {'rms_norm_eps': 1e-5, 'layer_types': ['sliding_attention'],
             'num_dense_layers': 1, 'num_attention_heads': 4,
             'num_key_value_heads': 2, 'head_dim': 8, 'rope_theta': 1e4,
             'sliding_window': T}
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.layer(w, jnp.asarray(x), model, 0))
        # the silenced branch's own weights are nowhere in the result
        other = dict(w, **({'out': 2 * w['out']} if silenced == 'attention'
                           else {'ffn': [2 * a for a in w['ffn']]}))
        same = np.asarray(reference.layer(other, jnp.asarray(x), model, 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(want, same)


# ------------------------------------------------------------------ the share

N, D, E, H, K, HELD = 96, 16, 64, 12, 4, 8


def _share_data(seed=2):
    rng = np.random.default_rng(seed)
    return {'m': rng.normal(size=(N, D)).astype('float32'),
            'router': rng.normal(size=(D, E)).astype('float32'),
            'w1': rng.normal(size=(E, D, H)).astype('float32') * 0.3,
            'w3': rng.normal(size=(E, D, H)).astype('float32') * 0.3,
            'w2': rng.normal(size=(E, H, D)).astype('float32') * 0.3,
            # a bias that is NOT 0: it moves the choice and must stay out
            # of the gates
            'bias': rng.normal(size=(E,)).astype('float32') * 0.2,
            'shared': [rng.normal(size=s).astype('float32') * 0.3
                       for s in ((D, H), (D, H), (H, D))]}


def _run_blocks(helds, data):
    """models/afmoe.py expert_block on the tokens `m`, once for each entry
    of `helds` ((first, count), or None for every expert) in ONE Program,
    each block on its share of the given weights. Returns [(routed +
    shared, counts)]."""
    from paddle_tpu.models import afmoe as A
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        m = layers.create_parameter([1, N, D], 'float32', name='pm')
        fetch = []
        for held in helds:
            out, count, _ = A.expert_block(m, _sizes(
                hidden=D, n_expert=E, top_k=K, expert_width=H,
                shared_width=H, experts_held=held))
            fetch += [out, count]
    values = {'pm': data['m'][None]}
    for b, held in enumerate(helds):
        first, n = held or (0, E)
        values['moe_mlp_%d.w_0' % b] = data['router']
        values['moe_mlp_%d.w_4' % b] = data['bias']
        for i, k in enumerate(('w1', 'w3', 'w2')):
            values['moe_mlp_%d.w_%d' % (b, i + 1)] = data[k][first:first + n]
        for i, w in enumerate(data['shared']):
            values['fc_%d.w_0' % (3 * b + i)] = w
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for name, value in values.items():
            fluid.global_scope().find_var(name).get_tensor().set(
                value, fluid.CPUPlace())
        got = [np.asarray(g) for g in exe.run(main, fetch_list=fetch)]
    return [(got[2 * b][0], got[2 * b + 1]) for b in range(len(helds))]


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST of the model-configs guide, section 4: the routed
    parts of all 8 shares of one layer (first_expert_held 0, 8, .. 56)
    plus the shared expert, which every chip computes alike, counted ONCE
    add up to what the UNCUT plain reference gives for the whole layer;
    the counts are the whole layer's in every share; with a selection
    bias that is not 0."""
    reference = reference_module()
    data = _share_data()
    model = {'num_experts_per_tok': K, 'route_norm': True,
             'route_scale': 2.826, 'router_norm_eps': 1e-20}
    w = {'router': data['router'], 'bias': data['bias'],
         'experts_in': [data['w1'], data['w3']], 'experts_down': data['w2']}
    m = jnp.asarray(data['m'])[None]
    with jax.default_matmul_precision('highest'):
        shared = np.asarray(reference.dense(m, *data['shared']))[0]
        routed = np.asarray(reference.experts(w, m, model))[0]
        cut = dict(w, experts_in=[s[8:16] for s in w['experts_in']],
                   experts_down=data['w2'][8:16])
        part1 = np.asarray(reference.experts(
            cut, m, dict(model, first_expert_held=8)))[0]
    blocks = _run_blocks([None] + [(first, HELD)
                                   for first in range(0, E, HELD)], data)
    whole, counts = blocks[0]
    assert counts.sum() == N * K
    np.testing.assert_allclose(whole, routed + shared, rtol=2e-4, atol=2e-5)
    parts = []
    for part, count in blocks[1:]:
        np.testing.assert_array_equal(count, counts)
        parts.append(part - shared)            # the share's routed part
        assert np.abs(parts[-1]).max() > 0.05
    np.testing.assert_allclose(sum(parts) + shared, routed + shared,
                               rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(parts[1], part1, rtol=2e-4, atol=2e-5)
    # the gates carry the route scale and not the bias; the bias chose
    free = np.asarray(reference.experts(
        dict(w, bias=0 * data['bias']), m, model))[0]
    assert np.abs(free - routed).max() > 0.05
    # each token's gates sum to route_scale over ALL its chosen experts
    gates = np.asarray(reference.route(m[0], data['router'], data['bias'],
                                       model))
    np.testing.assert_allclose(gates.sum(-1), 2.826, rtol=1e-5)
    assert ((gates > 0).sum(-1) == K).all()


def test_the_bias_follows_the_load_and_no_gradient_reaches_it():
    """After a step b_e has moved by rate * sign(mean(c) - c_e), c the
    step's assignments per expert over ALL the router's experts; no
    optimizer op touches it (and append_backward hands out no gradient
    for it: `_layer_program`'s count above)."""
    from paddle_tpu.models import afmoe as A
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    rate = 0.01
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = A.get_model(
            layer_types=('sliding_attention', 'full_attention'),
            experts_held=(4, 4), bias_rate=rate)
    block = main.global_block()
    biases = [p for p in block.all_parameters() if not p.trainable]
    assert len(biases) == len(counts) == 1
    moved = {n for op in block.ops if op.type == 'adam'
             for n in op.input('Param')}
    assert moved and not moved & {b.name for b in biases}
    updates = [op for op in block.ops
               if op.attrs.get('name_scope') == 'router_bias']
    assert updates and block.ops.index(updates[0]) > max(
        i for i, op in enumerate(block.ops) if op.type == 'adam')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()

        def read():
            return [np.asarray(scope.find_var(b.name).get_tensor()).copy()
                    for b in biases]

        batch = next(iter(train()))
        feed = {feeds[0]: np.stack([b[0] for b in batch]),
                feeds[1]: np.stack([b[1] for b in batch])}
        before = read()
        losses = []
        for _ in range(6):
            out = exe.run(main, feed=feed, fetch_list=[loss] + counts)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
            after = read()
            for b0, b1, c in zip(before, after, out[1:]):
                c = np.asarray(c).astype('float64')
                assert c.sum() == 2 * 32 * 2      # dropless, over all 16
                np.testing.assert_allclose(
                    b1 - b0, rate * np.sign(c.mean() - c), atol=1e-7)
            before = after
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_a_layer_of_another_kind_is_refused():
    from paddle_tpu.models import afmoe as A
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match='sliding_attention'):
            A.afmoe(64, 16, layer_types=('conv',), n_dense=0, hidden=16,
                    n_head=2, n_kv_head=1, d_head=8, n_expert=4, top_k=2,
                    expert_width=8, shared_width=8)


# ------------------------------------------------ scopes, regions, schedule

def test_layers_differ_by_kind_scopes_regions_and_counters():
    """The toy cell's first three layers (dense-windowed, expert-windowed,
    expert-GLOBAL): the mixers are built under `window_attention` (rotary, the window)
    or `global_attention` (neither) by their kind, the two post-branch
    norms a layer under `sandwich_norm`, the shared experts under
    `shared_expert`; six norms a layer and the final one; the embedding's
    scale is sqrt(hidden); every layer is one recompute region; the
    scopes reach the optimized HLO's op_name."""
    from chipbench.harness import catalog, scopes
    cell = _toy_cell(num_hidden_layers=3)
    lowered = dict(path='grouped', held='4of16', dispatch='index',
                   scoring='sigmoid')
    before = obs.counter('moe.lowered', **lowered).value
    config, built = build_toy(cell, train=True)
    assert obs.counter('moe.lowered', **lowered).value - before == 2
    ops = built['main'].global_block().ops
    forward = [op for op in ops if not op.type.endswith('_grad')]
    flash = [op for op in forward if op.type == 'flash_attention']
    assert [op.attrs.get('name_scope') for op in flash] == [
        'window_attention', 'window_attention', 'global_attention']
    assert [op.attrs.get('window') for op in flash] == [24, 24, None]
    assert all(op.attrs['causal'] for op in flash)
    rotary = [op for op in forward if op.type == 'rotary_embedding']
    assert len(rotary) == 4 and all(
        op.attrs['name_scope'] == 'window_attention' for op in rotary)
    norms = [op for op in forward if op.type == 'rms_norm']
    assert len(norms) == 3 * 6 + 1
    by_scope = [op.attrs.get('name_scope') for op in norms]
    assert by_scope.count('sandwich_norm') == 6
    assert by_scope.count('window_attention') == 4          # q and k
    assert by_scope.count('global_attention') == 2
    assert by_scope.count(None) == 3 * 2 + 1
    sigmoids = [op for op in forward if op.type == 'sigmoid']
    assert len(sigmoids) == 3 and all(
        op.attrs['name_scope'] in ('window_attention', 'global_attention')
        for op in sigmoids)
    scale, = [op for op in forward if op.type == 'scale'
              and op.attrs.get('name_scope') is None][:1]
    assert scale.attrs['scale'] == pytest.approx(8.0)       # sqrt(64)
    moe = [op for op in forward if op.type == 'moe_mlp']
    assert len(moe) == 2 and not any(op.input('RouterX') for op in moe)
    assert all(op.attrs.get('name_scope') is None for op in moe)
    shared = [op for op in forward
              if op.attrs.get('name_scope') == 'shared_expert']
    assert [op.type for op in shared].count('mul') == 2 * 3
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 3
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          config, 5)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        exe.run(built['main'], feed=pool[0], fetch_list=[built['loss']])
        assert len(exe.step_artifact(built['main'], pool[0],
                                     [built['loss']]).regions) == 3
        text = exe.lowered_hlo(built['main'], pool[0], [built['loss']],
                               optimized=True)
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    under = {name: window.op_scopes_under(text, name) for name in (
        'window_attention', 'global_attention', 'sandwich_norm',
        'shared_expert')}
    assert all(under.values())
    kinds = {name: {s.rsplit('_', 1)[0] for s in found}
             for name, found in under.items()}
    assert kinds['sandwich_norm'] == {'rms_norm'}
    assert kinds['shared_expert'] >= {'mul'} and not kinds[
        'shared_expert'] & {'moe_mlp', 'rms_norm', 'flash_attention'}
    assert 'rotary_embedding' in kinds['window_attention']
    assert 'rotary_embedding' not in kinds['global_attention']
    for name in ('window_attention', 'global_attention'):
        assert kinds[name] >= {'mul', 'flash_attention', 'rms_norm',
                               'sigmoid'}
    names = list(under)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            assert not under[a] & under[b], (a, b)
    assert scopes.instruction_scopes(text)


def test_the_cell_trains_through_the_warm_up_and_checks_without():
    """What tests/test_chipbench/test_chipbench_schedule.py holds of the
    five older held cells, held here of this one (that file's list is the
    benchmark's; builders/afmoe.py says why its `held_share` is not named
    `experts`): the published optimizer is the held cells' linear warm-up,
    ONE schedule feeds every Adam op, the check Programs carry none of it,
    (builders/adam.py builds the rate; that file's cases run it)."""
    from chipbench.harness import catalog
    published = catalog.load_cell(CELL)['config']['optimizer']
    assert published == {
        'kind': 'adam', 'beta1': 0.9, 'beta2': 0.95, 'epsilon': 1e-08,
        'learning_rate': 4e-4, 'schedule': 'linear_warmup',
        'warmup_steps': 2000}
    cell = _toy_cell()
    config, traffic = cell['config'], cell['traffic']
    assert config['optimizer'] == published and 'optimizer' in config[
        'assumed']
    assert cell['builder'].held_share(config)[1] is not None
    assert not hasattr(cell['builder'], 'experts')
    scope, built = decoder_toy.started(cell)
    main = built['main']
    types = [op.type for op in main.global_block().ops]
    assert {'increment', 'elementwise_pow', 'elementwise_min'} <= set(types)
    assert types.count('increment') == 1
    rate, = {name for op in main.global_block().ops if op.type == 'adam'
             for name in op.input('LearningRate')}
    assert types.count('adam') == len(
        [p for p in main.global_block().all_parameters() if p.trainable])
    check = cell['builder'].build(
        dict(config, check=config['checks']['amp']), traffic, train=False)
    assert not {'increment', 'adam', 'sign'} & {
        op.type for op in check['main'].global_block().ops}
    written = {n for op in main.global_block().ops
               for n in op.output_arg_names}
    assert rate in written           # the schedule's, no startup constant
    std = float(np.std(np.asarray(scope.find_var(
        'embedding_0.w_0').get_tensor())))
    # the embedding at five units after the muP scale, every other matrix
    # at 0.02 (`assumed.initializers`: a held share's load at step 0)
    assert std == pytest.approx(0.11, rel=0.05)


# ------------------------------------------------------------- the benchmark

def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth, the dense layers that run, the experts
    held and the vocabulary are cut, and layer_types stands whole."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           CONFIG + '.json')) as f:
        held = json.load(f)
    kinds = ['sliding_attention'] * 3 + ['full_attention']
    source = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "layer_types": kinds * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'Trinity-Mini':
                    assert row['config'] == source
                    assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 5, 'num_dense_layers': 1, 'num_experts': 16,
           'vocab_size': 25024}
    for key, value in source.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: source[k] for k in cut}
    added = {'first_layer': 1, 'first_expert_held': 0,
             'router_norm_eps': 1e-20, 'bias_update_speed': 0.001,
             'initializer_range': 0.02, 'embedding_initializer_range': 0.11}
    assert {k: held['model'][k] for k in set(held['model']) - set(source)} \
        == added
    assert held['model']['bias_update_speed'] == source['load_balance_coeff']
    # the floors of the guide: the leading dense layers once, a whole
    # period and four layers after them, 8 experts, an eighth of the
    # vocabulary
    run = held['layer_types'][1:1 + held['num_hidden_layers']]
    assert run == ['sliding_attention', 'sliding_attention',
                   'full_attention', 'sliding_attention',
                   'sliding_attention']
    assert sorted(run[1:]) == sorted(kinds)
    assert held['num_experts'] >= 8
    assert held['vocab_size'] * 8 == source['vocab_size']
    assert (held['builder'], held['reference'], held['flops']) == (
        'afmoe',) * 3
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('top_level_keys', 'num_hidden_layers', 'num_dense_layers',
                'num_experts', 'vocab_size', 'attention_gate', 'qk_norms',
                'positions', 'window_convention', 'sandwich_norms',
                'mup_scale', 'router', 'bias_update', 'initializers',
                'optimizer', 'document_mask', 'recomputation'):
        assert held['assumed'][key], key
    assert '8 chips' in held['deployment'] and '705.5 M' in held[
        'deployment'] and '512' in held['deployment']
    # what this model adds is among the float32 entry's gradients: a
    # q-norm weight, a post-branch norm weight, the gate's projection
    f32 = set(held['checks']['float32']['grads'])
    assert {'rms_norm_1.w_0', 'rms_norm_3.w_0', 'fc_3.w_0',
            'embedding_0.w_0', 'moe_mlp_0.w_0', 'moe_mlp_0.w_3'} <= f32
    # the FIRST expert layer's router and stack, not the last's: one
    # token that flips its eighth choice in an early layer is routed anew
    # by every later one, and the last router's gradient then reads 0.013
    # in float32 (chip, PR 49, one seed of ten); the last layer's stack is
    # `amp_experts`'; 2^-6 is the room of ONE flipped held assignment in
    # the first layer itself (sqrt(1 / 8192) = 0.011; read 0.0061)
    assert not {'moe_mlp_3.w_0', 'moe_mlp_3.w_3'} & f32
    assert held['checks']['amp_experts']['grads'] == ['moe_mlp_3.w_3']
    assert [held['checks'][k]['tolerance']['grad'] for k in (
        'float32', 'amp', 'amp_experts')] == [2 ** -6, 2 ** -5, 0.25]
    assert held['checks']['float32']['tolerance']['grad'] < held['checks'][
        'amp']['tolerance']['grad'] < held['checks']['amp_experts'][
        'tolerance']['grad']
    for entry in held['checks'].values():
        assert len(entry['why']) > 400
    # the names are the ones the Program at the published depth gives them
    cell = _toy_cell()
    tree = cell['builder'].reference_params(
        cell['config'], cell['builder'].build(
            cell['config'], cell['traffic'])['main'], lambda n: n)[1]
    assert tree['layer0.q_norm'] == 'rms_norm_1.w_0'
    assert tree['layer0.norm_post_attn'] == 'rms_norm_3.w_0'
    assert tree['layer0.gate'] == 'fc_3.w_0'
    assert tree['layer2.q'] == 'fc_16.w_0'                  # the global
    assert tree['layer4.experts_down'] == 'moe_mlp_3.w_3'
    assert tree['layer4.norm_post_mlp'] == 'rms_norm_29.w_0'
    named = {n for v in tree.values()
             for n in (v if isinstance(v, list) else [v])}
    for entry in held['checks'].values():
        assert set(entry['grads']) <= named


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 8192 (ISSUE 49): matmuls 553 M (layer 1
    130, an expert layer 80.2, the head 102.5), scores 16384 a pair: the
    global layer 0.55 TFLOP, the four windowed 0.96; 6.04 TFLOP forward,
    18.1 a step; 705.5 M parameters."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 8192
    f = flops.forward_flops(config, traffic['batch'], traffic['seq'])
    per = {k: v / tokens / 1e6 for k, v in f.items()}
    assert flops.layer_counts(config['model']) == (1, 4, 1, 4)
    assert per['projections'] == pytest.approx(5 * 54.5, rel=0.005)
    assert per['dense'] == pytest.approx(75.5, rel=0.005)
    assert per['experts'] == per['shared'] == pytest.approx(4 * 12.58,
                                                            rel=0.005)
    assert per['router'] == pytest.approx(4 * 0.524, rel=0.005)
    assert per['head'] == pytest.approx(102.5, rel=0.005)
    matmuls = sum(per.values()) - per['global_scores'] - per['window_scores']
    assert matmuls == pytest.approx(553, rel=0.005)
    assert f['global_scores'] == pytest.approx(0.55e12, rel=0.01)
    assert f['window_scores'] == pytest.approx(0.96e12, rel=0.01)
    assert flops.admitted_pairs(8192) == 8192 * 8193 // 2
    assert flops.admitted_pairs(8192, 2048) == 2048 * 2049 // 2 \
        + 6144 * 2048
    assert flops.admitted_pairs(8192, 2048) == pytest.approx(14.68e6,
                                                             rel=1e-3)
    assert flops.admitted_pairs(100, 100) == flops.admitted_pairs(100)
    assert sum(f.values()) == pytest.approx(6.04e12, rel=0.005)
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(18.1e12, rel=0.005)
    mixers = f['projections'] + f['global_scores'] + f['window_scores']
    assert mixers / sum(f.values()) == pytest.approx(0.62, abs=0.01)
    costs = dict(flops.kernel_cost(config, traffic, 1),
                 experts=flops.expert_cost(config, traffic, 1),
                 swa=flops.window_attention_cost(config, traffic, 1))
    for name, (n_flops, nbytes) in costs.items():
        assert 0 < n_flops < step and nbytes > 0, name
    assert costs['flash_attention'][0] == pytest.approx(
        3 * (f['global_scores'] + f['window_scores']))
    assert costs['moe_mlp'][0] == pytest.approx(3 * f['experts'])
    # the gate's projection is among the windowed mixers' weights
    assert costs['swa'][0] == pytest.approx(
        3 * (0.8 * f['projections'] + f['window_scores']))
    assert flops.held_rows(config, 1, 8192) == 8192
    # the parameters of the deployment's table
    m = config['model']
    mixer = flops.mixer_weights(m)
    assert mixer == pytest.approx(27.26e6, rel=1e-3)
    norms = 4 * 2048 + 2 * 128
    dense_layer = mixer + 3 * 2048 * 6144 + norms
    expert_layer = mixer + 17 * 3 * 2048 * 1024 + 2048 * 128 + 128 + norms
    assert dense_layer == pytest.approx(65.0e6, rel=2e-3)
    assert expert_layer == pytest.approx(134.5e6, rel=2e-3)
    n = 2 * 25024 * 2048 + dense_layer + 4 * expert_layer + 2048
    assert n == pytest.approx(705.5e6, rel=1e-3)


def test_new_readers_read_their_scopes_or_nothing():
    """`sandwich_norm_ms` and `shared_expert_ms` on a hand-made reduction
    and a hand-made HLO, and `swa_roofline` with this configuration's
    cost; on a program that names no such scope (the parent's) nothing,
    and no error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(sandwich_norm)/jvp(rms_norm_3)/mul"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/transpose(jvp(sandwich_norm))/'
        'transpose(jvp(rms_norm_5))/mul"}',
        '  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/checkpoint/jvp(shared_expert)/jvp(mul_30)/dot_general"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(rms_norm_4)/mul"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(window_attention)/jvp(mul_4)/dot_general"}',
        '  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(shared_expert_x)/jvp(mul_31)/dot"}',
    ])
    red = {'steps': 5, 'fluid_scope_s': {
        'rms_norm_3': 0.10, 'rms_norm_5': 0.15, 'mul_30': 0.40,
        'rms_norm_4': 1.0, 'mul_4': 0.3, 'mul_31': 1.0}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('sandwich_norm_ms')(reading) \
        == pytest.approx(50.0)
    assert catalog.load_reader('shared_expert_ms')(reading) \
        == pytest.approx(80.0)
    share = catalog.load_reader('swa_roofline')(reading)
    least, bound = peaks.roofline(cell['flops'].window_attention_cost(
        cell['config'], cell['traffic'], 1), reading['peaks'])
    assert share == pytest.approx(100 * least / 0.06) and 0 < share < 100
    assert bound == 'flops'
    bare = hlo.replace('sandwich_norm', 'x').replace('shared_expert', 'y')
    for other in (dict(reading, hlo=bare), dict(reading, trace=None),
                  dict(reading, hlo=None)):
        for name in ('sandwich_norm_ms', 'shared_expert_ms'):
            assert catalog.load_reader(name)(other) is None
    entries = {m['name']: m for m in catalog.benchmark_json()['per_layer']}
    # the cell is IN an entry's list, whatever else is and in whatever
    # order: a later cell that names the scope edits no test here
    for name in ('sandwich_norm_ms', 'shared_expert_ms'):
        assert dict(entries[name], workloads=None) == {
            'name': name, 'unit': 'ms', 'better': 'lower',
            'source': 'device_trace', 'layer': 'Lowering rules',
            'moves': 'tokens_per_s', 'workloads': None}
    for name in ('sandwich_norm_ms', 'shared_expert_ms', 'swa_ms',
                 'swa_roofline', 'global_attn_ms', 'moe_ms',
                 'grouped_matmul_roofline', 'flash_roofline', 'mfu_pct',
                 'loss_head_ms'):
        assert CELL in entries[name]['workloads']
