"""SmallThinker on the normal path (ISSUE 37): `moe_mlp(router_input=)`
against the op without it, the toy model against the benchmark's plain
reference at a window of a few keys (which holds the window's edge, the
layer without positions and the router's tensor: moving any of them FAILS
the comparison), an expert layer's eight SHARES adding up to the uncut
reference's layer, the two name scopes, the regions, the counters, the
configuration's file, its FLOPs and its readers. Small sizes, on the
CPU."""
import functools
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name
from util import held_way

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'smallthinker_s16384'


reference_module = functools.partial(decoder_toy.reference_module,
                                     'smallthinker')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


# ------------------------------------------------------- the router's input

N, D, E, H, K, HELD = 96, 16, 64, 12, 6, 8


def build_layer(held, own, same=False):
    """One moe_mlp on the parameters `px` (the experts' tokens) and, with
    `own`, `pr` (the router's; `same`: the experts' tensor given twice):
    parameters, so that append_backward hands their gradients out."""
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.create_parameter([N, D], 'float32', name='px')
        r = layers.create_parameter([N, D], 'float32', name='pr') \
            if own and not same else None
        out, aux, count = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='relu', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_aux_loss=True, return_expert_count=True,
            experts_held=held, router_input=(x if same else r) if own
            else None)
        w = layers.data(name='w', shape=[D], dtype='float32')
        loss = layers.reduce_sum(layers.elementwise_mul(out, w)) + aux
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    return main, startup, [out, aux, count], grads


def run_layer(held, own, feeds, weights, same=False):
    """weights: router, gate stack, up stack, down stack. Returns (out,
    aux, count, {parameter name: gradient})."""
    main, startup, outs, grads = build_layer(held, own, same)
    first, n = held or (0, E)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        # every parameter is set below: no start-up program (a compile a
        # layer) is run
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        for i, w in enumerate(weights):
            scope.var('moe_mlp_0.w_%d' % i).get_tensor().set(
                w[first:first + n] if i else w, place)
        scope.var('px').get_tensor().set(feeds['x'], place)
        if 'pr' in grads:
            scope.var('pr').get_tensor().set(feeds['r'], place)
        names = sorted(grads)
        got = exe.run(main, feed={'w': feeds['w']},
                      fetch_list=outs + [grads[k] for k in names])
    return got[0], got[1], got[2], dict(zip(names, got[3:]))


def _layer_data(seed=0):
    rng = np.random.default_rng(seed)
    feeds = {k: rng.normal(size=(N, D)).astype('float32') for k in 'xrw'}
    weights = [rng.normal(size=(D, E)).astype('float32'),
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, H, D)).astype('float32') * 0.3]
    return feeds, weights


@pytest.mark.parametrize('held', [None, (8, 8)], ids=['all', 'share'])
def test_router_input_on_equal_tensors_is_the_op_without_it(held):
    """`router_input=x` and `router_input=None`: identical outputs,
    auxiliary loss, counts and gradients, bit for bit; given as ANOTHER
    tensor of equal values, the experts' gradient reaches one tensor and
    the router's the other, and together they are what the one got."""
    feeds, weights = _layer_data()
    plain = run_layer(held, False, feeds, weights)
    same = run_layer(held, True, feeds, weights, same=True)
    for a, b in zip(plain[:3], same[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sorted(plain[3]) == sorted(same[3])
    for k in plain[3]:
        np.testing.assert_array_equal(plain[3][k], same[3][k])
    split = run_layer(held, True, dict(feeds, r=feeds['x']), weights)
    for a, b in zip(plain[:3], split[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in plain[3]:
        if k != 'px':
            np.testing.assert_array_equal(plain[3][k], split[3][k])
    np.testing.assert_allclose(split[3]['px'] + split[3]['pr'],
                               plain[3]['px'], rtol=1e-5, atol=1e-6)
    assert np.abs(split[3]['px']).max() > 0
    assert np.abs(split[3]['pr']).max() > 0
    # the router's weight is moved by the tensor the router read alone
    other = run_layer(held, True, feeds, weights)
    assert np.abs(other[3]['moe_mlp_0.w_0']
                  - plain[3]['moe_mlp_0.w_0']).max() > 1e-3


def test_router_input_moves_the_choice_and_not_the_experts_rows():
    """Another tensor to the router: other experts are chosen (the
    counts move), and the counter says the router had its own input."""
    feeds, weights = _layer_data(1)
    before = obs.counter('moe.lowered', path='grouped', router='own').value
    plain = run_layer(None, False, feeds, weights)
    own = run_layer(None, True, feeds, weights)
    assert obs.counter('moe.lowered', path='grouped',
                       router='own').value > before
    assert plain[2].sum() == own[2].sum() == N * K
    assert not np.array_equal(plain[2], own[2])
    # the reference's block on the same two tensors
    reference = reference_module()
    w = {'router': weights[0], 'experts_in': weights[1:3],
         'experts_down': weights[3]}
    model = {'moe_num_active_primary_experts': K}
    with jax.default_matmul_precision('highest'):
        want, aux = reference.experts(w, jnp.asarray(feeds['r'])[None],
                                      jnp.asarray(feeds['x'])[None], model)
    np.testing.assert_allclose(own[0], np.asarray(want)[0], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(own[1], float(aux), rtol=1e-5)


def test_router_input_is_the_dropless_layers_and_of_the_inputs_shape():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[16], dtype='float32')
        other = layers.data(name='o', shape=[8], dtype='float32')
        with pytest.raises(ValueError, match='router_input.*dropless'):
            layers.moe_mlp(x, num_experts=8, hidden_size=8, router_input=x)
        with pytest.raises(ValueError, match='shape'):
            layers.moe_mlp(x, num_experts=8, hidden_size=8,
                           capacity_factor=None, router_input=other)


def test_the_eight_shares_are_the_uncut_references_layer():
    """THE SHARE TEST of the model-configs guide, section 4: the outputs
    of all 8 shares of one layer (first_expert_held 0, 8, .. 56) add up to
    what the UNCUT plain reference gives for the whole layer's experts,
    the router reading its own tensor; the counts are the whole layer's in
    every share."""
    reference = reference_module()
    feeds, weights = _layer_data(2)
    whole = run_layer(None, True, feeds, weights)
    assert whole[2].sum() == N * K
    parts = []
    for first in range(0, E, HELD):
        part = run_layer((first, HELD), True, feeds, weights)
        np.testing.assert_array_equal(part[2], whole[2])
        np.testing.assert_array_equal(part[1], whole[1])
        assert np.abs(part[0]).max() > 0
        parts.append(part[0])
    np.testing.assert_allclose(sum(parts), whole[0], rtol=2e-5, atol=2e-6)
    w = {'router': weights[0], 'experts_in': weights[1:3],
         'experts_down': weights[3]}
    model = {'moe_num_active_primary_experts': K}
    g, m = (jnp.asarray(feeds[k])[None] for k in 'rx')
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.experts(w, g, m, model)[0])[0]
        cut = dict(w, experts_in=[s[8:16] for s in weights[1:3]],
                   experts_down=weights[3][8:16])
        part1 = np.asarray(reference.experts(
            cut, g, m, dict(model, first_expert_held=8))[0])[0]
    np.testing.assert_allclose(sum(parts), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(parts[1], part1, rtol=2e-4, atol=2e-5)
    # the router on the experts' tensor is another layer
    assert np.abs(run_layer(None, False, feeds, weights)[0] - want).max() \
        > 0.05


@pytest.mark.parametrize('way', ['compact', 'blocks', 'overflow'])
def test_an_eighth_held_lays_out_half_its_rows_or_keeps_them_all(
        way, monkeypatch):
    """8 of 64 held, top 6 over 96 tokens: 576 rows, 72 expected, a layout
    of 256 (half the rows in whole tiles), chosen on the device. The
    router as drawn stays under it and takes the compact path (`compact`:
    the other path gives NaN); the same rows through `_held_blocks`
    (`blocks`: the compact path is made to call it) and a router forced
    onto the held experts, 576 rows, which overflows the layout
    (`overflow`: the compact path gives NaN) are each the cut plain
    reference's part in value and in every gradient: the experts' tensor,
    the router's own, the router's weight, the three stacks."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    assert moe_ops._held_layout(N * K, HELD, E) == 256
    feeds, weights = _layer_data(3)
    if way == 'overflow':
        feeds['r'] = np.abs(feeds['r']) + 0.1
        weights[0] = np.zeros((D, E), 'float32')
        weights[0][:, 8:8 + K] = 1.0 + np.arange(K)[::-1] / K
    held_way(monkeypatch, way)
    out, aux, count, grads = run_layer((8, HELD), True, feeds, weights)
    live = count[8:8 + HELD].sum()
    assert live == N * K if way == 'overflow' else 0 < live <= 256

    reference = reference_module()
    model = {'moe_num_active_primary_experts': K, 'first_expert_held': 8}

    def loss(g, m, router, w_gate, w_up, w_down):
        y, aux = reference.experts(
            {'router': router, 'experts_in': [w_gate, w_up],
             'experts_down': w_down}, g[None], m[None], model)
        return jnp.sum(y[0] * feeds['w']) + aux, y[0]

    cut = [weights[0]] + [w[8:8 + HELD] for w in weights[1:]]
    with jax.default_matmul_precision('highest'):
        want, y = jax.grad(loss, argnums=range(6), has_aux=True)(
            jnp.asarray(feeds['r']), jnp.asarray(feeds['x']), *cut)
    np.testing.assert_allclose(out, y, rtol=2e-4, atol=2e-5)
    assert np.abs(out).max() > 0.1
    names = ['pr', 'px'] + ['moe_mlp_0.w_%d' % i for i in range(4)]
    for name, b in zip(names, want):
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(grads[name], b, rtol=1e-3,
                                   atol=1e-4 * np.abs(b).max(),
                                   err_msg=name)


def test_equal_expert_layers_trace_one_body_a_program(monkeypatch):
    """Three held expert layers of equal shapes, one wider and one of the
    first shape under a `fluid.name_scope`: a training Program runs the
    layer's Python body (`_held_paths`: the conditional and both paths)
    once a shape and name scope, not once a layer, the lowered module
    holds one function for each and a pass (forward, backward) and calls
    it from each layer; the next Program traces its own (a path patched
    between two builds is never answered from the build before). The
    name scope keeps its own: XLA gives the shared branches of a
    conditional ONE caller's op_name, and a reader by name scope
    (`mtp_ms`) must not be handed the other layers' time."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    traced = []
    paths = moe_ops._held_paths

    def counting(ctx, params, *a, **static):
        traced.append(params['w1'].shape[-1])
        return paths(ctx, params, *a, **static)

    counting.__name__ = '_held_paths'
    monkeypatch.setattr(moe_ops, '_held_paths', counting)
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.create_parameter([N, D], 'float32', name='px')
        def block(x, hidden):
            return x + layers.moe_mlp(
                x, num_experts=E, hidden_size=hidden, act='relu', gated=True,
                top_k=K, norm_topk_prob=True, capacity_factor=None,
                bias_attr=False, experts_held=(8, HELD))

        for hidden in (H, H, 2 * H, H):
            x = block(x, hidden)
        with fluid.name_scope('mtp'):
            x = block(x, H)
        loss = layers.reduce_sum(x)
        grad = dict((p.name, g) for p, g in
                    fluid.backward.append_backward(loss))['px']
    assert sorted(traced) == [H] * 4 + [2 * H]   # shape inference, a layer
    for again in (1, 2):
        del traced[:]
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            got = exe.run(main, fetch_list=[loss, grad])
            assert all(np.isfinite(g).all() for g in got)
            assert sorted(traced) == [H, H, 2 * H]
            text = exe.lowered_hlo(main, {}, [loss, grad])
        assert sorted(traced) == [H, H, 2 * H]
        bodies = re.findall(r'func\.func private @(_held_paths\w*)', text)
        calls = re.findall(r'call @(_held_paths\w*)', text)
        # a body's forward and its backward; each called once a layer
        assert len(bodies) == len(set(bodies)) == 6
        assert sorted(calls.count(b) for b in bodies) == [1, 1, 1, 1, 3, 3]


def test_the_toy_cells_expert_layers_share_a_body_across_their_regions():
    """The cell's training step at the toy widths: four expert layers,
    each in a recompute region of its own, call the same two or three
    functions (the body's forward, its backward, and the rows again where
    they are not part of it), four times each. The
    regions hand jax one policy object (`step_artifact._REGION_KEEPS`): a
    policy made anew a region splits the body anew a region, and every
    layer gets functions of its own."""
    cell = _toy_cell()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        built = cell['builder'].build(cell['config'], cell['traffic'])
        exe.run(built['startup'])
        pool, _ = cell['generator'].make_pool(cell['traffic'],
                                              cell['config'], 3)
        assert len(exe.step_artifact(built['main'], pool[0],
                                     [built['loss']]).regions) == 4
        text = exe.lowered_hlo(built['main'], pool[0], [built['loss']])
    bodies = re.findall(r'func\.func private @(_held_paths\w*)', text)
    calls = re.findall(r'call @(_held_paths\w*)', text)
    assert 2 <= len(bodies) == len(set(bodies)) <= 3
    assert [calls.count(b) for b in bodies] == [4] * len(bodies)


# ------------------------------------------------------------------ the model

# a layer's parameters in creation order (models/smallthinker.py)
_PER_LAYER = 10


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/smallthinker.py through the Executor against
    chipbench/references/smallthinker.py in float32 to 1e-5: the loss and
    the gradient of EVERY parameter, at a window of 5 keys over rows of 80
    (a global layer without positions, three windowed rotary layers, 6
    query heads over 2, experts 4..7 of 16 held, the router on the
    pre-attention tensor); and under bf16 AMP within a stated
    tolerance."""
    cell = _toy_cell(sliding_window_size=5)
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert len(names) == 1 + 4 * _PER_LAYER + 2
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _router_after_attention(ref):
    def layer(w, x, model, index):
        eps = model['rms_norm_eps']
        g = ref.rms(x, w['norm_in'], eps)
        h = x + ref.attention(w, g, model, index)
        m = ref.rms(h, w['norm_post'], eps)
        y, aux = ref.experts(w, m, m, model)
        return h + y, aux
    ref.layer = layer


def _window_one_key(by):
    def move(ref):
        seen = ref.seen
        ref.seen = lambda rows, keys, window: seen(
            rows, keys, None if window is None else window + by)
    return move


def _rotary_everywhere(ref):
    attention = ref.attention
    ref.attention = lambda w, g, model, index: attention(
        w, g, dict(model, rope_layout=[1] * 64), index)


_MOVED = {'router_after_attention': _router_after_attention,
          'window_one_key_wider': _window_one_key(1),
          'window_one_key_narrower': _window_one_key(-1),
          'rotary_on_the_global_layer': _rotary_everywhere}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference whose router reads the post-attention tensor, whose
    window is one key off, or whose global layer has rotary positions,
    the same Program FAILS at the same tolerance. The reference is a
    fresh copy of the module with ONE function moved (its callers look it
    up in the module)."""
    reference = reference_module()
    _MOVED[rule](reference)
    cell = dict(_toy_cell(sliding_window_size=5), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert not got['passed']
    worst = max(got['grad_rel'].values())
    assert worst > 1e-3, worst
    if rule == 'router_after_attention':
        # the last layer's router weight is the entry that says it (the
        # two tensors differ by the mixer's output beside an embedding of
        # unit variance: 0.09 here, 0.6 with the embedding at 0.02)
        assert got['grad_rel']['moe_mlp_3.w_0'] > 0.05


def test_layers_differ_by_kind_scopes_regions_and_counters():
    """Layer 0 is built under `global_attention` with no window and no
    rotary op, layers 1 to 3 under `window_attention` with both; every
    layer is one recompute region; the scopes reach the optimized HLO's
    op_name; the router's own input is counted."""
    from chipbench.harness import catalog, scopes
    cell = _toy_cell()
    before = obs.counter('moe.lowered', path='grouped', held='4of16',
                         dispatch='index', router='own').value
    config, built = build_toy(cell, train=True)
    assert obs.counter('moe.lowered', path='grouped', held='4of16',
                       dispatch='index', router='own').value - before == 4
    ops = built['main'].global_block().ops
    flash = [op for op in ops if op.type == 'flash_attention']
    assert [op.attrs.get('name_scope') for op in flash] == [
        'global_attention'] + ['window_attention'] * 3
    assert [op.attrs.get('window') for op in flash] == [None, 24, 24, 24]
    assert all(op.attrs['causal'] for op in flash)
    rotary = [op for op in ops if op.type == 'rotary_embedding']
    assert len(rotary) == 6 and all(
        op.attrs['name_scope'] == 'window_attention' for op in rotary)
    moe = [op for op in ops if op.type == 'moe_mlp']
    assert len(moe) == 4 and all(op.input('RouterX') for op in moe)
    # the router reads the norm the projections read, the experts another
    for op in moe:
        assert op.input('RouterX') != op.input('X')
        assert op.attrs.get('name_scope') is None
        assert op.attrs['act'] == 'relu' and op.input('W3')
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 4
    text = decoder_toy.one_step_hlo(cell, config, built)
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    under_w = window.op_scopes_under(text, 'window_attention')
    under_g = window.op_scopes_under(text, 'global_attention')
    assert under_w and under_g and not under_w & under_g
    assert {s.rsplit('_', 1)[0] for s in under_g} >= {'mul',
                                                      'flash_attention'}
    assert 'rotary_embedding' in {s.rsplit('_', 1)[0] for s in under_w}
    assert 'rotary_embedding' not in {s.rsplit('_', 1)[0] for s in under_g}
    assert not any(s.startswith(('moe_mlp', 'rms_norm'))
                   for s in under_w | under_g)
    assert scopes.instruction_scopes(text)


def test_small_preset_trains():
    from paddle_tpu.models import smallthinker as S
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = S.get_model(experts_held=(4, 4))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        batch = next(iter(train()))
        feed = {feeds[0]: np.stack([b[0] for b in batch]),
                feeds[1]: np.stack([b[1] for b in batch])}
        losses = []
        for _ in range(12):
            out = exe.run(main, feed=feed, fetch_list=[loss, counts[0]])
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        # dropless: every assignment is counted, over all 16 experts
        assert np.asarray(out[1]).sum() == 2 * 32 * 2
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_a_layout_of_another_length_is_refused():
    from paddle_tpu.models import smallthinker as S
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match='rope_layout'):
            S.smallthinker(64, 16, n_layer=4, hidden=16, n_head=2,
                           n_kv_head=1, d_head=8, rope_layout=[0, 1],
                           n_expert=4, top_k=2, expert_width=8)


# ------------------------------------------------------------- the benchmark

def test_the_builders_rate_climbs_linearly_to_the_configurations_peak():
    """chipbench/builders/smallthinker.py: the train Program's Adam
    reads peak x step / warmup_steps (the window is the warm-up's first
    steps); the token embedding starts at `embedding_initializer_range`
    and every other matrix at `initializer_range`."""
    cell = _toy_cell()
    opt = cell['config']['optimizer']
    assert (opt['learning_rate'], opt['warmup_steps']) == (4e-4, 2000)
    np.testing.assert_allclose(decoder_toy.rates_of_training(cell, 3),
                               [4e-4 * n / 2000 for n in (1, 2, 3)],
                               rtol=1e-5)
    scope, _ = decoder_toy.started(cell)
    std = {n: float(np.std(np.asarray(scope.find_var(n).get_tensor())))
           for n in ('embedding_0.w_0', 'fc_0.w_0')}
    assert std['embedding_0.w_0'] == pytest.approx(1.0, rel=0.05)
    assert std['fc_0.w_0'] == pytest.approx(0.02, rel=0.05)


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth, the experts held and the vocabulary are
    cut, and the two layouts stand whole."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'smallthinker_21b_a3b.json')) as f:
        held = json.load(f)
    period = [0, 1, 1, 1]
    source = {"head_dim": 128, "hidden_size": 2560,
              "max_position_embeddings": 16384,
              "model_name": "smallthinker_21b_instruct",
              "moe_ffn_hidden_size": 768,
              "moe_num_active_primary_experts": 6,
              "moe_num_primary_experts": 64,
              "moe_primary_router_apply_softmax": True,
              "norm_topk_prob": True, "num_attention_heads": 28,
              "num_hidden_layers": 52, "num_key_value_heads": 4,
              "rms_norm_eps": 1e-06, "rope_layout": period * 13,
              "rope_scaling": None, "rope_theta": 1500000,
              "sliding_window_layout": period * 13,
              "sliding_window_size": 4096, "tie_word_embeddings": False,
              "vocab_size": 151936}
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'SmallThinker-21BA3B-Instruct':
                    assert row['config'] == source
                    assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 4, 'moe_num_primary_experts': 8,
           'vocab_size': 18992}
    for key, value in source.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: source[k] for k in cut}
    assert set(held['model']) - set(source) == {
        'router_aux_loss_coef', 'initializer_range',
        'embedding_initializer_range', 'first_expert_held'}
    # the floors of the guide: a whole period and four layers, 8 experts,
    # an eighth of the vocabulary
    assert held['num_hidden_layers'] % 4 == 0
    assert held['sliding_window_layout'][:held['num_hidden_layers']] \
        == period
    assert held['moe_num_primary_experts'] >= 8
    assert held['vocab_size'] * 8 >= source['vocab_size']
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('top_level_keys', 'router_input', 'router_softmax',
                'experts', 'attention', 'window_convention',
                'rotary_pairing', 'router_aux_loss_coef', 'initializers',
                'optimizer', 'document_mask', 'recomputation'):
        assert held['assumed'][key], key
    assert '8 chips' in held['deployment']
    # the global layer's Wq, a windowed layer's Wq and Wk, the last
    # layer's router and held down stack, the embedding
    assert set(held['checks']['float32']['grads']) == {
        'fc_0.w_0', 'fc_4.w_0', 'fc_5.w_0', 'moe_mlp_3.w_0',
        'moe_mlp_3.w_3', 'embedding_0.w_0'}
    for entry in held['checks'].values():
        assert len(entry['why']) > 400


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 16384 (ISSUE 37): 573 M, of which the
    scores 271 M (the global layer 117 M, the windowed three 154 M: 468 M
    without the band), the projections 168 M, the head 97 M, the held
    experts 35 M; 28.2 TFLOP a step; 370.5 M parameters."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 16384
    f = {k: v / tokens / 1e6 for k, v in flops.forward_flops(
        config, traffic['batch'], traffic['seq']).items()}
    total = sum(f.values())
    assert total == pytest.approx(573, rel=0.005)
    assert f['projections'] == pytest.approx(4 * 41.9, rel=0.005)
    assert f['global_scores'] == pytest.approx(117.4, rel=0.005)
    assert f['window_scores'] == pytest.approx(3 * 51.4, rel=0.005)
    assert f['experts'] == pytest.approx(35.4, rel=0.005)
    assert f['router'] == pytest.approx(1.31, rel=0.005)
    assert f['head'] == pytest.approx(97.2, rel=0.005)
    assert flops.admitted_pairs(16384) == 16384 * 16385 // 2
    assert flops.admitted_pairs(16384, 4096) \
        == 4096 * 4097 // 2 + 12288 * 4096
    assert flops.admitted_pairs(100, 100) == flops.admitted_pairs(100)
    # a windowed layer never costs the triangle
    assert flops.admitted_pairs(16384, 4096) < 0.45 * flops.admitted_pairs(
        16384)
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(28.2e12, rel=0.005)
    scores = f['global_scores'] + f['window_scores']
    assert scores / total == pytest.approx(0.47, abs=0.01)
    costs = dict(flops.kernel_cost(config, traffic, 1),
                 experts=flops.expert_cost(config, traffic, 1),
                 swa=flops.window_attention_cost(config, traffic, 1))
    for name, (n_flops, nbytes) in costs.items():
        assert 0 < n_flops < step and nbytes > 0, name
    assert costs['flash_attention'][0] == pytest.approx(
        3 * scores * tokens * 1e6)
    assert flops.held_rows(config, 1, 16384) == 16384 * 6 / 8
    # the parameters of the deployment's table
    m = config['model']
    mixer = flops.mixer_weights(m)
    assert mixer == pytest.approx(20.97e6, rel=1e-3)
    layer = mixer + 2560 * 64 + 2 * 2560 + 8 * 3 * 2560 * 768
    n = 2 * 18992 * 2560 + 4 * layer + 2560
    assert n == pytest.approx(370.5e6, rel=2e-3)


def test_new_readers_read_their_scopes_or_nothing():
    """`swa_ms`, `swa_roofline` and `global_attn_ms` on a hand-made
    reduction and a hand-made HLO; on a program that names no such scope
    (the parent's) nothing, and no error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(window_attention)/jvp(mul_4)/dot_general"}',
        '  %custom-call.2 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/transpose(jvp(window_attention))/'
        'transpose(jvp(flash_attention_1))/pallas_call"}',
        '  %custom-call.3 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/checkpoint/jvp(global_attention)/'
        'jvp(flash_attention_0))/pallas_call"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mul_20)/dot_general"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(window_attention_like)/jvp(mul_21)/dot"}',
    ])
    red = {'steps': 5, 'fluid_scope_s': {
        'mul_4': 0.10, 'flash_attention_1': 0.40, 'flash_attention_0': 0.25,
        'mul_20': 1.0, 'mul_21': 1.0}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('swa_ms')(reading) == pytest.approx(100.0)
    assert catalog.load_reader('global_attn_ms')(reading) \
        == pytest.approx(50.0)
    share = catalog.load_reader('swa_roofline')(reading)
    least, bound = peaks.roofline(cell['flops'].window_attention_cost(
        cell['config'], cell['traffic'], 1), reading['peaks'])
    assert share == pytest.approx(100 * least / 0.1) and 0 < share < 100
    assert bound == 'flops'
    bare = hlo.replace('window_attention', 'x').replace('global_attention',
                                                        'y')
    for other in (dict(reading, hlo=bare), dict(reading, trace=None),
                  dict(reading, hlo=None)):
        for name in ('swa_ms', 'swa_roofline', 'global_attn_ms'):
            assert catalog.load_reader(name)(other) is None
    glm = catalog.load_cell('glm47flash_s8192')
    assert catalog.load_reader('swa_roofline')(
        dict(reading, cell=glm)) is None
