"""Ling-3.0-flash on the normal path (ISSUE 55): the toy model (layers
KDA-dense, then KDA, KDA, KDA, MLA, KDA, KDA with experts; 4 heads of 16,
keys of 16 + 8 rotary beside values of 16, 16 experts in 4 groups of which
2 stay, top 2, 4 held) against the plain reference on the loss and every
gradient; each rule the configuration forced FAILING the comparison when
moved in the reference; the four held shares adding up to the uncut layer;
the router's groups against a sort; the interleaved rotary pairs; the
reference's walk with its weights on the host against `jax.grad` of the
same function in one piece; name scopes, regions, counters, the
configuration's file against the catalog's row, its FLOPs and its reader.
Small sizes, on the CPU."""
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
from paddle_tpu.parallel.moe import router_topk

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'ling3flash_s8192'
KINDS = ['kda_dense', 'kda_experts', 'kda_experts', 'kda_experts',
         'mla_experts', 'kda_experts', 'kda_experts']


reference_module = functools.partial(decoder_toy.reference_module,
                                     'bailing_hybrid')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


# trainable parameters a layer: the mixer's norm and parameters, the post
# norm, the feed-forward's (the selection bias is no trainable parameter)
_PER_KIND = {'kda': 14, 'mla': 7, 'dense': 1 + 3, 'experts': 1 + 1 + 3 + 3}


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/bailing_hybrid.py through the Executor against
    chipbench/references/bailing_hybrid.py in float32: the loss and the
    gradient of EVERY trainable parameter over rows of 80 to 1e-5 (six KDA
    mixers in chunks of 16 with their three convolutions, Wf, Wb, Wg and
    the sigmoid-gated norm; one MLA mixer at keys of 24 and values of 16
    with its head-wise gate; the grouped router), but the decay's own
    parameters to 1e-4: A_log, dt_bias and Wf reach the loss through
    exp(G_i - G_j) of float32 running sums of up to 80 at this init (most
    gates at their floor of -5), whose rounding (4e-6 of a factor) is all
    of what is left of them; and under bf16 AMP within a stated
    tolerance."""
    cell = _toy_cell()
    reference = reference_module()
    assert reference.kinds_of(cell['config']['model']) == KINDS
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-4})
    assert len(names) == 2 + 1 + sum(
        _PER_KIND[part] for kind in KINDS for part in kind.split('_'))
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    decays = {n for n in names if n.startswith('create_parameter_')} | {
        'fc_%d.w_0' % i for i in (3, 13, 23, 33, 51, 61)}
    loose = {n: r for n, r in got['grad_rel'].items()
             if r > 1e-5 and n not in decays}
    assert not loose, loose
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _decay_a_head(ref):
    plain = ref.delta_scan
    ref.delta_scan = lambda q, k, v, g, beta: plain(
        q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
        beta)


def _no_groups(ref):
    plain = ref.route
    ref.route = lambda m, w, bias, model: plain(
        m, w, bias, dict(model, n_group=1, topk_group=1))


def _scale_of_the_values_width(ref):
    plain = ref._head
    ref._head = lambda q, k, v: plain(
        q * np.sqrt(q.shape[-1] / v.shape[-1]), k, v)


def _rotate_half(ref):
    def rotary(x, theta):
        t, r = x.shape[-2], x.shape[-1]
        inv = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
        angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
        cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1),
                          jnp.float32)
        sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1),
                          jnp.float32)
        turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
        return x * cos + turned * sin
    ref.rotary = rotary


def _no_head_gate(ref):
    plain = ref.mla
    ref.mla = lambda w, u, model: plain(
        dict(w, gate=jnp.zeros_like(w['gate'])), u, model) * 2.0


_MOVED = {
    'the_decay_averaged_over_a_heads_channels': _decay_a_head,
    'the_groups_left_out_of_the_router': _no_groups,
    'the_key_scale_of_the_values_width': _scale_of_the_values_width,
    'rotate_half_pairs': _rotate_half,
    'no_head_wise_gate': _no_head_gate,
}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """Each rule the configuration forced, moved in the REFERENCE: the toy
    comparison that passes at 1e-4 then fails by orders of magnitude on
    some gradient (the first three are the controls the chip's float32
    check is shown failing on)."""
    reference = reference_module()       # a fresh copy, ONE function moved
    _MOVED[rule](reference)
    cell = dict(_toy_cell(), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-4})
    assert not got['passed']
    assert max(got['grad_rel'].values()) > 1e-2, rule


# ------------------------------------------------------------------ the share

N, D, E, H, K, HELD, GROUPS, STAY = 96, 16, 16, 12, 2, 4, 4, 2


def _run_share(held, xs, weights):
    """weights: router, gate stack, up stack, down stack, bias."""
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, count, _ = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held,
            scoring='sigmoid', selection_bias=True, gate_scale=2.5,
            n_group=GROUPS, topk_group=STAY)
    first, n = held or (0, E)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        # every parameter is set below: no start-up program (a compile a
        # share) is run
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        for i, w in enumerate(weights):
            scope.var('moe_mlp_0.w_%d' % i).get_tensor().set(
                w[first:first + n] if i in (1, 2, 3) else w, place)
        return exe.run(main, feed={'x': xs}, fetch_list=[out, count])


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST of the model-configs guide, section 4, under the
    grouped router: the routed parts of all 4 shares of one layer
    (first_expert_held 0, 4, 8, 12: a share is one GROUP of the router),
    with the shared expert counted once, add up to what the UNCUT plain
    reference gives for the whole expert block; the counts are the whole
    layer's in every share and no token chose outside two groups."""
    reference = reference_module()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N, D)).astype('float32')
    weights = [rng.normal(size=(D, E)).astype('float32'),
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, H, D)).astype('float32') * 0.3,
               rng.normal(size=E).astype('float32') * 0.2]
    whole, count = _run_share(None, xs, weights)
    assert count.sum() == N * K
    parts = []
    for first in range(0, E, HELD):
        part, count_s = _run_share((first, HELD), xs, weights)
        np.testing.assert_array_equal(count_s, count)
        parts.append(part)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    shared = [rng.normal(size=s).astype('float32') * 0.3
              for s in ((D, H), (D, H), (H, D))]
    model = {'num_experts_per_tok': K, 'norm_topk_prob': True,
             'routed_scaling_factor': 2.5, 'n_group': GROUPS,
             'topk_group': STAY}
    w = {'router': weights[0], 'experts_in': weights[1:3],
         'experts_down': weights[3], 'bias': weights[4], 'shared': shared}
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.experts(w, jnp.asarray(xs)[None],
                                            model))[0]
        once = np.asarray((jax.nn.silu(xs @ shared[0]) * (xs @ shared[1]))
                          @ shared[2])
        cut = dict(w, experts_in=[s[4:8] for s in weights[1:3]],
                   experts_down=weights[3][4:8])
        part1 = np.asarray(reference.experts(
            cut, jnp.asarray(xs)[None], dict(model, first_expert_held=4)))[0]
        free = np.asarray(reference.experts(
            w, jnp.asarray(xs)[None], dict(model, n_group=1,
                                           topk_group=1)))[0]
    np.testing.assert_allclose(sum(parts) + once, want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(parts[1] + once, part1, rtol=2e-4, atol=2e-5)
    # the groups bind here: the free top 2 is another layer
    assert np.abs(free - want).max() > 0.05


def _sorted_router(c, scores, top_k, groups, stay, scale):
    """The grouped choice by sorting, in numpy; ties to the lower index."""
    n, e = c.shape
    size = e // groups
    experts, gates = [], []
    for row, s in zip(c, scores):
        rank = [np.sort(row[g * size:(g + 1) * size])[-2:].sum()
                for g in range(groups)]
        kept = sorted(range(groups), key=lambda g: (-rank[g], g))[:stay]
        allowed = [i for i in range(e) if i // size in kept]
        chosen = sorted(allowed, key=lambda i: (-row[i], i))[:top_k]
        experts.append(chosen)
        gates.append(scale * s[chosen] / (s[chosen].sum() + 1e-20))
    return np.asarray(experts).T, np.asarray(gates).T


@pytest.mark.parametrize('groups,stay,top_k', [(8, 4, 8), (4, 2, 2),
                                               (4, 1, 3)])
def test_the_grouped_router_is_the_sorted_choice(groups, stay, top_k):
    """`router_topk(n_group=, topk_group=)` against a choice by sorting:
    64 experts, random scores and a bias; `n_group=1` is the path without
    groups; a TIE between two groups' ranks goes to the lower group."""
    rng = np.random.default_rng(groups + stay)
    logits = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=64) * 0.1, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(logits))
    got_e, got_g = router_topk(logits, top_k, True, 'sigmoid', bias, 2.5,
                               None, groups, stay)
    want_e, want_g = _sorted_router(scores + np.asarray(bias), scores, top_k,
                                    groups, stay, 2.5)
    np.testing.assert_array_equal(got_e, want_e)
    np.testing.assert_allclose(got_g, want_g, rtol=1e-6)
    plain = router_topk(logits, top_k, True, 'sigmoid', bias, 2.5)
    for a, b in zip(plain, router_topk(logits, top_k, True, 'sigmoid', bias,
                                       2.5, None, 1, 1)):
        np.testing.assert_array_equal(a, b)
    # two groups of equal rank: logits equal in groups 1 and 2, lower in
    # the others; one group stays, and it is group 1
    size = 64 // groups
    tied = np.full((3, 64), -3.0, 'float32')
    tied[:, size:3 * size] = 1.0
    e, _ = router_topk(jnp.asarray(tied), min(top_k, size), True, 'sigmoid',
                       None, 1.0, None, groups, 1)
    assert np.all(np.asarray(e) // size == 1)
    with pytest.raises(NotImplementedError, match='softmax'):
        router_topk(logits, 2, n_group=4, topk_group=2)
    with pytest.raises(ValueError, match='groups'):
        router_topk(logits, 40, True, 'sigmoid', None, 1.0, None, 8, 4)
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[8], dtype='float32')
        with pytest.raises(ValueError, match='sigmoid'):
            layers.moe_mlp(x, num_experts=8, hidden_size=8,
                           capacity_factor=None, n_group=4, topk_group=2)
        with pytest.raises(ValueError, match='groups'):
            layers.moe_mlp(x, num_experts=8, hidden_size=8, top_k=3,
                           capacity_factor=None, scoring='sigmoid',
                           n_group=4, topk_group=1)


def test_interleaved_rotary_turns_neighbours_in_place():
    """`rotary_embedding(interleave=True)`: elements 2j and 2j + 1 turn by
    t * base^(-2j/R), each staying where it is; a partial rotary_dim
    passes the rest through; the default is the rotate-half it was."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 2, 9, 12)).astype('float32')

    def run(**kw):
        main, startup = framework.Program(), framework.Program()
        with unique_name.guard(), framework.program_guard(main, startup):
            out = layers.rotary_embedding(
                layers.data(name='x', shape=list(x.shape), dtype='float32',
                            append_batch_size=False), base=100.0, **kw)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            return exe.run(main, feed={'x': x}, fetch_list=[out])[0]

    def pairs(x, r):
        angle = np.arange(9)[:, None] * 100.0 ** (-np.arange(0, r, 2) / r)
        y = x.copy()
        y[..., 0:r:2] = x[..., 0:r:2] * np.cos(angle) \
            - x[..., 1:r:2] * np.sin(angle)
        y[..., 1:r:2] = x[..., 1:r:2] * np.cos(angle) \
            + x[..., 0:r:2] * np.sin(angle)
        return y

    np.testing.assert_allclose(run(interleave=True), pairs(x, 12),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(run(interleave=True, rotary_dim=8),
                               pairs(x, 8), rtol=1e-5, atol=1e-6)
    assert np.abs(run() - pairs(x, 12)).max() > 0.1
    np.testing.assert_allclose(
        run(interleave=True), reference_module().rotary(jnp.asarray(x),
                                                        100.0),
        rtol=1e-5, atol=1e-6)


def test_the_walk_with_host_weights_is_the_gradient_of_the_whole():
    """`walk` (the weights on the host, one layer at a time on the device,
    the layers pulled back one by one, a KDA mixer's heads in groups)
    gives the loss and every gradient that
    `jax.value_and_grad(forward_loss)` gives in one piece."""
    reference = reference_module()
    cell = _toy_cell(num_hidden_layers=3, kept_layers=[0, 4, 5])
    model = cell['config']['model']
    assert reference.kinds_of(model) == ['kda_dense', 'kda_experts',
                                         'mla_experts']
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        config = dict(cell['config'], check={'grads': []}, amp='none')
        built = cell['builder'].build(config, cell['traffic'], train=False)
        exe.run(built['startup'])
        scope = fluid.global_scope()
        params, _ = cell['builder'].reference_params(
            config, built['main'],
            lambda n: np.asarray(scope.find_var(n).get_tensor()))
    rng = np.random.default_rng(1)
    ids, labels = (jnp.asarray(rng.integers(0, model['vocab_size'],
                                            size=(2, 40)), jnp.int32)
                   for _ in range(2))
    with jax.default_matmul_precision('highest'):
        # the walk's KDA mixers two heads at a time, the whole's all four
        reference.HEAD_GROUP = 2
        loss, grads = reference.walk(params, model, ids, labels)
        reference.HEAD_GROUP = 8
        want, want_grads = jax.value_and_grad(
            lambda p: reference.forward_loss(p, model, ids, labels))(
                jax.tree_util.tree_map(jnp.asarray, params))
    assert abs(loss - float(want)) < 1e-6 * abs(float(want))
    assert set(grads) == set(want_grads)
    for k in grads:
        for a, b in zip(*(v if isinstance(v, list) else [v]
                          for v in (grads[k], want_grads[k]))):
            if k.endswith('.bias'):
                continue            # no gradient reaches the choice
            assert np.linalg.norm(np.asarray(a) - np.asarray(b)) \
                <= 1e-5 * np.linalg.norm(np.asarray(b)) + 1e-9, k


def test_layers_are_mixer_and_feed_forward_scopes_regions_and_counters():
    """Seven recompute regions, one a layer; the mixers' ops under
    `kda_mixer` or `latent_attention`, the shared experts' under
    `shared_expert`, the bias updates under `router_bias`; the builder's
    `bailing.layers` counts and the lowering's counters of one trace."""
    cell = _toy_cell()
    before = {k: obs.counter('bailing.layers', **k_).value for k, k_ in (
        ('kda_dense', dict(mixer='kda', ffn='dense')),
        ('kda_experts', dict(mixer='kda', ffn='experts')),
        ('mla_experts', dict(mixer='mla', ffn='experts')))}
    _, built = build_toy(cell, train=True)
    after = {k: obs.counter('bailing.layers', mixer=k.split('_')[0],
                            ffn=k.split('_')[1]).value for k in before}
    assert {k: after[k] - before[k] for k in before} == {
        'kda_dense': 1, 'kda_experts': 5, 'mla_experts': 1}
    ops = built['main'].global_block().ops
    marks = [op.attrs.get('recompute') for op in ops]
    runs = [m for i, m in enumerate(marks)
            if m is not None and (i == 0 or marks[i - 1] != m)]
    assert len(runs) == len(set(runs)) == 7
    assert {op.attrs.get('name_scope') for op in ops} == {
        None, 'kda_mixer', 'latent_attention', 'shared_expert',
        'router_bias'}
    by_scope = {}
    for op in ops:
        by_scope.setdefault(op.attrs.get('name_scope'), []).append(op.type)
    assert by_scope['kda_mixer'].count('gated_delta_rule') == 6
    assert by_scope['kda_mixer'].count('causal_conv1d') == 18
    assert by_scope['kda_mixer'].count('gated_rms_norm') == 6
    assert by_scope['latent_attention'].count('flash_attention') == 1
    assert by_scope[None].count('moe_mlp') == 6
    assert by_scope['router_bias'].count('sign') == 6
    norm = [op for op in ops if op.type == 'gated_rms_norm'][0]
    assert norm.attrs['gate_act'] == 'sigmoid'
    rule = [op for op in ops if op.type == 'gated_delta_rule'][0]
    assert rule.attrs['gate_floor'] == -5.0
    assert len(built['main'].global_block().var(
        rule.input('G')[0]).shape) == 4
    moe = [op for op in ops if op.type == 'moe_mlp'][0]
    assert (moe.attrs['n_group'], moe.attrs['topk_group']) == (4, 2)
    # the head is the last `mul` built, outside every region
    muls = [op for op in ops if op.type == 'mul']
    assert muls[-1].attrs.get('recompute') is None

    def counts():
        return {
            'channel': obs.counter('gdn.lowered', chunk=16,
                                   gate='channel').value,
            'intra': obs.counter('gdn.intra', way='composed').value,
            'scan': obs.counter('gdn.scan', way='composed').value,
            'conv': obs.counter('conv1d.way', way='composed').value,
            'norm': obs.counter('gated_rms_norm.way', way='composed').value,
            'router': obs.counter('moe.router', groups=4, kept=2).value}

    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          cell['config'], 5)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        was = counts()
        loss, = exe.run(built['main'], feed=pool[0],
                        fetch_list=[built['loss']])
        rose = {k: v - was[k] for k, v in counts().items()}
    assert np.isfinite(loss).all()
    # a trace counts each op at least once (custom_vjp traces its rule)
    assert rose['channel'] >= 6 and rose['intra'] == rose['scan'] \
        == rose['channel']
    assert rose['conv'] >= 18 and rose['norm'] >= 6 and rose['router'] >= 6
    assert rose['conv'] == 3 * rose['channel']


def test_a_clamp_on_an_experts_swiglu_is_refused_not_guessed():
    """The source clamps an expert's SwiGLU from layer 35 on; a stage that
    kept such a layer would need the clamp, which is not built: the model
    function raises."""
    cell = _toy_cell()
    assert cell['builder'].swiglu_limits(cell['config']) == [0] * 14
    deep = dict(cell['config'], model=dict(
        cell['config']['model'], kept_layers=[0, 2, 3, 4, 5, 6, 35]))
    assert cell['builder'].swiglu_limits(deep)[-2:] == [4, 5]
    with pytest.raises(NotImplementedError, match='clamp'):
        cell['builder'].build(dict(deep, check={'grads': []}),
                              cell['traffic'], train=False)
    from paddle_tpu.models import bailing_hybrid as B
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match='layer_ids'):
            B.bailing_hybrid(64, 16, n_layer=3, layer_ids=[0, 1])


def test_small_preset_trains():
    """models/bailing_hybrid.get_model(): dense-KDA, KDA, MLA; the loss
    falls on a batch it sees again, the counts are over all 16 experts,
    the biases move by the rate."""
    from paddle_tpu.models import bailing_hybrid as B
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = B.get_model(
            experts_held=(4, 4), learning_rate=3e-3)
    assert feeds == ['input_ids', 'labels'] and len(counts) == 2
    batch = next(iter(train()))
    feed = {'input_ids': np.stack([r[0] for r in batch]),
            'labels': np.stack([r[1] for r in batch])}
    biases = [v.name for v in main.list_vars()
              if isinstance(v, framework.Parameter) and not v.trainable]
    assert len(biases) == 2
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = [exe.run(main, feed=feed, fetch_list=[loss] + counts)
               for _ in range(12)]
        moved = np.asarray(fluid.global_scope().find_var(
            biases[0]).get_tensor())
    losses = [float(np.asarray(o[0]).reshape(-1)[0]) for o in out]
    assert losses[-1] < losses[0] - 0.05, losses
    assert all(int(c.sum()) == 2 * 32 * 2 for c in out[0][1:])
    assert 0 < np.abs(moved).max() <= 12 * 0.001 + 1e-6


# the source's config.json as the catalog's row holds it (model-configs
# guide, architectures.jsonl, `Ling-3.0-flash`), the two lists of 42 aside
SOURCE = {
    'first_k_dense_replace': 2,
    'gated_attention_proj_granularity_type': 'head_wise',
    'group_norm_size': 1, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 2560, 'intermediate_size': 6144, 'kda_lower_bound': -5,
    'kda_safe_gate': True, 'kv_lora_rank': 512, 'layer_group_size': 6,
    'linear_silu': True, 'max_position_embeddings': 262144,
    'max_window_layers': 20, 'moe_intermediate_size': 768,
    'moe_router_enable_expert_bias': True,
    'moe_shared_expert_intermediate_size': 768,
    'mtp_loss_scaling_factor': 0, 'mtp_use_kda': False, 'n_group': 8,
    'no_kda_lora': True, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts': 512, 'num_experts_per_tok': 8, 'num_hidden_layers': 42,
    'num_key_value_heads': 32, 'num_kv_heads_for_linear_attn': 0,
    'num_nextn_predict_layers': 1, 'num_shared_experts': 1,
    'partial_rotary_factor': 0.5, 'q_lora_rank': None, 'qk_head_dim': 192,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_interleave': True, 'rope_scaling': None, 'rope_theta': 6000000,
    'rotary_dim': 64, 'routed_scaling_factor': 2.5,
    'scale_router_input': False, 'score_function': 'sigmoid',
    'scoring_func': 'sigmoid', 'seq_aux': True,
    'short_conv_kernel_size': 4, 'tie_word_embeddings': False,
    'topk_group': 4, 'topk_method': 'noaux_tc', 'up_proj_norm': False,
    'use_bias': False, 'use_kda_lora': False, 'use_mla_nope': False,
    'use_nGPT': False, 'use_qk_norm': True, 'use_qkv_bias': False,
    'v_head_dim': 128, 'value_norm': False, 'vocab_size': 157184,
    'model_type': 'bailing_hybrid',
}
REDUCED = {'num_hidden_layers': 7, 'first_k_dense_replace': 1,
           'num_experts': 8, 'vocab_size': 19648,
           'num_nextn_predict_layers': 0}


def test_configuration_file_holds_the_published_sizes():
    """Every key of the catalog row's `config` at the top level at its
    published value but the five reduced ones; `model` repeats them for
    the builder; the contract's own checks; `assumed` and `deployment`
    say what the issue asks."""
    from chipbench.harness import contract
    with open(os.path.join(REPO, 'chipbench/configs/ling_3_0_flash.json')) \
            as f:
        held = json.load(f)
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    entry = [c for c in spec['configs'] if c['name'] == 'ling_3_0_flash'][0]
    assert entry['source'] == held['source'] == (
        'https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/'
        'config.json')
    assert entry['reduced'] == held['reduced'] == list(REDUCED)
    assert held['reduced_from'] == {k: SOURCE[k] for k in REDUCED}
    contract.check_reduced(entry, held)
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert held[key] == want, key
        assert held['model'][key] == want, key
    limits = [0] * 35 + [4] * 7, [0] * 34 + [5] * 6 + [7] * 2
    for key, want in zip(('expert_swiglu_limit_list',
                          'share_expert_swiglu_limit_list'), limits):
        assert held[key] == held['model'][key] == want
    assert not any(contract.names_a_width(k) for k in REDUCED)
    m = held['model']
    assert m['kept_layers'] == [0, 2, 3, 4, 5, 6, 7]
    assert reference_module().kinds_of(m) == KINDS
    assert (held['builder'], held['reference'], held['flops']) == (
        'bailing_hybrid',) * 3
    assert held['amp'] == 'bf16'
    assert held['optimizer'] == {
        'kind': 'adam', 'beta1': 0.9, 'beta2': 0.95, 'epsilon': 1e-08,
        'learning_rate': 0.0004, 'schedule': 'linear_warmup',
        'warmup_steps': 2000}
    for key in ('layer_pattern', 'decay', 'num_kv_heads_for_linear_attn',
                'use_qk_norm', 'use_mla_nope', 'partial_rotary_factor',
                'max_window_layers', 'group_norm_size', 'head_gate',
                'swiglu_limits', 'initializers', 'decay_parameters',
                'document_mask', 'seq_aux', 'num_experts', 'vocab_size',
                'num_nextn_predict_layers', 'optimizer'):
        assert held['assumed'].get(key, '').strip(), key
    assert '64 chips' in held['deployment'] \
        and '884.46 M' in held['deployment']
    spec_cell = [w for w in spec['workloads'] if w['name'] == CELL][0]
    assert (spec_cell['config'], spec_cell['traffic'], spec_cell['chips']) \
        == ('ling_3_0_flash', 'zipf_lm_b1_s8192', 1)
    # the cell is IN the reader's list, whatever a later cell adds to it
    kda_ms, = [m_ for m_ in spec['per_layer'] if m_['name'] == 'kda_ms']
    assert CELL in kda_ms['workloads']
    assert dict(kda_ms, workloads=None) == {
        'name': 'kda_ms', 'unit': 'ms', 'better': 'lower',
        'source': 'device_trace', 'layer': 'Lowering rules',
        'moves': 'tokens_per_s', 'workloads': None}


def test_the_checks_names_are_the_parameters_the_issue_asks_for():
    """`float32`: the embedding, layer 0's Wf, A_log, dt_bias, Wb, the
    convolution of k, the output norm's weight and Wg, the MLA layer's Wq,
    Wkvb and Wgate, the last expert layer's router and held gate stack;
    `amp`: the embedding, layer 0's Wf and A_log."""
    from chipbench.harness import catalog, check
    cell = catalog.load_cell(CELL)
    config = dict(cell['config'], check={'grads': []})
    built = cell['builder'].build(config, cell['traffic'], train=False)
    _, tree = cell['builder'].reference_params(config, built['main'],
                                               lambda n: None)
    checks = cell['config']['checks']
    assert sorted(checks) == ['amp', 'float32']
    paths = check.grad_paths(tree, set(checks['float32']['grads']))
    assert {paths[n] for n in checks['float32']['grads']} == {
        ('tok_emb', None), ('layer0.f', None), ('layer0.a_log', None),
        ('layer0.dt_bias', None), ('layer0.b', None),
        ('layer0.conv_k', None), ('layer0.norm_out', None),
        ('layer0.g', None), ('layer4.q', None), ('layer4.kv_b', None),
        ('layer4.gate', None), ('layer6.router', None),
        ('layer6.experts_in', 0)}
    paths = check.grad_paths(tree, set(checks['amp']['grads']))
    assert {paths[n] for n in checks['amp']['grads']} == {
        ('tok_emb', None), ('layer0.f', None), ('layer0.a_log', None)}
    assert checks['float32']['amp'] == 'none' \
        and checks['float32']['matmul_precision'] == 'highest'
    # between the chip's readings and the controls' (PR 55): a flipped
    # token's 0.018 and bf16's 0.098; bf16's 0.033 and 8-bit's 0.104
    assert checks['float32']['tolerance'] == {'loss': 1e-4,
                                              'grad': 0.046875}
    assert checks['amp']['tolerance'] == {'loss': 1e-3, 'grad': 0.09375}
    for entry in checks.values():
        assert entry['sample'] == 1 and len(entry['why']) > 400
    # 884.46 M parameters, as the issue counts them (the six selection
    # biases of 512 beside)
    total = sum(int(np.prod(v.shape)) for v in built['main'].list_vars()
                if isinstance(v, framework.Parameter))
    assert total == 884456384 + 6 * 512


def test_flops_of_the_cell_are_the_issues_arithmetic():
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    flops, config, traffic = cell['flops'], cell['config'], cell['traffic']
    m = config['model']
    assert flops.layer_counts(m) == (6, 1, 1, 6)
    # the mixers' matrices, as the issue counts the parameters (less the
    # filters, vectors and norms)
    assert flops.kda_weights(m) == 63049888 - 3 * 4 * 4096 - 32 - 4096 - 128
    assert flops.mla_weights(m) == 31965696 - 512
    assert flops.held_rows(config, 1, 8192) == 8192 * 8 * 8 / 512 == 1024
    f = flops.forward_flops(config, 1, 8192)
    t = 8192
    assert f['delta_rule'] == 6 * t * 32 * 7 * 128 * 128
    assert f['attention'] == 0.5 * 2 * t * t * 32 * (192 + 128)
    assert f['conv'] == 6 * t * 2 * 4 * 3 * 4096
    assert f['dense'] == t * 3 * 2 * 2560 * 6144
    assert f['experts'] == 6 * 1024 * 3 * 2 * 2560 * 768
    assert f['router'] == 6 * t * 2 * 2560 * 512
    assert f['head'] == t * 2 * 2560 * 19648
    step = flops.train_step_flops(config, traffic)
    assert step == 3.0 * sum(f.values())
    # the issue's 30.6 TFLOP a row of 8192
    assert 29e12 < step < 32e12
    cost = flops.delta_rule_cost(config, traffic)
    assert cost[0] == 3.0 * f['delta_rule']
    assert cost[1] == 3 * 6 * t * (2 * 4 * 4096 + 4 * 32 + 4 * 4096)
    mla = flops.latent_attention_cost(config, traffic)
    assert mla[0] == 3.0 * (f['mla_projections'] + f['attention'])
    kernels = flops.kernel_cost(config, traffic, 1)
    assert set(kernels) == {'flash_attention', 'moe_mlp'}
    assert kernels['flash_attention'] == (
        3.0 * f['attention'], 6 * t * 32 * (192 + 128) * 2)
    assert kernels['moe_mlp'][0] == 3.0 * f['experts']
    assert flops.expert_cost(config, traffic)[0] == 3.0 * (
        f['experts'] + f['router'])


def test_new_reader_reads_its_scope_or_nothing():
    """`kda_ms` on a hand-made reduction and a hand-made HLO: the scopes
    under `kda_mixer`, forward or backward; nothing, and no error, where
    the program names no such scope (the parent's) or nothing was
    traced."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(kda_mixer)/jvp(mul_4)/dot_general"}',
        '  %custom-call.2 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/transpose(jvp(kda_mixer))/'
        'transpose(jvp(gated_delta_rule_2))/gdn_scan/pallas_call"}',
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(latent_attention)/jvp(mul_12)/'
        'dot_general"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(kda_mixer_like)/jvp(mul_21)/dot_general"}',
    ])
    red = {'steps': 5, 'fluid_scope_s': {
        'mul_4': 0.10, 'gated_delta_rule_2': 0.40, 'mul_12': 0.05,
        'mul_21': 1.0}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('kda_ms')(reading) == pytest.approx(100.0)
    assert catalog.load_reader('mla_ms')(reading) == pytest.approx(10.0)
    bare = hlo.replace('kda_mixer', 'x')
    for other in (dict(reading, hlo=bare), dict(reading, trace=None),
                  dict(reading, hlo=None)):
        assert catalog.load_reader('kda_ms')(other) is None
