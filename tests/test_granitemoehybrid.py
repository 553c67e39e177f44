"""GraniteMoeHybrid on the normal path (ISSUE 53): the toy model (three
Mamba-2 layers of ONE B/C group and one attention layer without positions,
a dense gated feed-forward in each, the four multipliers, the tied head)
against the plain reference on the loss and every gradient; the tied
table's gradient as the sum of its two uses; each multiplier and each rule
the configuration forced FAILING the comparison when moved in the
reference; the reference's walk with its weights on the host against
`jax.grad` of the same function in one piece; name scopes, regions,
counters, the configuration's file against the catalog's row, its FLOPs
and its readers. Small sizes, on the CPU."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, unique_name

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'granite4hmicro_s8192'
# the issue's small size: 3 mamba + 1 attention, vocabulary 256
SMALL = dict(layer_types=['mamba', 'mamba', 'mamba', 'attention'],
             num_hidden_layers=4, vocab_size=256)


reference_module = functools.partial(decoder_toy.reference_module,
                                     'granitemoehybrid')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


# trainable parameters a layer: the mixer's norm and parameters, the
# feed-forward's norm and two matrices
_PER_KIND = {'mamba': 1 + 8 + 3, 'attention': 1 + 4 + 3}


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/granitemoehybrid.py through the Executor against
    chipbench/references/granitemoehybrid.py in float32 to 1e-5: the loss
    and the gradient of EVERY trainable parameter over rows of 80 (three
    Mamba-2 mixers of 4 heads in ONE group in chunks of 16, the
    convolution's bias, dt_bias, A_log, D and the one-group gated norm
    among them; attention of 4 heads over 2 without positions at scores x
    1/64; a gated feed-forward in every layer; the tied table); and under
    bf16 AMP within a stated tolerance."""
    cell = _toy_cell(**SMALL)
    assert cell['builder'].kinds(cell['config']['model']) == \
        SMALL['layer_types']
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert len(names) == 1 + sum(_PER_KIND[k]
                                 for k in SMALL['layer_types']) + 1
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _model(**over):
    """A reference whose functions see `over` in place of the model's
    keys."""
    def move(ref):
        plain_layer, plain_head = ref.layer, ref.head_loss
        ref.layer = lambda w, x, model, kind: plain_layer(
            w, x, dict(model, **over), kind)
        ref.head_loss = lambda x, n, t, labels, model: plain_head(
            x, n, t, labels, dict(model, **over))
    return move


def _embedding_unscaled(ref):
    plain = ref.pieces

    def pieces(model):
        fn = plain(model)
        fn['embed'] = jax.jit(lambda table, ids: table[ids])
        fn['embed_back'] = jax.jit(
            lambda dtable, ids, dx: dtable.at[ids].add(dx))
        return fn
    ref.pieces = pieces


def _untied_head(ref):
    """The head's gradient does not reach the table."""
    plain = ref.pieces

    def pieces(model):
        fn = plain(model)
        head = fn['head']

        def cut(x, w_norm, table, labels):
            loss, (dx, dn, dt) = head(x, w_norm, table, labels)
            return loss, (dx, dn, jnp.zeros_like(dt))
        fn['head'] = cut
        return fn
    ref.pieces = pieces


def _scan_by_two_groups(ref):
    """B and C's two halves read as two groups of half the state."""
    plain = ref.mamba
    ref.MIXERS = dict(ref.MIXERS, mamba=lambda w, u, model: plain(
        w, u, dict(model, mamba_n_groups=2,
                   mamba_d_state=model['mamba_d_state'] // 2)))


def _norm_by_two_groups(ref):
    """The gated norm over two groups of half the columns; the scan as it
    is. The mixer with a norm weight of 1 and an identity for Wout gives
    y * silu(z) normed over ALL columns; a positive scale a token cancels
    in a norm by halves of the same token (eps does not: at 1e-5 against
    unit-scale y that is below what the comparison resolves)."""
    plain = ref.mamba

    def mamba(w, u, model):
        inner = model['mamba_n_heads'] * model['mamba_d_head']
        normed = plain(dict(w, out=jnp.eye(inner, dtype=jnp.float32),
                            norm_out=jnp.ones((inner,), jnp.float32)),
                       u, model)
        halves = normed.reshape(normed.shape[:-1] + (2, inner // 2))
        halves = halves * jax.lax.rsqrt(
            jnp.mean(jnp.square(halves), -1, keepdims=True)
            + model['rms_norm_eps'])
        return (w['norm_out'] * halves.reshape(normed.shape)) @ w['out']
    ref.MIXERS = dict(ref.MIXERS, mamba=mamba)


_MOVED = {
    'embedding_multiplier_left_out': _embedding_unscaled,
    'residual_multiplier_of_one': _model(residual_multiplier=1.0),
    'scores_over_sqrt_head_dim': _model(attention_multiplier=16 ** -0.5),
    'logits_not_divided': _model(logits_scaling=1.0),
    'untied_head': _untied_head,
    'scan_grouped_by_two': _scan_by_two_groups,
    'norm_grouped_by_two': _norm_by_two_groups,
}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference without one of the four multipliers (the embedding's 12,
    the branches' 0.22, the scores' 1/64, the logits' 1/8), with a head
    whose gradient does not reach the table, with B and C read as two
    groups, or with the gated norm taken by two groups, the same Program
    FAILS at the same tolerance. The reference is a fresh copy of the
    module with ONE function moved."""
    reference = reference_module()
    _MOVED[rule](reference)
    cell = dict(_toy_cell(**SMALL), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert not got['passed']
    assert max(got['grad_rel'].values()) > 1e-3


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses():
    """One parameter `[vocab, hidden]`, bound twice (the lookup and the
    head's `mul`): its gradient is the head's dense one PLUS the lookup's
    scattered one times the embedding's multiplier, and the reference
    without the head's part misses by about as much as it holds."""
    cell = _toy_cell(**SMALL)
    uses = obs.counter('model.shared_param_uses').value
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert obs.counter('model.shared_param_uses').value - uses >= 1
    assert names[0] == 'granite_tok_emb'
    assert names.count('granite_tok_emb') == 1
    assert got['grad_rel']['granite_tok_emb'] < 1e-5
    reference = reference_module()
    _untied_head(reference)
    _, cut = check_all(dict(cell, reference=reference),
                        {'loss': 1e-5, 'grad': 1e-5})
    assert cut['loss_rel'] < 1e-5
    assert cut['grad_rel']['granite_tok_emb'] > 0.1
    others = [v for k, v in cut['grad_rel'].items()
              if k != 'granite_tok_emb']
    assert max(others) < 1e-5


def test_the_walk_with_host_weights_is_the_gradient_of_the_whole():
    """`loss_and_grads` (forward keeping each layer's input, backward
    with `jax.vjp` of ONE layer at a time, the layer's parameters put on
    the device for the call) against `jax.value_and_grad` of
    `forward_loss`, the same function in one piece: every path, and the
    results live on the host."""
    ref = reference_module()
    cell = _toy_cell(**SMALL)
    config = cell['config']
    scope, built = decoder_toy.started(cell)
    params, tree = cell['builder'].reference_params(
        config, built['main'],
        lambda n: np.asarray(scope.find_var(n).get_tensor()))
    assert all(isinstance(v, np.ndarray) for v in params.values())
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          config, 9)
    model = config['model']
    loss, grads = ref.loss_and_grads(params, model, pool[0], sorted(tree))
    ids, labels = (jnp.asarray(pool[0][k], jnp.int32)
                   for k in ('input_ids', 'labels'))
    with jax.default_matmul_precision('highest'):
        want, want_grads = jax.value_and_grad(
            lambda p: ref.forward_loss(p, model, ids, labels))(
            jax.tree_util.tree_map(jnp.asarray, params))
    assert abs(loss - float(want)) <= 1e-6 * abs(float(want))
    assert sorted(grads) == sorted(tree)
    for path in tree:
        assert isinstance(grads[path], np.ndarray), path
        a, b = grads[path], np.asarray(want_grads[path])
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b), path
    # a second call on the same sample walks nothing again
    again = ref.loss_and_grads(params, model, pool[0], ['tok_emb'])
    assert again[1]['tok_emb'] is grads['tok_emb']


def test_layers_are_mixer_and_feed_forward_scopes_regions_and_counters():
    """Ten layers off `layer_types`, each a mixer AND a gated feed-forward
    behind a norm each in ONE recompute region; the Mamba-2 mixers are
    nemotron_h's, built under `mamba_mixer` at one group, the attention
    mixer under `attention_mixer` with the configuration's scale and no
    rotary op, every feed-forward under `dense_mlp`; the multipliers are
    `scale` ops; the head is the last `mul` and reads the table; the
    builder counts a layer of each kind; the scopes reach the optimized
    HLO's op_name."""
    from chipbench.harness import catalog
    from paddle_tpu.models import granitemoehybrid as G, nemotron_h
    assert G.mamba_mixer is nemotron_h.mamba_mixer
    assert G.LAYER_TYPES[:10] == tuple(
        catalog.load_cell(CELL)['config']['layer_types'][:10])
    cell = _toy_cell()
    before = {k: obs.counter('granite.layers', kind=k).value
              for k in ('mamba', 'attention')}
    conv = obs.counter('conv1d.lowered', taps=4, act='silu',
                       bias='true').value
    config, built = build_toy(cell, train=True)
    assert obs.counter('granite.layers', kind='mamba').value \
        - before['mamba'] == 9
    assert obs.counter('granite.layers', kind='attention').value \
        - before['attention'] == 1
    assert obs.counter('conv1d.lowered', taps=4, act='silu',
                       bias='true').value - conv == 9
    ops = built['main'].global_block().ops
    forward = [op for op in ops if not op.type.endswith('_grad')
               and op.type != 'adam']
    kinds = [op.type for op in forward]
    assert kinds.count('rms_norm') == 21 and 'rotary_embedding' not in kinds
    assert kinds.count('ssd_scan') == kinds.count('causal_conv1d') == 9
    assert kinds.count('gated_rms_norm') == 9
    assert kinds.count('flash_attention') == 1
    scales = [op.attrs['scale'] for op in forward if op.type == 'scale'
              and op.attrs.get('name_scope') is None]
    assert scales.count(pytest.approx(0.22)) == 20
    assert scales.count(12.0) == 1 and scales.count(0.125) == 1
    for op in forward:
        scope = op.attrs.get('name_scope')
        if op.type in ('ssd_scan', 'causal_conv1d', 'gated_rms_norm'):
            assert scope == 'mamba_mixer', op.type
        if op.type == 'flash_attention':
            assert scope == 'attention_mixer' and op.attrs['causal']
            assert op.attrs['scale'] == 0.015625
        if op.type in ('rms_norm', 'lookup_table'):
            assert scope is None
        if op.type == 'gated_rms_norm':
            assert op.attrs['norm_before_gate'] is False
            assert op.attrs.get('groups', 1) == 1
        if op.type == 'ssd_scan':
            assert op.attrs['chunk_size'] == 16 and op.input('D')
            b = built['main'].global_block().var(op.input('B')[0])
            assert b.shape[2] == 1                     # ONE group
    muls = [op for op in forward if op.type == 'mul']
    assert [op.attrs.get('name_scope') for op in muls].count(
        'dense_mlp') == 20
    assert muls[-1].attrs.get('name_scope') is None
    table = built['main'].global_block().var('granite_tok_emb')
    assert tuple(table.shape) == (211, 64)
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 10
    text = decoder_toy.one_step_hlo(cell, config, built)
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    mamba = window.op_scopes_under(text, 'mamba_mixer')
    attn = window.op_scopes_under(text, 'attention_mixer')
    mlp = window.op_scopes_under(text, 'dense_mlp')
    assert mamba and attn and mlp
    assert not (mamba & attn or mamba & mlp or attn & mlp)
    assert {s.rsplit('_', 1)[0] for s in mamba} >= {
        'mul', 'causal_conv1d', 'ssd_scan', 'gated_rms_norm'}
    assert {s.rsplit('_', 1)[0] for s in attn} >= {'mul', 'flash_attention'}
    assert {s.rsplit('_', 1)[0] for s in mlp} >= {'mul', 'swish'}
    assert len([s for s in mlp if s.startswith('mul_')]) == 20


def test_a_layer_of_another_kind_is_refused():
    from paddle_tpu.models import granitemoehybrid as G
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match="'mamba' or 'attention'"):
            G.granitemoehybrid(64, 16, layer_types=('mamba', 'moe'),
                               hidden=16, ssm_heads=2, ssm_head_dim=8,
                               ssm_state=8, chunk_size=8, n_head=2,
                               n_kv_head=1, d_head=8, mlp_width=32)


def test_small_preset_trains():
    from paddle_tpu.models import granitemoehybrid as G
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, train, _, feeds = G.get_model()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        batch = next(iter(train()))
        feed = {feeds[0]: np.stack([b[0] for b in batch]),
                feeds[1]: np.stack([b[1] for b in batch])}
        losses = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
            for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ------------------------------------------------------------- the benchmark

# the catalog's row `granite-4.0-h-micro`, its `config` copied here
SOURCE = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth and the vocabulary are cut, no width, and
    `layer_types` stands whole."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'granite_4_0_h_micro.json')) as f:
        held = json.load(f)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'granite-4.0-h-micro':
                    assert row['config'] == SOURCE
                    assert row['source_url'] == held['source']
    assert held['source'] == ('https://huggingface.co/ibm-granite/'
                              'granite-4.0-h-micro/blob/main/config.json')
    cut = {'num_hidden_layers': 10, 'vocab_size': 12544}
    for key, value in SOURCE.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: SOURCE[k] for k in cut}
    assert set(held['model']) - set(SOURCE) == {
        'head_dim', 'initializer_range', 'time_step_min', 'time_step_max',
        'time_step_floor'}
    run = held['layer_types'][:held['num_hidden_layers']]
    assert run == ['mamba'] * 5 + ['attention'] + ['mamba'] * 4
    assert len(held['layer_types']) == 40
    assert held['layer_types'].count('attention') == 4
    # the floors of the guide: a whole period, an eighth of the vocabulary
    assert held['vocab_size'] * 8 == SOURCE['vocab_size']
    assert held['amp'] == 'bf16'
    assert sorted(held['checks']) == ['amp', 'float32']
    for key in ('top_level_keys', 'num_hidden_layers', 'vocab_size',
                'dense', 'multipliers', 'head_dim', 'attention_positions',
                'mamba_layout', 'mamba_expand', 'time_step_limit',
                'initializers', 'optimizer', 'max_position_embeddings',
                'document_mask', 'recomputation'):
        assert held['assumed'][key], key
    for said in ('four stages', 'eight chips', '772.16 M', '9.27 GB',
                 '12.35 GB', '3.2 %'):
        assert said in held['deployment'], said
    # layer 0's mixer (Win, the convolution's bias, A_log, dt_bias, D, the
    # gated norm), layer 5's Wq and Wk, layer 9's feed-forward, the table
    assert set(held['checks']['float32']['grads']) == {
        'granite_tok_emb', 'fc_0.w_0', 'causal_conv1d_0.b_0',
        'create_parameter_0.w_0', 'create_parameter_1.w_0',
        'create_parameter_2.w_0', 'gated_rms_norm_0.w_0', 'fc_20.w_0',
        'fc_21.w_0', 'fc_40.w_0', 'fc_41.w_0'}
    assert set(held['checks']['amp']['grads']) == {
        'granite_tok_emb', 'fc_0.w_0', 'create_parameter_0.w_0',
        'create_parameter_1.w_0'}
    assert held['checks']['float32']['tolerance']['loss'] == 1e-4
    assert held['checks']['amp']['tolerance']['loss'] == 1e-3
    for entry in held['checks'].values():
        assert len(entry['why']) > 400


def test_the_checks_names_are_the_parameters_the_issue_asks_for():
    """At the published stretch the names the `checks` carry are layer 0's
    mixer, layer 5's Wq and Wk and layer 9's feed-forward (the builder's
    tree says which path each name is)."""
    from chipbench.harness import check
    cell = _toy_cell()
    with fluid.scope_guard(fluid.Scope()):
        built = cell['builder'].build(
            dict(cell['config'], check={'grads': []}, amp='none'),
            cell['traffic'], train=False)
        _, tree = cell['builder'].reference_params(
            cell['config'], built['main'], lambda n: n)
    paths = check.grad_paths(
        tree, set(cell['config']['checks']['float32']['grads']))
    assert {n: p for n, (p, _) in paths.items()} == {
        'granite_tok_emb': 'tok_emb', 'fc_0.w_0': 'layer0.in',
        'causal_conv1d_0.b_0': 'layer0.conv_bias',
        'create_parameter_0.w_0': 'layer0.dt_bias',
        'create_parameter_1.w_0': 'layer0.a_log',
        'create_parameter_2.w_0': 'layer0.d',
        'gated_rms_norm_0.w_0': 'layer0.norm_out',
        'fc_20.w_0': 'layer5.q', 'fc_21.w_0': 'layer5.k',
        'fc_40.w_0': 'layer9.mlp_in', 'fc_41.w_0': 'layer9.mlp_out'}


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """The parameters by part (ISSUE 53's count: a Mamba-2 layer
    76,182,976, the attention layer 60,821,504, 772.16 M in all) and the
    forward FLOPs a token at 8192."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 8192
    m = config['model']
    assert flops.layer_counts(m) == (9, 1)
    assert flops.mamba_widths(m) == (4096, 256, 64)
    mixer = flops.mamba_weights(m) + 4 * 4352 + 4352 + 3 * 64 + 4096
    assert flops.mamba_weights(m) == 2048 * 8512 + 4096 * 2048
    assert mixer == 25847232
    assert flops.mlp_weights(m) == 50331648
    mamba_layer = mixer + flops.mlp_weights(m) + 2 * 2048
    attn_layer = flops.attention_weights(m) + flops.mlp_weights(m) + 2 * 2048
    assert flops.attention_weights(m) == 10485760
    assert (mamba_layer, attn_layer) == (76182976, 60821504)
    n = 9 * mamba_layer + attn_layer + 12544 * 2048 + 2048
    assert n == 772160448
    assert 12 * n == pytest.approx(9.27e9, rel=1e-3)
    assert 16 * n == pytest.approx(12.35e9, rel=1e-3)
    f = flops.forward_flops(config, traffic['batch'], traffic['seq'])
    assert f['dense_mlp'] == 10 * tokens * 2 * 50331648
    assert f['ssd'] == 9 * tokens * 5 * 4096 * 128
    assert f['attention'] == 2 * 2 * 64 * 32 * 8192 * 8193 // 2
    assert f['head'] == tokens * 2 * 2048 * 12544
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(3 * sum(f.values()))
    # 6 x parameters x tokens + attention + the scan and the convolution
    assert 37e12 < step < 41e12
    assert f['head'] / sum(f.values()) == pytest.approx(0.032, abs=0.001)
    assert flops.dense_mlp_flops(config, traffic) == \
        6 * 50331648 * tokens * 10
    ssd = flops.ssd_cost(config, traffic, 1)
    # x, y 4096 wide, B, C 128 each in bf16, dt 64 in float32, a pass
    assert ssd[1] == 3 * 9 * 8192 * (2 * (2 * 4096 + 256) + 4 * 64)
    assert ssd[0] == 3 * f['ssd']
    flash = flops.kernel_cost(config, traffic, 1)
    assert set(flash) == {'flash_attention'}
    width = 8192 * 64 * 2
    assert flash['flash_attention'] == (
        3.0 * f['attention'], (4 * 32 + 8 * 8) * width + 2 * 32 * 8192 * 4)


def test_new_readers_read_their_scope_or_nothing():
    """`dense_mlp_ms` and `dense_mlp_peak_pct` on a hand-made reduction
    and a hand-made HLO; on a program that names no such scope (the
    parent's) or a configuration that counts no such FLOPs nothing, and no
    error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(dense_mlp)/jvp(mul_2)/dot_general"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/transpose(jvp(dense_mlp))/'
        'transpose(jvp(swish_0))/mul"}',
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mamba_mixer)/jvp(mul_0)/dot_general"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(dense_mlp_like)/jvp(mul_9)/dot"}',
    ])
    red = {'steps': 5,
           'fluid_scope_s': {'mul_2': 1.0, 'swish_0': 0.25, 'mul_0': 0.5,
                             'mul_9': 1.0},
           'fluid_op_s': {'mul': 2.5}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('dense_mlp_ms')(reading) == \
        pytest.approx(250.0)
    share = catalog.load_reader('dense_mlp_peak_pct')(reading)
    required = 6 * 50331648 * 8192 * 10
    assert share == pytest.approx(100 * required / 0.25 / 197e12)
    assert 0 < share < 75
    for other in (dict(reading, hlo=hlo.replace('dense_mlp', 'x')),
                  dict(reading, trace=None), dict(reading, hlo=None),
                  dict(reading, peaks=None)):
        assert catalog.load_reader('dense_mlp_peak_pct')(other) is None
    for other in (dict(reading, hlo=hlo.replace('dense_mlp', 'x')),
                  dict(reading, trace=None), dict(reading, hlo=None)):
        assert catalog.load_reader('dense_mlp_ms')(other) is None
    # a configuration without `dense_mlp_flops` (every older one)
    older = dict(reading, cell=catalog.load_cell('nemotron3nano_s8192'))
    assert catalog.load_reader('dense_mlp_peak_pct')(older) is None
