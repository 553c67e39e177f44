"""Qwen3-Next on the normal path (ISSUE 30): the delta rule's layer through
the Executor, the causal convolution, partial rotary and grouped key-value
heads against few-line formulas, the whole toy model against the
benchmark's plain reference, the counters and scopes, and the
configuration's file. The rule itself is tests/test_gated_delta_rule.py's,
an expert layer that holds a share of its experts
tests/test_held_experts.py's. Small sizes, on the CPU."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name
from decoder_toy import REPO, check_all, toy_cell
from test_gated_delta_rule import delta_inputs, plain_delta_net
from util import grads_of as _grads_of, input_parameter as _input


def test_delta_rule_layer_runs_the_op_with_its_scopes_and_counters():
    """layers.gated_delta_rule through the Executor: the recurrence's
    values and gradients, one `gated_delta_rule_<i>` scope with the stages
    `gdn_intra` and `gdn_scan` inside, the counters of the lowering."""
    args = delta_inputs(9, 48, 'mild')
    names = ['q', 'k', 'v', 'g', 'beta']
    w = np.random.default_rng(3).normal(size=args[2].shape).astype('float32')
    lowered = obs.counter('gdn.lowered', chunk=16, gate='head').value
    tokens = obs.counter('gdn.tokens').value

    def build():
        return layers.gated_delta_rule(
            *(_input(n, a) for n, a in zip(names, args)), chunk_size=16,
            qk_l2norm=True)

    got, grads, text = _grads_of(build, {'w': w}, names, optimized=True)
    want = plain_delta_net(*args)
    g_want = jax.grad(lambda *a: jnp.sum(plain_delta_net(*a) * w),
                      argnums=range(5))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(grads, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
    assert obs.counter('gdn.lowered', chunk=16, gate='head').value > lowered
    assert obs.counter('gdn.tokens').value - tokens >= 2 * 48
    scoped = [l for l in text.splitlines() if 'gated_delta_rule_' in l]
    assert any('gdn_intra' in l for l in scoped)
    assert any('gdn_scan' in l for l in scoped)
    assert any('transpose' in l and 'gdn_scan' in l for l in scoped)


def test_delta_rule_multiplies_bf16_under_amp_and_carries_float32():
    args = delta_inputs(4, 32, 'mild')
    names = ['q', 'k', 'v', 'g', 'beta']
    w = np.ones(args[2].shape, 'float32')

    def build():
        return layers.gated_delta_rule(
            *(_input(n, a) for n, a in zip(names, args)), chunk_size=16,
            qk_l2norm=True)

    got, _, text = _grads_of(build, {'w': w}, names, amp=True)
    want = np.asarray(plain_delta_net(*args))
    assert got.dtype == np.float32
    err = np.abs(got - want).max() / np.abs(want).max()
    assert 0 < err < 2.0 ** -5, err
    dots = [l for l in text.splitlines() if 'dot_general' in l]
    assert [l for l in dots if 'xbf16>, tensor' in l]
    # the solve's merges stay float32 at full precision
    assert [l for l in dots if 'HIGHEST' in l and 'bf16' not in l]


# ------------------------------------------------------------ convolution

def test_causal_conv1d_is_a_four_term_sum():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 11, 6)).astype('float32')
    f = rng.normal(size=(4, 6)).astype('float32')
    w = rng.normal(size=(2, 11, 6)).astype('float32')
    before = obs.counter('conv1d.lowered', taps=4, act='silu').value

    def build():
        return layers.causal_conv1d(
            _input('x', x), 4, act='silu', param_attr=fluid.ParamAttr(
                name='f', initializer=fluid.initializer
                .NumpyArrayInitializer(f)))

    def formula(x, f):
        p = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(f[0] * p[:, 0:11] + f[1] * p[:, 1:12]
                           + f[2] * p[:, 2:13] + f[3] * p[:, 3:14])

    got, (gx, gf), text = _grads_of(build, {'w': w}, ['x', 'f'],
                                    optimized=True)
    np.testing.assert_allclose(got, formula(x, f), rtol=1e-5, atol=1e-6)
    wx, wf = jax.grad(lambda a, b: jnp.sum(formula(a, b) * w),
                      argnums=(0, 1))(x, f)
    np.testing.assert_allclose(gx, wx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gf, wf, rtol=1e-4, atol=1e-5)
    # causal: token t sees nothing after t; the last tap is token t's own
    np.testing.assert_allclose(
        got[:, 0], jax.nn.silu(f[3] * x[:, 0]), rtol=1e-5, atol=1e-6)
    assert obs.counter('conv1d.lowered', taps=4, act='silu').value > before
    assert 'causal_conv1d_' in text


# ------------------------------------------- partial rotary, grouped heads

def test_partial_rotary_turns_the_first_elements_only():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 3, 10, 16)).astype('float32')
    w = rng.normal(size=(2, 3, 10, 16)).astype('float32')
    before = obs.counter('rotary.lowered', rotary_dim=4).value
    whole = obs.counter('rotary.lowered').value

    def build():
        return layers.rotary_embedding(_input('x', x), base=100.0,
                                       rotary_dim=4)

    def formula(x):
        angle = jnp.arange(10.)[:, None] * 100.0 ** (
            -2 * jnp.arange(2.) / 4)[None, :]
        a, b = x[..., :2], x[..., 2:4]               # pairs (i, i + 2)
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle),
                                x[..., 4:]], -1)

    got, (gx,), _ = _grads_of(build, {'w': w}, ['x'])
    np.testing.assert_allclose(got, formula(x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx, jax.grad(
        lambda a: jnp.sum(formula(a) * w))(x), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    assert obs.counter('rotary.lowered', rotary_dim=4).value > before
    assert obs.counter('rotary.lowered').value == whole
    with pytest.raises(ValueError, match='even number'):
        build_bad = framework.Program()
        with framework.program_guard(build_bad, framework.Program()):
            layers.rotary_embedding(
                layers.data(name='x', shape=[2, 4, 8], dtype='float32'),
                rotary_dim=3)


def test_grouped_key_value_heads_serve_their_query_heads():
    rng = np.random.default_rng(7)
    q = rng.normal(size=(2, 6, 12, 8)).astype('float32')
    k, v = (rng.normal(size=(2, 2, 12, 8)).astype('float32')
            for _ in range(2))
    w = rng.normal(size=q.shape).astype('float32')
    before = obs.counter('flash.grouped', q_heads=6, kv_heads=2,
                         head_dim=8).value

    def build():
        return layers.fused_attention(_input('q', q), _input('k', k),
                                      _input('v', v), causal=True,
                                      scale=8 ** -0.5)

    def formula(q, k, v):
        k, v = (jnp.repeat(t, 3, axis=1) for t in (k, v))   # head h // 3
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * 8 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
        return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)

    got, grads, _ = _grads_of(build, {'w': w}, ['q', 'k', 'v'])
    np.testing.assert_allclose(got, formula(q, k, v), rtol=1e-4, atol=1e-5)
    want = jax.grad(lambda *a: jnp.sum(formula(*a) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
    assert obs.counter('flash.grouped', q_heads=6, kv_heads=2,
                       head_dim=8).value > before
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[6, 12, 8], dtype='float32')
        y = layers.data(name='y', shape=[4, 12, 8], dtype='float32')
        with pytest.raises(ValueError, match='6 query heads over 4'):
            layers.fused_attention(x, y, y)


# ----------------------------------------------------------------- the model

def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/qwen3_next.py through the Executor against
    chipbench/references/qwen3_next.py in float32: the loss and the
    gradient of EVERY parameter of one period (three DeltaNet layers, one
    attention layer, four expert blocks holding experts 4..7 of 16)."""
    cell = toy_cell('qwen3next_s8192')
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 5e-4})
    assert len(names) == 1 + 3 * (8 + 9) + (7 + 9) + 2
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    assert max(got['grad_rel'].values()) < 5e-4
    # T = 80 is not a multiple of the chunk: the padding was exercised
    assert cell['traffic']['seq'] % 64


def test_small_preset_trains_and_routes_over_all_experts():
    from paddle_tpu.models import qwen3_next
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = qwen3_next.get_model(
            experts_held=(4, 4))
    rows = next(train())
    feed = {'input_ids': np.stack([r[0] for r in rows]),
            'labels': np.stack([r[1] for r in rows])}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        for _ in range(6):
            out = exe.run(main, feed=feed, fetch_list=[loss, counts[-1]])
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert out[1].shape == (16,) and out[1].sum() == 2 * 32 * 2
    types = [op.type for op in main.global_block().ops]
    assert types.count('gated_delta_rule') == 3
    assert types.count('causal_conv1d') == 3
    assert types.count('flash_attention') == 1
    assert types.count('moe_mlp') == 4
    # the head is the last fc built: loss_head_ms reads the last `mul`
    muls = [op for op in main.global_block().ops if op.type == 'mul']
    head = main.global_block().var(muls[-1].input('Y')[0])
    assert head.shape[-1] == 256


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at the
    top level (the driver compares those) and in `model` (the builder reads
    that); only the depth, the experts held and the vocabulary are cut."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'qwen3_next_80b_a3b.json')) as f:
        held = json.load(f)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    rows = []
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(l) for l in f if l.strip()]
    source = {"decoder_sparse_step": 1, "full_attention_interval": 4,
              "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
              "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
              "linear_key_head_dim": 128, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_value_head_dim": 128,
              "max_position_embeddings": 262144, "mlp_only_layers": [],
              "model_type": "qwen3_next", "moe_intermediate_size": 512,
              "norm_topk_prob": True, "num_attention_heads": 16,
              "num_experts": 512, "num_experts_per_tok": 10,
              "num_hidden_layers": 48, "num_key_value_heads": 2,
              "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
              "rope_scaling": None, "rope_theta": 10000000,
              "shared_expert_intermediate_size": 512,
              "tie_word_embeddings": False, "use_sliding_window": False,
              "vocab_size": 151936}
    for row in rows:
        if row['name'] == 'Qwen3-Next-80B-A3B-Instruct':
            assert row['config'] == source
            assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 4, 'num_experts': 16, 'vocab_size': 18992}
    for key, value in source.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == sorted(cut, key=list(cut).index)
    assert held['reduced_from'] == {k: source[k] for k in cut}
    assert set(held['model']) - set(source) == {
        'router_aux_loss_coef', 'initializer_range', 'first_expert_held'}
    # the floors of the guide: a whole period, 8 experts, an eighth
    assert held['num_hidden_layers'] % held['full_attention_interval'] == 0
    assert held['num_experts'] >= 8
    assert held['vocab_size'] * 8 >= source['vocab_size']
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('norm_weights', 'projection_layout', 'multi_token_prediction',
                'router_aux_loss_coef', 'initializers', 'optimizer',
                'document_mask', 'top_level_keys'):
        assert held['assumed'][key], key
    assert '32 chips' in held['deployment']


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 8192 (ISSUE 30): three DeltaNet layers 213 M
    of which the recurrence 11 M, attention 55 M of projections and 67 M
    of scores, four expert blocks 42 M of which the held experts 8 M, the
    head 78 M; no roofline's cost is above what the whole step needs."""
    from chipbench.harness import catalog
    cell = catalog.load_cell('qwen3next_s8192')
    config, traffic = cell['config'], cell['traffic']
    tokens = traffic['batch'] * traffic['seq']
    f = {k: v / tokens / 1e6 for k, v in cell['flops'].forward_flops(
        config, traffic['batch'], traffic['seq']).items()}
    assert f['delta_projections'] == pytest.approx(3 * 67.4, rel=0.01)
    assert f['delta_rule'] == pytest.approx(3 * 32 * 7 * 128 * 128 / 1e6)
    assert f['attention_projections'] == pytest.approx(54.5, rel=0.01)
    assert f['attention'] == pytest.approx(67.1, rel=0.01)
    assert f['experts'] == pytest.approx(4 * 10 * 16 / 512 * 6.29, rel=0.01)
    blocks = f['experts'] + f['router'] + f['shared_expert']
    assert blocks == pytest.approx(41.5, rel=0.02)
    assert f['head'] == pytest.approx(77.8, rel=0.01)
    step = cell['flops'].train_step_flops(config, traffic)
    assert step == pytest.approx(11.2e12, rel=0.02)
    costs = dict(cell['flops'].kernel_cost(config, traffic, 1),
                 experts=cell['flops'].expert_cost(config, traffic, 1),
                 delta=cell['flops'].delta_rule_cost(config, traffic, 1))
    for name, (flops, nbytes) in costs.items():
        assert 0 < flops < step and nbytes > 0, name
    assert cell['flops'].held_rows(config, 1, 8192) == 8192 * 10 / 32


def test_new_readers_read_their_scopes_or_nothing():
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell('qwen3next_s8192')
    red = {'steps': 5, 'fluid_op_s': {'gated_delta_rule': 0.25,
                                      'causal_conv1d': 0.01}}
    reading = {'trace': red, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('gdn_ms')(reading) == pytest.approx(50.0)
    assert catalog.load_reader('conv1d_ms')(reading) == pytest.approx(2.0)
    share = catalog.load_reader('gdn_roofline')(reading)
    least, bound = peaks.roofline(cell['flops'].delta_rule_cost(
        cell['config'], cell['traffic'], 1), reading['peaks'])
    assert share == pytest.approx(100 * least / 0.05) and 0 < share < 100
    assert bound == 'bytes'
    # a program without the ops (the parent's): nothing, and no error
    for trace in (None, {'steps': 5, 'fluid_op_s': {}}):
        for name in ('gdn_ms', 'gdn_roofline', 'conv1d_ms'):
            assert catalog.load_reader(name)(
                dict(reading, trace=trace)) is None
    olmoe = catalog.load_cell('olmoe_s4096')
    assert catalog.load_reader('gdn_roofline')(
        dict(reading, cell=olmoe)) is None
