"""OLMoE on the normal path (ISSUE 26): the dropless expert rule, RMS norm
and rotary embedding against few-line formulas, the whole toy model against
the benchmark's plain reference, what AMP casts, the typed refusal
on a mesh, and the configuration's file. Small sizes, on the CPU."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name

from decoder_toy import REPO, check_all, reference_module, toy_cell
from util import input_parameter as _input

N, D, E, H = 48, 16, 8, 12


def _silu(x):
    return x / (1.0 + np.exp(-x))


def plain_moe(x, wr, w1, w3, w2, k, norm):
    """Token by token, expert by expert, float64. Returns (out, chosen
    [N, k], the gap between each token's k-th and (k+1)-th logit)."""
    x = x.astype(np.float64)
    logits = x @ wr
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-logits, axis=-1, kind='stable')
    chosen = order[:, :k]
    srt = -np.sort(-logits, axis=-1)
    out = np.zeros((x.shape[0], w2.shape[-1]))
    for t in range(x.shape[0]):
        g = p[t, chosen[t]]
        if norm and k > 1:
            g = g / g.sum()
        for j, e in enumerate(chosen[t]):
            h = _silu(x[t] @ w1[e]) * (x[t] @ w3[e])
            out[t] += g[j] * (h @ w2[e])
    return out, chosen, srt[:, k - 1] - srt[:, k]


def build_moe(k, capacity_factor, norm, amp=False):
    """(main, startup, x, out, aux, count) of one gated, bias-free layer."""
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, aux, count = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=k, norm_topk_prob=norm, capacity_factor=capacity_factor,
            bias_attr=False, return_aux_loss=True, return_expert_count=True)
        if amp:
            fluid.amp.decorate_program(main)
    return main, startup, x, out, aux, count


def _weights(scope):
    return [np.asarray(scope.find_var('moe_mlp_0.w_%d' % i).get_tensor())
            for i in range(4)]       # router, gate (W1), up (W3), down (W2)


@pytest.mark.parametrize('norm', [False, True], ids=['raw', 'renormalised'])
def test_dropless_rule_matches_the_plain_reference(norm):
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N, D)).astype('float32')
    main, startup, _, out, _, count = build_moe(3, None, norm)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, cnt = exe.run(main, feed={'x': xs}, fetch_list=[out, count])
        wr, w1, w3, w2 = _weights(fluid.global_scope())
    want, chosen, gap = plain_moe(xs, wr, w1, w3, w2, 3, norm)
    # a token whose 3rd and 4th logits lie within float32's error of each
    # other may choose otherwise; none does at this seed, and the test says
    # so instead of loosening the comparison
    assert gap.min() > 1e-5, gap.min()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(cnt, np.bincount(chosen.ravel(),
                                                   minlength=E))
    assert cnt.sum() == N * 3
    # the two forms of the gates differ, so each case holds its own
    other, _, _ = plain_moe(xs, wr, w1, w3, w2, 3, not norm)
    assert np.abs(other - want).max() > 1e-3


def test_router_forced_onto_the_same_experts_drops_nothing():
    """Every token to experts 0 and 1: the dropless path computes all
    N x 2 assignments; fixed capacity at the default factor drops."""
    rng = np.random.default_rng(1)
    xs = np.abs(rng.normal(size=(N, D))).astype('float32') + 0.1
    outs = {}
    for name, cf in (('dropless', None), ('capacity', 2.0)):
        main, startup, _, out, _, count = build_moe(2, cf, False)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            wr = np.zeros((D, E), 'float32')
            wr[:, 0], wr[:, 1] = 2.0, 1.0      # x > 0: expert 0, then 1
            fluid.global_scope().find_var('moe_mlp_0.w_0').get_tensor() \
                .set(wr, fluid.CPUPlace())
            outs[name] = exe.run(main, feed={'x': xs},
                                 fetch_list=[out, count])
            _, w1, w3, w2 = _weights(fluid.global_scope())
    want, chosen, _ = plain_moe(xs, wr, w1, w3, w2, 2, False)
    assert set(chosen.ravel()) == {0, 1}
    got, cnt = outs['dropless']
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert cnt.tolist() == [N, N] + [0] * (E - 2) and cnt.sum() == N * 2
    # capacity 2.0 * 2 * 48 / 8 = 24 slots an expert: half of each is lost
    dropped = np.abs(outs['capacity'][0] - want).max(axis=-1) > 1e-4
    assert 0 < dropped.sum() <= N


def test_dropless_equals_fixed_capacity_where_nothing_overflows():
    rng = np.random.default_rng(2)
    xs = rng.normal(size=(N, D)).astype('float32')
    got = {}
    for name, cf in (('dropless', None), ('capacity', float(E))):
        main, startup, _, out, aux, _ = build_moe(2, cf, True)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)          # same seed, same names: same weights
            got[name] = exe.run(main, feed={'x': xs}, fetch_list=[out, aux])
    np.testing.assert_allclose(got['dropless'][0], got['capacity'][0],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got['dropless'][1], got['capacity'][1],
                               rtol=1e-6)


def _grads_of(build, feed, wrt):
    """Runs a one-op Program forward and backward; returns (out, grads)."""
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out = build()
        loss = layers.reduce_sum(layers.elementwise_mul(
            out, layers.data(name='w', shape=list(out.shape),
                             dtype='float32', append_batch_size=False)))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res = exe.run(main, feed=feed,
                      fetch_list=[out] + [grads[n] for n in wrt])
    return res[0], res[1:]


def test_rms_norm_forward_and_gradient():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 6, 32)).astype('float32')
    w = rng.normal(size=(4, 6, 32)).astype('float32')
    scale = rng.normal(size=32).astype('float32')

    def build():
        return layers.rms_norm(_input('x', x), epsilon=1e-5, param_attr=fluid.ParamAttr(
            name='s', initializer=fluid.initializer.NumpyArrayInitializer(
                scale)))

    def formula(x, s):
        return s * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-5)

    got, (gx, gs) = _grads_of(build, {'w': w}, ['x', 's'])
    want = formula(x, scale)
    wx, ws = jax.grad(lambda a, s: jnp.sum(formula(a, s) * w),
                      argnums=(0, 1))(x, scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx, wx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gs, ws, rtol=1e-4, atol=1e-5)


def test_rotary_embedding_forward_and_gradient():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 3, 10, 8)).astype('float32')
    w = rng.normal(size=(2, 3, 10, 8)).astype('float32')

    def build():
        return layers.rotary_embedding(_input('x', x), base=100.0)

    def formula(x):
        i = jnp.arange(4, dtype=jnp.float32)
        angle = jnp.arange(10.)[:, None] * 100.0 ** (-2 * i / 8)[None, :]
        a, b = x[..., :4], x[..., 4:]                # pairs (i, i + 4)
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    got, (gx,) = _grads_of(build, {'w': w}, ['x'])
    np.testing.assert_allclose(got, formula(x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx, jax.grad(
        lambda a: jnp.sum(formula(a) * w))(x), rtol=1e-5, atol=1e-6)
    # position 0 is not turned; a rotation keeps each pair's length
    np.testing.assert_allclose(got[..., 0, :], x[..., 0, :], rtol=1e-6)
    np.testing.assert_allclose(got[..., :4] ** 2 + got[..., 4:] ** 2,
                               x[..., :4] ** 2 + x[..., 4:] ** 2, rtol=1e-4)


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/olmoe.py through the Executor against
    chipbench/references/olmoe.py in float32: the loss and the gradient of
    EVERY parameter (two layers, so the mean over layers of the router loss
    is held too)."""
    names, got = check_all(toy_cell('olmoe_s4096'),
                           {'loss': 1e-5, 'grad': 2e-4})
    assert len(names) == 1 + 2 * 12 + 2 and set(got['grad_rel']) == set(names)
    assert got['passed'], got
    assert max(got['grad_rel'].values()) < 2e-4


def test_a_second_comparison_compiles_no_program_and_reads_the_same():
    """tests/decoder_toy.py: the Program's side of one (cell, entry, seed)
    is lowered, compiled and run once; `run_check` gives the next verdict
    on the recorded fetches, and a reference with a rule moved still fails
    it."""
    cell = toy_cell('olmoe_s4096')
    _, first = check_all(cell, {'loss': 1e-5, 'grad': 2e-4})
    misses = obs.counter('executor.cache.misses').value
    names, again = check_all(cell, {'loss': 1e-5, 'grad': 2e-4})
    assert obs.counter('executor.cache.misses').value == misses
    assert again['passed'] and again['grad_rel'] == first['grad_rel']
    assert again['loss'] == first['loss']
    moved = reference_module('olmoe')
    moved.rotary = lambda x, theta: x
    _, got = check_all(dict(cell, reference=moved),
                       {'loss': 1e-5, 'grad': 2e-4})
    assert obs.counter('executor.cache.misses').value == misses
    assert not got['passed'] and max(got['grad_rel'].values()) > 1e-2


def test_amp_leaves_the_router_in_float32_and_counts_what_it_lowers():
    """Under AMP the experts multiply bf16 operands, the router float32
    ones at full precision: the rule casts the expert stacks and the rows it
    gives them, and neither X nor GateW. The trace-time counters name the
    path."""
    rng = np.random.default_rng(6)
    xs = rng.normal(size=(N, D)).astype('float32')
    before = obs.counter('moe.lowered', path='grouped').value
    main, startup, _, out, _, count = build_moe(2, None, False, amp=True)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, cnt = exe.run(main, feed={'x': xs}, fetch_list=[out, count])
        hlo = exe.lowered_hlo(main, {'x': xs}, [out])
        wr, w1, w3, w2 = _weights(fluid.global_scope())
    want, chosen, gap = plain_moe(xs, wr, w1, w3, w2, 2, False)
    # float32 routing: the choice is the float64 reference's to the last
    # token although the experts ran in bf16
    assert gap.min() > 1e-5
    np.testing.assert_array_equal(cnt, np.bincount(chosen.ravel(),
                                                   minlength=E))
    assert np.abs(got - want).max() < 2.0 ** -6 * np.abs(want).max()
    assert np.abs(got - want).max() > 0            # bf16 did run
    dots = [l for l in hlo.splitlines() if 'dot_general' in l]
    route = [l for l in dots if 'HIGHEST' in l]
    assert len(route) == 1 and 'bf16' not in route[0] \
        and '48x16xf32' in route[0], route
    assert len([l for l in dots if 'xbf16>, ' in l]) == 3, dots
    # every trace of the rule counts, build-time shape inference (a
    # stand-in batch) included
    assert obs.counter('moe.lowered', path='grouped').value > before


def test_norm_and_rotary_count_their_lowerings():
    a, b = (obs.counter(n).value for n in ('rms_norm.lowered',
                                           'rotary.lowered'))
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[2, 4, 8], dtype='float32')
        out = layers.rotary_embedding(layers.rms_norm(x))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((1, 2, 4, 8), 'float32')},
                fetch_list=[out])
    assert obs.counter('rms_norm.lowered').value > a
    assert obs.counter('rotary.lowered').value > b


def test_dropless_on_a_mesh_that_shards_the_experts_is_refused():
    """dp=4 divides 8 experts: moe_apply would drop, so the rule refuses by
    type and names the four-chip twin; the static collective analysis knows
    a dropless layer moves nothing."""
    from paddle_tpu.fluid.analysis import collectives
    from paddle_tpu.parallel.moe import DroplessOnMeshError
    main, startup, _, out, _, _ = build_moe(2, None, False)
    op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
    assert collectives.op_collectives(op, main, {'dp': 4}) == []
    fixed = build_moe(2, 2.0, False)[0]
    op = [o for o in fixed.global_block().ops if o.type == 'moe_mlp'][0]
    assert collectives.op_collectives(op, fixed, {'dp': 4}) == [
        ('all_to_all', 'dp'), ('all_to_all', 'dp')]
    main.set_mesh({'dp': 4})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(DroplessOnMeshError, match='four-chip'):
            exe.run(main, feed={'x': np.ones((N, D), 'float32')},
                    fetch_list=[out])


def test_gated_experts_take_no_biases():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[D], dtype='float32')
        with pytest.raises(ValueError, match='bias_attr=False'):
            layers.moe_mlp(x, num_experts=E, hidden_size=H, gated=True)


def test_grouped_matmul_kernel_matches_ragged_dot_interpreted():
    """The Pallas grouped matmul (the TPU's lowering of the dropless rule)
    in the interpreter against lax.ragged_dot: forward and both gradients,
    an empty group and groups that straddle tiles."""
    from paddle_tpu.ops.kernels.grouped_matmul import grouped_matmul, usable
    rng = np.random.default_rng(7)
    m, k, n = 256, 128, 256
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    sizes = jnp.asarray([10, 0, 200, 46], jnp.int32)
    tiles = ((128, 128, 128),) * 3

    def kernel(a, b):
        return jnp.sum(jnp.sin(grouped_matmul(a, b, sizes, True, tiles)))

    def plain(a, b):
        return jnp.sum(jnp.sin(jax.lax.ragged_dot(a, b, sizes)))

    got = jax.value_and_grad(kernel, (0, 1))(lhs, rhs)
    want = jax.value_and_grad(plain, (0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert usable(65536) and not usable(100)


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at the
    top level (the driver compares those) and in `model` (the builder reads
    that); only the depth is cut."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'olmoe_1b_7b.json')) as f:
        held = json.load(f)
    source = {'attention_bias': False, 'clip_qkv': None, 'hidden_act': 'silu',
              'hidden_size': 2048, 'intermediate_size': 1024,
              'max_position_embeddings': 4096, 'model_type': 'olmoe',
              'norm_topk_prob': False, 'num_attention_heads': 16,
              'num_experts': 64, 'num_experts_per_tok': 8,
              'num_hidden_layers': 16, 'num_key_value_heads': 16,
              'rms_norm_eps': 1e-05, 'rope_scaling': None,
              'rope_theta': 10000, 'tie_word_embeddings': False,
              'vocab_size': 50304}
    for key, value in source.items():
        want = 1 if key == 'num_hidden_layers' else value
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == ['num_hidden_layers']
    assert held['reduced_from'] == {'num_hidden_layers': 16}
    assert set(held['model']) - set(source) == {'router_aux_loss_coef',
                                                'initializer_range'}
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('expert_width', 'router_aux_loss_coef', 'router_z_loss',
                'optimizer', 'document_mask', 'learning_rate_schedule'):
        assert held['assumed'][key]
    assert held['deployment']
