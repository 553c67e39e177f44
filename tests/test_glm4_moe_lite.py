"""GLM-4.7-Flash on the normal path (ISSUE 32): the sigmoid router with its
selection bias against ten lines of numpy (and the softmax router
bit-equal to what it was), latent attention against the plain reference's
mixer, an expert layer's eight SHARES adding up to the uncut layer, the
multi-token prediction module's shared embedding and head, the bias
update after the optimizer, recompute regions, name scopes in op_name, the
whole toy model against the benchmark's plain reference, and the
configuration's file. Small sizes, on the CPU."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name
from paddle_tpu.parallel.moe import router_topk
from util import held_way

import decoder_toy
from decoder_toy import REPO, check_all

CELL = 'glm47flash_s8192'

reference_module = functools.partial(decoder_toy.reference_module,
                                     'glm4_moe_lite')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


# ---------------------------------------------------------------- the router

def router_topk_before(logits, top_k, norm_topk_prob=True):
    """paddle_tpu/parallel/moe.py router_topk as PR 31 left it."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx = lax.top_k(logits, top_k)
    gate = jnp.take_along_axis(probs, idx, axis=-1)
    if top_k > 1 and norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return idx.T, gate.T


@pytest.mark.parametrize('top_k,norm', [(1, True), (8, False), (8, True),
                                        (10, True)])
@pytest.mark.parametrize('experts', [64, 512])
def test_softmax_router_is_bit_equal_to_what_it_was(experts, top_k, norm):
    logits = jnp.asarray(np.random.default_rng(experts + top_k).normal(
        size=(777, experts)).astype('float32') * 0.9)
    for fn in (lambda f: f, jax.jit):
        got = fn(lambda x: router_topk(x, top_k, norm))(logits)
        want = fn(lambda x: router_topk_before(x, top_k, norm))(logits)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def numpy_sigmoid_router(logits, bias, top_k, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    chosen = np.argsort(-(s + bias), axis=-1, kind='stable')[:, :top_k]
    picked = np.take_along_axis(s, chosen, axis=-1)
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)


@pytest.mark.parametrize('biased', [False, True])
def test_sigmoid_router_is_ten_lines_of_numpy(biased):
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(300, 64)).astype('float32') * 0.9
    bias = (rng.normal(size=64) * 0.3 if biased else np.zeros(64)
            ).astype('float32')
    expert, gate = router_topk(jnp.asarray(logits), 4, True, 'sigmoid',
                               jnp.asarray(bias), 1.8)
    want_e, want_g = numpy_sigmoid_router(logits, bias, 4, 1.8)
    np.testing.assert_array_equal(np.asarray(expert).T, want_e)
    np.testing.assert_allclose(np.asarray(gate).T, want_g, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(gate).sum(0), 1.8, rtol=1e-5)


def test_the_bias_moves_the_choice_and_never_the_gates():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(200, 16)).astype('float32'))
    bias = np.zeros(16, 'float32')
    bias[3] = 5.0                       # expert 3 is always chosen now
    plain_e, plain_g = router_topk(logits, 2, False, 'sigmoid')
    moved_e, moved_g = router_topk(logits, 2, False, 'sigmoid',
                                   jnp.asarray(bias))
    assert not (np.asarray(plain_e) == 3).any(0).all()
    assert (np.asarray(moved_e) == 3).any(0).all()
    # a gate is the chosen expert's own sigmoid, whatever the bias said
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(
        np.asarray(moved_g).T,
        np.take_along_axis(s, np.asarray(moved_e).T, axis=-1), rtol=1e-6)
    # and no gradient reaches it
    g = jax.grad(lambda b: router_topk(logits, 2, True, 'sigmoid', b,
                                       1.8)[1].sum())(jnp.asarray(bias))
    assert not np.asarray(g).any()
    # all scores zero: the source's 1e-20 keeps the division finite
    flat = router_topk(jnp.full((4, 8), -1e4, jnp.float32), 2, True,
                       'sigmoid')[1]
    assert np.isfinite(np.asarray(flat)).all()


def test_the_capacity_layer_refuses_what_only_the_dropless_one_has():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[16], dtype='float32')
        for kw in ({'scoring': 'sigmoid'}, {'selection_bias': True},
                   {'gate_scale': 1.8}):
            with pytest.raises(ValueError, match='dropless'):
                layers.moe_mlp(x, num_experts=8, hidden_size=8, **kw)
        with pytest.raises(ValueError, match='scoring'):
            layers.moe_mlp(x, num_experts=8, hidden_size=8,
                           capacity_factor=None, scoring='tanh')
        # a bias or a gate scale under a softmax router: no model has it
        for kw in ({'selection_bias': True}, {'gate_scale': 1.8}):
            with pytest.raises(ValueError, match='sigmoid'):
                layers.moe_mlp(x, num_experts=8, hidden_size=8,
                               capacity_factor=None, **kw)
    logits = jnp.zeros((4, 8), jnp.float32)
    for kw in ({'bias': jnp.zeros(8)}, {'gate_scale': 1.8}):
        with pytest.raises(NotImplementedError, match='softmax'):
            router_topk(logits, 2, **kw)


# ------------------------------------------------------------------ the share

N, D, E, H, K, HELD = 96, 16, 64, 12, 4, 8


def build_share(held):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, count, bias = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held,
            scoring='sigmoid', selection_bias=True, gate_scale=1.8)
    return main, startup, out, count, bias


def run_share(held, xs, weights):
    """weights: router, gate stack, up stack, down stack, bias."""
    main, startup, out, count, _ = build_share(held)
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


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST of the model-configs guide, section 4: the routed
    parts of all 8 shares of one layer (first_expert_held 0, 8, .. 56),
    with the shared expert counted once, add up to what the UNCUT plain
    reference gives for the whole expert block; the counts are the whole
    layer's in every share; the bias is not zero here."""
    reference = reference_module()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N, D)).astype('float32')
    weights = [rng.normal(size=(D, E)).astype('float32'),
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, D, H)).astype('float32') * 0.3,
               rng.normal(size=(E, H, D)).astype('float32') * 0.3,
               rng.normal(size=E).astype('float32') * 0.2]
    whole, count = run_share(None, xs, weights)
    assert count.sum() == N * K
    parts = []
    for first in range(0, E, HELD):
        part, count_s = run_share((first, HELD), xs, weights)
        np.testing.assert_array_equal(count_s, count)
        assert np.abs(part).max() > 0
        parts.append(part)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    shared = [rng.normal(size=s).astype('float32') * 0.3
              for s in ((D, H), (D, H), (H, D))]
    model = {'num_experts_per_tok': K, 'norm_topk_prob': True,
             'routed_scaling_factor': 1.8}
    w = {'router': weights[0], 'experts_in': weights[1:3],
         'experts_down': weights[3], 'bias': weights[4], 'shared': shared}
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.experts(w, jnp.asarray(xs)[None],
                                            model))[0]
        once = np.asarray((jax.nn.silu(xs @ shared[0]) * (xs @ shared[1]))
                          @ shared[2])
        # a cut reference gives its share's partial sum, too
        cut = dict(w, experts_in=[s[8:16] for s in weights[1:3]],
                   experts_down=weights[3][8:16])
        part1 = np.asarray(reference.experts(
            cut, jnp.asarray(xs)[None], dict(model, first_expert_held=8)))[0]
    np.testing.assert_allclose(sum(parts) + once, want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(parts[1] + once, part1, rtol=2e-4, atol=2e-5)
    # wrong rules are far away: no 1.8, gates renormalised over the held
    assert np.abs(sum(parts) / 1.8 + once - want).max() > 0.05


@pytest.mark.parametrize('way', ['compact', 'blocks', 'overflow'])
def test_an_eighth_held_under_the_sigmoid_router_on_either_path(
        way, monkeypatch):
    """8 of 64 held, top 4 over 192 tokens under the sigmoid router with
    its bias and 1.8: 768 rows, 96 expected, a layout of 256 (half the
    rows in whole tiles), chosen on the device. The router as drawn stays
    under it and takes the compact path (`compact`: the other gives NaN);
    the same rows through `_held_blocks` (`blocks`: the compact path is
    made to call it); a bias that gives the held experts every choice,
    768 rows, overflows the layout (`overflow`: the compact path gives
    NaN). Each is the cut plain reference's routed part in value and in
    every gradient: the input's, the router's, the three stacks'; none
    reaches the bias."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    tokens = 2 * N
    assert moe_ops._held_layout(tokens * K, HELD, E) == 256
    rng = np.random.default_rng(5)
    xs, w = (rng.normal(size=(tokens, D)).astype('float32')
             for _ in range(2))
    weights = [rng.normal(size=(D, E)).astype('float32'),
               rng.normal(size=(HELD, D, H)).astype('float32') * 0.3,
               rng.normal(size=(HELD, D, H)).astype('float32') * 0.3,
               rng.normal(size=(HELD, H, D)).astype('float32') * 0.3,
               rng.normal(size=E).astype('float32') * 0.2]
    if way == 'overflow':
        weights[4][8:8 + K] += 4.0
    held_way(monkeypatch, way)
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out, count, _ = layers.moe_mlp(
            layers.create_parameter([tokens, D], 'float32', name='px'),
            num_experts=E, hidden_size=H, act='swish', gated=True, top_k=K,
            norm_topk_prob=True, capacity_factor=None, bias_attr=False,
            return_expert_count=True, experts_held=(8, HELD),
            scoring='sigmoid', selection_bias=True, gate_scale=1.8)
        loss = layers.reduce_sum(layers.elementwise_mul(out, layers.data(
            name='w', shape=[D], dtype='float32')))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    names = ['px'] + ['moe_mlp_0.w_%d' % i for i in range(4)]
    assert sorted(grads) == sorted(names)         # none for the bias
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        for name, value in zip(names + ['moe_mlp_0.w_4'], [xs] + weights):
            scope.find_var(name).get_tensor().set(value, place)
        got = exe.run(main, feed={'w': w},
                      fetch_list=[out, count] + [grads[n] for n in names])
    live = got[1][8:8 + HELD].sum()
    assert live == tokens * K if way == 'overflow' else 0 < live <= 256

    reference = reference_module()
    model = {'num_experts_per_tok': K, 'norm_topk_prob': True,
             'routed_scaling_factor': 1.8, 'first_expert_held': 8}
    none = [np.zeros(s, 'float32') for s in ((D, H), (D, H), (H, D))]

    def part(x, router, w_gate, w_up, w_down):
        y = reference.experts(
            {'router': router, 'experts_in': [w_gate, w_up],
             'experts_down': w_down, 'bias': weights[4], 'shared': none},
            x[None], model)[0]
        return jnp.sum(y * w), y

    with jax.default_matmul_precision('highest'):
        want, y = jax.grad(part, argnums=range(5), has_aux=True)(
            jnp.asarray(xs), *weights[:4])
    np.testing.assert_allclose(got[0], y, rtol=2e-4, atol=2e-5)
    assert np.abs(got[0]).max() > 0.1
    for name, a, b in zip(names, got[2:], want):
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * np.abs(b).max(),
                                   err_msg=name)


# ---------------------------------------------------------- latent attention

MLA = dict(hidden_size=32, num_attention_heads=4, q_lora_rank=12,
           kv_lora_rank=8, qk_nope_head_dim=12, qk_rope_head_dim=4,
           v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-5)
MLA_NAMES = ('q_a', 'q_norm', 'q_b', 'kv_a', 'kv_norm', 'kv_b', 'out')


def build_mixer(seq, amp=False):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 5
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[seq, MLA['hidden_size']],
                        dtype='float32')
        x.stop_gradient = False
        with fluid.name_scope('latent_attention'):
            out = layers.latent_attention(
                x, MLA['hidden_size'], MLA['num_attention_heads'],
                MLA['q_lora_rank'], MLA['kv_lora_rank'],
                MLA['qk_nope_head_dim'], MLA['qk_rope_head_dim'],
                MLA['v_head_dim'], rope_theta=MLA['rope_theta'],
                param_attr=fluid.ParamAttr(
                    initializer=fluid.initializer.Normal(0., 0.3)))
        w = layers.data(name='w', shape=[seq, MLA['hidden_size']],
                        dtype='float32')
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
        if amp:
            fluid.amp.decorate_program(main)
    return main, startup, out, grads


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
def test_latent_attention_is_the_references_mixer(amp):
    reference = reference_module()
    seq = 24
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(2, seq, MLA['hidden_size'])).astype('float32')
    ws = rng.normal(size=xs.shape).astype('float32')
    main, startup, out, grads = build_mixer(seq, amp)
    names = sorted(grads, key=lambda n: [p.name for p in
                                         main.all_parameters()].index(n))
    assert len(names) == 7
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={'x': xs, 'w': ws},
                      fetch_list=[out] + [grads[n] for n in names])
        weights = {k: np.asarray(fluid.global_scope().find_var(n)
                                 .get_tensor())
                   for k, n in zip(MLA_NAMES, names)}
    weights['norm_in'] = np.ones(MLA['hidden_size'], 'float32')

    def mixer(w):
        # the layer takes its input normed; a norm of weight 1 over rows
        # of unit mean square is the identity
        return reference.latent_attention(w, unit, MLA)

    unit = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-5)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        for k, n in zip(MLA_NAMES, names):
            scope.find_var(n).get_tensor().set(weights[k], place)
        got = exe.run(main, feed={'x': unit, 'w': ws},
                      fetch_list=[out] + [grads[n] for n in names])
    with jax.default_matmul_precision('highest'):
        want, pull = jax.vjp(mixer, {k: jnp.asarray(v)
                                     for k, v in weights.items()})
        want_grads, = pull(jnp.asarray(ws))
    tol = 0.05 if amp else 2e-5

    def rel(a, b):
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    assert rel(got[0], np.asarray(want)) < tol
    for k, g in zip(MLA_NAMES, got[1:]):
        assert rel(g, np.asarray(want_grads[k])) < tol, k


def test_latent_attention_takes_values_of_their_own_width():
    """Keys of 12 + 4 beside values of 8 build since PR 55 (the attention
    kernels take v and the output at their own width): the op's V is
    [B, H, T, 8], Wo [4 x 8, size]; without a query latent one matrix
    makes the queries."""
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[8, 32], dtype='float32')
        out = layers.latent_attention(x, 32, 4, 12, 8, 12, 4, 8)
        block = framework.default_main_program().global_block()
        flash = [op for op in block.ops if op.type == 'flash_attention'][0]
        assert tuple(block.var(flash.input('V')[0]).shape)[1:] == (4, 8, 8)
        assert tuple(block.var(flash.input('K')[0]).shape)[1:] == (4, 8, 16)
        assert tuple(out.shape)[1:] == (8, 32)
        n = len(framework.default_main_program().all_parameters())
        layers.latent_attention(x, 32, 4, None, 8, 12, 4, 8,
                                rope_interleave=True, head_gate=True)
        assert len(framework.default_main_program().all_parameters()) \
            == n + 6


# ------------------------------------------------------- regions and scopes

def _run_toy_program(cell, train, feed_seed=5, steps=1, strip=False,
                     feeds=None, optimized=False):
    """Builds the toy cell's Program, runs it `steps` times on the pool's
    batch (or once on each of `feeds`), returns (built, results, the
    executor's lowered text, the scope's state)."""
    from chipbench.harness import check
    config = dict(cell['config'], check={'grads': []}, amp='none')
    built = cell['builder'].build(config, cell['traffic'], train=train)
    if not train:
        names = [n for n in check.parameter_names(built['main'])
                 if built['main'].global_block().var(n).trainable]
        built = cell['builder'].build(
            dict(config, check={'grads': names}), cell['traffic'],
            train=False)
    if strip:
        for op in built['main'].global_block().ops:
            op.attrs.pop('recompute', None)
        built['main']._use_remat = False
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          config, feed_seed)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        fetch = [built['loss']] + [built['grads'][n]
                                   for n in sorted(built['grads'])]
        feeds = feeds or [pool[0]] * steps
        out = [exe.run(built['main'], feed=feed, fetch_list=fetch)
               for feed in feeds]
        text = exe.lowered_hlo(built['main'], feeds[0], fetch,
                               optimized=optimized)
        scope = fluid.global_scope()
        state = {v.name: np.asarray(scope.find_var(v.name).get_tensor())
                 for v in built['main'].list_vars()
                 if v.persistable and scope.find_var(v.name) is not None
                 and scope.find_var(v.name).get_tensor() is not None}
    return built, out, text, state


@functools.lru_cache(maxsize=None)
def _checked_toy_program():
    """The toy cell's check Program (every trainable parameter's gradient)
    on the pool's batch, run once for the tests that read it."""
    return _run_toy_program(_toy_cell(), train=False)


def test_recompute_regions_change_no_number_and_are_one_a_layer():
    """Six regions (five layers and the module), each a run of ops; the
    loss and every gradient are what the unmarked Program gives."""
    built, marked, text, _ = _checked_toy_program()
    _, plain, plain_text, _ = _run_toy_program(_toy_cell(), train=False,
                                               strip=True)
    ops = built['main'].global_block().ops
    marks = [op.attrs.get('recompute') for op in ops]
    runs = [m for i, m in enumerate(marks)
            if m is not None and (i == 0 or marks[i - 1] != m)]
    assert len(runs) == len(set(runs)) == 6
    assert built['main']._use_remat
    # six more barriers than the rules' own (the loss heads', the held
    # experts' blocks)
    barriers = [t.count('optimization_barrier') for t in (text, plain_text)]
    assert barriers[0] >= barriers[1] + 6
    for a, b in zip(marked[0], plain[0]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)
    # the main head is outside every region and is the last `mul` built
    muls = [op for op in ops if op.type == 'mul']
    assert muls[-1].attrs.get('recompute') is None
    assert muls[-1].input('Y') == ['glm_head']
    assert 'name_scope' not in muls[-1].attrs


def test_memory_optimize_without_a_region_recomputes_the_whole_forward():
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        loss = layers.mean(layers.fc(layers.fc(x, 8, act='relu'), 1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    with pytest.warns(DeprecationWarning):
        fluid.memory_optimize(main)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'x': np.ones((4, 8), 'float32')}
        compiled = exe.step_artifact(main, feed, [loss])
        ad = compiled.ad_idx
        assert list(compiled.regions) == [0]
        assert compiled.regions[0][0] == ad
        assert 'optimization_barrier' in exe.lowered_hlo(main, feed, [loss])


def test_name_scope_reaches_op_name_and_scope_of_reads_what_it_read():
    from chipbench.harness import scopes
    cell = _toy_cell()
    built, _, text, _ = _run_toy_program(cell, train=True, optimized=True)
    ops = built['main'].global_block().ops
    paths = {op.attrs.get('name_scope') for op in ops}
    assert paths == {None, 'latent_attention', 'mtp',
                     'mtp/latent_attention', 'router_bias'}
    flash = [op for op in ops if op.type == 'flash_attention']
    assert len(flash) == 6
    assert sum(op.attrs['name_scope'] == 'mtp/latent_attention'
               for op in flash) == 1
    names = scopes.instruction_scopes(text)
    assert names
    mixers = [n for n in names.values() if 'latent_attention' in n]
    assert mixers
    for op_name in names.values():
        scope = scopes.scope_of(op_name)
        if scope is None:
            continue
        # the innermost `<op>_<index>` is a Fluid op type still
        assert scope[0] not in ('latent_attention', 'mtp', 'router_bias')
    assert any(scopes.scope_of(n) and scopes.scope_of(n)[0] == 'mul'
               for n in mixers)


@pytest.mark.parametrize('prefix,written', [
    ('layer_3', ['layer_3_']), ('block.0', ['block_0_']),
    ('mtp 1', ['mtp_1_']), ('enc-dec', ['enc_dec']),
    ('a/b_2', ['a', 'b_2_']), ('plain', ['plain'])])
def test_any_name_scope_is_taken_and_its_label_is_no_op_scope(prefix,
                                                              written):
    """The reference's idiom (`layer_1`, `block.0`) builds and runs; the
    trace shows the prefix so that the innermost `<type>_<index>` of an
    op_name is still the op's."""
    from chipbench.harness import scopes
    from paddle_tpu.fluid import lowering
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        with fluid.name_scope(prefix):
            y = layers.fc(x, 4)
    assert all(op.attrs['name_scope'] == prefix
               for op in main.global_block().ops)
    assert [lowering.scope_label(n) for n in prefix.split('/')] == written
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'x': np.ones((2, 8), 'float32')}
        exe.run(main, feed=feed, fetch_list=[y])
        names = scopes.instruction_scopes(
            exe.lowered_hlo(main, feed, [y], optimized=True))
    under = [n for n in names.values() if '/%s/' % written[-1] in n]
    assert under
    assert {scopes.scope_of(n)[0] for n in under} <= {'mul', 'elementwise_add'}


# ------------------------------------------------------------------ the model

def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/glm4_moe_lite.py through the Executor against
    chipbench/references/glm4_moe_lite.py in float32: the loss and the
    gradient of EVERY trainable parameter (a dense layer, four expert
    layers holding experts 4..7 of 16, the module; embedding and head
    shared); and under bf16 AMP within a stated tolerance."""
    cell = _toy_cell()
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 5e-4})
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    # embedding; 9 + 3 dense; 4 x (9 + 4 + 3); module 3 + 16 + 1; head;
    # final norm (the five selection biases are no trainable parameter)
    assert len(names) == 1 + 12 + 4 * 16 + 20 + 1 + 1
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    assert amp['passed'], amp
    assert max(got['grad_rel'].values()) < 5e-4


def _reference_grads(cell, state, built, batch, untie):
    """loss and gradients of the plain reference on the scope's weights;
    `untie`: the module gets copies of the embedding and the head."""
    reference = reference_module()
    params, _ = cell['builder'].reference_params(
        dict(cell['config'], amp='none'), built['main'],
        lambda name: state[name])
    if untie:
        params['mtp.tok_emb'] = params['tok_emb'].copy()
        params['mtp.head'] = params['head'].copy()
    ids, labels = (jnp.asarray(batch[k], jnp.int32)
                   for k in ('input_ids', 'labels'))
    with jax.default_matmul_precision('highest'):
        return jax.value_and_grad(lambda p: reference.forward_loss(
            p, cell['config']['model'], ids, labels))(
            jax.tree_util.tree_map(jnp.asarray, params))


def test_shared_embedding_and_head_get_the_sum_of_both_uses():
    cell = _toy_cell()
    built, out, _, state = _checked_toy_program()
    grads = dict(zip(sorted(built['grads']), out[0][1:]))
    block = built['main'].global_block()
    uses = {n: sum(n in op.input_arg_names for op in block.ops)
            for n in ('glm_tok_emb', 'glm_head')}
    assert uses == {'glm_tok_emb': 2, 'glm_head': 2}
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          cell['config'], 5)
    _, untied = _reference_grads(cell, state, built, pool[0], untie=True)
    for shared, (main_use, module_use) in {
            'glm_tok_emb': ('tok_emb', 'mtp.tok_emb'),
            'glm_head': ('head', 'mtp.head')}.items():
        a, b = np.asarray(untied[main_use]), np.asarray(untied[module_use])
        assert np.linalg.norm(a) > 0 and np.linalg.norm(b) > 0
        np.testing.assert_allclose(grads[shared], a + b, rtol=2e-4,
                                   atol=1e-7)
        assert np.linalg.norm(grads[shared] - a) > 0.05 * np.linalg.norm(a)


def test_the_modules_last_position_does_not_reach_the_loss():
    """An id that stands at labels[T - 1] and nowhere else is read by the
    embedding once, as the INPUT of the module's last position (it is a
    target, too, of the main head and of the module's position T - 2:
    those reach the head, not the embedding). That position predicts
    nothing, so the id's row of the shared embedding gets a gradient of
    exactly zero; one position earlier it does not."""
    cell = _toy_cell()
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          cell['config'], 5)
    feed = {k: np.asarray(v).copy() for k, v in pool[0].items()}
    unused = min(set(range(cell['config']['model']['vocab_size']))
                 - set(feed['input_ids'].ravel()) - set(feed['labels'].ravel()))
    moved = {}
    for position in (-1, -2):
        moved[position] = dict(feed, labels=feed['labels'].copy())
        moved[position]['labels'][:, position] = unused
    # one Program, run on both feeds
    built, out, _, _ = _run_toy_program(cell, train=False,
                                        feeds=list(moved.values()))
    rows = {}
    for position, got in zip(moved, out):
        grads = dict(zip(sorted(built['grads']), got[1:]))
        rows[position] = np.asarray(grads['glm_tok_emb'])[unused]
    assert not rows[-1].any()
    assert np.abs(rows[-2]).max() > 0


def test_the_bias_update_is_the_sign_rule_and_no_optimizers_business():
    cell = _toy_cell()
    before = obs.counter('moe.bias_updates').value
    built, out, _, state = _run_toy_program(cell, train=True, steps=1)
    assert obs.counter('moe.bias_updates').value - before == 5
    main = built['main']
    block = main.global_block()
    biases = [v.name for v in main.list_vars()
              if isinstance(v, framework.Parameter) and not v.trainable]
    assert len(biases) == 5
    ad = [op for op in block.ops if op.type == 'autodiff'][0]
    assert not set(biases) & set(ad.attrs['param_names'])
    adam = [op for op in block.ops if op.type == 'adam']
    assert not set(biases) & {n for op in adam for n in op.input_arg_names}
    assert not [n for n in state if any(b in n for b in biases)
                and n not in biases]              # no moment, no power
    # after minimize: every bias op follows the last adam op
    last_adam = max(i for i, op in enumerate(block.ops) if op.type == 'adam')
    first_bias = min(i for i, op in enumerate(block.ops)
                     if op.attrs.get('name_scope') == 'router_bias')
    assert first_bias > last_adam
    # one step from zero: +-0.001 by the sign of mean(c) - c_e
    counts = [op.output('ExpertCount')[0] for op in block.ops
              if op.type == 'moe_mlp']
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        pool, _ = cell['generator'].make_pool(
            dict(cell['traffic'], pool=1), cell['config'], 5)
        got = exe.run(main, feed=pool[0], fetch_list=counts)
        scope = fluid.global_scope()
        for name, c in zip(biases, got):
            b = np.asarray(scope.find_var(name).get_tensor())
            c = np.asarray(c, np.float64)
            assert c.shape == (16,) and c.sum() == 2 * 80 * 3
            np.testing.assert_allclose(
                b, 0.001 * np.sign(c.mean() - c), atol=1e-9)
    assert obs.counter('moe.lowered', path='grouped', held='4of16',
                       dispatch='index', scoring='sigmoid').value > 0


def test_small_preset_trains_and_shares_its_embedding_and_head():
    from paddle_tpu.models import glm4_moe_lite
    before = obs.counter('model.shared_param_uses').value
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = glm4_moe_lite.get_model(
            experts_held=(4, 4))
    assert obs.counter('model.shared_param_uses').value - before == 2
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
        assert exe.cache_stats['misses'] <= 2     # start-up and the step
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert out[1].shape == (16,) and out[1].sum() == 2 * 32 * 2
    types = [op.type for op in main.global_block().ops]
    assert types.count('flash_attention') == 4     # three layers, the module
    assert types.count('moe_mlp') == 3
    assert types.count('softmax_with_cross_entropy') == 2
    assert types.count('lookup_table') == 2


# ------------------------------------------------- the configuration's file

def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at the
    top level (the driver compares those) and in `model` (the builder reads
    that); only the depth, the experts held and the vocabulary are cut."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'glm_4_7_flash.json')) as f:
        held = json.load(f)
    source = {"attention_bias": False, "hidden_act": "silu",
              "hidden_size": 2048, "intermediate_size": 10240,
              "max_position_embeddings": 202752,
              "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
              "topk_method": "noaux_tc", "norm_topk_prob": True,
              "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
              "n_routed_experts": 64, "n_shared_experts": 1,
              "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
              "first_k_dense_replace": 1, "num_hidden_layers": 47,
              "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
              "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
              "rope_scaling": None, "rope_theta": 1000000,
              "tie_word_embeddings": False, "q_lora_rank": 768,
              "kv_lora_rank": 512, "qk_nope_head_dim": 192,
              "qk_rope_head_dim": 64, "v_head_dim": 256,
              "vocab_size": 154880}
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'GLM-4.7-Flash':
                    assert row['config'] == source
                    assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 5, 'n_routed_experts': 8,
           'vocab_size': 19360}
    for key, value in source.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: source[k] for k in cut}
    assert set(held['model']) - set(source) == {
        'mtp_loss_weight', 'bias_update_speed', 'initializer_range',
        'first_expert_held'}
    # the floors of the guide: four expert layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert held['num_hidden_layers'] - held['first_k_dense_replace'] >= 4
    assert held['n_routed_experts'] >= 8
    assert held['vocab_size'] * 8 >= source['vocab_size']
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('mtp_layout', 'mtp_input', 'mtp_order', 'mtp_loss_weight',
                'bias_update_speed', 'bias_counts', 'rotary_pairing',
                'initializers', 'optimizer', 'document_mask',
                'top_level_keys'):
        assert held['assumed'][key], key
    assert '8 chips' in held['deployment']
    assert set(held['checks']['float32']['grads']) >= {
        'glm_tok_emb', 'glm_head', 'fc_40.w_0', 'moe_mlp_3.w_0'}


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 8192 (ISSUE 32): 1.21 GFLOP, of which the
    six mixers 63 % (projections 22 %, scores 42 %), the two heads 13 %,
    the five expert blocks 12 %, the dense feed-forward 10 %; 29.7 TFLOP a
    step; 706.5 M parameters."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    f = {k: v / tokens / 1e6 for k, v in flops.forward_flops(
        config, traffic['batch'], traffic['seq']).items()}
    total = sum(f.values())
    assert total == pytest.approx(1210, rel=0.01)
    assert flops.mixer_weights(config['model']) == pytest.approx(21.76e6,
                                                                 rel=1e-3)
    assert f['mla_projections'] / total == pytest.approx(0.22, abs=0.01)
    assert f['attention'] / total == pytest.approx(0.42, abs=0.01)
    assert f['head'] / total == pytest.approx(0.13, abs=0.01)
    blocks = f['experts'] + f['router'] + f['shared_expert']
    assert blocks / total == pytest.approx(0.12, abs=0.01)
    assert f['experts'] / blocks == pytest.approx(1 / 3, abs=0.02)
    assert f['dense'] / total == pytest.approx(0.10, abs=0.01)
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(29.7e12, rel=0.01)
    costs = dict(flops.kernel_cost(config, traffic, 1),
                 experts=flops.expert_cost(config, traffic, 1),
                 mla=flops.latent_attention_cost(config, traffic, 1))
    for name, (n_flops, nbytes) in costs.items():
        assert 0 < n_flops < step and nbytes > 0, name
    assert flops.held_rows(config, 1, 8192) == 8192 * 4 / 8
    # the parameters of the deployment's table
    m = config['model']
    mixer = flops.mixer_weights(m) + 2048 + 768 + 512 + 2048
    expert = 3 * 2048 * 1536
    layer = mixer + 2048 * 64 + 64 + 9 * expert
    n = (2 * 19360 * 2048 + mixer + 3 * 2048 * 10240 + 5 * layer
         + 2 * 2048 * 2048 + 3 * 2048 + 2048)
    assert n == pytest.approx(706.5e6, rel=2e-3)


def test_new_readers_read_their_scopes_or_nothing():
    """`mla_ms`, `mla_roofline` and `mtp_ms` on a hand-made reduction and
    a hand-made HLO: a scope counts where its op_name has the name as an
    element of its path, forward or backward."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(latent_attention)/jvp(mul_4)/dot_general"}',
        '  %custom-call.2 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/transpose(jvp(mtp))/transpose(jvp(latent_attention))/'
        'transpose(jvp(flash_attention_9))/pallas_call"}',
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mtp)/checkpoint/jvp(mul_12)/dot_general"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mul_20)/dot_general"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mtp_like)/jvp(mul_21)/dot_general"}',
    ])
    red = {'steps': 5, 'fluid_scope_s': {
        'mul_4': 0.10, 'flash_attention_9': 0.40, 'mul_12': 0.05,
        'mul_20': 1.0, 'mul_21': 1.0}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('mla_ms')(reading) == pytest.approx(100.0)
    assert catalog.load_reader('mtp_ms')(reading) == pytest.approx(90.0)
    share = catalog.load_reader('mla_roofline')(reading)
    least, bound = peaks.roofline(cell['flops'].latent_attention_cost(
        cell['config'], cell['traffic'], 1), reading['peaks'])
    assert share == pytest.approx(100 * least / 0.1) and 0 < share < 100
    assert bound == 'flops'
    # a program that names no such scope (the parent's), or no trace:
    # nothing, and no error
    bare = hlo.replace('latent_attention', 'x').replace('mtp', 'y')
    for other in (dict(reading, hlo=bare), dict(reading, trace=None),
                  dict(reading, hlo=None)):
        for name in ('mla_ms', 'mla_roofline', 'mtp_ms'):
            assert catalog.load_reader(name)(other) is None
    olmoe = catalog.load_cell('olmoe_s4096')
    assert catalog.load_reader('mla_roofline')(
        dict(reading, cell=olmoe)) is None
