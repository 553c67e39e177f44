"""Nemotron-H on the normal path (ISSUE 40): `ssd_scan` against the
token-by-token recurrence of the benchmark's plain reference, forward and
every gradient; the gated norm's Mamba-2 mode against its composition;
squared-ReLU experts of two matrices, whole and as a share, on both held
paths; the 16 shares of an expert part adding up to the uncut reference's;
the toy model against the plain reference on every gradient (and a moved
rule FAILING the comparison); name scopes, regions, counters, the
configuration's file, its FLOPs and its readers. Small sizes, on the CPU."""
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
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from util import held_way

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'nemotron3nano_s8192'


reference_module = functools.partial(decoder_toy.reference_module,
                                     'nemotron_h')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


# ------------------------------------------------------------------ the scan

def _scan_inputs(b, t, h, p, g, n, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (normal(b, t, h, p), jax.nn.softplus(normal(b, t, h) - 1.0),
            -jnp.exp(normal(h)), normal(b, t, g, n), normal(b, t, g, n),
            normal(h))


def _recurrence(x, dt, a, b, c, d):
    """The reference's token-by-token recurrence on the op's arguments."""
    rep = x.shape[2] // b.shape[2]
    return reference_module().selective_scan(
        x, dt, a, jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2), d)


# (batch, tokens, heads, head width, groups, state, chunk)
_SCANS = {
    'ragged_two_rows': (2, 37, 4, 8, 2, 16, 16),
    'one_group': (1, 48, 4, 8, 1, 8, 16),
    'a_head_a_group': (2, 20, 2, 4, 2, 8, 8),
    'shorter_than_a_chunk': (1, 5, 2, 8, 1, 8, 128),
}


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
@pytest.mark.parametrize('case', sorted(_SCANS))
def test_ssd_scan_is_the_recurrence(case, amp):
    """The chunked op against the recurrence S_t = exp(dt_t A) S_(t-1) +
    dt_t x_t B_t^T, y_t = S_t C_t + D x_t walked token by token: the
    output and the gradient of all six inputs, for T no multiple of the
    chunk, G < H, two rows; with bf16 operands (what AMP hands the rule)
    within bf16's rounding, the output still float32."""
    *shape, chunk = _SCANS[case]
    args = _scan_inputs(*shape)
    w = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape),
                    jnp.float32)

    def cast(v):
        x, dt, a, b, c, d = v
        return tuple(t.astype(jnp.bfloat16) for t in (x, b, c)) \
            if amp else (x, b, c)

    def op(*v):
        x, b, c = cast(v)
        y = la.ssd_scan(x, v[1], v[2], b, c, v[5], chunk_size=chunk)
        return jnp.sum(y * w), y

    def plain(*v):
        y = _recurrence(*v)
        return jnp.sum(y * w), y

    # one compile a function, not an op-by-op walk
    (_, y), got = jax.jit(jax.value_and_grad(
        op, argnums=range(6), has_aux=True))(*args)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        plain, argnums=range(6), has_aux=True))(*args)
    assert y.dtype == jnp.float32 and y.shape == args[0].shape
    tol = 2.0 ** -6 if amp else 2e-5
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y).max()) <= tol * scale
    if amp:
        assert float(jnp.abs(y - want_y).max()) > 0        # bf16 did run
    for name, a, b in zip(('x', 'dt', 'a', 'b', 'c', 'd'), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel <= (2.0 ** -5 if amp else 2e-5), (name, rel)


def test_ssd_scan_without_the_skip_and_its_state_in_float32():
    """No `d`: the output is the state's read alone. The state a chunk
    hands the next is float32 whatever the operands: the lowered scan's
    carry holds no bf16."""
    x, dt, a, b, c, d = _scan_inputs(1, 40, 4, 8, 2, 16)
    with_d = la.ssd_scan(x, dt, a, b, c, d, chunk_size=8)
    bare = la.ssd_scan(x, dt, a, b, c, None, chunk_size=8)
    np.testing.assert_allclose(bare, with_d - d[:, None] * x, rtol=1e-5,
                               atol=1e-5)
    text = jax.jit(lambda *v: la.ssd_scan(*v, chunk_size=8)).lower(
        x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
        c.astype(jnp.bfloat16), d).as_text()
    loops = [l for l in text.splitlines() if 'stablehlo.while' in l]
    assert len(loops) == 1 and 'bf16' not in loops[0], loops
    assert 'xf32>' in loops[0]


def _build_scan(amp, t=24, h=4, p=8, g=2, n=8, chunk=8):
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.create_parameter([2, t, h, p], 'float32', name='px')
        bc = layers.create_parameter([2, t, 2, g, n], 'float32', name='pbc')
        dt = layers.softplus(layers.create_parameter([2, t, h], 'float32',
                                                     name='pdt'))
        a = layers.scale(layers.exp(layers.create_parameter(
            [h], 'float32', name='pa')), scale=-1.0)
        d = layers.create_parameter([h], 'float32', name='pd')
        b, c = (layers.reshape(v, shape=[0, 0, g, n])
                for v in layers.split(bc, 2, dim=2))
        y = layers.ssd_scan(x, dt, a, b, c, d, chunk_size=chunk)
        loss = layers.reduce_sum(layers.square(y))
        grads = dict((q.name, v) for q, v in
                     fluid.backward.append_backward(loss))
        if amp:
            fluid.amp.decorate_program(main)
    return main, startup, y, grads


def test_the_layer_counts_its_lowering_and_names_its_stages():
    """`layers.ssd_scan` is one Program op; its lowering counts
    `ssd.lowered{chunk, heads, groups}` and `ssd.tokens`, and the three
    stages stand under the op's scope in the lowered module, forward and
    backward."""
    label = dict(chunk=8, heads=4, groups=2)
    before = (obs.counter('ssd.lowered', **label).value,
              obs.counter('ssd.tokens').value)
    main, startup, y, grads = _build_scan(amp=True)
    assert [op.type for op in main.global_block().ops].count('ssd_scan') == 1
    assert sorted(grads) == ['pa', 'pbc', 'pd', 'pdt', 'px']
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, fetch_list=[y] + [grads[k] for k in
                                              sorted(grads)])
        text = exe.lowered_hlo(main, {}, [y] + list(grads.values()),
                               optimized=True)
    assert out[0].dtype == np.float32 and np.isfinite(out[0]).all()
    assert all(np.abs(g).max() > 0 for g in out[1:])
    assert obs.counter('ssd.lowered', **label).value > before[0]
    assert (obs.counter('ssd.tokens').value - before[1]) % (2 * 24) == 0
    names = set(re.findall(r'op_name="([^"]*\(ssd_scan_\d+\)[^"]*)"', text))
    for stage in ('ssd_intra', 'ssd_scan', 'ssd_inter'):
        under = [n for n in names if re.search(r'[/(]%s[/)]' % stage, n)]
        assert any(n.startswith('jit(step)/jvp(ssd_scan_') for n in under), \
            stage
        assert any('transpose(jvp(ssd_scan_' in n for n in under), stage


def test_ssd_scan_refuses_groups_that_do_not_divide_the_heads():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[8, 4, 8], dtype='float32')
        dt = layers.data(name='dt', shape=[8, 4], dtype='float32')
        a = layers.create_parameter([4], 'float32')
        b = layers.data(name='b', shape=[8, 3, 8], dtype='float32')
        with pytest.raises(ValueError, match='ssd_scan'):
            layers.ssd_scan(x, dt, a, b, b)


# ------------------------------------------------------------ the gated norm

@pytest.mark.parametrize('groups', [1, 4])
@pytest.mark.parametrize('first', [True, False], ids=['norm_first',
                                                      'gate_first'])
def test_gated_rms_norm_modes_are_their_compositions(first, groups):
    """`norm_before_gate` false gates first and normalises the product,
    `groups` normalises each part of the last axis by itself; the default
    is the function the op was (bit for bit the old expression); values
    and the three gradients against plain jax.numpy."""
    rng = np.random.default_rng(2)
    x, z = (jnp.asarray(rng.normal(size=(2, 6, 32)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.normal(size=32), jnp.float32)
    t = jnp.asarray(rng.normal(size=(2, 6, 32)), jnp.float32)
    eps = 1e-5

    def plain(x, z, w):
        u = x if first else x * jax.nn.silu(z)
        parts = u.reshape(2, 6, groups, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, -1, keepdims=True) + eps)
        y = w * parts.reshape(2, 6, 32)
        return y * jax.nn.silu(z) if first else y

    def op(x, z, w):
        return la.gated_rms_norm(x, z, w, (eps, first, groups))

    np.testing.assert_allclose(op(x, z, w), plain(x, z, w), rtol=2e-6,
                               atol=2e-6)
    got = jax.grad(lambda *v: jnp.sum(op(*v) * t), argnums=(0, 1, 2))(x, z, w)
    want = jax.grad(lambda *v: jnp.sum(plain(*v) * t), argnums=(0, 1, 2))(
        x, z, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    if first and groups == 1:
        old = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                + eps) * w * jax.nn.silu(z)
        np.testing.assert_array_equal(op(x, z, w), old)


def test_the_gated_norm_layer_writes_its_mode_only_where_it_departs():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[6, 32], dtype='float32')
        z = layers.data(name='z', shape=[6, 32], dtype='float32')
        layers.gated_rms_norm(x, z)
        layers.gated_rms_norm(x, z, norm_before_gate=False, groups=4)
        ops = [op for op in framework.default_main_program().global_block().ops
               if op.type == 'gated_rms_norm']
        assert set(ops[0].attrs) & {'norm_before_gate', 'groups'} == set()
        assert ops[1].attrs['norm_before_gate'] is False
        assert ops[1].attrs['groups'] == 4
        with pytest.raises(ValueError, match='groups'):
            layers.gated_rms_norm(x, z, groups=5)


# ----------------------------------------------------------------- the share

N, D, E, H, K, HELD = 96, 16, 128, 12, 6, 8


def build_share(held, tokens=N, grads=False):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.create_parameter([tokens, D], 'float32', name='px')
        out, count, bias = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='relu2', gated=False,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held,
            scoring='sigmoid', selection_bias=True, gate_scale=2.5)
        got = {}
        if grads:
            loss = layers.reduce_sum(layers.elementwise_mul(
                out, layers.data(name='w', shape=[D], dtype='float32')))
            got = dict((p.name, g) for p, g in
                       fluid.backward.append_backward(loss))
    return main, startup, out, count, got


def _share_weights(rng, n=E):
    """router, W1 stack, W2 stack, the selection bias"""
    return [rng.normal(size=(D, E)).astype('float32'),
            rng.normal(size=(n, D, H)).astype('float32') * 0.3,
            rng.normal(size=(n, H, D)).astype('float32') * 0.3,
            rng.normal(size=E).astype('float32') * 0.2]


def run_share(held, xs, weights):
    main, startup, out, count, _ = build_share(held)
    first, n = held or (0, E)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        # every parameter is set below: no start-up program (a compile a
        # share) is run
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        scope.var('px').get_tensor().set(xs, place)
        for i, w in enumerate(weights):
            scope.var('moe_mlp_0.w_%d' % i).get_tensor().set(
                w[first:first + n] if i in (1, 2) else w, place)
        return exe.run(main, fetch_list=[out, count])


MODEL = {'num_experts_per_tok': K, 'norm_topk_prob': True,
         'routed_scaling_factor': 2.5}


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST of the model-configs guide, section 4: the routed
    parts of all 16 shares of one expert part (first_expert_held 0, 8, ..
    120), with the shared expert counted once, add up to what the UNCUT
    plain reference gives for the whole part; the counts are the whole
    layer's in every share; the bias is not zero here; the experts are
    two matrices and a squared ReLU."""
    reference = reference_module()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N, D)).astype('float32')
    weights = _share_weights(rng)
    label = dict(path='grouped', held='8of128', dispatch='index',
                 scoring='sigmoid', act='relu2', gated='false')
    before = obs.counter('moe.lowered', **label).value
    whole, count = run_share(None, xs, weights)
    assert count.sum() == N * K
    parts = []
    for first in range(0, E, HELD):
        part, count_s = run_share((first, HELD), xs, weights)
        np.testing.assert_array_equal(count_s, count)
        parts.append(part)
    assert obs.counter('moe.lowered', **label).value > before
    assert sum(np.abs(p).max() > 0 for p in parts) >= 12
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    shared = [rng.normal(size=s).astype('float32') * 0.3
              for s in ((D, 2 * H), (2 * H, D))]
    w = {'router': weights[0], 'experts_in': weights[1],
         'experts_out': weights[2], 'bias': weights[3], 'shared': shared}
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.experts(w, jnp.asarray(xs)[None],
                                            MODEL))[0]
        once = np.asarray(jnp.square(jax.nn.relu(xs @ shared[0]))
                          @ shared[1])
        # a cut reference gives its share's partial sum, too
        cut = dict(w, experts_in=weights[1][8:16],
                   experts_out=weights[2][8:16])
        part1 = np.asarray(reference.experts(
            cut, jnp.asarray(xs)[None], dict(MODEL, first_expert_held=8)))[0]
    np.testing.assert_allclose(sum(parts) + once, want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(parts[1] + once, part1, rtol=2e-4, atol=2e-5)
    # wrong rules are far away: no 2.5, a plain ReLU
    assert np.abs(sum(parts) / 2.5 + once - want).max() > 0.05
    plain_relu = dict(w, shared=[np.zeros_like(s) for s in shared])
    assert np.abs(np.asarray(reference.experts(
        plain_relu, jnp.asarray(xs)[None], MODEL))[0]
        - sum(parts)).max() < 1e-3


@pytest.mark.parametrize('way', ['compact', 'blocks', 'overflow'])
def test_a_sixteenth_held_of_two_matrix_experts_on_either_path(
        way, monkeypatch):
    """8 of 128 held, top 6 over 256 tokens under the sigmoid router with
    its bias and 2.5, squared-ReLU experts WITHOUT a gate matrix: 1536
    rows, 96 expected, a layout of 768 chosen on the device. The router
    as drawn stays under it and takes the compact path (`compact`: the
    other gives NaN); the same rows through `_held_blocks` (`blocks`); a
    bias that gives the held experts every choice overflows the layout
    (`overflow`: the compact path gives NaN). Each is the cut plain
    reference's routed part in value and in every gradient: the input's,
    the router's, the two stacks'; none reaches the bias."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    tokens = 256
    assert moe_ops._held_layout(tokens * K, HELD, E) == 768
    rng = np.random.default_rng(5)
    xs, w = (rng.normal(size=(tokens, D)).astype('float32')
             for _ in range(2))
    weights = _share_weights(rng, HELD)
    if way == 'overflow':
        weights[3][8:8 + K] += 4.0
    held_way(monkeypatch, way)
    main, startup, out, count, grads = build_share((8, HELD), tokens, True)
    names = ['px'] + ['moe_mlp_0.w_%d' % i for i in range(3)]
    assert sorted(grads) == sorted(names)         # none for the bias
    assert not [op for op in main.global_block().ops
                if op.type == 'moe_mlp'][0].input('W3')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope, place = fluid.global_scope(), fluid.CPUPlace()
        for name, value in zip(names + ['moe_mlp_0.w_3'], [xs] + weights):
            scope.find_var(name).get_tensor().set(value, place)
        got = exe.run(main, feed={'w': w},
                      fetch_list=[out, count] + [grads[n] for n in names])
    live = got[1][8:8 + HELD].sum()
    assert live == tokens * K if way == 'overflow' else 0 < live <= 768

    reference = reference_module()
    none = [np.zeros(s, 'float32') for s in ((D, H), (H, D))]

    def part(x, router, w_in, w_out):
        y = reference.experts(
            {'router': router, 'experts_in': w_in, 'experts_out': w_out,
             'bias': weights[3], 'shared': none}, x[None],
            dict(MODEL, first_expert_held=8))[0]
        return jnp.sum(y * w), y

    with jax.default_matmul_precision('highest'):
        want, y = jax.grad(part, argnums=range(4), has_aux=True)(
            jnp.asarray(xs), *weights[:3])
    np.testing.assert_allclose(got[0], y, rtol=2e-4, atol=2e-5)
    assert np.abs(got[0]).max() > 0.1
    for name, a, b in zip(names, got[2:], want):
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * np.abs(b).max(),
                                   err_msg=name)


def test_relu2_is_an_activation_of_the_whole_layer_too():
    """Every expert here (no share), the capacity path and the dropless
    one: relu(h)^2 between two matrices, against numpy."""
    rng = np.random.default_rng(4)
    xs = rng.normal(size=(32, 8)).astype('float32')
    for capacity in (None, 8.0):
        main, startup = framework.Program(), framework.Program()
        with unique_name.guard(), framework.program_guard(main, startup):
            x = layers.data(name='x', shape=[8], dtype='float32')
            out = layers.moe_mlp(x, num_experts=4, hidden_size=6,
                                 act='relu2', top_k=1, bias_attr=False,
                                 capacity_factor=capacity)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            got, = exe.run(main, feed={'x': xs}, fetch_list=[out])
            wr, w1, w2 = (np.asarray(fluid.global_scope().find_var(
                'moe_mlp_0.w_%d' % i).get_tensor()) for i in range(3))
        logits = xs @ wr
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        pick = logits.argmax(-1)
        want = np.stack([probs[i, e] * (np.maximum(xs[i] @ w1[e], 0) ** 2
                                        @ w2[e])
                         for i, e in enumerate(pick)])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------------ the model

# trainable parameters a block after its norm (models/nemotron_h.py)
_PER_KIND = {'M': 8, '*': 4, 'E': 5}


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/nemotron_h.py through the Executor against
    chipbench/references/nemotron_h.py in float32 to 1e-5: the loss and
    the gradient of EVERY trainable parameter over rows of 80 (four
    Mamba-2 mixers of 4 heads in 2 groups in chunks of 16, the
    convolution's bias, dt_bias, A_log, D and the grouped gated norm
    among them; attention of 4 heads over 2 without positions; experts 4
    .. 7 of 16 held, two matrices and a squared ReLU); and under bf16 AMP
    within a stated tolerance."""
    cell = _toy_cell()
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    assert cell['builder'].pattern(cell['config']['model']) == 'MEMEM*EME'
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert len(names) == 1 + sum(1 + _PER_KIND[k] for k in 'MEMEM*EME') + 2
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _rotary_attention(ref):
    """Rotary positions (rotate-half, theta 10000) on the attention
    block's queries and keys."""
    plain = ref._head

    def head(q, k, v):
        t, d = q.shape[-2:]
        inv = 10000.0 ** (-np.arange(0, d, 2) / d)
        angle = np.arange(t)[:, None] * inv[None, :]
        cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1),
                                jnp.float32) for f in (np.cos, np.sin))

        def turn(x):
            half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
            return x * cos + half * sin
        return plain(turn(q), turn(k), v)
    ref._head = head


def _bias_dropped(ref):
    ref.PARTS = dict(ref.PARTS, M=lambda w, u, model: ref.mamba(
        dict(w, conv_bias=jnp.zeros_like(w['conv_bias'])), u, model))


def _skip_dropped(ref):
    ref.PARTS = dict(ref.PARTS, M=lambda w, u, model: ref.mamba(
        dict(w, d=jnp.zeros_like(w['d'])), u, model))


def _gate_scale_dropped(ref):
    ref.PARTS = dict(ref.PARTS, E=lambda w, u, model: ref.experts(
        w, u, dict(model, routed_scaling_factor=1.0)))


def _plain_relu(ref):
    ref._relu2 = lambda m, w_in, w_out: jax.nn.relu(m @ w_in) @ w_out


_MOVED = {'rotary_on_the_attention_block': _rotary_attention,
          'no_convolution_bias': _bias_dropped,
          'no_skip_d': _skip_dropped,
          'gates_without_the_scaling_factor': _gate_scale_dropped,
          'experts_of_a_plain_relu': _plain_relu}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference whose attention has rotary positions, whose convolution
    has no bias, whose scan has no skip, whose gates lack the 2.5 or whose
    experts are a plain ReLU, the same Program FAILS at the same
    tolerance. The reference is a fresh copy of the module with ONE
    function moved."""
    reference = reference_module()
    _MOVED[rule](reference)
    cell = dict(_toy_cell(), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert not got['passed']
    assert max(got['grad_rel'].values()) > 1e-3


def test_blocks_are_one_part_each_scopes_regions_and_counters():
    """Nine blocks off the pattern string, each ONE norm, ONE part and ONE
    add in one recompute region; the Mamba-2 mixers are built under
    `mamba_mixer` (projections, convolution with its bias, the scan, the
    grouped gate-first norm), the attention block under `attention_mixer`
    with no rotary op anywhere; the scopes reach the optimized HLO's
    op_name; the convolution's bias and the experts' form are counted."""
    from chipbench.harness import catalog
    cell = _toy_cell()
    moe = dict(path='grouped', held='4of16', dispatch='index',
               scoring='sigmoid', act='relu2', gated='false')
    before = (obs.counter('moe.lowered', **moe).value,
              obs.counter('conv1d.lowered', taps=4, act='silu',
                          bias='true').value,
              obs.counter('moe.bias_updates').value)
    config, built = build_toy(cell, train=True)
    assert obs.counter('moe.lowered', **moe).value - before[0] == 4
    assert obs.counter('conv1d.lowered', taps=4, act='silu',
                          bias='true').value - before[1] == 4
    assert obs.counter('moe.bias_updates').value - before[2] == 4
    ops = built['main'].global_block().ops
    forward = [op for op in ops if not op.type.endswith('_grad')]
    kinds = [op.type for op in forward]
    assert kinds.count('rms_norm') == 10 and 'rotary_embedding' not in kinds
    assert kinds.count('ssd_scan') == kinds.count('causal_conv1d') == 4
    assert kinds.count('flash_attention') == 1
    for op in forward:
        scope = op.attrs.get('name_scope')
        if op.type in ('ssd_scan', 'causal_conv1d', 'gated_rms_norm'):
            assert scope == 'mamba_mixer', op.type
        if op.type == 'flash_attention':
            assert scope == 'attention_mixer' and op.attrs['causal']
        if op.type in ('moe_mlp', 'rms_norm'):
            assert scope is None
    for op in forward:
        if op.type == 'causal_conv1d':
            assert op.input('Bias') and op.attrs['act'] == 'silu'
        if op.type == 'gated_rms_norm':
            assert op.attrs['norm_before_gate'] is False
            assert op.attrs['groups'] == 2
        if op.type == 'ssd_scan':
            assert op.attrs['chunk_size'] == 16 and op.input('D')
        if op.type == 'moe_mlp':
            assert op.attrs['act'] == 'relu2' and not op.input('W3')
            assert op.input('SelectionBias')
            assert op.attrs['gate_scale'] == 2.5
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 9
    text = decoder_toy.one_step_hlo(cell, config, built)
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    mamba = window.op_scopes_under(text, 'mamba_mixer')
    attn = window.op_scopes_under(text, 'attention_mixer')
    assert mamba and attn and not mamba & attn
    assert {s.rsplit('_', 1)[0] for s in mamba} >= {
        'mul', 'causal_conv1d', 'ssd_scan', 'gated_rms_norm'}
    assert {s.rsplit('_', 1)[0] for s in attn} >= {'mul', 'flash_attention'}
    assert not any(s.startswith(('moe_mlp', 'rms_norm'))
                   for s in mamba | attn)


def test_a_pattern_of_another_letter_is_refused():
    from paddle_tpu.models import nemotron_h as N
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match="'M', '\\*' or 'E'"):
            N.nemotron_h(64, 16, pattern='M-', hidden=16, ssm_heads=2,
                         ssm_head_dim=8, ssm_groups=1, ssm_state=8,
                         chunk_size=8, n_head=2, n_kv_head=1, d_head=8,
                         n_expert=4, top_k=2, expert_width=8,
                         shared_width=16)


def test_head_vectors_are_the_sources_draws():
    """dt_bias is the inverse softplus of a step in [0.001, 0.1], A in
    [1, 16], D one; a block's draws are its index's."""
    from paddle_tpu.models import nemotron_h as N
    dt_bias, a_log, d = N.head_vectors(64, 3, 0.001, 0.1, 1e-4)
    dt = np.log1p(np.exp(dt_bias))
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-9
    assert 1.0 <= np.exp(a_log).min() and np.exp(a_log).max() <= 16.0
    assert (d == 1).all()
    again = N.head_vectors(64, 3, 0.001, 0.1, 1e-4)
    other = N.head_vectors(64, 4, 0.001, 0.1, 1e-4)
    np.testing.assert_array_equal(dt_bias, again[0])
    assert not np.array_equal(dt_bias, other[0])


def test_small_preset_trains_and_moves_its_biases():
    from paddle_tpu.models import nemotron_h as N
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = N.get_model(experts_held=(4, 4))
    biases = [v.name for v in main.list_vars()
              if isinstance(v, framework.Parameter) and not v.trainable]
    assert len(biases) == 2
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
        moved = np.asarray(fluid.global_scope().find_var(
            biases[0]).get_tensor())
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert 0 < np.abs(moved).max() <= 12 * 0.001 + 1e-9


# ------------------------------------------------------------- the benchmark

def test_the_builders_rate_climbs_linearly_to_the_configurations_peak():
    cell = _toy_cell()
    opt = cell['config']['optimizer']
    assert (opt['learning_rate'], opt['warmup_steps']) == (4e-4, 2000)
    np.testing.assert_allclose(decoder_toy.rates_of_training(cell, 3),
                               [4e-4 * n / 2000 for n in (1, 2, 3)],
                               rtol=1e-5)


SOURCE = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth, the experts held and the vocabulary are
    cut, and the pattern string stands whole."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'nemotron_3_nano_30b_a3b.json')) as f:
        held = json.load(f)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'NVIDIA-Nemotron-3-Nano-30B-A3B-BF16':
                    assert row['config'] == SOURCE
                    assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 9, 'n_routed_experts': 8,
           'vocab_size': 16384}
    for key, value in SOURCE.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: SOURCE[k] for k in cut}
    assert set(held['model']) - set(SOURCE) == {
        'bias_update_speed', 'initializer_range', 'first_expert_held'}
    # the pattern is no period: its first nine blocks hold every kind
    run = held['hybrid_override_pattern'][:held['num_hidden_layers']]
    assert run == 'MEMEM*EME' == held['assumed']['pattern_as_run'][:9]
    assert (run.count('M'), run.count('E'), run.count('*')) == (4, 4, 1)
    whole = SOURCE['hybrid_override_pattern']
    assert (whole.count('M'), whole.count('E'), whole.count('*'),
            len(whole)) == (23, 23, 6, 52)
    # the floors of the guide: four blocks of each repeated kind, 8
    # experts, an eighth of the vocabulary
    assert held['n_routed_experts'] >= 8
    assert held['vocab_size'] * 8 >= SOURCE['vocab_size']
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('top_level_keys', 'num_hidden_layers', 'n_routed_experts',
                'vocab_size', 'attention_positions', 'expand',
                'time_step_limit', 'initializers', 'optimizer',
                'router_aux_loss', 'bias_update_speed', 'document_mask',
                'recomputation'):
        assert held['assumed'][key], key
    assert '16 chips' in held['deployment']
    # a Mamba-2 block's Win, A_log and dt_bias, a held expert stack, the
    # embedding: what the issue asks the chip's comparison to hold (the
    # FIRST expert block's in float32: a flipped token cascades into the
    # later blocks' routers, `checks.float32.why`)
    assert {'fc_0.w_0', 'create_parameter_0.w_0', 'create_parameter_1.w_0',
            'moe_mlp_0.w_0', 'moe_mlp_0.w_1', 'embedding_0.w_0'} <= set(
        held['checks']['float32']['grads'])
    assert {'embedding_0.w_0', 'fc_0.w_0'} <= set(
        held['checks']['amp']['grads'])
    assert held['checks']['amp_experts']['grads'] == ['moe_mlp_3.w_1']
    for entry in held['checks'].values():
        assert len(entry['why']) > 400


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 8192 by part (ISSUE 40's count): a
    Mamba-2 mixer's two matrices 77 M, its recurrence 5 x 4096 x 128, the
    attention block's projections and its causal scores, a shared expert
    40 M, the held experts at a sixteenth, the head 88 M; the parameters
    of the deployment's table."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 8192
    m = config['model']
    assert flops.block_counts(m) == (4, 1, 4)
    assert flops.mamba_widths(m) == (4096, 2048, 64)
    assert flops.mamba_weights(m) == 2688 * 10304 + 4096 * 2688
    f = {k: v / tokens / 1e6 for k, v in flops.forward_flops(
        config, traffic['batch'], traffic['seq']).items()}
    assert f['mamba_projections'] == pytest.approx(4 * 77.4, rel=0.005)
    assert f['ssd'] == pytest.approx(4 * 2.62, rel=0.005)
    assert f['attention_projections'] == pytest.approx(46.8, rel=0.005)
    assert f['attention'] == pytest.approx(
        2 * 2 * 128 * 32 * 8193 / 2 / 1e6, rel=1e-6)
    assert f['shared_expert'] == pytest.approx(4 * 39.9, rel=0.005)
    assert f['experts'] == pytest.approx(4 * 6 / 16 * 19.96, rel=0.005)
    assert f['head'] == pytest.approx(88.1, rel=0.005)
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(3 * sum(f.values()) * tokens * 1e6)
    assert 15e12 < step < 20e12
    assert flops.held_rows(config, 1, 8192) == 8192 * 6 / 16
    costs = dict(flops.kernel_cost(config, traffic, 1),
                 experts=flops.expert_cost(config, traffic, 1),
                 ssd=flops.ssd_cost(config, traffic, 1))
    for name, (n_flops, nbytes) in costs.items():
        assert 0 < n_flops < step and nbytes > 0, name
    # two matmuls an expert a pass: six grouped calls a block, not nine
    rows, stack = 3072, 8 * 2688 * 1856 * 2
    assert costs['moe_mlp'][1] == 4 * 6 * (rows * (2688 + 1856) * 2 + stack)
    assert costs['moe_mlp'][0] == pytest.approx(
        3 * 4 * rows * 2 * 2 * 2688 * 1856)
    # the scan's operands: x, y 4096 wide, B, C 1024 each in bf16, dt 64
    # in float32, a pass; three passes' worth a step
    assert costs['ssd'][1] == 3 * 4 * 8192 * (2 * (2 * 4096 + 2048) + 4 * 64)
    # the parameters of the deployment's table
    mamba = flops.mamba_weights(m) + 5 * 6144 + 3 * 64 + 4096 + 2688
    attn = flops.attention_weights(m) + 2688
    expert = 2688 * 128 + 128 + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688
    n = 2 * 16384 * 2688 + 4 * mamba + attn + 4 * expert + 2688
    assert mamba == pytest.approx(38.7e6, rel=2e-3)
    assert attn == pytest.approx(23.4e6, rel=2e-3)
    assert expert == pytest.approx(100.1e6, rel=2e-3)
    assert n == pytest.approx(666.9e6, rel=2e-3)


def test_new_readers_read_their_scopes_or_nothing():
    """`ssd_ms`, `ssd_roofline` and `mamba_ms` on a hand-made reduction
    and a hand-made HLO; on a program that has no such op or scope (the
    parent's) nothing, and no error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mamba_mixer)/jvp(mul_4)/dot_general"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/transpose(jvp(mamba_mixer))/'
        'transpose(jvp(ssd_scan_1))/ssd_intra/dot_general"}',
        '  %custom-call.3 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/checkpoint/jvp(attention_mixer)/'
        'jvp(flash_attention_0))/pallas_call"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mul_20)/dot_general"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mamba_mixer_like)/jvp(mul_21)/dot"}',
    ])
    red = {'steps': 5,
           'fluid_scope_s': {'mul_4': 0.10, 'ssd_scan_1': 0.40,
                             'flash_attention_0': 0.25, 'mul_20': 1.0,
                             'mul_21': 1.0},
           'fluid_op_s': {'ssd_scan': 0.40, 'mul': 2.1}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('mamba_ms')(reading) == pytest.approx(100.0)
    assert catalog.load_reader('ssd_ms')(reading) == pytest.approx(80.0)
    share = catalog.load_reader('ssd_roofline')(reading)
    least, bound = peaks.roofline(cell['flops'].ssd_cost(
        cell['config'], cell['traffic'], 1), reading['peaks'])
    assert share == pytest.approx(100 * least / 0.08) and 0 < share < 100
    assert bound == 'bytes'
    bare = dict(red, fluid_op_s={'mul': 2.1})
    for other in (dict(reading, hlo=hlo.replace('mamba_mixer', 'x'),
                       trace=bare),
                  dict(reading, trace=None), dict(reading, hlo=None,
                                                  trace=bare)):
        for name in ('ssd_ms', 'ssd_roofline', 'mamba_ms'):
            assert catalog.load_reader(name)(other) is None, name
    # a configuration without `ssd_cost` (every older one) reads nothing
    older = dict(reading, cell=catalog.load_cell('qwen3next_s8192'))
    assert catalog.load_reader('ssd_roofline')(older) is None
