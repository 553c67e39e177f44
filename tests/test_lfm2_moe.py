"""LFM2-MoE on the normal path (ISSUE 44): the double-gated short
convolution against three shifted multiply-adds, forward and every
gradient; the router's renormalisation with the source's 1e-6 and the
default unchanged; the head tied to the embedding (one parameter, two
gradients summed, one Adam update); a quarter share on both held paths and
the four shares adding up to the uncut reference's layer; the toy model
against the plain reference on every gradient (and a moved rule FAILING
the comparison); name scopes, regions, counters, the configuration's file,
its FLOPs and its readers. Small sizes, on the CPU."""
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
from util import held_way

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'lfm2_s16384'


reference_module = functools.partial(decoder_toy.reference_module,
                                     'lfm2_moe')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)


def _set(scope, name, value):
    scope.var(name).get_tensor().set(np.asarray(value, 'float32'),
                                     fluid.CPUPlace())


def _get(scope, name):
    return np.asarray(scope.find_var(name).get_tensor())


# ------------------------------------------------------------------ the mixer

HIDDEN = 32


def _mixer_program(batch, seq, amp):
    from paddle_tpu.models import lfm2_moe as L
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        g = layers.create_parameter([batch, seq, HIDDEN], 'float32',
                                    name='g')
        out = L.short_conv_mixer(g, {'hidden': HIDDEN, 'conv_kernel': 3,
                                     'std': 0.3})
        loss = layers.reduce_sum(layers.elementwise_mul(
            out, layers.data(name='w', shape=[batch, seq, HIDDEN],
                             dtype='float32', append_batch_size=False)))
        grads = dict((p.name, v) for p, v in
                     fluid.backward.append_backward(loss))
        if amp:
            fluid.amp.decorate_program(main)
    return main, startup, out, grads


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
@pytest.mark.parametrize('batch,seq', [(2, 37), (1, 64), (2, 5)],
                         ids=['two_ragged_rows', 'one_row', 'short_rows'])
def test_short_conv_mixer_is_three_shifted_multiply_adds(batch, seq, amp):
    """models/lfm2_moe.py short_conv_mixer through the Executor against
    the plain reference's `short_conv` (three shifted multiply-adds of
    B * x~ between the two gates, the chunks in the order B | C | x~, no
    activation): the value and the gradient of the input, both
    projections and the filter; T no multiple of any tile, two rows (row
    1's first two tokens see zeros, not row 0's last); under bf16 AMP
    within bf16's rounding. One `causal_conv1d` op with both gates and NO
    elementwise_mul in the Program."""
    reference = reference_module()
    rng = np.random.default_rng(seq)
    g = rng.normal(size=(batch, seq, HIDDEN)).astype('float32')
    w = rng.normal(size=(batch, seq, HIDDEN)).astype('float32')
    main, startup, out, grads = _mixer_program(batch, seq, amp)
    names = ['g', 'fc_0.w_0', 'causal_conv1d_0.w_0', 'fc_1.w_0']
    kinds = [op.type for op in main.global_block().ops
             if not op.type.endswith('_grad')]
    assert kinds[:4] == ['mul', 'split', 'causal_conv1d', 'mul']
    conv, = (op for op in main.global_block().ops
             if op.type == 'causal_conv1d')
    assert conv.input('InGate') and conv.input('OutGate')
    assert conv.attrs['act'] == '' and not conv.input('Bias')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        _set(scope, 'g', g)
        weights = {'in': _get(scope, names[1]), 'conv': _get(scope, names[2]),
                   'out': _get(scope, names[3])}
        got = exe.run(main, feed={'w': w},
                      fetch_list=[out] + [grads[n] for n in names])
    assert weights['in'].shape == (HIDDEN, 3 * HIDDEN)
    assert weights['conv'].shape == (3, HIDDEN)

    def plain(g, w_in, w_conv, w_out):
        y = reference.short_conv({'in': w_in, 'conv': w_conv, 'out': w_out},
                                 g, {'conv_L_cache': 3})
        return jnp.sum(y * w), y

    with jax.default_matmul_precision('highest'):
        want, y = jax.grad(plain, argnums=range(4), has_aux=True)(
            jnp.asarray(g), *(jnp.asarray(weights[k])
                              for k in ('in', 'conv', 'out')))
    tol = 2.0 ** -5 if amp else 2e-5
    assert np.abs(got[0] - y).max() <= tol * np.abs(y).max()
    if amp:
        assert np.abs(got[0] - y).max() > 0                # bf16 did run
    for name, a, b in zip(names, got[1:], want):
        assert np.abs(b).max() > 0, name
        rel = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert rel <= tol, (name, rel)
    # the chunks' ORDER is the source's: with B and C swapped (C * x~
    # through the filter, gated by B) the same weights give another mixer
    swapped = np.concatenate([weights['in'][:, HIDDEN:2 * HIDDEN],
                              weights['in'][:, :HIDDEN],
                              weights['in'][:, 2 * HIDDEN:]], 1)
    other = plain(jnp.asarray(g), swapped, weights['conv'],
                  weights['out'])[1]
    if seq > 5:
        assert np.abs(other - y).max() > 0.05 * np.abs(y).max()


# ----------------------------------------------------------------- the router

def _gates(logits, bias, top_k, eps, scale=1.0):
    """Ten lines of numpy: sigmoid scores, the top k of score + bias, the
    chosen scores over their sum + eps."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    idx = np.argsort(-(s + bias), axis=-1, kind='stable')[:, :top_k]
    g = np.take_along_axis(s, idx, -1)
    return idx, scale * g / (g.sum(-1, keepdims=True) + eps)


@pytest.mark.parametrize('norm_eps', [None, 1e-6, 0.5],
                         ids=['default_1e-20', 'lfm2_1e-6', 'a_large_one'])
def test_router_renormalises_with_the_epsilon_it_is_given(norm_eps):
    """parallel/moe.py router_topk under 'sigmoid' with a selection bias:
    the gates are s / (sum of the chosen s + eps), eps the source's 1e-6
    where the attribute says so and DeepSeek-V3's 1e-20 where it says
    nothing (what the four older held cells trace); the choice is of
    score + bias and the same whatever eps."""
    from paddle_tpu.parallel.moe import router_topk
    rng = np.random.default_rng(2)
    logits = rng.normal(size=(64, 32)).astype('float32')
    bias = (rng.normal(size=32) * 0.3).astype('float32')
    kw = {} if norm_eps is None else {'norm_eps': norm_eps}
    expert, gate = router_topk(jnp.asarray(logits), 4, True, 'sigmoid',
                               jnp.asarray(bias), 1.0, **kw)
    idx, want = _gates(logits, bias, 4, 1e-20 if norm_eps is None
                       else norm_eps)
    np.testing.assert_array_equal(np.asarray(expert).T, idx)
    np.testing.assert_allclose(np.asarray(gate).T, want, rtol=2e-6)
    total = np.asarray(gate).sum(0)
    if norm_eps == 0.5:
        assert total.max() < 0.9          # an epsilon that shows
    else:
        np.testing.assert_allclose(total, 1.0, rtol=2e-6)


def test_the_layer_writes_norm_eps_only_where_it_is_given():
    with framework.program_guard(framework.Program(), framework.Program()):
        x = layers.data(name='x', shape=[8], dtype='float32')
        common = dict(num_experts=8, hidden_size=4, top_k=2, gated=True,
                      act='swish', bias_attr=False, capacity_factor=None,
                      scoring='sigmoid', selection_bias=True)
        layers.moe_mlp(x, **common)
        layers.moe_mlp(x, norm_eps=1e-6, **common)
        ops = [op for op in framework.default_main_program().global_block()
               .ops if op.type == 'moe_mlp']
        assert 'norm_eps' not in ops[0].attrs
        assert ops[1].attrs['norm_eps'] == 1e-6


# ------------------------------------------------------------- the tied head

def _tied_program(vocab, hidden, seq, optimizer):
    """An embedding, one norm and the tied head: the model's first and
    last ops with nothing between them."""
    from paddle_tpu.models import lfm2_moe as L
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 5
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, _, _ = L.lfm2_moe(vocab, seq, layer_types=(), hidden=hidden)
        grads = {}
        if optimizer:
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        else:
            grads = dict((p.name, g) for p, g in
                         fluid.backward.append_backward(loss))
    return main, startup, loss, grads


def test_tied_head_sums_the_lookups_scatter_and_the_heads_matmul():
    """ONE parameter [vocab, hidden] read by `lookup_table` and,
    transposed, by the last `mul`: its gradient is the lookup's
    scatter-add PLUS the head's dense matmul, each computed here by hand;
    either alone is far away; the head is the `mul` of the highest
    index."""
    from paddle_tpu.models import lfm2_moe as L
    vocab, hidden, seq = 23, 8, 12
    before = obs.counter('model.shared_param_uses').value
    main, startup, loss, grads = _tied_program(vocab, hidden, seq, False)
    assert obs.counter('model.shared_param_uses').value == before + 1
    params = [v.name for v in main.list_vars()
              if isinstance(v, framework.Parameter)]
    assert params == [L.EMBEDDING, 'rms_norm_0.w_0']
    ops = main.global_block().ops
    forward = [op.type for op in ops if not op.type.endswith('_grad')]
    assert forward[:4] == ['lookup_table', 'rms_norm', 'transpose', 'mul']
    head, = (op for op in ops if op.type == 'mul')
    assert ops[2].input('X') == [L.EMBEDDING]
    assert head.input('Y') == ops[2].output('Out')
    rng = np.random.default_rng(1)
    ids = rng.integers(0, vocab, size=(2, seq)).astype('int64')
    labels = rng.integers(0, vocab, size=(2, seq)).astype('int64')
    table = rng.normal(size=(vocab, hidden)).astype('float32')
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _set(fluid.global_scope(), L.EMBEDDING, table)
        got_loss, got = exe.run(
            main, feed={'input_ids': ids, 'labels': labels},
            fetch_list=[loss, grads[L.EMBEDDING]])

    def untied(emb, head):
        x = emb[ids]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        logp = jax.nn.log_softmax(x @ head.T, -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    want_loss, (scatter, dense) = jax.value_and_grad(
        untied, argnums=(0, 1))(jnp.asarray(table), jnp.asarray(table))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got, scatter + dense, rtol=1e-4, atol=1e-6)
    for one in (scatter, dense):
        assert np.linalg.norm(got - one) > 0.2 * np.linalg.norm(got)
    # the lookup's part touches only the rows the ids named
    assert np.abs(np.asarray(scatter)[np.setdiff1d(
        np.arange(vocab), ids)]).max() == 0


def test_adam_sees_the_tied_parameter_once():
    """One `adam` op for the embedding, one pair of moments, and after a
    step the parameter has moved by Adam's first step (the rate, in the
    direction of the SUMMED gradient's sign) exactly once."""
    from paddle_tpu.models import lfm2_moe as L
    vocab, hidden, seq = 23, 8, 12
    main, startup, loss, _ = _tied_program(vocab, hidden, seq, True)
    adams = [op for op in main.global_block().ops if op.type == 'adam']
    assert sorted(op.input('Param')[0] for op in adams) == sorted(
        [L.EMBEDDING, 'rms_norm_0.w_0'])
    moments = [v.name for v in main.list_vars()
               if v.name.startswith('moment') and L.EMBEDDING in v.name]
    assert len(moments) == 2
    _, _, _, grads = _tied_program(vocab, hidden, seq, False)
    rng = np.random.default_rng(2)
    feed = {'input_ids': rng.integers(0, vocab, size=(2, seq)),
            'labels': rng.integers(0, vocab, size=(2, seq))}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = _get(fluid.global_scope(), L.EMBEDDING).copy()
        exe.run(main, feed=feed, fetch_list=[loss])
        after = _get(fluid.global_scope(), L.EMBEDDING)
    step = after - before
    moved = np.abs(step) > 0
    # Adam's first step is rate * g / (|g| + eps'): the rate where a
    # gradient is not tiny, once and not twice
    assert moved.mean() > 0.9
    assert np.abs(step).max() <= 0.01 * (1 + 1e-3)
    assert np.median(np.abs(step[moved])) == pytest.approx(0.01, rel=1e-2)


# ----------------------------------------------------------------- the share

N, D, E, H, K, HELD = 96, 16, 32, 12, 4, 8


def build_share(held, tokens=N, grads=False):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.create_parameter([tokens, D], 'float32', name='px')
        out, count, bias = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held,
            scoring='sigmoid', selection_bias=True, norm_eps=1e-6)
        got = {}
        if grads:
            loss = layers.reduce_sum(layers.elementwise_mul(
                out, layers.data(name='w', shape=[D], dtype='float32')))
            got = dict((p.name, g) for p, g in
                       fluid.backward.append_backward(loss))
    return main, startup, out, count, got


def _share_weights(rng, n=E):
    """router, W1, W3 and W2 stacks, the selection bias"""
    return [rng.normal(size=(D, E)).astype('float32'),
            rng.normal(size=(n, D, H)).astype('float32') * 0.3,
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
        scope = fluid.global_scope()
        _set(scope, 'px', xs)
        for i, w in enumerate(weights):
            _set(scope, 'moe_mlp_0.w_%d' % i,
                 w[first:first + n] if i in (1, 2, 3) else w)
        return exe.run(main, fetch_list=[out, count])


MODEL = {'num_experts_per_tok': K, 'norm_topk_prob': True,
         'routed_scaling_factor': 1, 'router_norm_eps': 1e-6}


def _reference_weights(weights, first=0, n=E):
    return {'router': weights[0],
            'experts_in': [weights[1][first:first + n],
                           weights[2][first:first + n]],
            'experts_down': weights[3][first:first + n], 'bias': weights[4]}


def test_the_four_shares_are_the_uncut_layer():
    """THE SHARE TEST of the model-configs guide, section 4: the parts of
    all 4 shares of one expert layer (first_expert_held 0, 8, 16, 24; no
    shared expert to count once) add up to what the UNCUT plain reference
    gives for the whole layer; the counts are the whole layer's in every
    share; the bias is not zero here, the renormalisation carries 1e-6,
    and a cut reference gives its own share's partial sum."""
    reference = reference_module()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N, D)).astype('float32')
    weights = _share_weights(rng)
    label = dict(path='grouped', held='8of32', dispatch='index',
                 scoring='sigmoid')
    before = obs.counter('moe.lowered', **label).value
    whole, count = run_share(None, xs, weights)
    assert count.sum() == N * K
    parts = []
    for first in range(0, E, HELD):
        part, count_s = run_share((first, HELD), xs, weights)
        np.testing.assert_array_equal(count_s, count)
        parts.append(part)
    assert obs.counter('moe.lowered', **label).value > before
    assert all(np.abs(p).max() > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    with jax.default_matmul_precision('highest'):
        want = np.asarray(reference.experts(
            _reference_weights(weights), jnp.asarray(xs)[None], MODEL))[0]
        part1 = np.asarray(reference.experts(
            _reference_weights(weights, 8, 8), jnp.asarray(xs)[None],
            dict(MODEL, first_expert_held=8)))[0]
    np.testing.assert_allclose(sum(parts), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(parts[1], part1, rtol=2e-4, atol=2e-5)
    # a wrong rule is far away: gates renormalised over the held only
    assert np.abs(parts[1] * 4 - want).max() > 0.05


@pytest.mark.parametrize('way', ['compact', 'blocks', 'overflow'])
def test_a_quarter_held_on_either_path(way, monkeypatch):
    """8 of 32 held, top 4 over 512 tokens under the sigmoid router with
    its bias: 2048 rows, 512 expected, a layout of HALF the rows (1024:
    twice the expected, where an eighth has four times) chosen on the
    device. The router as drawn stays under it and takes the compact path
    (`compact`: the other gives NaN); the same rows through `_held_blocks`
    (`blocks`); a bias that gives the held experts every choice overflows
    the layout (`overflow`: the compact path gives NaN). Each is the cut
    plain reference's part in value and in every gradient: the input's,
    the router's, the three stacks'; none reaches the bias."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    tokens = 512
    assert moe_ops._held_layout(tokens * K, HELD, E) == 1024
    assert moe_ops._held_cap(tokens * K, HELD, E) == 5120
    rng = np.random.default_rng(5)
    xs, w = (rng.normal(size=(tokens, D)).astype('float32')
             for _ in range(2))
    weights = _share_weights(rng, HELD)
    if way == 'overflow':
        weights[4][8:8 + K] += 4.0
    held_way(monkeypatch, way)
    main, startup, out, count, grads = build_share((8, HELD), tokens, True)
    names = ['px'] + ['moe_mlp_0.w_%d' % i for i in range(4)]
    assert sorted(grads) == sorted(names)         # none for the bias
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for name, value in zip(names + ['moe_mlp_0.w_4'], [xs] + weights):
            _set(scope, name, value)
        got = exe.run(main, feed={'w': w},
                      fetch_list=[out, count] + [grads[n] for n in names])
    live = got[1][8:8 + HELD].sum()
    assert live == tokens * K if way == 'overflow' else 0 < live <= 1024

    reference = reference_module()

    def part(x, router, w1, w3, w2):
        y = reference.experts(
            {'router': router, 'experts_in': [w1, w3], 'experts_down': w2,
             'bias': weights[4]}, x[None],
            dict(MODEL, first_expert_held=8))[0]
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


# ------------------------------------------------------------------ the model

def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/lfm2_moe.py through the Executor against
    chipbench/references/lfm2_moe.py in float32 to 1e-5: the loss and the
    gradient of EVERY trainable parameter over rows of 80 (published
    layers 1 to 5: a short convolution with a dense feed-forward,
    attention of 4 heads over 2 with the q/k norms and rotary, three more
    short convolutions, four expert layers with experts 4 .. 7 of 16
    held; the TIED embedding once); and under bf16 AMP within a stated
    tolerance."""
    cell = _toy_cell()
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    run, dense_end = cell['builder'].stretch(cell['config']['model'])
    assert (list(run), dense_end) == ([1, 2, 3, 4, 5], 2)
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    # the embedding; layer 1 (2 norms, 3 + 3); layer 2 (2 norms, 6 + 4);
    # layers 3 to 5 (2 norms, 3 + 4); the final norm; NO head of its own
    assert len(names) == 1 + 8 + 12 + 3 * 9 + 1
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _silu_in_the_convolution(ref):
    def short_conv(w, g, model):
        gate_in, gate_out, x = jnp.split(g @ w['in'], 3, axis=-1)
        bx = gate_in * x
        t = bx.shape[1]
        conv = sum(w['conv'][j] * jnp.pad(
            bx, ((0, 0), (2 - j, 0), (0, 0)))[:, :t] for j in range(3))
        return (gate_out * jax.nn.silu(conv)) @ w['out']
    ref.short_conv = short_conv


def _a_filter_that_looks_one_token_further(ref):
    """A kernel of 4 whose oldest tap repeats the filter's first: what a
    convolution of another length would add."""
    plain = ref.short_conv

    def short_conv(w, g, model):
        gate_in, gate_out, x = jnp.split(g @ w['in'], 3, axis=-1)
        bx = gate_in * x
        extra = w['conv'][0] * jnp.pad(bx, ((0, 0), (3, 0), (0, 0)))[
            :, :bx.shape[1]]
        return plain(w, g, model) + (gate_out * extra) @ w['out']
    ref.short_conv = short_conv


def _rotary_before_the_norms(ref):
    def attention(w, g, model):
        n_q, n_kv = (model['num_attention_heads'],
                     model['num_key_value_heads'])
        d, eps = model['head_dim'], model['norm_eps']
        b, t, _ = g.shape

        def heads(x, n):
            return x.reshape(b, t, n, d).transpose(2, 0, 1, 3)

        q = ref.rms(ref.rotary(heads(g @ w['q'], n_q), model['rope_theta']),
                    w['q_norm'], eps)
        k = ref.rms(ref.rotary(heads(g @ w['k'], n_kv),
                               model['rope_theta']), w['k_norm'], eps)
        v = heads(g @ w['v'], n_kv)
        group = n_q // n_kv
        ctx = jax.lax.map(lambda t: ref._head(*t), (
            q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)))
        return ctx.transpose(1, 2, 0, 3).reshape(b, t, n_q * d) @ w['out']
    ref.attention = attention


def _a_head_that_forgets_the_embedding(ref):
    """The head's use of the embedding gives it no gradient: what is left
    is the lookup's scatter alone."""
    plain = ref._block_loss
    ref._block_loss = lambda y, w_final, table, labels, eps: plain(
        y, w_final, jax.lax.stop_gradient(table), labels, eps)


def _gates_of_a_softmax(ref):
    def route(m, w_router, bias, model):
        z = m @ w_router
        _, top_i = jax.lax.top_k(jax.nn.sigmoid(z) + bias,
                                 model['num_experts_per_tok'])
        chosen = jnp.sum(jax.nn.one_hot(top_i, z.shape[-1], dtype=z.dtype),
                         axis=1)
        gates = jax.nn.softmax(jnp.where(chosen > 0, z, -jnp.inf), -1)
        return gates * model['routed_scaling_factor']
    ref.route = route


_MOVED = {'silu_in_the_convolution': _silu_in_the_convolution,
          'a_convolution_of_four': _a_filter_that_looks_one_token_further,
          'rotary_before_the_q_k_norms': _rotary_before_the_norms,
          'a_head_that_forgets_the_embedding':
              _a_head_that_forgets_the_embedding,
          'gates_of_a_softmax_over_the_chosen': _gates_of_a_softmax}


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference whose convolution has an activation or a fourth tap,
    whose rotary comes BEFORE the q/k norms, whose head gives the
    embedding no gradient or whose gates are a softmax, the same Program
    FAILS at the same tolerance. The reference is a fresh copy of the
    module with ONE function moved."""
    reference = reference_module()
    _MOVED[rule](reference)
    cell = dict(_toy_cell(), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert not got['passed']
    assert max(got['grad_rel'].values()) > 1e-3


def test_layers_scopes_regions_and_counters():
    """Five layers off `layer_types` from `first_layer` on, each one
    recompute region; the short convolutions are built under
    `short_conv_mixer` (two projections, `split`, ONE `causal_conv1d` with
    both gates, no activation), the attention operator under
    `attention_mixer` (q/k norms BEFORE rotary); the scopes reach the
    optimized HLO's op_name; the mixers, the convolutions' form, the
    experts' form and the bias updates are counted."""
    from chipbench.harness import catalog
    cell = _toy_cell()
    moe = dict(path='grouped', held='4of16', dispatch='index',
               scoring='sigmoid')
    conv = dict(taps=3, act='none', gates='2')
    before = (obs.counter('moe.lowered', **moe).value,
              obs.counter('conv1d.lowered', **conv).value,
              obs.counter('moe.bias_updates').value,
              obs.counter('shortconv.mixers').value,
              obs.counter('shortconv.tokens').value)
    config, built = build_toy(cell, train=True)
    assert obs.counter('shortconv.mixers').value - before[3] == 4
    assert obs.counter('moe.bias_updates').value - before[2] == 4
    ops = built['main'].global_block().ops
    forward = [op for op in ops if not op.type.endswith('_grad')]
    kinds = [op.type for op in forward]
    assert kinds.count('causal_conv1d') == kinds.count('split') == 4
    assert kinds.count('flash_attention') == 1
    assert kinds.count('rotary_embedding') == 2
    assert kinds.count('moe_mlp') == 4
    # two norms a layer, the q/k norms, the final norm
    assert kinds.count('rms_norm') == 10 + 2 + 1
    # the head: the embedding through a transpose into the LAST mul
    muls = [i for i, k in enumerate(kinds) if k == 'mul']
    assert kinds[muls[-1] - 1] == 'transpose'
    assert forward[muls[-1] - 1].input('X') == ['lfm2_tok_emb']
    for i, op in enumerate(forward):
        scope = op.attrs.get('name_scope')
        if op.type in ('causal_conv1d', 'split'):
            assert scope == 'short_conv_mixer', op.type
        if op.type in ('flash_attention', 'rotary_embedding'):
            assert scope == 'attention_mixer'
        if op.type == 'rotary_embedding':
            # the norm, the transpose to heads, THEN the turn
            assert [o.type for o in forward[i - 2:i]] == ['rms_norm',
                                                          'transpose']
        if op.type == 'moe_mlp':
            assert scope is None and op.input('SelectionBias')
            assert op.attrs['norm_eps'] == 1e-6 and op.input('W3')
            assert op.attrs['experts_held'] == [4, 4]
            assert 'gate_scale' not in op.attrs       # the factor is 1
        if op.type == 'causal_conv1d':
            assert op.input('InGate') and op.input('OutGate')
            assert op.attrs['act'] == ''
        if op.type == 'flash_attention':
            assert op.attrs['causal']
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 5
    text = decoder_toy.one_step_hlo(cell, config, built)
    assert obs.counter('moe.lowered', **moe).value - before[0] >= 4
    assert obs.counter('conv1d.lowered', **conv).value - before[1] >= 4
    assert obs.counter('shortconv.tokens').value > before[4]
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    conv_scopes = window.op_scopes_under(text, 'short_conv_mixer')
    attn = window.op_scopes_under(text, 'attention_mixer')
    assert conv_scopes and attn and not conv_scopes & attn
    assert {s.rsplit('_', 1)[0] for s in conv_scopes} >= {
        'mul', 'causal_conv1d'}
    assert {s.rsplit('_', 1)[0] for s in attn} >= {'mul', 'flash_attention'}
    assert not any(s.startswith('moe_mlp') for s in conv_scopes | attn)
    # the head's mul is no mixer's: loss_head_ms reads the highest index
    from chipbench.harness import scopes
    every = {scopes.scope_of(name)
             for name in scopes.instruction_scopes(text).values()}
    top = max(index for kind, index in every - {None} if kind == 'mul')
    assert 'mul_%d' % top not in conv_scopes | attn


def test_a_layer_of_another_kind_is_refused():
    from paddle_tpu.models import lfm2_moe as L
    with framework.program_guard(framework.Program(), framework.Program()):
        with pytest.raises(ValueError, match="'conv' or 'full_attention'"):
            L.lfm2_moe(64, 16, layer_types=('conv', 'sliding'), hidden=16,
                       n_head=2, n_kv_head=1, d_head=8, dense_width=16,
                       n_expert=4, top_k=2, expert_width=8, n_dense=1)


def test_the_published_order_of_operators():
    from paddle_tpu.models import lfm2_moe as L
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'lfm2_8b_a1b.json')) as f:
        held = json.load(f)
    assert list(L.LAYER_TYPES) == held['layer_types']
    assert L.LAYER_TYPES.count('conv') == 18


def test_small_preset_trains_and_moves_its_biases():
    from paddle_tpu.models import lfm2_moe as L
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, train, _, feeds = L.get_model(experts_held=(4, 4))
    biases = [v.name for v in main.list_vars()
              if isinstance(v, framework.Parameter) and not v.trainable]
    assert len(biases) == 3
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
        moved = _get(fluid.global_scope(), biases[0])
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
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth, the dense layers that run, the experts
    held and the vocabulary are cut, and `layer_types` stands whole."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'lfm2_8b_a1b.json')) as f:
        held = json.load(f)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'LFM2-8B-A1B':
                    assert row['config'] == SOURCE
                    assert row['source_url'] == held['source']
    cut = {'num_hidden_layers': 5, 'num_dense_layers': 1, 'num_experts': 8,
           'vocab_size': 16384}
    for key, value in SOURCE.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: SOURCE[k] for k in cut}
    assert set(held['model']) - set(SOURCE) == {
        'head_dim', 'first_layer', 'first_expert_held', 'router_norm_eps',
        'bias_update_speed', 'initializer_range'}
    m = held['model']
    assert m['head_dim'] * m['num_attention_heads'] == m['hidden_size']
    assert (m['first_layer'], m['router_norm_eps']) == (1, 1e-6)
    # published layers 1 to 5: the second dense layer, then one whole
    # period (attention, three short convolutions) of expert layers
    run = held['layer_types'][1:6]
    assert run == ['conv', 'full_attention', 'conv', 'conv', 'conv']
    assert held['assumed']['layers_as_run'].startswith('1 to 5')
    # the floors of the guide: a whole period and four layers after the
    # dense ones, 8 experts, an eighth of the vocabulary
    assert held['num_hidden_layers'] - held['num_dense_layers'] >= 4
    assert held['num_experts'] >= 8
    assert held['vocab_size'] * 8 >= SOURCE['vocab_size']
    assert sorted(held['checks']) == ['amp', 'amp_experts', 'float32']
    for key in ('top_level_keys', 'num_hidden_layers', 'num_dense_layers',
                'num_experts', 'vocab_size', 'tied_head', 'head_dim',
                'intermediate_size', 'qk_norms', 'short_convolution',
                'router', 'bias_update_speed', 'router_aux_loss',
                'initializers', 'optimizer', 'document_mask',
                'recomputation'):
        assert held['assumed'][key], key
    assert '4 chips' in held['deployment']
    # what the issue asks the chip's comparison to hold: the tied
    # embedding, a mixer's in_proj and its filter, Wq or Wk, a router and
    # a held expert stack, in the timed arithmetic and in float32
    assert {'lfm2_tok_emb', 'fc_0.w_0', 'causal_conv1d_0.w_0',
            'fc_5.w_0'} <= set(held['checks']['amp']['grads'])
    assert {'lfm2_tok_emb', 'fc_0.w_0', 'causal_conv1d_0.w_0', 'fc_5.w_0',
            'fc_6.w_0'} <= set(held['checks']['float32']['grads'])
    assert any(g.startswith('moe_mlp_') and g.endswith('.w_0')
               for g in held['checks']['float32']['grads'])
    assert held['checks']['amp_experts']['grads'] == ['moe_mlp_3.w_3']
    for entry in held['checks'].values():
        assert len(entry['why']) > 400


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """Forward FLOPs a token at 16384 by part (ISSUE 44's count): layer 1
    121.6 M, layer 2's projections 21.0 and scores 67.1, the held experts
    22.0 a layer, layers 3 to 5 55.7 each, the head 67.1, 466 together
    and 22.9 TFLOP a step; the parameters of the deployment's table."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 16384
    m = config['model']
    assert flops.layer_counts(m) == (4, 1, 1, 4)
    assert flops.routed_experts(config) == 32
    assert flops.held_rows(config, 1, 16384) == 16384
    f = {k: v / tokens / 1e6
         for k, v in flops.forward_flops(config, 1, 16384).items()}
    assert f['shortconv_projections'] / 4 + f['dense'] == pytest.approx(
        121.6, abs=0.1)
    assert f['attention_projections'] == pytest.approx(21.0, abs=0.1)
    assert f['attention'] == pytest.approx(67.1, abs=0.1)
    assert f['experts'] / 4 == pytest.approx(22.0, abs=0.1)
    assert f['shortconv_projections'] / 4 + (f['experts'] + f['router']) \
        / 4 == pytest.approx(55.7, abs=0.1)
    assert f['head'] == pytest.approx(67.1, abs=0.1)
    assert sum(f.values()) == pytest.approx(466.1, abs=0.1)
    assert flops.train_step_flops(config, traffic) == pytest.approx(
        22.9e12, rel=2e-3)
    # the stage between a mixer's projections: 11 arrays of [T, 2048] bf16
    cost = flops.shortconv_cost(config, traffic)
    assert cost[0] == 3 * 4 * tokens * 2 * 4 * 2048 ** 2
    assert cost[1] == 4 * 11 * tokens * 2048 * 2
    kernels = flops.kernel_cost(config, traffic)
    assert set(kernels) == {'flash_attention', 'moe_mlp'}
    assert kernels['moe_mlp'][0] == 3 * 4 * 16384 * 3 * 2 * 2048 * 1792
    assert kernels['flash_attention'][0] == pytest.approx(
        3 * 67.1e6 * tokens, rel=1e-3)
    # parameters as built, by part (the configuration's `deployment`)
    built = cell['builder'].build(dict(config, check={'grads': []}),
                                  traffic, train=False)
    sizes = {v.name: int(np.prod(v.shape))
             for v in built['main'].list_vars()
             if isinstance(v, framework.Parameter)}
    assert sum(sizes.values()) == 507820288
    assert sizes['lfm2_tok_emb'] == 16384 * 2048
    assert sizes['fc_0.w_0'] + sizes['causal_conv1d_0.w_0'] \
        + sizes['fc_1.w_0'] == 16783360
    assert sum(sizes['moe_mlp_0.w_%d' % i] for i in (1, 2, 3)) == 88080384
    assert '507.8 M parameters' in config['deployment']


def test_new_readers_read_their_scope_or_nothing():
    """`shortconv_ms` and `shortconv_roofline` on a hand-made reduction
    and a hand-made HLO; on a program that names no such scope (the
    parent's) nothing, and no error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(short_conv_mixer)/jvp(mul_4)/dot_general"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/transpose(jvp(short_conv_mixer))/'
        'transpose(jvp(causal_conv1d_1))/mul"}',
        '  %custom-call.3 = bf16[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/checkpoint/jvp(attention_mixer)/'
        'jvp(flash_attention_0))/pallas_call"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(mul_20)/dot_general"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(short_conv_mixer_like)/jvp(mul_21)/dot"}',
    ])
    red = {'steps': 5,
           'fluid_scope_s': {'mul_4': 0.30, 'causal_conv1d_1': 0.05,
                             'flash_attention_0': 0.25, 'mul_20': 1.0,
                             'mul_21': 1.0},
           'fluid_op_s': {'causal_conv1d': 0.05, 'mul': 2.3}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite']}
    assert catalog.load_reader('shortconv_ms')(reading) == pytest.approx(70.0)
    share = catalog.load_reader('shortconv_roofline')(reading)
    flop, nbytes = cell['flops'].shortconv_cost(cell['config'],
                                                cell['traffic'], 1)
    least = flop / 197e12 + nbytes / 819e9
    assert share == pytest.approx(100 * least / 0.07) and 0 < share < 100
    # the two times ADD: the stage is the memory's, after the MXU's
    assert least > peaks.roofline((flop, nbytes), reading['peaks'])[0]
    for other in (dict(reading, hlo=hlo.replace('short_conv_mixer', 'x')),
                  dict(reading, trace=None), dict(reading, hlo=None)):
        for name in ('shortconv_ms', 'shortconv_roofline'):
            assert catalog.load_reader(name)(other) is None, name
    # a configuration without `shortconv_cost` (every older one) has no
    # share to report
    older = catalog.load_cell('nemotron3nano_s8192')
    assert catalog.load_reader('shortconv_roofline')(
        dict(reading, cell=older)) is None
