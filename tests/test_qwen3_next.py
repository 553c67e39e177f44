"""Qwen3-Next on the normal path (ISSUE 30): the chunked gated delta rule
against the token-by-token recurrence, the causal convolution, partial
rotary and grouped key-value heads against few-line formulas, an expert
layer that holds a SHARE of its experts (the shares add up to the whole
layer; absent rows cost nothing and poison nothing; the held rows are
reached by index, once a layer: ISSUE 31), the whole toy model
against the benchmark's plain reference, the counters and scopes, and the
configuration's file. Small sizes, on the CPU."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, unique_name
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.fluid.ops_impl import moe_ops
from util import nan_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, 'tests', 'test_chipbench'))


# ---------------------------------------------------------------- delta rule

def recurrence(q, k, v, g, beta):
    """The definition, token by token: q, k [B, T, H, Dk] (already
    normalised, scaled and repeated), v [B, T, H, Dv], g, beta [B, T, H];
    a g of [B, T, H, Dk] is a decay a CHANNEL (a row of the state)."""
    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        # g [B, H]: a decay a head; [B, H, Dk]: a decay a row of the state
        s = s * jnp.exp(g_t)[(Ellipsis,) + (None,) * (4 - g_t.ndim)]
        write = b_t[..., None] * (v_t - jnp.einsum('bhkv,bhk->bhv', s, k_t))
        s = s + k_t[..., :, None] * write[..., None, :]
        return s, jnp.einsum('bhkv,bhk->bhv', s, q_t)

    s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(token, s0, tuple(jnp.moveaxis(a, 1, 0)
                                         for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def plain_delta_net(q, k, v, g, beta):
    """The op's contract on the recurrence: l2 norm, q / sqrt(Dk), each
    key head serving Hv / Hk value heads."""
    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    rep = v.shape[2] // q.shape[2]
    q, k = l2(q) * q.shape[-1] ** -0.5, l2(k)
    return recurrence(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g,
                      beta)


def delta_inputs(seed, t, gates):
    rng = np.random.default_rng(seed)
    b, hk, hv, dk, dv = 2, 2, 4, 16, 8
    q, k = (jnp.asarray(rng.normal(size=(b, t, hk, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, t, hv, dv)), jnp.float32)
    if gates == 'plain':            # the delta rule without its gates
        g, beta = jnp.zeros((b, t, hv)), jnp.ones((b, t, hv))
    elif gates == 'strong':         # a state forgotten within a few tokens
        g = -jnp.asarray(rng.uniform(5, 12, size=(b, t, hv)), jnp.float32)
        beta = jnp.asarray(rng.uniform(0, 1, size=(b, t, hv)), jnp.float32)
    elif gates.startswith('channel'):
        # a decay a channel, within its floor of -5: everywhere in (-5, 0),
        # or ('channel_floor') most of it AT the floor, a saturated gate
        g = -jnp.asarray(rng.uniform(0, 5, size=(b, t, hv, dk)), jnp.float32)
        if gates == 'channel_floor':
            g = jnp.where(jnp.asarray(rng.uniform(size=g.shape)) < 0.7,
                          -5.0, g)
        beta = jnp.asarray(rng.uniform(0, 1, size=(b, t, hv)), jnp.float32)
    else:
        g = -jnp.asarray(rng.uniform(0, 0.3, size=(b, t, hv)), jnp.float32)
        beta = jnp.asarray(rng.uniform(0, 1, size=(b, t, hv)), jnp.float32)
    return q, k, v, g, beta


# (T, chunk): chunks that divide T and that do not, a blockwise solve (64
# = 4 x 16, 32), plain forward substitution (8, 24), one chunk, many
SHAPES = [(64, 64), (128, 32), (40, 16), (50, 32), (37, 8), (72, 24),
          (200, 64)]


@pytest.mark.parametrize('gates', ['mild', 'plain', 'strong', 'channel',
                                   'channel_floor'])
@pytest.mark.parametrize('t,chunk', SHAPES)
def test_chunked_delta_rule_is_the_recurrence(t, chunk, gates):
    """Forward and the gradient of every input, float32 on the host; with
    a decay a channel ([B, T, H, Dk]) too, down to its floor."""
    args = delta_inputs(t, t, gates)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    floor = -5.0 if args[3].ndim == 4 else None
    with jax.default_matmul_precision('highest'):
        def chunked(*a):
            return la.gated_delta_rule(*a, chunk_size=chunk, qk_l2norm=True,
                                       gate_floor=floor)

        got = chunked(*args)
        want = plain_delta_net(*args)
        g_got = jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                         argnums=range(5))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(plain_delta_net(*a) * weight),
                          argnums=range(5))(*args)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * scale
    for name, a, b in zip('q k v g beta'.split(), g_got, g_want):
        err = float(jnp.linalg.norm(a - b))
        assert err < 3e-4 * float(jnp.linalg.norm(b)) + 1e-7, (name, err)


def test_a_decay_constant_over_a_heads_channels_is_the_decay_a_head():
    """g [B, T, H, Dk] with one value a head gives what g [B, T, H] gives,
    values and gradients (g's summed over the channels)."""
    q, k, v, g, beta = delta_inputs(3, 100, 'mild')
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    weight = jnp.asarray(np.random.default_rng(1).normal(size=v.shape),
                         jnp.float32)

    def loss(g, floor):
        return jnp.sum(weight * la.gated_delta_rule(
            q, k, v, g, beta, chunk_size=64, qk_l2norm=True,
            gate_floor=floor))

    with jax.default_matmul_precision('highest'):
        by_head, d_head = jax.value_and_grad(loss)(g, None)
        by_channel, d_channel = jax.value_and_grad(loss)(wide, -5.0)
    np.testing.assert_allclose(by_channel, by_head, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(d_channel, -1), d_head, rtol=1e-3,
                               atol=1e-5)


def test_a_decay_a_channel_needs_a_floor_it_can_exponentiate():
    """The rule refuses a per-channel g without `gate_floor`, and one whose
    half block of 16 rows overflows a float32 (8 x 6 > 44); it holds g to
    the floor, and a g AT the floor keeps its whole gradient."""
    q, k, v, g, beta = delta_inputs(3, 32, 'channel')
    for floor in (None, -6.0, 1.0):
        with pytest.raises(ValueError, match='gate_floor'):
            la.gated_delta_rule(q, k, v, g, beta, chunk_size=16,
                                gate_floor=floor)
    # a chunk of 8 is one block of 8: 4 x 6 = 24 is taken
    la.gated_delta_rule(q, k, v, g, beta, chunk_size=8, gate_floor=-6.0)
    below = la.gated_delta_rule(q, k, v, g - 10.0, beta, chunk_size=16,
                                gate_floor=-5.0)
    at = la.gated_delta_rule(q, k, v, jnp.full_like(g, -5.0), beta,
                             chunk_size=16, gate_floor=-5.0)
    np.testing.assert_array_equal(below, at)
    d = jax.grad(lambda g: jnp.sum(la.gated_delta_rule(
        q, k, v, g, beta, chunk_size=16, gate_floor=-5.0)))(
            jnp.full_like(g, -5.0))
    d_in = jax.grad(lambda g: jnp.sum(la.gated_delta_rule(
        q, k, v, g, beta, chunk_size=16, gate_floor=-5.0)))(
            jnp.full_like(g, -5.0 + 1e-4))
    np.testing.assert_allclose(d, d_in, rtol=2e-2, atol=1e-6)
    with pytest.raises(ValueError, match='gate_floor'):
        layers.gated_delta_rule(
            *(_input(n, a) for n, a in zip('qkvgb', (q, k, v, g, beta))))


def test_unit_lower_inverse_blockwise_equals_substitution():
    rng = np.random.default_rng(2)
    a = jnp.tril(jnp.asarray(rng.normal(size=(3, 64, 64)), jnp.float32), -1)
    want = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    for fn in (la._inverse, la._forward_substitution):
        np.testing.assert_allclose(fn(a), want, rtol=2e-4, atol=2e-4)
    # its own backward: d(L^-1) = -L^-1 dL L^-1 on the strict lower part
    w = jnp.asarray(rng.normal(size=(3, 64, 64)), jnp.float32)
    got = jax.grad(lambda m: jnp.sum(la._unit_lower_inverse(m) * w))(a)
    want = jax.grad(lambda m: jnp.sum(la._forward_substitution(
        jnp.tril(m, -1)) * w))(a)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _input(name, value):
    return layers.create_parameter(
        list(value.shape), 'float32', name=name,
        default_initializer=fluid.initializer.NumpyArrayInitializer(
            np.asarray(value)))


def _grads_of(build, feed, wrt, amp=False, optimized=False):
    """Runs a small Program forward and backward; returns (out, grads,
    the lowered text)."""
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out = build()
        loss = layers.reduce_sum(layers.elementwise_mul(
            out, layers.data(name='w', shape=list(out.shape),
                             dtype='float32', append_batch_size=False)))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
        if amp:
            fluid.amp.decorate_program(main)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetch = [out] + [grads[n] for n in wrt]
        res = exe.run(main, feed=feed, fetch_list=fetch)
        text = exe.lowered_hlo(main, feed, fetch, optimized=optimized)
    return res[0], res[1:], text


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


# ----------------------------------------------------------------- the share

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
    scope.find_var('moe_mlp_0.w_0').get_tensor().set(weights[0], place)
    for i in (1, 2, 3):
        scope.find_var('moe_mlp_0.w_%d' % i).get_tensor().set(
            weights[i][first:first + count], place)


def run_share(held, xs, weights=None):
    main, startup, out, aux, count = build_share(held, n=len(xs))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if weights is not None:
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


# ----------------------------------------------------------------- the model

def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/qwen3_next.py through the Executor against
    chipbench/references/qwen3_next.py in float32: the loss and the
    gradient of EVERY parameter of one period (three DeltaNet layers, one
    attention layer, four expert blocks holding experts 4..7 of 16)."""
    import chipbench_toy as toy
    from chipbench.harness import check
    cell = toy.load_toy_cell('qwen3next_s8192')
    assert cell['builder'].experts(cell['config']) == (16, (4, 4))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        built = cell['builder'].build(cell['config'], cell['traffic'])
        exe.run(built['startup'])
        names = check.parameter_names(built['main'])
        entry = dict(cell['config']['checks']['float32'], grads=names,
                     tolerance={'loss': 1e-5, 'grad': 5e-4})
        got = check.run_check(cell, exe, fluid.global_scope(), 5, entry)
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
