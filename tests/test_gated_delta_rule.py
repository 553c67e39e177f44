"""The gated delta rule as an op (ops_impl/linear_attention_ops.py, ISSUE
30; a decay a channel since ISSUE 55): the chunked rule against the
token-by-token recurrence on values and every gradient, a decay a channel
against a decay a head and at its floor, the blockwise solve against
substitution. The models that run the op (tests/test_qwen3_next.py,
tests/test_bailing_hybrid.py) test their layers, not the rule. Small
sizes, on the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from util import input_parameter as _input, out_and_grads


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

    def chunked(*a):
        return la.gated_delta_rule(*a, chunk_size=chunk, qk_l2norm=True,
                                   gate_floor=floor)

    with jax.default_matmul_precision('highest'):
        got, g_got = out_and_grads(chunked, args, weight)
        want, g_want = out_and_grads(plain_delta_net, args, weight)
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
        both = jax.jit(jax.value_and_grad(loss), static_argnums=1)
        by_head, d_head = both(g, None)
        by_channel, d_channel = both(wide, -5.0)
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
    floored = jax.jit(lambda g: la.gated_delta_rule(
        q, k, v, g, beta, chunk_size=16, gate_floor=-5.0))
    np.testing.assert_array_equal(floored(g - 10.0),
                                  floored(jnp.full_like(g, -5.0)))
    d_of = jax.jit(jax.grad(lambda g: jnp.sum(floored(g))))
    d = d_of(jnp.full_like(g, -5.0))
    d_in = d_of(jnp.full_like(g, -5.0 + 1e-4))
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
