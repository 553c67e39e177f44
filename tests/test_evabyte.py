"""EvaByte on the normal path (ISSUE 59): the toy model (two layers of an
EVA mixer and a SwiGLU, windows of 64, chunks of 8, rows of 256, eight
next-byte heads over 320 ids) against the plain reference on the loss and
every gradient; each rule the configuration forced FAILING the comparison
when moved in the reference; the mixer against a double loop over queries
and keys; the staircase and aligned geometries of the flash kernels in
interpret mode against the dense masks, forward, every gradient and the
log-sum-exp's cotangent; the merged pair against one dense softmax; the
pooling against jax.numpy; the reference's walk with its weights on the
host against `jax.grad` of the same function in one piece; name scopes,
regions, counters, the configuration's file against the catalog's row, its
FLOPs and its readers. Small sizes, on the CPU."""
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
from paddle_tpu.ops.flash_attention import (
    _stair_lse, _stair_maps, _stair_maps_kv, flash_attention_summary,
    merge_lse, reference_attention_summary, summary_blocks)

import decoder_toy
from decoder_toy import REPO, build_toy, check_all

CELL = 'evabyte_s8192'

reference_module = functools.partial(decoder_toy.reference_module, 'evabyte')
_toy_cell = functools.partial(decoder_toy.toy_cell, CELL)

# trainable parameters a layer: two norms, Wq, Wk, Wv, mu, phi, Wo, and
# the SwiGLU's three matrices
_PER_LAYER = 11


def test_toy_model_agrees_with_the_plain_reference_on_every_gradient():
    """models/evabyte.py through the Executor against
    chipbench/references/evabyte.py in float32 to 1e-5: the loss and the
    gradient of EVERY trainable parameter over rows of 256 (four windows of
    64, so three of them attend through the staircase of summaries; the
    two learned vectors a head, the norms' offsets, the eight-headed head
    among them); and under bf16 AMP within a stated tolerance."""
    cell = _toy_cell()
    names, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert len(names) == 1 + 2 * _PER_LAYER + 2
    assert set(got['grad_rel']) == set(names)
    assert got['passed'], got
    assert abs(got['loss'] - np.log(320)) < 0.05
    _, amp = check_all(cell, {'loss': 1e-3, 'grad': 0.25}, amp='amp')
    assert amp['passed'], amp


def _tree(cell):
    with fluid.scope_guard(fluid.Scope()):
        _, built = build_toy(cell, train=False)
        return cell['builder'].reference_params(
            cell['config'], built['main'], lambda n: n)


# ---------------------------------------------------------------- moved rules

def _dense_eva(sliding=False, own_window=False, two_softmaxes=False,
               exchanged=False, phi_unscaled=False):
    """The mixer over the WHOLE row at once, scores [B, H, T, T + T / c],
    with one rule moved: a second writing of the reference's `eva` (with
    nothing moved the two agree; the double-loop test holds both)."""
    def move(ref):
        def eva(w, u, model):
            h = model['num_attention_heads']
            d = model['hidden_size'] // h
            c, win = model['chunk_size'], model['window_size']
            scale = d ** -0.5
            bsz, t, _ = u.shape
            q, k, v = (ref.rotary((u @ w[n]).reshape(bsz, t, h, d),
                                  model['rope_theta']) if n != 'v'
                       else (u @ w[n]).reshape(bsz, t, h, d)
                       for n in ('q', 'k', 'v'))
            mu, phi = (w['phi'], w['mu']) if exchanged else \
                (w['mu'], w['phi'])
            kbar, vbar = ref.pool(k, v, mu, phi * (1.0 / scale)
                                  if phi_unscaled else phi, c, scale)
            pos = jnp.arange(t)
            first = jnp.arange(t // c) * c
            if sliding:
                seen = (pos[:, None] >= pos[None, :]) & (
                    pos[:, None] - pos[None, :] < win)
            else:
                seen = (pos[:, None] >= pos[None, :]) & (
                    pos[:, None] // win == pos[None, :] // win)
            before = pos[:, None] // win >= first[None, :] // win \
                if own_window else \
                pos[:, None] // win > first[None, :] // win
            se = jnp.where(seen, jnp.einsum('bqhd,bkhd->bhqk', q, k)
                           * scale, -jnp.inf)
            ss = jnp.where(before, jnp.einsum('bqhd,bnhd->bhqn', q, kbar)
                           * scale, -jnp.inf)
            if two_softmaxes:
                pe = jax.nn.softmax(se, -1)
                ps = jnp.where(before.any(-1)[:, None],
                               jax.nn.softmax(jnp.where(
                                   before.any(-1)[:, None], ss, 0.0), -1),
                               0.0)
            else:
                p = jax.nn.softmax(jnp.concatenate([se, ss], -1), -1)
                pe, ps = p[..., :t], p[..., t:]
            o = jnp.einsum('bhqk,bkhd->bqhd', pe, v) \
                + jnp.einsum('bhqn,bnhd->bqhd', ps, vbar)
            return o.reshape(bsz, t, h * d) @ w['out']
        ref.eva = eva
    return move


def _head_loss_by(picked):
    """A head_loss whose head j's mean is over `picked(logp_j [B, T,
    vocab], labels, j)`, the log-probabilities it gathers."""
    def move(ref):
        def head_loss(x, w_norm, w_head, labels, model):
            p, vocab = model['num_pred_heads'], model['vocab_size']
            bsz, t, _ = x.shape
            logp = jax.nn.log_softmax((ref.rms(
                x, w_norm, model['rms_norm_eps']) @ w_head).reshape(
                bsz, t, p, vocab), -1)
            return sum(-jnp.mean(picked(logp[:, :, j], labels, j))
                       for j in range(p)) / p
        ref.head_loss = head_loss
    return move


def _gather(logp, labels):
    return jnp.take_along_axis(logp, labels[..., None], -1)


# every head trained on the NEXT byte: head j at t against labels[t]
_labels_not_shifted = _head_loss_by(lambda logp, labels, j: _gather(
    logp[:, :logp.shape[1] - j], labels[:, :labels.shape[1] - j]))
# head j's mean over ALL T positions, its last j read against id 0
_tail_not_left_out = _head_loss_by(lambda logp, labels, j: _gather(
    logp, jnp.pad(labels, ((0, 0), (0, j)))[:, j:]))


def _norm_without_unit_offset(ref):
    ref.rms = lambda t, w, eps: w * t * jax.lax.rsqrt(
        jnp.mean(jnp.square(t), -1, keepdims=True) + eps)


_MOVED = {
    'sliding_window_for_aligned': _dense_eva(sliding=True),
    'own_windows_summaries_seen': _dense_eva(own_window=True),
    'two_softmaxes_outputs_added': _dense_eva(two_softmaxes=True),
    'mu_and_phi_exchanged': _dense_eva(exchanged=True),
    'scale_missing_from_phis_logits': _dense_eva(phi_unscaled=True),
    'labels_not_shifted_by_head': _labels_not_shifted,
    'tail_not_left_out': _tail_not_left_out,
    'norm_without_unit_offset': _norm_without_unit_offset,
}


def test_the_dense_writing_of_the_mixer_is_the_reference_unmoved():
    """`_dense_eva` with nothing moved against the unmoved reference: the
    moved cases below differ from it by their one rule."""
    reference = reference_module()
    _dense_eva()(reference)
    _, got = check_all(dict(_toy_cell(), reference=reference),
                       {'loss': 1e-5, 'grad': 1e-5})
    assert got['passed'], got


@pytest.mark.parametrize('rule', sorted(_MOVED))
def test_a_moved_rule_fails_the_comparison(rule):
    """The comparison above holds what this configuration forced: against
    a reference whose window slides, whose queries see their own window's
    summaries, whose two key sets have a softmax each, whose learned
    vectors are exchanged or whose phi has no scale, whose heads are all
    trained on the next byte or over the row's tail, or whose norm has no
    unit offset, the same Program FAILS at the same tolerance. The
    reference is a fresh copy of the module with ONE function moved."""
    reference = reference_module()
    _MOVED[rule](reference)
    cell = dict(_toy_cell(), reference=reference)
    _, got = check_all(cell, {'loss': 1e-5, 'grad': 1e-5})
    assert not got['passed']
    assert max(got['grad_rel'].values()) > 1e-3


def test_mixer_against_a_double_loop_over_queries_and_keys():
    """The reference's `eva` at T = 128 (four windows of 32, chunks of 4)
    against the equations walked one query and one key at a time in
    numpy float64: the exact keys of the query's own window up to it, the
    summaries of the chunks in windows before it, one normaliser."""
    ref = reference_module()
    model = {'num_attention_heads': 2, 'hidden_size': 16, 'chunk_size': 4,
             'window_size': 32, 'rope_theta': 100000}
    rng = np.random.default_rng(3)
    t, h, d, c, win = 128, 2, 8, 4, 32
    w = {n: rng.normal(size=(16, 16)).astype('float32') * 0.3
         for n in ('q', 'k', 'v', 'out')}
    w['mu'], w['phi'] = (rng.normal(size=(h, d)).astype('float32')
                         for _ in range(2))
    u = rng.normal(size=(1, t, 16)).astype('float32')
    with jax.default_matmul_precision('highest'):
        got = np.asarray(ref.eva(
            {k: jnp.asarray(v) for k, v in w.items()}, jnp.asarray(u),
            model))[0]

    def rot(x):                                      # [T, H, D]
        out = np.empty_like(x)
        for pos in range(t):
            for j in range(d // 2):
                a = pos * 100000.0 ** (-2.0 * j / d)
                x0, x1 = x[pos, :, j], x[pos, :, j + d // 2]
                out[pos, :, j] = x0 * np.cos(a) - x1 * np.sin(a)
                out[pos, :, j + d // 2] = x1 * np.cos(a) + x0 * np.sin(a)
        return out

    u64 = u[0].astype(np.float64)
    q, k, v = ((u64 @ w[n].astype(np.float64)).reshape(t, h, d)
               for n in ('q', 'k', 'v'))
    q, k = rot(q), rot(k)
    s = d ** -0.5
    want = np.zeros((t, h, d))
    for head in range(h):
        kbar, vbar = np.zeros((t // c, d)), np.zeros((t // c, d))
        for n in range(t // c):
            ms = range(c * n, c * n + c)
            a = np.exp([w['mu'][head] @ k[m, head] for m in ms])
            b = np.exp([s * (w['phi'][head] @ k[m, head]) for m in ms])
            kbar[n] = sum(x * k[m, head] for x, m in zip(a / a.sum(), ms))
            vbar[n] = sum(x * v[m, head] for x, m in zip(b / b.sum(), ms))
        for pos in range(t):
            top, bottom = np.zeros(d), 0.0
            for m in range(pos // win * win, pos + 1):          # E_t
                e = np.exp(s * (q[pos, head] @ k[m, head]))
                top, bottom = top + e * v[m, head], bottom + e
            for n in range(pos // win * win // c):              # P_t
                e = np.exp(s * (q[pos, head] @ kbar[n]))
                top, bottom = top + e * vbar[n], bottom + e
            want[pos, head] = top / bottom
    want = want.reshape(t, h * d) @ w['out'].astype(np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------- kernels and ops

def _qkv(seed, b=2, h=2, t=256, d=16, every=8, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, t, d)), dtype)
               for _ in range(3))
    kbar, vbar = (jnp.asarray(rng.normal(size=(b, h, t // every, d)), dtype)
                  for _ in range(2))
    return q, k, v, kbar, vbar


# tests/test_flash_attention.py's tolerances: float32 2e-5 forward and
# 3e-4 on the gradients (a gradient sums a tile's rows in another order
# than the dense chain); bf16 2 eps forward and 4 eps on the gradients
# (its arithmetic stands there), the merge adding nothing: its weights
# come from float32 log-sum-exps and multiply in float32
BF16_EPS = 2.0 ** -8


@pytest.mark.parametrize('summaries', [True, False],
                         ids=['staircase_and_aligned', 'aligned_alone'])
@pytest.mark.parametrize('tiles', [(32, 8), (64, 8), (16, 4)])
def test_geometries_match_the_dense_masks_forward_and_gradients(tiles,
                                                                summaries):
    """`flash_attention_summary` in interpret mode (windows of 64, a
    summary every 8, rows of 256: the staircase's q-blocks and summary
    blocks at several tiles, the aligned part on the triangular grid of
    64-rows) against the dense masks under one softmax, forward and the
    gradients of q, k, v and both summaries."""
    q, k, v, kbar, vbar = _qkv(1)
    args = (q, k, v) + ((kbar, vbar) if summaries else ())
    kw = dict(window=64, every=8) if summaries else dict(window=64)

    def flash(*a):
        return flash_attention_summary(*a, block_q=tiles[0],
                                       block_k=tiles[1], interpret=True,
                                       **kw)

    def dense(*a):
        return reference_attention_summary(*a, **kw)

    np.testing.assert_allclose(np.asarray(flash(*args)),
                               np.asarray(dense(*args)), rtol=2e-5,
                               atol=2e-5)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=q.shape),
                     jnp.float32)
    nums = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), argnums=nums)(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), argnums=nums)(*args)
    for a, b, name in zip(got, want, ('q', 'k', 'v', 'kbar', 'vbar')):
        assert float(jnp.abs(b).max()) > 0.1, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


def test_staircase_alone_with_the_log_sum_exps_cotangent():
    """The staircase's custom_vjp by itself: (o, lse) over the queries
    from the second window on against the summaries before the last
    window, every block admitted or skipped whole, against a dense
    masked softmax; the gradients of a loss that reads BOTH outputs (the
    merge hands the kernels an lse cotangent, folded into delta)."""
    q, _, _, kbar, vbar = _qkv(4)
    window, per, bq, bk = 64, 8, 32, 4
    q_s, kb, vb = q[:, :, window:], kbar[:, :, :-per], vbar[:, :, :-per]

    def flash(q_s, kb, vb):
        return _stair_lse(q_s, kb, vb, 0.25, bq, bk, window // bq,
                          per // bk, True)

    def dense(q_s, kb, vb):
        s = jnp.einsum('bhqd,bhnd->bhqn', q_s, kb) * 0.25
        seen = (jnp.arange(q_s.shape[2])[:, None] // window
                >= jnp.arange(kb.shape[2])[None, :] // per)
        s = jnp.where(seen, s, -jnp.inf)
        return (jnp.einsum('bhqn,bhnd->bhqd', jax.nn.softmax(s, -1), vb),
                jax.scipy.special.logsumexp(s, axis=-1))

    for a, b in zip(flash(q_s, kb, vb), dense(q_s, kb, vb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)

    def loss(f):
        def of(*a):
            o, lse = f(*a)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))
        return of

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q_s, kb, vb)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q_s, kb, vb)
    for a, b, name in zip(got, want, ('q', 'kbar', 'vbar')):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


def test_bf16_operands_hold_the_bf16_tolerance():
    """bf16 in: 2 eps forward and 4 eps on the gradients against the
    float32 dense chain on the same (rounded) inputs."""
    args = _qkv(6, dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    kw = dict(window=64, every=8)
    got = flash_attention_summary(*args, block_q=32, block_k=8,
                                  interpret=True, **kw)
    assert got.dtype == jnp.bfloat16
    want = reference_attention_summary(*wide, **kw)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        <= 2 * BF16_EPS * scale
    ct = jnp.asarray(np.random.default_rng(7).integers(-2, 3, got.shape),
                     jnp.float32)
    nums = tuple(range(5))
    g = jax.grad(lambda *a: jnp.sum(flash_attention_summary(
        *a, block_q=32, block_k=8, interpret=True, **kw).astype(
        jnp.float32) * ct), argnums=nums)(*args)
    w = jax.grad(lambda *a: jnp.sum(reference_attention_summary(
        *a, **kw) * ct), argnums=nums)(*wide)
    for a, b, name in zip(g, w, ('q', 'k', 'v', 'kbar', 'vbar')):
        assert a.dtype == jnp.bfloat16
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) \
            <= 4 * BF16_EPS * float(jnp.abs(b).max()), name


def test_merged_pair_is_one_dense_softmax():
    """`merge_lse` of two partial attentions over DISJOINT key sets of
    different lengths against one softmax over their union; a set that
    admits nothing (lse -inf-like, o anything finite) leaves the other as
    it is; ring attention imports the same function."""
    import importlib
    import inspect
    ring = importlib.import_module('paddle_tpu.parallel.ring_attention')
    assert 'merge_lse(o, lse, o_s, lse_s)' in inspect.getsource(
        ring._ring_attention_flash)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)), jnp.float32)
    k1, v1 = (jnp.asarray(rng.normal(size=(1, 2, 24, 8)), jnp.float32)
              for _ in range(2))
    k2, v2 = (jnp.asarray(rng.normal(size=(1, 2, 5, 8)), jnp.float32)
              for _ in range(2))

    def part(k, v):
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k)
        return (jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v),
                jax.scipy.special.logsumexp(s, -1))

    o, lse = merge_lse(*part(k1, v1), *part(k2, v2))
    want, want_lse = part(jnp.concatenate([k1, k2], 2),
                          jnp.concatenate([v1, v2], 2))
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-6, atol=2e-6)
    o1, lse1 = part(k1, v1)
    alone, _ = merge_lse(o1, lse1, jnp.zeros_like(o1),
                         jnp.full_like(lse1, -1e30))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(o1))


def test_staircase_maps_hold_the_admitted_blocks_once_each():
    """Both enumerations list exactly the (q-block, summary-block) pairs
    whose summaries lie in an earlier window, once each; a q-block's
    pairs and a summary block's pairs are consecutive (an accumulator
    runs over them)."""
    for nq, qpw, spw in ((12, 4, 1), (6, 2, 2), (3, 1, 4), (8, 4, 2)):
        want = {(i, j) for i in range(nq)
                for j in range((i // qpw + 1) * spw)}
        im, jm = _stair_maps(nq, qpw, spw)
        im2, jm2 = _stair_maps_kv(nq, qpw, spw)
        for a, b in ((im, jm), (im2, jm2)):
            assert len(a) == len(want)
            assert set(zip(a.tolist(), b.tolist())) == want
        assert (np.diff(im) >= 0).all() and (np.diff(jm2) >= 0).all()
    assert summary_blocks(2048, 16) == (512, 128)
    assert summary_blocks(4096, 16) == (512, 256)
    assert summary_blocks(64, 8) is None and summary_blocks(2048, 64) is None


def test_counters_say_which_geometries_a_call_took():
    q, k, v, kbar, vbar = _qkv(9, b=1, h=1)

    def count():
        return {g: obs.counter('flash.forward', geometry=g).value
                for g in ('aligned', 'staircase')}
    before = count()
    flash_attention_summary(q, k, v, kbar, vbar, window=64, every=8,
                            block_q=32, block_k=8, interpret=True)
    flash_attention_summary(q, k, v, window=64, interpret=True)
    # one window: the exact part is the answer
    flash_attention_summary(q, k, v, kbar, vbar, window=256, every=8,
                            block_q=32, block_k=8, interpret=True)
    after = count()
    assert after['aligned'] - before['aligned'] == 3
    assert after['staircase'] - before['staircase'] == 1
    with pytest.raises(ValueError, match='do not divide a row'):
        flash_attention_summary(q, k, v, window=96, interpret=True)
    with pytest.raises(ValueError, match='cover the row'):
        flash_attention_summary(q, k, v, kbar[:, :, :-1], vbar[:, :, :-1],
                                window=64, every=8, interpret=True)
    with pytest.raises(ValueError, match='no staircase tiles'):
        flash_attention_summary(q, k, v, kbar, vbar, window=64, every=8,
                                interpret=True)


def test_chunk_softmax_pool_against_jax_numpy():
    """The op's rule (custom vjp that keeps k, v and the two weight
    arrays) against the reference's `pool` and its autodiff, forward and
    the gradients of k, v, mu and phi; bf16 keys and values give float32
    logits and weights."""
    from paddle_tpu.fluid.ops_impl.linear_attention_ops import \
        chunk_softmax_pool
    ref = reference_module()
    rng = np.random.default_rng(10)
    k, v = (jnp.asarray(rng.normal(size=(2, 3, 32, 8)), jnp.float32)
            for _ in range(2))
    mu, phi = (jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
               for _ in range(2))

    def plain(k, v, mu, phi):
        kb, vb = ref.pool(k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                          mu, phi, 4, 0.3)
        return kb.transpose(0, 2, 1, 3), vb.transpose(0, 2, 1, 3)

    got, want = chunk_softmax_pool(k, v, mu, phi, 4, 0.3), plain(k, v, mu,
                                                                 phi)
    for a, b in zip(got, want):
        assert a.shape == (2, 3, 8, 8)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)

    def loss(f):
        return lambda *a: sum(jnp.sum(jnp.sin(x)) for x in f(*a))
    g = jax.grad(loss(lambda *a: chunk_softmax_pool(*a, 4, 0.3)),
                 argnums=(0, 1, 2, 3))(k, v, mu, phi)
    w = jax.grad(loss(plain), argnums=(0, 1, 2, 3))(k, v, mu, phi)
    for a, b, name in zip(g, w, ('k', 'v', 'mu', 'phi')):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
    low = chunk_softmax_pool(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                             mu, phi, 4, 0.3)
    assert low[0].dtype == low[1].dtype == jnp.bfloat16
    wide = plain(k.astype(jnp.bfloat16).astype(jnp.float32),
                 v.astype(jnp.bfloat16).astype(jnp.float32), mu, phi)
    for a, b in zip(low, wide):          # one rounding of the result
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) \
            <= BF16_EPS * float(jnp.abs(b).max())


def test_layers_refuse_what_the_op_does_not_compute():
    from paddle_tpu.fluid import layers
    with framework.program_guard(framework.Program(), framework.Program()):
        q = layers.data(name='q', shape=[2, 64, 8], dtype='float32')
        s = layers.data(name='s', shape=[2, 8, 8], dtype='float32')
        vec = layers.create_parameter([2, 8], 'float32')
        kbar, vbar = layers.chunk_softmax_pool(q, q, vec, vec, chunk=8)
        assert tuple(kbar.shape[1:]) == tuple(vbar.shape[1:]) == (2, 8, 8)
        out = layers.fused_attention(q, q, q, causal=True, aligned_window=32,
                                     summary=(kbar, vbar), summary_every=8)
        assert tuple(out.shape[1:]) == (2, 64, 8)
        for kw, said in (
                (dict(aligned_window=32), 'causal self-attention'),
                (dict(causal=True, aligned_window=32, window=8),
                 'causal self-attention'),
                (dict(causal=True, aligned_window=48), 'do not divide'),
                (dict(causal=True, summary=(s, s), summary_every=8),
                 'belong to aligned windows'),
                (dict(causal=True, aligned_window=32, summary=(s, s)),
                 'come together'),
                (dict(causal=True, aligned_window=32, summary=(s, s),
                      summary_every=16), 'one summary every'),
                (dict(causal=True, aligned_window=32, summary=(s, s),
                      summary_every=5), 'one summary every')):
            with pytest.raises(ValueError, match=said):
                layers.fused_attention(q, q, q, **kw)
        with pytest.raises(ValueError, match='chunks of'):
            layers.chunk_softmax_pool(q, q, vec, vec, chunk=7)
        with pytest.raises(ValueError, match='a learned vector a head'):
            layers.chunk_softmax_pool(q, q, s, vec, chunk=8)


def test_rms_norm_unit_offset_is_one_plus_the_weight():
    from paddle_tpu.fluid import layers
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        plain = layers.rms_norm(x)
        offset = layers.rms_norm(x, unit_offset=True)
    ops = [op for op in main.global_block().ops if op.type == 'rms_norm']
    assert 'unit_offset' not in ops[0].attrs and ops[1].attrs['unit_offset']
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        w = [np.asarray(scope.find_var(op.input('Scale')[0]).get_tensor())
             for op in ops]
        assert (w[0] == 1).all() and (w[1] == 0).all()
        feed = {'x': np.random.default_rng(0).normal(
            size=(3, 8)).astype('float32')}
        a, b = exe.run(main, feed=feed, fetch_list=[plain, offset])
    np.testing.assert_allclose(a, b, rtol=1e-6)


# ------------------------------------------------------------------ the walk

def test_the_walk_with_host_weights_is_the_gradient_of_the_whole():
    """`loss_and_grads` (forward keeping each layer's input, backward
    with `jax.vjp` of ONE layer at a time, the layer's parameters put on
    the device for the call) against `jax.value_and_grad` of
    `forward_loss`, the same function in one piece: every path, and the
    results live on the host."""
    ref = reference_module()
    cell = _toy_cell()
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
        assert np.linalg.norm(b) > 0, path
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b), path
    again = ref.loss_and_grads(params, model, pool[0], ['tok_emb'])
    assert again[1]['tok_emb'] is grads['tok_emb']


# ------------------------------------------------- scopes, regions, counters

def test_layers_are_mixer_and_feed_forward_scopes_regions_and_counters():
    """Two layers, each an EVA mixer AND a SwiGLU behind a unit-offset
    norm each in ONE recompute region; the mixer under `eva_mixer`, the
    pooling under `eva_summary` inside it, the feed-forward under
    `dense_mlp`; the attention is ONE op with its summaries; the head is
    the last `mul`; the loss one closed-form cross entropy; the builder
    counts a layer; the scopes reach the optimized HLO's op_name."""
    from chipbench.harness import catalog
    cell = _toy_cell()
    before = obs.counter('evabyte.layers').value
    pooled = obs.counter('chunk_pool.lowered', chunk=8).value
    aligned = obs.counter('flash.aligned', way='xla',
                          summaries='true').value
    config, built = build_toy(cell, train=True)
    assert obs.counter('evabyte.layers').value - before == 2
    ops = built['main'].global_block().ops
    forward = [op for op in ops if not op.type.endswith('_grad')
               and op.type != 'adam']
    kinds = [op.type for op in forward]
    assert kinds.count('rms_norm') == 5
    assert kinds.count('rotary_embedding') == 4
    assert kinds.count('chunk_softmax_pool') == 2
    assert kinds.count('flash_attention') == 2
    assert kinds.count('softmax_with_cross_entropy') == 1
    for op in forward:
        scope = op.attrs.get('name_scope')
        if op.type == 'rms_norm':
            assert scope is None and op.attrs['unit_offset'] is True
        if op.type == 'chunk_softmax_pool':
            assert scope == 'eva_mixer/eva_summary'
            assert op.attrs['chunk'] == 8 and op.attrs['scale'] == 0.25
        if op.type == 'flash_attention':
            assert scope == 'eva_mixer' and op.attrs['causal']
            assert op.attrs['aligned_window'] == 64
            assert op.attrs['summary_every'] == 8
            assert op.input('SummaryK') and op.input('SummaryV')
            assert 'window' not in op.attrs
        if op.type == 'rotary_embedding':
            assert op.attrs['base'] == 100000.0 and scope == 'eva_mixer'
        if op.type == 'lookup_table':
            assert scope is None
    muls = [op for op in forward if op.type == 'mul']
    scopes = [op.attrs.get('name_scope') for op in muls]
    assert scopes.count('dense_mlp') == 6 and scopes.count('eva_mixer') == 8
    assert scopes[-1] is None                               # the head
    head = built['main'].global_block().var(muls[-1].input('Y')[0])
    assert tuple(head.shape) == (64, 8 * 320)
    table = built['main'].global_block().var('embedding_0.w_0')
    assert tuple(table.shape) == (320, 64)
    regions = {op.attrs.get('recompute') for op in ops
               if op.attrs.get('recompute') is not None}
    assert len(regions) == 2
    kept = [n for op in forward for n in op.attrs.get('recompute_keep', [])]
    assert len(kept) == 2 * 4          # h and the outputs of Wq, Wk, Wv
    text = decoder_toy.one_step_hlo(cell, config, built)
    assert obs.counter('chunk_pool.lowered', chunk=8).value - pooled >= 2
    assert obs.counter('flash.aligned', way='xla',
                       summaries='true').value - aligned >= 2
    window = catalog.load_module(catalog.ROOT, 'layers', 'name_scope_window')
    mixer = window.op_scopes_under(text, 'eva_mixer')
    summary = window.op_scopes_under(text, 'eva_summary')
    mlp = window.op_scopes_under(text, 'dense_mlp')
    assert mixer and summary and mlp and summary < mixer
    assert not mixer & mlp
    assert {s.rsplit('_', 1)[0] for s in mixer} >= {
        'mul', 'rotary_embedding', 'chunk_softmax_pool', 'flash_attention'}
    assert {s.rsplit('_', 3)[0] for s in summary} == {'chunk'}
    assert {s.rsplit('_', 1)[0] for s in mlp} >= {'mul', 'swish'}
    assert len([s for s in mlp if s.startswith('mul_')]) == 6


def test_the_builder_refuses_sizes_the_mechanism_does_not_have():
    from paddle_tpu.models import evabyte as E
    small = dict(n_layer=1, hidden=16, n_head=2, d_head=8, mlp_width=32)
    for kw, said in ((dict(window_size=24, chunk_size=16),
                      'not a whole number of chunks'),
                     (dict(window_size=32, chunk_size=8, num_chunks=4),
                      'num_chunks'),
                     (dict(window_size=48, chunk_size=8),
                      'not a whole number of windows')):
        with framework.program_guard(framework.Program(),
                                     framework.Program()):
            with pytest.raises(ValueError, match=said):
                E.evabyte(320, 64, **small, **kw)


def test_small_preset_trains():
    from paddle_tpu import models
    assert 'evabyte' in models.model_list
    E = models.get_model_module('evabyte')
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 1
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, train, _, feeds = E.get_model()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        batch = next(iter(train()))
        feed = {feeds[0]: np.stack([b[0] for b in batch]),
                feeds[1]: np.stack([b[1] for b in batch])}
        losses = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
            for _ in range(20)]
    assert np.isfinite(losses).all()
    assert abs(losses[0] - np.log(320)) < 0.05
    assert all(b < a for a, b in zip(losses, losses[1:]))


# ------------------------------------------------------------- the benchmark

# the catalog's row `EvaByte`, its `config` copied here
SOURCE = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}

_CHECKED = {'embedding_0.w_0': 'tok_emb', 'fc_1.w_0': 'layer0.k',
            'create_parameter_0.w_0': 'layer0.mu',
            'create_parameter_1.w_0': 'layer0.phi', 'fc_28.w_0': 'head'}


def test_configuration_file_holds_the_published_sizes():
    """Every key of the source's config.json at its published value, at
    the top level (the driver compares those) and in `model` (the builder
    reads that); only the depth is cut: no width, not the vocabulary, not
    the prediction heads."""
    with open(os.path.join(REPO, 'chipbench', 'configs',
                           'evabyte.json')) as f:
        held = json.load(f)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            for row in (json.loads(l) for l in f if l.strip()):
                if row['name'] == 'EvaByte':
                    assert row['config'] == SOURCE
                    assert row['source_url'] == held['source']
    assert held['source'] == \
        'https://huggingface.co/EvaByte/EvaByte/blob/main/config.json'
    cut = {'num_hidden_layers': 4}
    for key, value in SOURCE.items():
        want = cut.get(key, value)
        assert held[key] == want and held['model'][key] == want, key
    assert set(held['model']) == set(SOURCE)
    assert held['reduced'] == list(cut)
    assert held['reduced_from'] == {k: SOURCE[k] for k in cut}
    assert held['amp'] == 'bf16'
    assert held['optimizer'] == {
        'kind': 'adam', 'beta1': 0.9, 'beta2': 0.95, 'epsilon': 1e-08,
        'learning_rate': 0.0004}
    assert sorted(held['checks']) == ['amp', 'float32']
    for key in ('top_level_keys', 'num_hidden_layers', 'attention_class',
                'aligned_windows', 'learned_vectors',
                'rotary_before_pooling', 'rotary_pairing', 'one_softmax',
                'head', 'labels', 'norm_add_unit_offset', 'initializers',
                'mixed_precision', 'hidden_act', 'optimizer',
                'document_mask', 'dropout', 'recomputation'):
        assert held['assumed'][key], key
    for said in ('eight stages', '821.37 M', '821,366,784', '202,391,552',
                 '9.86 GB', '6.49 B'):
        assert said in held['deployment'], said
    for entry in held['checks'].values():
        assert set(entry['grads']) == set(_CHECKED)
        assert len(entry['why']) > 400
    assert held['checks']['amp']['tolerance']['loss'] == 1e-3
    assert held['checks']['float32']['tolerance']['loss'] == 1e-4
    assert held['checks']['float32']['matmul_precision'] == 'highest'


def test_the_checks_names_are_the_parameters_the_issue_asks_for():
    """At the published depth the names the `checks` carry are the
    embedding, layer 0's Wk, mu and phi, and the head (the builder's tree
    says which path each name is)."""
    from chipbench.harness import catalog, check
    published = catalog.load_cell(CELL)['config']
    cell = _toy_cell(num_hidden_layers=4)
    _, tree = _tree(cell)
    paths = check.grad_paths(
        tree, set(published['checks']['float32']['grads']))
    assert {n: p for n, (p, _) in paths.items()} == _CHECKED
    toy = _toy_cell()
    _, tree = _tree(toy)
    paths = check.grad_paths(tree,
                             set(toy['config']['checks']['amp']['grads']))
    assert {p for p, _ in paths.values()} == set(_CHECKED.values())


def test_flops_of_the_cell_are_the_issues_arithmetic():
    """The parameters by part (ISSUE 59's count: a layer 202,391,552,
    821,366,784 in all) and the step's 40.30 + 1.96 TFLOP at one row of
    8192."""
    from chipbench.harness import catalog
    cell = catalog.load_cell(CELL)
    config, traffic = cell['config'], cell['traffic']
    flops = cell['flops']
    tokens = traffic['batch'] * traffic['seq']
    assert tokens == 8192
    m = config['model']
    assert flops.head_dim(m) == 128
    assert flops.mixer_weights(m) == 4 * 4096 ** 2 == 67108864
    assert flops.mlp_weights(m) == 3 * 4096 * 11008 == 135266304
    assert flops.head_weights(m) == 4096 * 8 * 320 == 10485760
    assert flops.parameters(m) == 821366784
    assert flops.parameters(dict(m, num_hidden_layers=32)) == pytest.approx(
        6.49e9, rel=1e-3)
    assert 12 * flops.parameters(m) == pytest.approx(9.86e9, rel=1e-3)
    assert flops.admitted_pairs(m, 8192) == (
        4 * 2048 * 2049 // 2, (0 + 128 + 256 + 384) * 2048)
    assert flops.admitted_pairs(m, 2048) == (2048 * 2049 // 2, 0)
    f = flops.forward_flops(config, traffic['batch'], traffic['seq'])
    matmul = 4 * (67108864 + 135266304) + 10485760
    assert matmul == 819986432
    assert f['eva_projections'] + f['dense_mlp'] + f['head'] == \
        2 * matmul * tokens
    assert 3 * 2 * matmul * tokens == pytest.approx(40.30e12, rel=1e-3)
    pairs = 8392704 + 1572864
    assert f['attention_exact'] + f['attention_summary'] == \
        pairs * 32 * 4 * 512
    assert 3 * pairs * 32 * 4 * 512 == pytest.approx(1.96e12, rel=2e-3)
    step = flops.train_step_flops(config, traffic)
    assert step == pytest.approx(3 * sum(f.values()))
    assert step == pytest.approx(42.3e12, rel=2e-3)
    assert f['head'] / sum(f.values()) == pytest.approx(0.0122, abs=0.0005)
    assert flops.dense_mlp_flops(config, traffic) == \
        6 * 135266304 * tokens * 4
    cost = flops.kernel_cost(config, traffic, 1)
    assert set(cost) == {'flash_attention'}
    a_row = 32 * 128 * 2
    calls = 6 * a_row * (8192 + 8192) + 2 * 32 * 8192 * 4 \
        + 6 * a_row * (6144 + 384) + 2 * 32 * 6144 * 4
    assert cost['flash_attention'] == (
        3.0 * (f['attention_exact'] + f['attention_summary']), 4 * calls)
    eva = flops.eva_cost(config, traffic, 1)
    assert eva[0] == 3.0 * (f['eva_projections'] + f['attention_exact']
                            + f['attention_summary'])
    assert eva[1] == 4 * (
        3 * 2 * 67108864 + 3 * 8192 * 2 * 2 * 2 * 4096 + calls
        + 6 * a_row * (8192 + 512) + 8 * a_row * 6144)
    # the mixers are MXU-bound by what they require
    assert eva[0] / 197e12 > 4 * eva[1] / 819e9


def test_new_readers_read_their_scope_or_nothing():
    """`eva_ms`, `eva_summary_ms` and `eva_roofline` on a hand-made
    reduction and a hand-made HLO; on a program that names no such scope
    (the parent's) or a configuration that counts no such cost nothing,
    and no error."""
    from chipbench.harness import catalog, peaks
    cell = catalog.load_cell(CELL)
    hlo = '\n'.join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(eva_mixer)/jvp(mul_2)/dot_general"}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step)/transpose(jvp(eva_mixer))/transpose(jvp(eva_summary))/'
        'transpose(jvp(chunk_softmax_pool_0))/mul"}',
        '  %call.3 = f32[8]{0} custom-call(%p), metadata={op_name='
        '"jit(step)/jvp(eva_mixer)/jvp(flash_attention_0)/'
        'jit(staircase_fwd)/pallas_call"}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(dense_mlp)/jvp(mul_9)/dot"}',
        '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(step)/jvp(eva_mixer_like)/jvp(mul_7)/dot"}',
    ])
    red = {'steps': 5,
           'fluid_scope_s': {'mul_2': 1.0, 'chunk_softmax_pool_0': 0.25,
                             'flash_attention_0': 0.5, 'mul_9': 1.0,
                             'mul_7': 4.0},
           'fluid_op_s': {'mul': 6.0},
           'kernel_by_op_s': {'flash_attention': 0.5},
           'kernel_by_callee_s': {'flash_attention': {
               '': 0.3, 'staircase_fwd': 0.05, 'staircase_bwd': 0.15}}}
    reading = {'trace': red, 'hlo': hlo, 'cell': cell, 'chips': 1,
               'peaks': peaks.PEAKS['TPU v5 lite'], 'kernel_cost': None}
    assert catalog.load_reader('eva_ms')(reading) == pytest.approx(350.0)
    assert catalog.load_reader('eva_summary_ms')(reading) == \
        pytest.approx(50.0 + 40.0)
    share = catalog.load_reader('eva_roofline')(reading)
    flops, _ = cell['flops'].eva_cost(cell['config'], cell['traffic'], 1)
    assert share == pytest.approx(100 * flops / 197e12 / 0.35)
    assert 0 < share < 100
    for name in ('eva_ms', 'eva_summary_ms', 'eva_roofline'):
        for other in (dict(reading, hlo=hlo.replace('eva_', 'x_')),
                      dict(reading, trace=None), dict(reading, hlo=None)):
            assert catalog.load_reader(name)(other) is None, name
    assert catalog.load_reader('eva_roofline')(
        dict(reading, peaks=None)) is None
    # a configuration without `eva_cost` (every older one)
    older = dict(reading, cell=catalog.load_cell('granite4hmicro_s8192'))
    assert catalog.load_reader('eva_roofline')(older) is None
    # the staircase's kernels not told apart (no such callee): the
    # pooling's time alone
    plain = dict(reading, trace=dict(red, kernel_by_callee_s={
        'flash_attention': {'': 0.5}}))
    assert catalog.load_reader('eva_summary_ms')(plain) == \
        pytest.approx(50.0)
