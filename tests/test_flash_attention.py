"""Flash-attention kernel numerics (pallas interpret mode on CPU) and the
fused_attention fluid op, vs the plain-XLA oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.layers as layers

from util import flash_schedules as _count_passes, fresh_program


def _fa():
    # the package's attribute of that name is the function
    import importlib
    return importlib.import_module('paddle_tpu.ops.flash_attention')


def _flash_as(schedule, q, k, v, key_bias=None, causal=False, window=None,
              block_q=None, block_k=None):
    """flash_attention_lse under the interpreter with the backward's
    schedule PINNED ('tile', 'head' or None: two passes) through
    _flash_lse's static argument, whatever _prep's rule chose (and
    counted) for the shapes."""
    fa = _fa()
    window = fa._window_of(window, causal, q.shape[2])
    q, k, v, kb, scale, bq, bk, _, interp, Tq, _ = fa._prep(
        q, k, v, key_bias, None, block_q, block_k, True, causal=causal,
        window=window)
    o, lse = fa._flash_lse(q, k, v, kb, bool(causal), window, scale, bq, bk,
                           schedule, interp)
    return o[:, :, :Tq], lse[:, :, :Tq]


def _rand_qkv(B=2, H=2, Tq=20, Tk=20, D=16, seed=0):
    r = np.random.RandomState(seed)
    q = r.randn(B, H, Tq, D).astype('float32')
    k = r.randn(B, H, Tk, D).astype('float32')
    v = r.randn(B, H, Tk, D).astype('float32')
    kb = np.where(r.rand(B, Tk) < 0.25, -1e9, 0.0).astype('float32')
    kb[:, 0] = 0.0   # keep at least one live key per row
    return q, k, v, kb


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('with_bias', [False, True])
def test_forward_matches_reference(causal, with_bias):
    q, k, v, kb = _rand_qkv()
    bias = kb if with_bias else None
    got = ops.flash_attention(q, k, v, key_bias=bias, causal=causal,
                              interpret=True)
    want = ops.reference_attention(q, k, v, key_bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_uneven_lengths():
    # Tq != Tk and non-multiple-of-block sizes exercise the padding path
    q, k, v, kb = _rand_qkv(Tq=9, Tk=33)
    got = ops.flash_attention(q, k, v, key_bias=kb, interpret=True)
    want = ops.reference_attention(q, k, v, key_bias=kb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_gradients_match_reference(causal):
    q, k, v, kb = _rand_qkv(B=1, H=2, Tq=12, Tk=12, D=8, seed=1)

    def loss_flash(q, k, v):
        o = ops.flash_attention(q, k, v, key_bias=kb, causal=causal,
                                interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = ops.reference_attention(q, k, v, key_bias=kb, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


def test_fused_attention_layer():
    B, H, T, D = 2, 2, 6, 4
    r = np.random.RandomState(3)
    qv = r.randn(B, H, T, D).astype('float32')
    kv = r.randn(B, H, T, D).astype('float32')
    vv = r.randn(B, H, T, D).astype('float32')
    with fresh_program() as (main, startup):
        q = layers.data(name='q', shape=[H, T, D], dtype='float32')
        k = layers.data(name='k', shape=[H, T, D], dtype='float32')
        v = layers.data(name='v', shape=[H, T, D], dtype='float32')
        out = layers.fused_attention(q, k, v, causal=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, = exe.run(main, feed={'q': qv, 'k': kv, 'v': vv},
                       fetch_list=[out])
    want = ops.reference_attention(qv, kv, vv, causal=True)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ring_attention_matches_full():
    from paddle_tpu import parallel
    from paddle_tpu.parallel.ring_attention import ring_self_attention
    mesh = parallel.make_mesh({'sp': 8})
    B, H, T, D = 2, 2, 16, 4
    r = np.random.RandomState(4)
    q = r.randn(B, H, T, D).astype('float32')
    k = r.randn(B, H, T, D).astype('float32')
    v = r.randn(B, H, T, D).astype('float32')
    kb = np.where(r.rand(B, T) < 0.25, -1e9, 0.0).astype('float32')
    kb[:, 0] = 0.0
    for causal in (False, True):
        got = ring_self_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), axis='sp',
                                  key_bias=jnp.asarray(kb), causal=causal,
                                  interpret=True)
        want = ops.reference_attention(q, k, v, key_bias=kb, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg='causal=%s' % causal)


def test_ulysses_attention_matches_full_and_ring():
    from paddle_tpu import parallel
    from paddle_tpu.parallel.ring_attention import ring_self_attention
    from paddle_tpu.parallel.ulysses import ulysses_self_attention
    mesh = parallel.make_mesh({'sp': 8})
    B, H, T, D = 2, 8, 16, 4       # H divisible by sp=8
    r = np.random.RandomState(5)
    q = r.randn(B, H, T, D).astype('float32')
    k = r.randn(B, H, T, D).astype('float32')
    v = r.randn(B, H, T, D).astype('float32')
    kb = np.where(r.rand(B, T) < 0.25, -1e9, 0.0).astype('float32')
    kb[:, 0] = 0.0
    for causal in (False, True):
        got = ulysses_self_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), axis='sp',
                                     key_bias=jnp.asarray(kb), causal=causal,
                                  interpret=True)
        want = ops.reference_attention(q, k, v, key_bias=kb, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg='causal=%s' % causal)
        ring = ring_self_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), axis='sp',
                                   key_bias=jnp.asarray(kb), causal=causal,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ring),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import pytest
    from paddle_tpu import parallel
    from paddle_tpu.parallel.ulysses import ulysses_self_attention
    mesh = parallel.make_mesh({'sp': 8})
    q = jnp.zeros((1, 3, 16, 4), jnp.float32)   # 3 heads, sp=8
    with pytest.raises(ValueError, match='ring_self_attention'):
        ulysses_self_attention(mesh, q, q, q, axis='sp', interpret=True)


def test_forward_multiblock_grids():
    # multi-block q AND k grids (2x2) — exercises the scratch accumulation
    # across the innermost grid dim and the revisited output block
    q, k, v, kb = _rand_qkv(B=2, H=2, Tq=256, Tk=256, D=32, seed=7)
    for causal in (False, True):
        got = ops.flash_attention(q, k, v, key_bias=kb, causal=causal,
                                  interpret=True)
        want = ops.reference_attention(q, k, v, key_bias=kb, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg='causal=%s' % causal)


def test_gradients_multiblock():
    q, k, v, kb = _rand_qkv(B=1, H=1, Tq=256, Tk=256, D=16, seed=8)

    def mk(fn):
        def g(q, k, v):
            o = fn(q, k, v, key_bias=kb, causal=True)
            return jnp.sum(o * jnp.sin(o))
        return jax.grad(g, argnums=(0, 1, 2))

    g1 = mk(lambda *a, **kw: ops.flash_attention(*a, interpret=True, **kw))(q, k, v)
    g2 = mk(ops.reference_attention)(q, k, v)
    for a, b, name in zip(g1, g2, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_flash_attention_lse_forward_and_grads():
    """(o, lse) wrapper: lse matches the oracle logsumexp, and gradients
    flow correctly through BOTH outputs (the delta - dlse trick)."""
    q, k, v, kb = _rand_qkv(B=1, H=2, Tq=12, Tk=12, D=8, seed=5)

    def ref_o_lse(q, k, v, causal):
        D = q.shape[-1]
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * D ** -0.5
        s = s + kb[:, None, None, :]
        if causal:
            T = q.shape[2]
            m = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(m, s, -1e9)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)
        return o, lse

    for causal in (False, True):
        o, lse = ops.flash_attention_lse(q, k, v, key_bias=kb,
                                         causal=causal, interpret=True)
        ro, rlse = ref_o_lse(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                                   rtol=2e-5, atol=2e-5)

        # a loss touching BOTH o and lse — this exercises the lse cotangent
        def loss_flash(q, k, v, _c=causal):
            o, lse = ops.flash_attention_lse(q, k, v, key_bias=kb,
                                             causal=_c, interpret=True)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))

        def loss_ref(q, k, v, _c=causal):
            o, lse = ref_o_lse(q, k, v, _c)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b, name in zip(g1, g2, 'qkv'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg='causal=%s %s' % (causal, name))


def test_ring_attention_flash_impl_matches_dense_and_full():
    """The flash-backed ring (per-shard pallas blocks + lse merge) agrees
    with the dense ring and the full-attention oracle, fwd and bwd."""
    from paddle_tpu import parallel
    from paddle_tpu.parallel.ring_attention import ring_self_attention
    mesh = parallel.make_mesh({'sp': 4})
    B, H, T, D = 2, 2, 16, 4
    r = np.random.RandomState(6)
    q = jnp.asarray(r.randn(B, H, T, D).astype('float32'))
    k = jnp.asarray(r.randn(B, H, T, D).astype('float32'))
    v = jnp.asarray(r.randn(B, H, T, D).astype('float32'))
    kbn = np.where(r.rand(B, T) < 0.25, -1e9, 0.0).astype('float32')
    kbn[:, 0] = 0.0
    kb = jnp.asarray(kbn)
    for causal in (False, True):
        got = ring_self_attention(mesh, q, k, v, axis='sp', key_bias=kb,
                                  causal=causal, impl='flash',
                                  interpret=True)
        want = ops.reference_attention(q, k, v, key_bias=kb, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5,
                                   err_msg='causal=%s' % causal)

        def loss_ring(q, k, v, _c=causal):
            o = ring_self_attention(mesh, q, k, v, axis='sp', key_bias=kb,
                                    causal=_c, impl='flash',
                                    interpret=True)
            return jnp.sum(o * jnp.cos(o))

        def loss_full(q, k, v, _c=causal):
            o = ops.reference_attention(q, k, v, key_bias=kb, causal=_c)
            return jnp.sum(o * jnp.cos(o))

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, 'qkv'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg='causal=%s %s' % (causal, name))


def test_tri_maps_enumerate_lower_triangle():
    from paddle_tpu.ops.flash_attention import (_tri_maps, _tri_maps_kv,
                                                _use_tri)
    for n in (1, 2, 3, 5):
        im, jm = _tri_maps(n)
        assert len(im) == n * (n + 1) // 2
        assert set(zip(im.tolist(), jm.tolist())) == {
            (i, j) for i in range(n) for j in range(i + 1)}
        # row-major: q-block index non-decreasing, each row starts at j=0
        assert all(im[t] <= im[t + 1] for t in range(len(im) - 1))
        im2, jm2 = _tri_maps_kv(n)
        assert set(zip(im2.tolist(), jm2.tolist())) == {
            (i, j) for i in range(n) for j in range(i + 1)}
        # k-block-major: within a k-block, q runs j..n-1 consecutively
        starts = [t for t in range(len(im2)) if im2[t] == jm2[t]]
        assert len(starts) == n
    # selection predicate: aligned causal self-attention only
    assert _use_tri(True, 256, 256, 128, 128)
    assert not _use_tri(False, 256, 256, 128, 128)   # not causal
    assert not _use_tri(True, 256, 512, 128, 128)    # cross lengths
    assert not _use_tri(True, 256, 256, 128, 64)     # uneven blocks
    assert not _use_tri(True, 128, 128, 128, 128)    # single block


def test_causal_triangular_grid_3x3_forward_and_grads():
    """3x3-block causal triangle (T=384, bq=bk=128): the scalar-prefetch
    grid must agree with the XLA oracle through forward and backward."""
    q, k, v, kb = _rand_qkv(B=2, H=1, Tq=384, Tk=384, D=16, seed=11)
    got = ops.flash_attention(q, k, v, key_bias=kb, causal=True,
                              interpret=True)
    want = ops.reference_attention(q, k, v, key_bias=kb, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def mk(fn):
        def g(q, k, v):
            o = fn(q, k, v, key_bias=kb, causal=True)
            return jnp.sum(o * jnp.sin(o))
        return jax.grad(g, argnums=(0, 1, 2))

    g1 = mk(lambda *a, **kw: ops.flash_attention(*a, interpret=True, **kw))(q, k, v)
    g2 = mk(ops.reference_attention)(q, k, v)
    for a, b, name in zip(g1, g2, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_flash_under_a_mesh_lowers_only_per_shard():
    """ISSUE 21, found on four chips: a bare Mosaic call inside a
    GSPMD-partitioned jit does not get all-gathered operands — jax refuses
    to lower it. The lowering rule therefore routes through
    flash_attention_sharded under a mesh; jax.export for the TPU platform
    shows both outcomes without a chip."""
    from jax import export
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import parallel
    mesh = parallel.make_mesh({"dp": 2, "tp": 2})
    sh = NamedSharding(mesh, P('dp', 'tp', None, None))
    x = jax.ShapeDtypeStruct((4, 4, 256, 64), jnp.bfloat16, sharding=sh)

    def lower(fn):
        return export.export(jax.jit(fn, in_shardings=(sh, sh, sh),
                                     out_shardings=sh),
                             platforms=['tpu'])(x, x, x).mlir_module()

    with pytest.raises(NotImplementedError, match='shard_map'):
        lower(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, interpret=False))
    assert 'tpu_custom_call' in lower(
        lambda q, k, v: ops.flash_attention_sharded(
            mesh, q, k, v, causal=True, interpret=False))
    # and the per-shard call computes what the reference computes
    r = np.random.RandomState(9)
    q, k, v = [jnp.asarray(r.randn(4, 4, 256, 64).astype('float32'))
               for _ in range(3)]
    got = ops.flash_attention_sharded(mesh, q, k, v, causal=True,
                                      interpret=True)
    want = ops.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# bf16 in: the tiles reach the dots as bf16, everything else stays float32
# ---------------------------------------------------------------------------

# One bf16 rounding moves a value by at most 2^-8 of itself. Against the
# float32 reference ON THE SAME bf16 INPUTS, in relative norm, the kernel
# adds: forward, p rounded before p @ v (1) and o rounded on its way out
# (1) = 2 eps. Gradients, with a cotangent that is exact in bf16 (the loss
# is linear in o): dv has p rounded (1) and its own rounding out (1); dq
# and dk have ds rounded (1), delta = sum(do * o) read off the ROUNDED o
# (1) and their own rounding out (1), and dp - delta cancels, which is
# given the one spacing dv leaves spare: 4 eps. Products of bf16 tiles are
# exact in the float32 accumulator and s, m, l, lse, delta never leave
# float32, so lse holds the float32 tolerance. Not fitted: the interpreter
# reads 0.5 eps forward and 0.6 to 0.8 eps on the gradients.
BF16_EPS = 2.0 ** -8

_BF16_CASES = {
    'plain': dict(Tq=128, Tk=128),
    'key_bias': dict(Tq=128, Tk=128, bias=True),
    # Tq != Tk keeps the causal mask on the rectangular grid
    'causal_rectangular': dict(Tq=128, Tk=256, bias=True, causal=True,
                               block=128),
    # three tiles a side: the rule gives one pass over the head (PR 42);
    # the two kernels it replaced there, pinned
    'causal_triangular_3x3': dict(Tq=384, Tk=384, bias=True, causal=True,
                                  block=128),
    'causal_triangular_3x3_two_passes': dict(Tq=384, Tk=384, bias=True,
                                             causal=True, block=128,
                                             pin=None),
    'head_4x4_lse_cotangent': dict(Tq=512, Tk=512, bias=True, causal=True,
                                   block=128, lse=True),
    'head_band_window_200': dict(Tq=512, Tk=512, bias=True, causal=True,
                                 block=128, window=200),
    'uneven_lengths': dict(Tq=9, Tk=33, bias=True),
    'lse_cotangent': dict(Tq=128, Tk=128, bias=True, causal=True, lse=True),
    # the one-pass backward (PR 27; the four single-tile cases above take
    # it too): a length that pads to its tile, and a causal head walked in
    # two 512 sub-tiles, the first of which sees half the keys
    'one_pass_pads_200': dict(Tq=200, Tk=200, bias=True, causal=True),
    'one_pass_causal_sub_tiles': dict(Tq=1024, Tk=1024, bias=True,
                                      causal=True, lse=True),
}


def _ref_o_lse(q, k, v, bias, causal, window=None):
    """reference_attention and the logsumexp of its scores."""
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * q.shape[-1] ** -0.5
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        ahead = (jnp.arange(q.shape[2])[:, None]
                 - jnp.arange(k.shape[2])[None, :])
        seen = ahead >= 0 if window is None else (ahead >= 0) & (
            ahead < window)
        s = jnp.where(seen, s, -1e9)
    return (ops.reference_attention(q, k, v, key_bias=bias, causal=causal,
                                    window=window),
            jax.scipy.special.logsumexp(s, axis=-1))


def _rel_norm(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize('case', sorted(_BF16_CASES))
def test_bf16_inputs_match_float32_reference(case):
    c = _BF16_CASES[case]
    causal, with_lse = c.get('causal', False), c.get('lse', False)
    q, k, v, kb = _rand_qkv(B=1, H=2, Tq=c['Tq'], Tk=c['Tk'], D=32, seed=21)
    q, k, v = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
    bias = jnp.asarray(kb) if c.get('bias') else None
    r = np.random.RandomState(22)
    # cotangents that bf16 holds exactly, so both sides see the same ones
    w = jnp.asarray(r.randn(1, 2, c['Tq'], 32), jnp.bfloat16)
    u = jnp.asarray(r.randn(1, 2, c['Tq']), jnp.bfloat16).astype(jnp.float32)

    def flash(q, k, v):
        kw = dict(key_bias=bias, causal=causal, window=c.get('window'),
                  block_q=c.get('block'), block_k=c.get('block'))
        if 'pin' in c:
            return _flash_as(c['pin'], q, k, v, **kw)
        return ops.flash_attention_lse(q, k, v, interpret=True, **kw)

    def ref(q, k, v):
        return _ref_o_lse(q, k, v, bias, causal, c.get('window'))

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            val = jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))
            if with_lse:
                val = val + jnp.sum(lse * u)
            return val, (o, lse)
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    g_k, (o_k, lse_k) = loss(flash)(q, k, v)
    with jax.default_matmul_precision('highest'):
        g_r, (o_r, lse_r) = loss(ref)(*[x.astype(jnp.float32)
                                        for x in (q, k, v)])
    assert o_k.dtype == jnp.bfloat16 and lse_k.dtype == jnp.float32
    assert all(g.dtype == jnp.bfloat16 for g in g_k)
    assert _rel_norm(o_k, o_r) <= 2 * BF16_EPS
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_r),
                               rtol=2e-5, atol=2e-5)
    for got, want, name in zip(g_k, g_r, ('dq', 'dk', 'dv')):
        assert _rel_norm(got, want) <= 4 * BF16_EPS, name


def _walk(jp, out):
    for e in jp.eqns:
        out.append(e)
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    _walk(sub, out)
    return out


def _kernel_bodies(dtype, causal, block_q=128, block_k=128, T=256, Tk=None,
                   pin='rule'):
    """The traced bodies of the kernels of one forward and backward at
    2 x 2 x T x 64 (blocks of 128: the rectangular grid, or the triangular
    one when causal; None: the default tiles), as (name, [eqns]); with
    `pin`, the backward's schedule whatever the rule gives the shapes."""
    q = jax.ShapeDtypeStruct((2, 2, T, 64), dtype)
    k = jax.ShapeDtypeStruct((2, 2, Tk or T, 64), dtype)

    def flash(q, k, v):
        kw = dict(causal=causal, block_q=block_q, block_k=block_k)
        if pin != 'rule':
            return _flash_as(pin, q, k, v, **kw)[0]
        return ops.flash_attention(q, k, v, interpret=True, **kw)

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, k)
    calls = [e for e in _walk(jaxpr.jaxpr, [])
             if e.primitive.name == 'pallas_call']
    return [(e.params['jaxpr'].debug_info.func_name,
             _walk(e.params['jaxpr'], [])) for e in calls]


# path -> the forced block (None: the default tiles, one tile a head here),
# the mask, the bodies' dots sorted. Two passes: 2 + 3 + 4 = 9 dots and
# three exp passes over a score tile; one pass: 2 + 5 and two. Two tiles a
# side on the triangular grid are one pass over the head by the rule (PR
# 42), and the two kernels where the static argument pins them.
_BODY_PATHS = {
    'rectangular': dict(block=128, causal=False, dots=[2, 3, 4]),
    'triangular': dict(block=128, causal=True, dots=[2, 3, 4], pin=None),
    'head': dict(block=128, causal=True, dots=[2, 5],
                 one='_bwd_head_kernel'),
    'one_pass': dict(block=None, causal=False, dots=[2, 5],
                     one='_bwd_fused_kernel'),
    'one_pass_causal': dict(block=None, causal=True, dots=[2, 5],
                            one='_bwd_fused_kernel'),
}


@pytest.mark.parametrize('path', sorted(_BODY_PATHS))
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_kernel_dots_take_the_inputs_dtype(dtype, path):
    """The mechanism, pinned off the chip, on every path: all dots of the
    bodies (2 + 3 + 4 with two backward kernels, 2 + 5 with one) take
    operands of the refs' dtype and give float32; with float32 in nothing
    is cast at all; with bf16 in p and ds are cast down as dot operands
    and nothing is cast up, but for the one-pass bodies' k tile on its way
    through the transposition that dq = (k^T ds^T)^T needs. No score-sized
    tile is transposed on any path: the transposed-score bodies turn round
    their lane-broadcast float32 row statistics, and the one-pass bodies k
    and dq^T besides. Each backward body takes exp of one score tile: the
    one-pass backwards compute s, p, dp and ds once."""
    c = _BODY_PATHS[path]
    bodies = _kernel_bodies(jnp.dtype(dtype), c['causal'], c['block'],
                            c['block'], pin=c.get('pin', 'rule'))
    assert len(bodies) == len(c['dots']), [n for n, _ in bodies]
    assert all(('_tri' in name) == (path in ('triangular', 'head'))
               for name, _ in bodies if name != '_bwd_head_kernel')
    if 'one' in c:
        assert [n for n, _ in bodies][1:] == [c['one']]
    # the k tile a one-pass body turns round: a head's 256 rows, or a
    # 128-tile of them
    k_tile = {'_bwd_fused_kernel': (256, 64), '_bwd_head_kernel': (128, 64)}
    n_dots = []
    for name, eqns in bodies:
        fused = name in k_tile
        dots = [e for e in eqns if e.primitive.name == 'dot_general']
        n_dots.append(len(dots))
        for e in dots:
            assert [str(a.aval.dtype) for a in e.invars] == [dtype] * 2, name
            assert e.outvars[0].aval.dtype == jnp.float32, name
        for e in eqns:
            if e.primitive.name == 'transpose':
                aval = e.invars[0].aval
                assert aval.dtype == jnp.float32, name
                # never a [256, 256] score tile: statistics [rows, 128],
                # and in the one-pass body k [256, 64] and dq^T [64, 256]
                assert 128 in aval.shape or (fused and 64 in aval.shape), (
                    name, aval.shape)
        if 'bwd' in name:
            exps = [e for e in eqns if e.primitive.name == 'exp']
            assert [e.invars[0].aval.ndim for e in exps] == [2], name
        casts = [(str(e.invars[0].aval.dtype), str(e.params['new_dtype']),
                  e.invars[0].aval.shape)
                 for e in eqns if e.primitive.name == 'convert_element_type'
                 and e.invars[0].aval.dtype != e.params['new_dtype']]
        floats = [c for c in casts if 'float' in c[0] and 'float' in c[1]]
        if dtype == 'bfloat16':
            up = [c for c in floats if c[:2] == ('bfloat16', 'float32')]
            assert floats and {c[:2] for c in floats} - {
                ('bfloat16', 'float32')} == {('float32', 'bfloat16')}, name
            assert [c[2] for c in up] == ([k_tile[name]] if fused else []), name
        else:
            assert not floats, (name, floats)
    assert sorted(n_dots) == c['dots']


def test_flash_lowered_counts_once_per_call_per_lowering_by_dtype():
    from paddle_tpu import obs

    def count():
        return {d: sum(obs.counter('flash.lowered', operands=d, grid=g).value
                       for g in ('band', 'triangle', 'rect'))
                for d in ('bfloat16', 'float32')}

    def two_calls(q, k, v):
        o = ops.flash_attention(q, k, v, interpret=True)
        o = ops.flash_attention(o, k, v, causal=True, interpret=True)
        return o.astype(jnp.float32).sum()

    step = jax.jit(jax.grad(two_calls, argnums=(0, 1, 2)))
    x16 = jnp.ones((1, 1, 8, 8), jnp.bfloat16)
    before = count()
    for _ in range(3):          # three steps, one lowering
        step(x16, x16, x16)
    after = count()
    assert after['bfloat16'] - before['bfloat16'] == 2
    assert after['float32'] == before['float32']
    x32 = x16.astype(jnp.float32)
    step(x32, x32, x32)         # another dtype is another lowering
    step(x32, x32, x32)
    assert count()['float32'] - before['float32'] == 2
    assert count()['bfloat16'] == after['bfloat16']


# ---------------------------------------------------------------------------
# the one-pass backward (PR 27): one kernel where a head's scores are one tile
# ---------------------------------------------------------------------------

_ONE_PASS_CASES = {
    'plain': dict(T=128),
    'causal': dict(T=128, causal=True),
    'pad_key_bias': dict(T=256, bias=True),
    'pads_200_to_256': dict(T=200, bias=True, causal=True),
    'lse_cotangent': dict(T=128, bias=True, lse=True),
    'cross_lengths': dict(T=128, Tk=384, bias=True),
    'causal_sub_tiles': dict(T=1024, bias=True, causal=True, lse=True),
}


def _rose(before, after):
    return {k: int(after[k] - before[k]) for k in after}


def _count_tiles():
    from paddle_tpu import obs
    return {g: obs.counter('flash.tiles', grid=g).value
            for g in ('band', 'triangle', 'rect')}


@pytest.mark.parametrize('case', sorted(_ONE_PASS_CASES))
def test_one_pass_backward_matches_reference_float32(case):
    """Float32 in: the float32 tolerances of the two-kernel tests above,
    unedited (3e-4), on every shape of mask, padding and cotangent the
    one-pass body sees."""
    c = _ONE_PASS_CASES[case]
    causal, T, Tk = c.get('causal', False), c['T'], c.get('Tk', c['T'])
    q, k, v, kb = _rand_qkv(B=2, H=1, Tq=T, Tk=Tk, D=16, seed=31)
    if c.get('bias'):
        kb[:, Tk - Tk // 8:] = -1e9               # a padded tail as well
    bias = jnp.asarray(kb) if c.get('bias') else None

    def ref(q, k, v):
        return _ref_o_lse(q, k, v, bias, causal)

    def flash(q, k, v):
        return ops.flash_attention_lse(q, k, v, key_bias=bias, causal=causal,
                                       interpret=True)

    def grads(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            val = jnp.sum(o * jnp.cos(o))
            return val + jnp.sum(jnp.sin(lse)) if c.get('lse') else val
        return jax.grad(f, argnums=(0, 1, 2))(*map(jnp.asarray, (q, k, v)))

    before = _count_passes()
    got = grads(flash)
    after = _count_passes()
    assert _rose(before, after) == {'tile': 1, 'head': 0, 'two': 0}
    for a, b, name in zip(got, grads(ref), 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


@pytest.mark.parametrize('schedule,causal', [
    ('tile', False), ('tile', True), ('head', True)],
    ids=['full', 'causal', 'head_causal'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_one_pass_equals_two_passes(dtype, schedule, causal):
    """The same inputs through the schedules (512 x 512 scores: one pass
    in sub-tiles of 256, or one pass over the head's three tile pairs,
    against the triangular or rectangular grid of 256-tiles) agree to the
    dots' rounding: the arithmetic is the same, the order of the sums
    over blocks is not."""
    fa = _fa()
    q, k, v, kb = _rand_qkv(B=1, H=2, Tq=512, Tk=512, D=32, seed=41)
    q, k, v = [jnp.asarray(x, jnp.dtype(dtype)) for x in (q, k, v)]
    q, k, v, kb, scale, bq, bk, chosen, interp, _, _ = fa._prep(
        q, k, v, jnp.asarray(kb), None, 256, 256, True, causal=causal)
    assert chosen == ('head' if causal else None) and (bq, bk) == (256, 256)
    o, lse = fa._fwd_call(q, k, v, kb, causal, scale, bq, bk, interp)
    r = np.random.RandomState(42)
    do = jnp.asarray(r.randn(*o.shape), o.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))
    args = (q, k, v, kb, do, lse, delta, causal, scale, bq, bk)
    one = fa._bwd_call(*args, schedule, interp)
    two = fa._bwd_call(*args, None, interp)
    for a, b, name in zip(one, two, ('dq', 'dk', 'dv')):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        assert _rel_norm(a, b) <= (BF16_EPS if dtype == 'bfloat16'
                                   else 1e-6), name
    if schedule == 'head':
        # the pair's arithmetic is the dk/dv kernel's own, step for step
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(one[1:], two[1:]))


@pytest.mark.parametrize('schedule', ['tile', 'head', None],
                         ids=['tile', 'head', 'two'])
@pytest.mark.parametrize('widths', [(192, 128), (24, 16)],
                         ids=['192x128', '24x16'])
def test_values_narrower_than_keys_match_reference(widths, schedule):
    """Keys and queries of one width beside values of another (latent
    attention without a query latent: 128 + 64 rotary against 128): the v,
    o, do and dv blocks take the values' width, the scores are what they
    were. Forward and every backward schedule against the XLA chain,
    causal over 512 positions (one tile, or three pairs of 256-tiles)."""
    fa = _fa()
    d, dv = widths
    r = np.random.RandomState(d)
    q, k = (jnp.asarray(0.3 * r.randn(1, 2, 512, d), jnp.float32)
            for _ in range(2))
    v, do = (jnp.asarray(r.randn(1, 2, 512, dv), jnp.float32)
             for _ in range(2))
    block = None if schedule == 'tile' else 256

    def lowered():      # counted under the values' width, whatever the grid
        return sum(fa.obs.counter('flash.lowered', operands='float32',
                                  grid=g, dv=dv).value
                   for g in ('rect', 'triangle'))

    before = lowered()
    with jax.default_matmul_precision('highest'):
        want, pull = jax.vjp(lambda *a: ops.reference_attention(
            *a, causal=True, sm_scale=d ** -0.5), q, k, v)
        got, pull_got = jax.vjp(
            lambda *a: _flash_as(schedule, *a, causal=True, block_q=block,
                                 block_k=block)[0], q, k, v)
        g_want, g_got = pull(do), pull_got(do)
    assert got.shape == (1, 2, 512, dv)
    assert lowered() > before
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
    for a, b, name in zip(g_got, g_want, ('dq', 'dk', 'dv')):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4, err_msg=name)


def test_fused_attention_takes_values_of_their_own_width():
    """The op through the Executor: [B, H, T, 24] queries and keys, values
    of 16, the result [B, H, T, 16], equal to the XLA chain's."""
    q, k, _, _ = _rand_qkv(D=24, seed=3)
    _, _, v, _ = _rand_qkv(D=16, seed=4)
    with fresh_program() as (main, startup):
        out = layers.fused_attention(
            *(layers.data(name=n, shape=list(a.shape), dtype='float32',
                          append_batch_size=False)
              for n, a in (('q', q), ('k', k), ('v', v))), causal=True,
            scale=24 ** -0.5)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, = exe.run(main, feed={'q': q, 'k': k, 'v': v},
                       fetch_list=[out])
    assert got.shape == v.shape
    np.testing.assert_allclose(
        got, ops.reference_attention(q, k, v, causal=True,
                                     sm_scale=24 ** -0.5), rtol=1e-5,
        atol=1e-5)


# 2 x 2 x T x 64 bf16: a head's dq is 8 x 64 x T bytes of VMEM (float32 and
# the two bf16 output buffers), 64 MiB at 131072 positions
@pytest.mark.parametrize('case,kw,schedule', [
    ('one_tile', dict(T=256), 'tile'),
    ('one_tile_causal', dict(T=256, causal=True), 'tile'),
    ('causal_1024_in_sub_tiles', dict(T=1024, causal=True), 'tile'),
    ('two_tiles', dict(T=2048), None),
    ('causal_2048_triangular', dict(T=2048, causal=True), 'head'),
    ('block_q_forced_below_T', dict(T=256, block_q=128), None),
    ('blocks_forced_causal_1024', dict(T=1024, causal=True, block_q=512,
                                       block_k=512), 'head'),
    ('oblong_tiles_causal_rectangular', dict(T=1024, causal=True,
                                             block_q=256, block_k=512), None),
    ('cross_lengths_two_key_tiles', dict(T=256, Tk=2048), None),
    ('cross_lengths_causal', dict(T=1024, Tk=2048, causal=True), None),
    # heads of 64 are counted as a whole lane tile (PR 44): what fits is
    # what fits at D = 128
    ('causal_32768_fits_vmem', dict(T=32768, causal=True), 'head'),
    ('causal_65536_narrow_heads_over_the_vmem_budget',
     dict(T=65536, causal=True), None),
    ('causal_131072_over_the_vmem_budget', dict(T=131072, causal=True), None),
], ids=lambda x: x if isinstance(x, str) else None)
def test_backward_routing_reads_the_shapes(case, kw, schedule):
    """One pallas_call in the backward where a head's scores are one tile
    (the forward's, or the table's largest when nobody forced a tile) or
    where the head is causal self-attention on the triangular grid and
    its dq fits the VMEM budget, two otherwise (the rectangular grid; a
    head too long); the counter says the same, once per call per
    lowering."""
    def trace():
        return [name for name, _ in _kernel_bodies(
            jnp.bfloat16, kw.get('causal', False), kw.get('block_q'),
            kw.get('block_k'), kw['T'], kw.get('Tk'))]

    before = _count_passes()
    names = trace()
    after = _count_passes()
    assert names[1:] == {
        'tile': ['_bwd_fused_kernel'], 'head': ['_bwd_head_kernel']}.get(
        schedule, names[1:]) and len(names) == (2 if schedule else 3), names
    want = {'tile': 0, 'head': 0, 'two': 0}
    want[schedule or 'two'] = 1
    assert _rose(before, after) == want
    if case == 'one_tile':      # a tile the caller passes is a forced tile
        assert len(_kernel_bodies(jnp.bfloat16, False, 128, None, 256)) == 3


def test_flash_backward_counts_once_per_call_per_lowering():
    def three_calls(q, k, v):
        o = ops.flash_attention(q, k, v, interpret=True)
        o = ops.flash_attention(o, k, v, causal=True, block_q=128,
                                block_k=128, interpret=True)
        o = ops.flash_attention(o, k, v, block_q=128, block_k=128,
                                interpret=True)
        return o.astype(jnp.float32).sum()

    step = jax.jit(jax.grad(three_calls, argnums=(0, 1, 2)))
    x = jnp.ones((1, 1, 256, 8), jnp.bfloat16)
    before = _count_passes()
    for _ in range(3):          # three steps, one lowering
        step(x, x, x)
    assert _rose(before, _count_passes()) == {'tile': 1, 'head': 1, 'two': 1}


# ---------------------------------------------------------------------------
# one pass over a head of many tiles (PR 42): dq in VMEM across the key loop
# ---------------------------------------------------------------------------

# T = 512 in 128-tiles: a triangle of 4 x 4 tiles, 10 pairs. A window of
# 129 ends on a tile's edge (128 keys back: a band of two tiles, 7 pairs),
# one of 200 inside the next tile and one of 257 on its edge (a band of
# three, 9 pairs); one of 400 reaches every tile, so the grid is the
# triangle and the window the mask alone. Batch 2 with a key bias is the
# stale-block hazard of the strategy note.
_HEAD_CASES = {
    'triangle_4x4': dict(pairs=10),
    'triangle_4x4_lse_cotangent': dict(pairs=10, lse=True),
    'triangle_pads_450_to_512': dict(pairs=10, T=450),
    'band_ends_on_a_tile_edge': dict(pairs=7, window=129),
    'band_ends_inside_a_tile': dict(pairs=9, window=200),
    'band_of_three_lse_cotangent': dict(pairs=9, window=257, lse=True),
    'window_over_every_tile': dict(pairs=10, window=400, grid='triangle'),
}


@pytest.mark.parametrize('case', sorted(_HEAD_CASES))
def test_head_backward_matches_reference_float32(case):
    """Float32 in: the float32 tolerances of the two-kernel tests,
    unedited (3e-4), with batch 2, a key bias and a padded tail; the
    counters say that the rule took one pass over the head and how many
    tile pairs its two grids visit."""
    c = _HEAD_CASES[case]
    T, window = c.get('T', 512), c.get('window')
    q, k, v, kb = _rand_qkv(B=2, H=2, Tq=T, Tk=T, D=16, seed=61)
    kb[:, T - T // 8:] = -1e9
    bias = jnp.asarray(kb)

    def ref(q, k, v):
        return _ref_o_lse(q, k, v, bias, True, window)

    def flash(q, k, v):
        return ops.flash_attention_lse(q, k, v, key_bias=bias, causal=True,
                                       window=window, block_q=128,
                                       block_k=128, interpret=True)

    def grads(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            val = jnp.sum(o * jnp.cos(o))
            return val + jnp.sum(jnp.sin(lse)) if c.get('lse') else val
        return jax.grad(f, argnums=(0, 1, 2))(*map(jnp.asarray, (q, k, v)))

    passes, tiles = _count_passes(), _count_tiles()
    got = grads(flash)
    assert _rose(passes, _count_passes()) == {'tile': 0, 'head': 1, 'two': 0}
    grid = c.get('grid', 'band' if window else 'triangle')
    assert _rose(tiles, _count_tiles()) == {
        g: 2 * c['pairs'] * (g == grid) for g in tiles}
    for a, b, name in zip(got, grads(ref), 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


# ---------------------------------------------------------------------------
# a sliding window (PR 37): the band of tiles, and the mask's second edge
# ---------------------------------------------------------------------------

def _window_inputs(T, H=2, Hkv=None, D=16, B=1, seed=51):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, H, T, D), jnp.float32)
    k, v = (jnp.asarray(r.randn(B, Hkv or H, T, D), jnp.float32)
            for _ in range(2))
    do = jnp.asarray(r.randn(B, H, T, D), jnp.float32)
    return q, k, v, do


def _window_pair(q, k, v, do, window, tile, schedule='rule'):
    """((loss, (dq, dk, dv)) of the kernels, of the oracle): the kernels
    under the interpreter in `tile`-blocks, key-value heads repeated over
    their group as the op's rule repeats them; the backward as the rule
    schedules it, or as `schedule` pins it."""
    group = q.shape[1] // k.shape[1]

    def wide(t):
        return jnp.repeat(t, group, axis=1)

    def flash(q, k, v):
        kw = dict(causal=True, window=window, block_q=tile, block_k=tile)
        if schedule == 'rule':
            o = ops.flash_attention(q, wide(k), wide(v), interpret=True, **kw)
        else:
            o = _flash_as(schedule, q, wide(k), wide(v), **kw)[0]
        return jnp.sum(o * do)

    def oracle(q, k, v):
        o = ops.reference_attention(q, wide(k), wide(v), causal=True,
                                    window=window)
        return jnp.sum(o * do)

    return (jax.value_and_grad(flash, (0, 1, 2))(q, k, v),
            jax.value_and_grad(oracle, (0, 1, 2))(q, k, v))


# T = 640 in 128-tiles is a grid of five tiles a side: a window of one tile
# is a band of two (nb 1), a tile and a half a band of three
_WINDOW_CASES = {
    'one_key': dict(window=1),
    'three_keys': dict(window=3),
    'one_tile': dict(window=128),
    'a_tile_and_a_half': dict(window=192),
    'no_tile_multiple': dict(window=300),
    'the_whole_row': dict(window=640),
    'longer_than_the_row': dict(window=1000),
    'one_pass': dict(window=100, T=384, tile=None),
    'rectangular_grid': dict(window=100, T=384, tile=(128, 256)),
}


# every case on the triangular grid under both of its backwards: the one
# pass over the head that the rule gives it (PR 42), and the two kernels
@pytest.mark.parametrize('case,schedule', [
    (case, schedule) for case in sorted(_WINDOW_CASES)
    for schedule in (('head', None) if 'tile' not in _WINDOW_CASES[case]
                     else ('rule',))],
    ids=lambda x: {None: 'two_passes'}.get(x, x))
def test_window_forward_and_gradients_match_reference(case, schedule):
    c = _WINDOW_CASES[case]
    T, tile = c.get('T', 640), c.get('tile', 128)
    q, k, v, do = _window_inputs(T)
    if isinstance(tile, tuple):
        def flash(q, k, v):
            return jnp.sum(do * ops.flash_attention(
                q, k, v, causal=True, window=c['window'], block_q=tile[0],
                block_k=tile[1], interpret=True))
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        _, want = _window_pair(q, k, v, do, c['window'], None)
    else:
        got, want = _window_pair(q, k, v, do, c['window'], tile, schedule)
    assert abs(float(got[0]) - float(want[0])) <= 3e-4 * T
    # against the cotangent's norm where a gradient is zero (a window of
    # one key: the softmax of one score is 1 whatever q and k are)
    floor = float(jnp.linalg.norm(do))
    for a, b, name in zip(got[1], want[1], ('dq', 'dk', 'dv')):
        err = float(jnp.linalg.norm(a - b))
        assert err <= 3e-6 * max(float(jnp.linalg.norm(b)), floor), name


def test_window_is_not_the_window_one_key_off():
    """The comparison above holds the window's edge: the oracle one key
    wider or narrower is another function."""
    q, k, v, do = _window_inputs(640)
    got, _ = _window_pair(q, k, v, do, 192, 128)
    for other in (191, 193):
        _, off = _window_pair(q, k, v, do, other, 128)
        assert _rel_norm(got[1][0], off[1][0]) > 1e-3


def test_window_of_the_whole_row_is_plain_causal_on_the_same_grid():
    q, k, v, do = _window_inputs(640)
    before = _count_tiles()
    whole, _ = _window_pair(q, k, v, do, 640, 128)
    after = _count_tiles()
    plain, _ = _window_pair(q, k, v, do, None, 128)
    assert float(whole[0]) == float(plain[0])
    for a, b in zip(whole[1], plain[1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the triangle of five tiles, forward + the one pass; no band
    assert after['triangle'] - before['triangle'] == 2 * 15
    assert after['band'] == before['band']


def test_window_over_grouped_heads_28_over_4():
    """28 query heads over 4 key-value heads at a toy width: a group of 7,
    no power of two, through the repeat the op's rule makes."""
    q, k, v, do = _window_inputs(256, H=28, Hkv=4, D=8)
    got, want = _window_pair(q, k, v, do, 100, 128)
    assert got[1][1].shape == (1, 4, 256, 8)
    for a, b, name in zip(got[1], want[1], ('dq', 'dk', 'dv')):
        assert _rel_norm(a, b) <= 3e-6, name


def test_no_window_gives_the_maps_of_the_triangle():
    """`window=None`: the enumeration a plain causal call always had,
    as arrays."""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    for n in (1, 2, 5, 32):
        i = np.repeat(np.arange(n), np.arange(1, n + 1))
        j = np.concatenate([np.arange(r + 1) for r in range(n)])
        got = fa._tri_maps(n)
        assert got[0].dtype == got[1].dtype == np.int32
        assert np.array_equal(got[0], i) and np.array_equal(got[1], j)
        ii = np.concatenate([np.arange(c, n) for c in range(n - 1, -1, -1)])
        jj = np.concatenate([np.full(n - c, c)
                             for c in range(n - 1, -1, -1)])
        got = fa._tri_maps_kv(n)
        assert np.array_equal(got[0], ii) and np.array_equal(got[1], jj)
        assert fa._band(None, 512, n) is None
        assert fa._tile_pairs(n) == len(i)


@pytest.mark.parametrize('n,tile,window', [
    (5, 128, 128), (5, 128, 192), (5, 128, 129), (5, 128, 1), (8, 16, 40),
    (32, 512, 4096)])
def test_band_maps_visit_each_admitted_tile_once(n, tile, window):
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    nb = fa._band(window, tile, n)
    # the tiles that hold a pair the mask admits, from the positions
    pos = np.arange(n * tile)
    ahead = pos[:, None] - pos[None, :]
    admitted = ((ahead >= 0) & (ahead < window)).reshape(
        n, tile, n, tile).any(axis=(1, 3))
    want = {(i, j) for i in range(n) for j in range(n) if admitted[i, j]}
    for maps in (fa._tri_maps(n, nb), fa._tri_maps_kv(n, nb)):
        pairs = list(zip(maps[0].tolist(), maps[1].tolist()))
        assert len(pairs) == len(set(pairs)) == len(want)
        assert set(pairs) == want
        assert fa._tile_pairs(n, nb) == len(pairs)
    # forward and dq: a q-tile's k-tiles consecutive, first to diagonal
    i, j = fa._tri_maps(n, nb)
    for row in range(n):
        ks = j[i == row]
        assert np.array_equal(np.flatnonzero(i == row),
                              np.arange(ks.size) + np.flatnonzero(i == row)[0])
        assert ks[-1] == row and np.array_equal(
            ks, np.arange(max(0, row - (n if nb is None else nb)), row + 1))
    # dk/dv: a k-tile's q-tiles consecutive steps, diagonal first
    i, j = fa._tri_maps_kv(n, nb)
    for col in range(n):
        at = np.flatnonzero(j == col)
        assert np.array_equal(at, np.arange(at.size) + at[0])
        assert i[at][0] == col and np.array_equal(
            i[at], np.arange(col, col + at.size))
    if (n, tile, window) == (32, 512, 4096):
        assert nb == 8 and fa._tile_pairs(n, nb) == 252 \
            and fa._tile_pairs(n) == 528


def test_flash_tiles_counts_the_band():
    """A lowering says off the chip which grid a call took and how many
    tile pairs a head its grids visit (two where the backward is one pass
    over the head, three where it is dq and dk/dv)."""
    from paddle_tpu import obs

    def read():
        return ({g: obs.counter('flash.tiles', grid=g).value
                 for g in ('band', 'triangle', 'rect')},
                {g: obs.counter('flash.lowered', operands='float32',
                                grid=g).value
                 for g in ('band', 'triangle', 'rect')})

    q, k, v, _ = _window_inputs(640)
    tiles0, calls0 = read()
    ops.flash_attention(q, k, v, causal=True, window=192, block_q=128,
                        block_k=128, interpret=True)
    tiles1, calls1 = read()
    # rows of 1, 2, 3, 3, 3 tiles: 12 pairs, forward and the one pass
    assert tiles1['band'] - tiles0['band'] == 2 * 12
    assert calls1['band'] - calls0['band'] == 1
    assert tiles1['triangle'] == tiles0['triangle']
    ops.flash_attention(q, k, v, causal=True, window=192, block_q=128,
                        block_k=256, interpret=True)
    tiles2, calls2 = read()
    # oblong tiles: the rectangular grid, 5 x 3 pairs (keys pad to 768)
    assert tiles2['rect'] - tiles1['rect'] == 3 * 15
    assert calls2['rect'] - calls1['rect'] == 1 \
        and tiles2['band'] == tiles1['band']


def test_window_needs_causal_and_a_whole_number():
    q, k, v, _ = _window_inputs(128)
    for kw in (dict(causal=False, window=8), dict(causal=True, window=0),
               dict(causal=True, window=2.5)):
        with pytest.raises(ValueError, match='window'):
            ops.flash_attention(q, k, v, interpret=True, **kw)
        with pytest.raises(ValueError, match='window'):
            ops.reference_attention(q, k, v, **kw)


def test_fused_attention_window_op_matches_reference_and_refuses():
    """The attribute through the op to the XLA chain a host takes, with
    grouped heads; a window without causal is refused at construction."""
    q, k, v, _ = _window_inputs(40, H=6, Hkv=2, D=8, B=2)
    with fresh_program() as (main, startup):
        qv, kv, vv = (layers.data(name=n, shape=list(t.shape[1:]),
                                  dtype='float32')
                      for n, t in (('q', q), ('k', k), ('v', v)))
        out = layers.fused_attention(qv, kv, vv, causal=True, window=7)
        with pytest.raises(ValueError, match='window'):
            layers.fused_attention(qv, kv, vv, window=7)
        op = [o for o in main.global_block().ops
              if o.type == 'flash_attention'][0]
        assert op.attrs['window'] == 7
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, = exe.run(main, feed={'q': np.asarray(q), 'k': np.asarray(k),
                                   'v': np.asarray(v)}, fetch_list=[out])
    want = ops.reference_attention(q, jnp.repeat(k, 3, axis=1),
                                   jnp.repeat(v, 3, axis=1), causal=True,
                                   window=7)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the mask as an added tile (PR 60): on the triangular grid and its band a
# pair adds the tile of its KIND and computes no mask; without a key bias
# and without padded keys the call has no bias operand
# ---------------------------------------------------------------------------

# T = 512 in 128-tiles, four a side. By window: the masked block diagonals
# (_mask_diffs) and the grid. A window of one key is the diagonal alone
# (its tile holds both edges); of a tile or a tile + 1 the diagonal and the
# band's lower edge; one that ends INSIDE a tile (130, 200) crosses two
# diagonals below the first; 400 reaches every tile (the triangle) and
# still masks the last diagonal; 512 is no window.
_TILE_WINDOWS = {
    'no_window': (None, (0,), 10),
    'one_key': (1, (0,), 4),
    'half_a_tile': (64, (0, 1), 7),
    'a_tile': (128, (0, 1), 7),
    'a_tile_and_a_key': (129, (0, 1), 7),
    'a_tile_and_two_keys': (130, (0, 1, 2), 9),
    'ends_inside_a_tile': (200, (0, 1, 2), 9),
    'two_tiles': (256, (0, 2), 9),
    'every_tile': (400, (0, 3), 10),
    'the_whole_row': (512, (0,), 10),
}


def _count_masked():
    from paddle_tpu import obs
    return {g: obs.counter('flash.tiles_masked', grid=g).value
            for g in ('band', 'triangle', 'rect')}


def _parent_backward(q, k, v, kb, do, lse, delta, scale, window):
    """The pair arithmetic of the backward bodies before PR 60, written
    out over the whole head in jnp: scale, the bias added, the mask
    SELECTED (NEG_BIG exactly where a position is not seen), p from the
    saved lse, p and ds cast to the operands' dtype for their dots,
    float32 sums."""
    dt, f32 = q.dtype, jnp.float32
    T = q.shape[2]

    def dot(eq, a, b):
        return jnp.einsum(eq, a, b, preferred_element_type=f32,
                          precision='highest')

    s = dot('bhqd,bhkd->bhqk', q, k) * scale
    if kb is not None:
        s = s + kb[:, :, None, :]
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    s = jnp.where(seen, s, -1e9)
    p = jnp.exp(s - lse[..., :1])
    dv = dot('bhqk,bhqd->bhkd', p.astype(dt), do)
    dp = dot('bhqd,bhkd->bhqk', do, v)
    ds = (p * (dp - delta[..., :1]) * scale).astype(dt)
    return (dot('bhqk,bhkd->bhqd', ds, k).astype(dt),
            dot('bhqk,bhqd->bhkd', ds, q).astype(dt), dv.astype(dt))


@pytest.mark.parametrize('with_bias', [False, True], ids=['no_bias', 'bias'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('case', sorted(_TILE_WINDOWS))
def test_mask_tiles_equal_the_computed_mask(case, dtype, with_bias,
                                            monkeypatch):
    """Batch 2 (the stale-block hazard of the strategy note). Forward: o
    and lse of the triangle or band equal the rectangular grid's TO THE
    BIT (an interior pair adds zeros, a masked position's p is exactly 0
    either way). Backward: the two kernels equal the rectangular grid's to
    the bit as well, and they and the one pass over the head equal the
    parent's pair arithmetic within the tolerances of
    test_one_pass_equals_two_passes."""
    fa = _fa()
    window, diffs, pairs = _TILE_WINDOWS[case]
    q, k, v, kb = _rand_qkv(B=2, H=2, Tq=512, Tk=512, D=16, seed=71)
    q, k, v = [jnp.asarray(x, jnp.dtype(dtype)) for x in (q, k, v)]
    if window == 1:
        # a query whose one seen key the bias removes is a degenerate row
        # (the module docstring): there the bias only shifts
        kb = np.where(kb < 0, -2.5, 0.0).astype('float32')
    bias = jnp.asarray(kb) if with_bias else None
    do = jnp.asarray(np.random.RandomState(72).randn(*q.shape), q.dtype)

    def run(schedules):
        w = fa._window_of(window, True, 512)
        qp, kp, vp, kbp, scale, bq, bk, chosen, interp, _, _ = fa._prep(
            q, k, v, bias, None, 128, 128, True, causal=True, window=w)
        o, lse = fa._fwd_call(qp, kp, vp, kbp, True, scale, bq, bk, interp, w)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        delta = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))
        args = (qp, kp, vp, kbp, do, lse, delta, True, scale, bq, bk)
        return (kbp, chosen, o, lse, delta, scale, w,
                [fa._bwd_call(*args, s, interp, w) for s in schedules])

    masked0 = _count_masked()
    kbp, chosen, o, lse, delta, scale, w, (head, two) = run(['head', None])
    assert (kbp is None) == (not with_bias) and chosen == 'head'
    nb = fa._band(w, 128, 4)
    assert fa._mask_diffs(w, 128, 4) == diffs \
        and fa._tile_pairs(4, nb) == pairs
    grid = 'triangle' if nb is None else 'band'
    assert _rose(masked0, _count_masked()) == {
        g: 2 * sum(4 - d for d in diffs) * (g == grid) for g in masked0}
    # the rectangular grid at EQUAL tiles, whose bodies still compute the
    # mask of every pair (_mask_causal) and add a bias of zeros: the
    # arithmetic every causal pair had before PR 60
    monkeypatch.setattr(fa, '_use_tri', lambda *a: False)
    kbr, _, o_r, lse_r, _, _, _, (two_r,) = run([None])
    assert kbr is not None
    assert np.array_equal(np.asarray(o, np.float32),
                          np.asarray(o_r, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(lse_r))
    for a, b, name in zip(two, two_r, ('dq', 'dk', 'dv')):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name
    want = _parent_backward(q, k, v, kbr, do, lse, delta, scale, w)
    for got in (head, two):
        for a, b, name in zip(got, want, ('dq', 'dk', 'dv')):
            assert a.dtype == jnp.dtype(dtype)
            assert _rel_norm(a, b) <= (BF16_EPS if dtype == 'bfloat16'
                                       else 1e-6), name


def test_padded_keys_keep_their_bias_on_the_triangle():
    """A row of 450 pads to 512: no bias was given, but the padded keys
    are removed through one, so the call keeps the operand; the result is
    the rectangular grid's to the bit and the oracle's."""
    fa = _fa()
    q, k, v, _ = _rand_qkv(B=2, H=2, Tq=450, Tk=450, D=16, seed=73)
    kbp = fa._prep(*map(jnp.asarray, (q, k, v)), None, None, 128, 128, True,
                   causal=True)[3]
    assert kbp.shape == (2, 1, 512) and float(kbp[0, 0, 449]) == 0.0 \
        and float(kbp[0, 0, 450]) == fa.NEG_BIG
    # queries AFTER the padded keys would see them but for the bias: not
    # causal, on the grid that keeps the operand whatever it holds
    got = ops.flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True)
    want = ops.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got = ops.flash_attention(q, k, v, causal=True, window=200, block_q=128,
                              block_k=128, interpret=True)
    want = ops.reference_attention(q, k, v, causal=True, window=200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('n', [1, 2, 3, 4, 5, 6])
def test_pair_kinds_against_the_positions(n):
    """For a triangle of n tiles a side (tiles of 4 positions) and every
    window from one key to past the row, so every band `nb` and every
    place a window can end in a tile: a pair's kind is 0 exactly where the
    positions say the pair holds nothing to mask, the additive tile of
    every other kind IS the pair's mask, in both enumerations, and
    _masked_pairs counts them."""
    fa = _fa()
    tile = 4
    pos = np.arange(n * tile)
    ahead = pos[:, None] - pos[None, :]
    zeros = jnp.zeros((tile, tile), jnp.float32)
    for window in [None] + list(range(1, n * tile + 2)):
        w = fa._window_of(window, True, n * tile)
        seen = ahead >= 0 if w is None else (ahead >= 0) & (ahead < w)
        blocks = seen.reshape(n, tile, n, tile).transpose(0, 2, 1, 3)
        nb, diffs = fa._band(w, tile, n), fa._mask_diffs(w, tile, n)
        assert len(diffs) <= 3 and diffs[0] == 0
        tiles = [np.zeros((tile, tile), np.float32)] + [
            np.asarray(fa._mask_causal(zeros, d * tile, 0, 0, w))
            for d in diffs]
        turned = [np.zeros((tile, tile), np.float32)] + [
            np.asarray(fa._mask_causal(zeros, d * tile, 0, 1, w))
            for d in diffs]
        for maps in (fa._tri_maps(n, nb), fa._tri_maps_kv(n, nb)):
            i, j = maps
            kinds = np.asarray(fa._pair_kind(i - j, diffs))
            for a, b, kind in zip(i, j, kinds):
                want = np.where(blocks[a, b], 0.0, fa.NEG_BIG)
                assert (kind == 0) == bool(blocks[a, b].all()), (window, a, b)
                assert np.array_equal(tiles[kind], want), (window, a, b)
                assert np.array_equal(turned[kind], want.T), (window, a, b)
            assert np.count_nonzero(kinds) == fa._masked_pairs(n, diffs)


@pytest.mark.parametrize('window,pairs,masked,grid', [
    (4096, 252, 56, 'band'), (None, 528, 32, 'triangle')])
def test_flash_tiles_masked_at_smallthinkers_sizes(window, pairs, masked,
                                                   grid):
    """16384 positions in 512-tiles, traced and not run: of the band's 252
    pairs under a window of 4096 the 32 diagonal and the 24 lower-edge
    ones add a mask, of the triangle's 528 the 32 diagonal ones, in the
    forward and in the one pass; no bias is handed in."""
    from paddle_tpu import obs
    x = jax.ShapeDtypeStruct((1, 1, 16384, 128), jnp.bfloat16)

    def read():
        return (obs.counter('flash.tiles', grid=grid).value,
                obs.counter('flash.tiles_masked', grid=grid).value)

    before = read()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, window=window, interpret=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)
    after = read()
    assert (after[0] - before[0], after[1] - before[1]) == (
        2 * pairs, 2 * masked)
    calls = [e for e in _walk(jaxpr.jaxpr, [])
             if e.primitive.name == 'pallas_call']
    # forward: the two maps and q, k, v; the one pass: do, lse, delta too
    assert [len(e.invars) for e in calls] == [5, 8]
