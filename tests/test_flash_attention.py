"""Flash-attention kernel numerics (pallas interpret mode on CPU) and the
fused_attention fluid op, vs the plain-XLA oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.layers as layers

from util import fresh_program


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
    'causal_triangular_3x3': dict(Tq=384, Tk=384, bias=True, causal=True,
                                  block=128),
    'uneven_lengths': dict(Tq=9, Tk=33, bias=True),
    'lse_cotangent': dict(Tq=128, Tk=128, bias=True, causal=True, lse=True),
    # the one-pass backward (PR 27; the four single-tile cases above take
    # it too): a length that pads to its tile, and a causal head walked in
    # two 512 sub-tiles, the first of which sees half the keys
    'one_pass_pads_200': dict(Tq=200, Tk=200, bias=True, causal=True),
    'one_pass_causal_sub_tiles': dict(Tq=1024, Tk=1024, bias=True,
                                      causal=True, lse=True),
}


def _ref_o_lse(q, k, v, bias, causal):
    """reference_attention and the logsumexp of its scores."""
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * q.shape[-1] ** -0.5
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        s = jnp.where(jnp.arange(q.shape[2])[:, None]
                      >= jnp.arange(k.shape[2])[None, :], s, -1e9)
    return (ops.reference_attention(q, k, v, key_bias=bias, causal=causal),
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
        return ops.flash_attention_lse(
            q, k, v, key_bias=bias, causal=causal, block_q=c.get('block'),
            block_k=c.get('block'), interpret=True)

    def ref(q, k, v):
        return _ref_o_lse(q, k, v, bias, causal)

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


def _kernel_bodies(dtype, causal, block_q=128, block_k=128, T=256, Tk=None):
    """The traced bodies of the kernels of one forward and backward at
    2 x 2 x T x 64 (blocks of 128: the rectangular grid, or the triangular
    one when causal; None: the default tiles), as (name, [eqns])."""
    q = jax.ShapeDtypeStruct((2, 2, T, 64), dtype)
    k = jax.ShapeDtypeStruct((2, 2, Tk or T, 64), dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: ops.flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, k)
    calls = [e for e in _walk(jaxpr.jaxpr, [])
             if e.primitive.name == 'pallas_call']
    return [(e.params['jaxpr'].debug_info.func_name,
             _walk(e.params['jaxpr'], [])) for e in calls]


# path -> the forced block (None: the default tiles, one tile a head here),
# the mask, the bodies' dots sorted. Two passes: 2 + 3 + 4 = 9 dots and
# three exp passes over a score tile; one pass: 2 + 5 and two.
_BODY_PATHS = {
    'rectangular': dict(block=128, causal=False, dots=[2, 3, 4]),
    'triangular': dict(block=128, causal=True, dots=[2, 3, 4]),
    'one_pass': dict(block=None, causal=False, dots=[2, 5]),
    'one_pass_causal': dict(block=None, causal=True, dots=[2, 5]),
}


@pytest.mark.parametrize('path', sorted(_BODY_PATHS))
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_kernel_dots_take_the_inputs_dtype(dtype, path):
    """The mechanism, pinned off the chip, on every path: all dots of the
    bodies (2 + 3 + 4 with two backward kernels, 2 + 5 with one) take
    operands of the refs' dtype and give float32; with float32 in nothing
    is cast at all; with bf16 in p and ds are cast down as dot operands
    and nothing is cast up, but for the one-pass body's k tile on its way
    through the transposition that dq = (k^T ds^T)^T needs. No score-sized
    tile is transposed on any path: the transposed-score bodies turn round
    their lane-broadcast float32 row statistics, and the one-pass body k
    and dq^T besides. Each backward body takes exp of one score tile: the
    one-pass backward computes s, p, dp and ds once."""
    c = _BODY_PATHS[path]
    bodies = _kernel_bodies(jnp.dtype(dtype), c['causal'], c['block'],
                            c['block'])
    assert len(bodies) == len(c['dots']), [n for n, _ in bodies]
    assert all(('_tri' in name) == (path == 'triangular')
               for name, _ in bodies)
    if path.startswith('one_pass'):
        assert [n for n, _ in bodies][1:] == ['_bwd_fused_kernel']
    n_dots = []
    for name, eqns in bodies:
        fused = name == '_bwd_fused_kernel'
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
            assert [c[2] for c in up] == ([(256, 64)] if fused else []), name
        else:
            assert not floats, (name, floats)
    assert sorted(n_dots) == c['dots']


def test_flash_lowered_counts_once_per_call_per_lowering_by_dtype():
    from paddle_tpu import obs

    def count():
        return {d: obs.counter('flash.lowered', operands=d).value
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


def _count_passes():
    from paddle_tpu import obs
    return {p: obs.counter('flash.backward', passes=p).value
            for p in ('one', 'two')}


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
    assert (after['one'] - before['one'], after['two'] - before['two']) \
        == (1, 0)
    for a, b, name in zip(got, grads(ref), 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


@pytest.mark.parametrize('causal', [False, True], ids=['full', 'causal'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_one_pass_equals_two_passes(dtype, causal):
    """The same inputs through both schedules (512 x 512 scores: one pass
    in sub-tiles of 256 against the triangular or rectangular grid of
    256-tiles) agree to the dots' rounding: the arithmetic is the same,
    the order of the sums over blocks is not."""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    q, k, v, kb = _rand_qkv(B=1, H=2, Tq=512, Tk=512, D=32, seed=41)
    q, k, v = [jnp.asarray(x, jnp.dtype(dtype)) for x in (q, k, v)]
    q, k, v, kb, scale, bq, bk, one_pass, interp, _, _ = fa._prep(
        q, k, v, jnp.asarray(kb), None, 256, 256, True, causal=causal)
    assert not one_pass and (bq, bk) == (256, 256)
    o, lse = fa._fwd_call(q, k, v, kb, causal, scale, bq, bk, interp)
    r = np.random.RandomState(42)
    do = jnp.asarray(r.randn(*o.shape), o.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))
    args = (q, k, v, kb, do, lse, delta, causal, scale, bq, bk)
    one = fa._bwd_call(*args, True, interp)
    two = fa._bwd_call(*args, False, interp)
    for a, b, name in zip(one, two, ('dq', 'dk', 'dv')):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        assert _rel_norm(a, b) <= (BF16_EPS if dtype == 'bfloat16'
                                   else 1e-6), name


@pytest.mark.parametrize('case,kw,bwd_calls', [
    ('one_tile', dict(T=256), 1),
    ('one_tile_causal', dict(T=256, causal=True), 1),
    ('causal_1024_in_sub_tiles', dict(T=1024, causal=True), 1),
    ('two_tiles', dict(T=2048), 2),
    ('causal_2048_triangular', dict(T=2048, causal=True), 2),
    ('block_q_forced_below_T', dict(T=256, block_q=128), 2),
    ('blocks_forced_causal_1024', dict(T=1024, causal=True, block_q=512,
                                       block_k=512), 2),
    ('cross_lengths_two_key_tiles', dict(T=256, Tk=2048), 2),
], ids=lambda x: x if isinstance(x, str) else None)
def test_backward_routing_reads_the_shapes(case, kw, bwd_calls):
    """One pallas_call in the backward where a head's scores are one tile
    (the forward's, or the table's largest when nobody forced a tile), two
    otherwise; the counter says the same, once per call per lowering."""
    def trace():
        return [name for name, _ in _kernel_bodies(
            jnp.bfloat16, kw.get('causal', False), kw.get('block_q'),
            kw.get('block_k'), kw['T'], kw.get('Tk'))]

    before = _count_passes()
    names = trace()
    after = _count_passes()
    assert len(names) == 1 + bwd_calls, names
    assert ('_bwd_fused_kernel' in names) == (bwd_calls == 1)
    want = {'one': int(bwd_calls == 1), 'two': int(bwd_calls == 2)}
    assert {p: after[p] - before[p] for p in want} == want
    if case == 'one_tile':      # a tile the caller passes is a forced tile
        assert len(_kernel_bodies(jnp.bfloat16, False, 128, None, 256)) == 3


def test_flash_backward_counts_once_per_call_per_lowering():
    def two_calls(q, k, v):
        o = ops.flash_attention(q, k, v, interpret=True)
        o = ops.flash_attention(o, k, v, causal=True, block_q=128,
                                block_k=128, interpret=True)
        return o.astype(jnp.float32).sum()

    step = jax.jit(jax.grad(two_calls, argnums=(0, 1, 2)))
    x = jnp.ones((1, 1, 256, 8), jnp.bfloat16)
    before = _count_passes()
    for _ in range(3):          # three steps, one lowering
        step(x, x, x)
    after = _count_passes()
    assert {p: after[p] - before[p] for p in after} == {'one': 1, 'two': 1}
