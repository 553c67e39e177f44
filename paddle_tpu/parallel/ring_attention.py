"""Ring attention: sequence/context parallelism over a mesh axis.

TPU-first answer to long-context scaling (the reference caps sequence
length by single-GPU memory; see machine_translation.py max_length): shard
the sequence axis of q/k/v over a mesh axis, keep q local, and rotate the
k/v shards around the ring with ppermute while accumulating the online
softmax — each device only ever holds O(T/n) keys, so max context scales
linearly with the ring size, and the ppermute rides the ICI torus
concurrently with the local attention block (compute hides comm).

Use inside shard_map (ring_attention) or via the pjit-level wrapper
(ring_self_attention) which sets up the shard_map over a Mesh axis.
"""
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

NEG_BIG = -1e9


def _resolve_impl(impl, interpret):
    """None -> env override, else the kernel where it compiles (flash when
    the caller says Mosaic, dense when it says interpreter — the
    interpreted kernel is a test vehicle, not a path); typos raise rather
    than silently running the O(Tl^2) dense body."""
    if impl is None:
        import os
        impl = os.environ.get('PADDLE_TPU_RING_IMPL',
                              'dense' if interpret else 'flash')
    if impl not in ('flash', 'dense'):
        raise ValueError(
            "ring attention impl must be 'flash' or 'dense', got %r" % impl)
    return impl


def ring_attention(q, k, v, axis_name, key_bias=None, causal=False,
                   sm_scale=None, impl=None, *, interpret):
    """Per-shard body (call inside shard_map).

    q, k, v: [B, H, T_local, D] — the sequence axis sharded over axis_name.
    key_bias: [B, T_local] additive bias for the local keys (or None).
    impl: 'flash' runs each local block through the pallas flash kernel
        (no [Tl, Tl] score matrix ever materializes — the long-context MXU
        path) and merges ring steps with logsumexp statistics; 'dense' is
        the plain-XLA einsum body. None selects flash when the kernel
        compiles (interpret=False), dense otherwise (overridable with
        PADDLE_TPU_RING_IMPL).
    interpret: the caller's pallas-mode decision for the flash body
        (ops/flash_attention.py): False on a TPU mesh, True off it.
    """
    impl = _resolve_impl(impl, interpret)
    if impl == 'flash':
        return _ring_attention_flash(q, k, v, axis_name, key_bias, causal,
                                     sm_scale, interpret)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32) * sm_scale
    if key_bias is None:
        key_bias = jnp.zeros((B, Tl), jnp.float32)
    # non-differentiable mask, matching ops.flash_attention / ulysses
    key_bias = lax.stop_gradient(key_bias)

    m = jnp.full((B, H, Tl), -1e30, jnp.float32)
    l = jnp.zeros((B, H, Tl), jnp.float32)
    acc = jnp.zeros((B, H, Tl, D), jnp.float32)
    kc, vc, kbc = k, v, key_bias
    perm = [(i, (i + 1) % n) for i in range(n)]

    qpos = idx * Tl + jnp.arange(Tl)

    def one_step(s, m, l, acc, kc, vc, kbc):
        src = (idx - s) % n           # whose kv shard we currently hold
        sc = jnp.einsum('bhqd,bhkd->bhqk', qf, kc.astype(jnp.float32))
        sc = sc + kbc[:, None, None, :].astype(jnp.float32)
        if causal:
            kpos = src * Tl + jnp.arange(Tl)
            sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, NEG_BIG)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            'bhqk,bhkd->bhqd', p, vc.astype(jnp.float32))
        if s != n - 1:   # the last shard needs no further rotation
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            kbc = lax.ppermute(kbc, axis_name, perm)
        return m_new, l, acc, kc, vc, kbc

    # ring size = mesh axis size is static, so the loop unrolls at trace time
    for s in range(int(n)):
        m, l, acc, kc, vc, kbc = one_step(s, m, l, acc, kc, vc, kbc)

    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, key_bias, causal, sm_scale,
                          interpret):
    """Ring schedule with the pallas flash kernel as the per-step block.

    Each ring step computes (o_s, lse_s) = flash(q_local, kv_shard); steps
    merge with the standard partial-softmax combine
    (ops/flash_attention.py merge_lse)
        lse' = logaddexp(lse, lse_s)
        o'   = o * e^{lse-lse'} + o_s * e^{lse_s-lse'}
    which is exact (the union of key shards IS full attention). Causality
    across shards is a per-step trichotomy on the ring offset — fully
    visible (earlier shard: plain kernel), diagonal (own shard: causal
    kernel), fully masked (later shard: skip) — so the kernel's local
    causal mask is always position-correct. Gradients flow through both
    kernel outputs (ops.flash_attention._flash_lse_bwd) and the combine.
    """
    from ..ops.flash_attention import flash_attention_lse, merge_lse

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    if key_bias is None:
        key_bias = jnp.zeros((B, Tl), jnp.float32)
    key_bias = lax.stop_gradient(key_bias)

    o = jnp.zeros((B, H, Tl, D), jnp.float32)
    lse = jnp.full((B, H, Tl), -1e30, jnp.float32)
    kc, vc, kbc = k, v, key_bias
    perm = [(i, (i + 1) % n) for i in range(n)]

    for s in range(int(n)):
        src = (idx - s) % n           # whose kv shard we currently hold
        if causal:
            def visible(kc=kc, vc=vc, kbc=kbc):
                return flash_attention_lse(q, kc, vc, key_bias=kbc,
                                           causal=False, sm_scale=sm_scale,
                                           interpret=interpret)

            def diagonal(kc=kc, vc=vc, kbc=kbc):
                return flash_attention_lse(q, kc, vc, key_bias=kbc,
                                           causal=True, sm_scale=sm_scale,
                                           interpret=interpret)

            def masked():
                return (jnp.zeros((B, H, Tl, D), q.dtype),
                        jnp.full((B, H, Tl), -1e30, jnp.float32))

            o_s, lse_s = lax.cond(
                src > idx, masked,
                lambda: lax.cond(src == idx, diagonal, visible))
        else:
            o_s, lse_s = flash_attention_lse(q, kc, vc, key_bias=kbc,
                                             causal=False, sm_scale=sm_scale,
                                             interpret=interpret)
        o, lse = merge_lse(o, lse, o_s, lse_s)
        if s != n - 1:   # the last shard needs no further rotation
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            kbc = lax.ppermute(kbc, axis_name, perm)
    return o.astype(q.dtype)


def ring_self_attention(mesh, q, k, v, axis='sp', key_bias=None,
                        causal=False, sm_scale=None, impl=None, *,
                        interpret):
    """pjit-level entry: q/k/v [B, H, T, D] with T sharded over mesh axis."""
    from ._sp import sp_shard_map
    # resolve HERE so check_vma is exact
    impl = _resolve_impl(impl, interpret)

    def body(q, k, v, kb):
        return ring_attention(q, k, v, axis, key_bias=kb, causal=causal,
                              sm_scale=sm_scale, impl=impl,
                              interpret=interpret)

    # pallas ShapeDtypeStructs carry no varying-mesh-axes info, so the vma
    # check must be off when the flash body runs
    return sp_shard_map(body, mesh, q, k, v, axis, key_bias,
                        check_vma=impl == 'dense')
