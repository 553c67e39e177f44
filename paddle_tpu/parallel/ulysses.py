"""All-to-all (Ulysses-style) sequence parallelism.

The second long-context strategy next to ring attention
(ring_attention.py): instead of rotating K/V shards around the ring, ONE
all-to-all re-partitions the sharded tensors from sequence-sharded
[B, H, T/n, D] to head-sharded [B, H/n, T, D], the fused flash-attention
kernel runs locally per head group, and a second all-to-all restores the
sequence sharding. Comm volume is O(1) exchanges instead of n ppermute
steps, at the price of requiring n | H; memory stays O(T) per chip since
the local compute is the flash kernel. Ring wins when T is extreme; both
ride the same mesh axis and are interchangeable (key_bias is a
non-differentiable mask in both, matching ops.flash_attention).

(The reference has no counterpart — sequence length there is capped by
single-GPU memory.)
"""
from jax import lax

from ..ops.flash_attention import flash_attention
from ._sp import sp_shard_map

__all__ = ['ulysses_attention', 'ulysses_self_attention']


def ulysses_attention(q, k, v, axis_name, key_bias=None, causal=False,
                      sm_scale=None, *, interpret):
    """Per-shard body (call inside shard_map).

    q, k, v: [B, H, T_local, D] with the sequence axis sharded over
    axis_name; H must be divisible by the axis size. key_bias is the
    LOCAL [B, T_local] additive key bias (or None). interpret is the
    caller's pallas-mode decision for the local flash kernel
    (ops/flash_attention.py): False on a TPU mesh, True off it.
    """
    # seq-sharded -> head-sharded: each device now owns H/n heads, full T
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kb = None
    if key_bias is not None:
        kb = lax.all_gather(key_bias, axis_name, axis=1, tiled=True)
    out = flash_attention(qg, kg, vg, key_bias=kb, causal=causal,
                          sm_scale=sm_scale, interpret=interpret)
    # head-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_self_attention(mesh, q, k, v, axis='sp', key_bias=None,
                           causal=False, sm_scale=None, *, interpret):
    """pjit-level entry: q/k/v [B, H, T, D] with T sharded over mesh
    axis `axis` (same contract as ring_self_attention)."""
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            'ulysses needs heads %% mesh axis == 0 (H=%d, %s=%d); use '
            'ring_self_attention for head counts that do not divide'
            % (q.shape[1], axis, n))

    def body(q, k, v, kb):
        return ulysses_attention(q, k, v, axis, key_bias=kb, causal=causal,
                                 sm_scale=sm_scale, interpret=interpret)

    return sp_shard_map(body, mesh, q, k, v, axis, key_bias,
                        check_vma=False)  # pallas flash kernel inside
