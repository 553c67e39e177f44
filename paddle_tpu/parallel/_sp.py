"""Shared plumbing for the sequence-parallel attention strategies
(ring_attention.py, ulysses.py): both take [B, H, T, D] q/k/v with T
sharded over one mesh axis and an optional [B, T] additive key bias."""
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def sp_shard_map(body, mesh, q, k, v, axis, key_bias, check_vma=True):
    """Wrap a per-shard attention body in shard_map with the sequence
    sharding contract; defaults a zero key bias. check_vma=False only for
    bodies containing pallas calls, whose ShapeDtypeStructs carry no
    varying-mesh-axes info (the default check rejects them). When the mesh
    also carries 'dp', the batch dim stays dp-sharded — each dp replica
    runs its own sequence ring/all_to_all over its batch slice instead of
    re-computing the global batch."""
    from jax import shard_map

    bdim = 'dp' if ('dp' in mesh.shape and axis != 'dp') else None
    if bdim is not None and q.shape[0] % mesh.shape['dp']:
        raise ValueError(
            'sequence-parallel attention on a dp-carrying mesh: batch %d '
            'must be divisible by dp=%d (drop the remainder, e.g. '
            'paddle.batch(..., drop_last=True))'
            % (q.shape[0], mesh.shape['dp']))
    qkv_spec = P(bdim, None, axis, None)
    kb_spec = P(bdim, axis)
    if key_bias is None:
        key_bias = jnp.zeros((q.shape[0], k.shape[2]), jnp.float32)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(qkv_spec, qkv_spec, qkv_spec, kb_spec),
                   out_specs=qkv_spec, check_vma=check_vma)
    return fn(q, k, v, key_bias)


def stack_unit_params(per_unit_params):
    """[{param pytree} per stage/expert] -> one pytree with a leading unit
    axis (shard it over the pp/ep mesh axis)."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_unit_params)


