"""Local SGD — the TPU analogue of the reference's async training mode.

Reference transpiler/distribute_transpiler.py:185-206 (sync_mode=False,
wired into listen_and_serv at :281) lets every trainer push gradients and
pull parameters without a barrier: replicas advance on stale parameters and
updates mix asynchronously. That shape exists to hide slow-network latency
behind computation; inside one XLA module there is no lock-free parameter
server to talk to, and GSPMD's replicated parameters are bit-identical by
construction.

The honest TPU mapping is LOCAL SGD (post-local SGD): each dp replica owns
ITS OWN parameter copy (a leading replica axis sharded over dp), takes
`sync_steps` purely local optimizer steps — no cross-replica traffic at all
— then one `pmean` over ICI averages the copies. Statistically this is the
same regime async pserver training targets (replica divergence between
mixes, periodic consensus) with strictly cheaper communication.

Used directly (functional API), and pointed to by the Executor's loud
warning when a DistributeTranspiler program carries sync_mode=False.
"""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

__all__ = ['LocalSGD']


def _leaf_spec(x, axis):
    """Shard the leading (replica) axis; everything else stays local.
    0-d leaves (scalar step counts, temperatures) have no leading dim to
    split — they replicate to every replica."""
    if jnp.ndim(x) == 0:
        return P()
    return P(axis, *([None] * (jnp.ndim(x) - 1)))


class LocalSGD(object):
    """Drive per-replica optimizer steps with periodic parameter averaging.

    step_fn(params, batch) -> (new_params, aux) is the USER's purely local
    update (forward + grad + optimizer) written for ONE replica; params is
    any pytree. LocalSGD runs it under shard_map so each dp shard advances
    its own copy, and `sync` averages the copies with one collective.

        ls = LocalSGD(step_fn, mesh, axis='dp', sync_steps=4)
        params = ls.replicate(params)       # add + shard the replica axis
        for i, batch in enumerate(stream):
            params, aux = ls.step(params, batch)   # zero ICI traffic
            if (i + 1) % ls.sync_steps == 0:
                params = ls.sync(params)           # one pmean over ICI
        final = ls.collapse(params)         # consensus copy, replica axis

    sync_steps=1 degenerates to synchronous data-parallel (every step
    averages), matching the reference's sync_mode=True semantics.
    """

    def __init__(self, step_fn, mesh, axis='dp', sync_steps=1):
        self.mesh = mesh
        self.axis = axis
        self.sync_steps = int(sync_steps)
        self.n = mesh.shape[axis]
        ax = axis

        def local_body(params, batch):
            # shard_map hands each device its [1, ...] slice of the
            # replica axis; strip it, step locally, put it back
            p = jax.tree_util.tree_map(lambda x: x[0], params)
            new_p, aux = step_fn(p, batch)
            return (jax.tree_util.tree_map(lambda x: x[None], new_p),
                    jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None],
                                           aux))

        def sync_body(params):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, ax), params)

        def specs_like(tree, leading_only=False):
            return jax.tree_util.tree_map(
                lambda x: P(ax) if leading_only else _leaf_spec(x, ax), tree)

        def _step(params, batch):
            return _shard_map(
                local_body, mesh=self.mesh,
                in_specs=(specs_like(params), specs_like(batch)),
                out_specs=(specs_like(params), P(ax)),
            )(params, batch)

        def _sync(params):
            return _shard_map(
                sync_body, mesh=self.mesh,
                in_specs=(specs_like(params),),
                out_specs=specs_like(params),
            )(params)

        self._step = jax.jit(_step)
        self._sync = jax.jit(_sync)

    # -- state movement -------------------------------------------------
    def replicate(self, params):
        """Tile every leaf with a leading replica axis of size n, sharded
        over the mesh axis (each device starts from the same copy)."""
        def place(x):
            x = jnp.asarray(x)
            tiled = jnp.broadcast_to(x[None], (self.n,) + x.shape)
            sh = NamedSharding(self.mesh, _leaf_spec(tiled, self.axis))
            return jax.device_put(tiled, sh)
        return jax.tree_util.tree_map(place, params)

    def shard_batch(self, batch):
        """Split a host batch along dim 0 across replicas."""
        def place(x):
            x = jnp.asarray(x)
            sh = NamedSharding(self.mesh, _leaf_spec(x, self.axis))
            return jax.device_put(x, sh)
        return jax.tree_util.tree_map(place, batch)

    def collapse(self, params):
        """Average the replica copies down to one ordinary pytree."""
        synced = self._sync(params)
        return jax.tree_util.tree_map(lambda x: np_like(x), synced)

    # -- the two phases -------------------------------------------------
    def step(self, params, batch):
        """One purely local step on every replica (no collectives)."""
        return self._step(params, batch)

    def sync(self, params):
        """Average all replica copies (one pmean over the mesh axis)."""
        return self._sync(params)


def np_like(x):
    """First replica of a synced leaf (all replicas equal post-sync)."""
    import numpy as np
    return np.asarray(x[0])
