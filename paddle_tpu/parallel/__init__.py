"""Mesh / sharding / collective utilities — the distributed backbone.

TPU-first replacement for the reference's NCCL AllReduce (paddle/fluid/
platform/nccl_helper.h + framework/details/nccl_all_reduce_op_handle.*) and
the pserver/gRPC distributed runtime (operators/send_recv + Go pserver):
parallelism is expressed as jax.sharding over a device Mesh and XLA GSPMD
inserts the collectives on ICI/DCN. Multi-host scale-out is the same program
over a bigger mesh (jax.distributed.initialize on each host).

The moe `all_to_all` dispatch pattern here (parallel/moe.py) is also the
wire under `paddle_tpu.embedding` — row-sharded huge-vocab lookup tables
with bucket/dedup/exchange lookups and per-shard sparse updates, the
pserver workload rebuilt TPU-native (docs/embedding.md).
"""
import re

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ['annotate_tp', 'auto_tp_rules', 'fsdp_shard_params',
           'make_mesh', 'data_sharding', 'replicated', 'shard_batch',
           'replicate', 'shard_params_by_rules', 'psum', 'all_gather',
           'reduce_scatter', 'ppermute', 'shard_optimizer_states',
           'init_multihost', 'init_distributed', 'process_count',
           'process_index', 'global_batch', 'Mesh', 'NamedSharding', 'P',
           'Heartbeat', 'HostLost',
           'ring_attention', 'ring_self_attention',
           'ulysses_attention', 'ulysses_self_attention',
           'pipeline_apply', 'pipeline_manual_axes', 'stack_stage_params',
           'moe_apply', 'stack_expert_params', 'LocalSGD']

from .ring_attention import ring_attention, ring_self_attention  # noqa: E402
from .ulysses import ulysses_attention, ulysses_self_attention  # noqa: E402
from .tp import annotate_tp, auto_tp_rules  # noqa: E402
from .pipeline import (pipeline_apply, pipeline_manual_axes,  # noqa: E402
                       stack_stage_params)
from .moe import moe_apply, stack_expert_params  # noqa: E402
from .local_sgd import LocalSGD  # noqa: E402
from .heartbeat import Heartbeat, HostLost  # noqa: E402


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, local_device_ids=None):
    """Join the multi-process GSPMD runtime (docs/parallel.md): wraps
    jax.distributed.initialize so every host sees the global device set,
    after which ONE annotated Program spans every host's chips — the
    Executor assembles each host's per-host feed slice into the global
    sharded batch (parallel.global_batch) and XLA places the collectives
    on ICI/DCN. The production sibling of init_multihost (which keeps the
    reference's PADDLE_TRAINER_* env compatibility).

    num_processes=1 (or unset, outside any cluster) is the single-process
    no-op: nothing to initialize, the local devices ARE the mesh. Returns
    {'num_processes', 'process_id', 'initialized'} so launchers can log
    what they joined."""
    if num_processes is None and coordinator_address is None \
            and process_id is None:
        num_processes = 1
    if num_processes is not None and int(num_processes) <= 1:
        return {'num_processes': 1, 'process_id': 0, 'initialized': False}
    if coordinator_address is None or process_id is None \
            or num_processes is None:
        raise ValueError(
            'init_distributed needs coordinator_address, num_processes '
            'and process_id for a %r-process cluster (got %r, %r, %r)'
            % (num_processes, coordinator_address, num_processes,
               process_id))
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id),
        local_device_ids=local_device_ids)
    return {'num_processes': int(num_processes),
            'process_id': int(process_id), 'initialized': True}


def process_count():
    """Number of processes in the (initialized) runtime; 1 single-host."""
    return jax.process_count()


def process_index():
    """This process's id in the runtime; 0 single-host."""
    return jax.process_index()


def global_batch(sharding, local_data):
    """Assemble a global sharded array from THIS process's slice of the
    batch (docs/parallel.md): under a multi-process mesh each host feeds
    only the rows its devices own (`reader.shard(num_hosts, host_id)`
    upstream), and jax.make_array_from_process_local_data stitches the
    per-host slices into one global jax.Array — no host ever
    materializes (or transfers) the whole batch. Single-process, the
    local slice IS the global batch and this is a plain device_put."""
    if jax.process_count() > 1 and hasattr(
            jax, 'make_array_from_process_local_data'):
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(local_data))
    if not isinstance(local_data, jax.Array):
        # device_put straight from host memory into the sharded
        # placement — staging through jnp.asarray would commit the whole
        # batch to device 0 first
        local_data = np.asarray(local_data)
    return jax.device_put(local_data, sharding)


_mh_warned = [False]


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None, local_device_ids=None):
    """Join a multi-host mesh: wraps jax.distributed.initialize so every
    host sees the global device set, then the SAME GSPMD program spans
    ICI+DCN (the reference instead spawned pserver processes and connected
    trainers over gRPC, transpiler/distribute_transpiler.py:167).

    Arguments default from the reference's launcher environment
    (PADDLE_TRAINER_ENDPOINTS/PADDLE_TRAINERS/PADDLE_TRAINER_ID) so
    reference-style cluster scripts work unchanged; returns False (no-op)
    when neither args nor env describe a cluster — single-host dev keeps
    working without any setup.

    DEPRECATED shim (docs/migration.md): `init_distributed` is the
    first-class multi-process entry of the GSPMD executor path — explicit
    cluster arguments, a structured return, and the documented pairing
    with `reader.shard` + per-host feeds. This wrapper survives for the
    PADDLE_TRAINER_* env compatibility only.
    """
    import os
    import warnings
    if not _mh_warned[0]:
        _mh_warned[0] = True
        warnings.warn(
            'parallel.init_multihost is deprecated: call '
            'parallel.init_distributed(coordinator_address=..., '
            'num_processes=..., process_id=...) — the multi-process init '
            'of the first-class GSPMD path (docs/parallel.md, '
            'docs/migration.md). init_multihost remains only for '
            'PADDLE_TRAINER_* env-driven launchers.',
            DeprecationWarning, stacklevel=2)
    if coordinator_address is None:
        eps = os.environ.get('PADDLE_TRAINER_ENDPOINTS', '')
        if eps:
            coordinator_address = eps.split(',')[0].strip()
    if num_processes is None and os.environ.get('PADDLE_TRAINERS'):
        num_processes = int(os.environ['PADDLE_TRAINERS'])
    if process_id is None and os.environ.get('PADDLE_TRAINER_ID'):
        process_id = int(os.environ['PADDLE_TRAINER_ID'])
    if (coordinator_address is None or process_id is None
            or num_processes in (None, 0, 1)):
        return False  # incomplete cluster description: single-host no-op
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id,
        local_device_ids=local_device_ids)
    return True


def make_mesh(axes=None, devices=None):
    """Build a Mesh from {'dp': 2, 'tp': 4}-style axis sizes (row-major)."""
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {'dp': len(devices)}
    names = tuple(axes.keys())
    sizes = tuple(axes.values())
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError("mesh needs %d devices, only %d available"
                         % (n, len(devices)))
    arr = np.asarray(devices[:n]).reshape(sizes)
    return Mesh(arr, names)


def data_sharding(mesh, axis='dp', ndim=2):
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated(mesh):
    return NamedSharding(mesh, P())


def shard_batch(mesh, value, axis='dp'):
    """Place a host batch sharded along its leading dim."""
    arr = jnp.asarray(np.asarray(value))
    return jax.device_put(arr, data_sharding(mesh, axis, arr.ndim))


def replicate(mesh, value):
    return jax.device_put(jnp.asarray(np.asarray(value)), replicated(mesh))


def shard_params_by_rules(values, mesh, rules):
    """Apply tensor-parallel shardings by name pattern.

    values: dict name -> array; rules: [(regex, PartitionSpec)]. Unmatched
    names are replicated. This is how tp/ep layouts are declared — GSPMD
    then partitions every matmul touching the sharded weights and inserts
    the all-reduces, replacing hand-written Megatron-style comm.
    """
    out = {}
    for name, v in values.items():
        spec = None
        for pat, s in rules:
            if re.search(pat, name):
                spec = s
                break
        sh = NamedSharding(mesh, spec if spec is not None else P())
        try:
            out[name] = jax.device_put(v, sh)
        except ValueError as e:
            import warnings
            warnings.warn(
                "shard_params_by_rules: %s does not fit spec %s (%s); "
                "replicating instead" % (name, spec, e))
            out[name] = jax.device_put(v, replicated(mesh))
    return out


def _already_mesh_placed(v):
    """True for values a previous sharding pass placed with a
    non-replicated NamedSharding — later passes leave them alone so
    composed recipes (ZeRO state + FSDP params) don't undo each other."""
    sh = getattr(v, 'sharding', None)
    return (isinstance(sh, NamedSharding)
            and any(s is not None for s in sh.spec))


def shard_optimizer_states(values, mesh, axis='dp'):
    """ZeRO-style sharding of optimizer accumulators over the dp axis —
    the TPU answer to pserver memory scaling (each "server shard" is a mesh
    coordinate holding 1/N of the state). Values already mesh-sharded by a
    previous pass are left untouched."""
    out = {}
    n = mesh.shape[axis]
    for name, v in values.items():
        if _already_mesh_placed(v):
            out[name] = v
        elif v.ndim >= 1 and v.shape[0] % n == 0:
            out[name] = jax.device_put(
                v, NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1)))))
        else:
            out[name] = jax.device_put(v, replicated(mesh))
    return out


def fsdp_shard_params(values, mesh, axis='dp', min_size=1024):
    """ZeRO-3 / FSDP parameter sharding: every large parameter is sharded
    over the data axis (first divisible dim), so per-chip parameter HBM
    scales 1/N; GSPMD inserts the all-gather at each use site and the
    matching reduce-scatter on the gradient, which is exactly the FSDP
    schedule. Small tensors (< min_size elements) stay replicated — the
    gather latency outweighs the memory.

    Beyond the reference: its pserver sharding (slice_var_up) only moved
    OPTIMIZER memory off the trainers; this shards the parameters
    themselves. Combine with shard_optimizer_states for full ZeRO-3 (in
    either order — both passes skip values the other already sharded).
    """
    out = {}
    n = mesh.shape[axis]
    for name, v in values.items():
        if _already_mesh_placed(v):
            out[name] = v
            continue
        spec = None
        if hasattr(v, 'ndim') and v.ndim >= 1 and v.size >= min_size:
            for d in range(v.ndim):
                if v.shape[d] % n == 0:
                    spec = P(*([None] * d), axis)
                    break
        if spec is None:
            out[name] = jax.device_put(v, replicated(mesh))
        else:
            out[name] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


# -- collective wrappers (usable inside shard_map'ped fns) --
def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)
