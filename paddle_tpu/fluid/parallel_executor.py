"""ParallelExecutor: data-parallel training over the device mesh.

Parity: reference python/paddle/fluid/parallel_executor.py + the C++ SSA
graph executor (paddle/fluid/framework/details/*) that scatters the batch
over GPUs and NCCL-allreduces gradients.

DEPRECATED shim (docs/parallel.md, docs/migration.md): data parallelism is
a first-class Program concern now — ``program.set_mesh({'dp': N})`` (plus
``ParamAttr(sharding=...)`` for parameter layouts) and plain
``Executor.run``/``run_bundle`` lower the annotated Program through ONE
GSPMD-partitioned XLA module. This class survives as a thin wrapper that
emits exactly those annotations for the duration of each ``run`` call:
``BuildStrategy.ReduceStrategy.Reduce`` becomes per-parameter ZeRO-3
sharding annotations, the feed shards over the mesh's data axis, and the
compiled step carries explicit in/out shardings + the memory plan's
donation vector — the same code path ``run_bundle`` and the Trainer use.
"""
import warnings

import numpy as np

import jax
from jax.sharding import Mesh

from .executor import Executor, global_scope
from .framework import default_main_program

__all__ = ['ParallelExecutor', 'ExecutionStrategy', 'BuildStrategy']

# ZeRO-3 floor for the Reduce build strategy's emitted annotations —
# mirrors parallel.fsdp_shard_params(min_size=1024): gather latency on a
# tiny tensor outweighs the bytes saved.
_FSDP_MIN_SIZE = 1024

_warned = [False]


def _warn_deprecated():
    if _warned[0]:
        return
    _warned[0] = True
    warnings.warn(
        "ParallelExecutor is deprecated: declare the mesh on the Program "
        "instead — program.set_mesh({'dp': N}) (ParamAttr(sharding=...) "
        "for parameter layouts) and run it through the plain "
        "Executor.run/run_bundle/Trainer. See docs/parallel.md and "
        "docs/migration.md.", DeprecationWarning, stacklevel=3)


class ExecutionStrategy(object):
    """Shim of the reference ExecutionStrategy pybind struct."""

    def __init__(self):
        self.num_threads = 0
        self.use_event = True
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1


class BuildStrategy(object):
    """Shim of the reference BuildStrategy pybind struct."""

    class ReduceStrategy(object):
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy(object):
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""


class ParallelExecutor(object):
    """reference parallel_executor.py:ParallelExecutor — now a shim that
    emits GSPMD annotations (module docstring).

    Single-host surface: the dp mesh spans this process's visible devices.
    The reference's `num_trainers`/`trainer_id` multi-node path
    (parallel_executor.py:43-46,74 — one NCCL clique across nodes) is
    accepted for API compatibility but does not grow the mesh here;
    multi-host scale-out is `parallel.init_distributed()`
    (jax.distributed) BEFORE building the executor, after which the same
    GSPMD program spans every host's devices (tests/test_multihost.py)."""

    def __init__(self, use_cuda=None, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None, devices=None,
                 num_devices=None, use_tpu=None, **kwargs):
        _warn_deprecated()
        self._program = main_program or default_main_program()
        self._loss_name = loss_name
        self._scope = scope or global_scope()
        self._build_strategy = build_strategy
        devs = devices or jax.devices()
        if num_devices is not None:
            if num_devices > len(devs):
                raise ValueError("num_devices=%d > %d visible devices"
                                 % (num_devices, len(devs)))
            devs = devs[:num_devices]
        self._mesh = Mesh(np.asarray(devs), ('dp',))
        self._ndev = len(devs)
        self._axes = (('dp', self._ndev),)
        self._exe = Executor()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope

    @property
    def device_count(self):
        return self._ndev

    def _emit_annotations(self):
        """Translate the build strategy into per-tensor sharding
        annotations: ReduceStrategy.Reduce (the reference's partitioned
        parameter updates) becomes ZeRO-3 — each large persistable
        annotated ('dp' on its first divisible dim), exactly
        parallel.fsdp_shard_params' placement rule. Returns the vars WE
        annotated so run() can revert them: like the mesh attrs, the
        annotations are armed per call — they must not leak onto the
        user's Program (or into its clones / saved artifacts) after this
        deprecated shim returns."""
        bs = self._build_strategy
        if bs is None or bs.reduce_strategy != \
                BuildStrategy.ReduceStrategy.Reduce:
            return []
        emitted = []
        for v in self._program.global_block().vars.values():
            if not v.persistable or v.sharding or v.shape is None:
                continue
            if any(d < 0 for d in v.shape):
                continue
            if int(np.prod(v.shape or (1,))) < _FSDP_MIN_SIZE:
                continue
            for d, size in enumerate(v.shape):
                if size % self._ndev == 0:
                    v.sharding = (None,) * d + ('dp',)
                    emitted.append(v)
                    break
        return emitted

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        """reference parallel_executor.py:run. The feed is ONE global batch
        (sharded over the mesh), matching feed_dict semantics.

        Implementation: arm the Program's mesh annotation for THIS call
        only (a later plain Executor.run on the same program must stay
        single-device — the scope's mesh-placed params are a separate,
        documented GSPMD property) and dispatch through the one annotated
        executor path."""
        feed = feed if feed is not None else feed_dict or {}
        p = self._program
        emitted = self._emit_annotations()
        prev = (getattr(p, '_mesh_axes', None),
                getattr(p, '_mesh_data_axis', None),
                getattr(p, '_dist_mesh', None),
                getattr(p, '_annot_axes', None))
        p._mesh_axes = self._axes
        p._mesh_data_axis = 'dp'
        p._dist_mesh = self._mesh   # pre-built: first n devices only
        p._annot_axes = self._axes
        try:
            return self._exe.run(p, feed=feed, fetch_list=fetch_list,
                                 scope=self._scope,
                                 return_numpy=return_numpy)
        finally:
            (p._mesh_axes, p._mesh_data_axis, p._dist_mesh,
             p._annot_axes) = prev
            for v in emitted:
                v.sharding = None

    def bcast_params(self):
        """Parity shim: with GSPMD-replicated params there is nothing to
        broadcast — XLA keeps replicas consistent."""
        return None
