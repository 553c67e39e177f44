"""Executor: lowers a Program into ONE jitted XLA computation.

Parity: reference python/paddle/fluid/executor.py:256 + the C++ interpreter
(paddle/fluid/framework/executor.cc) that walks the ProgramDesc op-by-op,
launching a CUDA kernel per op.

TPU-first redesign: Executor.run symbolically evaluates the whole block
through the lowering registry inside a single jax.jit trace, keyed by
(program version, feed signature, fetch names). XLA then fuses the entire
step — forward, backward (one jax.grad over the traced forward, contributed
by the `autodiff` op that backward.append_backward plants), optimizer
updates — into one module: one device launch per step vs hundreds.
Persistable variables (parameters, optimizer state, BN stats) live in the
Scope as device arrays and are donated to each step, so updates are
in-place in HBM.

Pipelined hot loop (docs/perf.md): `run_bundle` scans K steps inside ONE
compiled module (one dispatch + one host round-trip per K steps),
`run(sync='async')` returns lazy FetchHandles so the host runs ahead of
the device, and the persistent compilation cache (utils/compile_cache.py,
JAX_COMPILATION_CACHE_DIR) reuses XLA executables across processes (zero
cold compiles on restart).
"""
import collections
import contextlib
import functools
import os
import threading
import time
import warnings
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from .. import obs
from ..utils import compile_cache
from . import core
from . import ops_impl  # noqa: F401  (registers all rules)
from .framework import default_main_program
from .lowering import SeqValue

# ZeRO floor (elements): tensors smaller than this keep their tp-only
# layout instead of ('tp','dp')-product sharding — mirrors
# parallel.fsdp_shard_params(min_size=1024). Tests lower it to exercise
# the product path on tiny models.
_ZERO_MIN_SIZE = 1024

__all__ = ['Executor', 'FetchHandle', 'global_scope', 'scope_guard',
           '_switch_scope', 'Scope', 'anomaly_guard']

# Compile-time stderr capture for XLA partitioner diagnostics
# (docs/parallel.md): the SPMD partitioner reports "Involuntary full
# rematerialization" — a sharding transition it can only do by
# replicating the whole tensor — through C++ logging on fd 2, invisible
# to Python warnings and absent from any API. PADDLE_TPU_REMAT_CAPTURE=0
# disables the fd redirection for embedders whose stderr is not dup-able.
ENV_REMAT_CAPTURE = 'PADDLE_TPU_REMAT_CAPTURE'
_REMAT_MARKER = b'Involuntary full rematerialization'


def _remat_capture_enabled():
    return os.environ.get(ENV_REMAT_CAPTURE, '1').lower() not in (
        '0', 'off', 'false', 'no')


# fd 2 is process-global state: two overlapping captures (two Executors
# compiling on different threads) would interleave dup2 save/restore and
# could leave stderr pointing at a deleted temp file forever. One capture
# at a time; a contended compile simply runs uncaptured (missing one
# remat detection beats corrupting fd 2).
_CAPTURE_FD2_LOCK = threading.Lock()


@contextlib.contextmanager
def _capture_fd2(sink):
    """Tee C++-level stderr (fd 2) into `sink` (a list of bytes) for the
    duration, re-emitting everything to the real stderr afterwards —
    capture must never swallow a diagnostic, only OBSERVE it. This is the
    only hook that sees XLA's C++ log lines (glog writes straight to the
    fd); Python-level warnings hooks never fire for them. Degrades to a
    no-op when the fd cannot be duplicated (exotic embedders) or when
    another thread is already capturing."""
    import io
    import sys as _sys
    import tempfile
    if not _CAPTURE_FD2_LOCK.acquire(blocking=False):
        yield
        return
    try:
        try:
            _sys.stderr.flush()
        except Exception:
            pass
        old = tmp = None
        try:
            old = os.dup(2)
            tmp = tempfile.TemporaryFile()
            os.dup2(tmp.fileno(), 2)
        except (OSError, ValueError, io.UnsupportedOperation):
            # partial setup must not leak per compile: close whatever
            # succeeded before degrading to a no-op
            if old is not None:
                try:
                    os.close(old)
                except OSError:
                    pass
            if tmp is not None:
                try:
                    tmp.close()
                except Exception:
                    pass
            yield
            return
        try:
            yield
        finally:
            try:
                _sys.stderr.flush()
            except Exception:
                pass
            os.dup2(old, 2)
            os.close(old)
            try:
                tmp.seek(0)
                data = tmp.read()
                tmp.close()
                if data:
                    sink.append(data)
                    os.write(2, data)
            except Exception:
                pass
    finally:
        _CAPTURE_FD2_LOCK.release()


def anomaly_guard(program=None, enable=True, max_consecutive_skips=None):
    """Enable the COMPILED-path anomaly guard (`check_nan_inf` for the
    one-module world): the jitted step computes a cheap health vector
    inside the XLA module — finiteness of the loss and of every gradient,
    plus the global grad-norm — and, when the step is unhealthy, SKIPS it:
    every persistable output (params, optimizer state, BN stats) is
    `where(healthy, new, old)`-selected back to its pre-step value, the
    same policy AMP loss-scaling uses for overflowed steps. No eager
    fallback, no extra launch: the guard is a few fused reductions on
    values the backward pass already produced.

    The reference's FLAGS_check_nan_inf aborted the process from the C++
    interpreter loop; that loop no longer exists on the compiled path, and
    a long-running job is better served by skip-and-continue. The eager
    per-op attribution mode is still available via
    fluid.debugger.check_nan_inf().

    After each guarded run, `exe.last_step_health` holds the numpy health
    vector and `exe.skipped_steps` counts skips. With
    max_consecutive_skips=N, the N-th consecutive unhealthy step raises
    FloatingPointError on the host (divergence, not a transient)."""
    if program is None:
        program = default_main_program()
    program._anomaly_guard = bool(enable)
    program._anomaly_guard_max_skips = max_consecutive_skips
    program._bump_version()
    return program


class _VarHolder(object):
    """Mimics the pybind Variable handle (find_var().get_tensor())."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _TensorHandle(self._scope, self._name)

    def set(self, value, place=None):
        self._scope.vars[self._name] = jnp.asarray(value)


class _TensorHandle(object):
    """The pybind Tensor surface on a scope var: reads like an ndarray
    (__array__), writes back with set(value, place) — the reference idiom
    `scope.find_var(n).get_tensor().set(arr, place)` loads pretrained
    parameters in place (book test_label_semantic_roles.py:180)."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def _raw(self):
        v = self._scope.vars[self._name]
        return v.data if isinstance(v, SeqValue) else v

    def __array__(self, dtype=None, copy=None):
        if copy is False:
            # NumPy 2 __array__ contract: materializing a device array on
            # the host always copies, so a no-copy request is unsatisfiable
            raise ValueError(
                'converting a device tensor to numpy requires a '
                'device-to-host copy; copy=False cannot be satisfied')
        a = np.asarray(self._raw())
        if dtype is not None and a.dtype != np.dtype(dtype):
            a = a.astype(dtype)
        elif copy:
            a = a.copy()
        return a

    def set(self, value, place=None):
        self._scope.vars[self._name] = jnp.asarray(value)

    def shape(self):
        # metadata only — no device-to-host transfer
        return list(self._raw().shape)

    def __repr__(self):
        return '_TensorHandle(%r, shape=%r)' % (self._name, self.shape())


class Scope(object):
    """name -> device array store, optionally chained to a parent scope
    (reference paddle/fluid/framework/scope.h: kid scopes fall back to
    the parent on lookup; writes stay local)."""

    def __init__(self, parent=None):
        self.vars = collections.OrderedDict()
        self.parent = parent

    def find_var(self, name):
        if name in self.vars:
            return _VarHolder(self, name)
        if self.parent is not None:
            return self.parent.find_var(name)
        return None

    def var(self, name):
        self.vars.setdefault(name, None)
        return _VarHolder(self, name)

    def new_scope(self):
        return Scope(parent=self)

    def _chain_get(self, name, default=None):
        s = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return default

    def _chain_set(self, name, value):
        """Update the scope that OWNS `name` (so persistable updates made
        while running under a kid scope land where the var lives); new
        names are created locally."""
        s = self
        while s is not None:
            if name in s.vars:
                s.vars[name] = value
                return
            s = s.parent
        self.vars[name] = value

    def __contains__(self, name):
        if name in self.vars:
            return True
        return self.parent is not None and name in self.parent


_global_scope = Scope()


def global_scope():
    return _global_scope


def _switch_scope(scope):
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    return prev


@contextlib.contextmanager
def scope_guard(scope):
    prev = _switch_scope(scope)
    try:
        yield
    finally:
        _switch_scope(prev)


def _spec_key(spec):
    """A PartitionSpec's identity for the compiled-step cache key, with
    trailing Nones dropped: a step's OUTPUT shardings come back
    canonicalized (P('tp', None) -> P('tp',)), and keying on the spelling
    would recompile the whole step on its second run."""
    entries = tuple(spec)
    while entries and entries[-1] is None:
        entries = entries[:-1]
    return str(entries)


def _named_sharding(v):
    """A mesh-placed array's NamedSharding, else None."""
    if isinstance(v, jax.Array) and isinstance(v.sharding, NamedSharding):
        return v.sharding
    return None


def _as_fetch_name(f):
    from .framework import Variable
    if isinstance(f, Variable):
        return f.name
    return str(f)


from .step_artifact import (StepArtifact, _feed_signature, _is_annotated,
                            stable_signature as _stable_sig)


class _StepKey(NamedTuple):
    """What `Executor._place_and_key` derives every step: the compiled
    step's cache key and everything it was derived from."""
    key: tuple
    key_id: str             # short id of `key` in telemetry
    feed_vals: dict         # the placed feed
    feed_bytes: int
    feed_sig: tuple
    fetch_names: list
    persist_in: tuple       # scope-initialized persistables, sorted
    persist_shardings: dict
    jit_shardings: Any      # the annotation path's trees, else None
    dist_mesh: Any
    amp: bool
    quant: bool
    guard: bool
    opt: str


class _Prepared(NamedTuple):
    """What `Executor._prepare` returns; `lookup` is {'outcome', 'key',
    'entries'}."""
    compiled: StepArtifact
    feed_vals: dict
    persist: dict
    lookup: dict
    feed_bytes: int


# Process-wide executor telemetry (docs/observability.md). Shared,
# UNLABELED instruments: per-executor labels would grow the registry
# without bound under executor churn (tests, notebooks); the
# per-instance view lives in plain ints behind exe.cache_stats.
_C_MISSES = obs.counter('executor.cache.misses')
_C_FEED_BYTES = obs.counter('executor.feed.bytes')
# of those, the bytes `_put` handed to PJRT as views (below)
_C_FEED_RESHAPED = obs.counter('executor.feed.reshaped_bytes')
_C_SKIPPED = obs.counter('anomaly.skipped_steps')
# async-fetch pipeline (docs/perf.md): how many run(sync='async') fetch
# handles are outstanding (dispatched, not yet host-synced), and the
# executor.host_stall.seconds histogram (recorded via obs.span in
# FetchHandle.block) measuring time the host actually BLOCKED on the
# device — the number that proves (or disproves) the overlap.
_G_INFLIGHT = obs.gauge('executor.inflight')
_C_BUNDLED_STEPS = obs.counter('executor.bundle.steps')
# involuntary-rematerialization detections during compile (the MULTICHIP
# blind spot: the warning only ever lived in dryrun stderr tails; now it
# is an executor.remat_detected event + this counter, so a sharding
# regression shows up in obs_report)
_C_REMAT = obs.counter('executor.remat_detected')
# sharded-embedding subsystem (docs/embedding.md): upper bound on table
# rows touched by sparse updates this process ran (the per-step bound is
# static — the id count of the step's lookups; dedup/merge can only
# shrink it). The per-key geometry lives in the embedding.lookup /
# embedding.update_rows run-log events; this counter carries the volume.
_C_EMBED_ROWS = obs.counter('embedding.rows_touched')

# A large dense host array crosses the link in a shape the host can copy
# in runs (docs/perf.md): PJRT lays a host array out for the device's
# tiling on the host, before the DMA, and for `f32[256,224,224,3]` that
# relayout moves two elements at a time and takes twice as long as the
# link. `_put` hands such an array over as views of the same buffer,
# `[shape[0], the rest]` cut along its rows, and the device gives it its
# declared shape. On a TPU v5e (PERF.md section 6, PR 52,
# `tools/bench_feed_put.py`; put to landed in the declared shape, medians
# of 20): the 154 MB batch 38.0 to 38.4 ms as it is, 18.8 to 20.7 as
# `[256, 150528]`, 15.5 in 8 pieces of 19 MB (each piece's relayout runs
# under the DMA of the one before; 16 pieces 14.9), of which the device's
# reshape is 2.8; flat it takes 45 to 48 (the device's reshape 20.6).
# _VIEW_FEED_BYTES is the smallest power of two at which one view beat
# or tied the plain put for every shape swept (`--sweep`, declared
# against one view, ms): [n, 32, 32, 3] 1 MiB 1.25 / 1.15 to 1.22, 2 MiB
# 1.97 / 1.23 to 1.37, 4 MiB 3.23 / 1.49; [n, 100, 50] and
# [n, 3, 64, 64] still LOSE at 2 MiB (1.16 / 1.32, 1.11 / 1.37), tie at
# 4 (1.75 / 1.58, 1.60 / 1.58) and win from 8 (2.87 / 2.00).
_VIEW_FEED_BYTES = 1 << 22
# A piece holds at least this much: at 8 to 9 MB a piece the pieces
# bought 0.3 to 0.8 ms of 3.0 to 4.9, at 19 MB 3.3 to 5.3 of 18.8 to
# 20.7; a piece's put costs the host 0.1 to 0.2 ms alone and 0.7 ms in
# the cell's step, where it waits for room behind the pieces before it.
_VIEW_PIECE_BYTES = 1 << 24
_SUBLANES, _LANES = 8, 128
# Where that holds. The host's own backend has no tiling to lay out for:
# there the views cost a copy the plain put does not make (19 MB: 15.0
# against 1.9 ms; CPU, PR 52).
_VIEW_PLATFORMS = ('tpu',)


def _run_views(arr):
    """The views in which `_put` hands the host array `arr` to PJRT when
    it does not go as it is, else None: for a C-contiguous array of at
    least `_VIEW_FEED_BYTES` whose rows the device's tiling breaks
    (`ndim` >= 3, a trailing dimension that is no multiple of the 128
    lanes) and which as `[shape[0], the rest]` fills the device's
    (8, 128) tiles (at least 8 rows of at least a tile's 1024 elements:
    a row's last lane tile then pads it by under an eighth), that view
    cut along its rows into pieces of whole tiles of at least
    `_VIEW_PIECE_BYTES` (the last: what is left). Reshapes and row
    slices of a C-contiguous array are views: no host copy is made
    here, and none for an array that is not (it goes as it is)."""
    if (arr.nbytes < _VIEW_FEED_BYTES or arr.ndim < 3
            or arr.shape[-1] % _LANES == 0 or arr.shape[0] < _SUBLANES
            or arr.size < arr.shape[0] * _SUBLANES * _LANES
            or not arr.flags.c_contiguous):
        return None
    rows = arr.reshape(arr.shape[0], -1)
    step = -(-_VIEW_PIECE_BYTES // (arr.nbytes // len(rows)))
    step = -(-step // _SUBLANES) * _SUBLANES
    return [rows[i:i + step] for i in range(0, len(rows), step)]


@functools.partial(jax.jit, static_argnums=0)
def _declared_shape(shape, *pieces):
    rows = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    return rows.reshape(shape)


# The parts of a first call (docs/observability.md): jax reports how long
# its own stages took through jax.monitoring duration events. While an
# `executor.first_call` span is open on this thread the listener keeps
# each event's interval, so the span can say how much of it was Python
# tracing and lowering and how much the backend (XLA's compile, or the
# read from the persistent cache). Intervals, not sums: a jitted function
# traced inside another reports inside its caller's interval.
_JAX_TRACE_EVENTS = ('/jax/core/compile/jaxpr_trace_duration',
                     '/jax/core/compile/jaxpr_to_mlir_module_duration')
_JAX_BACKEND_EVENT = '/jax/core/compile/backend_compile_duration'
_JAX_RETRIEVAL_EVENT = '/jax/compilation_cache/cache_retrieval_time_sec'
_first_call_open = threading.local()
_first_call_listening = []      # non-empty once the listener is registered


def _on_jax_duration(event, duration, **_):
    parts = getattr(_first_call_open, 'parts', None)
    if parts is not None:
        t1 = time.perf_counter()
        parts.append((event, t1 - duration, t1))


def _listen_first_call():
    """Opens this thread's collection of jax's duration events (the
    listener is registered on first use, once per process) and returns
    the list it fills until `_first_call_open.parts` is set to None."""
    if not _first_call_listening:
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _first_call_listening.append(True)
    parts = _first_call_open.parts = []
    return parts


def _covered(intervals, lo, hi):
    """(start of the first, total length of the union) of `intervals`
    clipped to [lo, hi]; (lo, 0.0) when there are none."""
    total, start, end = 0.0, None, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            start = a if start is None else start
            total += b - a
            end = b
    return (lo if start is None else start), total


def _record_first_call_parts(parts, lo, hi, key_id):
    """Post-hoc children of the open `executor.first_call` span from
    jax's own duration events between `lo` and `hi`: `.trace` (jaxpr
    trace plus lowering to MLIR) and `.backend` (XLA's compile, or
    the read from the persistent cache: `cached` when every backend
    compile of the call was served by a retrieval). What is left of
    the span is its self time: dispatch and the step's run."""
    trace = [(a, b) for e, a, b in parts if e in _JAX_TRACE_EVENTS]
    backend = [(a, b) for e, a, b in parts if e == _JAX_BACKEND_EVENT]
    retrieved = sum(e == _JAX_RETRIEVAL_EVENT for e, _, _ in parts)
    t0, seconds = _covered(trace, lo, hi)
    obs.span_record('executor.first_call.trace', seconds, t0=t0,
                    key=key_id)
    t0, seconds = _covered(backend, lo, hi)
    obs.span_record('executor.first_call.backend', seconds, t0=t0,
                    key=key_id, cached=0 < retrieved == len(backend))


# RLock: FetchHandle.__del__ may run from a GC pass triggered INSIDE an
# _inflight_delta call on the same thread (allocation under the lock);
# a plain Lock would self-deadlock. The instrument locks in obs.metrics
# are reentrant for the same reason.
_inflight_lock = threading.RLock()
_inflight_n = 0


def _inflight_delta(d):
    global _inflight_n
    with _inflight_lock:
        _inflight_n += d
        _G_INFLIGHT.set(_inflight_n)


class FetchHandle(object):
    """Lazy fetch from `run(sync='async')`: wraps the step's device-side
    output so the device-to-host sync happens at FIRST READ
    (np.asarray / float() / .block()), not inside run(). The host can
    dispatch the next step(s) while the device still works on this one —
    the async dispatch window that hides host latency.

    Contract:
      * `np.asarray(handle)` (or `float(handle)` for one-element fetches)
        blocks until the value is on the host; the wait is recorded in the
        `executor.host_stall.seconds` histogram, and the result is cached.
      * `.ready` is a non-blocking completion probe.
      * deferred errors: a step that fails ON DEVICE (or a conversion that
        fails) raises at the first read — and again at every later read —
        not at run() time (docs/migration.md).
      * the `executor.inflight` gauge counts handles created minus handles
        synced (or garbage-collected unread)."""

    __slots__ = ('_value', '_materialize', '_result', '_synced')

    def __init__(self, value, materialize=None):
        self._value = value
        self._materialize = materialize if materialize is not None \
            else (lambda v=value: np.asarray(v))
        self._result = None
        self._synced = False
        _inflight_delta(1)

    @property
    def ready(self):
        """Non-blocking: has the device finished producing this value?"""
        if self._synced:
            return True
        try:
            return bool(self._value.is_ready())
        except AttributeError:
            return True

    def block(self):
        """Materialize on the host (cached). Records the blocking wait as
        executor.host_stall; re-raises a deferred device error on every
        read."""
        if not self._synced:
            was_ready = self.ready
            try:
                with obs.span('executor.host_stall', ready=was_ready):
                    self._result = (True, self._materialize())
            except BaseException as e:
                self._result = (False, e)
            finally:
                self._synced = True
                self._value = None
                self._materialize = None
                _inflight_delta(-1)
        ok, payload = self._result
        if ok:
            return payload
        raise payload

    def __array__(self, dtype=None, copy=None):
        a = np.asarray(self.block())
        if dtype is not None and a.dtype != np.dtype(dtype):
            a = a.astype(dtype)
        elif copy:
            a = a.copy()
        return a

    def __float__(self):
        a = np.asarray(self.block())
        if a.size != 1:
            raise TypeError(
                'float() on a fetch handle of shape %r — only one-element '
                'fetches convert to a scalar' % (a.shape,))
        return float(a.reshape(-1)[0])

    def __del__(self):
        # never-read handle: release its inflight slot so the gauge does
        # not drift (the device work itself completes regardless)
        if not getattr(self, '_synced', True):
            self._synced = True
            try:
                _inflight_delta(-1)
            except Exception:
                pass   # interpreter shutdown: registry may be gone

    def __repr__(self):
        state = 'synced' if self._synced else (
            'ready' if self.ready else 'pending')
        return 'FetchHandle(%s)' % state


class StepHandle(object):
    """Pinned low-overhead driver for ONE compiled (program, feed-sig,
    fetch) step — the continuous-batching decode engine's hot loop
    (paddle_tpu.serving.decode) calls the same jitted module thousands of
    times per second with per-slot donated state, and `run()`'s per-call
    work (feed placement, cache key derivation, persist re-collection
    from the scope, fetch conversion, step spans) would dominate the
    step itself. `Executor.acquire_step` resolves all of that ONCE:

      * the donated (written) persistables live as device arrays INSIDE
        the handle between calls and are donated to every step — the
        memory plan's in-place state update, with zero per-call scope
        walks. The scope is kept in sync after each step, so
        `save_inference_model`/tools reading the scope always see the
        live arrays, never a donated (invalidated) buffer;
      * read-only persistables (weights) and the feed signature are
        fixed at acquire time; `step()` takes pre-placed feed arrays (or
        nothing) and returns the raw device-side fetches — the caller
        decides when to pay the host sync;
      * the first call still classifies compile-vs-persistent-hit via
        the executor's timed-first-call probe, so warmup telemetry
        (executor.compile spans, cache_stats) is identical to run()'s.
        Steady-state calls record NO per-step run-log events (a decode
        loop would write thousands of span records per second); the
        handle's own `steps` carries the volume instead.

    Programs that CREATE persistables (startup-style) are rejected at
    acquire: the donated pytree structure must be stable across calls.
    RNG-consuming ops see a fixed key unless `seed` is passed per call.
    """

    __slots__ = ('_exe', '_compiled', '_scope', '_program', '_donated',
                 '_readonly', '_key', '_first', '_lookup', 'steps')

    def __init__(self, exe, compiled, scope, program, persist, lookup):
        self._exe = exe
        self._compiled = compiled
        self._scope = scope
        self._program = program
        donated, readonly = compiled.plan.split(persist)
        self._donated = donated
        self._readonly = readonly
        self._key = jax.random.key(0)
        # a compiled step already first-called via run() (warmup) needs
        # no compile-classification probe here
        self._first = not compiled._obs_compiled
        self._lookup = lookup
        self.steps = 0

    @property
    def state(self):
        """Merged name -> device array view of the step's persistable
        state (donated + read-only). Mutate via set_state."""
        view = dict(self._readonly)
        view.update(self._donated)
        return view

    def state_dict(self):
        """Placement-true {name: jax.Array} of this handle's persistable
        state — the artifact's state_dict seam (step_artifact.StepArtifact
        .state_dict), read through the scope the handle keeps in sync;
        what save_sharded consumes for a checkpoint taken mid-decode."""
        return self._compiled.state_dict(self._scope)

    def set_state(self, name, value):
        """Replace one persistable between steps (the decode engine's
        slot join: row-scatter a fresh request's state into the pool).
        Routes to the donated or read-only dict and keeps the scope in
        sync."""
        if name in self._donated:
            self._donated[name] = value
        elif name in self._readonly:
            self._readonly[name] = value
        else:
            raise KeyError('no persistable %r in this step (have %r)'
                           % (name, sorted(self._donated)
                              + sorted(self._readonly)))
        self._scope._chain_set(name, value)

    def step(self, feed=None, seed=None):
        """One execution; returns the raw fetch list (device arrays, in
        acquire-time fetch_list order). `feed` must match the
        acquire-time signature exactly (pre-placed arrays; None for a
        feedless step program)."""
        # the handle OWNS the donated persistables between calls; if
        # another path (run()/run_bundle/a second handle) drove the same
        # (program, scope) meanwhile, it re-collected and donated the
        # scope buffers this handle still points at — the next dispatch
        # would die with an opaque deleted-buffer error (on real chips)
        # or silently diverge from the scope (CPU, where donation is a
        # no-op). Scope identity is the platform-independent tell.
        for n, v in self._donated.items():
            if self._scope._chain_get(n) is not v:
                raise RuntimeError(
                    'StepHandle state invalidated: persistable %r was '
                    'rewritten in the scope by another execution path '
                    '(run()/run_bundle/another handle) since the last '
                    'step — a pinned handle must be the only driver of '
                    'its (program, scope); re-acquire_step() to resume'
                    % n)
        key = self._key if seed is None else jax.random.key(
            np.uint32(int(seed) % (1 << 32)))
        args = (self._donated, self._readonly, feed or {}, key)
        # the step's device counters stay unread: a handle's caller decides
        # when to pay a host sync, and its steps record no spans to carry
        # them (Executor._read_device is run()'s and run_bundle()'s)
        if self._first:
            res, outcome = self._exe._timed_first_call(
                self._compiled, 'step', args, self._lookup['key'],
                handle=True)
            self._first = False
            if outcome != 'compile':
                self._lookup['outcome'] = outcome
        else:
            res = self._compiled(*args)
        for n, v in res.new_persist.items():
            self._donated[n] = v
            self._scope._chain_set(n, v)
        if res.health is not None:
            self._exe._observe_health(self._program, res.health)
        self.steps += 1
        return res.fetches


class Executor(object):
    """Parity: reference python/paddle/fluid/executor.py:256."""

    def __init__(self, place=None):
        if place is None:
            place = core.default_place()
        self.place = place
        # resolve now: an explicit place this process cannot honour is
        # refused here (core.DeviceUnavailableError), not at the first run
        self._jax_device = place.jax_device()
        self._cache = {}
        self._run_counter = 0
        # anomaly-guard observability (see anomaly_guard()): health of the
        # most recent guarded step, total skipped steps, and the running
        # consecutive-skip count backing max_consecutive_skips
        self.last_step_health = None
        self.skipped_steps = 0
        self._consecutive_skips = 0
        # per-instance compile-cache stats (process-wide aggregates go to
        # the registry counters above)
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_evictions = 0
        self._persistent_hits = 0
        self._last_compile_s = None
        self._last_cache_lookup = None   # {'outcome', 'key', 'entries'}
        # AOT warm signatures (docs/perf.md#aot): load_warm_signatures
        # arms the set of stable signature hashes whose executables were
        # imported from an exported artifact; first calls matching one
        # classify as aot_hit (vs plain persistent_hit / compile)
        self._aot_sigs = None
        self._aot_entries = None   # sig -> {'step': bool, 'bundles': set}
        self._aot_manifest = None
        self._aot_hits = 0
        self._aot_stale = 0
        # first calls that really XLA-compiled (vs deserialized): the
        # number the zero-online-compile contracts assert on
        self._online_compiles = 0
        # involuntary-rematerialization detections across this
        # executor's compiles (see _scan_remat); tests assert 0 on the
        # pipeline compositions that used to warn (MULTICHIP_r05 tail)
        self.remat_detected = 0
        # Persistent XLA compilation cache (utils/compile_cache.py): with
        # JAX_COMPILATION_CACHE_DIR set, or after an entry point called
        # compile_cache.enable(), a restarted process (Trainer resume,
        # serving warmup) deserializes already-built modules — zero cold
        # compiles on the second run. None = not wired, no probe.
        self._compile_cache_dir = compile_cache.wired()
        # cache entries THIS executor's first calls wrote (names):
        # export_warm_signatures ships exactly these when it can, instead
        # of whatever else accumulated in a shared long-lived cache dir
        self._warm_entries = set()

    def _device(self):
        return self._jax_device

    def _to_device(self, val, var=None):
        if isinstance(val, jax.Array):
            from jax.sharding import NamedSharding
            if (isinstance(val.sharding, NamedSharding)
                    or len(val.sharding.device_set) > 1):
                # mesh-placed by the caller — don't collapse the sharding
                return val
            return jax.device_put(val, self._device())
        if isinstance(val, SeqValue):
            return SeqValue(jax.device_put(jnp.asarray(val.data), self._device()),
                            jax.device_put(jnp.asarray(val.lengths), self._device()),
                            val.outer_lengths)
        from .lod_tensor import LoDTensor
        if isinstance(val, LoDTensor):
            sv = val.to_seq_value()
            return self._to_device(sv)
        return self._put(np.asarray(val))

    def _put(self, arr):
        """THE place a host ndarray meets this executor's device
        (`_to_device`, `run_bundle`'s stacker): put as it is, or, on a
        TPU and where `_run_views` names them, as views of the same
        buffer, one put after the other, that a jitted reshape dispatched
        right behind the last gives the declared shape, so that what the
        step, the feed signature and `_await_feed` see is the array the
        plain put would have made."""
        dev = self._device()
        views = _run_views(arr) if dev.platform in _VIEW_PLATFORMS else None
        if views is None:
            return jax.device_put(arr, dev)
        _C_FEED_RESHAPED.inc(arr.nbytes)
        return _declared_shape(
            arr.shape, *[jax.device_put(v, dev) for v in views])

    def _host_stage(self, val):
        """Host-side feed normalization WITHOUT device placement (the
        annotated path's counterpart to _to_device): LoDTensor ->
        SeqValue, everything else to numpy, leaving already-placed
        jax.Arrays alone. The mesh placement happens once, in
        _annot_shard_feed."""
        if isinstance(val, (jax.Array, SeqValue)):
            return val
        from .lod_tensor import LoDTensor
        if isinstance(val, LoDTensor):
            return val.to_seq_value()
        return np.asarray(val)

    def _annot_placement(self, program, scope):
        """The GSPMD annotation path (docs/parallel.md): a Program that
        declared its mesh via `set_mesh()` (with per-tensor specs on
        `ParamAttr(sharding=...)`/`Variable.sharding`) is lowered WITHOUT
        any strategy wrapper — this places every scope-initialized
        persistable on the mesh per its annotation (replicated when
        un-annotated), caches the built Mesh on the program, and returns
        it. The compiled step then runs with explicit in/out shardings
        and the memory plan's donation vector (_prepare)."""
        import collections as _c
        from .. import parallel
        axes = _c.OrderedDict(program._mesh_axes)
        mesh = parallel.make_mesh(axes)
        program._dist_mesh = mesh
        program._annot_axes = program._mesh_axes
        from jax.sharding import NamedSharding, PartitionSpec as P
        for v in program.list_vars():
            if not v.persistable:
                continue
            val = scope._chain_get(v.name)
            if val is None or isinstance(val, SeqValue):
                continue
            spec = P(*v.sharding) if v.sharding else P()
            try:
                placed = jax.device_put(val, NamedSharding(mesh, spec))
            except ValueError as e:
                warnings.warn(
                    'sharding annotation %r on %r does not fit the mesh '
                    '%r (%s); replicating instead — program_lint --mesh '
                    'catches this statically' % (
                        v.sharding, v.name, dict(axes), e))
                placed = jax.device_put(val, NamedSharding(mesh, P()))
            scope._chain_set(v.name, placed)
        return mesh

    def _ensure_dist_placement(self, program, scope):
        """Consume the program's parallelism declaration and return its
        Mesh (or None). Two sources, one consumer: (a) the first-class
        GSPMD annotation path — `Program.set_mesh()` + per-tensor
        sharding annotations (docs/parallel.md); (b) the legacy
        DistributeTranspiler `_dist_config` — build the dp mesh (capped
        at the locally visible devices; multi-host grows it via
        parallel.init_distributed), place parameters (replicated by
        default; dp-sharded ZeRO-3/FSDP when shard_parameters is set),
        and ZeRO-shard optimizer accumulators over dp (the reference's
        slice_var_up pserver memory scaling)."""
        mesh = getattr(program, '_dist_mesh', None)
        if mesh is not None and _is_annotated(program) \
                and getattr(program, '_annot_axes', None) \
                != program._mesh_axes:
            mesh = None   # set_mesh changed the spec: rebuild
        if mesh is not None:
            # Already built from annotations/_dist_config, or placed
            # directly by ParallelExecutor. False sentinel -> single
            # device, no-op.
            if mesh:
                self._replace_strays(program, scope, mesh)
            return mesh or None
        dist = getattr(program, '_dist_config', None)
        if dist is None:
            if _is_annotated(program):
                return self._annot_placement(program, scope)
            return None
        if not dist.get('sync_mode', True) and not getattr(
                program, '_async_warned', False):
            # reference distribute_transpiler.py:185-206 async pserver
            # updates; inside one GSPMD module replicas are bit-identical
            # and the gradient all-reduce is part of the compiled step, so
            # the Program path stays synchronous. The supported async
            # analogue is local SGD (parallel/local_sgd.py).
            warnings.warn(
                "DistributeTranspiler sync_mode=False: the TPU Program path "
                "runs SYNCHRONOUS data-parallel (GSPMD all-reduce each "
                "step). For async-style training use "
                "paddle_tpu.parallel.LocalSGD (periodic parameter "
                "averaging, docs/distributed.md).", UserWarning,
                stacklevel=3)
            program._async_warned = True
        from .. import parallel
        n_dev = len(jax.devices())
        pp = int(dist.get('pp_size') or 1)
        pp_axis = dist.get('pp_axis', 'pp')
        sp = int(dist.get('sp_size') or 1)
        tp = int(dist.get('tp_size') or 1)
        fixed = pp * sp * tp  # structural axis sizes are never capped
        if fixed > n_dev:
            raise RuntimeError(
                'mesh needs pp=%d x sp=%d x tp=%d = %d devices but only %d '
                'are visible' % (pp, sp, tp, fixed, n_dev))
        dp = min(int(dist.get('dp_size') or 1), max(1, n_dev // fixed))
        axes = {}
        if dp > 1:
            axes['dp'] = dp
        if tp > 1:
            axes['tp'] = tp
        if pp > 1:
            axes[pp_axis] = pp
        if sp > 1:
            axes['sp'] = sp
        if not axes:
            program._dist_mesh = False
            return None
        mesh = parallel.make_mesh(axes)
        program._dist_mesh = mesh
        acc_names = {v.name for v in program.list_vars()
                     if getattr(v, '_is_optimizer_accumulator', False)}
        persistable = {v.name for v in program.list_vars() if v.persistable}
        fsdp = dist.get('shard_parameters', False)
        # ZeRO-3 subsumes the lower levels: sharding the parameters while
        # replicating Adam state (2x the params) would silently forfeit
        # the memory scaling just asked for
        zero = dist.get('shard_optimizer_states', False) or fsdp
        # tp: Megatron layouts from the program graph
        # (TensorParallelTranspiler); accumulators inherit their master
        # parameter's layout (names embed the param name, shapes match)
        tp_specs = {}
        if tp > 1:
            import re as _re
            rules = parallel.auto_tp_rules(program)
            for name in persistable:
                for pat, spec in rules:
                    if _re.search(pat, name):
                        tp_specs[name] = spec
                        break
            for name in acc_names & persistable:
                if name in tp_specs:
                    continue
                av = scope.vars.get(name)
                for pname, spec in list(tp_specs.items()):
                    pv = scope.vars.get(pname)
                    if (pname in name and av is not None and pv is not None
                            and getattr(av, 'shape', None) == pv.shape):
                        tp_specs[name] = spec
                        break
        import re as _re2
        from jax.sharding import PartitionSpec as _P
        has_dp = 'dp' in mesh.shape

        def compose_dp(spec, v):
            """Also shard a ZeRO-requested var over dp: put 'dp' on the
            first dim the tp layout left whole (and that divides). When no
            free dim divides dp (typically 1-D biases / their moments,
            whose only dim 'tp' took), shard a tp-taken dim over the
            ('tp', 'dp') PRODUCT instead — each device then holds
            size/(tp*dp) elements, the full ZeRO scaling. The product
            path (only) floors at _ZERO_MIN_SIZE elements, mirroring
            fsdp_shard_params' min_size rationale: gather latency on a
            tiny tensor outweighs the bytes saved. The free-dim 'dp'
            path above keeps its historical no-floor behavior."""
            entries = list(tuple(spec)) + [None] * (v.ndim - len(tuple(spec)))
            for i, e in enumerate(entries):
                if e is None and v.shape[i] % mesh.shape['dp'] == 0:
                    entries[i] = 'dp'
                    return _P(*entries)
            if v.size < _ZERO_MIN_SIZE:
                return _P(*entries)   # keep the tp-only layout, no warning
            prod = mesh.shape['tp'] * mesh.shape['dp']
            for i, e in enumerate(entries):
                if e == 'tp' and v.shape[i] % prod == 0:
                    entries[i] = ('tp', 'dp')
                    return _P(*entries)
            return None

        for name in persistable:
            v = scope.vars.get(name)
            if v is None or isinstance(v, SeqValue):
                continue
            if name in tp_specs:
                spec = tp_specs[name]
                wants_zero = has_dp and ((zero and name in acc_names)
                                         or (fsdp and name not in acc_names))
                if wants_zero:
                    both = compose_dp(spec, v)
                    if both is not None:
                        spec = both
                    else:
                        warnings.warn(
                            '%r keeps a tp-only layout %r (no remaining '
                            'dim divides dp=%d); its dp ZeRO sharding is '
                            'forfeited' % (name, spec, mesh.shape['dp']))
                # single placement path shared with the functional API
                # (device_put + warn-and-replicate on misfit)
                scope.vars.update(parallel.shard_params_by_rules(
                    {name: v}, mesh,
                    [('^' + _re2.escape(name) + '$', spec)]))
            elif has_dp and zero and name in acc_names:
                scope.vars.update(parallel.shard_optimizer_states(
                    {name: v}, mesh))
            elif has_dp and fsdp and name not in acc_names:
                # ZeRO-3: the parameters themselves shard over dp (the
                # reference's slice_var_up split param blocks across
                # pservers; this is its GSPMD equivalent)
                scope.vars.update(parallel.fsdp_shard_params(
                    {name: v}, mesh))
            else:
                scope.vars[name] = parallel.replicate(mesh, v)
        return mesh

    def _replace_strays(self, program, scope, mesh):
        """Re-assert mesh placement of persistables that were overwritten
        with single-device arrays since the first placement pass (io.load /
        load_inference_model / user writes into the scope) — mixing them
        with mesh-replicated feeds would fail jit's device check."""
        if len(mesh.devices.flat) <= 1:
            return
        from .. import parallel
        from jax.sharding import NamedSharding, PartitionSpec as P
        for v in program.list_vars():
            if not v.persistable:
                continue
            val = scope.vars.get(v.name)
            if (isinstance(val, jax.Array)
                    and len(val.sharding.device_set) == 1):
                if getattr(v, 'sharding', None):
                    # annotated var: re-assert ITS declared layout, not a
                    # blanket replicate (io.load overwrote a sharded
                    # param; replicating it would silently forfeit the
                    # annotation until the next cold placement)
                    try:
                        scope.vars[v.name] = jax.device_put(
                            val, NamedSharding(mesh, P(*v.sharding)))
                        continue
                    except ValueError:
                        pass   # misfit: fall through to replicate
                scope.vars[v.name] = parallel.replicate(mesh, val)

    def _annot_shard_feed(self, name, dv, mesh, program):
        """Feed placement for the annotation path: an explicitly
        annotated feed var takes its own spec; otherwise the batch dim
        shards over the program's data axis (replicated when none is
        declared or the value is a scalar). On a multi-process mesh the
        caller feeds its PER-HOST slice and the global array is
        assembled via parallel.global_batch
        (jax.make_array_from_process_local_data) — each host transfers
        only its own rows (docs/parallel.md)."""
        from .. import parallel
        from jax.sharding import NamedSharding, PartitionSpec as P
        if isinstance(dv, SeqValue):
            return SeqValue(
                self._annot_shard_feed(name, dv.data, mesh, program),
                self._annot_shard_feed(name, dv.lengths, mesh, program),
                dv.outer_lengths)
        var = program.global_block().vars.get(name)
        spec = getattr(var, 'sharding', None) if var is not None else None
        data_axis = getattr(program, '_mesh_data_axis', None)
        if spec is not None:
            # trim to the VALUE's rank: a SeqValue feed recurses here for
            # its rank-1 lengths vector with the data var's multi-dim
            # spec — only the leading (batch) entries can apply to it
            sh = NamedSharding(mesh, P(*spec[:dv.ndim]))
        elif (data_axis is not None and data_axis in mesh.shape
                and dv.ndim >= 1):
            n = mesh.shape[data_axis]
            # multi-process: dv is THIS host's slice, so the divisibility
            # contract is on the assembled global batch (local rows x
            # process_count), not on the local rows alone — checking the
            # local slice against the global axis size would spuriously
            # reject e.g. 12 local rows on a 2-host dp=8 mesh (global 24,
            # 3 rows/device: valid)
            global_rows = dv.shape[0] * jax.process_count()
            if global_rows % n:
                raise ValueError(
                    "feed %r global batch size %d (%d per-host rows x %d "
                    "processes) is not divisible by the %r mesh axis size "
                    "%d; drop the remainder (e.g. "
                    "paddle.batch(..., drop_last=True))"
                    % (name, global_rows, dv.shape[0], jax.process_count(),
                       data_axis, n))
            sh = NamedSharding(mesh, P(data_axis))
        else:
            return parallel.replicate(mesh, dv)
        return parallel.global_batch(sh, dv)

    def _dist_shard_feed(self, name, dv, mesh):
        from .. import parallel
        if isinstance(dv, SeqValue):
            return SeqValue(self._dist_shard_feed(name, dv.data, mesh),
                            self._dist_shard_feed(name, dv.lengths, mesh),
                            dv.outer_lengths)
        if 'dp' not in mesh.shape:
            # pp-only mesh: feeds replicate; microbatching happens inside
            # the pipelined step
            return parallel.replicate(mesh, dv)
        dp = mesh.shape['dp']
        if dv.ndim == 0:
            return parallel.replicate(mesh, dv)
        if dv.shape[0] % dp:
            raise ValueError(
                "distributed feed %r batch size %d is not divisible by the "
                "dp mesh size %d; drop the remainder (e.g. "
                "paddle.batch(..., drop_last=True))" % (name, dv.shape[0], dp))
        return jax.device_put(dv, parallel.data_sharding(mesh, 'dp', dv.ndim))

    def _place_feed(self, program, feed, dist_mesh):
        """Device-place one step's feed dict (dtype coercion, LoD wrapping,
        mesh sharding). Shared by _prepare and run_bundle's per-step
        stacker."""
        feed_vals = {}
        block = program.global_block()
        annot = dist_mesh is not None and _is_annotated(program)
        for name, val in feed.items():
            var = block.vars.get(name)
            # annotated path: stay on the host — _annot_shard_feed /
            # parallel.global_batch place the value DIRECTLY into its
            # mesh sharding; committing the full global batch to one
            # device first would require single-chip HBM to hold it
            # (defeating pod-scale batches) and pay a second transfer
            dv = self._host_stage(val) if annot \
                else self._to_device(val, var)
            if var is not None and var.lod_level > 0 and not isinstance(dv, SeqValue):
                # dense feed for a lod var: treat every row as full-length
                lens = (jnp if isinstance(dv, jax.Array) else np).full(
                    (dv.shape[0],), dv.shape[1], 'int32')
                dv = SeqValue(dv, lens)
            if var is not None and not isinstance(dv, SeqValue):
                want = np.dtype(var.dtype) if var.dtype != 'bfloat16' else jnp.bfloat16
                if dv.dtype != want:
                    dv = dv.astype(want)
            if annot:
                dv = self._annot_shard_feed(name, dv, dist_mesh, program)
            elif dist_mesh is not None:
                dv = self._dist_shard_feed(name, dv, dist_mesh)
            feed_vals[name] = dv
        return feed_vals

    def _prepare(self, program, feed, fetch_list, scope,
                 use_program_cache=True, verify_bundle=False, spans=False):
        """Shared front half of every path to a compiled step, three
        functions by lifetime: `_place_and_key` runs every step,
        `_build_step` once a cache key, `_bind_state` binds this call.
        The lookup it returns (`outcome` 'hit' or 'miss') is also left as
        `_last_cache_lookup`, a diagnostic for readers outside this class
        (fluid/profiler.py, the serving warmup): the caller that makes an
        entry's first call refines its `outcome` in place. `spans` (run()
        and run_bundle() pass obs.enabled()) times the placement and the
        feed as child spans of the caller's `executor.prepare`."""
        k = self._place_and_key(program, feed, fetch_list, scope, spans)
        compiled = self._cache.get(k.key) if use_program_cache else None
        if compiled is None:
            compiled = self._build_step(program, scope, k)
            if use_program_cache:
                self._cache[k.key] = compiled
            outcome = 'miss'
        else:
            self._cache_hits += 1
            outcome = 'hit'
        persist = self._bind_state(program, scope, k, compiled,
                                   verify_bundle)
        lookup = self._last_cache_lookup = {
            'outcome': outcome, 'key': k.key_id, 'entries': len(self._cache)}
        return _Prepared(compiled, k.feed_vals, persist, lookup,
                         k.feed_bytes)

    def _place_and_key(self, program, feed, fetch_list, scope, spans):
        """EVERY STEP: place the state and the feed, and derive the
        compiled step's cache key from what was placed. Returns a
        `_StepKey`: the key with everything derived on the way to it, so
        the build of a miss derives nothing again."""
        with obs.span_if(spans, 'executor.placement') as sp:
            dist_mesh = self._ensure_dist_placement(program, scope)
            if sp is not None:
                sp.fields['mesh'] = dist_mesh is not None
        with obs.span_if(spans, 'executor.feed') as sp:
            reshaped = _C_FEED_RESHAPED.value
            feed_vals = self._place_feed(program, feed, dist_mesh)
            # feed-transfer accounting: nbytes is metadata only (no device
            # sync); SeqValues carry their dense payload + length vectors
            fb = 0
            for dv in feed_vals.values():
                if isinstance(dv, SeqValue):
                    fb += int(getattr(dv.data, 'nbytes', 0))
                    fb += int(getattr(dv.lengths, 'nbytes', 0))
                else:
                    fb += int(getattr(dv, 'nbytes', 0))
            _C_FEED_BYTES.inc(fb)
            if sp is not None:
                sp.fields['bytes'] = fb
                sp.fields['reshaped'] = int(
                    _C_FEED_RESHAPED.value - reshaped)

        fetch_names = [_as_fetch_name(f) for f in fetch_list]
        feed_sig = tuple(sorted(_feed_signature(n, v) for n, v in feed_vals.items()))
        persist_in = tuple(sorted(
            v.name for v in program.list_vars()
            if v.persistable and scope._chain_get(v.name) is not None
            and v.name not in feed_vals))
        from . import amp as amp_mod
        from .passes import quant_pass as quant_mod
        amp = amp_mod.is_amp(program)
        quant = quant_mod.is_quant(program)
        guard = bool(getattr(program, '_anomaly_guard', False))
        persist_shardings = {}
        for n in persist_in:
            sh = _named_sharding(scope._chain_get(n))
            if sh is not None:
                persist_shardings[n] = sh
        shard_sig = tuple(sorted((n, _spec_key(s.spec), s.mesh)
                                 for n, s in persist_shardings.items()))
        # GSPMD annotation path: jit sharding trees from the ACTUAL
        # placements (persist values were just mesh-placed by
        # _annot_placement; feed values by _annot_shard_feed), plus the
        # raw annotations for persistables the step creates. The
        # StepArtifact derives its in/out shardings + donation vector
        # from these through the memory plan.
        jit_shardings = None
        if _is_annotated(program) and dist_mesh is not None:
            jit_shardings = {
                'persist': {n: persist_shardings.get(n)
                            for n in persist_in},
                'feed': {n: _named_sharding(v)
                         for n, v in feed_vals.items()},
                'specs': {v.name: v.sharding for v in program.list_vars()
                          if v.persistable and getattr(v, 'sharding',
                                                       None)},
            }
        from . import passes as passes_mod
        from ..ops import kernels as kernels_mod
        opt = passes_mod.opt_mode()
        # the enabled pallas-kernel set is a TRACE-time routing decision
        # (lowering.use_kernel): it must be part of the cache key or a
        # knob flip would be served the other variant's cached step.
        # `quant` mirrors `amp`: marking a program after it already ran
        # must recompile, not serve the cached fp32 module.
        key = (program._uid, program._version, feed_sig, tuple(fetch_names),
               persist_in, amp, quant,
               bool(getattr(program, '_use_remat', False)),
               shard_sig, dist_mesh, guard, opt, kernels_mod.signature())
        # short stable-within-process id naming this compiled module in
        # telemetry (step spans, compiled_op_table's header)
        key_id = '%08x' % (hash(key) & 0xFFFFFFFF)
        return _StepKey(key, key_id, feed_vals, fb, feed_sig, fetch_names,
                        persist_in, persist_shardings, jit_shardings,
                        dist_mesh, amp, quant, guard, opt)

    def _lowering_platform(self, dist_mesh):
        """The platform a step's rules lower for (ctx.platform). Under a
        mesh the arrays live on the MESH's devices, whatever the place
        says: kernel choice must follow where the data is, or a CPUPlace
        executor over a TPU mesh lowers flash_attention to the reference
        chain. tools/aot_cell.py overrides this on its own instance to
        lower a described chip's step on a host."""
        if dist_mesh is not None:
            return dist_mesh.devices.flat[0].platform
        return self._device().platform

    def _build_step(self, program, scope, k):
        """ONCE A KEY, on a miss: optimise, build, probe, fall back. The
        ladder: build the optimised clone and probe it; on any failure
        warn, record `passes.error`, and build the Program as handed."""
        from . import passes as passes_mod
        self._cache_misses += 1
        _C_MISSES.inc()
        plat = self._lowering_platform(k.dist_mesh)

        def build(run_program):
            return StepArtifact(
                run_program, run_program.global_block(), list(k.feed_vals),
                k.fetch_names, k.persist_in, k.feed_sig, k.key_id, program,
                lambda n: k.feed_vals.get(n, scope._chain_get(n)),
                amp=k.amp, platform=plat,
                persist_shardings=k.persist_shardings, mesh=k.dist_mesh,
                guard=k.guard, jit_shardings=k.jit_shardings)

        def fell_back(what, e, **stage):
            warnings.warn(
                '%s=%s: %s failed (%s: %s) — lowering the unoptimized '
                'program' % (passes_mod.ENV_OPT, k.opt, what,
                             type(e).__name__, e), RuntimeWarning)
            obs.event('passes.error', key=k.key_id, **stage,
                      error='%s: %s' % (type(e).__name__, e))

        # Ahead-of-lowering optimization (docs/passes.md):
        # PADDLE_TPU_OPT={off,default,aggressive}, applied ONCE per
        # compiled-step cache key exactly like verify — the steady state
        # re-optimizes nothing. The ORIGINAL program is never mutated; the
        # StepArtifact lowers the optimized clone. An optimizer failure
        # must never take down a training run: fall back to the
        # unoptimized lowering, loudly. A quant-marked program REQUIRES
        # the pass pipeline: unlike amp there is no ctx-flag fallback in
        # the lowering, so honoring the mark can't be conditional on
        # PADDLE_TPU_OPT.
        optimized = program
        if k.opt != 'off' or k.quant:
            try:
                optimized, _opt_report = passes_mod.optimize(
                    program, feeds=set(k.feed_vals), fetches=k.fetch_names,
                    level=k.opt if k.opt != 'off' else 'default',
                    where='executor')
            except Exception as e:
                fell_back('program optimization', e)
        # the Program -> jittable-step build (op walk, sparse plan,
        # pipeline region checks); the XLA compile itself happens on the
        # first call and is timed as executor.compile in run().
        with obs.span('executor.lowering', key=k.key_id):
            compiled = None
            if optimized is not program:
                try:
                    compiled = build(optimized)
                    # PROBE the optimized step by tracing it now (.lower()
                    # = trace to StableHLO, no XLA compile, no execution,
                    # no donation): a pass bug that slipped the
                    # optimizer's def-use self-check — e.g. a rule
                    # resolving env by attr name — must surface HERE,
                    # where the fallback catches it, not on the first
                    # run() call where nothing does. Costs one extra trace
                    # per optimized cache key, a small slice of the XLA
                    # compile the key pays anyway.
                    compiled._jitted.lower(
                        *compiled.plan.split({n: scope._chain_get(n)
                                              for n in compiled.persist_in}),
                        k.feed_vals, jax.random.key(0))
                except Exception as e:
                    fell_back('lowering the optimized program', e,
                              stage='lowering')
                    compiled = None
            if compiled is None:
                compiled = build(program)
        # report ONLY the tables whose sparse path actually arms — a
        # planned table with unresolvable ids falls back dense in
        # _grad_setup and must not be claimed sparse here
        active = sorted(w for w, r in compiled._embed_rows.items() if r)
        if active:
            obs.event(
                'embedding.update_rows', key=k.key_id, tables=active,
                rows_per_step=compiled._embed_rows_step,
                sharded=k.dist_mesh is not None)
        obs.event('executor.artifact', key=k.key_id,
                  feeds=len(k.feed_vals), fetches=len(k.fetch_names),
                  persistables=len(k.persist_in),
                  donates=len(compiled.donate_names),
                  mesh=k.dist_mesh is not None)
        return compiled

    def _bind_state(self, program, scope, k, compiled, verify_bundle):
        """BIND this call: the verifier's lookup, the persist dict the
        step is called with, and the pin of its donated state."""
        # Ahead-of-lowering program verification (docs/analysis.md):
        # PADDLE_TPU_VERIFY={off,warn,error}, ONE analysis per cache key —
        # the steady-state loop never re-analyzes, so verify overhead
        # amortizes to zero (the analysis.verify span is the proof). The
        # env model is exact for this step: the real feed names, the real
        # scope-initialized persistables, and the StepArtifact's actual
        # donation decision to cross-check.
        from . import analysis
        analysis.maybe_verify(
            program, key=('verify', verify_bundle) + k.key, where='executor',
            feeds=set(k.feed_vals), fetches=k.fetch_names,
            initialized=set(k.persist_in) | set(k.feed_vals),
            donates=compiled.mutates_persist, bundle=verify_bundle,
            dead_ops=False)
        persist = {n: scope._chain_get(n) for n in compiled.persist_in}
        # pin the donated state's placement ONCE (the artifact's donate-
        # exactly-once contract, fluid/step_artifact.py#pin_state): an
        # uncommitted first call (fresh startup outputs, io.load host
        # arrays) would re-specialize the executable on call two — the
        # old run_bundle "warm twice" wart. Mesh-placed programs and
        # place-less executors own their placement and skip this.
        pin_dev = self._device() if k.dist_mesh is None else None
        for n in compiled.pin_state(persist, pin_dev):
            scope._chain_set(n, persist[n])
        return persist

    # -- persistent-compile-cache probe -----------------------------------

    def _cc_entry_names(self):
        """Entry names in the persistent compilation cache dir (a set),
        or None when the cache is not wired. A cold compile writes
        exactly one new entry (the min-compile-time/min-size floors are
        zeroed at construction), so no-new-entries across a first jitted
        call means the executable was DESERIALIZED — a persistent hit;
        the new names also feed `_warm_entries`, the tracked set
        export_warm_signatures ships. Cost: one flat scandir (jax's
        cache is a flat directory), and only on FIRST calls — never in
        the steady-state loop. `-atime` sidecars are excluded (reads may
        touch them). Caveats (stats, not correctness): a concurrent
        writer inside the probe window can make a hit look like a
        compile, and a compile jax declines to serialize (cache-write
        error, uncacheable executable) against an already non-empty dir
        would read as a hit."""
        d = self._compile_cache_dir
        if not d:
            return None
        if not os.path.isdir(d):
            return set()
        try:
            with os.scandir(d) as it:
                return {e.name for e in it
                        if not e.name.endswith('-atime')}
        except OSError:
            return set()

    def _aot_warmed(self, aot_sig, entry):
        """Did the loaded AOT manifest warm THIS entry point of the
        signature? `entry` is 'step' or ('bundle', K) — a blob exported
        from a replica that only ever bundled at K=8 never serialized
        the K=4 scan or the plain step, so a first call for those must
        classify as an ordinary compile, not a stale blob."""
        if aot_sig is None or aot_sig not in (self._aot_sigs or ()):
            return False
        rec = (self._aot_entries or {}).get(aot_sig)
        if rec is None:
            return True   # pre-entry-index manifest: signature-level only
        if entry == 'step':
            return rec['step']
        return entry[1] in rec['bundles']

    def _timed_first_call(self, compiled, entry, args, key_id, **fields):
        """Run the first call of an artifact's entry point, 'step' or
        ('bundle', K), through its call seam (trace + XLA compile OR
        persistent-cache deserialize happen synchronously inside it),
        classify which one happened, record it, and mark the entry as
        made. Returns (the StepResult, the outcome). A real cold compile
        emits the `executor.compile` span; a persistent hit emits an
        `executor.compile.persistent_hit` event instead — so a warm-cache
        restart's run log shows ZERO compile spans for already-cached
        keys (docs/perf.md). A persistent hit whose stable signature was
        imported by load_warm_signatures classifies further as an
        `executor.compile.aot_hit` — the cold-replica zero-compile
        contract (docs/perf.md#aot); an armed signature that COMPILES
        anyway is a stale AOT blob and is flagged loudly. The compile
        window also tees fd-2 stderr to catch the SPMD partitioner's
        involuntary-rematerialization diagnostic (_scan_remat) — only on
        first calls, never in the steady-state loop."""
        fn = compiled if entry == 'step' else compiled.bundle(entry[1])
        # the stable signature is only worth hashing when a loaded
        # manifest could match it
        aot_sig = _stable_sig(compiled) if self._aot_sigs else None
        with obs.span_if(obs.enabled(), 'executor.first_call',
                         key=key_id) as sp:
            parts = _listen_first_call() if sp is not None else None
            pre = self._cc_entry_names()
            captured = []
            t0 = time.perf_counter()
            try:
                if _remat_capture_enabled():
                    with _capture_fd2(captured):
                        out = fn(*args)
                else:
                    out = fn(*args)
            finally:
                _first_call_open.parts = None
            dt = time.perf_counter() - t0
            self._scan_remat(captured, key_id)
            post = self._cc_entry_names()
            hit = bool(pre) and post == pre
            if pre is not None and post:
                # the entries this first call wrote are THIS executor's warm
                # set — what an AOT export ships
                self._warm_entries.update(post - pre)
            warmed = self._aot_warmed(aot_sig, entry)
            if hit:
                self._persistent_hits += 1
                outcome = 'aot_hit' if warmed else 'persistent_hit'
                if warmed:
                    self._aot_hits += 1
                obs.event('executor.compile.%s' % outcome, key=key_id,
                          seconds=round(dt, 6), **fields)
            else:
                outcome = 'compile'
                self._online_compiles += 1
                obs.span_record('executor.compile', dt, key=key_id, **fields)
                self._last_compile_s = dt
                if warmed:
                    # the manifest PROMISED this signature was serialized but
                    # the first call compiled online anyway (cache entry
                    # missing/invalidated, jax/backend drift): a stale blob —
                    # the exact silent failure program_lint --aot types
                    self._aot_stale += 1
                    obs.event('executor.aot.stale', key=key_id, sig=aot_sig,
                              seconds=round(dt, 6))
                    warnings.warn(
                        'AOT warm signature %s (key %s) COMPILED online '
                        'despite the loaded warm-signature manifest claiming '
                        'it — the AOT blob is stale (re-export it; '
                        'program_lint --aot checks this statically)'
                        % (aot_sig, key_id), RuntimeWarning)
            if sp is not None:
                sp.fields['outcome'] = outcome
                _record_first_call_parts(parts, t0, t0 + dt, key_id)
        if entry == 'step':
            compiled._obs_compiled = True
        else:
            compiled._obs_bundles.add(entry[1])
        return out, outcome

    def _scan_remat(self, captured, key_id):
        """Turn captured compile-time stderr into the
        `executor.remat_detected` signal: XLA's SPMD partitioner logged
        "Involuntary full rematerialization" — it could only satisfy a
        sharding transition by replicating the tensor and re-partitioning
        it, a full all-gather the program's annotations did not ask for.
        Counted per compile (event + counter + exe.remat_detected), so a
        sharding regression is a number in obs_report, not a line lost in
        a dryrun's stderr tail."""
        n = sum(c.count(_REMAT_MARKER) for c in captured)
        if not n:
            return
        self.remat_detected += n
        _C_REMAT.inc(n)
        obs.event('executor.remat_detected', key=key_id, count=n)
        warnings.warn(
            'XLA SPMD partitioner reported %d involuntary full '
            'rematerialization(s) while compiling key %s: a sharding '
            'transition could only be satisfied by replicate-then-'
            'repartition (a full all-gather per step). Check the in/out '
            'sharding consistency of the step (docs/parallel.md); '
            'program_lint --mesh flags the static cases.' % (n, key_id),
            RuntimeWarning, stacklevel=3)

    def run(self,
            program=None,
            feed=None,
            fetch_list=None,
            feed_var_name='feed',
            fetch_var_name='fetch',
            scope=None,
            return_numpy=True,
            use_program_cache=True,
            sync='auto'):
        """sync (docs/perf.md):
          'auto'  — current default behavior: fetches are materialized on
                    the host before run() returns (blocking); reserved to
                    let the executor pick the mode per call site.
          'block' — explicit blocking fetch (same as 'auto' today).
          'async' — return lazy FetchHandle objects immediately after
                    dispatch; the device-to-host sync happens at first
                    read (np.asarray/float), recorded as
                    executor.host_stall. Device errors defer to first
                    read. return_numpy decides what .block() yields for
                    sequence fetches (ndarray vs LoDTensor). NOTE: an
                    armed anomaly_guard needs a host decision per step,
                    so it syncs on the health vector before returning —
                    the wait is recorded as a host_stall
                    (cause=anomaly_guard) and mostly serializes the
                    async window.

        Spans (docs/observability.md): `executor.step` and its child
        `executor.fetch` always; while observability is on also
        `executor.prepare` (with `executor.placement` and `executor.feed`
        below it), `executor.rng`, `executor.dispatch` (a first call:
        `executor.first_call`) and, first thing inside a blocking
        `executor.fetch`, `executor.feed_wait`: the wait for the fed
        arrays' transfer, which leaves the fetch's self time the wait for
        the device."""
        if sync not in ('auto', 'block', 'async'):
            raise ValueError(
                "sync must be 'auto', 'block' or 'async', got %r" % (sync,))
        if program is None:
            program = default_main_program()
        if feed is None:
            feed = {}
        if fetch_list is None:
            fetch_list = []
        if scope is None:
            scope = global_scope()

        # Telemetry (docs/observability.md): the step span covers the
        # whole run — prepare, device dispatch, fetch sync. When
        # observability is off this is two perf_counter calls and an
        # in-memory histogram record; no file IO, no device syncs. Its
        # child spans (prepare, placement, feed, rng, dispatch,
        # first_call, and feed_wait inside the blocking fetch:
        # _await_feed) say where a step's time goes and exist only
        # while observability is on: `on` is the step's one check.
        on = obs.enabled()
        with obs.span('executor.step') as step_sp:
            with obs.span_if(on, 'executor.prepare') as sp:
                compiled, feed_vals, persist, look, feed_bytes = \
                    self._prepare(
                        program, feed, fetch_list, scope,
                        use_program_cache=use_program_cache, spans=on)
                if sp is not None:
                    sp.fields['cache'] = look['outcome']
            self._run_counter += 1
            step_sp.fields.update(run=self._run_counter,
                                  cache=look['outcome'],
                                  key=look['key'],
                                  feed_bytes=feed_bytes)
            with obs.span_if(on, 'executor.rng'):
                rng = jax.random.key(np.uint32(
                    ((program.random_seed or 0) * 2654435761
                     + self._run_counter) % (1 << 32)))
            from . import debugger as _dbg
            from . import profiler as _prof
            check = _dbg.nan_inf_check_active()
            op_hook = _prof.op_event_hook()
            if check or op_hook is not None:
                res = compiled.debug_step(
                    persist, feed_vals, rng, check_nan_inf=check,
                    on_op=op_hook)
            elif not compiled._obs_compiled:
                # first jitted call of this cache entry: jax traces and
                # XLA-compiles (or persistent-cache-deserializes)
                # synchronously inside it; _timed_first_call measures it
                # and records executor.compile ONLY for real cold
                # compiles (plus one step's dispatch either way)
                res, outcome = self._timed_first_call(
                    compiled, 'step',
                    compiled.plan.split(persist) + (feed_vals, rng),
                    look['key'])
                step_sp.fields['compiled'] = (outcome == 'compile')
                if outcome != 'compile':
                    look['outcome'] = step_sp.fields['cache'] = outcome
            else:
                with obs.span_if(on, 'executor.dispatch'):
                    res = compiled(*compiled.plan.split(persist),
                                   feed_vals, rng)
            if compiled.sparse_plan:
                _C_EMBED_ROWS.inc(compiled._embed_rows_step)
            for n, v in res.new_persist.items():
                scope._chain_set(n, v)
            if res.health is not None:
                # the guard's contract is a HOST decision per step, so
                # this syncs on the (tiny) health vector — which waits
                # for the step itself. Under sync='async' that wait is
                # the step's real host stall: record it, or the overlap
                # histogram would read ~0 and lie (the guard largely
                # serializes the async window; docs/perf.md).
                with obs.span_if(sync == 'async', 'executor.host_stall',
                                 cause='anomaly_guard'):
                    self._observe_health(program, res.health)

            fetch_f32 = bool(getattr(program, '_fetch_f32', False))

            # fetch conversion is where the device-to-host sync happens
            # (np.asarray blocks on the step's outputs) — unless
            # sync='async', which wraps each output in a lazy FetchHandle
            # and returns without waiting on the device
            with obs.span('executor.fetch', sync=sync):
                if on and sync != 'async':
                    self._await_feed(feed_vals, feed_bytes)
                out = [self._convert_fetch(v, fetch_f32, return_numpy,
                                           sync == 'async')
                       for v in res.fetches]
                if on and res.counters is not None and sync != 'async':
                    step_sp.fields['device'] = self._read_device(
                        compiled, res.counters)
        return out

    def acquire_step(self, program=None, feed=None, fetch_list=None,
                     scope=None):
        """Resolve (program, feed-sig, fetch) ONCE and return a pinned
        StepHandle whose repeated `.step()` calls skip the per-run
        prepare pass entirely — the hot-loop entry point for per-step
        state machines like the continuous-batching decode engine
        (docs/serving.md). `feed` is an EXAMPLE fixing the signature
        (may be empty/None for a feedless state-update program); the
        donated persistable state is held inside the handle between
        calls (in-place updates per the memory plan) with the scope kept
        in sync. The compiled module is the same one run() would build
        and lives in the same cache (warmup via run() or a prior handle
        carries over; `cache_stats` counts the single lookup)."""
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        prep = self._prepare(program, feed, fetch_list, scope)
        compiled = prep.compiled
        gap = compiled.plan.uninitialized(compiled.persist_in)
        if gap:
            raise ValueError(
                'acquire_step: program writes persistable(s) %r that have '
                'no scope value yet — a handle needs a stable donated '
                'state structure; run the startup program first' % gap)
        return StepHandle(self, compiled, scope, program, prep.persist,
                          prep.lookup)

    def step_artifact(self, program=None, feed=None, fetch_list=None,
                      scope=None):
        """The cached StepArtifact for (program, feed-sig, fetch) —
        resolved through the same _prepare pass run() uses (a cache HIT
        after the first step, so calling this in a hot loop costs a
        dict lookup). Public seam for consumers of artifact metadata
        that must not rebuild it: the streaming delta publisher reads
        `touched_rows`/`sparse_plan` here (docs/embedding.md
        "streaming ids")."""
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        return self._prepare(program, feed or {}, fetch_list or [],
                             scope).compiled

    def _convert_fetch(self, v, fetch_f32, return_numpy, lazy):
        """One fetched value -> what run()/run_bundle() hand back: numpy /
        device array / LoDTensor, or a lazy FetchHandle over the same
        conversion when lazy."""
        def _cast_back(x):
            # Float16Transpiler contract: users keep fetching float32
            if fetch_f32 and hasattr(x, 'dtype') and str(x.dtype) == 'bfloat16':
                return x.astype(jnp.float32)
            return x

        if isinstance(v, SeqValue):
            from .lod_tensor import LoDTensor
            sv = SeqValue(_cast_back(v.data), v.lengths, v.outer_lengths)

            def mat(sv=sv):
                lt = LoDTensor.from_seq_value(sv)
                return np.asarray(lt.data) if return_numpy else lt

            if lazy:
                return FetchHandle(sv.data, mat)
            return mat()
        v = _cast_back(v)
        if lazy:
            if return_numpy:
                return FetchHandle(v)
            # return_numpy=False keeps the value ON DEVICE in blocking
            # mode; the async handle honors that — block() waits for
            # completion but hands back the device array, no host copy
            return FetchHandle(v, lambda v=v: jax.block_until_ready(v))
        return np.asarray(v) if return_numpy else v

    def run_bundle(self, program=None, feeds=None, fetch_list=None,
                   steps=None, scope=None, return_numpy=True,
                   use_program_cache=True, sync='auto'):
        """Run K training steps as ONE compiled XLA module: a lax.scan of
        the exact step body run() jits, amortizing the Python prepare
        pass, the device dispatch, and the host round-trip over K steps —
        the hot-loop pipelining lever for small/host-bound models
        (docs/perf.md).

        feeds: a list of K per-step feed dicts with identical signatures
        (shapes/dtypes); they are stacked on a new leading axis and
        scanned over. steps, when given, must equal len(feeds).

        Semantics vs K unbundled run() calls — identical by construction:
          * per-step RNG seeds advance exactly as run()'s counter does
            (a dropout mask at bundled step j equals unbundled run j);
          * the anomaly guard (when armed) evaluates health PER inner
            step, rolls back that step's persistables in-graph, and skips
            are observed/escalated per step on the host afterwards;
          * persistables land back in the scope once, at bundle end.
        One documented divergence: max_consecutive_skips escalation
        raises AFTER the bundle's module ran — inner steps past the
        escalation point already executed in-graph (each unhealthy one
        individually rolled back), so the scope holds bundle-end state,
        whereas K unbundled runs would have stopped at the raising step.
        Divergence is a stop-the-run condition either way; the state is
        consistent, just K-j steps further along.

        Returns one entry per fetch, STACKED per step: ndarray/device
        array with a leading K axis (sequence fetches: a list of K
        LoDTensors), or lazy FetchHandles over the same when
        sync='async'."""
        if sync not in ('auto', 'block', 'async'):
            raise ValueError(
                "sync must be 'auto', 'block' or 'async', got %r" % (sync,))
        if program is None:
            program = default_main_program()
        if fetch_list is None:
            fetch_list = []
        if scope is None:
            scope = global_scope()
        feeds = list(feeds or [])
        if not feeds:
            raise ValueError('run_bundle needs a non-empty list of '
                             'per-step feed dicts')
        K = len(feeds)
        if steps is not None and int(steps) != K:
            raise ValueError('steps=%d but %d feed dicts were given'
                             % (steps, K))
        on = obs.enabled()
        with obs.span('executor.bundle', steps=K) as bsp:
            with obs.span_if(on, 'executor.prepare') as sp:
                compiled, feed0, persist, look, feed_bytes = \
                    self._prepare(
                        program, feeds[0], fetch_list, scope,
                        use_program_cache=use_program_cache,
                        verify_bundle=True, spans=on)
                if sp is not None:
                    sp.fields['cache'] = look['outcome']
            bsp.fields.update(cache=look['outcome'], key=look['key'])
            extras = compiled.plan.uninitialized(compiled.persist_in)
            if extras:
                raise ValueError(
                    'run_bundle: persistable output(s) %r have no value '
                    'in the scope yet, so they cannot thread through the '
                    'scan carry; run the startup program (or one '
                    'unbundled step) first so every persistable is '
                    'initialized' % (sorted(extras),))
            mesh = compiled.mesh
            names0 = set(feed0)
            for j, f in enumerate(feeds[1:], start=1):
                if set(f) != names0:
                    raise ValueError(
                        'run_bundle feed %d has names %r, expected %r — '
                        'a bundle is ONE compiled module over a uniform '
                        'feed set' % (j, sorted(f), sorted(names0)))
            stacked = {}
            slow_names = []
            for name, v0 in feed0.items():
                # fast path (the hot Trainer/bench case): K host ndarrays,
                # no mesh, no sequence structure — ONE np.stack and ONE
                # device transfer per feed name instead of K device_puts
                # plus a device-side stack
                if (mesh is None and not isinstance(v0, SeqValue)
                        and all(isinstance(f[name], np.ndarray)
                                for f in feeds)):
                    vals = []
                    for j, f in enumerate(feeds):
                        a = f[name]
                        if a.shape != v0.shape:
                            raise ValueError(
                                'run_bundle feed %d input %r has shape '
                                '%r, expected %r (step 0) — a bundle is '
                                'ONE compiled module over uniform shapes'
                                % (j, name, a.shape, tuple(v0.shape)))
                        vals.append(a)
                    arr = np.stack(vals)
                    if arr.dtype != v0.dtype:
                        arr = arr.astype(v0.dtype)
                    stacked[name] = self._put(arr)
                else:
                    slow_names.append(name)
            if slow_names:
                # general path: place each step's feed like run() would
                # and stack leaf-wise on device (SeqValue is a pytree, so
                # sequence feeds stack their data and length planes
                # together; mesh feeds keep their sharding pipeline)
                sig0 = tuple(sorted(_feed_signature(n, feed0[n])
                                    for n in slow_names))
                per_step = [{n: feed0[n] for n in slow_names}]
                for j, f in enumerate(feeds[1:], start=1):
                    fv = self._place_feed(
                        program, {n: f[n] for n in slow_names}, mesh)
                    sig = tuple(sorted(_feed_signature(n, v)
                                       for n, v in fv.items()))
                    if sig != sig0:
                        raise ValueError(
                            'run_bundle feed %d has signature %r, '
                            'expected every step to match step 0 (%r) — '
                            'a bundle is ONE compiled module over '
                            'uniform shapes' % (j, sig, sig0))
                    per_step.append(fv)
                stacked.update(jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *per_step))
            # feed-transfer accounting: _prepare counted ONLY step 0's
            # payload (its placed feed also pays one duplicate small
            # transfer — the price of sharing run()'s signature/cache
            # path); top up the counter to the full stacked volume so
            # executor.feed.bytes doesn't under-report bundles K-fold
            fb = sum(int(getattr(leaf, 'nbytes', 0))
                     for leaf in jax.tree_util.tree_leaves(stacked))
            _C_FEED_BYTES.inc(max(0, fb - feed_bytes))
            # per-step RNG seeds: exactly the integers K successive run()
            # calls would derive from the shared counter
            base = (program.random_seed or 0) * 2654435761
            seeds = np.asarray(
                [(base + self._run_counter + j + 1) % (1 << 32)
                 for j in range(K)], np.uint32)
            run_base = self._run_counter
            self._run_counter += K
            _C_BUNDLED_STEPS.inc(K)
            args = compiled.plan.split(persist) + (stacked, seeds)
            if K not in compiled._obs_bundles:
                res, outcome = self._timed_first_call(
                    compiled, ('bundle', K), args, look['key'],
                    bundle_steps=K)
                bsp.fields['compiled'] = (outcome == 'compile')
                if outcome != 'compile':
                    look['outcome'] = bsp.fields['cache'] = outcome
            else:
                res = compiled.bundle(K)(*args)
            if compiled.sparse_plan:
                _C_EMBED_ROWS.inc(K * compiled._embed_rows_step)
            for n, v in res.new_persist.items():
                scope._chain_set(n, v)
            if res.health is not None:
                # ONE host sync of the tiny [K] health matrix; skips are
                # then observed (and escalated) per inner step, exactly
                # as K unbundled runs would have. Under sync='async' the
                # wait on the bundle's outputs happens HERE — record it
                # as the host stall it is.
                with obs.span_if(sync == 'async', 'executor.host_stall',
                                 cause='anomaly_guard', steps=K):
                    h_np = {k: np.asarray(v)
                            for k, v in res.health.items()}
                for j in range(K):
                    self._observe_health(
                        program, {k: v[j] for k, v in h_np.items()},
                        run_id=run_base + j + 1)

            fetch_f32 = bool(getattr(program, '_fetch_f32', False))
            with obs.span('executor.fetch', sync=sync, steps=K):
                if on and sync != 'async':
                    self._await_feed(stacked, fb)
                out = []
                for v in res.fetches:
                    if isinstance(v, SeqValue):
                        # stacked [K, batch, ...] sequence fetch -> K
                        # per-step values (LoDTensor conversion is
                        # per-step by construction)
                        def mat_steps(v=v):
                            return [self._convert_fetch(
                                SeqValue(v.data[j], v.lengths[j],
                                         tuple(o[j] for o in
                                               v.outer_lengths)
                                         if v.outer_lengths else None),
                                fetch_f32, return_numpy, False)
                                for j in range(K)]
                        if sync == 'async':
                            out.append(FetchHandle(v.data, mat_steps))
                        else:
                            out.append(mat_steps())
                    else:
                        out.append(self._convert_fetch(
                            v, fetch_f32, return_numpy, sync == 'async'))
                if on and res.counters is not None and sync != 'async':
                    bsp.fields['device'] = self._read_device(
                        compiled, res.counters)
        return out

    def _await_feed(self, feed_vals, feed_bytes):
        """The wait for the step's INPUT, as the span `executor.feed_wait`
        (docs/observability.md): blocks on every jax.Array the step was
        fed (a SeqValue's planes among them; a host-staged numpy value or
        a feedless step leaves nothing to wait for). The feed is never
        donated (StepArtifact donates persistables only), so the arrays
        outlive the dispatch and the wait ends when the transfer has
        landed, not when the step has read them; where `_put` sent a view
        the fed array is the device-side reshape's result, so the wait
        includes that reshape. `ready` says every array
        had landed on entry: the transfer hid under the host's own
        dispatch. Called only while observability is on, first thing
        inside a blocking `executor.fetch`, whose self time is then the
        wait for the device alone; off, or under sync='async' (it would
        serialize what that mode overlaps), nobody calls it and no fed
        array is touched after dispatch."""
        arrays = [a for a in jax.tree_util.tree_leaves(feed_vals)
                  if isinstance(a, jax.Array)]
        with obs.span('executor.feed_wait', bytes=feed_bytes) as sp:
            sp.fields['ready'] = all(a.is_ready() for a in arrays)
            for a in arrays:
                a.block_until_ready()

    def _read_device(self, compiled, counters):
        """THE host read of a step's device counters (StepArtifact.
        _device_counters), and their recording: what goes under
        `fields['device']` of the step's own record, an entry a declared
        op (a bundle's [K, counters]: a list a step). Called only while
        observability is on and only where the caller has just blocked on
        the step's fetches, inside its `executor.fetch` span: the step is
        complete, so this waits for nothing, and a step's host time
        outside the fetch is what it was. With observability off, or
        under sync='async', nobody calls it and the vector is never
        copied to the host."""
        values = np.asarray(counters)
        if values.ndim == 1:
            return compiled.device_record(values)
        return [compiled.device_record(v) for v in values]

    def _observe_health(self, program, health, run_id=None):
        """Host side of the anomaly guard: record the health vector, count
        skips, warn per skipped step, and escalate persistent divergence
        (max_consecutive_skips) to a FloatingPointError."""
        h = {k: np.asarray(v) for k, v in health.items()}
        self.last_step_health = h
        if run_id is None:
            run_id = self._run_counter
        if bool(h['healthy']):
            self._consecutive_skips = 0
            return
        self.skipped_steps += 1
        self._consecutive_skips += 1
        _C_SKIPPED.inc()
        obs.event('anomaly.skip', run=run_id,
                  grad_norm=float(h['grad_norm']),
                  loss_finite=bool(h['loss_finite']),
                  grads_finite=bool(h['grads_finite']),
                  consecutive=self._consecutive_skips)
        warnings.warn(
            'anomaly guard: step %d skipped (loss_finite=%s '
            'grads_finite=%s grad_norm=%s) — parameters and optimizer '
            'state were rolled back' % (
                run_id, bool(h['loss_finite']),
                bool(h['grads_finite']), float(h['grad_norm'])),
            RuntimeWarning, stacklevel=3)
        max_skips = getattr(program, '_anomaly_guard_max_skips', None)
        if max_skips is not None and self._consecutive_skips >= max_skips:
            raise FloatingPointError(
                'anomaly guard: %d consecutive unhealthy steps (limit %d) '
                '— the run has diverged, not hit a transient; last health: '
                '%r' % (self._consecutive_skips, max_skips,
                        {k: v.tolist() for k, v in h.items()}))

    def lowered_hlo(self, program=None, feed=None, fetch_list=None,
                    scope=None, optimized=False):
        """HLO text of the EXACT fused step run() would execute for this
        (program, feed, fetch) combination — each instruction's metadata
        op_name carries the `<fluid_op_type>_<index>` named scope stamped
        by lowering.run_op, so profiler traces and this dump attribute the
        compiled module back to Fluid ops (the reference's per-op tracer
        attributes the real run; profiler.py:81-130). optimized=True
        returns post-XLA-pass HLO (what actually executes, fusions and
        all); False returns the stable pre-optimization module."""
        _, lowered = self._lower_current_step(program, feed, fetch_list,
                                              scope)
        if optimized:
            return lowered.compile().as_text()
        return lowered.as_text()

    def _lower_current_step(self, program, feed, fetch_list, scope):
        """Shared prep for the step diagnostics (lowered_hlo /
        compiled_memory_stats): resolve defaults, build-or-fetch the
        cached compiled step, and lower the EXACT jitted call run()
        would make. Returns (compiled, jax Lowered)."""
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        prep = self._prepare(program, feed or {}, fetch_list or [], scope)
        return prep.compiled, prep.compiled._jitted.lower(
            *prep.compiled.plan.split(prep.persist), prep.feed_vals,
            jax.random.key(0))

    def compiled_memory_stats(self, program=None, feed=None,
                              fetch_list=None, scope=None):
        """XLA's CompiledMemoryStats for the EXACT fused step run() would
        execute for this (program, feed, fetch) combination — argument/
        output/temp byte sizes of the compiled module. The temp figure is
        the per-step scratch footprint the docs/perf.md and
        docs/embedding.md sparse-vs-dense claims are measured with
        (tests/test_embedding.py). Costs one lowering + compile
        (absorbed by the persistent compile cache when wired); the
        compiled-step cache itself is shared with run()."""
        _, lowered = self._lower_current_step(program, feed, fetch_list,
                                              scope)
        return lowered.compile().memory_analysis()

    def embed_rows_per_step(self, program=None, feed=None,
                            fetch_list=None, scope=None):
        """Static rows-touched-per-step bound of this step's ACTIVE
        sparse-embedding plan (docs/embedding.md): the number the
        embedding.rows_touched counter advances by per run. 0 means the
        step updates its tables densely (no plan, or every planned
        table fell back). Resolves through the same compiled-step cache
        as run()."""
        return self.step_artifact(program, feed, fetch_list,
                                  scope)._embed_rows_step

    # -- elastic checkpoint seam (docs/robustness.md#elastic) --------------

    def state_dict(self, program=None, scope=None):
        """The scope's persistable train state, placement-true: {name:
        jax.Array} for every scope-initialized persistable of `program`,
        each carrying its LIVE sharding (mesh placement is (re)asserted
        first, so an annotated program's arrays are NamedSharding-placed
        per their annotations — a vocab-sharded table comes back as 8
        device shards, never a gathered dense host array). This is what
        utils.checkpoint.save_sharded consumes: each host then writes
        only the shards it can address. LoD (SeqValue) persistables are
        skipped with a warning — the dense npz path owns those."""
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        self._ensure_dist_placement(program, scope)
        out = {}
        for v in program.list_vars():
            if not v.persistable:
                continue
            val = scope._chain_get(v.name)
            if val is None:
                continue
            if isinstance(val, SeqValue):
                warnings.warn(
                    'state_dict skips LoD persistable %r (SeqValue '
                    'state has no sharded-checkpoint representation)'
                    % v.name, RuntimeWarning)
                continue
            out[v.name] = (val if isinstance(val, jax.Array)
                           else jnp.asarray(val))
        return out

    def load_state_dict(self, state, program=None, scope=None):
        """Restore a state_dict into the scope, re-placed per the
        program's CURRENT annotations — the reshard-on-restore seam: the
        arrays may arrive from utils.checkpoint.load_sharded on a
        different mesh shape than they were saved on (8 devices -> 4
        after an elastic restart); each is device_put into the
        annotation's NamedSharding over the program's own mesh, so the
        step's sharding fixed point holds from the first post-restore
        run. Entries that are not persistables of the program are
        skipped with a warning; program persistables absent from `state`
        keep their scope values. Returns the restored names."""
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        mesh = self._ensure_dist_placement(program, scope)
        annot = mesh is not None and _is_annotated(program)
        from jax.sharding import NamedSharding, PartitionSpec as P
        pvars = {v.name: v for v in program.list_vars() if v.persistable}
        restored, unknown = [], []
        for name, val in state.items():
            v = pvars.get(name)
            if v is None:
                unknown.append(name)
                continue
            if annot:
                spec = (P(*v.sharding) if getattr(v, 'sharding', None)
                        else P())
                try:
                    val = jax.device_put(val, NamedSharding(mesh, spec))
                except ValueError as e:
                    warnings.warn(
                        'load_state_dict: annotation %r on %r does not '
                        'fit the mesh (%s); replicating instead'
                        % (getattr(v, 'sharding', None), name, e))
                    val = jax.device_put(val, NamedSharding(mesh, P()))
            elif mesh is not None:
                # legacy-dist mesh: keep an already-mesh-placed array's
                # layout (ZeRO/FSDP state restored by load_sharded);
                # single-device values replicate and _replace_strays /
                # the placement pass re-assert specifics on the next run
                if not (isinstance(val, jax.Array)
                        and len(val.sharding.device_set) > 1):
                    from .. import parallel
                    val = parallel.replicate(mesh, val)
            else:
                val = self._to_device(val)
            scope._chain_set(name, val)
            restored.append(name)
        if unknown:
            warnings.warn(
                'load_state_dict: %d checkpoint entr(ies) are not '
                'persistables of this program and were skipped: %s'
                % (len(unknown), sorted(unknown)[:8]), RuntimeWarning)
        obs.event('executor.load_state_dict', restored=len(restored),
                  skipped=len(unknown),
                  mesh=sorted(dict(mesh.shape).items()) if mesh else None)
        return restored

    @property
    def cache_stats(self):
        """THIS executor's compile-cache statistics
        (docs/observability.md): hits/misses/entries, evictions (close()
        drops), and the last XLA compile's wall seconds (None until
        something compiled). Process-wide aggregates of the same series
        live in the registry (executor.cache.*)."""
        return {'hits': self._cache_hits,
                'misses': self._cache_misses,
                'entries': len(self._cache),
                'evictions': self._cache_evictions,
                'persistent_hits': self._persistent_hits,
                'online_compiles': self._online_compiles,
                'aot_hits': self._aot_hits,
                'aot_stale': self._aot_stale,
                'aot_signatures': (len(self._aot_sigs)
                                   if self._aot_sigs is not None else None),
                'compile_cache_dir': self._compile_cache_dir,
                'last_compile_seconds': self._last_compile_s,
                'remat_detected': self.remat_detected}

    # -- AOT warm signatures (docs/perf.md#aot) -----------------------------

    def export_warm_signatures(self, dirname):
        """Serialize this executor's WARMED signature set as a portable
        AOT blob: a typed manifest of every compiled step artifact (feed
        names/shapes/dtypes, fetches, donation plan, program fingerprint,
        bundle lengths) plus the persistent compilation cache's
        serialized XLA executables. A cold replica / elastic restart
        calls `load_warm_signatures(dirname)` before its own warmup and
        reaches first step / first token with ZERO online compiles —
        the PR 4 per-machine persistent cache, extended across machines
        through the artifact. Requires the persistent compilation cache
        to have been wired when this executor was constructed
        (utils/compile_cache.py). Returns the
        manifest path; `tools/program_lint.py --aot DIR` lints the
        exported signature set against a saved program artifact."""
        from . import step_artifact
        path, man = step_artifact.write_aot(dirname, self)
        obs.event('executor.aot.exported', dir=os.path.basename(dirname),
                  signatures=len(man['signatures']),
                  cache_entries=len(man.get('cache_entries', [])))
        return path

    def load_warm_signatures(self, dirname):
        """Import an exported AOT blob: seed the persistent compilation
        cache with the blob's serialized executables and arm the stable-
        signature set, so every matching first call classifies as an
        `aot_hit` (cache_stats / executor.compile.aot_hit) instead of a
        cold compile. The blob's entries are COPIED into the process's
        compilation cache directory (compile_cache.enable(), wired here
        if it was not yet) — the import never writes into the artifact
        itself, so the blob stays pristine, and never points jax at a
        directory of its own. Returns the number of imported signatures."""
        import shutil
        from . import step_artifact
        man = step_artifact.read_aot(dirname)
        src = os.path.join(dirname, step_artifact.AOT_CACHE_DIR)
        if self._compile_cache_dir is None:
            self._compile_cache_dir = compile_cache.enable()
        imported = 0
        if os.path.isdir(src):
            for name in os.listdir(src):
                dst = os.path.join(self._compile_cache_dir, name)
                if not os.path.exists(dst):
                    shutil.copy2(os.path.join(src, name), dst)
                    imported += 1
        self._aot_sigs = {s['sig'] for s in man['signatures']}
        # per-entry-point warm index (see _aot_warmed): which of each
        # signature's entry points the blob actually serialized
        self._aot_entries = {
            s['sig']: {'step': bool(s.get('warmed_step', True)),
                       'bundles': {int(k) for k in s.get('bundles', [])}}
            for s in man['signatures']}
        self._aot_manifest = man
        if man.get('jax') != jax.__version__:
            warnings.warn(
                'AOT blob %r was exported under jax %s but this process '
                'runs %s — serialized executables will not deserialize '
                'and every first call will compile online (and be '
                'flagged executor.aot.stale)'
                % (dirname, man.get('jax'), jax.__version__),
                RuntimeWarning)
        obs.event('executor.aot.loaded', dir=os.path.basename(dirname),
                  signatures=len(self._aot_sigs),
                  cache_entries_imported=imported)
        return len(self._aot_sigs)

    def close(self):
        """Release compiled executables and drop cached jit state
        (reference executor.py:close tears down the C++ scope/comm; here
        the compiled-step cache holds the device buffers XLA pinned)."""
        self._cache_evictions += len(self._cache)
        for step in self._cache.values():
            for fn in [step._jitted] + list(step._bundles.values()):
                fn.clear_cache()
            step._bundles.clear()
        self._cache.clear()
        import gc
        gc.collect()
