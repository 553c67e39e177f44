"""Pod-scale serving: sharded replicas, cross-host routing, self-healing.

The serving counterpart of the elastic training runtime
(docs/robustness.md#elastic): one `set_mesh`-annotated Program served
as a single Router replica across the devices of a host, replicas
registered across MANY hosts behind one front door, and capacity that
heals itself when a host dies. Three layers (docs/serving.md#pod):

  * SHARDED REPLICAS — :class:`ShardedPredictor` loads an inference
    Program (program only, no dense params) onto a device mesh and
    restores its weights straight from a SHARDED checkpoint
    (`utils.checkpoint.load_latest_verified(mesh=...)` →
    `Executor.load_state_dict`): a row-sharded embedding table or a
    tensor-parallel decoder comes up WITHOUT ever materializing dense
    on any host, and the GSPMD executor serves it through the same
    all_to_all lookup wire training proved (docs/embedding.md). Feeds
    replicate (`set_mesh(..., data_axis=False)`), so every serving
    bucket works regardless of the mesh shape.
  * POD-AWARE ROUTING — :class:`PodWorker` registers a host's replicas
    into a shared-filesystem registry (the heartbeat/checkpoint
    posture: dependency-free, atomic-replace files) and serves their
    request spools; :class:`PodRouter` watches the registry, wraps each
    remote replica in an engine-protocol :class:`RemoteReplica` proxy,
    and runs the EXISTING Router semantics — least-loaded dispatch,
    quotas, swap, push_deltas — across process boundaries through the
    one replica abstraction (`Router.add_replica(..., host=, key=)`).
  * SELF-HEALING — each host heartbeats (`parallel.Heartbeat`); a stale
    host surfaces as the typed `HostLost`, its replicas are detached,
    every future still pending against them is RE-ROUTED to survivors
    (zero dropped futures — the router holds each request's feed until
    its response lands), and a heal command asks a surviving host to
    re-shard the replica onto its own topology via the same
    `load_latest_verified(mesh=...)` restore path. Queue-depth-driven
    :class:`Autoscaler` rides the same add/drain machinery for
    scale-up/down with zero-downtime cutover.

Events: serving.replica.{register,drain,lost,reshard}, the
router.pod_size gauge, and an obs_report `-- pod serving --` section
(docs/observability.md). Drilled by tests/test_pod_serving.py
(`pod` marker) and measured by `serve_bench --workload pod-sharded`.
"""
import collections
import concurrent.futures
import json
import os
import threading
import time
import uuid

import numpy as np

from .. import obs
from ..obs import trace
from .engine import (DeadlineExceeded, DeltaUnsupported, ServerClosed,
                     ServerOverloaded, ServingConfig, ServingEngine)
from .router import Router
from .transport import Channel, RpcServer, TransportError

__all__ = ['ShardedPredictor', 'save_serving_program', 'sharded_replica',
           'PodWorker', 'PodRouter', 'RemoteReplica', 'RpcReplica',
           'AutoscalePolicy', 'Autoscaler']

_C_REROUTED = obs.counter('serving.pod.rerouted_futures')
_C_HEALS = obs.counter('serving.pod.heals')
# stream failover accounting: failovers = live streams whose serving
# host died; resumes = the subset brought back token-exact from a
# decode-state checkpoint (failovers - resumes = typed HostLost streams)
_C_STREAM_FAILOVERS = obs.counter('serving.stream.failovers')
_C_STREAM_RESUMES = obs.counter('serving.stream.resumes')

# wire poll cadence: the spool transport is filesystem mailboxes, read
# at this period (same order as the engine's _POLL_S)
_POLL_S = 0.02

# One host, many sharded replicas: two compiled modules ISSUING
# COLLECTIVES (the all_to_all lookup wire) must never interleave on the
# same devices — XLA's rendezvous would pair participants across the
# two modules and deadlock. Replicas co-hosted on one process share the
# physical chips anyway, so serializing their dispatches costs nothing
# but removes the hazard (docs/serving.md#pod).
_MESH_DISPATCH_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# sharded replicas: program-only load + sharded-checkpoint restore
# ---------------------------------------------------------------------------

def save_serving_program(dirname, feeded_var_names, target_vars,
                         main_program=None, model_filename=None):
    """Save ONLY the pruned inference Program (no parameters) — the
    pod-serving artifact: a 100GB-table model's weights live in the
    SHARDED checkpoint (`utils.checkpoint.save_sharded`), never in a
    dense params file, so neither the save nor the load ever gathers a
    table whole (`fluid.io.save_inference_model` would —
    docs/serving.md#pod). The program keeps its mesh spec and sharding
    annotations through serialization; :class:`ShardedPredictor` is the
    loader. Returns the program file path."""
    from ..fluid import framework, io
    if isinstance(feeded_var_names, str):
        feeded_var_names = [feeded_var_names]
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    if main_program is None:
        main_program = framework.default_main_program()
    infer = main_program.clone(for_test=True).prune(list(target_vars))
    os.makedirs(dirname, exist_ok=True)
    meta = {
        'program': infer._to_dict(),
        'feed_names': list(feeded_var_names),
        'fetch_names': [v.name if isinstance(v, framework.Variable)
                        else str(v) for v in target_vars],
    }
    path = os.path.join(dirname, model_filename or io._PROGRAM_FILE)
    _atomic_json(path, meta)
    return path


class ShardedPredictor(object):
    """Predictor over a `set_mesh`-annotated Program with weights
    restored from a SHARDED checkpoint — the sharded-replica loader
    (docs/serving.md#pod).

    Loads the saved inference Program WITHOUT its dense params file,
    asserts/overrides the mesh (`mesh_axes`), and restores every
    persistable via `utils.checkpoint.load_latest_verified(ckpt_dir,
    mesh=...)` → `Executor.load_state_dict`: each array is assembled
    shard-by-shard onto this host's devices per its annotation — a
    vocab-sharded table arrives as per-device row shards and is NEVER
    materialized dense anywhere (reshard-on-restore covers a checkpoint
    written on a different topology). Inference then runs through the
    plain GSPMD executor — a row-sharded `lookup_table` takes the same
    all_to_all wire as training (docs/embedding.md), now on the serving
    path. Feeds REPLICATE by default (`data_axis=False`), so any
    serving bucket size works on any mesh; pass `data_axis='dp'` to
    shard request batches instead (buckets must then divide the axis).

    Drop-in for `inference.Predictor` wherever the serving engine
    expects one (run/feed_names/fetch_names/input_spec, private
    program/scope/executor seams — `push_rows` row-delta freshness
    works against the sharded table too)."""

    def __init__(self, model_dir, mesh_axes=None, ckpt_dir=None,
                 place=None, model_filename=None, data_axis=False):
        from .. import parallel
        from ..fluid import analysis, core, io
        from ..fluid.executor import Executor, Scope
        from ..fluid.framework import Program

        with open(os.path.join(model_dir,
                               model_filename or io._PROGRAM_FILE)) as f:
            meta = json.load(f)
        prog = Program._from_dict(meta['program'])
        axes = mesh_axes if mesh_axes is not None else prog.mesh_axes
        if not axes:
            raise ValueError(
                'ShardedPredictor needs a mesh: the saved program at %r '
                'carries no set_mesh spec and no mesh_axes= was given '
                '(an un-annotated model belongs in inference.Predictor)'
                % (model_dir,))
        prog.set_mesh(dict(axes), data_axis=data_axis)
        self._scope = Scope()
        self._place = place or core.default_place()
        self._exe = Executor(self._place)
        self._program = prog
        self.feed_names = list(meta['feed_names'])
        self._fetch_vars = [prog.global_block()._var_recursive(n)
                            for n in meta['fetch_names']]
        analysis.maybe_verify(
            prog, where='predictor', feeds=list(self.feed_names),
            fetches=[v.name for v in self._fetch_vars], concurrent=True)
        self.mesh = parallel.make_mesh(dict(prog.mesh_axes))
        self.state_step = None
        if ckpt_dir is not None:
            self._restore_sharded(ckpt_dir)
        else:
            # dense fallback: a small model saved the classic way still
            # serves sharded (load_persistables reads the params file,
            # load-time placement shards per the annotations)
            io.load_persistables(self._exe, model_dir, prog,
                                 scope=self._scope)

    @staticmethod
    def _referenced_names(program):
        """Every var name an op of `program` reads/writes, including
        names referenced through string attrs (control-flow rules
        resolve env by attr name — the decode idiom)."""
        out = set()

        def from_attr(a):
            if isinstance(a, str):
                out.add(a)
            elif isinstance(a, (list, tuple)):
                for x in a:
                    from_attr(x)
            elif isinstance(a, dict):
                for x in a.values():
                    from_attr(x)

        for blk in program.blocks:
            for op in blk.ops:
                for vs in list(op.inputs.values()) \
                        + list(op.outputs.values()):
                    for v in (vs if isinstance(vs, (list, tuple))
                              else [vs]):
                        out.add(getattr(v, 'name', v) if not
                                isinstance(v, str) else v)
                for a in op.attrs.values():
                    from_attr(a)
        return out

    def _restore_sharded(self, ckpt_dir):
        from ..utils import checkpoint as ck
        # prune() keeps dead optimizer vars LISTED; only persistables an
        # op actually references must come out of the checkpoint
        used = self._referenced_names(self._program)
        pvars = {v.name for v in self._program.list_vars()
                 if v.persistable and v.name in used}
        with obs.span('serving.sharded_restore',
                      dir=os.path.basename(str(ckpt_dir))) as sp:
            arrays, meta = ck.load_latest_verified(ckpt_dir,
                                                   mesh=self.mesh)
            # train-only state (optimizer moments) is legitimately
            # absent from an inference program: filter BEFORE
            # load_state_dict so the restore is quiet, then check the
            # program side is fully covered
            state = {n: a for n, a in arrays.items() if n in pvars}
            self._exe.load_state_dict(state, self._program,
                                      scope=self._scope)
            missing = sorted(pvars - set(state))
            if missing:
                raise RuntimeError(
                    'sharded checkpoint %r restores %d of %d program '
                    'persistables; missing: %s — the serving program '
                    'and the training checkpoint disagree'
                    % (ckpt_dir, len(state), len(pvars), missing[:8]))
            self.state_step = meta.get('step')
            sp.fields['restored'] = len(state)
            sp.fields['step'] = self.state_step

    @property
    def fetch_names(self):
        return [v.name for v in self._fetch_vars]

    @property
    def input_spec(self):
        blk = self._program.global_block()
        spec = {}
        for n in self.feed_names:
            v = blk.vars.get(n)
            if v is not None:
                spec[n] = (tuple(int(d) for d in v.shape), str(v.dtype))
        return spec

    def shard_shapes(self):
        """{name: per-device shard shape} for every multi-device
        persistable — the never-dense assertion surface (a VOCAB-row
        table on an 8-way mesh must report VOCAB/8 rows per device)."""
        out = {}
        for n, v in self._scope.vars.items():
            shards = getattr(v, 'addressable_shards', None)
            if shards and len(getattr(v.sharding, 'device_set', ())) > 1:
                out[n] = tuple(shards[0].data.shape)
        return out

    def run(self, feed):
        # the process-wide mesh-dispatch lock: a co-hosted replica's
        # collectives must not interleave with ours (see _MESH_DISPATCH_LOCK)
        with _MESH_DISPATCH_LOCK:
            return self._exe.run(self._program, feed=feed,
                                 fetch_list=self._fetch_vars,
                                 scope=self._scope)


def sharded_replica(model_dir, mesh_axes=None, ckpt_dir=None, config=None,
                    warm=True, example_feed=None, **predictor_kwargs):
    """One call from artifacts to a warmed sharded replica: build a
    :class:`ShardedPredictor` and wrap it in a `ServingEngine` (every
    bucket pre-compiled when `warm`). This is the builder shape the
    pod's heal path wants: `lambda reason: sharded_replica(...)`."""
    pred = ShardedPredictor(model_dir, mesh_axes=mesh_axes,
                            ckpt_dir=ckpt_dir, **predictor_kwargs)
    eng = ServingEngine(pred, config or ServingConfig())
    if warm:
        eng.warmup(example_feed)
    return eng


# ---------------------------------------------------------------------------
# wire: filesystem mailboxes (the heartbeat/checkpoint posture)
# ---------------------------------------------------------------------------

def _registry_dir(pod_dir):
    return os.path.join(pod_dir, 'registry')


def _beats_dir(pod_dir):
    return os.path.join(pod_dir, 'beats')


def _spool_dir(pod_dir, key):
    return os.path.join(pod_dir, 'spool', str(key))


def _ctl_dir(pod_dir, host):
    return os.path.join(pod_dir, 'ctl', 'h%d' % int(host))


def _streams_dir(pod_dir):
    # per-stream decode-state checkpoints (ckpt.<sid>.npz): written by
    # the SERVING worker at the stream's ckpt_every cadence, read by the
    # router's failover path to resume on a survivor token-exact
    return os.path.join(pod_dir, 'streams')


def _traces_dir(pod_dir):
    # per-process trace-span spill files (spans.p<pid>.json): every
    # participant (router + each worker) dumps its bounded span buffer
    # here on its stats cadence; obs.trace.TraceCollector stitches the
    # per-host files into end-to-end timelines, flagging spans a dead
    # host never closed as orphans (docs/observability.md#distributed-tracing)
    return os.path.join(pod_dir, trace.TRACE_DIR)


def _atomic_json(path, obj):
    tmp = '%s.tmp%d' % (path, os.getpid())
    with open(tmp, 'w') as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _atomic_npz(path, **arrays):
    # the tmp name must NOT keep the .npz suffix: spool/ctl scanners
    # match on it, and a scanner consuming a half-written tmp file both
    # corrupts the read AND makes the final os.replace fail
    tmp = '%s.tmp%d' % (path, os.getpid())
    with open(tmp, 'wb') as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


# typed errors cross the wire by name — the caller gets the SAME typed
# signal it would from an in-process engine (docs/serving.md#pod)
_TYPED_ERRORS = {
    'ServerOverloaded': ServerOverloaded,
    'ServerClosed': ServerClosed,
    'DeadlineExceeded': DeadlineExceeded,
    'DeltaUnsupported': DeltaUnsupported,
    'TransportError': TransportError,
    'ValueError': ValueError,
    'TypeError': TypeError,
    'KeyError': KeyError,
}


def _register_typed_errors():
    """Late-bound typed errors (their modules import lazily elsewhere in
    this file for the same reason): HostLost from the elastic runtime,
    StreamCancelled from the decode engine."""
    if 'HostLost' in _TYPED_ERRORS:
        return
    from ..parallel import HostLost
    from .decode import StreamCancelled
    _TYPED_ERRORS['HostLost'] = HostLost
    _TYPED_ERRORS['StreamCancelled'] = StreamCancelled


def _encode_error(exc):
    return json.dumps({'type': type(exc).__name__, 'message': str(exc)})


def _error_from_dict(d):
    _register_typed_errors()
    cls = _TYPED_ERRORS.get(d.get('type'), RuntimeError)
    return cls(d.get('message', 'remote replica error'))


def _decode_error(payload):
    try:
        d = json.loads(payload)
    except ValueError:
        return RuntimeError(str(payload))
    return _error_from_dict(d)


def _complete(fut, result=None, exc=None):
    """Resolve a future that may have been cancelled (predict() timeout)
    or already completed by a racing re-route — never raise into the
    poller/worker thread."""
    try:
        if not fut.set_running_or_notify_cancel():
            return False
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
        return True
    except Exception:        # InvalidStateError: already resolved
        return False


def _chain(src, dst):
    """Copy src's outcome into dst when src resolves (the re-route
    splice: the caller keeps ITS future; a survivor's future feeds it)."""
    def cb(f):
        if f.cancelled():
            dst.cancel()
            return
        e = f.exception()
        if e is not None:
            _complete(dst, exc=e)
        else:
            _complete(dst, result=f.result())
    src.add_done_callback(cb)


# ---------------------------------------------------------------------------
# PodWorker: a host's replicas, served from the shared registry
# ---------------------------------------------------------------------------

class PodWorker(object):
    """One serving HOST of the pod: registers replicas into the shared
    registry, answers their request spools, heartbeats, and heals —
    builds replacement replicas on a `heal` control command through the
    builders it was constructed with (docs/serving.md#pod).

    pod_dir: the shared directory (every host + the router must see it;
        the checkpoint filesystem is the natural choice).
    host: this host's integer id (beat files are per-host).
    builders: {model_id: callable(reason) -> warmed engine} — the heal
        path; a host with no builder for a model simply never receives
        its heal commands. `sharded_replica` closures are the intended
        shape: the replacement re-shards the checkpoint onto THIS
        host's topology (`load_latest_verified(mesh=...)`).
    transport: 'file' (atomic-npz spool mailboxes, PR 14's wire) or
        'rpc' (persistent TCP, serving/transport.py). The rpc wire is
        ADDITIVE: registry, beats, heal control, and stats publishing
        stay on the shared filesystem either way — only the request/
        response/stream hop moves to the socket, so the two wires stay
        drop-in interchangeable behind one seam (docs/serving.md#pod).
    rpc_max_inflight: per-connection wire admission cap (rpc only);
        a connection over it gets typed ServerOverloaded frames
        before the handler runs.
    """

    def __init__(self, pod_dir, host, builders=None, beat_interval=0.25,
                 stats_interval_s=0.2, poll_s=_POLL_S, transport='file',
                 rpc_max_inflight=64):
        from ..parallel import Heartbeat
        if transport not in ('file', 'rpc'):
            raise ValueError("transport must be 'file' or 'rpc', not %r"
                             % (transport,))
        self.pod_dir = str(pod_dir)
        self.host = int(host)
        self.transport = str(transport)
        self._builders = dict(builders or {})
        self._poll_s = float(poll_s)
        self._stats_every = float(stats_interval_s)
        for d in (_registry_dir(self.pod_dir), _beats_dir(self.pod_dir),
                  _ctl_dir(self.pod_dir, self.host),
                  _streams_dir(self.pod_dir)):
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._replicas = {}          # key -> dict(engine, thread, stop)
        self._last_telemetry_t = 0.0
        self._serial = 0
        self._stop = threading.Event()
        self._frozen = False         # simulate_death(): loops stall
        self._rpc = None
        if self.transport == 'rpc':
            self._rpc = RpcServer(self._rpc_handle,
                                  max_inflight=rpc_max_inflight,
                                  on_close=self._rpc_conn_closed)
        self.heartbeat = Heartbeat(_beats_dir(self.pod_dir),
                                   process_id=self.host, num_processes=0,
                                   interval=beat_interval)
        self.heartbeat.start()
        advert = {'host': self.host, 'pid': os.getpid(),
                  'transport': self.transport,
                  'builders': sorted(str(m) for m in self._builders)}
        if self._rpc is not None:
            advert['addr'] = list(self._rpc.addr)
        _atomic_json(os.path.join(_registry_dir(self.pod_dir),
                                  'host.%d.json' % self.host), advert)
        self._ctl_thread = threading.Thread(
            target=self._ctl_loop, name='pod-worker-ctl-h%d' % self.host,
            daemon=True)
        self._ctl_thread.start()

    # -- replica lifecycle -------------------------------------------------

    def serve(self, model_id, engine, name=None, heal_token=None,
              mesh=None):
        """Register `engine` as a replica of `model_id` and start
        answering its spool. Returns the registry key. The engine should
        already be WARM (every bucket pre-compiled) — registration makes
        it routable immediately."""
        with self._lock:
            self._serial += 1
            key = '%d.%s' % (self.host,
                             name if name is not None else
                             '%s-%d' % (model_id, self._serial))
            if key in self._replicas:
                raise ValueError('replica key %r already served' % key)
        spool = _spool_dir(self.pod_dir, key)
        os.makedirs(spool, exist_ok=True)
        if mesh is None:
            prog = getattr(getattr(engine, '_model', None), '_program',
                           None)
            axes = getattr(prog, 'mesh_axes', None)
            mesh = sorted(axes.items()) if axes else None
        stop = threading.Event()
        rec = {'engine': engine, 'stop': stop, 'spool': spool,
               'model_id': str(model_id),
               'stats_lock': threading.Lock()}
        t = threading.Thread(target=self._replica_loop, args=(key, rec),
                             name='pod-worker-%s' % key, daemon=True)
        rec['thread'] = t
        with self._lock:
            self._replicas[key] = rec
        self._publish_stats(key, rec)       # stats exist before routing
        reg = {'model_id': str(model_id), 'host': self.host, 'key': key,
               'pid': os.getpid(), 'mesh': mesh,
               'transport': self.transport,
               'feed_names': list(getattr(engine, 'feed_names', []) or []),
               'buckets': [int(b) for b in
                           getattr(engine, 'buckets', ()) or ()]}
        if self._rpc is not None:
            reg['addr'] = list(self._rpc.addr)
        if heal_token is not None:
            reg['heal_token'] = str(heal_token)
        t.start()
        _atomic_json(os.path.join(_registry_dir(self.pod_dir),
                                  'replica.%s.json' % key), reg)
        obs.event('serving.replica.register', model=str(model_id),
                  host=self.host, key=key,
                  healed=heal_token is not None)
        return key

    def retire(self, key, drain=True, timeout=None):
        """Deregister one replica (registry entry removed first, so the
        router stops routing to it) and drain its engine."""
        with self._lock:
            rec = self._replicas.pop(key, None)
        if rec is None:
            return False
        try:
            os.remove(os.path.join(_registry_dir(self.pod_dir),
                                   'replica.%s.json' % key))
        except OSError:
            pass
        rec['stop'].set()
        rec['thread'].join(timeout or 10.0)
        ok = rec['engine'].shutdown(drain=drain, timeout=timeout)
        obs.event('serving.replica.drain', model=rec['model_id'],
                  host=self.host, key=key, drain=bool(drain),
                  reason='retired')
        return ok

    def served(self):
        with self._lock:
            return sorted(self._replicas)

    def shutdown(self, drain=True, timeout=None):
        """Retire every replica, stop the heartbeat (peers will judge
        this host stale, correct for a stopping host), remove the host
        registration."""
        self._stop.set()
        ok = True
        for key in self.served():
            ok = self.retire(key, drain=drain, timeout=timeout) and ok
        self._host_telemetry(force=True)   # final spill: no span lost
        self.heartbeat.stop()
        if self._rpc is not None:
            self._rpc.close()
        try:
            os.remove(os.path.join(_registry_dir(self.pod_dir),
                                   'host.%d.json' % self.host))
        except OSError:
            pass
        return ok

    def simulate_death(self):
        """Test harness: stop beating and freeze every loop WITHOUT
        cleanup — indistinguishable from a SIGKILLed host to the
        router (beats stale, registration files orphaned, spooled
        requests never answered; rpc sockets stay OPEN but go silent,
        the wedged-process picture the heartbeat must see through)."""
        self._frozen = True
        if self._rpc is not None:
            self._rpc.freeze()
        self.heartbeat.stop()

    # -- spool service -----------------------------------------------------

    def _replica_loop(self, key, rec):
        engine, spool, stop = rec['engine'], rec['spool'], rec['stop']
        # requests taken but not yet answered: a request file stays on
        # disk until its response is written (crash-visible), so the
        # scan must skip what it already submitted
        rec['inflight'] = set()
        last_stats = 0.0
        while not stop.is_set() and not self._stop.is_set():
            if self._frozen:
                time.sleep(self._poll_s)
                continue
            try:
                names = sorted(os.listdir(spool))
            except OSError:
                names = []
            worked = False
            for fname in names:
                if stop.is_set() or self._frozen:
                    break
                path = os.path.join(spool, fname)
                if fname.startswith('rq.') and fname.endswith('.npz'):
                    if fname[3:-4] in rec['inflight']:
                        continue
                    worked = True
                    self._serve_request(engine, spool, path, fname,
                                        rec['inflight'])
                elif fname.startswith('push.') and fname.endswith('.npz'):
                    worked = True
                    self._serve_push(engine, spool, path, fname)
                elif fname == 'retire.json':
                    os.remove(path)
                    # deregister THEN drain, like retire()
                    threading.Thread(target=self.retire, args=(key,),
                                     daemon=True).start()
                    return
            now = time.monotonic()
            if now - last_stats >= self._stats_every:
                self._publish_stats(key, rec)
                last_stats = now
            if not worked:
                time.sleep(self._poll_s)

    def _serve_request(self, engine, spool, path, fname, inflight):
        uid = fname[3:-4]
        rs = os.path.join(spool, 'rs.%s.npz' % uid)
        inflight.add(uid)

        def respond(outs=None, exc=None):
            try:
                if exc is not None:
                    _atomic_npz(rs, __error__=np.frombuffer(
                        _encode_error(exc).encode(), np.uint8))
                else:
                    _atomic_npz(rs, **{'o:%d' % i: np.asarray(o)
                                       for i, o in enumerate(outs)})
            except Exception:
                pass
            try:
                os.remove(path)
            except OSError:
                pass
            inflight.discard(uid)

        try:
            with np.load(path, allow_pickle=False) as z:
                kwargs = json.loads(bytes(z['__meta__']).decode())
                feed = {k[2:]: z[k] for k in z.files if k.startswith('f:')}
        except Exception:
            # torn/unreadable request: leave it one cycle (the writer
            # replaces atomically, so this is a transient FS hiccup)
            inflight.discard(uid)
            return
        # the request JSON carries the caller's trace context; re-enter
        # it so this host's spans/events stitch into the same timeline
        tr = trace.from_headers(kwargs.pop('trace', None))
        h = trace.begin('serving.pod.serve', ctx=tr,
                        node='h%d' % self.host, uid=uid, wire='file')
        try:
            if h is not None:
                h.mark('trace.dispatch')
            with trace.activate(h.ctx if h is not None else None,
                                node='h%d' % self.host):
                fut = engine.submit(feed, **kwargs)
        except Exception as e:  # noqa: BLE001 — typed back to the caller
            if h is not None:
                h.end(error=type(e).__name__)
            respond(exc=e)
            return

        def done(f, _h=h):
            if self._frozen:
                # SIGKILL fidelity: a dead host answers nothing, and its
                # serve span stays OPEN — the spilled open span is the
                # orphan the trace collector flags
                return
            try:
                e = f.exception()
            except concurrent.futures.CancelledError as ce:
                e = ce
            respond(outs=None if e is not None else f.result(), exc=e)
            if _h is not None:
                _h.end(error=type(e).__name__ if e is not None else None)
        fut.add_done_callback(done)

    def _serve_push(self, engine, spool, path, fname):
        uid = fname[5:-4]
        ack = os.path.join(spool, 'pushok.%s.json' % uid)
        try:
            with np.load(path, allow_pickle=False) as z:
                meta = {}
                if '__meta__' in z.files:
                    try:
                        meta = json.loads(bytes(z['__meta__']).decode())
                    except ValueError:
                        meta = {}
                deltas = {}
                for k in z.files:
                    if k.startswith('i:'):
                        name = k[2:]
                        deltas[name] = (z[k], z['r:%s' % name])
            with trace.activate(trace.from_headers(meta.get('trace')),
                                node='h%d' % self.host):
                rows = engine.push_rows(deltas)
            _atomic_json(ack, {'ok': True, 'rows': int(rows)})
        except Exception as e:  # noqa: BLE001 — typed back to the caller
            _atomic_json(ack, {'ok': False,
                               'error': _encode_error(e)})
        try:
            os.remove(path)
        except OSError:
            pass

    def _publish_stats(self, key, rec):
        """Fold the engine's window into cumulative counters and write
        stats.json; returns the payload (the rpc 'stats' op replies
        with it directly). Serialized per replica: the file loop and
        rpc reader threads both publish, and the read-and-reset window
        must fold into `cum` exactly once."""
        engine = rec['engine']
        with rec.setdefault('stats_lock', threading.Lock()):
            cum = rec.setdefault('cum', collections.Counter())
            try:
                win = engine.stats_window()
            except Exception:
                return None
            live = {}
            for k in ('queue_depth', 'inflight', 'capacity', 'slots',
                      'pages_free', 'pages_total'):
                if k in win:
                    live[k] = win.pop(k)
            hw = win.pop('queue_high_water', 0)
            for k, v in win.items():
                if isinstance(v, (int, float)):
                    cum[k] += v
            exe = getattr(getattr(engine, '_model', None), '_exe', None)
            cache = {}
            if exe is not None:
                cs = exe.cache_stats
                cache = {'online_compiles': cs.get('online_compiles'),
                         'misses': cs.get('misses')}
            rec['stats_seq'] = rec.get('stats_seq', 0) + 1
            payload = {'seq': rec['stats_seq'], 'cum': dict(cum),
                       'live': live, 'queue_high_water': hw,
                       'cache': cache}
            _atomic_json(os.path.join(rec['spool'], 'stats.json'),
                         payload)
        self._host_telemetry()
        return payload

    def _host_telemetry(self, force=False):
        """Host-wide observability dumps riding the stats cadence: the
        trace-span spill (traces/spans.p<pid>.json, the collector's
        input) and the Prometheus exposition file (metrics.h<host>.prom)
        — scrape surfaces needing no live server. A frozen (simulated-
        dead) host stops dumping, so its LAST spill still holds the
        open spans the collector flags as orphans."""
        if self._frozen:
            return
        now = time.monotonic()
        if not force and now - self._last_telemetry_t < self._stats_every:
            return
        self._last_telemetry_t = now
        try:
            trace.spill(_traces_dir(self.pod_dir))
        except Exception:  # noqa: BLE001 — telemetry must not kill serving
            pass
        try:
            path = os.path.join(self.pod_dir,
                                'metrics.h%d.prom' % self.host)
            tmp = '%s.tmp%d' % (path, os.getpid())
            with open(tmp, 'w') as f:
                f.write(obs.metrics.render_prom())
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 — same
            pass

    # -- rpc service (transport='rpc'; serving/transport.py) ---------------

    def _rec(self, key):
        with self._lock:
            rec = self._replicas.get(key)
        if rec is None or self._frozen:
            raise ServerClosed('no replica %r on host %d'
                               % (key, self.host))
        return rec

    def _rpc_handle(self, conn, header, arrays):
        """Dispatch one frame (runs on the connection's reader thread —
        a blocking engine.submit() here IS the wire backpressure: this
        connection stops reading and the client's TCP window fills).
        Exceptions cross back as typed error frames (transport layer)."""
        op = header.get('op')
        if op == 'submit':
            self._rpc_submit(conn, header, arrays)
        elif op == 'push':
            self._rpc_push(conn, header, arrays)
        elif op == 'stats':
            payload = self._publish_stats(header.get('key'),
                                          self._rec(header.get('key')))
            conn.send({'uid': header.get('uid'), 'final': True,
                       'stats': payload or {}})
        elif op == 'metrics':
            # Prometheus text exposition over the wire: one frame in,
            # one final frame out carrying the whole registry — the
            # scrape path for deployments that never mount pod_dir
            conn.send({'uid': header.get('uid'), 'final': True,
                       'prom': obs.metrics.render_prom()})
        elif op == 'retire':
            ok = self.retire(header.get('key'),
                             drain=bool(header.get('drain', True)),
                             timeout=header.get('timeout'))
            conn.send({'uid': header.get('uid'), 'final': True,
                       'ok': bool(ok)})
        elif op == 'cancel':
            # fire-and-forget: the cancelled submit's own final frame
            # (typed StreamCancelled) is the acknowledgement
            entry = (conn.state.get('futs') or {}).get(
                header.get('cancel_uid'))
            if entry is not None:
                fut, engine = entry
                cancel = getattr(engine, 'cancel', None)
                if cancel is not None:
                    cancel(fut)
                else:
                    fut.cancel()
        else:
            raise ValueError('unknown rpc op %r' % (op,))

    def _rpc_submit(self, conn, header, arrays):
        uid = header['uid']
        rec = self._rec(header.get('key'))
        engine = rec['engine']
        kwargs = dict(header.get('meta') or {})
        feed = {n[2:]: arrays[n] for n in arrays if n.startswith('f:')}
        resume = {n[2:]: np.asarray(arrays[n])
                  for n in arrays if n.startswith('z:')}
        if resume:
            kwargs['resume'] = resume
        # frame header carries the caller's trace context; re-enter it
        # so this host's serve span stitches into the same timeline
        tr = trace.from_headers(header.get('trace'))
        h = trace.begin('serving.pod.serve', ctx=tr,
                        node='h%d' % self.host, uid=uid, wire='rpc')
        sid = header.get('sid')
        ckpt_path = None
        # dispatch stamp: set right before engine.submit; the first
        # token's server-side TTFT (dispatch -> token 1, no wire) is
        # measured against it and shipped in that token's frame header
        t_dispatch = [time.monotonic()]
        if header.get('stream'):
            # per-token emitter: enqueue on the connection's writer (the
            # decode loop never blocks); a dead consumer turns the False
            # return into a typed abort — the engine frees slot + pages.
            # The _frozen check keeps simulate_death() faithful to
            # SIGKILL: a dead host's in-process engine must stop having
            # observable effects the moment it "dies"
            sent_first = [False]

            def on_token(t, ids, _c=conn, _u=uid, _h=h):
                hdr = {'uid': _u, 'final': False, 'tok': int(t)}
                if not sent_first[0]:
                    sent_first[0] = True
                    sttft = round(time.monotonic() - t_dispatch[0], 6)
                    hdr['sttft'] = sttft
                    if _h is not None:
                        _h.mark('trace.first_token',
                                server_ttft_s=sttft)
                if self._frozen or not _c.send(
                        hdr, {'ids': np.asarray(ids)}):
                    raise TransportError(
                        'stream consumer disconnected')
            kwargs['on_token'] = on_token
        ckpt_every = int(header.get('ckpt_every') or 0)
        if sid and ckpt_every:
            ckpt_path = os.path.join(_streams_dir(self.pod_dir),
                                     'ckpt.%s.npz' % sid)

            def checkpoint(state, _p=ckpt_path):
                if self._frozen:     # a dead host writes nothing
                    return
                _atomic_npz(_p, **{k: np.asarray(v)
                                   for k, v in state.items()})
            kwargs['checkpoint'] = checkpoint
            kwargs['ckpt_every'] = ckpt_every
        if h is not None:
            h.mark('trace.dispatch')
        t_dispatch[0] = time.monotonic()
        try:
            with trace.activate(h.ctx if h is not None else None,
                                node='h%d' % self.host):
                fut = engine.submit(feed, **kwargs)
        except Exception as e:
            if h is not None:
                h.end(error=type(e).__name__)
            raise
        conn.state.setdefault('futs', {})[uid] = (fut, engine)

        def done(f, _c=conn, _u=uid, _p=ckpt_path, _h=h):
            (_c.state.get('futs') or {}).pop(_u, None)
            if self._frozen:
                # SIGKILL fidelity: a dead host answers nothing, never
                # closes its serve span (the spilled open span IS the
                # orphan the collector flags), and must not janitor the
                # shared stream checkpoint the failover path resumes
                # from
                return
            try:
                e = f.exception()
            except concurrent.futures.CancelledError as ce:
                e = ce
            if _h is not None:
                _h.end(error=type(e).__name__ if e is not None else None)
            if e is not None:
                _c.send({'uid': _u, 'final': True,
                         'error': {'type': type(e).__name__,
                                   'message': str(e)}})
            else:
                _c.send({'uid': _u, 'final': True},
                        {'o:%d' % i: np.asarray(o)
                         for i, o in enumerate(f.result())})
                if _p is not None:
                    try:   # finished stream: its checkpoint is garbage
                        os.remove(_p)
                    except OSError:
                        pass
        fut.add_done_callback(done)

    def _rpc_push(self, conn, header, arrays):
        rec = self._rec(header.get('key'))
        deltas = {}
        for n in arrays:
            if n.startswith('i:'):
                name = n[2:]
                deltas[name] = (np.asarray(arrays[n]),
                                np.asarray(arrays['r:%s' % name]))
        with trace.activate(trace.from_headers(header.get('trace')),
                            node='h%d' % self.host):
            rows = rec['engine'].push_rows(deltas)
        conn.send({'uid': header.get('uid'), 'final': True, 'ok': True,
                   'rows': int(rows)})

    def _rpc_conn_closed(self, conn):
        """A client connection died: reap its work. Queued requests are
        dropped at dequeue; a decoding stream's slot and pages free at
        the next loop tick (typed StreamCancelled — nobody is listening
        for the result anyway). A reconnecting client re-sends what it
        still wants (RpcReplica._on_reconnect)."""
        futs = conn.state.get('futs') or {}
        for uid, (fut, engine) in sorted(futs.items()):
            try:
                cancel = getattr(engine, 'cancel', None)
                if cancel is not None:
                    cancel(fut)
                else:
                    fut.cancel()
            except Exception:  # noqa: BLE001 — reaping is best-effort
                pass

    # -- control: heal commands --------------------------------------------

    def _ctl_loop(self):
        ctl = _ctl_dir(self.pod_dir, self.host)
        while not self._stop.is_set():
            if self._frozen:
                time.sleep(self._poll_s)
                continue
            try:
                names = sorted(os.listdir(ctl))
            except OSError:
                names = []
            for fname in names:
                if not (fname.startswith('cmd.')
                        and fname.endswith('.json')):
                    continue
                path = os.path.join(ctl, fname)
                cmd = _read_json(path)
                if cmd is None:
                    continue
                try:
                    os.remove(path)
                except OSError:
                    continue   # another thread/incarnation took it
                if cmd.get('cmd') == 'heal':
                    self._heal(cmd)
            time.sleep(self._poll_s)

    def _heal(self, cmd):
        model_id = cmd.get('model')
        token = cmd.get('token')
        builder = self._builders.get(model_id)
        if builder is None:
            self._heal_failed(token, 'host %d has no builder for %r'
                              % (self.host, model_id))
            return
        # the heal order carries the router's trace context: the whole
        # recovery (build -> re-shard -> register) lands on the same
        # timeline as the host loss that triggered it
        with trace.activate(trace.from_headers(cmd.get('trace')),
                            node='h%d' % self.host):
            try:
                with obs.span('serving.replica.build',
                              model=str(model_id), host=self.host,
                              reason=cmd.get('reason')):
                    engine = builder(cmd.get('reason', 'heal'))
                key = self.serve(model_id, engine, heal_token=token)
            except Exception as e:  # noqa: BLE001 — report, don't die
                self._heal_failed(token, '%s: %s' % (type(e).__name__, e))
                return
            obs.event('serving.replica.reshard', model=str(model_id),
                      host=self.host, key=key, token=str(token),
                      reason=cmd.get('reason'),
                      lost_host=cmd.get('lost_host'))

    def _heal_failed(self, token, why):
        obs.event('serving.pod.heal_failed', host=self.host,
                  token=str(token), error=str(why)[:200])
        if token:
            _atomic_json(os.path.join(_registry_dir(self.pod_dir),
                                      'healfail.%s.json' % token),
                         {'token': token, 'host': self.host,
                          'error': str(why)[:500]})


# ---------------------------------------------------------------------------
# RemoteReplica: the engine-protocol proxy the router balances on
# ---------------------------------------------------------------------------

class RemoteReplica(object):
    """Engine-protocol proxy for one registered replica on another
    host: submit/predict/stats_window/push_rows/shutdown look exactly
    like a local engine's, so `Router` (and everything riding it —
    quotas, push_deltas, drain) works unchanged across process
    boundaries. Requests travel as atomic files through the replica's
    spool; the proxy keeps every in-flight request's feed until its
    response lands, which is what makes host-loss re-routing LOSSLESS
    (`take_pending`)."""

    def __init__(self, pod_dir, reg, poll_s=_POLL_S):
        self.pod_dir = str(pod_dir)
        self.reg = dict(reg)
        self.key = reg['key']
        self.host = int(reg['host'])
        self.model_id = reg.get('model_id')
        self.feed_names = list(reg.get('feed_names') or [])
        self.buckets = tuple(reg.get('buckets') or ())
        self._spool = _spool_dir(self.pod_dir, self.key)
        self._poll_s = float(poll_s)
        self._lock = threading.Lock()
        self._pending = {}           # uid -> (future, feed, kwargs)
        self._seq = 0
        self._closed = False
        self._detached = False
        self._last_cum = collections.Counter()
        self._last_stats = {}
        self._thread = threading.Thread(
            target=self._poll_loop, name='pod-proxy-%s' % self.key,
            daemon=True)
        self._thread.start()

    # -- engine protocol ---------------------------------------------------

    def submit(self, feed, **kwargs):
        if self._closed:
            raise ServerClosed('remote replica %s is closed' % self.key)
        for k, v in kwargs.items():
            if callable(v):
                # typed, not a json.dumps crash: the mailbox wire has no
                # frame to carry a token back on
                raise ValueError(
                    'per-token streaming (%s=) needs the rpc transport; '
                    'the file wire only carries whole responses — start '
                    "the PodWorker with transport='rpc'" % k)
        # capture the caller's trace context (Router.submit dispatches
        # inside its activation) so a host-loss re-route keeps the
        # ORIGINAL trace_id; '_trace' stays client-side, the wire meta
        # carries it under 'trace' (the worker pops it back out)
        if kwargs.get('_trace') is None:
            hdrs = trace.headers()
            if hdrs is not None:
                kwargs['_trace'] = hdrs
        arrays = {str(n): np.asarray(a) for n, a in feed.items()}
        with self._lock:
            self._seq += 1
            uid = '%06d-%s' % (self._seq, uuid.uuid4().hex[:8])
            fut = concurrent.futures.Future()
            self._pending[uid] = (fut, arrays, dict(kwargs))
        meta = {k: v for k, v in kwargs.items() if k != '_trace'}
        if kwargs.get('_trace') is not None:
            meta['trace'] = kwargs['_trace']
        payload = {'f:%s' % n: a for n, a in arrays.items()}
        payload['__meta__'] = np.frombuffer(
            json.dumps(meta).encode(), np.uint8)
        try:
            _atomic_npz(os.path.join(self._spool, 'rq.%s.npz' % uid),
                        **payload)
        except OSError as e:
            with self._lock:
                self._pending.pop(uid, None)
            raise ServerClosed('replica %s spool unreachable: %s'
                               % (self.key, e))
        return fut

    def predict(self, feed, timeout=None, **kwargs):
        fut = self.submit(feed, timeout=timeout, **kwargs)
        return fut.result(timeout)

    def warmup(self, example_feed=None):
        # the worker warmed the engine before registering it; the
        # router-side contract (every bucket pre-compiled) already holds
        return list(self.buckets)

    def stats_window(self):
        """Window semantics preserved remotely: the worker publishes
        CUMULATIVE counters; the proxy diffs against its last read —
        read-and-reset, single consumer, exactly like the local
        engines. Live depth is the max of the published depth and this
        proxy's own in-flight count (the truest signal between
        publishes)."""
        st = _read_json(os.path.join(self._spool, 'stats.json')) or {}
        cum = collections.Counter(
            {k: v for k, v in (st.get('cum') or {}).items()
             if isinstance(v, (int, float))})
        win = dict(cum - self._last_cum)
        self._last_cum = cum
        self._last_stats = st
        live = st.get('live') or {}
        with self._lock:
            outstanding = len(self._pending)
        win['queue_depth'] = max(int(live.get('queue_depth', 0)),
                                 outstanding)
        win['inflight'] = int(live.get('inflight', 0))
        win['queue_high_water'] = max(int(st.get('queue_high_water', 0)),
                                      outstanding)
        win['capacity'] = live.get('capacity', 0)
        for k in ('slots', 'pages_free', 'pages_total'):
            if k in live:
                win[k] = live[k]
        return win

    def cache_stats(self):
        """The remote replica's published compile counters (the
        steady-state-compiles assertion surface) — read fresh from the
        worker's latest stats publish."""
        st = _read_json(os.path.join(self._spool, 'stats.json')) \
            or self._last_stats or {}
        return dict(st.get('cache') or {})

    def push_rows(self, deltas, timeout=30.0):
        if self._closed:
            raise ServerClosed('remote replica %s is closed' % self.key)
        uid = uuid.uuid4().hex[:12]
        payload = {}
        for name in sorted(deltas):
            ids, rows = deltas[name]
            payload['i:%s' % name] = np.asarray(ids)
            payload['r:%s' % name] = np.asarray(rows)
        hdrs = trace.headers()
        if hdrs is not None:
            payload['__meta__'] = np.frombuffer(
                json.dumps({'trace': hdrs}).encode(), np.uint8)
        _atomic_npz(os.path.join(self._spool, 'push.%s.npz' % uid),
                    **payload)
        ack_path = os.path.join(self._spool, 'pushok.%s.json' % uid)
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            ack = _read_json(ack_path)
            if ack is not None:
                try:
                    os.remove(ack_path)
                except OSError:
                    pass
                if ack.get('ok'):
                    return int(ack.get('rows', 0))
                raise _decode_error(ack.get('error', '{}'))
            if self._closed:
                break
            time.sleep(self._poll_s)
        raise ServerClosed(
            'remote replica %s did not acknowledge a %d-table delta '
            'push within %.1fs (host gone?)'
            % (self.key, len(deltas), timeout))

    def shutdown(self, drain=True, timeout=None):
        """Retire the remote replica: the worker deregisters it first
        (no new routing) then drains its engine; this proxy waits for
        its own in-flight responses."""
        if self._detached:
            self._closed = True
            return True
        self._closed = True     # no NEW submits through this proxy
        try:
            _atomic_json(os.path.join(self._spool, 'retire.json'),
                         {'drain': bool(drain)})
        except OSError:
            pass
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        while drain:
            with self._lock:
                n = len(self._pending)
            if n == 0:
                break
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(self._poll_s)
        return True

    # -- host-loss seam ----------------------------------------------------

    def take_pending(self):
        """Atomically detach every unanswered request — (future, feed,
        kwargs) triples the router re-routes to survivors. The proxy
        stops accepting new submits; a LATE response (the host was slow,
        not dead) still resolves any future the re-route has not beaten
        (first outcome wins, the other is dropped)."""
        self._closed = True
        self._detached = True
        self._detach_t = time.monotonic()
        with self._lock:
            pending = list(self._pending.values())
            # keep the map: a late rs file may still win the race
        return pending

    def outstanding(self):
        with self._lock:
            return len(self._pending)

    def _poll_loop(self):
        while True:
            try:
                names = os.listdir(self._spool)
            except OSError:
                names = []
            got = False
            for fname in names:
                if not (fname.startswith('rs.')
                        and fname.endswith('.npz')):
                    continue
                uid = fname[3:-4]
                with self._lock:
                    entry = self._pending.pop(uid, None)
                path = os.path.join(self._spool, fname)
                if entry is None:
                    try:
                        os.remove(path)   # cancelled/duplicate response
                    except OSError:
                        pass
                    continue
                got = True
                fut = entry[0]
                try:
                    with np.load(path, allow_pickle=False) as z:
                        if '__error__' in z.files:
                            _complete(fut, exc=_decode_error(
                                bytes(z['__error__']).decode()))
                        else:
                            outs = [z['o:%d' % i]
                                    for i in range(len(z.files))]
                            _complete(fut, result=outs)
                except Exception:
                    # torn read: put it back for the next cycle
                    with self._lock:
                        self._pending.setdefault(uid, entry)
                    continue
                try:
                    os.remove(path)
                except OSError:
                    pass
            if self._closed and not got:
                if not self._pending:
                    return
                # detached (host lost): late responses get a bounded
                # grace window, then the re-routed futures own the
                # outcome and this poller retires
                t0 = getattr(self, '_detach_t', None)
                if t0 is not None and time.monotonic() - t0 > 5.0:
                    return
            if not got:
                time.sleep(self._poll_s)


class RpcReplica(object):
    """RemoteReplica's socket twin: the same engine-protocol proxy
    (submit/predict/stats_window/push_rows/shutdown/take_pending), over
    ONE persistent `transport.Channel` to the replica's host instead of
    spool files. What the socket buys (docs/serving.md#pod):

      * no poll interval on the request/response hop — a response is a
        frame, not a file another poller must notice;
      * per-token STREAMING: submit kwargs carrying `on_token` mark the
        request `stream`; the worker emits one non-final frame per
        generated token, and the callback fires here on the channel's
        reader thread (end-to-end TTFT);
      * reconnect-with-replay: the channel re-dials forever on seeded
        backoff; after each reconnect every still-pending request is
        re-sent (first outcome wins — a duplicate final frame finds its
        uid already popped and is dropped; duplicate token frames are
        absorbed by the consumer's ordering contract);
      * a GARBLED frame (torn, bad magic) fails every pending future
        with the typed `TransportError` immediately — a poisoned stream
        is condemned, never trusted or hung on.

    Host-loss semantics are unchanged: the proxy keeps every pending
    request's feed AND kwargs, so `take_pending` hands the router the
    same lossless re-route triples the file proxy does — including the
    stream bookkeeping (`sid`, `ckpt_every`, `_last_t`) the decode-
    stream failover path resumes from."""

    def __init__(self, pod_dir, reg, poll_s=_POLL_S):
        self.pod_dir = str(pod_dir)
        self.reg = dict(reg)
        self.key = reg['key']
        self.host = int(reg['host'])
        self.model_id = reg.get('model_id')
        self.feed_names = list(reg.get('feed_names') or [])
        self.buckets = tuple(reg.get('buckets') or ())
        self._poll_s = float(poll_s)
        self._lock = threading.Lock()
        self._pending = {}           # uid -> (future, feed, kwargs)
        self._ctl = {}               # uid -> (future, header, arrays)
        self._seq = 0
        self._closed = False
        self._detached = False
        self._last_cum = collections.Counter()
        self._last_stats = {}
        addr = reg.get('addr') or ()
        if len(addr) != 2:
            raise ValueError('replica %r advertises no rpc addr'
                             % (self.key,))
        self._chan = Channel((str(addr[0]), int(addr[1])),
                             on_frame=self._on_frame,
                             on_reconnect=self._on_reconnect,
                             on_wire_error=self._on_wire_error,
                             seed=self.host)

    # -- engine protocol ---------------------------------------------------

    def submit(self, feed, **kwargs):
        if self._closed:
            raise ServerClosed('remote replica %s is closed' % self.key)
        # capture the caller's trace context (Router.submit dispatches
        # inside its activation): the pending entry keeps it so a
        # host-loss re-route resumes under the ORIGINAL trace_id
        if kwargs.get('_trace') is None:
            hdrs = trace.headers()
            if hdrs is not None:
                kwargs['_trace'] = hdrs
        arrays = {str(n): np.asarray(a) for n, a in feed.items()}
        with self._lock:
            self._seq += 1
            uid = '%06d-%s' % (self._seq, uuid.uuid4().hex[:8])
            fut = concurrent.futures.Future()
            self._pending[uid] = (fut, arrays, dict(kwargs))
        # best-effort: disconnected now -> the reconnect replay re-sends
        self._send_submit(uid, arrays, kwargs)
        return fut

    def _send_submit(self, uid, arrays, kwargs):
        # callables and resumed decode state never cross as JSON meta:
        # streaming intent travels as header flags, resume state as
        # typed array blobs, and the callbacks stay client-side; the
        # trace context rides the frame header, not the meta
        meta = {k: v for k, v in kwargs.items()
                if k not in ('on_token', 'checkpoint', 'resume', 'sid',
                             'ckpt_every', '_last_t', '_trace')}
        header = {'op': 'submit', 'uid': uid, 'key': self.key,
                  'meta': meta}
        if kwargs.get('_trace') is not None:
            header['trace'] = kwargs['_trace']
        wire = {'f:%s' % n: a for n, a in arrays.items()}
        if kwargs.get('on_token') is not None:
            header['stream'] = True
        if kwargs.get('sid'):
            header['sid'] = str(kwargs['sid'])
            header['ckpt_every'] = int(kwargs.get('ckpt_every') or 0)
        resume = kwargs.get('resume')
        if resume is not None:
            for n in sorted(resume):
                wire['z:%s' % n] = np.asarray(resume[n])
        return self._chan.send(header, wire)

    def predict(self, feed, timeout=None, **kwargs):
        fut = self.submit(feed, timeout=timeout, **kwargs)
        return fut.result(timeout)

    def warmup(self, example_feed=None):
        return list(self.buckets)

    # -- channel callbacks (reader thread) ---------------------------------

    def _on_frame(self, header, arrays):
        uid = header.get('uid')
        if not header.get('final'):
            # one streamed token; ordering/dedup is the consumer's
            # contract (router.TokenStream), _last_t feeds the failover
            # path's replayed-work accounting
            with self._lock:
                entry = self._pending.get(uid)
            if entry is None:
                return
            kwargs = entry[2]
            t = int(header.get('tok', 0))
            kwargs['_last_t'] = max(t, int(kwargs.get('_last_t') or 0))
            cb = kwargs.get('on_token')
            if cb is not None:
                sttft = header.get('sttft')
                if sttft is not None:
                    # first token's frame carries the worker's server-
                    # side TTFT (dispatch -> token 1, no wire): hand it
                    # to consumers that take it (TokenStream), fall back
                    # for plain 2-arg callbacks (failover replay path)
                    try:
                        cb(t, arrays.get('ids'), float(sttft))
                    except TypeError:
                        cb(t, arrays.get('ids'))
                else:
                    cb(t, arrays.get('ids'))
            return
        with self._lock:
            entry = self._pending.pop(uid, None)
            ctl = self._ctl.pop(uid, None) if entry is None else None
        fut = entry[0] if entry is not None else \
            (ctl[0] if ctl is not None else None)
        if fut is None:
            return          # duplicate final frame lost the race: drop
        if 'error' in header:
            _complete(fut, exc=_error_from_dict(header['error'] or {}))
        elif entry is not None:
            _complete(fut, result=[arrays['o:%d' % i]
                                   for i in range(len(arrays))])
        else:
            _complete(fut, result=header)

    def _on_reconnect(self):
        """The worker restarted or the network blinked: re-send every
        request still wanted. The worker cancelled the old incarnations
        when the connection died, so this never double-decodes; if a
        final frame DID land just before the cut, first-outcome-wins
        drops the duplicate."""
        with self._lock:
            pend = sorted(self._pending.items())
            ctl = sorted(self._ctl.items())
        for uid, (fut, arrays, kwargs) in pend:
            if not fut.done():
                self._send_submit(uid, arrays, kwargs)
        for uid, (fut, header, arrays) in ctl:
            if not fut.done():
                self._chan.send(header, arrays)

    def _on_wire_error(self, exc):
        """A garbled frame condemned the connection: every pending
        future fails TYPED now. No replay — a corrupted stream gives no
        honest claim about what the other side received; the caller
        (or the router's re-route machinery) owns the retry decision."""
        with self._lock:
            pend = list(self._pending.values())
            ctl = list(self._ctl.values())
            self._pending.clear()
            self._ctl.clear()
        err = exc if isinstance(exc, TransportError) \
            else TransportError(str(exc))
        for fut, _arrays, _kwargs in pend:
            _complete(fut, exc=err)
        for fut, _header, _arrays in ctl:
            _complete(fut, exc=err)

    # -- control rpcs ------------------------------------------------------

    def _ctl_rpc(self, header, arrays=None):
        with self._lock:
            self._seq += 1
            uid = 'c%05d-%s' % (self._seq, uuid.uuid4().hex[:6])
            fut = concurrent.futures.Future()
            header = dict(header, uid=uid)
            self._ctl[uid] = (fut, header, dict(arrays or {}))
        self._chan.send(header, arrays or {})
        return fut

    def stats_window(self):
        """Same window semantics as the file proxy (cumulative counters
        diffed against the last read), fed by a stats rpc instead of
        stats.json. The rpc is fired fresh each call but only waited on
        briefly — a slow or dead host costs the dispatch path
        milliseconds, and the reply (when it lands) freshens the NEXT
        sample; the heartbeat, not this path, decides the host is gone."""
        with self._lock:
            # abandon older unanswered stats probes (a dead host must
            # not accumulate one per sample window until reconnect)
            for uid in [u for u, (f, h, _a) in self._ctl.items()
                        if h.get('op') == 'stats' and not f.done()]:
                self._ctl.pop(uid)
        fut = self._ctl_rpc({'op': 'stats', 'key': self.key})

        def land(f, _self=self):
            try:
                if f.exception() is None:
                    _self._last_stats = f.result().get('stats') or {}
            except Exception:  # noqa: BLE001 — cancelled probe
                pass
        fut.add_done_callback(land)
        try:
            fut.result(max(0.05, 2 * self._poll_s))
        except Exception:  # noqa: BLE001 — fall back to the last landed
            pass
        st = self._last_stats or {}
        cum = collections.Counter(
            {k: v for k, v in (st.get('cum') or {}).items()
             if isinstance(v, (int, float))})
        win = dict(cum - self._last_cum)
        self._last_cum = cum
        live = st.get('live') or {}
        with self._lock:
            outstanding = len(self._pending)
        win['queue_depth'] = max(int(live.get('queue_depth', 0)),
                                 outstanding)
        win['inflight'] = int(live.get('inflight', 0))
        win['queue_high_water'] = max(int(st.get('queue_high_water', 0)),
                                      outstanding)
        win['capacity'] = live.get('capacity', 0)
        for k in ('slots', 'pages_free', 'pages_total'):
            if k in live:
                win[k] = live[k]
        return win

    def cache_stats(self):
        fut = self._ctl_rpc({'op': 'stats', 'key': self.key})
        try:
            st = fut.result(2.0).get('stats') or {}
            self._last_stats = st
        except Exception:  # noqa: BLE001 — dead host: last known
            st = self._last_stats or {}
        return dict(st.get('cache') or {})

    def metrics_text(self, timeout=5.0):
        """The worker host's full metrics registry in Prometheus text
        exposition format (the rpc `metrics` op) — the scrape path for
        deployments that never mount pod_dir."""
        fut = self._ctl_rpc({'op': 'metrics', 'key': self.key})
        reply = fut.result(float(timeout))
        return str(reply.get('prom') or '')

    def push_rows(self, deltas, timeout=30.0):
        if self._closed:
            raise ServerClosed('remote replica %s is closed' % self.key)
        payload = {}
        for name in sorted(deltas):
            ids, rows = deltas[name]
            payload['i:%s' % name] = np.asarray(ids)
            payload['r:%s' % name] = np.asarray(rows)
        header = {'op': 'push', 'key': self.key}
        hdrs = trace.headers()
        if hdrs is not None:
            header['trace'] = hdrs
        fut = self._ctl_rpc(header, payload)
        try:
            reply = fut.result(float(timeout))
        except concurrent.futures.TimeoutError:
            raise ServerClosed(
                'remote replica %s did not acknowledge a %d-table delta '
                'push within %.1fs (host gone?)'
                % (self.key, len(deltas), timeout))
        return int(reply.get('rows', 0))

    def cancel(self, future):
        """Ask the worker to cancel/abort the submit owning `future`
        (queued -> dropped; a decoding stream's slot and pages free at
        the next loop tick). Returns True when a cancel was sent."""
        with self._lock:
            uid = next((u for u, e in self._pending.items()
                        if e[0] is future), None)
        if uid is None:
            return False
        return self._chan.send({'op': 'cancel', 'cancel_uid': uid,
                                'key': self.key})

    def shutdown(self, drain=True, timeout=None):
        if self._detached:
            self._closed = True
            self._chan.close()
            return True
        self._closed = True      # no NEW submits through this proxy
        ok = True
        try:
            fut = self._ctl_rpc({'op': 'retire', 'key': self.key,
                                 'drain': bool(drain),
                                 'timeout': timeout})
            fut.result(30.0 if timeout is None else float(timeout))
        except Exception:  # noqa: BLE001 — already retired / host gone
            ok = False
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        while drain:
            with self._lock:
                n = len(self._pending)
            if n == 0:
                break
            if deadline is not None and time.monotonic() > deadline:
                ok = False
                break
            time.sleep(self._poll_s)
        self._chan.close()
        return ok

    # -- host-loss seam ----------------------------------------------------

    def take_pending(self):
        """Detach every unanswered request for re-routing — the same
        lossless triples as the file proxy's. The channel stays up for
        a bounded grace window (a late final frame from a slow-not-dead
        host still wins any future the re-route has not beaten), then
        closes so it stops re-dialing a dead address forever."""
        self._closed = True
        self._detached = True
        with self._lock:
            pending = list(self._pending.values())
            # keep the map: a late final frame may still win the race
        t = threading.Timer(5.0, self._chan.close)
        t.daemon = True
        t.start()
        return pending

    def outstanding(self):
        with self._lock:
            return len(self._pending)


# ---------------------------------------------------------------------------
# autoscaling: queue-depth-driven capacity, riding the swap machinery
# ---------------------------------------------------------------------------

class AutoscalePolicy(object):
    """When to grow/shrink a model's replica set (docs/serving.md#pod).

    scale_up_at / scale_down_at: thresholds on the PER-REPLICA windowed
        admission pressure (queue high-water + depth + in-flight, the
        same signal least-loaded dispatch balances on). Above the first
        for a full window -> one replica is added; below the second ->
        one is drained.
    cooldown_s: minimum seconds between scaling actions (a heal takes
        time to land; don't storm).
    """

    def __init__(self, min_replicas=1, max_replicas=4, scale_up_at=4.0,
                 scale_down_at=0.5, cooldown_s=5.0):
        if min_replicas < 1 or max_replicas < min_replicas:
            raise ValueError('need 1 <= min_replicas <= max_replicas')
        if scale_down_at >= scale_up_at:
            raise ValueError('scale_down_at must be < scale_up_at')
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.scale_up_at = float(scale_up_at)
        self.scale_down_at = float(scale_down_at)
        self.cooldown_s = float(cooldown_s)


class Autoscaler(object):
    """Queue-depth-driven replica scale-up/down for one model, riding
    the router's zero-downtime machinery: scale-UP builds + warms the
    incoming replica OFF TO THE SIDE (the swap() discipline — traffic
    never sees a cold compile) then `add_replica`s it atomically;
    scale-DOWN `remove_replica`s the least-loaded one and drains it in
    the background (no future lost). `builder(reason) -> warmed engine`
    adds in-process; a PodRouter wires `scale_up=` to a heal command so
    the new replica lands on the least-loaded HOST instead."""

    def __init__(self, router, model_id, policy, builder=None,
                 scale_up=None):
        if builder is None and scale_up is None:
            raise ValueError('Autoscaler needs builder= or scale_up=')
        self.router = router
        self.model_id = model_id
        self.policy = policy
        self._builder = builder
        self._scale_up = scale_up
        self._last_action_t = None
        self._building = False     # an async scale-up build in flight
        self.actions = []          # ('up'|'down', pressure) history

    def pressure(self):
        """Mean per-replica windowed admission pressure."""
        samples = self.router.sample_windows(self.model_id)
        if not samples:
            return None
        per = []
        for s in samples:
            w = s['window']
            per.append(w.get('queue_depth', 0) + w.get('inflight', 0)
                       + w.get('queue_high_water', 0)
                       + s.get('routed_since', 0))
        return float(sum(per)) / len(per)

    def tick(self):
        """One policy evaluation; returns 'up', 'down', or None. The
        pod/poll loop calls this each cycle; tests call it directly."""
        pol = self.policy
        now = time.monotonic()
        if self._last_action_t is not None \
                and now - self._last_action_t < pol.cooldown_s:
            return None
        p = self.pressure()
        if p is None:
            return None
        n = len(self.router.replicas(self.model_id))
        if p >= pol.scale_up_at and n < pol.max_replicas:
            if self._building:
                return None        # last scale-up is still building
            self._last_action_t = now
            obs.event('serving.autoscale', model=str(self.model_id),
                      direction='up', replicas=n, pressure=round(p, 3))
            if self._scale_up is not None:
                self._scale_up('scale_up')
            else:
                # build + warm OFF the caller's thread (tick runs
                # inside PodRouter.poll — a minutes-long sharded
                # restore must not stall host-loss detection), then
                # add atomically: the swap() discipline
                self._building = True

                def build():
                    try:
                        engine = self._builder('scale_up')
                        self.router.add_replica(self.model_id, engine)
                    except Exception as e:  # noqa: BLE001 — report
                        obs.event('serving.autoscale.error',
                                  model=str(self.model_id),
                                  error='%s: %s' % (type(e).__name__, e))
                    finally:
                        self._building = False

                threading.Thread(target=build, name='autoscale-build',
                                 daemon=True).start()
            self.actions.append(('up', p))
            return 'up'
        if p <= pol.scale_down_at and n > pol.min_replicas:
            self._last_action_t = now
            victim = min(self.router.sample_windows(self.model_id),
                         key=lambda s: (
                             s['window'].get('queue_depth', 0)
                             + s['window'].get('inflight', 0)
                             + s.get('routed_since', 0)))
            obs.event('serving.autoscale', model=str(self.model_id),
                      direction='down', replicas=n,
                      pressure=round(p, 3), rid=victim['rid'])
            self.router.remove_replica(self.model_id, victim['rid'],
                                       drain=True, reason='scale_down')
            self.actions.append(('down', p))
            return 'down'
        return None


# ---------------------------------------------------------------------------
# PodRouter: registry-driven routing + host-loss self-healing
# ---------------------------------------------------------------------------

class PodRouter(Router):
    """A Router whose replicas live on OTHER hosts, discovered through
    the shared-filesystem registry PodWorkers publish into
    (docs/serving.md#pod). Everything the single-process Router does —
    least-loaded dispatch, quotas, typed overload, swap, push_deltas —
    runs unchanged over RemoteReplica proxies; on top of it:

      * registry sync: new replica registrations become routable
        replicas (serving.replica.register), voluntary retirements are
        removed cleanly;
      * host-loss: a host whose heartbeat goes stale raises the typed
        `HostLost` inside the poll loop; its replicas are detached, the
        futures pending against them RE-ROUTED to survivors (zero
        dropped futures), and — with heal=True — a heal command asks
        the least-loaded surviving host with a builder to re-shard the
        replica onto its topology (serving.replica.{lost,reshard});
      * autoscaling: `enable_autoscale` ticks an Autoscaler per poll,
        scaling through heal commands (up) / draining removals (down).

    Call `poll()` for one deterministic pass (tests), or rely on the
    background thread (`poll_s` cadence)."""

    def __init__(self, pod_dir, window_s=0.25, poll_s=0.1,
                 heartbeat_timeout=2.0, heal=True, reroute_timeout=30.0,
                 start=True):
        from ..parallel import Heartbeat
        Router.__init__(self, window_s=window_s)
        self.pod_dir = str(pod_dir)
        for d in (_registry_dir(self.pod_dir), _beats_dir(self.pod_dir),
                  _streams_dir(self.pod_dir)):
            os.makedirs(d, exist_ok=True)
        self.heal = bool(heal)
        self._poll_s = float(poll_s)
        self._reroute_timeout = float(reroute_timeout)
        # pure watcher: beats nothing, watches hosts as they register
        self.heartbeat = Heartbeat(_beats_dir(self.pod_dir),
                                   process_id=-1, num_processes=0,
                                   timeout=heartbeat_timeout)
        self._pod_lock = threading.RLock()
        self._known = {}        # key -> dict(rid, proxy, model_id, host)
        self._hosts = {}        # host -> registration dict
        self._heals = {}        # token -> dict(model, lost_host, host, t)
        self._parked = []       # [(model_id, fut, feed, kwargs, t_expire)]
        self._autoscalers = {}
        self.lost_hosts = []    # [{'host', 'stale', 'error', ...}]
        self._last_spill_t = 0.0
        self._stop = threading.Event()
        self._thread = None
        if start:
            self._thread = threading.Thread(
                target=self._pod_loop, name='pod-router', daemon=True)
            self._thread.start()

    # -- registry sync -----------------------------------------------------

    def poll(self):
        """One synchronous registry/heartbeat/parked/autoscale pass."""
        with self._pod_lock:
            self._sync_hosts()
            self._sync_registry()
            self._check_hosts()
            self._retry_parked()
            self._check_heal_failures()
            for a in list(self._autoscalers.values()):
                try:
                    a.tick()
                except Exception as e:  # noqa: BLE001 — keep polling
                    obs.event('serving.autoscale.error',
                              error='%s: %s' % (type(e).__name__, e))
        self.spill_traces()

    def spill_traces(self, force=False):
        """Dump this process's trace-span buffer into the shared
        traces/ dir (where each PodWorker spills too) so the collector
        can stitch the router's request spans against the workers'
        serve spans. Cadenced off the poll loop; `force` for a final
        flush (shutdown) or deterministic tests."""
        now = time.monotonic()
        if not force and now - self._last_spill_t < 1.0:
            return
        self._last_spill_t = now
        try:
            trace.spill(_traces_dir(self.pod_dir))
        except Exception:  # noqa: BLE001 — telemetry must not kill poll
            pass

    def _pod_loop(self):
        while not self._stop.wait(self._poll_s):
            try:
                self.poll()
            except Exception as e:  # noqa: BLE001 — the loop must live
                obs.event('router.pod.error',
                          error='%s: %s' % (type(e).__name__, e))

    def _sync_hosts(self):
        reg = _registry_dir(self.pod_dir)
        try:
            names = os.listdir(reg)
        except OSError:
            names = []
        hosts = {}
        for fname in names:
            if fname.startswith('host.') and fname.endswith('.json'):
                d = _read_json(os.path.join(reg, fname))
                if d is not None and 'host' in d:
                    hosts[int(d['host'])] = d
        # watch EVERY advertised host — a builder-only host (no
        # replicas yet) must still be disqualified as a heal candidate
        # the moment its beats go stale; a host whose file vanished
        # (clean shutdown, or the host-loss janitor) stops being
        # watched so it cannot read as a fresh loss forever
        for h in hosts:
            if h not in self._hosts:
                self.heartbeat.watch(h)
        for h in self._hosts:
            if h not in hosts \
                    and not any(i['host'] == h
                                for i in self._known.values()):
                self.heartbeat.unwatch(h)
        self._hosts = hosts

    def _sync_registry(self):
        reg = _registry_dir(self.pod_dir)
        try:
            names = os.listdir(reg)
        except OSError:
            names = []
        seen = set()
        for fname in names:
            if not (fname.startswith('replica.')
                    and fname.endswith('.json')):
                continue
            d = _read_json(os.path.join(reg, fname))
            if d is None or 'key' not in d:
                continue
            key = d['key']
            seen.add(key)
            if key in self._known:
                continue
            # the ONE transport seam: everything downstream (routing,
            # quotas, host loss, heal, push) sees the same proxy protocol
            cls = RpcReplica if (d.get('transport') == 'rpc'
                                 and d.get('addr')) else RemoteReplica
            proxy = cls(self.pod_dir, d, poll_s=self._poll_s)
            model_id = d.get('model_id')
            if model_id not in self._models:
                self.add_model(model_id, [proxy])
                with self._lock:
                    r = self._models[model_id].replicas[-1]
                    r.host, r.key = proxy.host, key
                    rid = r.rid
                    self._update_gauge_locked()
                obs.event('serving.replica.register',
                          model=str(model_id), rid=rid,
                          host=proxy.host, key=key)
            else:
                rid = self.add_replica(model_id, proxy,
                                       host=proxy.host, key=key)
            self.heartbeat.watch(proxy.host)
            self._known[key] = {'rid': rid, 'proxy': proxy,
                                'model_id': model_id, 'host': proxy.host}
            token = d.get('heal_token')
            if token and token in self._heals:
                h = self._heals.pop(token)
                obs.event('serving.replica.reshard',
                          model=str(model_id), host=proxy.host, key=key,
                          token=str(token), lost_host=h.get('lost_host'),
                          mesh=d.get('mesh'),
                          heal_s=round(time.monotonic() - h['t'], 3))
        # voluntary retirement: the registration file vanished but the
        # host still beats — remove the replica; its worker drains it
        gone = sorted(set(self._known) - seen)
        stale = set(self.heartbeat.check(raise_error=False)) if gone \
            else ()
        for key in gone:
            info = self._known[key]
            host = info['host']
            if host in stale:
                continue    # host is stale: _check_hosts owns this key
            self._known.pop(key)
            self.remove_replica(info['model_id'], info['rid'],
                                drain=False, reason='retired')
            info['proxy'].shutdown(drain=True, timeout=0)
            if not any(i['host'] == host for i in self._known.values()):
                self.heartbeat.unwatch(host)

    # -- host loss: detach, re-route, heal ---------------------------------

    def _check_hosts(self):
        from ..parallel import HostLost
        try:
            self.heartbeat.check(raise_error=True)
            return
        except HostLost as e:
            stale = [h for h in e.stale
                     if any(i['host'] == h for i in self._known.values())]
            if not stale:
                return
            for host in stale:
                self._host_lost(host, e)

    def _host_lost(self, host, exc):
        record = {'host': host, 'stale': list(exc.stale),
                  'error': '%s: %s' % (type(exc).__name__, exc),
                  'replicas': 0, 'rerouted': 0, 'healed_models': []}
        lost_models = []
        for key, info in sorted(self._known.items()):
            if info['host'] != host:
                continue
            self._known.pop(key)
            # janitor the orphaned registration (a SIGKILLed host can't
            # clean up its own files) — otherwise the next registry
            # sync would re-adopt the dead replica; a RESTARTED host
            # writes a fresh file and is re-adopted normally
            try:
                os.remove(os.path.join(_registry_dir(self.pod_dir),
                                       'replica.%s.json' % key))
            except OSError:
                pass
            record['replicas'] += 1
            proxy, model_id = info['proxy'], info['model_id']
            pending = proxy.take_pending()
            self.remove_replica(model_id, info['rid'], drain=False,
                                reason='host_lost')
            obs.event('serving.replica.lost', model=str(model_id),
                      rid=info['rid'], host=host, key=key,
                      pending=len(pending))
            lost_models.append(model_id)
            t_exp = time.monotonic() + self._reroute_timeout
            for fut, feed, kwargs in pending:
                if fut.done():
                    continue
                self._reroute(model_id, fut, feed, kwargs, t_exp,
                              record)
        self.heartbeat.unwatch(host)
        # janitor the dead host's advert too: it must stop being a heal/
        # autoscale candidate NOW (a restarted host re-registers fresh)
        try:
            os.remove(os.path.join(_registry_dir(self.pod_dir),
                                   'host.%d.json' % host))
        except OSError:
            pass
        self._hosts.pop(host, None)
        if self.heal:
            for model_id in sorted(set(lost_models)):
                token = self.request_heal(model_id, reason='host_lost',
                                          lost_host=host)
                if token is not None:
                    record['healed_models'].append(model_id)
        self.lost_hosts.append(record)
        obs.event('router.host_lost', host=host,
                  replicas=record['replicas'],
                  rerouted=record['rerouted'],
                  heals=len(record['healed_models']))

    def _reroute(self, model_id, fut, feed, kwargs, t_expire,
                 record=None):
        """Send a detached request to a survivor, splicing the result
        into the caller's ORIGINAL future. Unroutable now (no survivor
        yet) -> parked and retried each poll until t_expire. A STREAMED
        request takes the checkpoint-resume path instead."""
        if kwargs.get('on_token') is not None or kwargs.get('sid'):
            return self._reroute_stream(model_id, fut, feed, kwargs,
                                        t_expire, record)
        # re-enter the request's ORIGINAL trace context (captured by the
        # proxy at submit time): the survivor's serve span lands on the
        # same timeline the lost host's orphan span belongs to
        with trace.activate(trace.from_headers(kwargs.get('_trace')),
                            node='router'):
            try:
                new_fut = self.submit(model_id, feed, **kwargs)
            except Exception:  # noqa: BLE001 — park: heal may be coming
                self._parked.append((model_id, fut, feed, kwargs,
                                     t_expire))
                return False
            _chain(new_fut, fut)
            _C_REROUTED.inc()
            if record is not None:
                record['rerouted'] += 1
            obs.event('serving.pod.reroute', model=str(model_id))
        return True

    def _reroute_stream(self, model_id, fut, feed, kwargs, t_expire,
                        record=None):
        """Decode-stream failover: resume the stream on a survivor from
        its last decode-state checkpoint, TOKEN-EXACT. The worker
        checkpointed the slot's full decode state every `ckpt_every`
        tokens (streams/ckpt.<sid>.npz); the survivor resumes at
        checkpoint step + 1 via the engine's `resume=` path (eager
        row writes — zero new compile signatures). Tokens 1..ckpt are
        replayed into the client callback first, so a consumer that saw
        FEWER than ckpt tokens (frames lost with the host) still gets
        every index; the consumer's ordering contract (TokenStream
        dedup) absorbs whatever it already saw.

        With checkpointing OFF (ckpt_every=0) the stream fails with
        the typed HostLost: silently re-decoding everything the
        consumer already acted on is the one thing a stream must never
        do quietly, and the cadence knob is the caller's opt-in. A
        stream lost BEFORE its first checkpoint restarts from scratch
        — fewer than ckpt_every tokens of replayed work, all absorbed
        by the dedup."""
        # the resumed segment continues the ORIGINAL stream's trace:
        # same trace_id across the failover, so the stitched timeline
        # shows dead-host orphan -> resume -> completion as one request
        with trace.activate(trace.from_headers(kwargs.get('_trace')),
                            node='router'):
            return self._resume_stream(model_id, fut, feed, kwargs,
                                       t_expire, record)

    def _resume_stream(self, model_id, fut, feed, kwargs, t_expire,
                       record):
        from ..parallel import HostLost
        sid = kwargs.get('sid')
        ckpt_every = int(kwargs.get('ckpt_every') or 0)
        seen_t = int(kwargs.get('_last_t') or 0)
        if not sid or not ckpt_every:
            _C_STREAM_FAILOVERS.inc()
            obs.event('serving.stream.failover', model=str(model_id),
                      sid=str(sid), resumed=False, seen_t=seen_t)
            _complete(fut, exc=HostLost(
                'decode stream lost with checkpointing disabled '
                '(ckpt_every=0): %d streamed token(s) cannot be resumed '
                'token-exact — pass ckpt_every= to stream() to opt into '
                'failover' % seen_t))
            return True
        state = None
        path = os.path.join(_streams_dir(self.pod_dir),
                            'ckpt.%s.npz' % sid)
        try:
            with np.load(path, allow_pickle=False) as z:
                state = {k: np.asarray(z[k]) for k in z.files}
        except Exception:  # noqa: BLE001 — no/torn ckpt: from scratch
            state = None
        ckpt_t = int(state['step']) if state is not None else 0
        cb = kwargs.get('on_token')
        if state is not None and cb is not None:
            ids = np.asarray(state['ids'])
            for s in range(1, ckpt_t + 1):
                try:
                    cb(s, ids[s - 1])
                except Exception:  # noqa: BLE001 — consumer's problem
                    pass
        kwargs2 = dict(kwargs)
        if state is not None:
            kwargs2['resume'] = state
        try:
            new_fut = self.submit(model_id, feed, **kwargs2)
        except Exception:  # noqa: BLE001 — park: a heal may be coming
            self._parked.append((model_id, fut, feed, kwargs2, t_expire))
            return False
        _chain(new_fut, fut)
        _C_REROUTED.inc()
        _C_STREAM_FAILOVERS.inc()
        _C_STREAM_RESUMES.inc()
        replayed = max(0, seen_t - ckpt_t)
        if record is not None:
            record['rerouted'] += 1
        obs.event('serving.stream.resume', model=str(model_id),
                  sid=str(sid), from_t=ckpt_t, seen_t=seen_t,
                  replayed=replayed)
        return True

    def _retry_parked(self):
        from ..parallel import HostLost
        parked, self._parked = self._parked, []
        now = time.monotonic()
        for model_id, fut, feed, kwargs, t_exp in parked:
            if fut.done():
                continue
            if now > t_exp:
                _complete(fut, exc=HostLost(
                    'request could not be re-routed within %.1fs of its '
                    'serving host dying (no survivor took it)'
                    % self._reroute_timeout))
                continue
            self._reroute(model_id, fut, feed, kwargs, t_exp)

    # -- streamed decode ---------------------------------------------------

    def stream(self, model_id, feed, ckpt_every=0, **kwargs):
        """Per-token streamed decode across the pod (`Router.stream`
        over the rpc proxies). `ckpt_every` > 0 opts the stream into
        decode-state checkpointing at that token cadence: if the
        serving host dies mid-generation, the stream is re-routed to a
        survivor and resumed TOKEN-EXACT from the last checkpoint
        (serving.stream.resume); with 0, a host loss fails the stream
        with the typed HostLost. The checkpoint rides the shared pod
        filesystem (streams/ckpt.<sid>.npz), so any survivor can pick
        it up."""
        if ckpt_every:
            kwargs['sid'] = uuid.uuid4().hex[:12]
            kwargs['ckpt_every'] = int(ckpt_every)
        return Router.stream(self, model_id, feed, **kwargs)

    # -- healing -----------------------------------------------------------

    def request_heal(self, model_id, reason='heal', lost_host=None,
                     exclude_hosts=()):
        """Ask the least-loaded live host with a builder for `model_id`
        to build+register a replacement replica (it re-shards the
        checkpoint onto its own topology). Returns the heal token, or
        None when no candidate host exists (retried implicitly when a
        capable host appears? no — callers re-request)."""
        stale = set(self.heartbeat.check(raise_error=False))
        if lost_host is not None:
            stale.add(lost_host)
        stale.update(exclude_hosts)
        cands = [h for h, d in sorted(self._hosts.items())
                 if h not in stale
                 and str(model_id) in (d.get('builders') or [])]
        if not cands:
            obs.event('serving.pod.heal_unroutable',
                      model=str(model_id), reason=reason)
            return None
        # least-loaded host = fewest replicas currently registered on it
        load = collections.Counter(i['host']
                                   for i in self._known.values())
        host = min(cands, key=lambda h: (load.get(h, 0), h))
        token = uuid.uuid4().hex[:12]
        self._heals[token] = {'model': model_id, 'lost_host': lost_host,
                              'host': host, 't': time.monotonic(),
                              'reason': reason,
                              'exclude': sorted(set(exclude_hosts))}
        # the heal order carries a trace context (continuing the caller's
        # when inside one), so the whole recovery — this request, the
        # target host's build/re-shard, the registration — stitches into
        # ONE timeline the collector can render
        ctx = trace.current()
        if ctx is None:
            ctx = trace.new_trace()
        os.makedirs(_ctl_dir(self.pod_dir, host), exist_ok=True)
        _atomic_json(os.path.join(_ctl_dir(self.pod_dir, host),
                                  'cmd.%s.json' % token),
                     {'cmd': 'heal', 'model': str(model_id),
                      'token': token, 'reason': reason,
                      'lost_host': lost_host,
                      'trace': trace.headers(ctx)})
        _C_HEALS.inc()
        with trace.activate(ctx, node='router'):
            obs.event('serving.pod.heal_requested', model=str(model_id),
                      host=host, token=token, reason=reason)
        return token

    def _check_heal_failures(self):
        reg = _registry_dir(self.pod_dir)
        try:
            names = os.listdir(reg)
        except OSError:
            return
        for fname in names:
            if not (fname.startswith('healfail.')
                    and fname.endswith('.json')):
                continue
            d = _read_json(os.path.join(reg, fname))
            try:
                os.remove(os.path.join(reg, fname))
            except OSError:
                continue
            token = (d or {}).get('token')
            h = self._heals.pop(token, None)
            if h is None:
                continue
            obs.event('serving.pod.heal_redispatch',
                      model=str(h['model']), failed_host=d.get('host'),
                      token=str(token),
                      error=str(d.get('error'))[:200])
            # bounded re-dispatch: the exclude set ACCUMULATES through
            # the token chain, so with every capable host failed the
            # chain terminates in heal_unroutable instead of
            # ping-ponging between two broken builders forever
            exclude = set(h.get('exclude') or ())
            if d.get('host') is not None:
                exclude.add(d['host'])
            self.request_heal(h['model'], reason=h.get('reason', 'heal'),
                              lost_host=h.get('lost_host'),
                              exclude_hosts=sorted(exclude))

    def pending_heals(self):
        with self._pod_lock:
            return {t: dict(h) for t, h in self._heals.items()}

    # -- autoscaling -------------------------------------------------------

    def enable_autoscale(self, model_id, policy, builder=None):
        """Tick an Autoscaler for `model_id` every poll. Default
        scale-up goes through a heal command (the replica lands on the
        least-loaded capable HOST); pass `builder` to add in-process
        replicas instead. Scale-down drains the least-loaded replica
        through the removal seam either way."""
        scale_up = None
        if builder is None:
            scale_up = lambda reason: self.request_heal(  # noqa: E731
                model_id, reason=reason)
        a = Autoscaler(self, model_id, policy, builder=builder,
                       scale_up=scale_up)
        with self._pod_lock:
            self._autoscalers[model_id] = a
        return a

    # -- drill/bench conveniences ------------------------------------------

    def wait_for_replicas(self, model_id, n, timeout=30.0):
        """Block until `model_id` has >= n routable replicas (drills:
        'pod is up'). Returns the replica view or raises TimeoutError."""
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            self.poll()
            try:
                view = self.replicas(model_id)
            except KeyError:
                view = []
            if len(view) >= n:
                return view
            time.sleep(self._poll_s)
        raise TimeoutError(
            'model %r has %d of %d wanted replicas after %.1fs'
            % (model_id, len(view), n, timeout))

    def shutdown(self, drain=True, timeout=None):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout or 10.0)
        ok = Router.shutdown(self, drain=drain, timeout=timeout)
        self.spill_traces(force=True)   # final flush: no span lost
        return ok
