"""High-level Trainer API.

Parity: reference python/paddle/fluid/trainer.py (Trainer:169,
CheckpointConfig:100, the Begin/End Epoch/Step events, build_feed_var_list:608)
— the train_func/optimizer_func loop used by every book chapter.

TPU-first notes: the reference's distribute-transpile-from-env branch
(pserver/NCCL2) is replaced by the mesh path — parallel=True runs the same
program GSPMD-sharded through ParallelExecutor (XLA inserts the ICI
collectives); multi-host setup goes through paddle_tpu.parallel.init_multihost.
Checkpoint/resume keeps the reference's crash-recovery semantics: periodic
persistable snapshots + (epoch, step) trainer args, auto-resumed when a
Trainer is constructed over a checkpoint dir, cleaned on successful finish.
"""
import contextlib
import os
import re

from .. import obs
from . import core
from . import framework
from . import io
from . import optimizer as opt_module
from . import parallel_executor
from . import unique_name
from .data_feeder import DataFeeder
from .executor import Executor, Scope, scope_guard

__all__ = [
    'Trainer', 'BeginEpochEvent', 'EndEpochEvent', 'BeginStepEvent',
    'EndStepEvent', 'CheckpointConfig',
]


class BeginEpochEvent(object):
    """reference trainer.py:40."""

    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent(object):
    """reference trainer.py:52."""

    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent(object):
    """reference trainer.py:64. Set self.fetch_metrics=False in the handler
    to skip fetching the train_func outputs this step."""

    def __init__(self, epoch_id, step_id):
        self.epoch = epoch_id
        self.step = step_id
        self.fetch_metrics = True


class EndStepEvent(object):
    """reference trainer.py:83."""

    def __init__(self, epoch_id, step_id, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


class CheckpointConfig(object):
    """reference trainer.py:100."""

    def __init__(self, checkpoint_dir=None, max_num_checkpoints=3,
                 epoch_interval=1, step_interval=10, commit_timeout=60.0,
                 async_save=False, wallclock_interval_s=None):
        """commit_timeout: sharded-checkpoint commit wait (seconds) —
        how long process 0 waits for every peer's staged manifest before
        declaring the save uncommitted (docs/robustness.md#elastic).
        Irrelevant to the dense npz format.

        async_save: move the sharded-checkpoint file IO + commit protocol
        off the step path onto a background writer thread
        (utils.checkpoint.save_sharded_async). The step-boundary cost
        shrinks to the buffer snapshot (device->host shard copies, taken
        synchronously so the next step may donate the device buffers);
        the atomic staging + manifest-last + commit-rename protocol is
        unchanged, so a SIGKILL mid-async-save still never leaves a
        latest-looking torn serial. Emergency / preemption / host-loss
        flushes first drain the in-flight writer, then save
        SYNCHRONOUSLY — they commit (or stage loudly) before exit.
        Sharded-format only; the dense npz path ignores it.

        wallclock_interval_s: unbounded-stream cadence
        (Trainer.train_stream): ALSO checkpoint whenever this many
        seconds have passed since the last save, regardless of the step
        interval — an online trainer consuming a slow stream must bound
        recovery by wall clock, not step count. Epoch-based train()
        ignores it."""
        assert epoch_interval >= 1
        assert step_interval >= 1
        self.checkpoint_dir = (checkpoint_dir if checkpoint_dir is not None
                               else os.getcwd())
        self.max_num_checkpoints = max_num_checkpoints
        self.epoch_interval = epoch_interval
        self.step_interval = step_interval
        self.commit_timeout = float(commit_timeout)
        self.async_save = bool(async_save)
        self.wallclock_interval_s = (float(wallclock_interval_s)
                                     if wallclock_interval_s is not None
                                     else None)
        self.epoch_id = 0
        self.step_id = 0
        self.load_serial = None


def check_and_get_place(place):
    """reference trainer.py:143 — default to the TPU when present."""
    return core.default_place() if place is None else place


def build_feed_var_list(program, feed_order=None):
    """reference trainer.py:608; feed_order None follows the program's
    data-var definition order."""
    if not isinstance(program, framework.Program):
        raise TypeError("The 'program' should be an object of Program")
    block = program.global_block()
    if feed_order is None:
        return [v for v in block.vars.values()
                if getattr(v, 'is_data', False)]
    if isinstance(feed_order, list):
        return [block.var(name) for name in feed_order]
    if not isinstance(feed_order, dict):
        raise TypeError("The 'feed_order' should be either None, list or dict.")
    if sorted(feed_order.values()) != list(range(len(feed_order))):
        raise ValueError("The values of 'feed_order' should be a permutation "
                         "of [0, len(feed_order))")
    return [block.var(name)
            for name, _ in sorted(feed_order.items(), key=lambda kv: kv[1])]


class Trainer(object):
    """reference trainer.py:169."""

    def __init__(self, train_func, optimizer_func, param_path=None,
                 place=None, parallel=False, checkpoint_config=None,
                 transpiler_fn=None, bundle_steps=1, sync='auto',
                 async_window=2, heartbeat=None, double_buffer=False):
        """transpiler_fn(train_program): optional hook applied after
        minimize — the high-level entry for the Program transpilers, e.g.
        lambda p: fluid.TensorParallelTranspiler(tp=2).transpile(p)
        (or SequenceParallel/Pipeline; TPU extension, the reference's
        Trainer had only the pserver path).

        Hot-loop pipelining (docs/perf.md):
          bundle_steps=K (K>1) runs K reader batches per device dispatch
          through Executor.run_bundle — one lax.scan-compiled module, one
          host round-trip per K steps. Begin/EndStepEvents still fire per
          logical step (End events carry that step's own metrics sliced
          from the bundle); BeginStepEvent.fetch_metrics is honored per
          BUNDLE (the first step's decision — a bundle is one compiled
          module with one fetch set). Periodic checkpoints are taken at
          bundle boundaries (the scope holds bundle-end state only).
          sync='async' (unbundled path) fetches metrics as lazy
          FetchHandles and keeps up to `async_window` steps in flight:
          the loss is only synced when the event handler reads it (or
          when the window evicts its oldest step), overlapping host
          bookkeeping with device execution.
          double_buffer=True moves the INPUT side off the critical path
          (docs/perf.md#overlap): a background prefetch thread
          (reader.pipeline.prefetch) runs the DataFeeder assembly — and,
          for plain single-device programs, the host->device transfer —
          of batch N+1 while step N executes, so the loop's per-step
          input wait (`trainer.input_stage` spans, the obs_report
          overlap ratio) reads ~0 in steady state. Values are
          bit-identical to the synchronous path: staging changes WHERE
          the feed work happens, never what is fed."""
        if bundle_steps < 1:
            raise ValueError('bundle_steps must be >= 1, got %r'
                             % (bundle_steps,))
        if sync not in ('auto', 'block', 'async'):
            raise ValueError("sync must be 'auto', 'block' or 'async', "
                             "got %r" % (sync,))
        if parallel and (bundle_steps > 1 or sync == 'async'):
            raise ValueError(
                'bundle_steps/sync="async" pipeline the single-program '
                'Executor hot loop; parallel=True (ParallelExecutor) '
                'does not compose with them — express dp via '
                'transpiler_fn instead')
        if bundle_steps > 1 and sync == 'async':
            raise ValueError(
                'bundle_steps=%d already amortizes the host round-trip '
                'over the bundle, and the bundled loop slices per-step '
                'metrics for its EndStepEvents (a blocking read); '
                "sync='async' applies to the unbundled loop — pick one"
                % bundle_steps)
        self.bundle_steps = int(bundle_steps)
        self.sync = sync
        self.async_window = max(1, int(async_window))
        self.double_buffer = bool(double_buffer)
        # input-overlap accounting (docs/perf.md#overlap): total seconds
        # the train loop actually WAITED for its next fed batch, and the
        # batches counted
        self.input_stage_s = 0.0
        self.batches_fed = 0
        # in-flight async sharded checkpoint (CheckpointConfig
        # async_save=True): at most ONE writer outstanding; every new
        # save, emergency flush, or cleanup drains it first
        self._async_ckpt = None
        self.__stop = False
        # preemption (SIGTERM/SIGINT while train() runs): the handler only
        # sets _preempt_requested; the loop finishes the in-flight step,
        # flushes an emergency checkpoint, and returns cleanly with
        # self.preempted = True. A fresh Trainer over the same checkpoint
        # dir resumes at the exact next step.
        self._preempt_requested = False
        self._preempt_signum = None
        self.preempted = False
        # elastic host-failure detection (docs/robustness.md#elastic):
        # a parallel.Heartbeat whose check() runs at every step boundary;
        # a stale peer flushes an emergency checkpoint and raises the
        # typed parallel.HostLost so a supervisor restarts on the
        # surviving topology. host_lost records what was detected.
        self.heartbeat = heartbeat
        self.host_lost = None
        # streaming-ids state (train_stream, docs/embedding.md): the
        # active {feed name: VocabTable} map serialized into every
        # checkpoint's meta, and the vocab meta recovered from a resumed
        # checkpoint (applied when train_stream() is handed its tables)
        self._stream_vocabs = None
        self._stream_resume_vocab = None
        self.parallel = parallel
        self.trainer_id = 0
        self.checkpoint_cfg = checkpoint_config
        if self.checkpoint_cfg:
            assert isinstance(self.checkpoint_cfg, CheckpointConfig)

        self.scope = Scope()
        self.startup_program = framework.Program()
        self.train_program = framework.Program()

        with self._prog_and_scope_guard():
            with unique_name.guard():
                outs = train_func()
                self.train_func_outputs = (outs if isinstance(outs, list)
                                           else [outs])
                self.test_program = self.train_program.clone(for_test=True)
                loss = self.train_func_outputs[0]
                optimizer = optimizer_func()
                if not isinstance(optimizer, opt_module.Optimizer):
                    raise TypeError(
                        "The optimizer should be an instance of Optimizer")
                optimizer.minimize(loss)
                if transpiler_fn is not None:
                    if self.parallel:
                        raise ValueError(
                            'parallel=True builds its own dp-only mesh and '
                            'would silently drop the transpiler_fn '
                            'annotations; compose dp via '
                            'fluid.DistributeTranspiler inside '
                            'transpiler_fn instead')
                    transpiler_fn(self.train_program)
                    # the for_test clone was taken before the hook ran
                    # (reference ordering); carry the mesh annotations over
                    # so test() runs against the same mesh-placed scope
                    dc = getattr(self.train_program, '_dist_config', None)
                    if dc is not None:
                        self.test_program._dist_config = dict(dc)
                        self.test_program._dist_mesh = None
                    # GSPMD annotation path: a hook that set_mesh() the
                    # train program must leave test() on the same mesh —
                    # the scope's persistables are mesh-placed
                    ma = getattr(self.train_program, '_mesh_axes', None)
                    if (ma is not None and getattr(
                            self.test_program, '_mesh_axes', None) is None):
                        self.test_program.set_mesh(
                            list(ma),
                            data_axis=self.train_program._mesh_data_axis)
                    self.train_program._retranspile_pipeline(
                        self.test_program)

        self.place = check_and_get_place(place)
        self.exe = Executor(self.place)
        with self._prog_and_scope_guard():
            self.exe.run(self.startup_program)

        self._serial = 0
        if self.checkpoint_cfg:
            self._maybe_resume_from_checkpoint()

        if param_path and os.path.isdir(param_path):
            with self._prog_and_scope_guard():
                io.load_params(self.exe, param_path,
                               main_program=self.train_program)

    # -- checkpoint/resume ------------------------------------------------

    def _use_sharded_ckpt(self):
        """Annotated (set_mesh) programs checkpoint SHARDED through
        utils.checkpoint.save_sharded: state_dict walks the mesh-placed
        persistables and each host writes only the shards it addresses —
        the dense io.save_checkpoint path would gather a vocab-sharded
        table whole on this host, undoing the sharding's footprint win
        (docs/robustness.md#elastic)."""
        from .executor import _is_annotated
        return _is_annotated(self.train_program)

    def _mesh_axes_list(self):
        mesh = getattr(self.train_program, '_dist_mesh', None)
        if not mesh:
            return None
        return [[str(n), int(s)] for n, s in
                zip(mesh.axis_names, mesh.devices.shape)]

    def _maybe_resume_from_checkpoint(self):
        cfg = self.checkpoint_cfg
        if not os.path.isdir(cfg.checkpoint_dir):
            return
        if self._use_sharded_ckpt():
            from ..utils import checkpoint as shck
            if shck.latest_step(cfg.checkpoint_dir) is not None \
                    and self._resume_sharded(cfg):
                return
            # fall through: no (intact) sharded serial — old dense
            # serials from a pre-elastic run still resume below
        # Newest first; a serial with a torn meta.json / missing or
        # CRC-mismatched params file (crash mid-save, bit rot) falls back
        # to the previous intact one — LOUDLY, because silently replaying
        # steps from an older snapshot is a surprise worth explaining.
        for serial in io.list_checkpoint_serials(cfg.checkpoint_dir)[::-1]:
            try:
                with self._prog_and_scope_guard():
                    with obs.span('trainer.checkpoint.load', serial=serial):
                        meta = io.load_checkpoint(
                            self.exe, cfg.checkpoint_dir, serial=serial,
                            main_program=self.train_program)
            except (RuntimeError, OSError, ValueError, KeyError) as e:
                import warnings
                obs.counter('trainer.resume.fallbacks').inc()
                obs.event('trainer.resume.fallback', serial=serial,
                          error='%s: %s' % (type(e).__name__, e))
                warnings.warn(
                    'checkpoint serial %d in %r failed to load (%s) — '
                    'falling back to the previous serial'
                    % (serial, cfg.checkpoint_dir, e), RuntimeWarning)
                continue
            args = meta.get('trainer_args') or {}
            cfg.load_serial = meta.get('step', 0)
            cfg.epoch_id = int(args.get('epoch_id', 0))
            cfg.step_id = int(args.get('step_id', 0))
            self._serial = int(meta.get('step', 0))
            self._stream_resume_vocab = args.get('streaming_vocab')
            return

    @staticmethod
    def _max_disk_serial(cfg):
        """Largest serial number any sharded_<n>[.tmp|.old] dir under the
        checkpoint dir claims — 0 when none."""
        best = 0
        if os.path.isdir(cfg.checkpoint_dir):
            for d in os.listdir(cfg.checkpoint_dir):
                m = re.fullmatch(r'sharded_(\d+)(\.tmp|\.old)?', d)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    def _resume_sharded(self, cfg):
        """Elastic resume (docs/robustness.md#elastic): restore the
        newest COMMITTED, integrity-verified sharded serial, resharding
        every persistable onto THIS run's mesh — the checkpoint may have
        been written on a different topology (8 devices before a host
        died, 4 now). Exact-step semantics are the dense path's: the
        meta records (epoch, step-within-epoch) and the train loop
        fast-forwards the reader past the already-done steps. Returns
        False (loudly) when no intact sharded serial restores, so the
        caller can try the legacy dense serials."""
        import warnings
        from ..utils import checkpoint as shck
        try:
            with self._prog_and_scope_guard():
                with obs.span('trainer.checkpoint.load', sharded=True):
                    mesh = self.exe._ensure_dist_placement(
                        self.train_program, self.scope)
                    arrays, meta = shck.load_latest_verified(
                        cfg.checkpoint_dir, mesh=mesh)
                    self.exe.load_state_dict(
                        arrays, self.train_program, scope=self.scope)
        except (RuntimeError, OSError, ValueError, KeyError) as e:
            obs.counter('trainer.resume.fallbacks').inc()
            obs.event('trainer.resume.fallback', serial='sharded',
                      error='%s: %s' % (type(e).__name__, e))
            warnings.warn(
                'sharded checkpoint resume from %r failed (%s) — trying '
                'the dense checkpoint serials'
                % (cfg.checkpoint_dir, e), RuntimeWarning)
            return False
        extra = meta.get('extra') or {}
        args = extra.get('trainer_args') or {}
        self._stream_resume_vocab = extra.get('streaming_vocab')
        cfg.load_serial = int(meta.get('step', 0))
        cfg.epoch_id = int(args.get('epoch_id', 0))
        cfg.step_id = int(args.get('step_id', 0))
        # resume numbering PAST every serial number present on disk —
        # committed, staged (.tmp) or demoted (.old). Reusing a crashed
        # incarnation's serial would reuse its staging dir, whose stale
        # step-matched peer manifests could satisfy the new save's
        # commit wait early (mixed-incarnation checkpoint). Every
        # restarted process derives the same number from the same
        # (quiescent) listing, so the cohort stays in step.
        self._serial = max(int(meta.get('step', 0)),
                           self._max_disk_serial(cfg))
        obs.event('elastic.resume', serial=self._serial,
                  epoch=cfg.epoch_id, step=cfg.step_id,
                  from_mesh=extra.get('mesh_axes'),
                  to_mesh=self._mesh_axes_list())
        return True

    def _save_sharded(self, epoch_id, step_id, preempted=False,
                      commit_timeout=None, sync=None):
        """The annotated-program save path: Executor.state_dict walks
        the mesh-placed persistables (a vocab-sharded table stays 8
        device shards — never gathered dense) and save_sharded streams
        each host's own shards, staging + manifest-last + atomic rename
        so a SIGKILL can never leave a latest-looking torn serial. The
        extra meta records the reader position (epoch, step-within-
        epoch) and the mesh shape, for exact-step topology-aware
        resume.

        sync=None follows CheckpointConfig.async_save; emergency paths
        pass sync=True. The async path (docs/perf.md#overlap) pays only
        the buffer snapshot at the step boundary — file IO and the
        commit protocol run on save_sharded_async's writer thread; the
        previous save's handle is drained first, so writers to one dir
        never overlap."""
        from ..utils import checkpoint as shck
        cfg = self.checkpoint_cfg
        if sync is None:
            sync = not getattr(cfg, 'async_save', False)
        args = {'epoch_id': epoch_id, 'step_id': step_id}
        if preempted:
            args['preempted'] = True
        ct = cfg.commit_timeout if commit_timeout is None else commit_timeout
        dest = os.path.join(cfg.checkpoint_dir, 'sharded_%d' % self._serial)
        meta = {'trainer_args': args, 'trainer_id': self.trainer_id,
                'mesh_axes': self._mesh_axes_list()}
        vocab_meta = self._vocab_meta()
        if vocab_meta is not None:
            meta['streaming_vocab'] = vocab_meta
        if not sync:
            # drain the previous writer BEFORE state_dict: ~0 wait in
            # steady state (the write finished steps ago), and it keeps
            # exactly one writer per checkpoint dir
            self._wait_async_ckpt()
        with self._prog_and_scope_guard():
            state = self.exe.state_dict(self.train_program,
                                        scope=self.scope)
            if sync:
                path = shck.save_sharded(dest, state, step=self._serial,
                                         extra_meta=meta,
                                         commit_timeout=ct)
            else:
                self._async_ckpt = shck.save_sharded_async(
                    dest, state, step=self._serial, extra_meta=meta,
                    commit_timeout=ct)
                return dest
        self._prune_sharded(cfg)
        return path

    def _wait_async_ckpt(self, final=False):
        """Drain the in-flight async sharded save (no-op when none).
        Steady state this wait is ~0 — the writer finished during the
        intervening steps; the span records whatever it actually was.
        A CommitTimeout or IO failure here is the PERIODIC-save posture
        (a missed checkpoint, not a dead run): warn loudly, keep
        training on the previous committed serial."""
        h = self._async_ckpt
        if h is None:
            return
        self._async_ckpt = None
        import warnings
        from ..utils.checkpoint import CommitTimeout
        with obs.span('trainer.checkpoint.async_wait',
                      ready=h.done(), final=final):
            try:
                h.wait()
            except CommitTimeout as e:
                warnings.warn(
                    'async sharded checkpoint did not commit (%s); '
                    'training continues on the previous committed '
                    'serial' % e, RuntimeWarning)
                return
            except Exception as e:
                obs.counter('trainer.async_ckpt.failures').inc()
                obs.event('trainer.async_ckpt.failure',
                          error='%s: %s' % (type(e).__name__, e))
                warnings.warn(
                    'async sharded checkpoint FAILED in the background '
                    '(%s: %s) — the serial is missing or partial; '
                    'training continues on the previous committed '
                    'serial' % (type(e).__name__, e), RuntimeWarning)
                return
        self._prune_sharded(self.checkpoint_cfg)

    def _prune_sharded(self, cfg):
        """Keep max_num_checkpoints committed sharded serials (process 0
        only on multi-process meshes — one pruner). Staging leftovers of
        pruned serials go with them."""
        import shutil
        import jax
        if jax.process_index() != 0:
            return
        from ..utils import checkpoint as shck
        serials = []
        for d in os.listdir(cfg.checkpoint_dir):
            m = re.fullmatch(r'sharded_(\d+)', d)
            if m:
                serials.append(int(m.group(1)))
        for s in sorted(serials)[:-cfg.max_num_checkpoints]:
            base = os.path.join(cfg.checkpoint_dir, 'sharded_%d' % s)
            shutil.rmtree(base, ignore_errors=True)
            shutil.rmtree(shck._staging_dir(base), ignore_errors=True)
            shutil.rmtree(base + shck._OLD_SUFFIX, ignore_errors=True)

    def _vocab_meta(self):
        """JSON-able {feed name: VocabTable.state_dict()} of the active
        streaming vocabs (None outside train_stream) — folded into
        every checkpoint's meta so exact-step resume holds under vocab
        drift: the restored map reproduces the id->row assignment the
        restored table rows were trained under
        (docs/embedding.md "streaming ids")."""
        if not self._stream_vocabs:
            return None
        return {str(k): vt.state_dict()
                for k, vt in self._stream_vocabs.items()}

    def _dense_trainer_args(self, epoch_id, step_id, **extra):
        args = {'epoch_id': epoch_id, 'step_id': step_id}
        args.update(extra)
        vm = self._vocab_meta()
        if vm is not None:
            args['streaming_vocab'] = vm
        return args

    def _save_checkpoint(self, epoch_id, step_id, force=False):
        """force=True skips the interval modulo gate — the bundled loop
        applies its own range-crossing gate (a bundle boundary rarely
        lands exactly ON an interval multiple) and records the bundle's
        LAST step, the state the scope actually holds."""
        cfg = self.checkpoint_cfg
        if force or (epoch_id % cfg.epoch_interval == 0
                     and step_id % cfg.step_interval == 0):
            self._serial += 1
            with obs.span('trainer.checkpoint.save',
                          serial=self._serial, epoch=epoch_id,
                          step=step_id,
                          sharded=self._use_sharded_ckpt()):
                if self._use_sharded_ckpt():
                    from ..utils.checkpoint import CommitTimeout
                    try:
                        self._save_sharded(epoch_id, step_id)
                    except CommitTimeout as e:
                        # a slow-but-alive peer (FS stall, GC pause)
                        # missed the commit window: this is a MISSED
                        # periodic checkpoint, not a dead run — the
                        # previous committed serial still carries any
                        # resume. Killing process 0 here would wedge
                        # the healthy peers inside their next
                        # collective. (A genuinely dead peer surfaces
                        # through the heartbeat gate instead.)
                        import warnings
                        warnings.warn(
                            'periodic sharded checkpoint did not '
                            'commit (%s); training continues on the '
                            'previous committed serial' % e,
                            RuntimeWarning)
                    return
                with self._prog_and_scope_guard():
                    io.save_checkpoint(
                        self.exe, cfg.checkpoint_dir,
                        trainer_id=self.trainer_id,
                        main_program=self.train_program,
                        step=self._serial,
                        trainer_args=self._dense_trainer_args(
                            epoch_id, step_id),
                        max_num_checkpoints=cfg.max_num_checkpoints)

    def _save_emergency_checkpoint(self, epoch_id, step_id,
                                   commit_timeout=None):
        """Preemption flush: unconditional (interval-ignoring) snapshot
        recording the exact (epoch, step) just completed, so a successor
        Trainer resumes at step_id + 1 — the reference's crash-recovery
        dirs never had a clean-shutdown writer; SIGTERM simply killed the
        process and lost everything since the last periodic snapshot.
        Annotated programs flush SHARDED, like the periodic path;
        commit_timeout shortens the commit wait when a peer is already
        known dead (host loss)."""
        cfg = self.checkpoint_cfg
        if not cfg:
            return None
        self._serial += 1
        with obs.span('trainer.checkpoint.emergency_flush',
                      serial=self._serial, epoch=epoch_id,
                      step=step_id, sharded=self._use_sharded_ckpt()):
            if self._use_sharded_ckpt():
                # drain any in-flight async writer, then flush
                # SYNCHRONOUSLY: the process is about to exit, and the
                # flush must commit (or stage loudly) before it does
                self._wait_async_ckpt(final=True)
                return self._save_sharded(epoch_id, step_id,
                                          preempted=True,
                                          commit_timeout=commit_timeout,
                                          sync=True)
            with self._prog_and_scope_guard():
                return io.save_checkpoint(
                    self.exe, cfg.checkpoint_dir,
                    trainer_id=self.trainer_id,
                    main_program=self.train_program,
                    step=self._serial,
                    trainer_args=self._dense_trainer_args(
                        epoch_id, step_id, preempted=True),
                    max_num_checkpoints=cfg.max_num_checkpoints)

    # -- preemption -------------------------------------------------------

    def _on_preempt_signal(self, signum, frame):
        # absolutely minimal: flag only. The loop (not the signal frame)
        # owns checkpointing — saving from here could re-enter numpy/jax
        # mid-step.
        self._preempt_requested = True
        self._preempt_signum = signum

    @contextlib.contextmanager
    def _preemption_handlers(self):
        """Install SIGTERM/SIGINT handlers for the duration of train(),
        restoring the previous handlers after. Signals can only be bound
        from the main thread; elsewhere (tests driving trainers from
        worker threads) preemption still works via request_preemption()."""
        import signal as _signal
        import threading
        installed = {}
        if threading.current_thread() is threading.main_thread():
            for sig in (_signal.SIGTERM, _signal.SIGINT):
                try:
                    installed[sig] = _signal.signal(
                        sig, self._on_preempt_signal)
                except (ValueError, OSError):
                    pass
        try:
            yield
        finally:
            for sig, prev in installed.items():
                try:
                    _signal.signal(sig, prev)
                except (ValueError, OSError):
                    pass

    def request_preemption(self):
        """Programmatic preemption (what the SIGTERM handler does): finish
        the in-flight step, flush an emergency checkpoint, return from
        train() cleanly with self.preempted = True."""
        self._preempt_requested = True

    def _finish_preemption(self, last_done):
        """Flush the emergency checkpoint for the last COMPLETED step (if
        any completed this run — otherwise prior checkpoints already
        reflect the state) and mark the trainer preempted."""
        import warnings
        cfg = self.checkpoint_cfg
        saved = False
        if last_done is not None and cfg:
            self._save_emergency_checkpoint(*last_done)
            saved = True
        self.preempted = True
        obs.counter('trainer.preemptions').inc()
        obs.event('trainer.preempted',
                  signum=self._preempt_signum or 'requested',
                  epoch=last_done[0] if last_done else None,
                  step=last_done[1] if last_done else None,
                  emergency_checkpoint=saved)
        where = ('at epoch %d step %d' % last_done if last_done is not None
                 else 'before any step completed')
        if saved:
            detail = 'emergency checkpoint flushed'
        elif cfg:
            detail = ('no emergency checkpoint needed (prior serials '
                      'already reflect the state)')
        else:
            detail = ('emergency checkpoint SKIPPED (no CheckpointConfig '
                      '— progress is lost)')
        warnings.warn(
            'preemption (%s) %s: %s; train() returning cleanly'
            % (self._preempt_signum or 'requested', where, detail),
            RuntimeWarning)

    def _clean_checkpoint(self):
        # Remove only the serial subdirs we created (dense checkpoint_<n>,
        # sharded sharded_<n> + their .tmp staging leftovers) — the
        # configured dir may be (and defaults to) the user's cwd.
        # An in-flight async writer must finish first: deleting dirs out
        # from under it would race the commit rename.
        import shutil
        self._wait_async_ckpt(final=True)
        d = self.checkpoint_cfg.checkpoint_dir
        if not os.path.isdir(d):
            return
        for sub in os.listdir(d):
            if re.fullmatch(r'(checkpoint|sharded)_\d+(\.tmp|\.old)?', sub):
                shutil.rmtree(os.path.join(d, sub), ignore_errors=True)

    # -- host-failure detection -------------------------------------------

    def _check_host_loss(self, last_done, window=None):
        """Heartbeat gate, run at every step boundary BEFORE the next
        dispatch (a dispatch against a dead peer hangs in the
        collective). A stale peer: drain in-flight work, flush an
        emergency checkpoint (sharded saves may legitimately fail to
        COMMIT here — the dead peer can't stage its manifest; the last
        periodic serial then carries the resume), record host_lost, and
        raise the typed parallel.HostLost so the supervisor restarts on
        the surviving topology (docs/robustness.md#elastic)."""
        hb = self.heartbeat
        if hb is None:
            return
        stale = hb.check(raise_error=False)
        if not stale:
            return
        import warnings
        from ..parallel.heartbeat import HostLost
        if window:
            self._drain_async_window(window)
        obs.event('elastic.host_lost', stale=[int(s) for s in stale],
                  epoch=last_done[0] if last_done else None,
                  step=last_done[1] if last_done else None,
                  mesh=self._mesh_axes_list())
        saved = None
        if self.checkpoint_cfg and last_done is not None:
            try:
                saved = self._save_emergency_checkpoint(
                    *last_done,
                    commit_timeout=max(1.0, hb.timeout))
            except Exception as e:
                warnings.warn(
                    'emergency checkpoint after host loss did not '
                    'commit (%s: %s) — resume will fall back to the '
                    'last committed serial' % (type(e).__name__, e),
                    RuntimeWarning)
        # "saved" from a non-zero process means STAGED only — process 0
        # performs the commit rename, and on this path process 0 may be
        # the dead host. Report commitment from the filesystem truth.
        committed = bool(saved) and os.path.isdir(saved)
        self.host_lost = {'stale': list(stale), 'last_done': last_done,
                          'emergency_checkpoint':
                              saved if committed else None,
                          'emergency_staged': saved}
        warnings.warn(
            'host(s) %s lost (heartbeat stale > %.1fs)%s — raising '
            'HostLost; restart on the surviving topology and resume '
            'from the last verified checkpoint'
            % (stale, hb.timeout,
               '; emergency checkpoint committed' if committed
               else '; emergency flush did not commit'), RuntimeWarning)
        raise HostLost(
            'host(s) %s stopped heartbeating during training%s'
            % (stale, ' (last completed step: epoch %d step %d)'
               % last_done if last_done else ''), stale=stale)

    # -- public API -------------------------------------------------------

    def stop(self):
        """reference trainer.py:373 — stop training at the next step."""
        self.__stop = True

    def train(self, num_epochs, event_handler, reader=None, feed_order=None):
        """reference trainer.py:379. While the loop runs, SIGTERM/SIGINT
        mean PREEMPTION, not crash: the in-flight step completes, an
        emergency checkpoint flushes, and train() returns cleanly with
        self.preempted = True (resume by constructing a new Trainer over
        the same checkpoint dir)."""
        self.preempted = False
        self._preempt_requested = False
        started_hb = False
        if self.heartbeat is not None and not self.heartbeat.running:
            self.heartbeat.start()
            started_hb = True
        try:
            with self._preemption_handlers():
                if self.parallel:
                    with self._prog_and_scope_guard():
                        pe = self._get_or_create_parallel_executor()
                    self._train_loop(pe, num_epochs, event_handler, reader,
                                     feed_order)
                else:
                    self._train_loop(self.exe, num_epochs, event_handler,
                                     reader, feed_order)
        finally:
            # train() returning means every checkpoint it started is
            # committed (or loudly failed) — an async writer must never
            # outlive the loop that owns its scope arrays
            self._wait_async_ckpt(final=True)
            if started_hb:
                self.heartbeat.stop()

    def train_stream(self, reader, event_handler=None, feed_order=None,
                     vocabs=None, publisher=None, max_steps=None):
        """Online training over an UNBOUNDED stream — the loop the
        reference's pserver async-training era served, TPU-native
        (docs/embedding.md "streaming ids"). `reader` is an ordinary
        batch-reader factory with NO epoch length: the loop runs until
        the stream ends, `stop()` is called, `max_steps` batches have
        run this call, preemption lands (emergency checkpoint + clean
        return, exactly like train()), or the heartbeat detects a host
        loss (typed HostLost).

        vocabs: {id feed name: streaming.VocabTable} — each named feed
        is translated raw-id -> row on the input stage (prefetch worker
        when double_buffer=True), rows referenced by the in-flight
        batch are pinned until its step completes, and evicted rows are
        zeroed (table + optimizer moments, streaming.RowResetter) at
        the step boundary BEFORE their new owner trains. Translation is
        pure host-side indexing: the compiled step signature never
        changes as the vocab drifts, and with an identity map the
        trained state is bit-exact vs the un-streamed loop (drilled).
        The vocab serializes into every checkpoint's meta and a resumed
        Trainer restores it here, so exact-step resume holds under
        drift.

        publisher: a streaming.DeltaPublisher — after each step the
        touched-row set (StepArtifact.touched_rows: host-side, off the
        step path) is collected, and the publisher's cadence pushes
        those rows' live values into the serving replicas
        (Router.push_deltas). Publisher failures other than the typed
        HostLost are warned and retried next cadence — freshness
        degrades, training never dies for a serving-side hiccup.

        Checkpoints follow CheckpointConfig's step_interval AND
        wallclock_interval_s (whichever fires first); epoch_id is
        recorded as 0 and serials are NOT cleaned on return — a stream
        has no "finished" state, the next Trainer resumes. There is no
        reader fast-forward on resume: a live stream is not replayable;
        the restored (vocab, table, moments) state carries the
        continuity. Returns the number of steps run this call."""
        import time as _time
        if self.parallel:
            raise ValueError('train_stream drives the single-program '
                             'Executor loop; parallel=True does not '
                             'compose with it (use GSPMD annotations)')
        if self.bundle_steps > 1 or self.sync == 'async':
            raise ValueError(
                'train_stream paces checkpoints, vocab leases, and '
                'delta publishing per STEP; bundle_steps>1 / '
                "sync='async' pipeline across steps — pick one "
                '(double_buffer=True overlaps the input side instead)')
        if event_handler is None:
            event_handler = lambda ev: None  # noqa: E731
        vocabs = dict(vocabs or {})
        self._stream_vocabs = vocabs
        cfg = self.checkpoint_cfg
        resumed = bool(cfg and cfg.load_serial)
        if vocabs and resumed and self._stream_resume_vocab:
            for fname, state in self._stream_resume_vocab.items():
                if fname in vocabs:
                    vocabs[fname].load_state_dict(state)
            # one-shot: a SECOND train_stream() call on this Trainer
            # continues the LIVE (drifted) vocab — re-applying the
            # checkpoint-time map would silently mis-map ids to rows
            self._stream_resume_vocab = None
        from ..streaming.vocab import RowResetter, table_state_names
        resetter = RowResetter()
        reset_names = {}
        for fname, vt in vocabs.items():
            if vt.table:
                reset_names[fname] = table_state_names(
                    self.train_program, vt.table)
                if hasattr(vt, 'validate_program'):
                    # tiered tables refuse a dim-sharded table TYPED
                    # (a spill would tear rows across hosts) — before
                    # any step runs, not on the first eviction
                    vt.validate_program(self.train_program)

        leases = {}   # step_id -> [Lease] (writer: input stage;
        #               reader: the loop after that step completes)

        def translate(step_id, fed):
            ls = []
            for fname, vt in vocabs.items():
                v = fed.get(fname)
                if v is None:
                    continue
                if not hasattr(v, 'dtype'):
                    raise TypeError(
                        'train_stream vocab feed %r is not a dense '
                        'array (got %r) — streaming ids are dense id '
                        'batches' % (fname, type(v).__name__))
                mapped, lease = vt.translate(v)
                fed[fname] = mapped.astype(v.dtype, copy=False)
                ls.append(lease)
            if ls:
                leases[step_id] = ls
            return fed

        def apply_resets():
            # zero evicted rows (table + moments) BEFORE the step that
            # trains their new owners dispatches — stale moments would
            # bleed the previous occupant's history into the new id.
            # A tiered table (embedding.tiers.TieredVocabTable) owns
            # its boundary instead: evictions SPILL to the host arena,
            # warm re-admissions RESTORE — and it reports the rows it
            # mutated so the delta publisher keeps serving replicas
            # converged across a spill/restore cycle.
            changed = None
            for fname, vt in vocabs.items():
                names = reset_names.get(fname)
                if not names:
                    continue
                if hasattr(vt, 'apply_step_boundary'):
                    ch = vt.apply_step_boundary(
                        self.scope._chain_get, self.scope._chain_set,
                        names)
                    if ch:
                        changed = changed or {}
                        for t, rows in ch.items():
                            prev = changed.get(t)
                            if prev is None:
                                changed[t] = rows
                            else:
                                changed[t] = sorted(
                                    {int(r) for r in prev}
                                    | {int(r) for r in rows})
                    continue
                rows = vt.drain_resets()
                if not rows:
                    continue
                arrays = [self.scope._chain_get(n) for n in names]
                new = resetter.reset(arrays, rows)
                for n, a in zip(names, new):
                    self.scope._chain_set(n, a)
            return changed

        steps_run = 0
        started_hb = False
        if self.heartbeat is not None and not self.heartbeat.running:
            self.heartbeat.start()
            started_hb = True
        self.preempted = False
        self._preempt_requested = False
        last_done = None
        last_ckpt_t = _time.monotonic()
        start_step = cfg.step_id + 1 if resumed else 0
        warned_dense = set()
        try:
            with self._preemption_handlers():
                with self._prog_and_scope_guard():
                    feed_vars = build_feed_var_list(self.train_program,
                                                    feed_order)
                    feeder = DataFeeder(feed_list=feed_vars,
                                        place=self.place)
                    fetch = [v.name for v in self.train_func_outputs]
                    it = self._iter_staged(reader, feeder, post=translate)
                    self._stream_it = it
                    for rel_id, fed in it:
                        step_id = start_step + rel_id
                        if self.__stop or (max_steps is not None
                                           and steps_run >= max_steps):
                            return steps_run
                        if self._preempt_requested:
                            self._finish_preemption(last_done)
                            return steps_run
                        self._check_host_loss(last_done)
                        tier_changed = apply_resets()
                        begin = BeginStepEvent(0, step_id)
                        event_handler(begin)
                        want = fetch if begin.fetch_metrics else []
                        self._steps_run = getattr(self, '_steps_run',
                                                  0) + 1
                        with obs.span('trainer.step',
                                      step_num=self._steps_run,
                                      epoch=0, step=step_id, stream=True):
                            metrics = self.exe.run(
                                program=self.train_program, feed=fed,
                                fetch_list=want)
                        last_done = (0, step_id)
                        steps_run += 1
                        for lease in leases.pop(rel_id, []):
                            lease.release()
                        if publisher is not None:
                            self._stream_publish(publisher, fed, want,
                                                 warned_dense, vocabs,
                                                 extra_rows=tier_changed)
                        if cfg:
                            due = (step_id > 0 and step_id
                                   % cfg.step_interval == 0)
                            wall = cfg.wallclock_interval_s
                            if not due and wall is not None:
                                due = (_time.monotonic() - last_ckpt_t
                                       >= wall)
                            if due:
                                self._save_checkpoint(0, step_id,
                                                      force=True)
                                last_ckpt_t = _time.monotonic()
                                for vt in vocabs.values():
                                    if hasattr(vt, 'mark_checkpoint'):
                                        # a committed serial no longer
                                        # references slots released
                                        # before it: recycle the
                                        # arena's limbo list
                                        vt.mark_checkpoint()
                        event_handler(EndStepEvent(0, step_id, metrics))
                        if self._preempt_requested:
                            self._finish_preemption(last_done)
                            return steps_run
                    return steps_run
        finally:
            it = getattr(self, '_stream_it', None)
            self._stream_it = None
            if it is not None:
                it.close()   # unblock the prefetch worker on early exit
            for ls in leases.values():
                for lease in ls:
                    lease.release()
            leases.clear()
            self._wait_async_ckpt(final=True)
            if started_hb:
                self.heartbeat.stop()
            self._stream_vocabs = None
            self._stream_art = None

    def _stream_publish(self, publisher, fed, fetch, warned_dense, vocabs,
                        extra_rows=None):
        """Collect this step's touched rows (host-side seam) and run the
        publisher's cadence. `extra_rows` ({table: rows}) carries rows
        the TIER boundary mutated outside the batch — zeroed on spill,
        scattered on restore — so serving replicas converge on them
        too. Serving-side failures warn and retry next cadence; the
        typed HostLost propagates — that is a pod event, not a
        publishing hiccup."""
        import warnings
        from ..parallel.heartbeat import HostLost
        # resolve the artifact ONCE per fetch set, not per step:
        # Executor.step_artifact runs the _prepare front half, which
        # re-places the whole feed batch on device — per-step that
        # would double the hot loop's host->device traffic just to
        # read metadata (the sparse plan does not depend on the batch)
        art = getattr(self, '_stream_art', None)
        if art is None or self._stream_art_key != tuple(fetch):
            try:
                art = self.exe.step_artifact(self.train_program, fed,
                                             fetch, scope=self.scope)
            except Exception as e:
                warnings.warn('train_stream: could not resolve the step '
                              'artifact for touched-row collection '
                              '(%s: %s)' % (type(e).__name__, e),
                              RuntimeWarning)
                return
            self._stream_art = art
            self._stream_art_key = tuple(fetch)
        for fname, vt in vocabs.items():
            t = vt.table
            if t and t not in art.sparse_plan and t not in warned_dense:
                warned_dense.add(t)
                warnings.warn(
                    'train_stream: table %r (vocab feed %r) is NOT on '
                    'the sparse update path — its update writes every '
                    'row each step, so touched-row deltas under-report '
                    'and row eviction is unsafe. Build the lookup with '
                    'is_sparse=True (docs/embedding.md)' % (t, fname),
                    RuntimeWarning)
        touched = art.touched_rows(fed)
        if extra_rows:
            import numpy as _np
            touched = dict(touched or {})
            for t, rows in extra_rows.items():
                merged = {int(r) for r in rows}
                prev = touched.get(t)
                if prev is not None:
                    merged.update(
                        int(r) for r in _np.asarray(prev).reshape(-1))
                touched[t] = _np.asarray(sorted(merged), _np.int64)
        if touched:
            publisher.collect(touched)
        try:
            publisher.maybe_publish(
                lambda name: self.scope._chain_get(name))
        except HostLost:
            raise
        except Exception as e:
            obs.counter('streaming.push_failures').inc()
            warnings.warn(
                'train_stream: delta push failed (%s: %s) — deltas are '
                'retained and retried at the next cadence'
                % (type(e).__name__, e), RuntimeWarning)

    def test(self, reader, feed_order=None):
        """reference trainer.py:409 — mean of train_func outputs over the
        test reader, on the for_test clone."""
        with scope_guard(self.scope):
            feed_vars = build_feed_var_list(self.test_program, feed_order)
            feeder = DataFeeder(feed_list=feed_vars, place=self.place)
            fetch = [v.name for v in self.train_func_outputs]
            import numpy as np
            accumulated = [0.0] * len(fetch)
            count = 0
            for data in reader():
                outs = self.exe.run(program=self.test_program,
                                    feed=feeder.feed(data), fetch_list=fetch)
                accumulated = [a + float(np.asarray(o).reshape(-1)[0])
                               for a, o in zip(accumulated, outs)]
                count += 1
            return [a / max(count, 1) for a in accumulated]

    def save_params(self, param_path):
        """reference trainer.py:421."""
        with self._prog_and_scope_guard():
            io.save_params(self.exe, dirname=param_path,
                           main_program=self.train_program)

    def save_inference_model(self, param_path, feeded_var_names,
                             target_var_indexes):
        """Persist the pruned inference graph + params (reference
        trainer.py save_inference_model variant)."""
        with self._prog_and_scope_guard():
            io.save_inference_model(
                param_path, feeded_var_names,
                [self.train_func_outputs[i] for i in target_var_indexes],
                self.exe, main_program=self.train_program)

    # -- internals --------------------------------------------------------

    @contextlib.contextmanager
    def _prog_and_scope_guard(self):
        with framework.program_guard(main_program=self.train_program,
                                     startup_program=self.startup_program):
            with scope_guard(self.scope):
                yield

    def _get_or_create_parallel_executor(self):
        if getattr(self, 'parallel_executor', None) is None:
            self.parallel_executor = parallel_executor.ParallelExecutor(
                use_cuda=False,
                loss_name=self.train_func_outputs[0].name,
                main_program=self.train_program, scope=self.scope)
        return self.parallel_executor

    @staticmethod
    def _bundle_feed_sig(fed):
        """Shape/dtype signature of one fed batch — bundles may only
        group batches that share it (one compiled module)."""
        from .executor import _feed_signature
        return tuple(sorted(_feed_signature(n, v) for n, v in fed.items()))

    def _drain_async_window(self, window, n_keep=0):
        """Sync the oldest in-flight steps until at most n_keep remain.
        Each block records executor.host_stall — the histogram that shows
        how much device time the async window actually hid."""
        from .executor import FetchHandle
        while len(window) > n_keep:
            for h in window.popleft():
                if isinstance(h, FetchHandle):
                    h.block()

    def _iter_staged(self, reader, feeder, skip_until=-1, post=None):
        """Yield (step_id, fed_batch) for one epoch's reader pass.

        double_buffer=False: the DataFeeder assembly runs inline (the
        historical behavior), timed as a `trainer.input_stage` span so
        the on/off A/B is measurable from one run log.

        double_buffer=True (docs/perf.md#overlap): assembly — and the
        host->device transfer for plain single-device programs — runs on
        a reader.pipeline.prefetch worker thread, staging batch N+1
        while step N executes. The span then measures only the time the
        loop actually BLOCKED on the queue: ~0 in the overlapped steady
        state (the obs_report step-artifact section computes the overlap
        ratio from input_stage vs trainer.step time). Bundled loops keep
        host ndarrays so run_bundle's single-stack device transfer stays
        on its fast path; mesh programs keep placement in _prepare.

        skip_until: last step id already completed before a crash
        (resume fast-forward) — those reader items are consumed and
        yielded as (step_id, None) WITHOUT feed assembly or
        input_stage accounting, so catching up past N done steps stays
        as cheap as it was before staging existed.

        post(step_id, fed) -> fed: per-batch feed rewrite hook, run on
        the SAME thread as the assembly (the prefetch worker when
        double-buffered, before device staging) — the streaming-ids
        loop translates raw ids through its VocabTable here, so
        admission/eviction overlap the previous step exactly like the
        rest of the input stage (docs/embedding.md "streaming ids")."""
        import time as _time

        def record(step_id, dt, staged):
            obs.span_record('trainer.input_stage', dt, step=step_id,
                            staged=staged)
            self.input_stage_s += dt
            self.batches_fed += 1

        if not self.double_buffer:
            def plain():
                for step_id, data in enumerate(reader()):
                    if step_id <= skip_until:
                        yield step_id, None
                        continue
                    t0 = _time.perf_counter()
                    fed = feeder.feed(data)
                    if post is not None:
                        fed = post(step_id, fed)
                    record(step_id, _time.perf_counter() - t0, False)
                    yield step_id, fed
            return plain()

        from ..reader import pipeline as rpipe
        exe, prog = self.exe, self.train_program
        place_in_worker = (not self.parallel and self.bundle_steps == 1
                           and getattr(prog, '_dist_config', None) is None
                           and getattr(prog, '_mesh_axes', None) is None)

        def tagged():
            return enumerate(reader())

        def stage(pair):
            step_id, data = pair
            if step_id <= skip_until:
                return step_id, None
            fed = feeder.feed(data)
            if post is not None:
                fed = post(step_id, fed)
            if place_in_worker:
                fed = exe._place_feed(prog, fed, None)
            return step_id, fed

        staged = rpipe.prefetch(tagged, depth=2, transform=stage)

        def overlapped():
            it = staged()
            try:
                while True:
                    t0 = _time.perf_counter()
                    try:
                        step_id, fed = next(it)
                    except StopIteration:
                        return
                    if fed is not None:
                        record(step_id, _time.perf_counter() - t0, True)
                    yield step_id, fed
            finally:
                it.close()   # unblock the prefetch worker on early exit

        return overlapped()

    def _train_loop(self, exe, num_epochs, event_handler, reader, feed_order):
        with self._prog_and_scope_guard():
            feed_vars = build_feed_var_list(self.train_program, feed_order)
            feeder = DataFeeder(feed_list=feed_vars, place=self.place)
            is_pe = isinstance(exe, parallel_executor.ParallelExecutor)
            fetch = [v.name for v in self.train_func_outputs]
            cfg = self.checkpoint_cfg
            start_epoch = cfg.epoch_id if cfg and cfg.load_serial else 0
            if self.bundle_steps > 1 and not is_pe:
                self._train_loop_bundled(exe, num_epochs, event_handler,
                                         reader, feeder, fetch)
                return
            use_async = self.sync == 'async' and not is_pe
            import collections
            window = collections.deque()   # in-flight async fetch handles
            # (epoch, step) of the last COMPLETED step this run — what an
            # emergency checkpoint must record when preemption is noticed
            # while the reader blocks / between steps, i.e. before another
            # exe.run ever happens
            last_done = None
            for epoch_id in range(start_epoch, num_epochs):
                event_handler(BeginEpochEvent(epoch_id))
                skip = (cfg.step_id if cfg and cfg.load_serial
                        and epoch_id == cfg.epoch_id else -1)
                for step_id, fed in self._iter_staged(reader, feeder,
                                                      skip_until=skip):
                    if self.__stop:
                        self._drain_async_window(window)
                        if cfg:
                            self._clean_checkpoint()
                        return
                    if self._preempt_requested:
                        # signal landed while the reader was producing
                        # this batch (which can block for a long time):
                        # flush NOW from the consistent between-step
                        # state instead of paying for one more step
                        self._drain_async_window(window)
                        self._finish_preemption(last_done)
                        return
                    # host-failure gate: BEFORE dispatching another step
                    # whose collectives would hang on a dead peer
                    self._check_host_loss(last_done, window)
                    if fed is None:
                        continue  # already done before the crash
                    begin = BeginStepEvent(epoch_id, step_id)
                    event_handler(begin)
                    want = fetch if begin.fetch_metrics else []
                    # trainer.step nests the executor.step span and, when
                    # observability is on, marks the XLA trace with
                    # StepTraceAnnotation so Perfetto groups device
                    # activity per training step
                    self._steps_run = getattr(self, '_steps_run', 0) + 1
                    with obs.span('trainer.step',
                                  step_num=self._steps_run,
                                  epoch=epoch_id, step=step_id):
                        if is_pe:
                            metrics = exe.run(want, feed=fed)
                        elif use_async:
                            metrics = exe.run(program=self.train_program,
                                              feed=fed,
                                              fetch_list=want,
                                              sync='async')
                        else:
                            metrics = exe.run(program=self.train_program,
                                              feed=fed,
                                              fetch_list=want)
                    last_done = (epoch_id, step_id)
                    if use_async:
                        # bounded dispatch window: the handler below may
                        # read (sync) its step's metrics or not — either
                        # way at most async_window steps stay un-synced
                        window.append(metrics)
                        self._drain_async_window(window,
                                                 n_keep=self.async_window)
                    if self._preempt_requested:
                        # the step above COMPLETED (run() synchronizes on
                        # its fetches; async handles sync on read); record
                        # it and leave. No _clean_checkpoint: the whole
                        # point is resuming.
                        self._drain_async_window(window)
                        self._finish_preemption(last_done)
                        event_handler(EndStepEvent(epoch_id, step_id,
                                                   metrics))
                        return
                    if cfg:
                        self._save_checkpoint(epoch_id, step_id)
                    event_handler(EndStepEvent(epoch_id, step_id, metrics))
                event_handler(EndEpochEvent(epoch_id))
                if self._preempt_requested:
                    # between epochs: same flush, no extra step
                    self._drain_async_window(window)
                    self._finish_preemption(last_done)
                    return
            self._drain_async_window(window)
            if cfg:
                self._clean_checkpoint()

    def _train_loop_bundled(self, exe, num_epochs, event_handler, reader,
                            feeder, fetch):
        """K-step bundled hot loop: buffer K reader batches, run them as
        ONE Executor.run_bundle dispatch, then fire the K EndStepEvents
        with per-step metric slices. Stop/preemption are honored at
        bundle boundaries (a partial buffer is flushed first, so no
        consumed batch is silently dropped); periodic checkpoints are
        taken after a bundle for its LAST step — the scope only ever
        holds bundle-end state."""
        import numpy as np
        K = self.bundle_steps
        cfg = self.checkpoint_cfg
        start_epoch = cfg.epoch_id if cfg and cfg.load_serial else 0
        last_done = None

        def bundle_checkpoint(first_step, done):
            """Periodic-checkpoint gate for a just-flushed bundle: save
            when ANY step in [first_step, last_step] crossed a
            step_interval mark — the boundary itself rarely lands on a
            multiple (K=8, interval=10 never does), so the unbundled
            modulo gate would silently never fire. Records the bundle's
            last step: that is the state the scope holds."""
            if not cfg or done is None:
                return
            epoch_id, last_step = done
            if epoch_id % cfg.epoch_interval:
                return
            if any(s % cfg.step_interval == 0
                   for s in range(first_step, last_step + 1)):
                self._save_checkpoint(epoch_id, last_step, force=True)

        def run_bundle_buf(buf, epoch_id):
            """Execute buffered (step_id, feed, want) entries; returns the
            last (epoch, step) done."""
            if not buf:
                return None
            want = buf[0][2]   # fetch_metrics decided per bundle
            feeds = [b[1] for b in buf]
            self._steps_run = getattr(self, '_steps_run', 0) + len(buf)
            with obs.span('trainer.step', step_num=self._steps_run,
                          epoch=epoch_id, step=buf[-1][0],
                          bundle_steps=len(buf)):
                stacked = exe.run_bundle(program=self.train_program,
                                         feeds=feeds, fetch_list=want)
            for j, (step_id, _f, _w) in enumerate(buf):
                if want:
                    metrics = [m[j] if isinstance(m, list)
                               else np.asarray(m)[j] for m in stacked]
                else:
                    metrics = []
                event_handler(EndStepEvent(epoch_id, step_id, metrics))
            return (epoch_id, buf[-1][0])

        for epoch_id in range(start_epoch, num_epochs):
            event_handler(BeginEpochEvent(epoch_id))
            buf = []   # (step_id, feed_dict, want) awaiting one dispatch
            buf_sig = None
            skip = (cfg.step_id if cfg and cfg.load_serial
                    and epoch_id == cfg.epoch_id else -1)
            for step_id, fed in self._iter_staged(reader, feeder,
                                                  skip_until=skip):
                if self.__stop:
                    done = run_bundle_buf(buf, epoch_id)
                    last_done = done or last_done
                    if cfg:
                        self._clean_checkpoint()
                    return
                if self._preempt_requested:
                    done = run_bundle_buf(buf, epoch_id)
                    last_done = done or last_done
                    self._finish_preemption(last_done)
                    return
                # host-failure gate; buffered batches are NOT flushed
                # through the mesh first (its peers are gone) — the
                # emergency path records the last COMPLETED bundle
                self._check_host_loss(last_done)
                if fed is None:
                    continue  # already done before the crash
                begin = BeginStepEvent(epoch_id, step_id)
                event_handler(begin)
                sig = self._bundle_feed_sig(fed)
                if buf and sig != buf_sig:
                    # batch shape changed mid-stream (classically: the
                    # reader's short last batch) — a bundle is one
                    # compiled module over uniform shapes, so flush what
                    # is buffered and start a new bundle
                    first = buf[0][0]
                    done = run_bundle_buf(buf, epoch_id)
                    last_done = done or last_done
                    buf = []
                    if not self._preempt_requested:
                        bundle_checkpoint(first, done)
                buf_sig = sig
                # fetch set is per BUNDLE (one compiled module): the first
                # buffered step's fetch_metrics decision wins
                want = (buf[0][2] if buf
                        else (fetch if begin.fetch_metrics else []))
                buf.append((step_id, fed, want))
                if len(buf) == K:
                    first = buf[0][0]
                    done = run_bundle_buf(buf, epoch_id)
                    last_done = done or last_done
                    buf = []
                    if self._preempt_requested:
                        self._finish_preemption(last_done)
                        return
                    bundle_checkpoint(first, done)
            if buf:   # partial bundle at epoch end
                first = buf[0][0]
                done = run_bundle_buf(buf, epoch_id)
                last_done = done or last_done
                if not self._preempt_requested:
                    bundle_checkpoint(first, done)
            event_handler(EndEpochEvent(epoch_id))
            if self._preempt_requested:
                self._finish_preemption(last_done)
                return
        if cfg:
            self._clean_checkpoint()
