"""paddle_tpu.obs — the runtime telemetry layer.

Three pieces (docs/observability.md has the full catalog):

  * a process-wide METRICS REGISTRY (obs.metrics): counters, gauges,
    fixed-bucket histograms. Always armed — an increment is a lock and an
    add, cheap enough for the executor hot path — so `exe.cache_stats`
    and the fault-drill assertions work with no environment set up.
  * a STRUCTURED RUN LOG: JSONL, one event per record, written under
    $PADDLE_TPU_OBS_DIR (or obs.enable(dir)). Created lazily on the first
    record; when observability is disabled there is NO file IO at all.
  * a SPAN API: `with obs.span("executor.step"): ...` nests via a
    thread-local stack, records wall time into the registry histogram
    `<name>.seconds`, appends a span record to the run log, and forwards
    to jax.profiler.TraceAnnotation (StepTraceAnnotation when step_num is
    given) so the same names appear in Perfetto/XLA traces.
  * a COMPLETED-SPAN BUFFER: while observability is on, every span record
    (the run log's, with `t0`/`t1` on time.perf_counter's clock) is also
    kept in a bounded in-memory buffer that obs.completed_spans()
    returns, so a reader after the run computes self times over a window
    without parsing a file. Generation-2 garbage collections land there
    too, as `host.gc` spans.

Disabled-mode contract (the default): spans still time into the in-memory
registry, but no file is written, no event is recorded, and jax is never
imported — this module is stdlib-only and only *reuses* jax.profiler when
the host program already imported jax AND observability is on. Tests load
the package standalone (importlib, no paddle_tpu parent) to enforce that.
"""
import atexit
import collections
import contextlib
import gc
import itertools
import os
import sys
import threading
import time

from . import metrics  # noqa: F401
from . import report  # noqa: F401
from . import runlog as _runlog
from . import slo  # noqa: F401
from . import trace  # noqa: F401
from .metrics import REGISTRY, counter, gauge, histogram  # noqa: F401

__all__ = ['metrics', 'report', 'slo', 'trace', 'REGISTRY', 'counter',
           'gauge', 'histogram', 'enabled', 'obs_dir', 'enable', 'disable',
           'event', 'span', 'span_if', 'span_record', 'completed_spans',
           'run_log_path', 'ENV_DIR']

ENV_DIR = 'PADDLE_TPU_OBS_DIR'
# Optional: pin the run-log to an EXACT file path instead of a fresh
# run-<stamp>-<pid>.jsonl — how a driver script collects its own events
# and every child process's into a single run file.
ENV_RUN_FILE = 'PADDLE_TPU_OBS_RUN_FILE'
# Ring-buffer bound of the run log (see runlog.RunLog); applies to fresh
# per-run files. A pinned shared file (ENV_RUN_FILE) stays unbounded by
# default because compaction would drop other writers' appends.
ENV_MAX_EVENTS = 'PADDLE_TPU_OBS_MAX_EVENTS'
DEFAULT_MAX_EVENTS = 500000
# Bound of the completed-span buffer: a 20 s benchmark window is some 140
# steps of 8 records (`executor.step` and its seven children; 2000 steps
# fit), a whole run with its set-up a few thousand.
SPAN_BUFFER_MAX = 16384

_state = {
    'override': None,      # None = follow env; (True, dir) / (False, None)
    'runlog': None,
    'runlog_dir': None,
    'failed_dir': None,    # dir whose run-log creation failed (warn once)
    'lock': threading.RLock(),
    # deque of completed span records; made with the first record kept,
    # and _gc_callback is in gc.callbacks exactly while it exists
    'spans': None,
    'spans_dropped': 0,    # records the bound pushed out of 'spans'
    'gc_t0': None,         # start of the generation-2 collection under way
}
# generation-2 collections seen by _gc_callback and not yet recorded:
# (t0, t1, collected). The callback runs wherever an allocation triggers
# a collection, possibly inside the run log's write, so it only appends
# here; the next record (or completed_spans()) turns them into spans.
_gc_pending = []
_span_ids = itertools.count(1)
_local = threading.local()
# span-name -> registry histogram, so the per-span fast path skips the
# registry's label-normalizing lookup (hot: 2 spans per executor step,
# 8 while observability is on)
_span_hists = {}


def obs_dir():
    """The active observability directory, or None when disabled.
    obs.enable()/disable() override the PADDLE_TPU_OBS_DIR environment."""
    ov = _state['override']
    if ov is not None:
        return ov[1] if ov[0] else None
    return os.environ.get(ENV_DIR) or None


def enabled():
    return obs_dir() is not None


def enable(dir_path):
    """Force observability on, writing a fresh run log under dir_path
    (tests and notebooks; production uses the environment variable)."""
    with _state['lock']:
        _close_runlog_locked()
        _state['override'] = (True, str(dir_path))


def disable():
    """Force observability off regardless of the environment; closes the
    current run log. Call enable()/disable(None-reset) via _reset() in
    tests to return to env-driven behavior."""
    with _state['lock']:
        _close_runlog_locked()
        _state['override'] = (False, None)


def _reset():
    """Back to environment-driven state with no open run log (tests)."""
    with _state['lock']:
        _close_runlog_locked()
        _state['override'] = None
        _span_hists.clear()   # drop handles detached by REGISTRY.reset()
        if _state['spans'] is not None:
            gc.callbacks.remove(_gc_callback)
        _state['spans'] = _state['gc_t0'] = None
        _state['spans_dropped'] = 0
        del _gc_pending[:]
    trace._reset()


@atexit.register
def _close_at_exit():
    # span records wait in the run log for their batch (runlog.SPAN_BATCH):
    # a process that ends in the ordinary way writes them out
    with _state['lock']:
        _close_runlog_locked()


def _close_runlog_locked():
    rl = _state['runlog']
    if rl is not None:
        rl.close()
    _state['runlog'] = None
    _state['runlog_dir'] = None
    _state['failed_dir'] = None


def _run_log():
    """The current run's RunLog, created lazily; None when disabled. A
    change of directory (enable() with a new path, env flip) starts a new
    run file. A directory whose run log cannot be created (unwritable
    path, full disk) is warned about ONCE and then skipped — telemetry
    must never take down the step it observes."""
    d = obs_dir()
    if d is None:
        return None
    rl = _state['runlog']
    if rl is not None and _state['runlog_dir'] == d:
        return rl
    if _state['failed_dir'] == d:
        return None
    with _state['lock']:
        rl = _state['runlog']
        if rl is None or _state['runlog_dir'] != d:
            _close_runlog_locked()
            # the env pin only applies in env-driven mode: an explicit
            # obs.enable(dir) (tests isolating a run) must not be
            # silently redirected into a leaked shared run file
            pinned = (os.environ.get(ENV_RUN_FILE)
                      if _state['override'] is None else None)
            path = pinned or _runlog.new_run_path(d)
            max_events = None if pinned else DEFAULT_MAX_EVENTS
            raw = os.environ.get(ENV_MAX_EVENTS)
            if raw:
                try:
                    max_events = int(raw) or None
                except ValueError:
                    pass
            try:
                rl = _runlog.RunLog(path, max_events=max_events)
            except Exception as e:
                _state['failed_dir'] = d
                import warnings
                warnings.warn(
                    'obs run log unavailable under %r (%s: %s); telemetry '
                    'file output disabled until the directory changes'
                    % (d, type(e).__name__, e), RuntimeWarning)
                return None
            _state['runlog'] = rl
            _state['runlog_dir'] = d
    return rl


def run_log_path():
    """Path of the current run's JSONL file (None when disabled or when
    nothing has been recorded yet — the file is created lazily). Span
    records go out in batches (runlog.SPAN_BATCH); asking for the path
    writes out the ones held back, so whoever reads the file next reads
    every record so far."""
    rl = _state['runlog']
    if rl is None or _state['runlog_dir'] != obs_dir():
        return None
    rl.flush()
    return rl.path


def _span_stack():
    st = getattr(_local, 'stack', None)
    if st is None:
        st = _local.stack = []
    return st


def current_span_id():
    st = getattr(_local, 'stack', None)
    return st[-1].id if st else None


def _span_hist(name):
    h = _span_hists.get(name)
    if h is None:
        h = REGISTRY.histogram(name + '.seconds')
        _span_hists[name] = h
    return h


def _span_rec(name, span_id, parent, t0, seconds, fields, tids=None):
    """THE span record: what the run log writes and completed_spans()
    returns. `t0`/`t1` are on time.perf_counter's clock (monotonic, the
    one every span is timed on), so records of one process compare;
    `ts` stays the run log's time.monotonic() stamp of the write."""
    rec = {'ts': time.monotonic(), 'kind': 'span', 'name': name,
           'span': span_id, 'parent': parent, 't0': t0, 't1': t0 + seconds,
           'dur_s': seconds, 'fields': fields}
    if tids:
        rec.update(tids)
    return rec


def _put(rec):
    """One completed span record into the buffer and the run log."""
    buf = _state['spans']
    if buf is None:
        with _state['lock']:
            buf = _state['spans']
            if buf is None:
                buf = _state['spans'] = collections.deque(
                    maxlen=SPAN_BUFFER_MAX)
                gc.callbacks.append(_gc_callback)
    if len(buf) == buf.maxlen:
        # the deque pushes its oldest record out; never silently
        with _state['lock']:
            _state['spans_dropped'] += 1
        REGISTRY.counter('obs.spans.dropped').inc()
    buf.append(rec)
    rl = _run_log()
    if rl is not None:
        rl.write(rec, flush=False)      # in batches: runlog.SPAN_BATCH


def _keep(rec):
    if _gc_pending:
        _drain_gc()
    _put(rec)


def _gc_callback(phase, info):
    """gc.callbacks hook, installed with the first kept record: times the
    generation-2 (full) collections, the ones long enough to stall a
    step. Only stashes; see _gc_pending."""
    if info['generation'] != 2:
        return
    if phase == 'start':
        _state['gc_t0'] = time.perf_counter()
        return
    t0, _state['gc_t0'] = _state['gc_t0'], None
    if t0 is not None and enabled():
        _gc_pending.append((t0, time.perf_counter(), info['collected']))


def _drain_gc():
    n = len(_gc_pending)
    pending, _gc_pending[:n] = _gc_pending[:n], []
    for t0, t1, collected in pending:
        _span_hist('host.gc').observe(t1 - t0)
        _put(_span_rec('host.gc', next(_span_ids), None, t0, t1 - t0,
                       {'generation': 2, 'collected': collected}))


def completed_spans():
    """The span records completed while observability was on, oldest
    first (a list of the run log's span dicts; see _span_rec), at most
    SPAN_BUFFER_MAX of them. When the bound has pushed records out, the
    list LEADS with a `spans.dropped` meta record (`fields.dropped`), as
    a compacted run log does, and the `obs.spans.dropped` counter holds
    the count: a reader that needs every record of a window checks the
    head. The buffer outlives disable() and an environment flip; only
    _reset() empties it."""
    if _gc_pending:
        _drain_gc()
    out = list(_state['spans'] or ())
    dropped = _state['spans_dropped']
    if dropped:
        out.insert(0, {'ts': time.monotonic(), 'kind': 'meta',
                       'name': 'spans.dropped', 'span': None,
                       'fields': {'dropped': dropped,
                                  'max_spans': SPAN_BUFFER_MAX}})
    return out


def event(name, **fields):
    """Record a one-shot event (no-op when disabled). Returns the record
    dict when written, else None — handy for tests."""
    rl = _run_log()
    if rl is None:
        return None
    rec = {'ts': time.monotonic(), 'kind': 'event', 'name': name,
           'span': current_span_id(), 'fields': fields}
    tids = trace._ids()
    if tids:
        rec.update(tids)
    rl.write(rec)
    return rec


class Span(object):
    """Context manager created by obs.span(). After __exit__, `.seconds`
    holds the wall time. `.fields` may be mutated inside the span — the
    run-log record is emitted at exit."""
    __slots__ = ('name', 'fields', 'step_num', 'id', 'parent', 't0',
                 'seconds', '_trace', '_tinfo', '_entered')

    def __init__(self, name, step_num=None, **fields):
        self.name = name
        self.fields = fields
        self.step_num = step_num
        self.id = None
        self.parent = None
        self.t0 = None
        self.seconds = None
        self._trace = None
        self._tinfo = None
        self._entered = False

    def __enter__(self):
        st = _span_stack()
        self.parent = st[-1].id if st else None
        self.id = next(_span_ids)
        st.append(self)
        self._entered = True
        # when a distributed trace is active this span joins it (and
        # becomes the parent of anything opened inside) — no-op otherwise
        self._tinfo = trace._span_begin(self.name)
        if enabled():
            self._enter_trace()
        self.t0 = time.perf_counter()
        return self

    def _enter_trace(self):
        # Forward to the XLA trace ONLY via an already-imported jax: the
        # disabled-mode (and jax-less) contract is "no jax import", and
        # sys.modules.get never triggers one.
        jaxmod = sys.modules.get('jax')
        if jaxmod is None:
            return
        try:
            prof = jaxmod.profiler
            if self.step_num is not None:
                self._trace = prof.StepTraceAnnotation(
                    self.name, step_num=int(self.step_num))
            else:
                self._trace = prof.TraceAnnotation(self.name)
            self._trace.__enter__()
        except Exception:
            self._trace = None

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.t0
        if self._trace is not None:
            try:
                self._trace.__exit__(exc_type, exc, tb)
            except Exception:
                pass
            self._trace = None
        st = _span_stack()
        if self._entered and st and st[-1] is self:
            st.pop()
        elif self._entered and self in st:   # mis-nested exit; stay sane
            st.remove(self)
        self._entered = False
        _span_hist(self.name).observe(self.seconds)
        err = '%s: %s' % (exc_type.__name__, exc) if exc_type is not None \
            else None
        tids = None
        if self._tinfo is not None:
            trec = trace._span_end(self._tinfo, fields=dict(self.fields),
                                   error=err)
            self._tinfo = None
            tids = {'trace': trec['trace'], 'tspan': trec['span']}
            if trec.get('parent') is not None:
                tids['tparent'] = trec['parent']
        if enabled():
            fields = dict(self.fields)
            if err is not None:
                fields['error'] = err
            if self.step_num is not None:
                fields.setdefault('step_num', self.step_num)
            _keep(_span_rec(self.name, self.id, self.parent, self.t0,
                            self.seconds, fields, tids))
        return False


def span(name, step_num=None, **fields):
    """Open a nested wall-time span. Always records `<name>.seconds` into
    the registry histogram; when observability is enabled it also appends
    a span record to the run log and brackets the region with
    jax.profiler.TraceAnnotation (StepTraceAnnotation when `step_num` is
    given), so Perfetto shows the same names the run log does."""
    return Span(name, step_num=step_num, **fields)


_NO_SPAN = contextlib.nullcontext()


def span_if(on, name, **fields):
    """obs.span(name, **fields) when `on`, else a shared no-op context
    whose `as` target is None: no Span, no clock, no histogram. For the
    child spans of a hot path that should exist only while observability
    is on — the caller reads enabled() ONCE and passes it down."""
    return Span(name, **fields) if on else _NO_SPAN


def span_record(name, seconds, t0=None, **fields):
    """Record a span POST-HOC: the caller timed the region itself and only
    afterwards knows whether (and under which name) it should be recorded.
    The executor needs this for `executor.compile` — a first jitted call
    is timed, then classified as a real cold compile (span recorded) or a
    persistent-cache hit (an `executor.compile.persistent_hit` event
    instead), so a warm-cache restart shows ZERO compile spans. Feeds the
    same registry histogram, run-log span schema and completed-span
    buffer as span(); no trace annotation (the region is already over).
    `t0` is the region's start on time.perf_counter's clock; left out,
    the region is taken to end now. Returns the record dict when
    observability is on, else None."""
    seconds = float(seconds)
    _span_hist(name).observe(seconds)
    if not enabled():
        return None
    if t0 is None:
        t0 = time.perf_counter() - seconds
    rec = _span_rec(name, next(_span_ids), current_span_id(), t0, seconds,
                    dict(fields), trace._ids())
    _keep(rec)
    return rec
