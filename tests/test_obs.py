"""Runtime telemetry layer (paddle_tpu.obs + tools/obs_report.py).

Covers the observability PR's acceptance criteria:
  - metrics registry semantics: counters/gauges/histograms, labels,
    percentile estimation, thread-safety smoke;
  - span nesting + the JSONL run-log schema round-trip (every record
    validates, ids link children to parents);
  - disabled mode is a TRUE no-op: no output file, and the obs package —
    loaded standalone in a subprocess — never imports jax, enabled or not;
  - an end-to-end fit_a_line-shaped training run whose obs_report shows
    the compile-vs-step split, the compile-cache hit ratio, and the
    anomaly-guard skip count;
  - exe.cache_stats + the compiled_op_table cache header;
  - profiler satellites: stop_profiler warns on an unwritable
    profile_path, cuda_profiler routes output_file, the context manager
    stops on exceptions;
  - obs_report --check exits nonzero on malformed records.
"""
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.obs import report as obs_report_mod
from paddle_tpu.obs import trace

from util import fresh_program

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, 'tools', 'obs_report.py')


@pytest.fixture
def obs_dir(tmp_path):
    """Observability forced ON into a per-test directory; always reset."""
    d = str(tmp_path / 'obs')
    obs.enable(d)
    try:
        yield d
    finally:
        obs._reset()


@pytest.fixture(autouse=True)
def _obs_reset_guard():
    yield
    obs._reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    c = obs.counter('t.reg.counter', site='a')
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # same (name, labels) -> same instrument; different labels -> distinct
    assert obs.counter('t.reg.counter', site='a') is c
    c2 = obs.counter('t.reg.counter', site='b')
    assert c2 is not c
    c2.inc(1.5)
    assert obs.REGISTRY.total('t.reg.counter') == 5.0

    g = obs.gauge('t.reg.gauge')
    assert g.value is None
    g.set(7)
    g.set(4.25)
    assert g.value == 4.25

    h = obs.histogram('t.reg.hist')
    assert h.percentile(50) is None
    for _ in range(95):
        h.observe(0.01)
    for _ in range(5):
        h.observe(2.0)
    assert h.count == 100
    assert h.min == 0.01 and h.max == 2.0
    assert h.percentile(50) <= 0.025          # inside the 10ms bucket
    assert h.percentile(99) > 0.5             # the tail is visible
    snap = h.snapshot()
    assert snap['count'] == 100 and snap['kind'] == 'histogram'
    assert sum(c for _, c in snap['buckets']) == 100

    # windowed percentile: only the observations BETWEEN two snapshots
    # count (serve_bench isolates one benchmark rep's TTFT this way)
    before = h.snapshot()
    for _ in range(10):
        h.observe(1.0)
    after = h.snapshot()
    assert h.percentile_window(before, after, 50) > 0.5   # no old 10ms
    assert h.percentile_window(after, after, 50) is None  # empty window

    # kind conflicts are loud, not silent corruption
    with pytest.raises(TypeError):
        obs.gauge('t.reg.counter', site='a')


def test_registry_thread_safety_smoke():
    c = obs.counter('t.threads.counter')
    h = obs.histogram('t.threads.hist')
    n_threads, per = 8, 500

    def work():
        for i in range(per):
            c.inc()
            h.observe(0.001 * (i % 7))

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per


# ---------------------------------------------------------------------------
# spans + JSONL schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl_schema_roundtrip(obs_dir):
    with obs.span('t.outer', step_num=4, tag='x') as outer:
        obs.event('t.note', detail='inside-outer')
        with obs.span('t.inner') as inner:
            pass
    assert outer.seconds is not None and inner.seconds is not None
    # span wall time landed in the registry histogram
    assert obs.histogram('t.outer.seconds').count >= 1

    path = obs.run_log_path()
    assert path and os.path.exists(path)
    events, errors = obs_report_mod.load_events(path)
    assert errors == [], errors
    for e in events:
        assert obs_report_mod.validate_record(e) is None
    by_name = {e['name']: e for e in events}
    assert by_name['run_start']['kind'] == 'meta'
    out_rec, in_rec = by_name['t.outer'], by_name['t.inner']
    assert out_rec['kind'] == in_rec['kind'] == 'span'
    assert in_rec['parent'] == out_rec['span']      # nesting round-trips
    assert by_name['t.note']['span'] == out_rec['span']
    assert out_rec['dur_s'] >= in_rec['dur_s'] >= 0
    assert out_rec['fields']['tag'] == 'x'
    assert out_rec['fields']['step_num'] == 4


def test_disabled_mode_writes_nothing(tmp_path):
    obs.disable()
    with obs.span('t.disabled'):
        assert obs.event('t.never') is None
    assert obs.run_log_path() is None
    assert list(tmp_path.iterdir()) == []
    # the registry still counts (cache_stats et al. work with obs off)
    assert obs.histogram('t.disabled.seconds').count >= 1


def test_unwritable_obs_dir_warns_once_never_raises(tmp_path):
    """Telemetry must never take down the step it observes: an obs dir
    that cannot be created warns ONCE and disables file output; spans and
    events keep working in-memory."""
    obs.enable(str(tmp_path / 'plainfile' / 'obs'))
    (tmp_path / 'plainfile').write_text('not a directory')
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter('always')
        with obs.span('t.unwritable'):
            assert obs.event('t.swallowed') is None
        obs.event('t.swallowed2')
    warns = [w for w in rec if 'run log unavailable' in str(w.message)]
    assert len(warns) == 1, [str(w.message) for w in rec]
    assert obs.run_log_path() is None
    assert obs.histogram('t.unwritable.seconds').count >= 1


def test_pinned_run_file_env(tmp_path, monkeypatch):
    """PADDLE_TPU_OBS_RUN_FILE pins the exact run-log path (how a driver
    script collects its children's events into one file), and a second
    writer appends without re-stamping run_start."""
    pinned = tmp_path / 'obs' / 'run-pinned.jsonl'
    monkeypatch.setenv('PADDLE_TPU_OBS_DIR', str(tmp_path / 'obs'))
    monkeypatch.setenv('PADDLE_TPU_OBS_RUN_FILE', str(pinned))
    obs._reset()
    obs.event('t.pin.first')
    assert obs.run_log_path() == str(pinned)
    obs._reset()          # simulate a second process opening the same file
    obs.event('t.pin.second')
    events, errors = obs_report_mod.load_events(str(pinned))
    assert errors == []
    names = [e['name'] for e in events]
    assert names.count('run_start') == 1
    assert 't.pin.first' in names and 't.pin.second' in names
    # an explicit enable() (a test isolating its run) must NOT be
    # silently redirected into the leaked pinned file
    obs.enable(str(tmp_path / 'isolated'))
    obs.event('t.pin.isolated')
    assert obs.run_log_path() != str(pinned)
    iso_events, _ = obs_report_mod.load_events(obs.run_log_path())
    assert any(e['name'] == 't.pin.isolated' for e in iso_events)
    pinned_events, _ = obs_report_mod.load_events(str(pinned))
    assert not any(e['name'] == 't.pin.isolated' for e in pinned_events)


def test_standalone_obs_never_imports_jax(tmp_path):
    """The package, loaded WITHOUT paddle_tpu, must not import jax in
    disabled mode (contract) nor even in enabled mode (it only forwards
    to an already-imported jax)."""
    code = '''
import importlib.util, os, sys
pkg = os.path.join(%r, 'paddle_tpu', 'obs')
spec = importlib.util.spec_from_file_location(
    'ptobs', os.path.join(pkg, '__init__.py'),
    submodule_search_locations=[pkg])
obs = importlib.util.module_from_spec(spec)
sys.modules['ptobs'] = obs
spec.loader.exec_module(obs)
os.environ.pop('PADDLE_TPU_OBS_DIR', None)

watch = sys.argv[1]
with obs.span('a', x=1):
    with obs.span('b'):
        obs.event('never')
obs.counter('c').inc()
assert obs.run_log_path() is None
assert os.listdir(watch) == [], os.listdir(watch)   # disabled: no file

obs.enable(os.path.join(watch, 'on'))
with obs.span('c2'):
    obs.event('now-recorded', k=1)
assert obs.run_log_path() is not None

assert 'jax' not in sys.modules, 'obs imported jax'
print('NOOP-OK')
''' % (REPO,)
    r = subprocess.run([sys.executable, '-c', code, str(tmp_path)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert 'NOOP-OK' in r.stdout


# ---------------------------------------------------------------------------
# executor cache stats + compiled_op_table header
# ---------------------------------------------------------------------------

def _fit_a_line_graph():
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1, act=None)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return loss


def _housing_batch(seed=0, n=16, poison=False):
    rng = np.random.RandomState(seed)
    xb = rng.rand(n, 13).astype('float32')
    if poison:
        xb[0, 0] = np.nan
    return xb, rng.rand(n, 1).astype('float32')


def test_cache_stats_and_table_header():
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        assert exe.cache_stats == {'hits': 0, 'misses': 0, 'entries': 0,
                                   'evictions': 0, 'persistent_hits': 0,
                                   'online_compiles': 0,
                                   'aot_hits': 0, 'aot_stale': 0,
                                   'aot_signatures': None,
                                   'compile_cache_dir': None,
                                   'last_compile_seconds': None,
                                   'remat_detected': 0}
        exe.run(startup)
        xb, yb = _housing_batch()
        for _ in range(3):
            exe.run(main, feed={'x': xb, 'y': yb}, fetch_list=[loss])
        st = exe.cache_stats
        assert st['misses'] == 2            # startup + train signatures
        assert st['hits'] == 2
        assert st['entries'] == 2
        assert st['last_compile_seconds'] > 0

        from paddle_tpu.fluid import profiler
        table, rows = profiler.compiled_op_table(
            exe, main, {'x': xb, 'y': yb}, [loss])
        head = table.splitlines()[0]
        # the header names the cached module the table attributed
        assert head.startswith('compiled module: cache hit key=')
        assert 'hits=' in head and 'misses=' in head
        assert exe._last_cache_lookup['key'] in head
        assert 'mul' in rows                 # the table itself still works

        exe.close()
        assert exe.cache_stats['entries'] == 0
        assert exe.cache_stats['evictions'] == 2


# ---------------------------------------------------------------------------
# end-to-end: train, then diagnose from the run log alone
# ---------------------------------------------------------------------------

def test_end_to_end_fit_a_line_report(tmp_path, monkeypatch):
    # the acceptance-criteria path: the ENV VAR switches the layer on
    monkeypatch.setenv('PADDLE_TPU_OBS_DIR', str(tmp_path / 'obs'))
    obs._reset()
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        fluid.anomaly_guard(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for i in range(6):
            xb, yb = _housing_batch(seed=i)
            exe.run(main, feed={'x': xb, 'y': yb}, fetch_list=[loss])
        xb, yb = _housing_batch(seed=99, poison=True)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            exe.run(main, feed={'x': xb, 'y': yb}, fetch_list=[loss])

    path = obs.run_log_path()
    assert path and os.path.exists(path)

    # the CLI (standalone load, no jax) both validates and summarizes
    chk = subprocess.run([sys.executable, CLI, path, '--check'],
                         capture_output=True, text=True, timeout=60)
    assert chk.returncode == 0, chk.stdout + chk.stderr

    rep = subprocess.run([sys.executable, CLI, path],
                         capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    out = rep.stdout
    # compile vs step split
    assert 'carried a compile' in out
    assert 'steady-state step time: p50' in out
    assert 'lowering' in out and 'compile(+first step)' in out
    # cache hit ratio: 8 runs, 2 misses (startup + train)
    assert 'hit ratio' in out
    assert '6 hits / 2 misses' in out
    # anomaly-guard skip is visible to the operator
    assert 'skipped steps: 1' in out


def test_obs_report_check_flags_malformed_records(tmp_path):
    p = tmp_path / 'run-bad.jsonl'
    good = {'ts': 1.0, 'kind': 'event', 'name': 'ok', 'span': None,
            'fields': {}}
    p.write_text(json.dumps(good) + '\n'
                 + 'this is not json\n'
                 + json.dumps({'ts': 'late', 'kind': 'event',
                               'name': 'bad-ts'}) + '\n'
                 + json.dumps({'ts': 2.0, 'kind': 'span',
                               'name': 'no-dur'}) + '\n')
    r = subprocess.run([sys.executable, CLI, str(p), '--check'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 2
    assert 'MALFORMED' in r.stderr
    assert '3 malformed record(s)' in r.stderr

    ok = tmp_path / 'run-ok.jsonl'
    ok.write_text(json.dumps(good) + '\n')
    r2 = subprocess.run([sys.executable, CLI, str(ok), '--check'],
                        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0, r2.stdout + r2.stderr


# ---------------------------------------------------------------------------
# profiler satellites
# ---------------------------------------------------------------------------

def test_stop_profiler_warns_on_unwritable_profile_path(tmp_path, capsys):
    from paddle_tpu.fluid import profiler
    bad = str(tmp_path / 'no' / 'such' / 'dir' / 'profile')
    profiler.start_profiler('All')
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter('always')
        profiler.stop_profiler(profile_path=bad)
    assert any('could not be written' in str(w.message) for w in rec), \
        [str(w.message) for w in rec]
    # the report still reached stdout
    assert 'paddle_tpu profiler' in capsys.readouterr().out


def test_cuda_profiler_routes_output_file(tmp_path):
    from paddle_tpu.fluid import profiler
    out_file = str(tmp_path / 'cuda_profile.txt')
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        out = fluid.layers.mean(fluid.layers.relu(x))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with profiler.cuda_profiler(out_file):
            exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                    fetch_list=[out])
    assert os.path.exists(out_file)
    assert 'paddle_tpu profiler' in open(out_file).read()


def test_profiler_context_stops_on_exception(tmp_path):
    from paddle_tpu.fluid import profiler
    path = str(tmp_path / 'profile')
    with pytest.raises(RuntimeError, match='boom'):
        with profiler.profiler('All', profile_path=path):
            raise RuntimeError('boom')
    # profiler disarmed AND the partial report was written
    assert not profiler._state['active']
    assert os.path.exists(path)


# ---------------------------------------------------------------------------
# distributed tracing (obs.trace)
# ---------------------------------------------------------------------------

def test_trace_context_headers_roundtrip_and_span_linkage():
    ctx = trace.new_trace()
    assert len(ctx.trace_id) == 16
    with trace.activate(ctx, node='router'):
        assert trace.current().trace_id == ctx.trace_id
        hdrs = trace.headers()
        # wire headers reconstruct the SAME trace on the far side
        far = trace.from_headers(json.loads(json.dumps(hdrs)))
        assert far.trace_id == ctx.trace_id
        h = trace.begin('t.tr.parent', node='router')
        child = trace.begin('t.tr.child', ctx=h.ctx, node='h0')
        child.mark('t.tr.milestone', k=1)
        child.end()
        h.end(ok=True)
    assert trace.current() is None          # activation scoped
    # garbage headers NEVER crash the serving path
    assert trace.from_headers(None) is None
    assert trace.from_headers({'nope': 1}) is None
    assert trace.from_headers('junk') is None
    # no active trace -> begin/mark are clean no-ops
    assert trace.begin('t.tr.orphanless') is None
    assert trace.mark('t.tr.nomark') is None


def test_trace_spill_and_collector_stitch_with_orphan(tmp_path):
    """Spans from two 'hosts' (one spilled via the API, one written as a
    dead host's spill file) stitch into ONE timeline with monotonic
    stage boundaries; the dead host's open span is flagged orphan."""
    tdir = str(tmp_path / 'traces')
    ctx = trace.new_trace()
    with trace.activate(ctx, node='router'):
        req = trace.begin('serving.request', node='router', uid=7)
        time.sleep(0.002)
        srv = trace.begin('serving.pod.serve', ctx=req.ctx, node='h0',
                          wire='rpc')
        srv.mark('trace.dispatch')
        time.sleep(0.002)
        srv.mark('trace.first_token', server_ttft_s=0.002)
        time.sleep(0.002)
        srv.end()
        req.end()
    assert trace.spill(tdir) is not None
    # a second host that died mid-request: its spill holds an OPEN span
    dead = {'pid': 99999, 'spans': [
        {'trace': ctx.trace_id, 'span': 'feedfeedfeedfeed',
         'parent': None, 'name': 'serving.pod.serve', 'node': 'h1',
         'pid': 99999, 't0': time.time(), 't1': None,
         'fields': {'wire': 'rpc'}}]}
    with open(os.path.join(tdir, 'spans.p99999.json'), 'w') as f:
        json.dump(dead, f)

    coll = trace.TraceCollector(tdir)
    coll.load()
    assert ctx.trace_id in coll.traces()
    tl = coll.timeline(ctx.trace_id)
    assert tl['trace'] == ctx.trace_id
    assert set(tl['nodes']) == {'router', 'h0', 'h1'}
    assert len(tl['orphans']) == 1
    assert tl['orphans'][0]['node'] == 'h1'
    points = {m['name']: m['t'] for m in tl['milestones']}
    # end-to-end milestones present and MONOTONIC
    for a, b in (('admit', 'serve'), ('serve', 'dispatch'),
                 ('dispatch', 'first_token'), ('first_token', 'done')):
        assert points[a] <= points[b], (a, b, points)
    assert all(st['seconds'] >= 0 for st in tl['stages'])
    stage_names = [st['stage'] for st in tl['stages']]
    assert 'dispatch->first_token' in stage_names


def test_trace_buffer_bounded_counts_drops():
    trace.set_capacity(32)
    try:
        ctx = trace.new_trace()
        before = obs.REGISTRY.total('obs.trace.dropped') or 0
        for i in range(100):
            trace.begin('t.tr.flood', ctx=ctx, i=i).end()
        dropped = (obs.REGISTRY.total('obs.trace.dropped') or 0) - before
        assert dropped >= 100 - 32          # eviction is COUNTED
        assert len(trace._buf) <= 32        # and the buffer stays bounded
    finally:
        trace.set_capacity(trace._DEFAULT_CAPACITY)


def test_slo_report_cli_renders_stitched_timeline(tmp_path):
    """tools/slo_report.py (standalone load, no jax) renders the
    per-stage breakdown + SLO verdicts; tightening a budget flips the
    exit code and names the violated percentile."""
    tdir = str(tmp_path / 'traces')
    ctx = trace.new_trace()
    with trace.activate(ctx, node='router'):
        req = trace.begin('serving.request', node='router')
        srv = trace.begin('serving.pod.serve', ctx=req.ctx, node='h0')
        srv.mark('trace.dispatch')
        time.sleep(0.002)
        srv.mark('trace.first_token')
        srv.end()
        req.end()
    trace.spill(tdir)
    cli = os.path.join(REPO, 'tools', 'slo_report.py')
    budgets = tmp_path / 'budgets.json'
    budgets.write_text(json.dumps({'budgets': {'ttft_p99_s': 5.0}}))
    r = subprocess.run([sys.executable, cli, '--traces', tdir,
                        '--trace', ctx.trace_id,
                        '--budgets', str(budgets)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ctx.trace_id in r.stdout
    assert 'dispatch->first_token' in r.stdout
    assert '-> PASS' in r.stdout
    budgets.write_text(json.dumps({'budgets': {'ttft_p99_s': 1e-9}}))
    r2 = subprocess.run([sys.executable, cli, '--traces', tdir,
                        '--budgets', str(budgets)],
                        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 1
    assert 'ttft_p99_s' in r2.stdout and 'VIOLATION' in r2.stdout
    # usage errors are typed exit 2
    r3 = subprocess.run([sys.executable, cli, '--traces',
                         str(tmp_path / 'nowhere')],
                        capture_output=True, text=True, timeout=60)
    assert r3.returncode == 2


# ---------------------------------------------------------------------------
# SLO budgets (obs.slo)
# ---------------------------------------------------------------------------

def test_slo_budget_pass_fail_missing_typed():
    # a FRESH registry: the global one carries whatever earlier tests
    # in this process observed, and these assertions are exact
    reg = obs.metrics.Registry()
    h = reg.histogram('serving.stream.ttft.seconds')
    for _ in range(20):
        h.observe(0.010)
    budget = obs.slo.SloBudget.from_dict(
        {'_comment': 'ignored',
         'budgets': {'ttft_p99_s': 1.0, 'recovery_s': 5.0}})
    res = budget.evaluate(registry=reg)
    assert res.passed
    assert [m.budget for m in res.missing] == ['recovery_s']
    assert any(l.endswith('PASS') for l in res.lines())

    tight = obs.slo.SloBudget({'ttft_p99_s': 0.001})
    res2 = tight.evaluate(registry=reg)
    assert not res2.passed
    v = res2.violations[0]
    assert isinstance(v, obs.slo.SloViolation)
    assert v.budget == 'ttft_p99_s' and v.measured > v.limit
    assert 'ttft_p99_s' in v.describe()

    # strict mode turns MISSING into failure (CI variant)
    strict = obs.slo.SloBudget({'recovery_s': 5.0})
    assert strict.evaluate(registry=reg).passed
    assert not strict.evaluate(registry=reg,
                               strict_missing=True).passed

    # an unknown key is legal but surfaces LOUDLY as missing (a budget
    # for a future metric must not silently pass)
    future = obs.slo.SloBudget(
        {'not_yet_a_budget': 1.0}).evaluate(registry=reg)
    assert [m.budget for m in future.missing] == ['not_yet_a_budget']


def test_slo_measures_recovery_and_dropped_from_events():
    reg = obs.metrics.Registry()           # isolated from other tests
    ev = [{'name': 'serving.replica.reshard',
           'fields': {'heal_s': 2.5}},
          {'name': 'bench.metric',
           'fields': {'metric': 'serve.decode_failover.resume_s',
                      'value': 0.75}}]
    m = obs.slo.measure(registry=reg, events=ev)
    assert m['recovery_s'] == 2.5           # slowest heal wins
    # dropped is only reported once serving counters EXIST (a vacuous 0
    # from an idle registry must not satisfy the budget)
    assert 'dropped' not in obs.slo.measure(registry=reg)
    reg.counter('serving.shed').inc(0)
    m2 = obs.slo.measure(registry=reg)
    assert m2.get('dropped') == 0


# ---------------------------------------------------------------------------
# Prometheus exposition (obs.metrics.render_prom)
# ---------------------------------------------------------------------------

def test_render_prom_exposition_format():
    obs.counter('t.prom.requests', wire='rpc').inc(3)
    obs.counter('t.prom.requests', wire='file').inc(1)
    obs.gauge('t.prom.lag').set(1.5)
    obs.gauge('t.prom.unset')               # never set: skipped
    h = obs.histogram('t.prom.lat')
    h.observe(0.005)
    h.observe(0.5)
    text = obs.metrics.render_prom()
    assert text.endswith('\n')
    assert '# TYPE t_prom_requests_total counter' in text
    assert 't_prom_requests_total{wire="rpc"} 3' in text
    assert 't_prom_lag 1.5' in text
    assert 't_prom_unset' not in text
    # histogram buckets are CUMULATIVE and end at +Inf == count
    assert 't_prom_lat_bucket{le="+Inf"} 2' in text
    assert 't_prom_lat_count 2' in text
    lines = [l for l in text.splitlines()
             if l.startswith('t_prom_lat_bucket')]
    counts = [float(l.rsplit(' ', 1)[1]) for l in lines]
    assert counts == sorted(counts)         # cumulative = monotonic


# ---------------------------------------------------------------------------
# run-log ring buffer
# ---------------------------------------------------------------------------

def test_runlog_ring_buffer_bounds_file_and_counts_drops(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_OBS_DIR', str(tmp_path / 'obs'))
    monkeypatch.setenv(obs.ENV_MAX_EVENTS, '10')
    obs._reset()
    before = obs.REGISTRY.total('obs.runlog.dropped') or 0
    for i in range(60):
        obs.event('t.ring.e%d' % i, i=i)
    path = obs.run_log_path()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    # bounded: max_events + compaction slack + meta head, nowhere near
    # the 60 writes (compaction fires past max_events + max(32, 10%))
    assert len(lines) <= 45, len(lines)
    names = [l['name'] for l in lines]
    assert names[0] == 'run_start'           # head preserved
    assert 'runlog.dropped' in names         # eviction is VISIBLE
    assert 't.ring.e59' in names             # newest survive
    assert 't.ring.e0' not in names          # oldest evicted
    dropped = (obs.REGISTRY.total('obs.runlog.dropped') or 0) - before
    assert dropped >= 20
    # the surviving tail still validates against the schema
    events, errors = obs_report_mod.load_events(path)
    assert errors == [], errors


# ---------------------------------------------------------------------------
# completed spans kept in memory, and the child spans of executor.step
# ---------------------------------------------------------------------------

def test_completed_spans_keep_clock_parents_and_fields(obs_dir):
    with obs.span('t.keep.outer', tag='x') as outer:
        with obs.span('t.keep.inner'):
            pass
        post = obs.span_record('t.keep.posthoc', 0.25, t0=outer.t0, n=3)
    kept = {r['name']: r for r in obs.completed_spans()}
    out, inn, ph = (kept['t.keep.' + k] for k in ('outer', 'inner',
                                                  'posthoc'))
    assert inn['parent'] == out['span'] == ph['parent']
    assert out['parent'] is None and out['fields'] == {'tag': 'x'}
    assert out['t0'] <= inn['t0'] <= inn['t1'] <= out['t1']
    assert out['dur_s'] == pytest.approx(out['t1'] - out['t0'])
    assert ph is post and ph['fields'] == {'n': 3}
    assert (ph['t0'], ph['t1']) == (outer.t0, outer.t0 + 0.25)
    # ONE record: the run log holds what the buffer holds
    events, errors = obs_report_mod.load_events(obs.run_log_path())
    assert errors == []
    logged = {e['name']: e for e in events if e['kind'] == 'span'}
    assert logged['t.keep.inner'] == json.loads(json.dumps(inn))
    # the buffer outlives the switch; only _reset() empties it
    obs.disable()
    assert len(obs.completed_spans()) == 3
    obs._reset()
    assert obs.completed_spans() == []


def test_completed_spans_bound_themselves_and_count_drops(obs_dir,
                                                          monkeypatch):
    monkeypatch.setattr(obs, 'SPAN_BUFFER_MAX', 8)
    before = obs.REGISTRY.total('obs.spans.dropped') or 0
    for i in range(20):
        with obs.span('t.flood', i=i):
            pass
    kept = obs.completed_spans()
    assert kept[0]['kind'] == 'meta' and kept[0]['name'] == 'spans.dropped'
    assert kept[0]['fields']['dropped'] == 12
    assert [r['fields']['i'] for r in kept[1:]] == list(range(12, 20))
    assert (obs.REGISTRY.total('obs.spans.dropped') or 0) - before == 12


def test_disabled_mode_buffers_nothing_and_installs_no_gc_hook():
    import gc
    obs.disable()
    with obs.span('t.off'):
        assert obs.span_record('t.off.posthoc', 0.1) is None
    with obs.span_if(False, 't.off.child') as sp:
        assert sp is None
    gc.collect()
    assert obs.completed_spans() == []
    assert obs._state['spans'] is None          # nothing was allocated
    assert obs._gc_callback not in gc.callbacks
    assert obs.histogram('t.off.child.seconds').count == 0


def test_full_garbage_collections_become_host_gc_spans(obs_dir):
    import gc
    with obs.span('t.gc.before'):
        pass                       # the first kept record installs the hook
    assert obs._gc_callback in gc.callbacks
    t0 = time.perf_counter()
    gc.collect(1)                  # a young collection is not recorded
    gc.collect()
    t1 = time.perf_counter()
    pauses = [r for r in obs.completed_spans() if r['name'] == 'host.gc']
    assert len(pauses) == 1
    assert pauses[0]['fields']['generation'] == 2
    assert 'collected' in pauses[0]['fields']
    assert t0 <= pauses[0]['t0'] <= pauses[0]['t1'] <= t1
    obs._reset()
    assert obs._gc_callback not in gc.callbacks


def _span_counts():
    """{span name: completed spans so far}, from the registry's
    `<name>.seconds` histograms, which every span feeds on or off."""
    return {s['name'][:-len('.seconds')]: s['count']
            for s in obs.REGISTRY.snapshot()
            if s['kind'] == 'histogram' and s['name'].endswith('.seconds')}


def _opened(before):
    return {k: v - before.get(k, 0) for k, v in _span_counts().items()
            if v - before.get(k, 0)}


STEP_CHILDREN = {'executor.prepare': 'executor.step',
                 'executor.placement': 'executor.prepare',
                 'executor.feed': 'executor.prepare',
                 'executor.rng': 'executor.step',
                 'executor.dispatch': 'executor.step',
                 'executor.fetch': 'executor.step',
                 'executor.feed_wait': 'executor.fetch'}


@pytest.mark.parametrize('on', [False, True], ids=['obs_off', 'obs_on'])
def test_executor_step_child_spans_exist_only_with_observability_on(
        on, tmp_path):
    """Off, Executor.run opens exactly the spans it opened before this
    split (executor.step and executor.fetch, executor.lowering and
    executor.compile on a first call) and keeps nothing. On, a first step
    has executor.first_call with its .trace and .backend parts, and a
    steady step exactly the child spans of docs/observability.md's
    table, nested under executor.step."""
    if on:
        obs.enable(str(tmp_path / 'obs'))
    else:
        obs.disable()
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xb, yb = _housing_batch()
        before = _span_counts()
        exe.run(main, feed={'x': xb, 'y': yb}, fetch_list=[loss])
        first = _opened(before)
        before = _span_counts()
        exe.run(main, feed={'x': xb, 'y': yb}, fetch_list=[loss])
        steady = _opened(before)
        exe.close()
    always = {'executor.step': 1, 'executor.fetch': 1}
    first_call = {'executor.lowering': 1, 'executor.compile': 1}
    if not on:
        assert steady == always
        assert first == dict(always, **first_call)
        assert obs.completed_spans() == []
        return
    assert steady == dict(always, **{k: 1 for k in STEP_CHILDREN})
    want = dict(steady, **first_call)
    del want['executor.dispatch']       # the first call is not a dispatch
    want.update({'executor.first_call': 1, 'executor.first_call.trace': 1,
                 'executor.first_call.backend': 1})
    assert first == want
    spans = obs.completed_spans()
    by_id = {r['span']: r for r in spans}
    last = [r for r in spans if r['name'] == 'executor.step'][-1]
    under = [r for r in spans if r['t0'] >= last['t0'] and r is not last
             and r['name'].startswith('executor.')]
    assert sorted(r['name'] for r in under) == sorted(STEP_CHILDREN)
    for r in under:
        assert by_id[r['parent']]['name'] == STEP_CHILDREN[r['name']]
        assert last['t0'] <= r['t0'] <= r['t1'] <= last['t1']
    kept = {r['name']: r for r in spans}
    assert kept['executor.prepare']['fields'] == {'cache': 'hit'}
    assert kept['executor.placement']['fields'] == {'mesh': False}
    assert kept['executor.feed']['fields']['bytes'] == xb.nbytes + yb.nbytes
    assert kept['executor.feed_wait']['fields'] == {
        'bytes': xb.nbytes + yb.nbytes, 'ready': True}
    call, trace_, backend = (kept['executor.first_call' + k]
                             for k in ('', '.trace', '.backend'))
    assert by_id[call['parent']]['name'] == 'executor.step'
    assert trace_['parent'] == backend['parent'] == call['span']
    assert call['fields']['outcome'] == 'compile'
    assert call['fields']['key'] == trace_['fields']['key'] == \
        by_id[call['parent']]['fields']['key']
    assert backend['fields']['cached'] is False     # no persistent cache
    assert trace_['dur_s'] > 0 and backend['dur_s'] > 0
    assert trace_['dur_s'] + backend['dur_s'] < call['dur_s']
    assert call['t0'] <= trace_['t0'] and backend['t1'] <= call['t1']


def _fed_step(**run_kwargs):
    """A warm fit_a_line step under the caller's observability, then one
    more: ({span name: count} the second opened, its records)."""
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xb, yb = _housing_batch()
        feed = {'x': xb, 'y': yb}
        exe.run(main, feed=feed, fetch_list=[loss])
        before, kept = _span_counts(), len(obs.completed_spans())
        out = exe.run(main, feed=feed, fetch_list=[loss], **run_kwargs)
        np.asarray(out[0])
        opened = _opened(before)
        exe.close()
    return opened, obs.completed_spans()[kept:]


class _Fed(object):
    """Stands where a fed jax.Array stands and counts what is asked of
    it: lands after `polls` calls of is_ready."""

    def __init__(self, polls):
        self.polls, self.asked, self.blocked = polls, 0, 0

    def is_ready(self):
        self.asked += 1
        return self.asked > self.polls

    def block_until_ready(self):
        self.blocked += 1
        return self


@pytest.mark.parametrize('landed', [True, False])
def test_feed_wait_blocks_on_every_fed_array_and_says_if_it_waited(
        landed, obs_dir, monkeypatch):
    """`ready` is every fed array's is_ready() on entry; each is then
    blocked on, a SeqValue's planes among them, and a host-staged numpy
    value is left alone."""
    import jax
    from paddle_tpu.fluid.lowering import SeqValue
    dense, data, lengths = _Fed(0), _Fed(0), _Fed(0 if landed else 1)
    exe = fluid.Executor(fluid.CPUPlace())
    with monkeypatch.context() as m:
        m.setattr(jax, 'Array', _Fed)
        exe._await_feed({'x': dense, 'seq': SeqValue(data, lengths),
                         'host': np.zeros((4, 2), 'float32')}, 96)
    rec, = [r for r in obs.completed_spans()
            if r['name'] == 'executor.feed_wait']
    assert rec['fields'] == {'bytes': 96, 'ready': landed}
    assert [a.blocked for a in (dense, data, lengths)] == [1, 1, 1]
    exe.close()


def test_feed_wait_of_a_feedless_step_waits_on_nothing(obs_dir):
    """The startup Program is fed nothing: the span is opened with zero
    bytes, ready, and asks nothing of any array."""
    with fresh_program() as (main, startup):
        _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.close()
    by_id = {r['span']: r for r in obs.completed_spans()}
    rec, = [r for r in by_id.values() if r['name'] == 'executor.feed_wait']
    assert rec['fields'] == {'bytes': 0, 'ready': True}
    assert by_id[rec['parent']]['name'] == 'executor.fetch'
    assert rec['dur_s'] < 1e-3


def test_feed_wait_is_not_opened_under_sync_async(obs_dir):
    """It would serialize what the mode exists to overlap:
    executor.host_stall stays that mode's span."""
    opened, recs = _fed_step(sync='async')
    assert 'executor.feed_wait' not in opened
    assert opened['executor.fetch'] == opened['executor.host_stall'] == 1
    assert 'executor.feed_wait' not in {r['name'] for r in recs}
    opened, _ = _fed_step(sync='block')
    assert opened['executor.feed_wait'] == 1


def test_run_bundle_opens_feed_wait_once_a_bundle(obs_dir):
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeds = [dict(zip('xy', _housing_batch(seed=i))) for i in range(3)]
        exe.run_bundle(main, feeds=feeds, fetch_list=[loss])
        before, kept = _span_counts(), len(obs.completed_spans())
        exe.run_bundle(main, feeds=feeds, fetch_list=[loss])
        opened = _opened(before)
        recs = obs.completed_spans()[kept:]
        before = _span_counts()
        exe.run_bundle(main, feeds=feeds, fetch_list=[loss], sync='async')
        assert 'executor.feed_wait' not in _opened(before)
        exe.close()
    assert opened['executor.bundle'] == opened['executor.fetch'] \
        == opened['executor.feed_wait'] == 1
    by_id = {r['span']: r for r in recs}
    wait, = [r for r in recs if r['name'] == 'executor.feed_wait']
    assert by_id[wait['parent']]['name'] == 'executor.fetch'
    # the stacked feed of the whole bundle, not step 0's
    assert wait['fields']['bytes'] == sum(
        v.nbytes for f in feeds for v in f.values())
    assert isinstance(wait['fields']['ready'], bool)


def test_await_feed_is_never_called_with_observability_off(monkeypatch):
    """Off, no fed array is touched after dispatch: run() and run_bundle()
    do not reach the helper."""
    calls = []
    monkeypatch.setattr(fluid.Executor, '_await_feed',
                        lambda self, *a: calls.append(a))
    obs.disable()
    opened, _ = _fed_step()
    assert opened == {'executor.step': 1, 'executor.fetch': 1}
    with fresh_program() as (main, startup):
        loss = _fit_a_line_graph()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeds = [dict(zip('xy', _housing_batch(seed=i))) for i in range(2)]
        exe.run_bundle(main, feeds=feeds, fetch_list=[loss])
        exe.close()
    assert calls == []


def test_feed_wait_leaves_the_host_dispatch_identity_alone(obs_dir):
    """`host_dispatch` is executor.step minus executor.fetch, and the
    step's parts outside the fetch (prepare self, placement, feed, rng,
    dispatch, step self) add up to it: the wait is inside the fetch, so
    it is in neither, and with the fetch's self time it is the fetch."""
    _, recs = _fed_step()
    by_name = {r['name']: r for r in recs}
    below = {}
    for r in recs:
        below.setdefault(r['parent'], []).append(r)

    def own(name):
        r = by_name[name]
        return r['dur_s'] - sum(c['dur_s'] for c in below.get(r['span'], ()))

    step, fetch, wait = (by_name['executor.' + k]
                         for k in ('step', 'fetch', 'feed_wait'))
    assert wait['parent'] == fetch['span'] and fetch['parent'] == step['span']
    outside = sum(own('executor.' + k) for k in (
        'prepare', 'placement', 'feed', 'rng', 'dispatch', 'step'))
    assert outside == pytest.approx(step['dur_s'] - fetch['dur_s'], rel=1e-9)
    assert wait['dur_s'] + own('executor.fetch') == pytest.approx(
        fetch['dur_s'], rel=1e-9)
    assert fetch['t0'] <= wait['t0'] <= wait['t1'] <= fetch['t1']


@pytest.mark.parametrize('large', [True, False], ids=['large', 'small'])
def test_feed_span_and_counter_say_what_went_as_a_view(large, obs_dir,
                                                       monkeypatch):
    """`executor.feed`'s `reshaped` is the bytes of THIS step that
    `Executor._put` handed over as views of its rows, and the process-wide
    counter `executor.feed.reshaped_bytes` rises by them; `bytes`, and
    the `bytes` of `executor.feed_wait` (which now waits on the reshaped
    array), are what they were. A small feed leaves both at rest."""
    from paddle_tpu.fluid import executor as executor_mod
    monkeypatch.setattr(executor_mod, '_VIEW_FEED_BYTES',
                        1024 if large else 1 << 40)
    monkeypatch.setattr(executor_mod, '_VIEW_PLATFORMS', ('tpu', 'cpu'))
    rs = np.random.RandomState(0)
    img = rs.rand(8, 3, 32, 16).astype('float32')
    yb = rs.rand(8, 1).astype('float32')
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='img', shape=[3, 32, 16],
                              dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1, act=None)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'img': img, 'y': yb}
        exe.run(main, feed=feed, fetch_list=[loss])
        counted = obs.REGISTRY.total('executor.feed.reshaped_bytes') or 0
        kept = len(obs.completed_spans())
        exe.run(main, feed=feed, fetch_list=[loss])
        recs = {r['name']: r for r in obs.completed_spans()[kept:]}
        exe.close()
    want = img.nbytes if large else 0
    assert recs['executor.feed']['fields'] == {
        'bytes': img.nbytes + yb.nbytes, 'reshaped': want}
    assert recs['executor.feed_wait']['fields']['bytes'] == \
        img.nbytes + yb.nbytes
    assert (obs.REGISTRY.total('executor.feed.reshaped_bytes') or 0) \
        - counted == want


def test_span_records_reach_the_run_log_in_batches(obs_dir):
    """A span record waits in the run log for its batch (a write and a
    flush for each cost a training step 0.7 ms on the chip's host, PR 23);
    an event, the SPAN_BATCH-th span, run_log_path() and the closing of
    the log each write out everything held back, in order."""
    from paddle_tpu.obs import runlog

    def on_disk():
        with open(path) as f:
            return [json.loads(line)['name'] for line in f]

    obs.event('t.batch.first')                 # creates the file
    path = obs.run_log_path()
    assert on_disk() == ['run_start', 't.batch.first']
    for i in range(3):
        with obs.span('t.batch.span'):
            pass
    assert on_disk() == ['run_start', 't.batch.first']      # held back
    obs.event('t.batch.event')      # an event goes out at once, in order
    assert on_disk()[2:] == ['t.batch.span'] * 3 + ['t.batch.event']
    for i in range(runlog.SPAN_BATCH + 1):
        with obs.span('t.batch.more'):
            pass
    assert on_disk().count('t.batch.more') == runlog.SPAN_BATCH
    assert obs.run_log_path() == path                       # flushes
    assert on_disk().count('t.batch.more') == runlog.SPAN_BATCH + 1
    with obs.span('t.batch.last'):
        pass
    obs._reset()                                            # closes
    assert on_disk()[-1] == 't.batch.last'
    events, errors = obs_report_mod.load_events(path)
    assert errors == []
