"""Pipelined training hot loop (ISSUE 4): K-step bundling via
Executor.run_bundle / Trainer(bundle_steps=K), the async fetch window
(run(sync='async') FetchHandles + Trainer in-flight window), and the
persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR).

Equivalence contract proved here:
  - K=1 vs K=4 bundles reach BIT-IDENTICAL parameters (the scan body
    compiles the same regardless of trip count);
  - per-step RNG (dropout masks) is bit-identical between K unbundled
    run() calls and one K-bundle (same seed integers, same keys);
  - the anomaly guard skips/rolls back PER INNER STEP inside a bundle
    exactly as it does unbundled, and escalation still fires;
  - a second process over the same JAX_COMPILATION_CACHE_DIR records
    ZERO executor.compile spans for already-cached keys.
"""
import gc
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid.executor import FetchHandle
from paddle_tpu.obs import report as obs_report
from paddle_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.bundle


@pytest.fixture
def obs_events(tmp_path):
    obs.enable(str(tmp_path / 'obs'))

    def read(name=None):
        path = obs.run_log_path()
        if path is None:
            return []
        events, errors = obs_report.load_events(path)
        assert errors == [], errors
        return [e for e in events if name is None or e['name'] == name]

    try:
        yield read
    finally:
        obs._reset()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _regression(lr=0.1, guard=False, max_skips=None):
    """fit_a_line-shaped net: fc -> square_error -> mean -> SGD. Built
    under a fresh unique_name guard so two builds name vars identically
    (the cross-executor equivalence comparisons key on names)."""
    from paddle_tpu.fluid import unique_name
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard():
        with fluid.program_guard(prog, start):
            x = fluid.layers.data(name='x', shape=[13], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            pred = fluid.layers.fc(input=x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    if guard:
        fluid.anomaly_guard(prog, max_consecutive_skips=max_skips)
    w_names = sorted(v.name for v in prog.list_vars()
                     if v.persistable and 'fc' in v.name)
    return prog, start, loss, w_names


def _feeds(n, seed=0, batch=16):
    rng = np.random.RandomState(seed)
    return [{'x': rng.rand(batch, 13).astype('float32'),
             'y': rng.rand(batch, 1).astype('float32')} for _ in range(n)]


def _train_bundled(feeds, K, guard=False, max_skips=None):
    """Fresh program/executor/scope; run all feeds in K-bundles. Returns
    (per-step losses, {w_name: value}, exe)."""
    prog, start, loss, w_names = _regression(guard=guard,
                                             max_skips=max_skips)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(start)
        for i in range(0, len(feeds), K):
            out = exe.run_bundle(prog, feeds=feeds[i:i + K],
                                 fetch_list=[loss])
            losses.extend(np.asarray(out[0]).reshape(-1).tolist())
        ws = {n: np.asarray(scope.vars[n]).copy() for n in w_names}
    return losses, ws, exe


# ---------------------------------------------------------------------------
# bundled-vs-unbundled equivalence
# ---------------------------------------------------------------------------

def test_bundle_k1_vs_k4_params_bit_identical():
    """The acceptance equivalence: identical parameters after N steps
    with K=1 vs K=4 — bit-exact, because both are the SAME scan body."""
    feeds = _feeds(8)
    l1, w1, _ = _train_bundled(feeds, 1)
    l4, w4, _ = _train_bundled(feeds, 4)
    assert l1 == l4
    assert sorted(w1) == sorted(w4)
    for n in w1:
        np.testing.assert_array_equal(w1[n], w4[n])


def test_bundle_matches_unbundled_run_trajectory():
    """One bundle vs K run() calls: same data, same seeds -> the same
    training trajectory. run() and the scan are DIFFERENT XLA modules, so
    individual reductions may round one ulp apart (docs/perf.md) — the
    assertion is allclose-tight, with the bit-exact guarantee covered by
    the K-vs-K test above."""
    feeds = _feeds(8)
    prog, start, loss, w_names = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        lu = [float(np.asarray(exe.run(prog, feed=f, fetch_list=[loss])[0])
                    .reshape(-1)[0]) for f in feeds]
        wu = {n: np.asarray(scope.vars[n]).copy() for n in w_names}
    lb, wb, _ = _train_bundled(feeds, 4)
    np.testing.assert_allclose(lu, lb, rtol=1e-6, atol=1e-7)
    for n in w_names:
        np.testing.assert_allclose(wu[n], wb[n], rtol=1e-5, atol=1e-7)


def test_bundle_fetches_stacked_per_step(obs_events):
    feeds = _feeds(6)
    prog, start, loss, _ = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        out = exe.run_bundle(prog, feeds=feeds, fetch_list=[loss], steps=6)
    assert len(out) == 1
    assert np.asarray(out[0]).shape[0] == 6     # stacked leading K axis
    bundles = obs_events('executor.bundle')
    assert len(bundles) == 1
    assert bundles[0]['fields']['steps'] == 6
    assert obs.REGISTRY.total('executor.bundle.steps') >= 6


def test_bundle_validation_errors():
    feeds = _feeds(4)
    prog, start, loss, _ = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        with pytest.raises(ValueError, match='non-empty'):
            exe.run_bundle(prog, feeds=[], fetch_list=[loss])
        with pytest.raises(ValueError, match='steps=3'):
            exe.run_bundle(prog, feeds=feeds, fetch_list=[loss], steps=3)
        bad_shape = dict(feeds[1], x=feeds[1]['x'][:5])
        with pytest.raises(ValueError, match='shape'):
            exe.run_bundle(prog, feeds=[feeds[0], bad_shape],
                           fetch_list=[loss])
        bad_names = {'x': feeds[1]['x']}
        with pytest.raises(ValueError, match='names'):
            exe.run_bundle(prog, feeds=[feeds[0], bad_names],
                           fetch_list=[loss])
        with pytest.raises(ValueError, match="sync"):
            exe.run_bundle(prog, feeds=feeds, fetch_list=[loss],
                           sync='nope')


# ---------------------------------------------------------------------------
# per-step RNG parity
# ---------------------------------------------------------------------------

def test_bundle_per_step_rng_parity():
    """Dropout masks at bundled inner step j equal unbundled run j's,
    bit-exactly: the scan body derives its key from the same seed integer
    run() hands jax.random.key. Dropout is applied DIRECTLY to the fed
    tensor so the comparison sees pure mask bits, no upstream matmul."""
    def build():
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            x = fluid.layers.data(name='x', shape=[32], dtype='float32')
            out = fluid.layers.dropout(x, dropout_prob=0.5)
        return prog, start, out

    feeds = [{'x': np.ones((4, 32), 'float32')} for _ in range(4)]

    prog, start, out = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        masks_u = [np.asarray(exe.run(prog, feed=f, fetch_list=[out])[0])
                   for f in feeds]

    prog, start, out = build()
    exe2 = fluid.Executor(fluid.CPUPlace())
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(start)
        stacked = exe2.run_bundle(prog, feeds=feeds, fetch_list=[out])
    masks_b = np.asarray(stacked[0])
    assert masks_b.shape[0] == 4
    dropped = 0
    for j in range(4):
        np.testing.assert_array_equal(masks_u[j], masks_b[j])
        dropped += int((masks_b[j] == 0).sum())
    assert dropped > 0                       # dropout actually dropped
    assert any(not np.array_equal(masks_b[0], masks_b[j])
               for j in range(1, 4))         # and per-step masks DIFFER


# ---------------------------------------------------------------------------
# anomaly guard inside a bundle
# ---------------------------------------------------------------------------

def test_bundle_anomaly_guard_per_step_skip(obs_events):
    feeds = _feeds(4)
    inj = FaultInjector(seed=3)
    feeds[1] = dict(feeds[1], x=inj.poison_nan(feeds[1]['x'], rate=0.5))

    prog, start, loss, w_names = _regression(guard=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter('always')
            exe.run_bundle(prog, feeds=feeds, fetch_list=[loss])
    # exactly ONE inner step skipped, observed per step on the host
    assert exe.skipped_steps == 1
    assert any('anomaly guard' in str(w.message) for w in rec)
    skips = obs_events('anomaly.skip')
    assert len(skips) == 1
    # the run id in the event names the INNER step (2nd of the bundle:
    # startup was run 1, so the poisoned step is run 3)
    assert skips[0]['fields']['run'] == 3
    # a healthy step after the poisoned one cleared the streak
    assert exe._consecutive_skips == 0
    assert bool(exe.last_step_health['healthy'])


def test_bundle_anomaly_guard_rollback_parity():
    """An all-poisoned bundle leaves params BIT-IDENTICAL to before it —
    the in-graph where-select rollback works per inner step under scan
    exactly as it does unbundled."""
    feeds = _feeds(4)
    inj = FaultInjector(seed=5)
    feeds = [dict(f, x=inj.poison_nan(f['x'], rate=1.0)) for f in feeds]

    prog, start, loss, w_names = _regression(guard=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        good = _feeds(1, seed=9)[0]
        exe.run(prog, feed=good, fetch_list=[loss])   # one real step
        before = {n: np.asarray(scope.vars[n]).copy() for n in w_names}
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            exe.run_bundle(prog, feeds=feeds, fetch_list=[loss])
        after = {n: np.asarray(scope.vars[n]) for n in w_names}
    assert exe.skipped_steps == 4
    for n in w_names:
        np.testing.assert_array_equal(before[n], after[n])


def test_bundle_anomaly_guard_escalation():
    """max_consecutive_skips fires from WITHIN a bundle's host-side
    per-step observation (divergence does not hide behind bundling)."""
    feeds = _feeds(6)
    inj = FaultInjector(seed=7)
    feeds = [dict(f, x=inj.poison_nan(f['x'], rate=1.0)) for f in feeds]
    prog, start, loss, _ = _regression(guard=True, max_skips=3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')
            with pytest.raises(FloatingPointError, match='consecutive'):
                exe.run_bundle(prog, feeds=feeds, fetch_list=[loss])
    assert exe.skipped_steps == 3   # raised at the limit, not after K


# ---------------------------------------------------------------------------
# async fetch window
# ---------------------------------------------------------------------------

def test_async_run_returns_lazy_handles(obs_events):
    feeds = _feeds(3)
    prog, start, loss, _ = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        blocking = float(np.asarray(
            exe.run(prog, feed=feeds[0], fetch_list=[loss])[0])
            .reshape(-1)[0])

    exe2 = fluid.Executor(fluid.CPUPlace())
    scope2 = fluid.Scope()
    prog, start, loss, _ = _regression()
    with fluid.scope_guard(scope2):
        exe2.run(start)
        h, = exe2.run(prog, feed=feeds[0], fetch_list=[loss],
                      sync='async')
    assert isinstance(h, FetchHandle)
    assert float(h) == blocking            # sync-on-demand, same value
    assert h.ready
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h))  # cached
    assert obs.histogram('executor.host_stall.seconds').count >= 1
    gc.collect()
    assert obs.gauge('executor.inflight').value == 0


def test_async_handle_defers_and_rereaises_errors():
    """A failure materializing the value surfaces at FIRST READ and again
    at every later read; the inflight slot is released exactly once."""
    calls = []

    def boom():
        calls.append(1)
        raise RuntimeError('device exploded')

    g = obs.gauge('executor.inflight')
    base = g.value or 0
    h = FetchHandle(np.zeros(3), boom)
    assert (g.value or 0) == base + 1
    with pytest.raises(RuntimeError, match='device exploded'):
        h.block()
    with pytest.raises(RuntimeError, match='device exploded'):
        np.asarray(h)
    assert calls == [1]                    # materialized once, cached
    assert (g.value or 0) == base


def test_async_unread_handle_releases_inflight_slot():
    h = FetchHandle(np.arange(4.0))
    g = obs.gauge('executor.inflight')
    assert (g.value or 0) >= 1
    del h
    gc.collect()
    assert (g.value or 0) == 0


def test_float_on_multi_element_handle_raises():
    h = FetchHandle(np.arange(4.0))
    with pytest.raises(TypeError, match='one-element'):
        float(h)
    h.block()


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------

def _trainer_pieces():
    def train_func():
        x = fluid.layers.data(name='x', shape=[13], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))

    def opt_func():
        return fluid.optimizer.SGD(learning_rate=0.1)

    rows = _feeds(10, seed=1, batch=4)

    def reader():
        for f in rows:
            yield [(f['x'][i], f['y'][i]) for i in range(len(f['x']))]

    return train_func, opt_func, reader


def _run_trainer(collect, **kw):
    train_func, opt_func, reader = _trainer_pieces()
    t = fluid.Trainer(train_func, opt_func, place=fluid.CPUPlace(), **kw)
    t.train(num_epochs=1, event_handler=collect, reader=reader,
            feed_order=['x', 'y'])
    w = {n: np.asarray(t.scope.vars[n]).copy() for n in t.scope.vars
         if n.endswith('.w_0')}
    return w


def test_trainer_bundled_event_stream_and_parity():
    events_plain, events_bundled = [], []

    def mk(sink):
        def handler(e):
            if isinstance(e, fluid.EndStepEvent):
                sink.append((e.step,
                             float(np.asarray(e.metrics[0]).reshape(-1)[0])))
        return handler

    w_plain = _run_trainer(mk(events_plain))
    # K=4 over 10 steps: two full bundles + one partial (10 = 4+4+2)
    w_bundled = _run_trainer(mk(events_bundled), bundle_steps=4)
    assert [s for s, _ in events_bundled] == [s for s, _ in events_plain]
    np.testing.assert_allclose([v for _, v in events_plain],
                               [v for _, v in events_bundled],
                               rtol=1e-6, atol=1e-7)
    for n in w_plain:
        np.testing.assert_allclose(w_plain[n], w_bundled[n],
                                   rtol=1e-5, atol=1e-7)


def test_trainer_async_window_syncs_at_handler_and_drains():
    losses = []

    def handler(e):
        if isinstance(e, fluid.EndStepEvent) and e.metrics:
            # reading the metric here IS the sync boundary
            losses.append(float(np.asarray(e.metrics[0]).reshape(-1)[0]))

    plain = []

    def phandler(e):
        if isinstance(e, fluid.EndStepEvent) and e.metrics:
            plain.append(float(np.asarray(e.metrics[0]).reshape(-1)[0]))

    _run_trainer(phandler)
    _run_trainer(handler, sync='async', async_window=2)
    np.testing.assert_allclose(plain, losses, rtol=1e-6, atol=0)
    gc.collect()
    assert obs.gauge('executor.inflight').value == 0


def test_trainer_async_window_handler_exception_mid_window():
    """A handler blowing up at step 3 (two steps still in flight) must
    propagate, and every in-flight handle must release its slot."""
    def handler(e):
        if isinstance(e, fluid.EndStepEvent) and e.step == 3:
            raise RuntimeError('handler crashed mid-window')

    with pytest.raises(RuntimeError, match='mid-window'):
        _run_trainer(handler, sync='async', async_window=2)
    gc.collect()
    assert obs.gauge('executor.inflight').value == 0


def test_trainer_rejects_incompatible_configs():
    train_func, opt_func, _ = _trainer_pieces()
    with pytest.raises(ValueError, match='bundle_steps'):
        fluid.Trainer(train_func, opt_func, bundle_steps=0)
    with pytest.raises(ValueError, match='sync'):
        fluid.Trainer(train_func, opt_func, sync='never')
    with pytest.raises(ValueError, match='parallel'):
        fluid.Trainer(train_func, opt_func, parallel=True, bundle_steps=4)


# ---------------------------------------------------------------------------
# persistent compile cache across processes
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys
import numpy as np
import paddle_tpu.fluid as fluid

prog, start = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, start):
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(start)
rng = np.random.RandomState(0)
feed = {'x': rng.rand(16, 13).astype('float32'),
        'y': rng.rand(16, 1).astype('float32')}
exe.run(prog, feed=feed, fetch_list=[loss])
exe.run(prog, feed=feed, fetch_list=[loss])
print('STATS=' + json.dumps(exe.cache_stats))
"""


def test_persistent_cache_second_process_zero_compiles(tmp_path):
    """The acceptance drill: process 1 cold-compiles into the cache dir;
    process 2 (same program, same feed signature) records ZERO
    executor.compile spans — every first call deserializes
    (executor.compile.persistent_hit events + cache_stats counter)."""
    cache = tmp_path / 'cc'

    def run_child(obs_dir):
        env = dict(os.environ,
                   JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   PADDLE_TPU_OBS_DIR=str(obs_dir))
        env.pop('PADDLE_TPU_OBS_RUN_FILE', None)
        r = subprocess.run([sys.executable, '-c', _CHILD],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr[-2000:]
        stats = json.loads(
            [ln for ln in r.stdout.splitlines()
             if ln.startswith('STATS=')][0][len('STATS='):])
        obs_dir = str(obs_dir)
        logs = [os.path.join(obs_dir, f) for f in os.listdir(obs_dir)]
        assert len(logs) == 1
        events, errors = obs_report.load_events(logs[0])
        assert errors == []
        return stats, events

    stats1, ev1 = run_child(tmp_path / 'obs1')
    compiles1 = [e for e in ev1 if e['name'] == 'executor.compile']
    assert compiles1, 'first process must cold-compile'
    assert stats1['persistent_hits'] == 0

    stats2, ev2 = run_child(tmp_path / 'obs2')
    compiles2 = [e for e in ev2 if e['name'] == 'executor.compile']
    assert compiles2 == [], \
        'second process re-compiled already-cached keys: %r' % compiles2
    phits = [e for e in ev2
             if e['name'] == 'executor.compile.persistent_hit']
    assert len(phits) == len(compiles1)
    assert stats2['persistent_hits'] == len(compiles1)
    # and the steps that hit carry the outcome in their span fields
    steps2 = [e for e in ev2 if e['name'] == 'executor.step'
              and e.get('fields', {}).get('cache') == 'persistent_hit']
    assert steps2


def test_trainer_bundled_handles_short_last_batch():
    """Readers rarely divide evenly: the bundled loop must flush the
    buffer when the batch shape changes (short last batch) instead of
    poisoning one bundle with mixed signatures — caught live on
    uci_housing (404 rows / batch 32)."""
    train_func, opt_func, _ = _trainer_pieces()
    rows = _feeds(1, seed=2, batch=23)[0]   # 23 = 5 batches of 4 + one of 3

    def reader():
        for i in range(0, 23, 4):
            xb, yb = rows['x'][i:i + 4], rows['y'][i:i + 4]
            yield [(xb[j], yb[j]) for j in range(len(xb))]

    seen = []

    def handler(e):
        if isinstance(e, fluid.EndStepEvent):
            seen.append((e.step,
                         float(np.asarray(e.metrics[0]).reshape(-1)[0])))

    t = fluid.Trainer(train_func, opt_func, place=fluid.CPUPlace(),
                      bundle_steps=4)
    t.train(num_epochs=1, event_handler=handler, reader=reader,
            feed_order=['x', 'y'])
    assert [s for s, _ in seen] == [0, 1, 2, 3, 4, 5]   # no step dropped
    assert all(np.isfinite(v) for _, v in seen)


def test_trainer_rejects_bundle_plus_async():
    train_func, opt_func, _ = _trainer_pieces()
    with pytest.raises(ValueError, match="sync='async'"):
        fluid.Trainer(train_func, opt_func, bundle_steps=4, sync='async')


def test_trainer_bundled_periodic_checkpoints_fire(tmp_path, obs_events):
    """K=8 bundles with step_interval=10: no bundle BOUNDARY ever lands
    on a multiple of 10, but steps 0 and 10 cross inside bundles — the
    range gate must fire for them (the naive modulo-on-boundary gate
    saved nothing, ever)."""
    train_func, opt_func, reader = _trainer_pieces()   # 10 steps/epoch
    cfg = fluid.CheckpointConfig(checkpoint_dir=str(tmp_path / 'ck'),
                                 step_interval=10)
    t = fluid.Trainer(train_func, opt_func, place=fluid.CPUPlace(),
                      bundle_steps=8, checkpoint_config=cfg)
    t.train(num_epochs=1, event_handler=lambda e: None, reader=reader,
            feed_order=['x', 'y'])
    saves = obs_events('trainer.checkpoint.save')
    # step 0 crosses in bundle [0..7]; the short bundle [8..9] has no
    # multiple of 10 inside it
    assert len(saves) == 1
    assert saves[0]['fields']['step'] == 7   # bundle-end state recorded
