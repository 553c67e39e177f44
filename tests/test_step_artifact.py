"""Step-artifact tier (ROADMAP item 5): one compiled-step artifact, four
thin drivers, and the pipeline overlap it unlocks.

Drills:
  * driver equivalence — run / run_bundle(K=1) / StepHandle.step / the
    serving dispatch produce BIT-identical fetches and share ONE
    compiled-step cache entry for the same program (the exact-arithmetic
    feed makes any summation order produce the same bits, so the
    assertion is equality, not allclose);
  * donate-exactly-once — every jitted entry point (step, each bundle K)
    compiles exactly once across repeated calls (the PR 4 "warm twice"
    run_bundle wart: uncommitted first-call state re-specialized the
    executable on call two);
  * double-buffered feeds — Trainer(double_buffer=True) trains
    bit-identically to the inline path while staging input assembly on a
    background thread (trainer.input_stage spans prove where the time
    went);
  * async sharded checkpointing — commits off the step path, emergency
    flush drains-and-commits before exit, and a SIGKILL mid-async-save
    never leaves a latest-looking torn serial (subprocess drill);
  * AOT warm signatures — an exported blob warms a COLD process to zero
    online compiles (aot_hit classified in cache_stats), and
    step_artifact.aot_check types a stale blob statically
    (tools/program_lint.py --aot).
"""
import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import step_artifact
from paddle_tpu.fluid.executor import StepArtifact
from paddle_tpu.fluid.step_artifact import StepResult
from paddle_tpu.obs import report as obs_report

pytestmark = pytest.mark.artifact

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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

def _forward_program():
    """Inference-shaped program whose arithmetic is EXACT in float32:
    weights and feeds are small powers of two, so every product and
    every partial sum is representable — any op ordering (run vs scan vs
    serving batch) must produce identical bits."""
    from paddle_tpu.fluid import unique_name
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard():
        with fluid.program_guard(prog, start):
            x = fluid.layers.data(name='x', shape=[8], dtype='float32')
            out = fluid.layers.fc(input=x, size=1, act=None,
                                  param_attr=fluid.ParamAttr(name='w'),
                                  bias_attr=fluid.ParamAttr(name='b'))
    return prog, start, out


def _exact_feed(batch=8):
    rng = np.random.RandomState(0)
    x = 2.0 ** rng.randint(-2, 2, size=(batch, 8))
    return {'x': x.astype('float32')}


def _init_exact_params(scope):
    w = (2.0 ** (-(np.arange(8) % 4))).astype('float32').reshape(8, 1)
    scope.vars['w'] = w
    scope.vars['b'] = np.asarray([0.125], 'float32')


def _regression(lr=0.1):
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
    return prog, start, loss


def _feeds(n, seed=0, batch=16):
    rng = np.random.RandomState(seed)
    return [{'x': rng.rand(batch, 13).astype('float32'),
             'y': rng.rand(batch, 1).astype('float32')} for _ in range(n)]


# ---------------------------------------------------------------------------
# one artifact, four drivers
# ---------------------------------------------------------------------------

def test_four_drivers_share_one_artifact_and_match_bitwise():
    """run / run_bundle(K=1) / StepHandle.step / serving dispatch: ONE
    compiled-step cache entry, one shared key, bit-identical fetches."""
    from paddle_tpu import serving

    prog, _start, out = _forward_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = _exact_feed()
    keys = []

    with fluid.scope_guard(scope):
        _init_exact_params(scope)

        r_run = np.asarray(
            exe.run(prog, feed=feed, fetch_list=[out])[0])
        keys.append(exe._last_cache_lookup['key'])
        assert exe._last_cache_lookup['outcome'] == 'miss'

        r_bundle = np.asarray(
            exe.run_bundle(prog, feeds=[feed], fetch_list=[out])[0])[0]
        keys.append(exe._last_cache_lookup['key'])
        assert exe._last_cache_lookup['outcome'] == 'hit'

        handle = exe.acquire_step(prog, feed=feed, fetch_list=[out])
        keys.append(exe._last_cache_lookup['key'])
        r_handle = np.asarray(handle.step(
            {'x': feed['x']})[0])

        class _Model(object):
            feed_names = ['x']
            fetch_names = [out.name]

            def run(self, f):
                with fluid.scope_guard(scope):
                    r = exe.run(prog, feed=f, fetch_list=[out])
                keys.append(exe._last_cache_lookup['key'])
                return r

        eng = serving.ServingEngine(
            _Model(), serving.ServingConfig(max_batch_size=8, buckets=[8]))
        try:
            r_serve = np.asarray(eng.predict(feed)[0])
        finally:
            eng.shutdown()

    # bit-identical across every driver (exact arithmetic: no tolerance)
    np.testing.assert_array_equal(r_run, r_bundle)
    np.testing.assert_array_equal(r_run, r_handle)
    np.testing.assert_array_equal(r_run, r_serve)
    # ONE artifact: a single cache entry, one miss, every driver on the
    # same key
    stats = exe.cache_stats
    assert stats['entries'] == 1, stats
    assert stats['misses'] == 1, stats
    assert len(set(keys)) == 1, keys
    # and the artifact enumerates both compiled entry points
    art = list(exe._cache.values())[0]
    assert isinstance(art, StepArtifact)
    assert ('step',) in art.signatures()
    assert ('bundle', 1) in art.signatures()


def _guarded_held_step():
    """A training step that hands back all four fields of a StepResult:
    an expert layer with a held share (a device counter, PR 35) under an
    armed anomaly guard (the health vector)."""
    from paddle_tpu.fluid import unique_name
    prog, start = fluid.Program(), fluid.Program()
    prog.random_seed = start.random_seed = 3
    with unique_name.guard(), fluid.program_guard(prog, start):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        out, _count = fluid.layers.moe_mlp(
            x, num_experts=16, hidden_size=8, act='swish', gated=True,
            top_k=2, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=(8, 8))
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    fluid.anomaly_guard(prog)
    feed = {'x': np.random.RandomState(5).rand(64, 16).astype('float32')}
    return prog, start, loss, feed


def test_no_attribute_of_an_artifact_is_born_outside_its_constructor():
    """The artifact is born whole: after run, run_bundle(K=2) and a
    StepHandle's step on one program it has the attributes it had right
    after construction (its identity and its first-call flags are
    constructor state, not set from outside)."""
    prog, start, loss, feed = _guarded_held_step()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(start)
        art = exe.step_artifact(prog, feed, [loss])
        born = set(vars(art))
        assert art._key_id == exe._last_cache_lookup['key']
        assert art._source_program is prog and art._feed_sig
        assert not art._obs_compiled and art._obs_bundles == set()
        exe.run(prog, feed=feed, fetch_list=[loss])
        exe.run_bundle(prog, feeds=[feed, feed], fetch_list=[loss])
        handle = exe.acquire_step(prog, feed=feed, fetch_list=[loss])
        handle.step({'x': feed['x']})
        assert exe.step_artifact(prog, feed, [loss]) is art
    assert art._obs_compiled and art._obs_bundles == {2}
    assert set(vars(art)) == born


@pytest.mark.parametrize('driver', ['call', 'first_call', 'debug_step',
                                    'unjitted', 'bundle'])
def test_every_driver_hands_back_a_step_result_read_by_name(driver):
    """One record for every way a step executes: the jitted call seam, a
    first call through the executor's probe, the eager debug_step, the
    un-jitted body `_step` and a bundle's K-scan (fields stacked on K) return a
    StepResult whose fetches, new_persist, health (the guard's) and
    counters (PR 35's) are read by name."""
    import jax
    prog, start, loss, feed = _guarded_held_step()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(start)
        art = exe.step_artifact(prog, feed, [loss])
        persist = {n: scope._chain_get(n) for n in art.persist_in}
        placed = {'x': jax.numpy.asarray(feed['x'])}
        key = jax.random.key(0)
        split = art.plan.split(persist)
        K = 1
        if driver == 'call':
            res = art(*split, placed, key)
        elif driver == 'first_call':
            res, outcome = exe._timed_first_call(
                art, 'step', split + (placed, key), art._key_id)
            assert outcome == 'compile' and art._obs_compiled
        elif driver == 'debug_step':
            res = art.debug_step(persist, placed, key)
        elif driver == 'unjitted':
            res = art._step(*split, placed, key)
        else:
            K = 2
            stacked = {'x': jax.numpy.stack([placed['x']] * K)}
            res = art.bundle(K)(*split, stacked,
                                np.asarray([1, 2], np.uint32))
    assert isinstance(res, StepResult)
    assert res._fields == ('fetches', 'new_persist', 'health', 'counters')
    lead = () if K == 1 else (K,)
    assert np.asarray(res.fetches[0]).shape == lead + (1,)
    assert set(res.new_persist) == set(art.persist_out)
    assert np.asarray(res.health['healthy']).shape == lead
    assert np.asarray(res.health['healthy']).all()
    assert np.asarray(res.counters).shape == lead + (len(art.counters),)
    assert len(art.counters) == 1


def test_each_signature_compiles_exactly_once():
    """The warm-twice regression drill: repeated run() and run_bundle()
    calls never re-specialize a jitted entry — each signature holds ONE
    executable (pin_state commits the donated state before the first
    call, so call one and call N share an argument signature)."""
    prog, start, loss = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feeds = _feeds(12, seed=3)
    with fluid.scope_guard(scope):
        exe.run(start)
        for f in feeds[:3]:
            exe.run(prog, feed=f, fetch_list=[loss])
        for i in range(3):
            exe.run_bundle(prog, feeds=feeds[3 + 3 * i:6 + 3 * i],
                           fetch_list=[loss])
    art = [a for a in exe._cache.values() if 3 in a._bundles][0]
    if not hasattr(art._jitted, '_cache_size'):
        pytest.skip('jax jit wrapper lacks _cache_size introspection')
    assert art._jitted._cache_size() == 1
    assert art._bundles[3]._cache_size() == 1


def test_pin_state_commits_scope_arrays_once():
    prog, start, loss = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        # fresh startup outputs are uncommitted; the first _prepare pins
        # them (committed device arrays) and syncs the scope
        exe.run(prog, feed=_feeds(1)[0], fetch_list=[loss])
        art = [a for a in exe._cache.values() if a.ad_idx is not None][0]
        persist = {n: scope._chain_get(n) for n in art.persist_in}
        assert art.pin_state(persist, exe._device()) == []


def test_step_handle_state_dict_seam():
    prog, start, loss = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        h = exe.acquire_step(prog, feed=_feeds(1)[0], fetch_list=[loss])
        sd = h.state_dict()
    assert set(sd) == set(h._compiled.persist_in)
    for n, v in sd.items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(scope._chain_get(n)))


# ---------------------------------------------------------------------------
# double-buffered feeds
# ---------------------------------------------------------------------------

_TRAIN_W = np.array([[1.5], [-2.0], [0.5], [3.0]], 'float32')


def _train_func():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1,
                           param_attr=fluid.ParamAttr(name='w'),
                           bias_attr=fluid.ParamAttr(name='b'))
    return fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))


def _train_reader(n=48, batch=8, seed=0):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n // batch):
            xs = rng.rand(batch, 4).astype('float32')
            ys = xs @ _TRAIN_W
            yield [(xs[i], ys[i]) for i in range(batch)]
    return r


def _sgd():
    return fluid.optimizer.SGD(learning_rate=0.1)


def _run_trainer(double_buffer, bundle_steps=1, epochs=3):
    losses = []

    def handler(ev):
        if isinstance(ev, fluid.EndStepEvent) and ev.metrics:
            losses.append(float(np.asarray(ev.metrics[0]).reshape(-1)[0]))

    tr = fluid.Trainer(train_func=_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), double_buffer=double_buffer,
                       bundle_steps=bundle_steps)
    tr.train(epochs, handler, reader=_train_reader(),
             feed_order=['x', 'y'])
    w = np.asarray(tr.scope.vars['w']).copy()
    return losses, w, tr


def test_trainer_double_buffer_bit_identical(obs_events):
    """Staging moves WHERE the feed work happens, never what is fed:
    losses and parameters are bit-identical with double_buffer on/off,
    and the on-leg records staged trainer.input_stage spans."""
    l_off, w_off, tr_off = _run_trainer(False)
    l_on, w_on, tr_on = _run_trainer(True)
    assert l_off == l_on
    np.testing.assert_array_equal(w_off, w_on)
    assert tr_on.batches_fed == tr_off.batches_fed > 0
    spans = obs_events('trainer.input_stage')
    assert any(s['fields'].get('staged') for s in spans)
    assert any(not s['fields'].get('staged') for s in spans)


def test_trainer_double_buffer_bundled_loop():
    l_off, w_off, _ = _run_trainer(False, bundle_steps=3)
    l_on, w_on, _ = _run_trainer(True, bundle_steps=3)
    assert l_off == l_on
    np.testing.assert_array_equal(w_off, w_on)


# ---------------------------------------------------------------------------
# async sharded checkpointing
# ---------------------------------------------------------------------------

def _mesh_hook(axes):
    return lambda p: p.set_mesh(axes)


def test_async_checkpoint_commits_and_resumes_exact_step(tmp_path,
                                                         obs_events):
    """CheckpointConfig(async_save=True): periodic saves commit from the
    writer thread (checkpoint.snapshot + committed events), training
    stats match the sync path, and a successor Trainer resumes at the
    exact next step."""
    ckpt = str(tmp_path / 'ck')
    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, step_interval=2,
                                 max_num_checkpoints=3, async_save=True)
    steps = []

    def handler(ev):
        if isinstance(ev, fluid.EndStepEvent):
            steps.append((ev.epoch, ev.step))
            if ev.epoch == 1 and ev.step == 3:
                tr.request_preemption()

    tr = fluid.Trainer(train_func=_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), checkpoint_config=cfg,
                       transpiler_fn=_mesh_hook({'dp': 8}))
    tr.train(3, handler, reader=_train_reader(), feed_order=['x', 'y'])
    assert tr.preempted
    assert tr._async_ckpt is None   # drained before train() returned
    # the emergency flush committed SYNCHRONOUSLY for the exact step
    from paddle_tpu.utils import checkpoint as ck
    arrays, meta = ck.load_latest_verified(ckpt)
    args = meta['extra']['trainer_args']
    assert (args['epoch_id'], args['step_id']) == (1, 3)
    assert args.get('preempted') is True
    # no staging leftovers pretending to be checkpoints
    assert not [d for d in os.listdir(ckpt) if d.endswith('.tmp')]
    # snapshots happened (async periodic path) and commits were observed
    assert obs_events('checkpoint.snapshot')
    assert obs_events('checkpoint.committed')

    # successor resumes at the exact next step
    seen = []

    def handler2(ev):
        if isinstance(ev, fluid.BeginStepEvent):
            seen.append((ev.epoch, ev.step))

    cfg2 = fluid.CheckpointConfig(checkpoint_dir=ckpt, step_interval=2,
                                  max_num_checkpoints=3, async_save=True)
    tr2 = fluid.Trainer(train_func=_train_func, optimizer_func=_sgd,
                        place=fluid.CPUPlace(), checkpoint_config=cfg2,
                        transpiler_fn=_mesh_hook({'dp': 8}))
    tr2.train(2, handler2, reader=_train_reader(), feed_order=['x', 'y'])
    assert seen[0] == (1, 4), seen[:3]


def test_async_checkpoint_matches_sync_trajectory(tmp_path):
    """async_save changes WHEN the files are written, never the training
    arithmetic: identical loss trajectories and final params."""
    def leg(async_save, sub):
        ckpt = str(tmp_path / sub)
        cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, step_interval=3,
                                     max_num_checkpoints=2,
                                     async_save=async_save)
        losses = []

        def handler(ev):
            if isinstance(ev, fluid.EndStepEvent) and ev.metrics:
                losses.append(float(np.asarray(
                    ev.metrics[0]).reshape(-1)[0]))

        tr = fluid.Trainer(train_func=_train_func, optimizer_func=_sgd,
                           place=fluid.CPUPlace(), checkpoint_config=cfg,
                           transpiler_fn=_mesh_hook({'dp': 8}))
        tr.train(2, handler, reader=_train_reader(),
                 feed_order=['x', 'y'])
        return losses, np.asarray(tr.scope.vars['w']).copy()

    l_sync, w_sync = leg(False, 'sync')
    l_async, w_async = leg(True, 'async')
    assert l_sync == l_async
    np.testing.assert_array_equal(w_sync, w_async)


_KILL_CHILD = r"""
import os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
from paddle_tpu.utils import checkpoint as shck

base = sys.argv[1]
arrays = {'w%%d' %% i: np.full((64, 64), float(i), 'float32')
          for i in range(4)}
# serial 1: committed cleanly — the fallback the torn serial must not mask
shck.save_sharded(os.path.join(base, 'sharded_1'), arrays, step=1)

# slow every shard write down so the parent can SIGKILL mid-save
_orig = shck._write_shard
def slow(fpath, data, sh):
    time.sleep(0.4)
    return _orig(fpath, data, sh)
shck._write_shard = slow

h = shck.save_sharded_async(os.path.join(base, 'sharded_2'),
                            arrays, step=2)
print('ASYNC_STARTED', flush=True)
h.wait()
print('NEVER_REACHED', flush=True)
time.sleep(60)
"""


def test_sigkill_mid_async_save_never_leaves_torn_serial(tmp_path):
    """The PR 10 torn-write drill re-run against the ASYNC path: SIGKILL
    while the background writer is mid-save leaves only the staging dir,
    which restore skips (loudly) in favor of the previous committed
    serial."""
    base = str(tmp_path / 'ck')
    os.makedirs(base)
    child = subprocess.Popen(
        [sys.executable, '-c', _KILL_CHILD % {'repo': _REPO}, base],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    try:
        line = child.stdout.readline()
        assert 'ASYNC_STARTED' in line, line
        # wait until the writer has staged at least one shard file, so
        # the kill lands genuinely mid-save
        staging = os.path.join(base, 'sharded_2.tmp')
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if os.path.isdir(staging) and os.listdir(staging):
                break
            time.sleep(0.05)
        else:
            pytest.fail('async writer never staged a shard')
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    # the torn save is only ever the .tmp staging dir; serial 2 must not
    # exist committed, and restore falls back to serial 1 with a warning
    assert not os.path.isdir(os.path.join(base, 'sharded_2'))
    from paddle_tpu.utils import checkpoint as ck
    with pytest.warns(RuntimeWarning, match='uncommitted'):
        arrays, meta = ck.load_latest_verified(base)
    assert meta['step'] == 1
    np.testing.assert_array_equal(np.asarray(arrays['w3']),
                                  np.full((64, 64), 3.0, 'float32'))


def test_overlapping_async_saves_to_one_dir_rejected(tmp_path):
    from paddle_tpu.utils import checkpoint as shck
    arrays = {'w': np.zeros((256, 256), 'float32')}
    dest = str(tmp_path / 'sharded_1')
    h = shck.save_sharded_async(dest, arrays, step=1)
    try:
        if not h.done():
            with pytest.raises(RuntimeError, match='in flight'):
                shck.save_sharded_async(dest, arrays, step=1)
    finally:
        h.wait()
    # after the writer finishes, a new save to the same dir is legal
    h2 = shck.save_sharded_async(dest, arrays, step=2)
    h2.wait()


# ---------------------------------------------------------------------------
# AOT warm signatures
# ---------------------------------------------------------------------------

_AOT_CHILD = r"""
import json, os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import paddle_tpu.fluid as fluid

mode, aot_dir = sys.argv[1], sys.argv[2]
prog, start = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, start):
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
if mode == 'import':
    exe.load_warm_signatures(aot_dir)
exe.run(start)
rng = np.random.RandomState(0)
feed = {'x': rng.rand(16, 13).astype('float32'),
        'y': rng.rand(16, 1).astype('float32')}
exe.run(prog, feed=feed, fetch_list=[loss])
exe.run_bundle(prog, feeds=[feed, feed], fetch_list=[loss])
if mode == 'export':
    exe.export_warm_signatures(aot_dir)
if mode == 'import':
    # a bundle length the blob never warmed: must compile as an
    # ORDINARY first call, not flag the blob stale
    exe.run_bundle(prog, feeds=[feed, feed, feed], fetch_list=[loss])
print('STATS=' + json.dumps(exe.cache_stats))
"""


def _run_aot_child(mode, aot_dir, cache_dir, obs_dir):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PADDLE_TPU_OBS_DIR=str(obs_dir))
    env.pop('PADDLE_TPU_OBS_RUN_FILE', None)
    env['JAX_COMPILATION_CACHE_DIR'] = str(cache_dir)
    r = subprocess.run(
        [sys.executable, '-c', _AOT_CHILD % {'repo': _REPO}, mode,
         str(aot_dir)],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    stats = json.loads([ln for ln in r.stdout.splitlines()
                        if ln.startswith('STATS=')][0][len('STATS='):])
    logs = [os.path.join(str(obs_dir), f)
            for f in os.listdir(str(obs_dir))]
    assert len(logs) == 1
    events, errors = obs_report.load_events(logs[0])
    assert errors == []
    return stats, events


def test_aot_export_warms_cold_process_to_zero_compiles(tmp_path):
    """The cold-replica contract: a fresh process whose compile cache
    directory is EMPTY (the environment places it; load_warm_signatures
    copies the blob's entries in and never points jax anywhere else)
    reaches its first step AND first bundle with ZERO executor.compile
    spans — every first call classifies aot_hit."""
    aot = tmp_path / 'aot'
    stats1, ev1 = _run_aot_child('export', aot, tmp_path / 'cc',
                                 tmp_path / 'obs1')
    compiles1 = [e for e in ev1 if e['name'] == 'executor.compile']
    assert compiles1 and stats1['aot_hits'] == 0
    man = step_artifact.read_aot(str(aot))
    assert man['signatures'] and man['cache_entries']
    # startup + train artifacts, the train one with its K=2 bundle
    assert any(s['bundles'] == [2] for s in man['signatures'])

    stats2, ev2 = _run_aot_child('import', aot, tmp_path / 'cc_cold',
                                 tmp_path / 'obs2')
    assert stats2['compile_cache_dir'] == str(tmp_path / 'cc_cold')
    compiles2 = [e for e in ev2 if e['name'] == 'executor.compile']
    # the ONLY online compile is the deliberately un-warmed K=3 bundle —
    # and it classifies as an ordinary compile, never as a stale blob
    assert [e['fields'].get('bundle_steps') for e in compiles2] == [3], \
        compiles2
    assert stats2['online_compiles'] == 1
    assert stats2['aot_hits'] == len(compiles1)
    assert stats2['aot_stale'] == 0
    hits = [e for e in ev2 if e['name'] == 'executor.compile.aot_hit']
    assert len(hits) == len(compiles1)
    assert [e for e in ev2 if e['name'] == 'executor.aot.loaded']
    # the step-artifact obs section renders the split
    text = obs_report.summarize(ev2)
    assert '-- step artifact --' in text
    assert 'AOT-hit' in text


def test_aot_check_types_stale_blobs():
    """step_artifact.aot_check (program_lint --aot): a fresh manifest is
    clean against its program; a drifted program / tampered manifest is
    a typed problem list, not a silent online recompile."""
    prog, start, loss = _regression()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(start)
        exe.run(prog, feed=_feeds(1)[0], fetch_list=[loss])
    man = step_artifact.aot_manifest(exe)
    # drop the startup artifact: check the TRAIN signature set
    man['signatures'] = [s for s in man['signatures']
                         if s['fetches'] == [loss.name]]
    assert step_artifact.aot_check(man, prog) == []

    # a structurally different program (extra layer) fingerprints apart
    from paddle_tpu.fluid import unique_name
    other, o_start = fluid.Program(), fluid.Program()
    with unique_name.guard():
        with fluid.program_guard(other, o_start):
            x = fluid.layers.data(name='x', shape=[13], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            h = fluid.layers.fc(input=x, size=4)
            pred = fluid.layers.fc(input=h, size=1)
            o_loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(o_loss)
    probs = step_artifact.aot_check(man, other)
    assert any('no exported signature matches' in p for p in probs)

    bad = json.loads(json.dumps(man))
    bad['signatures'][0]['feeds'][0]['dtype'] = 'int32'
    bad['signatures'][0]['donates'].append('ghost')
    probs = step_artifact.aot_check(bad, prog)
    assert any('recorded dtype' in p for p in probs)
    assert any('ghost' in p for p in probs)

    alien = dict(man, jax='0.0.1')
    probs = step_artifact.aot_check(alien, prog)
    assert any('jax' in p for p in probs)


def test_stable_signature_ignores_process_identity():
    """Two same-shaped builds in one process get the same stable
    signature (it must survive restarts, unlike the _uid-keyed cache
    key)."""
    sigs = []
    for _ in range(2):
        prog, start, loss = _regression()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(start)
            exe.run(prog, feed=_feeds(1)[0], fetch_list=[loss])
        art = [a for a in exe._cache.values()
               if a.fetch_names == [loss.name]][0]
        sigs.append(step_artifact.stable_signature(art))
    assert sigs[0] == sigs[1]


# ---------------------------------------------------------------------------
# obs report section
# ---------------------------------------------------------------------------

def test_obs_report_step_artifact_section_renders():
    def ev(name, kind='event', dur=None, **fields):
        rec = {'ts': 1.0, 'name': name, 'kind': kind, 'fields': fields}
        if kind == 'span':
            rec['dur_s'] = dur if dur is not None else 0.01
        return rec

    events = [
        ev('executor.artifact', key='abc', feeds=2, fetches=1,
           persistables=3, donates=3, mesh=False),
        ev('executor.compile', kind='span', dur=0.5, key='abc'),
        ev('executor.compile.aot_hit', key='abc', seconds=0.02),
        ev('executor.aot.loaded', signatures=2,
           cache_entries_imported=3),
        ev('trainer.step', kind='span', dur=0.1),
        ev('trainer.input_stage', kind='span', dur=0.001, staged=True),
        ev('checkpoint.snapshot', kind='span', dur=0.004, step=1,
           arrays=3),
        ev('checkpoint.commit', kind='span', dur=0.002, step=1),
        ev('trainer.checkpoint.async_wait', kind='span', dur=0.0005,
           ready=True),
    ]
    text = obs_report.summarize(events)
    assert '-- step artifact --' in text
    assert '1 artifact(s) built' in text
    assert '1 compiled online' in text and '1 AOT-hit' in text
    assert 'AOT blob loaded' in text
    assert 'input stage' in text and 'overlap ratio' in text
    assert 'async checkpoint snapshots' in text
    assert 'async-save waits' in text
