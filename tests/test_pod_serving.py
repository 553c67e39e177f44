"""Pod-scale serving drills (docs/serving.md#pod).

The serving tier crossed the line training crossed in PRs 7/9/10: a
`set_mesh`-annotated Program (row-sharded embedding table) serves as a
single Router replica through the GSPMD executor — restored from a
SHARDED checkpoint, never materialized dense — replicas register across
hosts through a shared-filesystem registry, and a dead serving host is
detected by heartbeat, its futures RE-ROUTED to survivors (zero dropped
futures) and its replica RE-SHARDED onto the surviving topology.

Every in-process drill simulates host death via `simulate_death()`
(beats stop + loops freeze: indistinguishable from SIGKILL to the
router); the 2-process drill (additionally `slow`, the test_elastic.py
harness) uses a real SIGKILL. Telemetry assertions verify an operator
could have SEEN each decision (docs/observability.md).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs, serving
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, _switch_scope
from paddle_tpu.obs import report as obs_report
from paddle_tpu.obs import trace
from paddle_tpu.parallel import HostLost
from paddle_tpu.serving import (AutoscalePolicy, Autoscaler, DecodeConfig,
                                DecodeEngine, PodRouter, PodWorker, Router,
                                ServerClosed, ServingConfig, ServingEngine,
                                ShardedPredictor, TransportError)
from paddle_tpu.serving.transport import Channel, RpcServer
from paddle_tpu.utils import checkpoint as ck
from paddle_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.pod

VOCAB, DIM = 64, 4


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


@pytest.fixture(params=['file', 'rpc'])
def transport(request):
    """Every pod drill runs on BOTH wires — the shared-filesystem
    mailbox and the length-prefixed TCP rpc transport — from ONE test
    body. The only knob is the PodWorker(transport=...) seam; the
    router discovers the wire from the registration record."""
    return request.param


# ---------------------------------------------------------------------------
# shared artifacts: a trained sharded-embedding scorer + sharded ckpt
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def artifacts(tmp_path_factory):
    """Train the acceptance-drill model (vocab-sharded table + fc head)
    on the dp=8 mesh, save a SHARDED checkpoint + the program-only
    serving artifact, and record dense reference scores for a probe."""
    base = tmp_path_factory.mktemp('pod_artifacts')
    model_dir = str(base / 'model')
    ckpt_dir = str(base / 'ckpt')
    main, startup, scope = (framework.Program(), framework.Program(),
                            Scope())
    prev = _switch_scope(scope)
    try:
        with unique_name.guard():
            with framework.program_guard(main, startup):
                ids = fluid.layers.data(name='ids', shape=[2, 1],
                                        dtype='int64')
                emb = fluid.layers.embedding(
                    ids, size=[VOCAB, DIM], is_sparse=True,
                    is_distributed=True,
                    param_attr=fluid.ParamAttr(name='emb_w',
                                               sharding=('dp', None)))
                pred = fluid.layers.fc(
                    input=emb, size=1, num_flatten_dims=2,
                    bias_attr=False,
                    param_attr=fluid.ParamAttr(name='fc_w'))
                loss = fluid.layers.mean(fluid.layers.square(pred - 1.0))
                fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
                main.set_mesh({'dp': 8})
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                rng = np.random.RandomState(0)
                for _ in range(3):
                    b = rng.randint(0, VOCAB, (8, 2, 1)).astype('int64')
                    exe.run(main, feed={'ids': b}, fetch_list=[loss])
                state = exe.state_dict(main, scope=scope)
                ck.save_sharded(os.path.join(ckpt_dir, 'sharded_7'),
                                {'emb_w': state['emb_w'],
                                 'fc_w': state['fc_w']}, step=7)
                serving.save_serving_program(model_dir, ['ids'], [pred],
                                             main_program=main)
                probe = rng.randint(0, VOCAB, (8, 2, 1)).astype('int64')
                infer = main.clone(for_test=True).prune([pred])
                ref = exe.run(infer, feed={'ids': probe},
                              fetch_list=[pred.name], scope=scope)
    finally:
        _switch_scope(prev)
    return {'model_dir': model_dir, 'ckpt_dir': ckpt_dir,
            'probe': probe, 'ref': np.asarray(ref[0])}


def _cfg(**kw):
    base = dict(max_batch_size=8, buckets=[8], max_queue_delay_ms=1.0)
    base.update(kw)
    return ServingConfig(**base)


def _builder(art, mesh_n, buckets=(8,)):
    def b(reason):
        return serving.sharded_replica(
            art['model_dir'], mesh_axes={'dp': mesh_n},
            ckpt_dir=art['ckpt_dir'], config=_cfg(buckets=list(buckets)))
    return b


# ---------------------------------------------------------------------------
# satellite 1: the replica registration-handle seam on the Router
# ---------------------------------------------------------------------------

class _StubEngine(object):
    """Engine-protocol stub: controllable window, recorded calls."""

    feed_names = ['x']

    def __init__(self, window=None, result=1.0):
        self.window = dict(window or {})
        self.result = result
        self.shutdowns = []
        self.pushed = []

    def submit(self, feed, **kw):
        import concurrent.futures
        f = concurrent.futures.Future()
        f.set_result([np.asarray(feed['x']) * self.result])
        return f

    def stats_window(self):
        return dict(self.window)

    def push_rows(self, deltas):
        self.pushed.append(deltas)
        return sum(len(i) for i, _ in deltas.values())

    def shutdown(self, drain=True, timeout=None):
        self.shutdowns.append(drain)
        return True


def test_replica_handles_add_remove(obs_events):
    r = Router(window_s=0.0)
    e1, e2, e3 = _StubEngine(), _StubEngine(), _StubEngine()
    r.add_model('m', [e1, e2])
    view = r.replicas('m')
    rids = [v['rid'] for v in view]
    assert len(set(rids)) == 2
    assert all(v['host'] is None and v['key'] is None for v in view)
    # add_replica returns the handle; registry coordinates stick
    rid3 = r.add_replica('m', e3, host=5, key='5.m-1')
    view = {v['rid']: v for v in r.replicas('m')}
    assert view[rid3]['host'] == 5 and view[rid3]['key'] == '5.m-1'
    ev = obs_events('serving.replica.register')
    assert ev and ev[-1]['fields']['host'] == 5
    # pod_size gauge: local host + host 5
    assert obs.gauge('router.pod_size').value == 2
    # remove by handle: drained in the background, typed event
    got = r.remove_replica('m', rid3, drain=True, reason='scale_down')
    assert got is e3
    deadline = time.monotonic() + 5
    while not e3.shutdowns and time.monotonic() < deadline:
        time.sleep(0.01)
    assert e3.shutdowns == [True]
    ev = obs_events('serving.replica.drain')
    assert ev and ev[-1]['fields']['reason'] == 'scale_down'
    assert len(r.replicas('m')) == 2
    # unknown handle is a no-op, not an error
    assert r.remove_replica('m', 999999) is None
    # detach (host-loss posture): engine untouched
    rid1 = r.replicas('m')[0]['rid']
    r.remove_replica('m', rid1, drain=False, reason='host_lost')
    assert e1.shutdowns == []
    assert obs.gauge('router.pod_size').value == 1
    r.shutdown(drain=False)


def test_sample_windows_refreshes_pressure():
    r = Router(window_s=0.0)
    e = _StubEngine(window={'queue_depth': 3, 'inflight': 2,
                            'queue_high_water': 5})
    r.add_model('m', [e])
    s = r.sample_windows('m')
    assert s[0]['window']['queue_depth'] == 3
    e.window['queue_depth'] = 0
    s = r.sample_windows('m')
    assert s[0]['window']['queue_depth'] == 0
    r.shutdown(drain=False)


# ---------------------------------------------------------------------------
# autoscaling: queue-depth-driven capacity on the add/remove seam
# ---------------------------------------------------------------------------

def test_autoscaler_up_down_with_cooldown(obs_events):
    r = Router(window_s=0.0)
    hot = {'queue_depth': 6, 'queue_high_water': 6}
    cold = {'queue_depth': 0, 'queue_high_water': 0}
    e0 = _StubEngine(window=dict(hot))
    r.add_model('m', [e0])
    built = []

    def builder(reason):
        built.append(reason)
        return _StubEngine(window=dict(cold))

    a = Autoscaler(r, 'm', AutoscalePolicy(
        min_replicas=1, max_replicas=2, scale_up_at=4.0,
        scale_down_at=0.5, cooldown_s=0.2), builder=builder)
    assert a.tick() == 'up'
    # the build runs OFF the tick thread (poll must not stall on a
    # sharded restore); the replica lands shortly after
    deadline = time.monotonic() + 5
    while len(r.replicas('m')) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert built == ['scale_up']
    assert len(r.replicas('m')) == 2
    # cooldown: no immediate second action even though pressure persists
    assert a.tick() is None
    time.sleep(0.25)
    # at max_replicas: pressure can no longer scale up
    assert a.tick() is None
    # pressure drops -> scale down to min, draining the idle replica
    e0.window = dict(cold)
    time.sleep(0.25)
    assert a.tick() == 'down'
    assert len(r.replicas('m')) == 1
    time.sleep(0.25)
    assert a.tick() is None          # min_replicas floor
    ev = obs_events('serving.autoscale')
    assert [e['fields']['direction'] for e in ev] == ['up', 'down']
    ev = obs_events('serving.replica.drain')
    assert ev and ev[-1]['fields']['reason'] == 'scale_down'
    r.shutdown(drain=False)


def test_autoscale_policy_validation():
    with pytest.raises(ValueError, match='min_replicas'):
        AutoscalePolicy(min_replicas=0)
    with pytest.raises(ValueError, match='scale_down_at'):
        AutoscalePolicy(scale_up_at=1.0, scale_down_at=2.0)
    with pytest.raises(ValueError, match='builder'):
        Autoscaler(Router(), 'm', AutoscalePolicy())


# ---------------------------------------------------------------------------
# sharded replicas: program-only artifact + sharded-checkpoint restore
# ---------------------------------------------------------------------------

def test_save_serving_program_writes_no_params(artifacts):
    names = os.listdir(artifacts['model_dir'])
    assert '__model__.json' in names
    assert not [n for n in names if 'params' in n], names


def test_sharded_predictor_never_dense_and_matches(artifacts,
                                                   obs_events):
    pred = ShardedPredictor(artifacts['model_dir'],
                            mesh_axes={'dp': 8},
                            ckpt_dir=artifacts['ckpt_dir'])
    # the table lives as per-device row shards — never dense anywhere
    assert pred.shard_shapes()['emb_w'] == (VOCAB // 8, DIM)
    assert pred.state_step == 7
    out = pred.run({'ids': artifacts['probe']})
    np.testing.assert_allclose(np.asarray(out[0]), artifacts['ref'],
                               rtol=1e-4, atol=1e-5)
    sp = obs_events('serving.sharded_restore')
    assert sp and sp[-1]['fields']['restored'] == 2
    # reshard-on-restore: the same checkpoint (saved on dp=8) comes up
    # on a dp=4 serving mesh, still sharded, same scores
    pred4 = ShardedPredictor(artifacts['model_dir'],
                             mesh_axes={'dp': 4},
                             ckpt_dir=artifacts['ckpt_dir'])
    assert pred4.shard_shapes()['emb_w'] == (VOCAB // 4, DIM)
    out4 = pred4.run({'ids': artifacts['probe']})
    np.testing.assert_allclose(np.asarray(out4[0]), artifacts['ref'],
                               rtol=1e-4, atol=1e-5)


def test_sharded_predictor_serving_wire_zero_steady_compiles(artifacts):
    """The all_to_all lookup wire on the SERVING path: engine warmup
    pre-compiles the bucket set, then steady traffic performs zero
    compiles (the PR 8 contract, now over a sharded Program)."""
    eng = serving.sharded_replica(
        artifacts['model_dir'], mesh_axes={'dp': 8},
        ckpt_dir=artifacts['ckpt_dir'], config=_cfg(buckets=[4, 8]))
    try:
        exe = eng._model._exe
        misses0 = exe.cache_stats['misses']
        for i in range(6):
            n = 3 if i % 2 else 8     # both buckets exercised
            out = eng.predict({'ids': artifacts['probe'][:n]},
                              timeout=60)
            np.testing.assert_allclose(np.asarray(out[0]),
                                       artifacts['ref'][:n],
                                       rtol=1e-4, atol=1e-5)
        assert exe.cache_stats['misses'] == misses0
    finally:
        eng.shutdown()


def test_sharded_predictor_missing_state_is_typed(artifacts, tmp_path):
    partial = str(tmp_path / 'partial_ck')
    arrays, _ = ck.load_latest_verified(artifacts['ckpt_dir'])
    ck.save_sharded(os.path.join(partial, 'sharded_1'),
                    {'emb_w': arrays['emb_w']}, step=1)
    with pytest.raises(RuntimeError, match='fc_w'):
        ShardedPredictor(artifacts['model_dir'], mesh_axes={'dp': 8},
                         ckpt_dir=partial)


def test_sharded_predictor_needs_a_mesh(artifacts, tmp_path):
    # strip the mesh from a copy of the program artifact
    with open(os.path.join(artifacts['model_dir'],
                           '__model__.json')) as f:
        meta = json.load(f)
    meta['program'].pop('mesh', None)
    os.makedirs(str(tmp_path / 'm'))
    with open(str(tmp_path / 'm' / '__model__.json'), 'w') as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match='mesh'):
        ShardedPredictor(str(tmp_path / 'm'))


def test_sharded_replica_takes_row_deltas(artifacts):
    """The streaming freshness path lands on a SHARDED table: push_rows
    scatters into the mesh-placed array; scores move accordingly."""
    eng = serving.sharded_replica(
        artifacts['model_dir'], mesh_axes={'dp': 8},
        ckpt_dir=artifacts['ckpt_dir'], config=_cfg())
    try:
        probe = np.zeros((8, 2, 1), np.int64)     # every lookup hits row 0
        before = np.asarray(eng.predict({'ids': probe}, timeout=60)[0])
        rows = np.full((1, DIM), 3.0, np.float32)
        assert eng.push_rows({'emb_w': (np.array([0]), rows)}) == 1
        after = np.asarray(eng.predict({'ids': probe}, timeout=60)[0])
        assert not np.allclose(before, after)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# pod registry + cross-host routing (in-process workers)
# ---------------------------------------------------------------------------

def _fake_model(delay=0.0, scale=2.0):
    class M(object):
        feed_names = ['x']

        def run(self, feed):
            if delay:
                time.sleep(delay)
            return [np.asarray(feed['x']) * scale]
    return M()


def _fake_engine(delay=0.0, scale=2.0, **cfg):
    cfg.setdefault('max_batch_size', 4)
    cfg.setdefault('buckets', [4])
    cfg.setdefault('max_queue_delay_ms', 0.5)
    return ServingEngine(_fake_model(delay, scale), ServingConfig(**cfg))


def test_pod_registry_roundtrip_and_retire(tmp_path, obs_events,
                                           transport):
    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        key = w.serve('m', _fake_engine())
        assert os.path.exists(os.path.join(
            pod, 'registry', 'replica.%s.json' % key))
        view = r.wait_for_replicas('m', 1, timeout=10)
        assert view[0]['host'] == 0 and view[0]['key'] == key
        out = r.predict('m', {'x': np.ones((2, 3), np.float32)},
                        timeout=20)
        np.testing.assert_allclose(out[0],
                                   2.0 * np.ones((2, 3), np.float32))
        # voluntary retire: registration file gone -> replica removed
        w.retire(key)
        deadline = time.monotonic() + 10
        while r.replicas('m') and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert r.replicas('m') == []
        ev = obs_events('serving.replica.register')
        assert any(e['fields'].get('key') == key for e in ev)
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_remote_typed_errors_cross_the_wire(tmp_path, transport):
    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('m', _fake_engine())
        r.wait_for_replicas('m', 1, timeout=10)
        # a malformed feed fails TYPED through the wire (ValueError
        # from the remote engine, not an opaque timeout)
        fut = r.submit('m', {'wrong_name': np.ones((2, 3), np.float32)})
        with pytest.raises(ValueError, match='feed names'):
            fut.result(20)
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_pod_host_loss_rerouted_futures_and_heal(tmp_path, obs_events,
                                                 transport):
    """The in-process self-healing drill: two hosts serve one model;
    host 1 dies mid-traffic (beats stop, spool freezes — SIGKILL as the
    router sees it); every future pending against it is re-routed to
    host 0 (ZERO dropped futures), the loss is typed HostLost, and the
    heal path builds a replacement on the survivor."""
    pod = str(tmp_path / 'pod')
    built = []

    def builder(reason):
        built.append(reason)
        return _fake_engine()

    w0 = PodWorker(pod, host=0, builders={'m': builder},
                   beat_interval=0.05, transport=transport)
    w1 = PodWorker(pod, host=1, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=0.5, start=False)
    x = np.ones((2, 3), np.float32)
    try:
        w0.serve('m', _fake_engine())
        w1.serve('m', _fake_engine())
        r.wait_for_replicas('m', 2, timeout=10)
        # warm the dispatch path, then kill host 1 with traffic pending
        assert r.predict('m', {'x': x}, timeout=20)
        w1.simulate_death()
        futs = [r.submit('m', {'x': x}) for _ in range(12)]
        deadline = time.monotonic() + 15
        while not r.lost_hosts and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        rec = r.lost_hosts[0]
        assert rec['host'] == 1 and rec['stale'] == [1]
        assert 'HostLost' in rec['error']           # typed verdict
        # zero dropped futures: every submit resolves with the right value
        for f in futs:
            np.testing.assert_allclose(f.result(30)[0], 2.0 * x)
        # self-heal: the survivor built + registered a replacement
        deadline = time.monotonic() + 20
        while len(r.replicas('m')) < 2 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        view = r.replicas('m')
        assert len(view) == 2 and all(v['host'] == 0 for v in view)
        assert built and built[0] == 'host_lost'
        ev = obs_events('serving.replica.lost')
        assert ev and ev[-1]['fields']['host'] == 1
        ev = obs_events('serving.replica.reshard')
        assert ev and ev[-1]['fields']['host'] == 0
        assert obs_events('router.host_lost')
        # a push against a bare-callable replica is refused TYPED
        # through the wire (DeltaUnsupported — no parameter scope), not
        # an opaque timeout: the remote error mapping covers the
        # publisher's failure posture
        from paddle_tpu.serving.engine import DeltaUnsupported
        with pytest.raises(DeltaUnsupported):
            r.push_deltas('m', {'w': (np.array([0]),
                                      np.zeros((1, 2), np.float32))})
        # the dead host is no longer a heal/scale candidate: a fresh
        # capacity request must land on the survivor, never on the
        # orphaned host-1 advert (its ctl mailbox answers nothing)
        assert 1 not in r._hosts
        token = r.request_heal('m', reason='scale_up')
        assert token is not None
        deadline = time.monotonic() + 20
        while len(r.replicas('m')) < 3 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert [v['host'] for v in r.replicas('m')] == [0, 0, 0]
    finally:
        r.shutdown(drain=False)
        w0.shutdown()
        w1.shutdown()


def test_pod_push_deltas_reaches_survivor_set(tmp_path, artifacts,
                                              transport):
    """Sharded replicas + host loss + heal, then Router.push_deltas —
    the DeltaPublisher contract against the RE-REGISTERED set: the push
    lands on every live (healed) replica through the wire."""
    pod = str(tmp_path / 'pod')
    w0 = PodWorker(pod, host=0,
                   builders={'rec': _builder(artifacts, 4)},
                   beat_interval=0.05, transport=transport)
    w1 = PodWorker(pod, host=1, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=0.5, start=False)
    try:
        w0.serve('rec', _builder(artifacts, 8)('boot'))
        w1.serve('rec', _builder(artifacts, 4)('boot'))
        r.wait_for_replicas('rec', 2, timeout=30)
        w1.simulate_death()
        deadline = time.monotonic() + 15
        while not r.lost_hosts and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        deadline = time.monotonic() + 60
        while len(r.replicas('rec')) < 2 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert all(v['host'] == 0 for v in r.replicas('rec'))
        rows = np.full((2, DIM), 0.25, np.float32)
        pushed = r.push_deltas('rec', {'emb_w': (np.array([0, 1]), rows)})
        assert pushed == 2                      # both healed replicas
        probe = np.zeros((8, 2, 1), np.int64)
        out = np.asarray(r.predict('rec', {'ids': probe}, timeout=60)[0])
        assert np.isfinite(out).all()
    finally:
        r.shutdown(drain=False)
        w0.shutdown()
        w1.shutdown()


def test_pod_autoscale_up_via_heal_and_down(tmp_path, obs_events,
                                            transport):
    pod = str(tmp_path / 'pod')
    built = []

    def builder(reason):
        built.append(reason)
        return _fake_engine()

    w = PodWorker(pod, host=0, builders={'m': builder},
                  beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.0,
                  heartbeat_timeout=5.0, start=False)
    try:
        # a slow replica so queued pressure is visible in the window
        w.serve('m', _fake_engine(delay=0.05))
        r.wait_for_replicas('m', 1, timeout=10)
        a = r.enable_autoscale('m', AutoscalePolicy(
            min_replicas=1, max_replicas=2, scale_up_at=3.0,
            scale_down_at=0.25, cooldown_s=0.3))
        x = np.ones((1, 2), np.float32)
        futs = [r.submit('m', {'x': x}) for _ in range(10)]
        deadline = time.monotonic() + 20
        while len(r.replicas('m')) < 2 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert len(r.replicas('m')) == 2        # scaled up via heal
        assert built == ['scale_up']
        for f in futs:
            f.result(30)
        # idle -> scale back down to the floor
        deadline = time.monotonic() + 30
        while len(r.replicas('m')) > 1 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.1)
        assert len(r.replicas('m')) == 1
        dirs = [e['fields']['direction']
                for e in obs_events('serving.autoscale')]
        assert dirs[0] == 'up' and 'down' in dirs
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_heal_failure_redispatches_to_capable_host(tmp_path,
                                                   obs_events,
                                                   transport):
    pod = str(tmp_path / 'pod')
    built = []

    def bad_builder(reason):
        raise RuntimeError('no capacity on this host')

    def good_builder(reason):
        built.append(reason)
        return _fake_engine()

    w1 = PodWorker(pod, host=1, builders={'m': bad_builder},
                   beat_interval=0.05, transport=transport)
    w2 = PodWorker(pod, host=2, builders={'m': good_builder},
                   beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        key = w2.serve('m', _fake_engine())
        r.wait_for_replicas('m', 1, timeout=10)
        # host 1 has fewer replicas -> picked first; its failure must
        # re-dispatch to host 2 (one bounded retry, typed event)
        token = r.request_heal('m', reason='drill')
        assert token is not None
        deadline = time.monotonic() + 20
        while len(r.replicas('m')) < 2 and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert len(r.replicas('m')) == 2
        assert built == ['drill']
        ev = obs_events('serving.pod.heal_failed')
        assert ev and ev[-1]['fields']['host'] == 1
        ev = obs_events('serving.replica.reshard')
        assert ev and ev[-1]['fields']['host'] == 2
        del key
    finally:
        r.shutdown(drain=False)
        w1.shutdown()
        w2.shutdown()


def test_decode_engine_replica_behind_the_pod_wire(tmp_path, transport):
    """The decode path rides the same registry: a DecodeEngine replica
    registered by a PodWorker serves autoregressive requests through
    the PodRouter — result tuples (ids, scores) and decode kwargs
    (max_new_tokens) cross the wire, matching the in-process engine."""
    rng = np.random.RandomState(7)
    weights = {
        'w_dec': (rng.randn(8 + 6, 32) * 0.3).astype(np.float32),
        'u_dec': (rng.randn(8, 32) * 0.3).astype(np.float32),
        'b_dec': (rng.randn(1, 32) * 0.1).astype(np.float32),
        'w_q': (rng.randn(8, 6) * 0.3).astype(np.float32),
        'w_emb': (rng.randn(20, 8) * 0.3).astype(np.float32),
        'w_out': (rng.randn(8, 20) * 0.3).astype(np.float32),
        'b_out': (rng.randn(1, 20) * 0.1).astype(np.float32),
    }

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=3, max_len=8, src_cap=5))

    enc = (rng.randn(4, 6) * 0.5).astype(np.float32)
    local = build()
    want_ids, want_scores = local.submit(
        {'enc': enc}, max_new_tokens=6).result(60)
    local.shutdown()

    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('mt', build())
        r.wait_for_replicas('mt', 1, timeout=10)
        got = r.submit('mt', {'enc': enc}, max_new_tokens=6).result(60)
        np.testing.assert_array_equal(np.asarray(got[0]), want_ids)
        np.testing.assert_allclose(np.asarray(got[1]), want_scores,
                                   rtol=1e-5, atol=1e-6)
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_heal_chain_terminates_when_every_builder_fails(tmp_path,
                                                        obs_events,
                                                        transport):
    """The exclude set ACCUMULATES through the re-dispatch token chain:
    with every capable host failing its build, the chain ends in a
    typed heal_unroutable instead of ping-ponging forever."""
    def bad(reason):
        raise RuntimeError('corrupt checkpoint')

    pod_dir = str(tmp_path / 'pod')
    w1 = PodWorker(pod_dir, host=1, builders={'m': bad},
                   beat_interval=0.05, transport=transport)
    w2 = PodWorker(pod_dir, host=2, builders={'m': bad},
                   beat_interval=0.05, transport=transport)
    r = PodRouter(pod_dir, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w2.serve('m', _fake_engine())
        r.wait_for_replicas('m', 1, timeout=10)
        assert r.request_heal('m', reason='drill') is not None
        deadline = time.monotonic() + 20
        while not obs_events('serving.pod.heal_unroutable') \
                and time.monotonic() < deadline:
            r.poll()
            time.sleep(0.05)
        assert obs_events('serving.pod.heal_unroutable')
        assert r.pending_heals() == {}          # chain terminated
        # exactly one failure per capable host, no ping-pong
        redispatches = obs_events('serving.pod.heal_redispatch')
        assert 1 <= len(redispatches) <= 2
        assert len(r.replicas('m')) == 1        # nothing half-built
    finally:
        r.shutdown(drain=False)
        w1.shutdown()
        w2.shutdown()


# ---------------------------------------------------------------------------
# tentpole: the rpc pod wire — frames, chaos, per-token streams, failover
# ---------------------------------------------------------------------------

def _mt_weights(vocab=20, dim=8, src=6, hidden=32, seed=7):
    rng = np.random.RandomState(seed)
    w = {
        'w_dec': (rng.randn(dim + src, hidden) * 0.3).astype(np.float32),
        'u_dec': (rng.randn(dim, hidden) * 0.3).astype(np.float32),
        'b_dec': (rng.randn(1, hidden) * 0.1).astype(np.float32),
        'w_q': (rng.randn(dim, src) * 0.3).astype(np.float32),
        'w_emb': (rng.randn(vocab, dim) * 0.3).astype(np.float32),
        'w_out': (rng.randn(dim, vocab) * 0.3).astype(np.float32),
        'b_out': (rng.randn(1, vocab) * 0.1).astype(np.float32),
    }
    enc = (rng.randn(4, src) * 0.5).astype(np.float32)
    return w, enc


def _wait(pred, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(step)
    return pred()


class _PollPump(object):
    """Drive PodRouter.poll() from a background thread while a test
    body blocks on a stream — failover detection must not depend on
    the consumer's goodwill."""

    def __init__(self, router, period=0.05):
        self._r, self._period = router, period
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            self._r.poll()
            time.sleep(self._period)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(5)


def test_transport_frame_roundtrip_and_counters():
    """The length-prefixed frame codec end to end: JSON header plus raw
    ndarray blobs cross a real socket BIT-EXACT (no base64, no pickle),
    and the wire telemetry counts frames/bytes both ways."""
    f_out0 = obs.counter('serving.transport.frames_out').value
    f_in0 = obs.counter('serving.transport.frames_in').value
    got = []
    ev = threading.Event()

    def handler(conn, header, arrays):
        conn.send({'uid': header['uid'], 'final': True,
                   'echo': header['meta']},
                  {k: v for k, v in arrays.items()})

    srv = RpcServer(handler)
    arrays = {
        'f:a': np.arange(12, dtype=np.float32).reshape(3, 4),
        'f:b': np.array([[1, -2], [3, -4]], np.int64),
        'f:c': np.array([True, False]),
    }

    def on_frame(header, arrs):
        got.append((header, arrs))
        ev.set()

    ch = Channel(srv.addr, on_frame, seed=1)
    try:
        meta = {'max_new_tokens': 6, 'nested': {'x': [1, 2.5, None]}}
        assert _wait(lambda: ch.send(
            {'op': 'submit', 'uid': 'u1', 'meta': meta}, arrays), 5)
        assert ev.wait(10), 'no echo frame'
        header, arrs = got[0]
        assert header['echo'] == meta          # JSON survives verbatim
        for name, want in arrays.items():
            assert arrs[name].dtype == want.dtype
            np.testing.assert_array_equal(arrs[name], want)
        assert obs.counter('serving.transport.frames_out').value > f_out0
        assert obs.counter('serving.transport.frames_in').value > f_in0
    finally:
        ch.close()
        srv.close()


def test_transport_overload_rejects_typed():
    """Wire-level admission: a server at max_inflight answers a typed
    ServerOverloaded error frame instead of queueing unboundedly — the
    engine admission contract, enforced one layer down."""
    release = threading.Event()

    def handler(conn, header, arrays):
        # reply later, off the reader thread (the engine posture)
        def finish():
            release.wait(20)
            conn.send({'uid': header['uid'], 'final': True})
        threading.Thread(target=finish, daemon=True).start()

    srv = RpcServer(handler, max_inflight=1)
    frames = []
    ev = threading.Event()

    def on_frame(header, arrs):
        frames.append(header)
        ev.set()

    ch = Channel(srv.addr, on_frame, seed=2)
    try:
        assert _wait(lambda: ch.send({'op': 'submit', 'uid': 'u1'}), 5)
        # second submit while the first is parked at the handler
        assert _wait(lambda: ch.send({'op': 'submit', 'uid': 'u2'}), 5)
        assert ev.wait(10)
        rejected = [h for h in frames if h.get('error')]
        assert rejected, frames
        assert rejected[0]['error']['type'] == 'ServerOverloaded'
        release.set()
        assert _wait(lambda: any(not h.get('error') for h in frames), 10)
    finally:
        release.set()
        ch.close()
        srv.close()


def test_chaos_garble_fails_typed_never_hangs():
    """A corrupted in-flight frame must surface as a typed
    TransportError at the reader — bad magic/bounds, not a hang and
    not a silently misparsed frame."""
    def handler(conn, header, arrays):
        conn.send({'uid': header['uid'], 'final': True},
                  {'a': arrays['f:a']})

    srv = RpcServer(handler)
    fi = FaultInjector(seed=3)
    proxy = fi.chaos_proxy(srv.addr)
    frames, errs = [], []
    ev = threading.Event()
    ch = Channel(proxy.addr, lambda h, a: (frames.append(h), ev.set()),
                 on_wire_error=errs.append, seed=11)
    a = np.ones((2, 3), np.float32)
    try:
        assert _wait(lambda: ch.send(
            {'op': 'submit', 'uid': 'u1'}, {'f:a': a}), 5)
        assert ev.wait(10)
        # corrupt the next server->client chunk: the reply frame
        proxy.garble(8, direction='down')
        ch.send({'op': 'submit', 'uid': 'u2'}, {'f:a': a})
        assert _wait(lambda: errs, 10), 'garble never surfaced'
        assert isinstance(errs[0], TransportError)
        assert obs.counter('serving.transport.errors').value >= 1
    finally:
        ch.close()
        proxy.close()
        srv.close()


def test_chaos_sever_reconnects_with_backoff():
    """A mid-stream connection cut is a network blip, not a dead host:
    the Channel redials on the shared utils/retry backoff schedule and
    traffic flows again through a NEW pairing."""
    def handler(conn, header, arrays):
        conn.send({'uid': header['uid'], 'final': True,
                   'echo': header.get('x')})

    srv = RpcServer(handler)
    fi = FaultInjector(seed=5)
    proxy = fi.chaos_proxy(srv.addr)
    frames, reconnects = [], []
    ev = threading.Event()
    ch = Channel(proxy.addr, lambda h, a: (frames.append(h), ev.set()),
                 on_reconnect=lambda: reconnects.append(1), seed=13)
    try:
        assert _wait(lambda: ch.send(
            {'op': 'submit', 'uid': 'u1', 'x': 1}), 5)
        assert ev.wait(10)
        proxy.sever()
        ev.clear()
        del frames[:]

        def resend():
            ch.send({'op': 'submit', 'uid': 'u2', 'x': 2})
            # a straggler duplicate echo of u1 may race the clear above
            # (the chaos proxy duplicates frames); the contract is that
            # the NEW pairing carries u2's echo, not that nothing stale
            # ever lands first
            return any(f.get('echo') == 2 for f in frames)

        assert _wait(resend, 15, step=0.1), 'no echo after sever'
        assert reconnects, 'reconnect hook never fired'
    finally:
        ch.close()
        proxy.close()
        srv.close()


def test_stream_inprocess_matches_submit(obs_events):
    """Router.stream over a local DecodeEngine: per-token callbacks
    arrive ordered 1..N, the final result is BIT-EQUAL to a plain
    submit of the same request, and TTFT is stamped end to end."""
    weights, enc = _mt_weights()

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=3, max_len=8, src_cap=5))

    ref_eng = build()
    want_ids, want_scores = ref_eng.submit(
        {'enc': enc}, max_new_tokens=6).result(60)
    ref_eng.shutdown()

    r = Router(window_s=0.0)
    r.add_model('mt', [build()])
    try:
        s = r.stream('mt', {'enc': enc}, max_new_tokens=6)
        toks = [(t, ids.copy()) for t, ids in s]
        assert [t for t, _ in toks] == list(range(1, 7))
        got_ids, got_scores = s.result(10)
        np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
        np.testing.assert_allclose(np.asarray(got_scores), want_scores,
                                   rtol=1e-5, atol=1e-6)
        assert s.ttft_s is not None and s.ttft_s > 0
        assert obs_events('serving.stream.open')
        first = obs_events('serving.stream.first_token')
        assert first and first[-1]['fields']['ttft_s'] > 0
        # done-callbacks race the result() waiter: wait for the close
        assert _wait(lambda: obs_events('serving.stream.close'), 5)
        closes = obs_events('serving.stream.close')
        assert closes[-1]['fields']['tokens'] == 6
    finally:
        r.shutdown(drain=False)


def test_stream_backpressure_never_drops_or_reorders(tmp_path):
    """A slow consumer on the rpc wire: the producer decodes far ahead
    of the reader, yet every token arrives exactly once, in order —
    the wire may buffer or stall, it may never drop or reorder."""
    weights, enc = _mt_weights()

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=16, src_cap=5))

    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport='rpc')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('mt', build())
        r.wait_for_replicas('mt', 1, timeout=30)
        s = r.stream('mt', {'enc': enc}, max_new_tokens=12)
        ts = []
        for t, ids in s:
            ts.append(t)
            time.sleep(0.03)          # consumer far slower than decode
        assert ts == list(range(1, 13)), ts
        ids, scores = s.result(10)
        assert np.asarray(ids).shape[1] == 12
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_stream_on_file_wire_is_typed_error(tmp_path):
    """The file mailbox cannot carry per-token frames: asking it to
    stream fails TYPED at submit time, naming the rpc transport —
    never a silent fallback to a whole-response future."""
    weights, enc = _mt_weights()
    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport='file')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('mt', DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=8, src_cap=5)))
        r.wait_for_replicas('mt', 1, timeout=30)
        with pytest.raises(ValueError, match="transport='rpc'"):
            s = r.stream('mt', {'enc': enc}, max_new_tokens=4)
            s.result(20)
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_stream_cancel_frees_slot_and_pages():
    """Mid-stream disconnect posture: cancelling a live stream aborts
    the slot and returns its PAGES to the pool — an abandoned stream
    must not leak decode capacity."""
    weights, enc = _mt_weights()
    eng = DecodeEngine(weights, DecodeConfig(
        slots=2, beam_size=1, max_len=64, src_cap=5,
        page_size=4, pages=40, prefix_cache=False))
    r = Router(window_s=0.0)
    r.add_model('mt', [eng])
    try:
        base = eng.stats
        seen = []
        s = r.stream('mt', {'enc': enc}, max_new_tokens=60)
        for t, ids in s:
            seen.append(t)
            if t >= 3:
                break
        s.cancel()
        with pytest.raises(Exception) as ei:
            s.result(20)
        assert type(ei.value).__name__ in ('StreamCancelled',
                                           'CancelledError')
        assert _wait(lambda: eng.stats['slots_occupied'] == 0, 10)
        assert _wait(lambda: eng.stats['pages_free']
                     == base['pages_free'], 10), eng.stats
        assert eng.stats['cancelled'] >= 1
        # capacity really is back: a fresh request decodes to the end
        ids, scores = r.predict('mt', {'enc': enc}, timeout=60,
                                max_new_tokens=4)
        assert np.asarray(ids).shape[1] == 4
    finally:
        r.shutdown(drain=False)


def test_stream_cadence_zero_host_loss_is_typed(tmp_path, obs_events):
    """ckpt_every=0 means the stream opted OUT of failover: losing the
    host mid-generation surfaces a typed HostLost naming the cadence
    knob — never a resume from state that was never checkpointed and
    never a hang."""
    weights, enc = _mt_weights()

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=40, src_cap=5))

    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport='rpc')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=0.5, start=False)
    try:
        w.serve('mt', build())
        r.wait_for_replicas('mt', 1, timeout=30)
        r.predict('mt', {'enc': enc}, timeout=120, max_new_tokens=2)
        with _PollPump(r):
            s = r.stream('mt', {'enc': enc}, max_new_tokens=32)
            for t, ids in s:
                if t == 3:
                    w.simulate_death()
                    break
            with pytest.raises(HostLost, match='ckpt_every'):
                s.result(60)
        ev = obs_events('serving.stream.failover')
        assert ev and ev[-1]['fields']['resumed'] is False
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_decode_stream_failover_token_exact(tmp_path, obs_events):
    """THE HEADLINE DRILL: a decode stream survives the death of the
    host generating it. Host 0 dies (SIGKILL posture: rpc frames
    freeze, beats stop, the checkpoint goes stale) mid-generation;
    the router re-routes the stream to the survivor, which resumes
    from the per-slot checkpoint. The client sees one ordered token
    sequence 1..N and a final result BIT-EQUAL to an uninterrupted
    reference — zero dropped futures, no restart from token 0."""
    weights, enc = _mt_weights()
    N = 32

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=40, src_cap=5))

    ref_eng = build()
    want_ids, want_scores = ref_eng.submit(
        {'enc': enc}, max_new_tokens=N).result(120)
    ref_eng.shutdown()

    pod = str(tmp_path / 'pod')
    w0 = PodWorker(pod, host=0, beat_interval=0.05, transport='rpc')
    w1 = PodWorker(pod, host=1, beat_interval=0.05, transport='rpc')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=0.5, start=False)
    workers = {0: w0, 1: w1}
    resumes0 = obs.counter('serving.stream.resumes').value
    try:
        e0 = build()
        e1 = build()
        engines = {0: e0, 1: e1}
        # warm BOTH engines so post-kill compiles are attributable to
        # the resume path alone (the zero-new-signatures contract)
        for e in (e0, e1):
            e.submit({'enc': enc}, max_new_tokens=2).result(120)
        misses_before = {h: e.cache_stats()['misses']
                         for h, e in engines.items()}
        w0.serve('mt', e0)
        w1.serve('mt', e1)
        r.wait_for_replicas('mt', 2, timeout=60)

        toks, killed = [], []
        with _PollPump(r):
            s = r.stream('mt', {'enc': enc}, ckpt_every=2,
                         max_new_tokens=N)
            for t, ids in s:
                toks.append((t, np.asarray(ids).copy()))
                if t == 3 and not killed:
                    for info in list(r._known.values()):
                        if info['proxy'].outstanding():
                            workers[info['host']].simulate_death()
                            killed.append(info['host'])
            got_ids, got_scores = s.result(120)
        assert len(killed) == 1                      # one host died
        survivor = engines[1 - killed[0]]
        # one ordered stream, no gap, no duplicate, no restart at 0
        assert [t for t, _ in toks] == list(range(1, N + 1))
        # token-exact: final beams bit-equal to the uninterrupted run
        np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
        np.testing.assert_allclose(np.asarray(got_scores), want_scores,
                                   rtol=1e-5, atol=1e-6)
        # the resume rode the checkpoint (typed event + counters), and
        # the survivor resumed WITHOUT compiling a new signature
        assert obs.counter('serving.stream.resumes').value == resumes0 + 1
        ev = obs_events('serving.stream.resume')
        assert ev, 'no stream.resume event'
        f = ev[-1]['fields']
        assert f['from_t'] >= 1 and f['replayed'] >= 0
        assert survivor.stats['resumed'] >= 1
        assert survivor.cache_stats()['misses'] \
            == misses_before[1 - killed[0]]
        ev = obs_events('router.host_lost')
        assert ev and ev[-1]['fields']['host'] == killed[0]
    finally:
        r.shutdown(drain=False)
        w0.shutdown()
        w1.shutdown()


# ---------------------------------------------------------------------------
# distributed tracing across the pod (docs/observability.md#tracing)
# ---------------------------------------------------------------------------

def test_trace_stitched_timeline_across_the_wire(tmp_path, obs_events,
                                                 transport):
    """One request over EACH wire produces ONE stitched timeline: the
    caller's trace context crosses the wire (rpc frame header / file
    __meta__ JSON), the worker re-enters it, and the collector stitches
    router + host spans into monotonic stage boundaries under a single
    trace_id."""
    weights, enc = _mt_weights()
    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport=transport)
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('mt', DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=12, src_cap=5)))
        r.wait_for_replicas('mt', 1, timeout=30)
        ctx = trace.new_trace()
        with trace.activate(ctx, node='client'):
            if transport == 'rpc':
                s = r.stream('mt', {'enc': enc}, max_new_tokens=6)
                assert [t for t, _ in s] == list(range(1, 7))
                s.result(60)
                # BOTH TTFT views exposed: client-side and the
                # server-side dispatch->token-1 twin off the frame header
                assert s.ttft_s is not None and s.ttft_s > 0
                assert s.server_ttft_s is not None
                assert 0 < s.server_ttft_s <= s.ttft_s
            else:
                r.predict('mt', {'enc': enc}, timeout=60,
                          max_new_tokens=6)
        r.spill_traces(force=True)
        coll = trace.TraceCollector(os.path.join(pod, 'traces'))
        coll.load()
        assert ctx.trace_id in coll.traces()
        tl = coll.timeline(ctx.trace_id)
        assert 'router' in tl['nodes'] and 'h0' in tl['nodes']
        serves = [s_ for s_ in tl['spans']
                  if s_['name'] == 'serving.pod.serve']
        assert serves and serves[0]['fields'].get('wire') == transport
        assert tl['orphans'] == []
        # stage boundaries exist and are MONOTONIC end to end
        names = [m['name'] for m in tl['milestones']]
        assert names[0] == 'admit' and names[-1] == 'done'
        assert 'serve' in names and 'dispatch' in names
        if transport == 'rpc':
            assert 'first_token' in names
        ts = [m['t'] for m in tl['milestones']]
        assert ts == sorted(ts)
        assert all(st['seconds'] >= 0 for st in tl['stages'])
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_trace_survives_stream_failover_with_orphan_flag(tmp_path,
                                                         obs_events):
    """SIGKILL mid-stream: the resumed segment rides the ORIGINAL
    trace_id (the router re-activates the stashed context before the
    survivor dispatch) and the dead host's serve span — spilled open,
    never closed — is flagged as an orphan in the stitched timeline."""
    weights, enc = _mt_weights()
    N = 16

    def build():
        return DecodeEngine(weights, DecodeConfig(
            slots=2, beam_size=1, max_len=24, src_cap=5))

    pod = str(tmp_path / 'pod')
    w0 = PodWorker(pod, host=0, beat_interval=0.05, transport='rpc')
    w1 = PodWorker(pod, host=1, beat_interval=0.05, transport='rpc')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=0.5, start=False)
    workers = {0: w0, 1: w1}
    try:
        w0.serve('mt', build())
        w1.serve('mt', build())
        r.wait_for_replicas('mt', 2, timeout=60)
        ctx = trace.new_trace()
        killed = []
        with _PollPump(r):
            with trace.activate(ctx, node='client'):
                s = r.stream('mt', {'enc': enc}, ckpt_every=2,
                             max_new_tokens=N)
            toks = []
            for t, ids in s:
                toks.append(t)
                if t == 3 and not killed:
                    for info in list(r._known.values()):
                        if info['proxy'].outstanding():
                            workers[info['host']].simulate_death()
                            killed.append(info['host'])
            s.result(120)
        assert len(killed) == 1
        assert toks == list(range(1, N + 1))     # token-exact resume
        r.spill_traces(force=True)
        coll = trace.TraceCollector(os.path.join(pod, 'traces'))
        coll.load()
        tl = coll.timeline(ctx.trace_id)
        serves = [s_ for s_ in tl['spans']
                  if s_['name'] == 'serving.pod.serve']
        hosts = {s_['node'] for s_ in serves}
        # BOTH segments — killed host's and survivor's — carry the
        # SAME trace_id
        assert hosts == {'h0', 'h1'}
        # the dead host's span never closed: flagged orphan
        assert len(tl['orphans']) >= 1
        orphan_nodes = {o['node'] for o in tl['orphans']}
        assert 'h%d' % killed[0] in orphan_nodes
        # the survivor's segment DID close inside the same trace
        closed = [s_ for s_ in serves if s_['t1'] is not None]
        assert any(s_['node'] == 'h%d' % (1 - killed[0])
                   for s_ in closed)
    finally:
        r.shutdown(drain=False)
        w0.shutdown()
        w1.shutdown()


def test_rpc_metrics_op_and_prom_dump(tmp_path):
    """Prometheus exposition over the pod: the rpc wire serves a
    `metrics` control frame (scrape without touching the registry
    process-locally) and the worker dumps the same text to
    `metrics.h<host>.prom` in the pod dir on its stats cadence."""
    pod = str(tmp_path / 'pod')
    w = PodWorker(pod, host=0, beat_interval=0.05, transport='rpc')
    r = PodRouter(pod, poll_s=0.05, window_s=0.05,
                  heartbeat_timeout=5.0, start=False)
    try:
        w.serve('m', _fake_engine())
        r.wait_for_replicas('m', 1, timeout=30)
        r.predict('m', {'x': np.ones((2, 3), np.float32)}, timeout=20)
        proxy = next(iter(r._known.values()))['proxy']
        text = proxy.metrics_text(timeout=10)
        assert '# TYPE' in text and '# HELP' in text
        assert 'serving_requests_total' in text
        # the file dump carries the SAME exposition format
        w._host_telemetry(force=True)
        path = os.path.join(pod, 'metrics.h0.prom')
        assert os.path.exists(path)
        assert '# TYPE' in open(path).read()
    finally:
        r.shutdown(drain=False)
        w.shutdown()


def test_set_mesh_data_axis_false_survives_round_trip():
    """The forced-replicate serving posture is a Program property like
    the amp flags: it must survive clone() and the _to_dict/_from_dict
    artifact round-trip (None would re-derive 'dp' on reload and
    silently re-shard request batches)."""
    p = framework.Program()
    p.set_mesh({'dp': 8}, data_axis=False)
    assert p._mesh_data_axis is False
    q = framework.Program._from_dict(p._to_dict())
    assert q.mesh_axes == {'dp': 8}
    assert q._mesh_data_axis is False
    assert p.clone()._mesh_data_axis is False
    # the default derivation is untouched
    d = framework.Program()
    d.set_mesh({'dp': 8})
    assert d._mesh_data_axis == 'dp'
    assert framework.Program._from_dict(
        d._to_dict())._mesh_data_axis == 'dp'


def test_pod_report_section(obs_events):
    obs.event('serving.replica.register', model='m', host=0, key='0.m-1')
    obs.event('serving.replica.register', model='m', host=1, key='1.m-1')
    obs.event('serving.replica.lost', model='m', host=1, key='1.m-1',
              pending=3)
    obs.event('router.host_lost', host=1, replicas=1, rerouted=3,
              heals=1)
    obs.event('serving.replica.reshard', model='m', host=0, key='0.m-2',
              token='t', heal_s=2.5)
    obs.event('serving.pod.heal_requested', model='m', host=0,
              token='t', reason='host_lost')
    obs.event('serving.autoscale', model='m', direction='up',
              replicas=1, pressure=5.0)
    text = obs_report.summarize(obs_events())
    assert '-- pod serving --' in text
    assert '2 registered across 2 host(s)' in text
    assert 'host LOST: h1' in text and '3 future(s) re-routed' in text
    assert 'reshard: model=m -> h0' in text
    assert 'autoscale: 1 up, 0 down' in text


def test_transport_streams_report_section(obs_events):
    obs.event('serving.transport.connect', addr=['127.0.0.1', 1])
    obs.event('serving.transport.reconnect', addr=['127.0.0.1', 1],
              attempts=3)
    obs.event('serving.transport.error', error='bad frame magic')
    obs.event('serving.stream.open', model='mt')
    obs.event('serving.stream.open', model='mt')
    obs.event('serving.stream.first_token', model='mt', ttft_s=0.2)
    obs.event('serving.stream.first_token', model='mt', ttft_s=0.4)
    obs.event('serving.stream.resume', model='mt', sid='s1', from_t=4,
              seen_t=5, replayed=1)
    obs.event('serving.stream.failover', model='mt', sid='None',
              resumed=False, seen_t=3)
    obs.event('serving.stream.close', model='mt', tokens=8, error=None)
    obs.event('serving.stream.close', model='mt', tokens=3,
              error='HostLost')
    text = obs_report.summarize(obs_events())
    assert '-- transport / streams --' in text
    assert '1 connect(s), 1 reconnect(s), 1 wire error(s)' in text
    assert 'streams: 2 opened, 2 closed (1 failed)' in text
    assert 'ttft: min=' in text
    assert '2 stream(s) lost a host, 1 resumed token-exact ' \
           '(1 token(s) replayed)' in text
    assert 'NOT resumed (ckpt_every=0)' in text


# ---------------------------------------------------------------------------
# the 2-process SIGKILL drill (the test_elastic.py harness, serving-side)
# ---------------------------------------------------------------------------

_POD_CHILD = r"""
import os, sys, time
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)
import numpy as np
from paddle_tpu import serving

host = int(sys.argv[1])
pod_dir, model_dir, ckpt_dir = sys.argv[2], sys.argv[3], sys.argv[4]
mesh_n, heal_n = int(sys.argv[5]), int(sys.argv[6])
stop_file = sys.argv[7]
transport = sys.argv[8] if len(sys.argv) > 8 else 'file'


def build(n):
    def b(reason):
        return serving.sharded_replica(
            model_dir, mesh_axes={'dp': n}, ckpt_dir=ckpt_dir,
            config=serving.ServingConfig(max_batch_size=8, buckets=[8],
                                         max_queue_delay_ms=1.0))
    return b


w = serving.PodWorker(pod_dir, host=host, transport=transport,
                      builders={'rec': build(heal_n)})
w.serve('rec', build(mesh_n)('boot'))
print('SERVING %d' % host)
sys.stdout.flush()
while not os.path.exists(stop_file):
    time.sleep(0.1)
w.shutdown()
print('STOPPED %d' % host)
"""


@pytest.mark.slow
def test_two_process_sigkill_mid_traffic(artifacts, tmp_path,
                                         obs_events, transport):
    """The acceptance drill: 2 serving host PROCESSES each serve the
    set_mesh-sharded Program (row-sharded table restored from the
    sharded checkpoint — never dense); one is SIGKILLed mid-traffic.
    Runs on BOTH wires: the rpc leg is the real-TCP SIGKILL case (the
    kernel resets the sockets; the router must see HostLost, not hang).
    Asserts: typed HostLost, ZERO dropped futures (every submit
    resolves with the right scores), the replica re-shards onto the
    survivor (dp=8 -> dp=4 via the PR 10 restore path), and post-
    recovery traffic performs zero steady-state compiles."""
    pod = str(tmp_path / 'pod')
    stop_file = str(tmp_path / 'stop')
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for host, mesh_n, heal_n in ((0, 8, 4), (1, 8, 4)):
        env = dict(os.environ, PYTHONPATH=here)
        env.pop('JAX_PLATFORMS', None)
        env.pop('XLA_FLAGS', None)
        env.pop('PADDLE_TPU_OBS_DIR', None)
        procs.append(subprocess.Popen(
            [sys.executable, '-c', _POD_CHILD, str(host), pod,
             artifacts['model_dir'], artifacts['ckpt_dir'],
             str(mesh_n), str(heal_n), stop_file, transport],
            env=env, cwd=here, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    r = PodRouter(pod, poll_s=0.1, window_s=0.1, heartbeat_timeout=1.5)
    probe, ref = artifacts['probe'], artifacts['ref']
    results, errors = [], []
    lock = threading.Lock()
    stop_traffic = threading.Event()

    def driver():
        while not stop_traffic.is_set():
            try:
                f = r.submit('rec', {'ids': probe})
                out = np.asarray(f.result(60)[0])
                with lock:
                    results.append(out)
            except Exception as e:  # noqa: BLE001 — counted, must be 0
                with lock:
                    errors.append(e)
            time.sleep(0.02)

    try:
        r.wait_for_replicas('rec', 2, timeout=240)
        threads = [threading.Thread(target=driver) for _ in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with lock:
                if len(results) >= 8:
                    break
            time.sleep(0.1)
        with lock:
            n_before = len(results)
        assert n_before >= 8, 'no pre-kill traffic completed'
        # SIGKILL host 1 mid-traffic (the elastic harness fault)
        procs[1].send_signal(signal.SIGKILL)
        t_kill = time.monotonic()
        while time.monotonic() - t_kill < 120:
            if r.lost_hosts:
                break
            time.sleep(0.1)
        assert r.lost_hosts and r.lost_hosts[0]['host'] == 1
        assert 'HostLost' in r.lost_hosts[0]['error']
        # survivor heals: replacement replica re-sharded onto dp=4
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            view = r.replicas('rec')
            if len(view) >= 2 and all(v['host'] == 0 for v in view):
                break
            time.sleep(0.2)
        view = r.replicas('rec')
        assert len(view) >= 2 and all(v['host'] == 0 for v in view)
        ev = obs_events('serving.replica.reshard')
        assert ev and ev[-1]['fields']['host'] == 0
        assert ev[-1]['fields'].get('mesh') == [['dp', 4]]
        # steady state after recovery: more traffic, zero compiles on
        # the survivor (its stats publish the executor counters)
        caches0 = {v['key']: 0 for v in view}
        time.sleep(1.0)
        for info in r._known.values():
            caches0[info['proxy'].key] = \
                (info['proxy'].cache_stats() or {}).get('misses') or 0
        with lock:
            n_mid = len(results)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with lock:
                if len(results) >= n_mid + 12:
                    break
            time.sleep(0.1)
        stop_traffic.set()
        for t in threads:
            t.join(60)
        for info in r._known.values():
            after = (info['proxy'].cache_stats() or {}).get('misses') or 0
            assert after == caches0.get(info['proxy'].key, after), \
                'replica %s compiled in steady state' % info['proxy'].key
        # ZERO dropped futures, every result correct
        assert errors == [], errors[:3]
        with lock:
            assert len(results) > n_before
            for out in results:
                np.testing.assert_allclose(out, ref, rtol=1e-4,
                                           atol=1e-5)
    finally:
        stop_traffic.set()
        with open(stop_file, 'w') as f:
            f.write('stop')
        r.shutdown(drain=False)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
    assert procs[1].returncode == -signal.SIGKILL
