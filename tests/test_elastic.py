"""Elastic pod training drills (docs/robustness.md#elastic).

The PR-1 fault-tolerance story re-done at pod scale: annotated (mesh)
programs checkpoint SHARDED through the Trainer (each host writes only
its shards — never a gathered dense table), saves commit atomically
(staging dir + manifest-last + rename, so a SIGKILL mid-save can never
leave a latest-looking torn serial), restore reshards onto whatever
topology survives (8 devices -> 4), and a heartbeat layer surfaces a
dead host as the typed parallel.HostLost after an emergency flush.

Every drill injects its faults through utils.faults.FaultInjector (or a
real SIGKILL on a child process), and the telemetry assertions verify an
operator could have SEEN each decision (docs/observability.md).
"""
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import obs, parallel
from paddle_tpu.obs import report as obs_report
from paddle_tpu.parallel import Heartbeat, HostLost
from paddle_tpu.utils import checkpoint as ck
from paddle_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.elastic

VOCAB, DIM = 64, 4


@pytest.fixture
def obs_events(tmp_path):
    """Run-log reader fixture (the test_faults idiom): behavior AND its
    telemetry are both asserted."""
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
# helpers: annotated trainers
# ---------------------------------------------------------------------------

_W = np.array([[1.5], [-2.0], [0.5], [3.0]], 'float32')


def _linear_train_func():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1,
                           param_attr=fluid.ParamAttr(name='w'),
                           bias_attr=fluid.ParamAttr(name='b'))
    return fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))


def _linear_reader(n=64, batch=8, seed=0):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n // batch):
            xs = rng.rand(batch, 4).astype('float32')
            ys = xs @ _W
            yield [(xs[i], ys[i]) for i in range(batch)]
    return r


def _emb_train_func():
    """Vocab-sharded table + fc head: the state whose checkpoint must
    NEVER gather dense (the adam moments inherit the annotation)."""
    ids = fluid.layers.data(name='ids', shape=[2, 1], dtype='int64')
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, DIM],
        param_attr=fluid.ParamAttr(name='emb_w', sharding=('dp', None)))
    pred = fluid.layers.fc(input=emb, size=1, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=fluid.ParamAttr(name='fc_w'))
    return fluid.layers.mean(fluid.layers.square(pred - 1.0))


def _emb_reader(n_batches=16, batch=8, seed=3):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n_batches):
            b = rng.randint(0, VOCAB, size=(batch, 2, 1)).astype('int64')
            yield [(b[i],) for i in range(batch)]
    return r


def _mesh_hook(axes):
    return lambda p: p.set_mesh(axes)


def _sgd():
    return fluid.optimizer.SGD(learning_rate=0.1)


def _adam():
    return fluid.optimizer.Adam(learning_rate=0.05)


class Crash(Exception):
    pass


def _losses_handler(losses, crash_at=None):
    def handler(ev):
        if isinstance(ev, fluid.EndStepEvent):
            losses.append(((ev.epoch, ev.step),
                           float(np.asarray(ev.metrics[0]))))
            if crash_at is not None and (ev.epoch, ev.step) == crash_at:
                raise Crash()
    return handler


def _named_shardings(state):
    from jax.sharding import NamedSharding
    return {n: v.sharding for n, v in state.items()
            if isinstance(v.sharding, NamedSharding)}


# ---------------------------------------------------------------------------
# Executor.state_dict / load_state_dict: the sharded-checkpoint seam
# ---------------------------------------------------------------------------

def test_state_dict_walks_placements_and_round_trips(tmp_path):
    """state_dict returns the LIVE mesh placements (the vocab-sharded
    table as 8 device shards, moments inheriting the annotation) and
    load_state_dict restores them bit-exact."""
    tr = fluid.Trainer(train_func=_emb_train_func, optimizer_func=_adam,
                       place=fluid.CPUPlace(),
                       transpiler_fn=_mesh_hook({'dp': 8}))
    tr.train(num_epochs=1, event_handler=lambda ev: None,
             reader=_emb_reader(4), feed_order=['ids'])
    state = tr.exe.state_dict(tr.train_program, scope=tr.scope)
    assert 'emb_w' in state and 'fc_w' in state
    sh = _named_shardings(state)
    assert str(sh['emb_w'].spec) == "PartitionSpec('dp',)" \
        or str(sh['emb_w'].spec) == "PartitionSpec('dp', None)"
    # every device holds 1/8 of the vocab — never the dense table
    assert state['emb_w'].addressable_shards[0].data.shape == (VOCAB // 8,
                                                               DIM)
    moments = [n for n in state
               if 'emb_w' in n and n != 'emb_w'
               and state[n].shape == (VOCAB, DIM)]
    assert moments, sorted(state)
    for m in moments:
        assert state[m].addressable_shards[0].data.shape == (VOCAB // 8,
                                                             DIM), m
    # round trip: clobber the scope, restore, compare bit-exact
    want = {n: np.array(np.asarray(v), copy=True)
            for n, v in state.items()}
    snapshot = dict(state)
    for n in snapshot:
        tr.scope.vars[n] = jax.numpy.zeros_like(snapshot[n])
    restored = tr.exe.load_state_dict(snapshot, tr.train_program,
                                      scope=tr.scope)
    assert set(restored) == set(snapshot)
    for n, v in want.items():
        np.testing.assert_array_equal(
            np.asarray(tr.scope.vars[n]), v, err_msg=n)
    # unknown entries are skipped with a warning, not written
    with pytest.warns(RuntimeWarning, match='not persistables'):
        tr.exe.load_state_dict({'no_such_var': np.zeros(3, 'f4')},
                               tr.train_program, scope=tr.scope)
    assert 'no_such_var' not in tr.scope.vars


def test_dense_save_checkpoint_warns_on_annotated_program(tmp_path):
    """fluid.io.save_checkpoint gathers dense — on a mesh-annotated
    program that is the OOM-on-a-pod hazard, so it must say so."""
    tr = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(),
                       transpiler_fn=_mesh_hook({'dp': 8}))
    tr.train(num_epochs=1, event_handler=lambda ev: None,
             reader=_linear_reader(16), feed_order=['x', 'y'])
    with tr._prog_and_scope_guard():
        with pytest.warns(RuntimeWarning, match='mesh-annotated'):
            fluid.io.save_checkpoint(tr.exe, str(tmp_path / 'dense'),
                                     main_program=tr.train_program)


# ---------------------------------------------------------------------------
# Trainer: sharded periodic checkpoints + topology-changing resume
# ---------------------------------------------------------------------------

def test_trainer_topology_change_resume_linear(tmp_path, obs_events):
    """The headline drill shape: an annotated trainer on an 8-device
    mesh crashes mid-epoch; a 4-device trainer over the same dir resumes
    from the newest committed sharded serial at the exact next step and
    the loss trajectory continues (matches an uninterrupted 8-device
    reference run step for step)."""
    # reference: uninterrupted run on the 8-mesh
    ref = []
    t0 = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(),
                       transpiler_fn=_mesh_hook({'dp': 8}))
    t0.train(num_epochs=2, event_handler=_losses_handler(ref),
             reader=_linear_reader(), feed_order=['x', 'y'])

    ckpt = str(tmp_path / 'ckpt')
    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, max_num_checkpoints=3,
                                 epoch_interval=1, step_interval=1)
    before = []
    t1 = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), checkpoint_config=cfg,
                       transpiler_fn=_mesh_hook({'dp': 8}))
    with pytest.raises(Crash):
        t1.train(num_epochs=2,
                 event_handler=_losses_handler(before, crash_at=(0, 5)),
                 reader=_linear_reader(), feed_order=['x', 'y'])
    w_at_crash = np.asarray(t1.scope.vars['w'])
    serials = [d for d in os.listdir(ckpt) if re.fullmatch(r'sharded_\d+', d)]
    assert serials, os.listdir(ckpt)
    # the commit protocol's artifacts: manifest + verified .sum sidecar
    newest = os.path.join(ckpt, 'sharded_%d'
                          % max(int(d.split('_')[1]) for d in serials))
    assert os.path.exists(os.path.join(newest, 'manifest.json'))
    assert os.path.exists(os.path.join(newest, 'manifest.json.sum'))
    assert not [d for d in os.listdir(ckpt) if d.endswith('.tmp')]
    assert obs_events('checkpoint.commit')

    # resume on HALF the devices
    cfg2 = fluid.CheckpointConfig(checkpoint_dir=ckpt,
                                  max_num_checkpoints=3,
                                  epoch_interval=1, step_interval=1)
    after = []
    t2 = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), checkpoint_config=cfg2,
                       transpiler_fn=_mesh_hook({'dp': 4}))
    assert cfg2.load_serial  # resumed from a sharded serial
    np.testing.assert_array_equal(np.asarray(t2.scope.vars['w']),
                                  w_at_crash)
    # restored state lives on the 4-device mesh
    assert len(t2.scope.vars['w'].sharding.device_set) == 4
    ev = obs_events('elastic.resume')
    assert ev and ev[-1]['fields']['from_mesh'] == [['dp', 8]]
    assert ev[-1]['fields']['to_mesh'] == [['dp', 4]]
    t2.train(num_epochs=2, event_handler=_losses_handler(after),
             reader=_linear_reader(), feed_order=['x', 'y'])
    # exact-step resume: (0, 5) is never replayed, (0, 6) is next
    steps_after = [s for s, _ in after]
    assert (0, 5) not in steps_after
    assert steps_after[0] == (0, 6)
    # trajectory continuity: resumed losses match the uninterrupted
    # reference at the same steps (dp=4 vs dp=8 differ only in float
    # reduction order)
    ref_map = dict(ref)
    for s, loss in after:
        np.testing.assert_allclose(loss, ref_map[s], rtol=1e-3,
                                   atol=1e-6, err_msg=str(s))
    # clean finish removes its sharded serials (and only them)
    assert not [d for d in os.listdir(ckpt) if d.startswith('sharded_')]


def test_trainer_sharded_embedding_topology_change(tmp_path):
    """The acceptance drill's state shape: a vocab-sharded table AND its
    sharded adam moments checkpoint as per-shard files (sizes checked —
    the dense table never materializes), then restore 8 -> 4 devices
    with resharding and exact values."""
    ckpt = str(tmp_path / 'ckpt')
    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, max_num_checkpoints=2,
                                 epoch_interval=1, step_interval=1)
    t1 = fluid.Trainer(train_func=_emb_train_func, optimizer_func=_adam,
                       place=fluid.CPUPlace(), checkpoint_config=cfg,
                       transpiler_fn=_mesh_hook({'dp': 8}))
    losses = []
    with pytest.raises(Crash):
        t1.train(num_epochs=2,
                 event_handler=_losses_handler(losses, crash_at=(0, 5)),
                 reader=_emb_reader(), feed_order=['ids'])
    emb_at_crash = np.asarray(t1.scope.vars['emb_w'])
    moment_names = [n for n in t1.scope.vars
                    if 'emb_w' in n and n != 'emb_w'
                    and getattr(t1.scope.vars[n], 'shape', None)
                    == (VOCAB, DIM)]
    assert moment_names
    moments_at_crash = {n: np.asarray(t1.scope.vars[n])
                        for n in moment_names}

    newest = max(int(d.split('_')[1]) for d in os.listdir(ckpt)
                 if re.fullmatch(r'sharded_\d+', d))
    sdir = os.path.join(ckpt, 'sharded_%d' % newest)
    # NO dense materialization: every emb_w / moment shard file holds
    # exactly one device's rows (VOCAB/8), never the whole table
    vocab_files = [f for f in os.listdir(sdir)
                   if 'emb_w' in f and f.endswith('.npy')]
    assert len(vocab_files) >= 8
    for f in vocab_files:
        arr = np.load(os.path.join(sdir, f))
        if arr.ndim == 2 and arr.shape[1] == DIM:
            assert arr.shape[0] == VOCAB // 8, (f, arr.shape)
    # static restorability onto the surviving topology
    assert ck.restorable(sdir, {'dp': 4}) == []

    cfg2 = fluid.CheckpointConfig(checkpoint_dir=ckpt,
                                  max_num_checkpoints=2,
                                  epoch_interval=1, step_interval=1)
    t2 = fluid.Trainer(train_func=_emb_train_func, optimizer_func=_adam,
                       place=fluid.CPUPlace(), checkpoint_config=cfg2,
                       transpiler_fn=_mesh_hook({'dp': 4}))
    assert cfg2.load_serial
    np.testing.assert_array_equal(np.asarray(t2.scope.vars['emb_w']),
                                  emb_at_crash)
    for n, v in moments_at_crash.items():
        np.testing.assert_array_equal(np.asarray(t2.scope.vars[n]), v,
                                      err_msg=n)
    # resharded placements: table and moments each hold VOCAB/4 rows
    # per device on the new mesh — checked through the state_dict seam
    state = t2.exe.state_dict(t2.train_program, scope=t2.scope)
    for n in ['emb_w'] + moment_names:
        assert state[n].addressable_shards[0].data.shape \
            == (VOCAB // 4, DIM), n
        assert len(state[n].sharding.device_set) == 4, n
    # training continues
    cont = []
    t2.train(num_epochs=1, event_handler=_losses_handler(cont),
             reader=_emb_reader(), feed_order=['ids'])
    assert cont and all(np.isfinite(l) for _, l in cont)
    assert cont[0][0] == (0, 6)   # exact-step resume, no epoch replay


# ---------------------------------------------------------------------------
# atomic commit: torn writes can never look committed
# ---------------------------------------------------------------------------

def _state_arrays(seed=0):
    rng = np.random.RandomState(seed)
    return {'w': rng.rand(8, 8).astype('float32'),
            'b': rng.rand(8).astype('float32')}


_TORN_CHILD = r"""
import os, sys, time
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 2)
import numpy as np
from paddle_tpu.utils import checkpoint as ck

base, marker = sys.argv[1], sys.argv[2]
orig = ck._write_shard

def slow(path, data, sh):
    orig(path, data, sh)
    with open(marker, 'w') as f:
        f.write('mid-save')
    time.sleep(120)   # the parent SIGKILLs us here — mid-save

ck._write_shard = slow
state = {'w': np.arange(64, dtype=np.float32).reshape(8, 8),
         'b': np.ones(8, np.float32)}
ck.save_sharded(os.path.join(base, 'sharded_2'), state, step=2)
print('UNEXPECTED: save committed')
"""


def test_sigkill_mid_save_leaves_no_committed_dir(tmp_path):
    """The torn-write acceptance drill: SIGKILL during save_sharded (a
    real child process, killed mid-shard-write) leaves only the staging
    dir; load_latest_verified falls back LOUDLY to the previous intact
    serial."""
    base = str(tmp_path / 'ckpts')
    ck.save_sharded(os.path.join(base, 'sharded_1'), _state_arrays(1),
                    step=1)
    marker = str(tmp_path / 'mid_save_marker')
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=here)
    env.pop('JAX_PLATFORMS', None)
    env.pop('XLA_FLAGS', None)
    proc = subprocess.Popen([sys.executable, '-c', _TORN_CHILD, base,
                             marker], env=env, cwd=here,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 180
        while not os.path.exists(marker):
            assert proc.poll() is None, proc.communicate()
            assert time.monotonic() < deadline, 'child never reached save'
            time.sleep(0.05)
        FaultInjector(0).kill_process(proc)   # the host-failure fault
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    # the save never committed: staging dir only, no sharded_2
    assert os.path.isdir(os.path.join(base, 'sharded_2.tmp'))
    assert not os.path.isdir(os.path.join(base, 'sharded_2'))
    assert ck.latest_step(base) == 1
    with pytest.warns(RuntimeWarning, match='uncommitted'):
        arrays, meta = ck.load_latest_verified(base)
    assert meta['step'] == 1
    np.testing.assert_array_equal(np.asarray(arrays['w']),
                                  _state_arrays(1)['w'])


def test_commit_timeout_is_typed_and_leaves_staging(tmp_path,
                                                    monkeypatch):
    """A peer that never stages its manifest: process 0's commit raises
    the typed CommitTimeout (the Trainer's periodic path treats it as a
    missed checkpoint, not a dead run) and the staging dir survives,
    uncommitted."""
    monkeypatch.setattr(jax, 'process_count', lambda: 2)
    d = str(tmp_path / 'ck' / 'sharded_1')
    with pytest.raises(ck.CommitTimeout, match='UNCOMMITTED'):
        ck.save_sharded(d, _state_arrays(), step=1, commit_timeout=0.3)
    assert os.path.isdir(d + '.tmp')
    assert not os.path.isdir(d)


def test_overwrite_commit_swaps_without_deleting_first(tmp_path):
    """Re-saving an existing serial replaces it atomically (swap, not
    rmtree-then-rename) and leaves no .old/.tmp debris on success."""
    d = str(tmp_path / 'ck' / 'sharded_1')
    ck.save_sharded(d, _state_arrays(1), step=1)
    ck.save_sharded(d, _state_arrays(2), step=1)
    arrays, _ = ck.load_sharded(d)
    np.testing.assert_array_equal(np.asarray(arrays['w']),
                                  _state_arrays(2)['w'])
    parent = os.path.dirname(d)
    assert [x for x in os.listdir(parent)] == ['sharded_1']


def test_kill_process_refuses_self():
    with pytest.raises(ValueError, match='CHILD'):
        FaultInjector(0).kill_process(os.getpid())


def test_only_uncommitted_dirs_is_a_loud_failure(tmp_path):
    base = str(tmp_path / 'ckpts')
    os.makedirs(os.path.join(base, 'sharded_3.tmp'))
    with pytest.warns(RuntimeWarning, match='uncommitted'):
        with pytest.raises(RuntimeError, match='no committed'):
            ck.load_latest_verified(base)


@pytest.mark.parametrize('what', ['drop_manifest', 'truncate_manifest',
                                  'corrupt_manifest', 'drop_shard',
                                  'truncate_shard'])
def test_torn_checkpoint_variants_fall_back(tmp_path, what):
    """FaultInjector.torn_checkpoint: every tear mode of the newest
    serial (manifest vs shard, drop vs truncate vs same-size bit rot —
    the last only the .sum CRC catches) falls back to the previous
    intact serial with a warning, never a raw JSON/KeyError."""
    base = str(tmp_path / 'ckpts')
    ck.save_sharded(os.path.join(base, 'sharded_1'), _state_arrays(1),
                    step=1)
    ck.save_sharded(os.path.join(base, 'sharded_2'), _state_arrays(2),
                    step=2)
    inj = FaultInjector(seed=5)
    mode, path = inj.torn_checkpoint(os.path.join(base, 'sharded_2'),
                                     what=what)
    assert mode == what
    problems = ck.verify_sharded(os.path.join(base, 'sharded_2'))
    assert problems, what
    with pytest.warns(RuntimeWarning, match='FAILED verification'):
        arrays, meta = ck.load_latest_verified(base)
    assert meta['step'] == 1
    np.testing.assert_array_equal(np.asarray(arrays['w']),
                                  _state_arrays(1)['w'])


def test_manifest_bit_rot_is_a_typed_verification_failure(tmp_path):
    """Same-size manifest corruption: without the .sum sidecar this was
    a raw json error; now it is a typed RuntimeError naming the
    manifest."""
    d = str(tmp_path / 'ck')
    ck.save_sharded(d, _state_arrays(), step=1)
    FaultInjector(seed=2).corrupt_file(os.path.join(d, 'manifest.json'))
    with pytest.raises(RuntimeError, match='manifest.*corrupt|corrupt.*manifest'):
        ck.load_sharded(d)
    problems = ck.verify_sharded(d)
    assert problems and 'manifest' in problems[0]


def test_old_format_checkpoints_still_load(tmp_path):
    """Checkpoints without the .sum sidecar (pre-elastic format) load
    and verify exactly as before."""
    d = str(tmp_path / 'ck')
    ck.save_sharded(d, _state_arrays(3), step=4)
    for f in list(os.listdir(d)):
        if f.endswith('.sum'):
            os.remove(os.path.join(d, f))
    assert ck.verify_sharded(d) == []
    arrays, meta = ck.load_sharded(d)
    assert meta['step'] == 4
    np.testing.assert_array_equal(np.asarray(arrays['w']),
                                  _state_arrays(3)['w'])


# ---------------------------------------------------------------------------
# restorable(): the static reshard-on-restore check (+ program_lint)
# ---------------------------------------------------------------------------

def _sharded_table_ckpt(tmp_path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:8]), ('dp',))
    state = {'emb': jax.device_put(
        np.arange(VOCAB * DIM, dtype=np.float32).reshape(VOCAB, DIM),
        NamedSharding(mesh, P('dp', None))),
        'b': jax.device_put(np.ones(8, np.float32),
                            NamedSharding(mesh, P()))}
    d = str(tmp_path / 'table_ck')
    ck.save_sharded(d, state, step=1)
    return d


def test_restorable_static_check(tmp_path):
    d = _sharded_table_ckpt(tmp_path)
    assert ck.restorable(d, {'dp': 4}) == []
    assert ck.restorable(d, {'dp': 16}) == []     # grow works too
    bad = ck.restorable(d, {'dp': 5})
    assert bad and 'tile' in bad[0]
    bad = ck.restorable(d, {'model': 4})
    assert bad and 'not on the target mesh' in bad[0]
    # coverage gap: a deleted shard file is visible statically
    victim = [f for f in os.listdir(d)
              if f.startswith('emb') and f.endswith('.npy')][0]
    os.remove(os.path.join(d, victim))
    man = ck._merged_manifest(d)
    man['arrays']['emb']['shards'] = \
        man['arrays']['emb']['shards'][:-1]
    bad = ck.restorable(man, {'dp': 4})
    assert bad and 'cover' in bad[0]


def test_program_lint_checkpoint_flag(tmp_path):
    """tools/program_lint.py --mesh ... --checkpoint DIR: the elastic
    restart pre-check, wired next to the sharding lint."""
    import importlib.util
    import io as _io
    from contextlib import redirect_stdout
    from util import fresh_program

    d = _sharded_table_ckpt(tmp_path)
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        pred = fluid.layers.fc(input=x, size=32)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        m = str(tmp_path / 'model')
        fluid.io.save_inference_model(m, ['x'], [pred], exe,
                                      main_program=main)

    spec = importlib.util.spec_from_file_location(
        'program_lint', os.path.join(os.path.dirname(__file__), '..',
                                     'tools', 'program_lint.py'))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    def run(argv):
        buf = _io.StringIO()
        with redirect_stdout(buf):
            rc = lint.main(argv)
        return rc, buf.getvalue()

    rc, out = run([m, '--mesh', 'dpx4', '--checkpoint', d, '--json'])
    doc = json.loads(out)
    assert rc == 0
    assert doc['checkpoint']['restorable'] is True
    rc, out = run([m, '--mesh', 'dpx5', '--checkpoint', d, '--json'])
    doc = json.loads(out)
    assert rc == 1
    assert doc['checkpoint']['restorable'] is False
    assert doc['checkpoint']['problems']
    # --checkpoint without --mesh is a usage error
    rc, _ = run([m, '--checkpoint', d])
    assert rc == 2


def test_reshard_restore_emits_span(tmp_path, obs_events):
    d = _sharded_table_ckpt(tmp_path)
    from jax.sharding import Mesh
    small = Mesh(np.asarray(jax.devices()[:4]), ('dp',))
    arrays, _ = ck.load_sharded(d, mesh=small)
    np.testing.assert_array_equal(
        np.asarray(arrays['emb']),
        np.arange(VOCAB * DIM, dtype=np.float32).reshape(VOCAB, DIM))
    spans = obs_events('checkpoint.reshard')
    assert spans
    f = spans[-1]['fields']
    assert f['from_mesh'] == 'dp=8' and f['to_mesh'] == 'dp=4'


def test_reshard_onto_mesh_missing_axis_replicates(tmp_path):
    """A saved axis absent from the restore mesh replicates that dim,
    loudly — the axis-set-changing elastic case."""
    d = _sharded_table_ckpt(tmp_path)
    from jax.sharding import Mesh
    other = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                 ('x', 'y'))
    with pytest.warns(RuntimeWarning, match='restore replicated'):
        arrays, _ = ck.load_sharded(d, mesh=other)
    np.testing.assert_array_equal(
        np.asarray(arrays['emb']),
        np.arange(VOCAB * DIM, dtype=np.float32).reshape(VOCAB, DIM))


# ---------------------------------------------------------------------------
# heartbeat: host-failure detection
# ---------------------------------------------------------------------------

def test_heartbeat_stale_detection_unit(tmp_path, obs_events):
    d = str(tmp_path / 'beats')
    hb0 = Heartbeat(d, process_id=0, num_processes=2, interval=0.03,
                    timeout=0.25)
    hb1 = Heartbeat(d, process_id=1, num_processes=2, interval=0.03,
                    timeout=0.25)
    hb0.start()
    hb1.start()
    try:
        deadline = time.monotonic() + 5
        while hb0.check(raise_error=False) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert hb0.check(raise_error=False) == []
        # peer 1 dies (its beats stop — stop() simulates the SIGKILL)
        hb1.stop()
        time.sleep(0.4)
        assert hb0.check(raise_error=False) == [1]
        with pytest.raises(HostLost) as ei:
            hb0.check()
        assert ei.value.stale == [1]
        ev = obs_events('parallel.heartbeat.stale')
        assert ev and ev[0]['fields']['peer'] == 1
        assert obs.counter('parallel.heartbeat.stale').value >= 1
        # peer restarts (fresh counter) -> recovery
        hb1b = Heartbeat(d, process_id=1, num_processes=2, interval=0.03,
                         timeout=0.25)
        hb1b.beat()
        assert hb0.check(raise_error=False) == []
        hb1b.stop()
    finally:
        hb0.stop()
        hb1.stop()


def test_heartbeat_never_arrived_peer_goes_stale(tmp_path):
    hb = Heartbeat(str(tmp_path / 'beats'), process_id=0, num_processes=3,
                   interval=0.03, timeout=0.2)
    hb.start()
    try:
        time.sleep(0.35)
        assert hb.check(raise_error=False) == [1, 2]
    finally:
        hb.stop()


def test_trainer_host_lost_flushes_and_raises(tmp_path, obs_events):
    """The Trainer surface: a stale peer raises typed HostLost AFTER an
    emergency sharded checkpoint, and a smaller-topology trainer resumes
    from it at the exact step."""
    ckpt = str(tmp_path / 'ckpt')
    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, max_num_checkpoints=5,
                                 epoch_interval=1, step_interval=1)
    # peer 1 of a declared 2-process job never beats: this host must
    # notice and bail out (the single-process commit still succeeds, so
    # the emergency flush is committed and resumable)
    hb = Heartbeat(str(tmp_path / 'beats'), process_id=0, num_processes=2,
                   interval=0.05, timeout=0.2)
    seen = []
    t1 = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), checkpoint_config=cfg,
                       transpiler_fn=_mesh_hook({'dp': 8}), heartbeat=hb)

    def handler(ev):
        if isinstance(ev, fluid.EndStepEvent):
            seen.append((ev.epoch, ev.step))
            time.sleep(0.3)   # let the peer's absence cross the timeout

    with pytest.warns(RuntimeWarning, match='lost'):
        with pytest.raises(HostLost) as ei:
            t1.train(num_epochs=2, event_handler=handler,
                     reader=_linear_reader(), feed_order=['x', 'y'])
    assert ei.value.stale == [1]
    assert t1.host_lost and t1.host_lost['stale'] == [1]
    assert t1.host_lost['last_done'] == seen[-1]
    assert t1.host_lost['emergency_checkpoint']   # committed (1-process)
    assert obs_events('elastic.host_lost')
    last_done = seen[-1]

    # supervisor restart on the surviving topology
    cfg2 = fluid.CheckpointConfig(checkpoint_dir=ckpt,
                                  max_num_checkpoints=5,
                                  epoch_interval=1, step_interval=1)
    after = []
    t2 = fluid.Trainer(train_func=_linear_train_func, optimizer_func=_sgd,
                       place=fluid.CPUPlace(), checkpoint_config=cfg2,
                       transpiler_fn=_mesh_hook({'dp': 4}))
    assert cfg2.load_serial
    assert (cfg2.epoch_id, cfg2.step_id) == last_done
    t2.train(num_epochs=1, event_handler=_losses_handler(after),
             reader=_linear_reader(), feed_order=['x', 'y'])
    if last_done[0] == 0:
        steps_after = [s for s, _ in after]
        assert last_done not in steps_after
        assert steps_after[0] == (0, last_done[1] + 1)


# ---------------------------------------------------------------------------
# the multi-process drill: SIGKILL one worker of a 2-host (8-device)
# job; the survivor detects, flushes, exits; resume on 4 devices
# ---------------------------------------------------------------------------

_MP_CHILD = r"""
import os, sys, time, signal, json
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 4)
import numpy as np
from paddle_tpu import parallel
import paddle_tpu.fluid as fluid

rank = int(sys.argv[1])
coord = sys.argv[2]
ckpt = sys.argv[3]
beats = sys.argv[4]
kill_step = int(sys.argv[5])
loss_log = sys.argv[6]

parallel.init_distributed(coordinator_address=coord, num_processes=2,
                          process_id=rank)
assert len(jax.devices()) == 8, jax.devices()

VOCAB, DIM = 64, 4

def train_func():
    ids = fluid.layers.data(name='ids', shape=[2, 1], dtype='int64')
    emb = fluid.layers.embedding(
        ids, size=[VOCAB, DIM],
        param_attr=fluid.ParamAttr(name='emb_w', sharding=('dp', None)))
    pred = fluid.layers.fc(input=emb, size=1, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=fluid.ParamAttr(name='fc_w'))
    return fluid.layers.mean(fluid.layers.square(pred - 1.0))

def global_batch(t):
    rng = np.random.RandomState(100 + t)
    return rng.randint(0, VOCAB, size=(8, 2, 1)).astype('int64')

def reader():
    # per-host slice of the deterministic global batch: host r feeds
    # rows [r*4, (r+1)*4) — make_array_from_process_local_data stitches
    for t in range(12):
        g = global_batch(t)[rank * 4:(rank + 1) * 4]
        yield [(g[i],) for i in range(4)]

hb = parallel.Heartbeat(beats, interval=0.1, timeout=1.2)
cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt, max_num_checkpoints=50,
                             epoch_interval=1, step_interval=1,
                             commit_timeout=60.0)
trainer = fluid.Trainer(train_func=train_func,
                        optimizer_func=lambda: fluid.optimizer.Adam(
                            learning_rate=0.05),
                        place=fluid.CPUPlace(), checkpoint_config=cfg,
                        transpiler_fn=lambda p: p.set_mesh({'dp': 8}),
                        heartbeat=hb)

losses = []

def handler(ev):
    if isinstance(ev, fluid.EndStepEvent):
        losses.append([ev.epoch, ev.step,
                       float(np.asarray(ev.metrics[0]))])
        if rank == 1 and ev.step == kill_step:
            os.kill(os.getpid(), signal.SIGKILL)   # host dies, no cleanup
        if rank == 0 and ev.step >= kill_step:
            time.sleep(2.0)   # let the dead peer's staleness accrue

try:
    trainer.train(num_epochs=1, event_handler=handler,
                  reader=lambda: reader(), feed_order=['ids'])
    print('FINISHED-WITHOUT-HOSTLOST')
    sys.exit(3)
except parallel.HostLost as e:
    with open(loss_log, 'w') as f:
        json.dump({'losses': losses, 'stale': e.stale,
                   'host_lost': trainer.host_lost is not None}, f)
    print('HOSTLOST', e.stale)
    sys.stdout.flush()
    # exit WITHOUT the atexit jax.distributed.shutdown barrier: with a
    # dead peer that barrier blocks until the coordination service
    # aborts the process (~100s later, SIGABRT) — a supervisor needs
    # the exit NOW, and the emergency state is already flushed
    os._exit(7)
"""


@pytest.mark.slow
def test_multiprocess_kill_one_worker_resumes_8_to_4(tmp_path):
    """The full elastic acceptance drill: 2 processes x 4 devices train
    one annotated Program on a dp=8 mesh with per-step sharded
    checkpoints; worker 1 is SIGKILLed mid-training; worker 0's
    heartbeat surfaces HostLost and exits cleanly; a 4-device restart
    resumes from the last COMMITTED serial (the survivor's emergency
    flush cannot commit — its peer is dead — and is skipped as
    uncommitted) at the exact next step, with the vocab-sharded table,
    its adam moments, and the loss trajectory continuing."""
    ckpt = str(tmp_path / 'ckpt')
    beats = str(tmp_path / 'beats')
    loss_log = str(tmp_path / 'losses.p0.json')
    kill_step = 5
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in (0, 1):
        env = dict(os.environ, PYTHONPATH=here)
        env.pop('JAX_PLATFORMS', None)
        env.pop('XLA_FLAGS', None)
        procs.append(subprocess.Popen(
            [sys.executable, '-c', _MP_CHILD, str(rank),
             '127.0.0.1:%d' % port, ckpt, beats, str(kill_step),
             loss_log], env=env, cwd=here, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    rc0, out0, err0 = outs[0]
    rc1, out1, err1 = outs[1]
    assert rc1 == -signal.SIGKILL, (rc1, out1, err1[-2000:])
    assert rc0 == 7, (rc0, out0, err0[-2000:])
    assert 'HOSTLOST' in out0

    log = json.load(open(loss_log))
    assert log['stale'] == [1]
    pre_losses = {(e, s): l for e, s, l in log['losses']}
    assert (0, kill_step) in pre_losses

    # the last COMMITTED serial records kill_step; the survivor's
    # emergency flush stayed an uncommitted staging dir
    assert ck.latest_step(ckpt) is not None
    tmp_dirs = [d for d in os.listdir(ckpt) if d.endswith('.tmp')]
    assert tmp_dirs, os.listdir(ckpt)

    # ---- restart on the surviving topology: 4 devices (this process
    # has 8 but the program meshes only dp=4) -------------------------
    import warnings as _warnings
    cfg = fluid.CheckpointConfig(checkpoint_dir=ckpt,
                                 max_num_checkpoints=50,
                                 epoch_interval=1, step_interval=1)
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter('always')
        t2 = fluid.Trainer(train_func=_mp_emb_train_func,
                           optimizer_func=lambda: fluid.optimizer.Adam(
                               learning_rate=0.05),
                           place=fluid.CPUPlace(), checkpoint_config=cfg,
                           transpiler_fn=_mesh_hook({'dp': 4}))
    assert any('uncommitted' in str(w.message) for w in rec)
    assert cfg.load_serial
    assert (cfg.epoch_id, cfg.step_id) == (0, kill_step)
    # restored sharded placements on the smaller mesh — and per-shard
    # file sizes in the committed serial prove no host ever wrote the
    # dense table
    sdir = os.path.join(ckpt, 'sharded_%d' % ck.latest_step(ckpt))
    for f in os.listdir(sdir):
        if 'emb_w' in f and f.endswith('.npy'):
            arr = np.load(os.path.join(sdir, f))
            if arr.ndim == 2 and arr.shape[1] == DIM:
                assert arr.shape[0] == VOCAB // 8, (f, arr.shape)
    state = t2.exe.state_dict(t2.train_program, scope=t2.scope)
    assert state['emb_w'].addressable_shards[0].data.shape \
        == (VOCAB // 4, DIM)

    cont = []
    t2.train(num_epochs=1, event_handler=_losses_handler(cont),
             reader=_mp_global_reader(), feed_order=['ids'])
    steps = [s for s, _ in cont]
    assert (0, kill_step) not in steps       # exact-step resume
    assert steps[0] == (0, kill_step + 1)
    assert all(np.isfinite(l) for _, l in cont)
    # trajectory continuity: the resumed run's first losses stay in the
    # converged regime the pre-kill run reached, not a cold restart
    pre_last = pre_losses[(0, kill_step)]
    assert cont[0][1] <= max(4 * pre_last, pre_last + 0.1), (
        pre_last, cont[0][1])


def _mp_emb_train_func():
    # the _MP_CHILD model, rebuilt in-parent for the resume phase
    return _emb_train_func()


def _mp_global_reader():
    def r():
        for t in range(12):
            rng = np.random.RandomState(100 + t)
            g = rng.randint(0, VOCAB, size=(8, 2, 1)).astype('int64')
            yield [(g[i],) for i in range(8)]
    return r
