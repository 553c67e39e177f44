"""Inference deployment: Predictor (program bundle) + compiled StableHLO
artifact (jax.export). Parity: reference inference/api tests + capi."""
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.layers as layers
from paddle_tpu import inference

from util import fresh_program


def _build_and_save(tmpdir, compiled=False):
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[8])
        y = layers.data(name='y', shape=[1])
        h = layers.fc(input=x, size=16, act='relu')
        pred = layers.fc(input=h, size=1)
        loss = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.random.RandomState(0).rand(4, 8).astype('float32')
        yv = xv.sum(1, keepdims=True).astype('float32')
        exe.run(main, feed={'x': xv, 'y': yv}, fetch_list=[loss])
        fluid.io.save_inference_model(str(tmpdir), ['x'], [pred], exe,
                                      main_program=main)
        if compiled:
            inference.export_compiled(str(tmpdir), {'x': xv}, [pred], exe,
                                      main_program=main)
        want, = exe.run(main.clone(for_test=True).prune([pred]),
                        feed={'x': xv}, fetch_list=[pred])
        return xv, want


def test_predictor_matches_training_graph(tmp_path):
    xv, want = _build_and_save(tmp_path)
    p = inference.Predictor(str(tmp_path), place=fluid.CPUPlace())
    assert p.feed_names == ['x']
    got, = p.run({'x': xv})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predictor_concurrent_threads_no_global_scope_race(tmp_path):
    """Two Predictors over DIFFERENT weights running on different threads
    must not race on the process-global scope: each run passes its
    private scope explicitly through Executor.run(scope=...) (the old
    scope_guard entry mutated the global and corrupted concurrent
    runs). Regression test for the serving PR's thread-safety fix."""
    from paddle_tpu.fluid.executor import global_scope
    dirs, wants = [], []
    xv = np.random.RandomState(0).rand(4, 8).astype('float32')
    for k in range(2):
        d = tmp_path / ('m%d' % k)
        with fresh_program() as (main, startup):
            x = layers.data(name='x', shape=[8])
            pred = layers.fc(
                input=x, size=1,
                param_attr=fluid.ParamAttr(
                    initializer=fluid.initializer.Constant(float(k + 1))))
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            fluid.io.save_inference_model(str(d), ['x'], [pred], exe,
                                          main_program=main)
            want, = exe.run(main.clone(for_test=True).prune([pred]),
                            feed={'x': xv}, fetch_list=[pred])
        dirs.append(str(d))
        wants.append(want)
    base_scope = global_scope()
    # what earlier tests of this worker left there is not this test's
    before = set(base_scope.vars)
    preds = [inference.Predictor(d, place=fluid.CPUPlace()) for d in dirs]
    errors = []

    def hammer(k):
        try:
            for _ in range(20):
                got, = preds[k].run({'x': xv})
                np.testing.assert_allclose(got, wants[k], rtol=1e-5,
                                           atol=1e-6)
        except Exception as e:  # noqa: BLE001 — surface in the main thread
            errors.append((k, e))

    ts = [threading.Thread(target=hammer, args=(k,)) for k in (0, 1, 0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert errors == []
    # the predictors' private vars never leaked into the global scope
    assert global_scope() is base_scope
    assert set(base_scope.vars) == before
    assert all(p._scope is not base_scope and p._scope.vars for p in preds)


def test_compiled_artifact_round_trip(tmp_path):
    xv, want = _build_and_save(tmp_path, compiled=True)
    run = inference.load_compiled(str(tmp_path))
    assert run.feed_names == ['x']
    got, = run({'x': xv})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_compiled_artifact_validates_feeds(tmp_path):
    """load_compiled checks names/dtypes/shapes against the exported
    meta and names the offending input, instead of failing deep inside
    exported.call."""
    xv, want = _build_and_save(tmp_path, compiled=True)
    run = inference.load_compiled(str(tmp_path))
    assert run.input_spec == {'x': ((4, 8), 'float32')}
    with pytest.raises(ValueError, match="missing input.*'x'"):
        run({})
    with pytest.raises(ValueError, match="unknown input.*'bogus'"):
        run({'x': xv, 'bogus': xv})
    with pytest.raises(ValueError, match="input 'x'.*shape.*exported"):
        run({'x': xv[:2]})
    with pytest.raises(ValueError, match="input 'x'.*dtype"):
        run({'x': xv.astype('int32')})
    # same-kind narrowing stays accepted (float64 fed what was exported
    # as float32 — the narrowing jnp.asarray always applied)
    got, = run({'x': xv.astype('float64')})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_compiled_artifact_sequence_model(tmp_path):
    # lod (sequence) input path through export_compiled
    with fresh_program() as (main, startup):
        words = layers.data(name='words', shape=[1], dtype='int64',
                            lod_level=1)
        emb = layers.embedding(input=words, size=[30, 8])
        pooled = layers.sequence_pool(input=emb, pool_type='average')
        pred = layers.fc(input=pooled, size=3, act='softmax')
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ids = np.random.RandomState(1).randint(0, 30, size=(2, 5, 1)).astype('int64')
        inference.export_compiled(str(tmp_path), {'words': ids}, [pred], exe,
                                  main_program=main)
        want, = exe.run(main.clone(for_test=True).prune([pred]),
                        feed={'words': ids}, fetch_list=[pred])
    run = inference.load_compiled(str(tmp_path))
    got, = run({'words': ids})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_export_compiled_from_tp_transpiled_program(tmp_path):
    """StableHLO export round-trips from a mesh-transpiled (tp=2) training
    program: the pruned inference graph loads and runs frameworkless."""
    from paddle_tpu import inference
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = layers.fc(input=x, size=8, act='tanh')
        pred = layers.fc(input=h, size=1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
        fluid.TensorParallelTranspiler(tp=2).transpile(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xs = np.random.RandomState(0).rand(4, 8).astype('float32')
        exe.run(main, feed={'x': xs, 'y': np.zeros((4, 1), 'float32')},
                fetch_list=[cost])
        want, = exe.run(main.clone(for_test=True),
                        feed={'x': xs, 'y': np.zeros((4, 1), 'float32')},
                        fetch_list=[pred])
        d = str(tmp_path / 'hlo')
        inference.export_compiled(d, {'x': xs}, [pred], exe,
                                  main_program=main)
        fn = inference.load_compiled(d)
        got = fn({'x': xs})
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
