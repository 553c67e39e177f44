"""Transpiler behavior: memory_optimize -> remat; inference BN fold.

Parity: reference transpiler/memory_optimization_transpiler.py (liveness
buffer reuse -> here jax.checkpoint rematerialisation) and
transpiler/inference_transpiler.py (conv+BN weight folding).
"""
import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid

from util import fresh_program


def _mlp_program():
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu')
    h = fluid.layers.fc(input=h, size=16, act='relu')
    pred = fluid.layers.fc(input=h, size=1)
    cost = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    return cost


def _trace_step(main, startup, cost):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {'x': np.random.rand(4, 8).astype('float32'),
            'y': np.random.rand(4, 1).astype('float32')}
    exe.run(main, feed=feed, fetch_list=[cost])
    (compiled,) = [c for c in exe._cache.values() if c.ad_idx is not None]
    from paddle_tpu.fluid.executor import global_scope
    persist = {n: global_scope().vars[n] for n in compiled.persist_in}
    feed_dev = {k: jax.numpy.asarray(v) for k, v in feed.items()}
    jaxpr = jax.make_jaxpr(compiled._step)(
        *compiled.plan.split(persist), feed_dev, jax.random.key(0))
    return compiled, str(jaxpr)


def test_memory_optimize_wires_remat():
    with fresh_program() as (main, startup):
        cost = _mlp_program()
        fluid.memory_optimize(main)
        compiled, jaxpr = _trace_step(main, startup, cost)
    assert compiled.use_remat
    assert 'remat' in jaxpr


def test_no_remat_by_default():
    with fresh_program() as (main, startup):
        cost = _mlp_program()
        compiled, jaxpr = _trace_step(main, startup, cost)
    assert not compiled.use_remat
    assert 'remat' not in jaxpr


def test_memory_optimize_invalidates_jit_cache():
    """Flipping the remat flag after a run must recompile, not reuse."""
    with fresh_program() as (main, startup):
        cost = _mlp_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'x': np.zeros((4, 8), 'float32'),
                'y': np.zeros((4, 1), 'float32')}
        exe.run(main, feed=feed, fetch_list=[cost])
        n_before = len(exe._cache)
        fluid.memory_optimize(main)
        exe.run(main, feed=feed, fetch_list=[cost])
        assert len(exe._cache) == n_before + 1


def test_remat_matches_no_remat_numerics():
    """Remat changes memory, not math: losses must track exactly."""
    losses = {}
    for use_remat in (False, True):
        np.random.seed(0)
        with fresh_program() as (main, startup):
            cost = _mlp_program()
            if use_remat:
                fluid.memory_optimize(main)
            main.random_seed = 7
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {'x': np.random.RandomState(1).rand(4, 8).astype('float32'),
                    'y': np.random.RandomState(2).rand(4, 1).astype('float32')}
            out = [float(exe.run(main, feed=feed, fetch_list=[cost])[0])
                   for _ in range(3)]
            losses[use_remat] = out
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)


def test_inference_transpiler_bn_fold():
    """Conv+BN fold must preserve outputs numerically (fresh BN stats and
    trained-looking stats alike)."""
    from paddle_tpu.fluid.executor import global_scope
    with fresh_program() as (main, startup):
        img = fluid.layers.data(name='img', shape=[3, 8, 8], dtype='float32')
        conv = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                   padding=1, act=None)
        bn = fluid.layers.batch_norm(input=conv, is_test=True)
        out = fluid.layers.relu(bn)
        infer_prog = main.clone(for_test=True)

        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = global_scope()
        # make BN stats non-trivial so the fold actually has to work
        rng = np.random.RandomState(3)
        for name, arr in list(scope.vars.items()):
            if arr is None:
                continue
            a = np.asarray(arr)
            if 'mean' in name:
                scope.vars[name] = jax.numpy.asarray(
                    rng.normal(0.5, 0.2, a.shape).astype(a.dtype))
            elif 'variance' in name:
                scope.vars[name] = jax.numpy.asarray(
                    rng.uniform(0.5, 2.0, a.shape).astype(a.dtype))

        feed = {'img': rng.rand(2, 3, 8, 8).astype('float32')}
        ref = exe.run(infer_prog, feed=feed, fetch_list=[out])[0]

        t = fluid.InferenceTranspiler()
        t.transpile(infer_prog, fluid.CPUPlace())
        folded = exe.run(infer_prog, feed=feed, fetch_list=[out])[0]
    np.testing.assert_allclose(ref, folded, rtol=1e-4, atol=1e-5)
