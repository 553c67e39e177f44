"""Executor semantics: feed/fetch forms, jit caching, persistables,
startup behavior, scopes.

Parity: reference tests/unittests/test_executor_and_mul.py + executor.py
API contracts.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope, global_scope, scope_guard

from util import fresh_program


def _net():
    x = layers.data(name='x', shape=[4], dtype='float32')
    y = layers.data(name='y', shape=[1], dtype='float32')
    pred = layers.fc(input=x, size=1)
    cost = layers.mean(layers.square_error_cost(input=pred, label=y))
    return pred, cost


def test_fetch_by_variable_and_by_name():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'x': np.ones((2, 4), 'float32'),
                'y': np.zeros((2, 1), 'float32')}
        a = exe.run(main, feed=feed, fetch_list=[cost])[0]
        b = exe.run(main, feed=feed, fetch_list=[cost.name])[0]
    np.testing.assert_allclose(a, b)


def test_jit_cache_reuse_and_invalidation():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'x': np.ones((2, 4), 'float32'),
                'y': np.zeros((2, 1), 'float32')}
        exe.run(main, feed=feed, fetch_list=[cost])
        n1 = len(exe._cache)
        exe.run(main, feed=feed, fetch_list=[cost])
        assert len(exe._cache) == n1          # same signature: reuse
        # different batch size -> new compile
        feed8 = {'x': np.ones((8, 4), 'float32'),
                 'y': np.zeros((8, 1), 'float32')}
        exe.run(main, feed=feed8, fetch_list=[cost])
        assert len(exe._cache) == n1 + 1
        # program mutation -> recompile (correctness, not staleness)
        out2 = layers.scale(pred, scale=3.0)
        exe.run(main, feed=feed, fetch_list=[out2])
        assert len(exe._cache) == n1 + 2


def test_mutated_program_recompiles_not_stale():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=2.0)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xs = np.ones((2, 4), 'float32')
        a = exe.run(main, feed={'x': xs}, fetch_list=[out])[0]
        out3 = layers.scale(out, scale=3.0)
        b = exe.run(main, feed={'x': xs}, fetch_list=[out3])[0]
    np.testing.assert_allclose(a, xs * 2)
    np.testing.assert_allclose(b, xs * 6)


def test_persistables_survive_between_runs():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w_name = [n for n in global_scope().vars if n.endswith('.w_0')][0]
        w0 = np.asarray(global_scope().vars[w_name]).copy()
        feed = {'x': np.ones((2, 4), 'float32'),
                'y': np.zeros((2, 1), 'float32')}
        exe.run(main, feed=feed, fetch_list=[cost])
        w1 = np.asarray(global_scope().vars[w_name])
        assert not np.allclose(w0, w1)        # the update stuck in the scope


def test_missing_feed_raises_with_name():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(Exception) as ei:
            exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                    fetch_list=[cost])
        assert 'y' in str(ei.value)


def test_float64_feed_autocast():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=1.0)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res = exe.run(main, feed={'x': np.ones((2, 4), np.float64)},
                      fetch_list=[out])[0]
    assert res.dtype == np.float32


def test_scope_guard_isolation():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        outer_names = set(global_scope().vars)
        other = Scope()
        with scope_guard(other):
            exe.run(startup)
            assert set(global_scope().vars) == outer_names
        # writes stayed in `other`
        assert set(other.vars) == outer_names


def test_scope_var_holder_api():
    s = Scope()
    h = s.var('t')
    h.set(np.arange(6, dtype='float32').reshape(2, 3))
    assert s.find_var('t') is not None
    np.testing.assert_allclose(s.find_var('t').get_tensor(),
                               np.arange(6, dtype='float32').reshape(2, 3))
    assert s.find_var('missing') is None


def test_startup_runs_initializers_once_each_run():
    with fresh_program() as (main, startup):
        w = layers.create_parameter(
            shape=[4], dtype='float32',
            default_initializer=fluid.initializer.Constant(7.0))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        np.testing.assert_allclose(
            np.asarray(global_scope().vars[w.name]), np.full(4, 7.0, 'float32'))


def test_executor_close_clears_cache():
    with fresh_program() as (main, startup):
        pred, cost = _net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((2, 4), 'float32'),
                            'y': np.zeros((2, 1), 'float32')},
                fetch_list=[cost])
        assert exe._cache
        exe.close()
        assert not exe._cache


def test_return_numpy_false_returns_device_arrays():
    import jax
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=2.0)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res = exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                      fetch_list=[out], return_numpy=False)[0]
    assert isinstance(res, jax.Array)


def test_tensor_handle_array_copy_false_raises():
    """NumPy 2 __array__ contract: a device array can never satisfy a
    no-copy conversion, so copy=False must raise, not silently copy."""
    from paddle_tpu.fluid.executor import Scope
    import numpy as np
    import pytest
    scope = Scope()
    scope.vars['v'] = np.arange(4.0)
    handle = scope.find_var('v').get_tensor()
    np.testing.assert_array_equal(np.asarray(handle), np.arange(4.0))
    with pytest.raises(ValueError, match='copy=False'):
        handle.__array__(copy=False)


# --- a large dense host feed goes to the device as views of its rows ------

from paddle_tpu.fluid import executor as executor_mod  # noqa: E402


@pytest.fixture
def low_view_threshold(monkeypatch):
    """Feeds of a kilobyte count as large and pieces hold 16 KiB, so the
    tests stay small, and the host's backend counts as one with a tiling
    (the tests run where there is no TPU)."""
    monkeypatch.setattr(executor_mod, '_VIEW_FEED_BYTES', 1024)
    monkeypatch.setattr(executor_mod, '_VIEW_PIECE_BYTES', 1 << 14)
    monkeypatch.setattr(executor_mod, '_VIEW_PLATFORMS', ('tpu', 'cpu'))


def _reshaped_bytes():
    return executor_mod._C_FEED_RESHAPED.value


def _spy_device_put(monkeypatch):
    """Records what every jax.device_put is handed, then does it."""
    import jax
    seen, real = [], jax.device_put

    def device_put(x, *a, **kw):
        seen.append(x)
        return real(x, *a, **kw)
    monkeypatch.setattr(jax, 'device_put', device_put)
    return seen


@pytest.mark.parametrize('dtype,shape,pieces', [
    ('float32', (8, 16, 32, 3), [8]),
    ('float32', (24, 16, 32, 3), [8, 8, 8]),
    ('float32', (30, 32, 16, 3), [8, 8, 8, 6]),
    ('float32', (16, 40, 30), [8, 8]),
    ('uint8', (24, 16, 32, 3), [16, 8]),
    ('uint8', (30, 32, 16, 3), [16, 14]),
    ('int32', (9, 3, 20, 20), [8, 1]),
    ('float16', (40, 3, 20, 20), [8, 8, 8, 8, 8])])
def test_large_feed_is_put_as_views_and_arrives_as_declared(
        dtype, shape, pieces, low_view_threshold, monkeypatch):
    import jax
    arr = (np.random.RandomState(0).rand(*shape) * 100).astype(dtype)
    seen = _spy_device_put(monkeypatch)
    exe = fluid.Executor(fluid.CPUPlace())
    before = _reshaped_bytes()
    out = exe._to_device(arr)
    assert isinstance(out, jax.Array)
    assert out.shape == arr.shape and out.dtype == arr.dtype
    assert np.array_equal(np.asarray(out), arr)
    assert _reshaped_bytes() - before == arr.nbytes
    # every transfer is of the caller's bytes: rows of [shape[0], the rest]
    assert [p.shape for p in seen] == [
        (n, arr.size // shape[0]) for n in pieces]
    assert all(np.shares_memory(p, arr) for p in seen)
    assert np.array_equal(np.concatenate(seen).reshape(shape), arr)
    exe.close()


def test_view_threshold_is_the_modules_constant():
    """At the real constants: the first array over the threshold goes as
    one view, one row less as it is; the cell's batch of images goes in
    8 pieces of 32 images; the token cells' feeds (under 1 MB) stay
    below."""
    n = executor_mod._VIEW_FEED_BYTES
    assert n & (n - 1) == 0 and (1 << 20) <= n <= (1 << 23)
    at = np.zeros((n // (32 * 32 * 3 * 4) + 1, 32, 32, 3), 'float32')
    assert at.nbytes >= n > at[:-1].nbytes
    view, = executor_mod._run_views(at)
    assert view.shape == (len(at), 3072) and np.shares_memory(view, at)
    assert executor_mod._run_views(at[:-1]) is None
    assert executor_mod._run_views(np.zeros((64, 1024), 'int64')) is None
    images = np.lib.stride_tricks.as_strided(     # no 154 MB in a test
        np.zeros(1, 'float32'), (256, 224, 224, 3), (0, 0, 0, 0))
    images.flags.writeable = False
    assert executor_mod._run_views(images) is None      # not contiguous
    like = np.empty((256, 224, 224, 3), 'float32')      # untouched pages
    assert [v.shape for v in executor_mod._run_views(like)] \
        == [(32, 150528)] * 8


def test_view_path_is_the_tpus_alone(monkeypatch):
    """On the host's own backend there is no tiling to lay out for: a
    large feed goes as it is, whatever its shape."""
    monkeypatch.setattr(executor_mod, '_VIEW_FEED_BYTES', 1024)
    arr = np.ones((24, 16, 32, 3), 'float32')
    assert executor_mod._run_views(arr) is not None
    seen = _spy_device_put(monkeypatch)
    exe = fluid.Executor(fluid.CPUPlace())
    before = _reshaped_bytes()
    out = exe._to_device(arr)
    assert _reshaped_bytes() == before and seen[0] is arr and len(seen) == 1
    assert out.shape == arr.shape
    exe.close()


def _plain_cases():
    import jax.numpy as jnp
    from paddle_tpu.fluid.lod_tensor import LoDTensor
    from paddle_tpu.fluid.lowering import SeqValue
    big = np.arange(8 * 16 * 32 * 3, dtype='float32').reshape(8, 16, 32, 3)
    lod = LoDTensor()
    lod.set(np.arange(600, dtype='float32').reshape(200, 3), None)
    lod.set_lod([[0, 120, 200]])
    return {
        'under_the_threshold': np.ones((4, 4, 3), 'float32'),
        'lane_friendly': np.ones((8, 8, 128), 'float32'),
        'two_dims': np.ones((512, 3), 'float32'),
        'few_rows': np.ones((4, 64, 64, 3), 'float32'),
        'short_rows': np.ones((512, 4, 3), 'float32'),
        'non_contiguous': big[:, ::2],
        'fortran_order': np.asfortranarray(big),
        'jax_array': jnp.asarray(big),
        'seq_value': SeqValue(np.ones((8, 400, 3), 'float32'),
                              np.full((8,), 400, 'int32')),
        'lod_tensor': lod,
    }


@pytest.mark.parametrize('case', [
    'under_the_threshold', 'lane_friendly', 'two_dims', 'few_rows',
    'short_rows', 'non_contiguous', 'fortran_order', 'jax_array',
    'seq_value', 'lod_tensor'])
def test_every_other_feed_takes_the_plain_path(
        case, low_view_threshold, monkeypatch):
    """The counter stands still, nothing is reshaped on the device, and
    a host array reaches device_put as the caller's own buffer in its
    own shape: no ascontiguousarray, no copy."""
    from paddle_tpu.fluid.lowering import SeqValue
    val = _plain_cases()[case]
    seen = _spy_device_put(monkeypatch)
    monkeypatch.setattr(
        executor_mod, '_declared_shape',
        lambda *a: pytest.fail('reshaped on the device'))
    exe = fluid.Executor(fluid.CPUPlace())
    before = _reshaped_bytes()
    out = exe._to_device(val)
    assert _reshaped_bytes() == before
    if isinstance(val, np.ndarray):
        put, = seen
        assert put.shape == val.shape and np.shares_memory(put, val)
        assert np.array_equal(np.asarray(out), val)
    elif case == 'jax_array':
        assert seen == [val]
    else:
        assert isinstance(out, SeqValue) and len(seen) == 2
    exe.close()


def _conv_net():
    img = layers.data(name='img', shape=[3, 24, 24], dtype='float32')
    label = layers.data(name='label', shape=[1], dtype='int64')
    conv = layers.conv2d(input=img, num_filters=4, filter_size=3, act='relu')
    pred = layers.fc(input=conv, size=5, act='softmax')
    loss = layers.mean(layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _conv_batch(seed=0, n=24):
    rs = np.random.RandomState(seed)
    return {'img': rs.rand(n, 3, 24, 24).astype('float32'),
            'label': rs.randint(0, 5, (n, 1)).astype('int64')}


def _conv_losses(feeds, place):
    """Three SGD steps' losses; `place(exe, feed)` makes what is fed."""
    with fresh_program() as (main, startup):
        main.random_seed = startup.random_seed = 7
        loss = _conv_net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, keys = [], []
        for f in feeds:
            out.append(exe.run(main, feed=place(exe, f),
                               fetch_list=[loss])[0])
            keys.append(dict(exe._last_cache_lookup))
        exe.close()
    return np.stack(out), keys


def test_large_feed_keeps_the_step_key_and_the_loss_bits(low_view_threshold):
    """Fed as numpy (views of its rows in three pieces, then the device's
    reshape) and as a jax.Array placed beforehand in the declared shape:
    the same losses bit for bit, and within one Executor the same cache
    key, so a run fed one way hits the entry a run fed the other way
    made."""
    import jax
    feeds = [_conv_batch(seed=i) for i in range(3)]
    assert len(executor_mod._run_views(feeds[0]['img'])) == 3

    def placed(exe, f):
        return {k: jax.device_put(v, exe._device()) for k, v in f.items()}
    before = _reshaped_bytes()
    as_numpy, keys = _conv_losses(feeds, lambda exe, f: f)
    assert _reshaped_bytes() - before == sum(f['img'].nbytes for f in feeds)
    assert [k['outcome'] for k in keys] == ['miss', 'hit', 'hit']
    before = _reshaped_bytes()
    as_placed, _ = _conv_losses(feeds, placed)
    assert _reshaped_bytes() == before
    turns = iter([placed, lambda exe, f: f, placed])
    mixed, keys = _conv_losses(feeds, lambda exe, f: next(turns)(exe, f))
    assert [k['outcome'] for k in keys] == ['miss', 'hit', 'hit']
    assert len({k['key'] for k in keys}) == 1
    assert len({k['entries'] for k in keys}) == 1
    assert as_numpy.tobytes() == as_placed.tobytes() == mixed.tobytes()


def test_run_bundle_stacks_through_the_same_put(low_view_threshold,
                                                monkeypatch):
    """The stacker's one transfer a feed name goes through `_put`: the
    [K, ...] stack of a large input goes as views too (K = 8 rows), and
    the bundle's losses are the plain put's bit for bit."""
    feeds = [_conv_batch(seed=i, n=8) for i in range(8)]

    def bundle():
        with fresh_program() as (main, startup):
            main.random_seed = startup.random_seed = 7
            loss = _conv_net()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            out, = exe.run_bundle(main, feeds=feeds, fetch_list=[loss])
            exe.close()
        return np.asarray(out)
    before = _reshaped_bytes()
    viewed = bundle()
    # the stack, and step 0's own placement on the way to the cache key
    assert _reshaped_bytes() - before == 9 * feeds[0]['img'].nbytes
    monkeypatch.setattr(executor_mod, '_VIEW_FEED_BYTES', 1 << 40)
    before = _reshaped_bytes()
    plain = bundle()
    assert _reshaped_bytes() == before
    assert viewed.tobytes() == plain.tobytes()
