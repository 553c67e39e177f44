"""Sharded-embedding subsystem (docs/embedding.md).

`embedding(is_sparse=True, is_distributed=True)` on a row-sharded table
(`ParamAttr(sharding=(axis, None))` + `Program.set_mesh`) lowers the
lookup to the all_to_all wire (paddle_tpu.embedding.lookup) and keeps the
gradient a touched-rows-only SparseRows applied per shard — the dense
[vocab, dim] gradient never exists. These drills pin:

  * the wire itself (bucket/dedup/exchange) against the dense gather,
    bit-exact, duplicates and padding_idx included;
  * the A/B contract on the 8-device CPU mesh: sharded-sparse training
    matches the replicated-dense path for fetches AND post-step table
    rows (documented tolerance: one float32 rounding from the merge's
    accumulation order), through run(), run_bundle(), and a 2-step
    trained deepfm, with steady-state compiles == 0 via cache_stats;
  * loud inertness (the silently-ignored-attr bug this PR retires), the
    untileable-vocab fallback, the DistributeTranspiler shim's
    annotation translation, and the obs events.

Conftest forces the 8-virtual-device CPU platform, so every mesh here is
real (8 shards), just not fast.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import embedding as emb_mod
from paddle_tpu.fluid import layers

from util import fresh_program

pytestmark = pytest.mark.embedding

VOCAB, DIM = 48, 8          # 48 rows over 8 shards: 6 rows per shard
AXIS = 'model'


def _mesh8():
    from paddle_tpu import parallel
    return parallel.make_mesh({AXIS: 8})


# ---------------------------------------------------------------------------
# the functional wire
# ---------------------------------------------------------------------------

def test_sharded_lookup_matches_dense_gather_bit_exact():
    """Forward wire vs jnp.take over duplicate-heavy ids of every shape:
    a gather is a gather no matter which shard answered it."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(VOCAB, DIM).astype('float32'))
    mesh = _mesh8()
    for shape in [(5,), (6, 4), (3, 2, 2)]:
        ids = jnp.asarray(rng.randint(0, VOCAB, size=shape), jnp.int32)
        out = emb_mod.sharded_lookup(w, ids, mesh, AXIS)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.take(w, ids, axis=0)))


def test_sharded_lookup_padding_idx_zeroes_rows():
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(VOCAB, DIM).astype('float32'))
    ids = jnp.asarray([3, 7, 3, 0, 7], jnp.int32)
    out = np.asarray(emb_mod.sharded_lookup(w, ids, _mesh8(), AXIS,
                                            padding_idx=7))
    assert np.all(out[[1, 4]] == 0)
    np.testing.assert_array_equal(out[0], np.asarray(w[3]))


def test_sharded_lookup_rejects_untileable_vocab():
    w = jnp.zeros((50, DIM))     # 50 % 8 != 0
    with pytest.raises(ValueError, match='pad_vocab'):
        emb_mod.sharded_lookup(w, jnp.zeros((4,), jnp.int32), _mesh8(),
                               AXIS)


def test_dedup_plan_collapses_duplicates():
    ids = jnp.asarray([9, 3, 9, 9, 3, 41], jnp.int32)
    uids, seg, order, n_unique = emb_mod.dedup_plan(ids)
    assert int(n_unique) == 3
    assert sorted(np.asarray(uids[:3]).tolist()) == [3, 9, 41]
    # every occurrence maps (through sort order + seg) back to its own id
    sid = np.asarray(ids)[np.asarray(order)]
    np.testing.assert_array_equal(np.asarray(uids)[np.asarray(seg)], sid)


def test_pad_vocab_and_wire_stats():
    assert emb_mod.pad_vocab(6041, 8) == 6048
    assert emb_mod.pad_vocab(48, 8) == 48
    s = emb_mod.wire_stats(24, VOCAB, DIM, 8)
    assert s['query_capacity'] == 3
    assert s['row_bytes_per_device'] == 3 * 8 * DIM * 4


# ---------------------------------------------------------------------------
# the Program path: A/B vs replicated dense on the same 8-device mesh
# ---------------------------------------------------------------------------

def _build(sharded, is_sparse, optimizer, seed=7, mesh_axes=None,
           vocab=VOCAB):
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    main.random_seed = seed
    startup.random_seed = seed
    ids = layers.data(name='ids', shape=[4, 1], dtype='int64')
    pa = fluid.ParamAttr(name='emb_w',
                         sharding=(AXIS, None) if sharded else None)
    emb = layers.embedding(ids, size=[vocab, DIM], is_sparse=is_sparse,
                           is_distributed=sharded, param_attr=pa)
    pred = layers.fc(input=emb, size=1, num_flatten_dims=2,
                     bias_attr=False,
                     param_attr=fluid.ParamAttr(name='fc_w'))
    loss = layers.mean(layers.square(pred - 1.0))
    optimizer().minimize(loss)
    if mesh_axes is not False:
        main.set_mesh(mesh_axes or {AXIS: 8})
    return main, startup, loss


def _train(sharded, is_sparse, optimizer, batches, bundle=0,
           mesh_axes=None, vocab=VOCAB, seed=7):
    """Returns (losses, table, plans, exe) after len(batches) steps."""
    with fresh_program() as (_, _s):
        main, startup, loss = _build(sharded, is_sparse, optimizer,
                                     mesh_axes=mesh_axes, vocab=vocab,
                                     seed=seed)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        if bundle:
            for i in range(0, len(batches), bundle):
                feeds = [{'ids': b} for b in batches[i:i + bundle]]
                out = exe.run_bundle(main, feeds=feeds, fetch_list=[loss])
                losses.extend(np.asarray(out[0]).reshape(-1).tolist())
        else:
            for b in batches:
                out = exe.run(main, feed={'ids': b}, fetch_list=[loss])
                losses.append(float(np.asarray(out[0]).reshape(())))
        from paddle_tpu.fluid.executor import global_scope
        table = np.asarray(global_scope().find_var('emb_w').get_tensor())
        plans = [c.sparse_plan for c in exe._cache.values()]
        return losses, table, plans, exe


def _batches(n=3, seed=3, dup=True):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        b = rng.randint(0, VOCAB, size=(6, 4, 1)).astype('int64')
        if dup:
            b[:3] = b[3:6]
        out.append(b)
    return out


def test_sharded_sparse_matches_replicated_dense_sgd():
    """The acceptance A/B: fetches and post-step table rows match the
    replicated dense path; the sparse plan is armed; steady-state
    compiles are zero (each signature compiles exactly once)."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    batches = _batches()
    dl, dt, dplans, _ = _train(False, False, sgd, batches)
    sl, st, splans, exe = _train(True, True, sgd, batches)
    assert any('emb_w' in p for p in splans if p)
    assert not any(p for p in dplans)
    # documented tolerance: the merge/scatter accumulation order differs
    # from the dense subtract by at most a float32 rounding per step
    np.testing.assert_allclose(sl, dl, rtol=1e-5)
    np.testing.assert_allclose(st, dt, rtol=1e-4, atol=1e-6)
    # steady state = zero recompiles: 2 keys (startup, step), each missed
    # once, and every later run hit
    stats = exe.cache_stats
    assert stats['misses'] == 2
    assert stats['hits'] == len(batches) - 1


def test_sharded_sparse_step_temp_does_not_grow_with_the_vocab():
    """The footprint side of the same A/B, from XLA's memory analysis of
    the exact cached step: the dense-replicated step's temporaries carry
    the [vocab, DIM] gradient, the sharded-sparse step's only the
    [rows touched, DIM] blocks, so they are the same bytes at 48 rows and
    at 4096; and the static rows-touched bound is the feed's 24 ids."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    feed = {'ids': _batches(n=1)[0]}
    temp = {}
    for vocab in (VOCAB, 4096):
        for sharded in (False, True):
            with fresh_program():
                main, startup, loss = _build(sharded, sharded, sgd,
                                             vocab=vocab)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                temp[vocab, sharded] = exe.compiled_memory_stats(
                    main, feed, [loss]).temp_size_in_bytes
                assert exe.embed_rows_per_step(main, feed, [loss]) == (
                    6 * 4 if sharded else 0)
    assert temp[4096, False] >= 4096 * DIM * 4
    assert temp[4096, True] == temp[VOCAB, True] < temp[VOCAB, False]


def test_sharded_sparse_matches_unsharded_sparse_adagrad_and_adam():
    """Nonlinear updates see each touched row once (merged duplicates) —
    per shard — and trajectories match the single-device SPARSE path
    (same merge math; only the partitioning differs). The dense path is
    NOT the reference here: adagrad/adam's first touch of a row moves it
    by ~lr*sign(g), so a near-zero gradient makes dense-vs-merged float
    noise flip signs — the dense<->sparse equivalence itself is pinned
    (well-away from that edge) in test_sparse_embedding.py."""
    for opt in (lambda: fluid.optimizer.Adagrad(learning_rate=0.1),
                lambda: fluid.optimizer.Adam(learning_rate=0.01)):
        batches = _batches()
        ul, ut, uplans, _ = _train(False, True, opt, batches,
                                   mesh_axes=False)
        sl, st, splans, _ = _train(True, True, opt, batches)
        assert any('emb_w' in p for p in uplans if p)
        assert any('emb_w' in p for p in splans if p)
        np.testing.assert_allclose(sl, ul, rtol=1e-5)
        np.testing.assert_allclose(st, ut, rtol=1e-4, atol=1e-6)


def test_sharded_sparse_run_bundle_matches_unbundled():
    """K-step bundling composes with the sharded wire + sparse update:
    the scan body is the same step, so trajectories agree."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    batches = _batches(n=4)
    ul, ut, _, _ = _train(True, True, sgd, batches)
    bl, bt, bplans, _ = _train(True, True, sgd, batches, bundle=2)
    assert any('emb_w' in p for p in bplans if p)
    np.testing.assert_allclose(bl, ul, rtol=1e-5)
    np.testing.assert_allclose(bt, ut, rtol=1e-5, atol=1e-7)


def test_sharded_dense_grad_path_without_is_sparse():
    """is_sparse=False + is_distributed=True: the wire still serves the
    lookup and jax.grad flows back through BOTH all_to_alls (transpose =
    all_to_all) into a row-sharded dense grad. No sparse plan."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    batches = _batches(n=2)
    dl, dt, _, _ = _train(False, False, sgd, batches)
    sl, st, splans, _ = _train(True, False, sgd, batches)
    assert not any(p for p in splans)
    np.testing.assert_allclose(sl, dl, rtol=1e-5)
    np.testing.assert_allclose(st, dt, rtol=1e-4, atol=1e-6)


def test_sharded_sparse_on_dp_model_mesh():
    """dp x model composition: batch shards over dp, table rows over
    model; the wire runs inside each dp row."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    batches = _batches(n=2)
    base_l, base_t, _, _ = _train(False, False, sgd, batches)
    sl, st, splans, _ = _train(True, True, sgd, batches,
                               mesh_axes={'dp': 2, AXIS: 4})
    assert any('emb_w' in p for p in splans if p)
    np.testing.assert_allclose(sl, base_l, rtol=1e-5)
    np.testing.assert_allclose(st, base_t, rtol=1e-4, atol=1e-6)


def test_untileable_vocab_falls_back_dense_with_warning():
    """vocab 50 over 8 shards: the rule warns and serves the dense gather
    — numerics match the replicated path exactly (the statically-checked
    EmbeddingShardUntileable case reached at runtime)."""
    sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
    rng = np.random.RandomState(5)
    batches = [rng.randint(0, 50, size=(6, 4, 1)).astype('int64')
               for _ in range(2)]
    dl, dt, _, _ = _train(False, False, sgd, batches, vocab=50)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter('always')
        sl, st, _, _ = _train(True, True, sgd, batches, vocab=50)
    assert any('does not tile' in str(w.message) for w in rec)
    np.testing.assert_allclose(sl, dl, rtol=1e-5)
    np.testing.assert_allclose(st, dt, rtol=1e-4, atol=1e-6)


def test_trained_deepfm_sharded_matches_unsharded():
    """2-step trained deepfm (both FM tables sharded-sparse, adam) vs the
    same model single-device sparse: the model the subsystem exists for.
    Small config; the footprint is held by
    test_sharded_sparse_step_temp_does_not_grow_with_the_vocab."""
    from paddle_tpu.models.deepfm import deepfm

    def run(dist):
        with fresh_program() as (main, startup):
            main.random_seed = 11
            startup.random_seed = 11
            feat = layers.data(name='feat_ids', shape=[6], dtype='int64')
            label = layers.data(name='label', shape=[1], dtype='int64')
            cost, _, _ = deepfm(feat, label, num_fields=6, vocab_size=64,
                                embed_dim=4, hidden=[16],
                                dist_axis=AXIS if dist else None,
                                is_sparse=True)
            fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
            if dist:
                main.set_mesh({AXIS: 8})
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(2)
            losses = []
            for _ in range(2):
                feed = {'feat_ids': rng.randint(0, 64, size=(8, 6))
                        .astype('int64'),
                        'label': rng.randint(0, 2, size=(8, 1))
                        .astype('int64')}
                out = exe.run(main, feed=feed, fetch_list=[cost])
                losses.append(float(np.asarray(out[0]).reshape(())))
            from paddle_tpu.fluid.executor import global_scope
            tables = {n: np.asarray(global_scope().find_var(n).get_tensor())
                      for n in ('fm_first_w', 'fm_embed')}
            plans = [c.sparse_plan for c in exe._cache.values()]
            return losses, tables, plans

    ul, utab, uplans = run(False)
    sl, stab, splans = run(True)
    assert any(set(p) == {'fm_first_w', 'fm_embed'}
               for p in splans if p)
    assert any(set(p) == {'fm_first_w', 'fm_embed'}
               for p in uplans if p)
    np.testing.assert_allclose(sl, ul, rtol=1e-4)
    for n in utab:
        np.testing.assert_allclose(stab[n], utab[n], rtol=1e-3,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# loud inertness + shims
# ---------------------------------------------------------------------------

def test_is_distributed_without_annotation_warns_at_build():
    with fresh_program():
        ids = layers.data(name='ids', shape=[1], dtype='int64')
        with pytest.warns(UserWarning, match='INERT'):
            layers.embedding(ids, size=[VOCAB, DIM], is_sparse=True,
                             is_distributed=True)


def test_annotated_without_mesh_warns_at_compile():
    """The annotation is declared but the TRAINING program never calls
    set_mesh: the compile warns, naming the table and the missing axis,
    and the lookup serves dense-replicated. Inference programs are
    exempt (the gather_table + set_mesh(None) export seam runs
    dense-after-gather on purpose)."""
    with fresh_program():
        ids = layers.data(name='ids', shape=[4, 1], dtype='int64')
        emb = layers.embedding(
            ids, size=[VOCAB, DIM], is_sparse=True, is_distributed=True,
            param_attr=fluid.ParamAttr(name='emb_w',
                                       sharding=(AXIS, None)))
        pred = layers.fc(input=emb, size=1, num_flatten_dims=2,
                         bias_attr=False)
        loss = layers.mean(layers.square(pred - 1.0))
        infer = fluid.default_main_program().clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {'ids': np.zeros((4, 4, 1), 'int64')}
        with pytest.warns(UserWarning, match='no mesh'):
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[loss])
        # the for_test clone (no autodiff) compiles WITHOUT the warning
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter('always')
            exe.run(infer, feed=feed, fetch_list=[loss])
        assert not [w for w in rec if 'no mesh' in str(w.message)]


def test_distribute_transpiler_shim_translates_to_annotations():
    """transpile() deprecation-warns and stamps the row-sharding
    annotation + dist_axis routing attr on is_distributed tables — the
    pserver -> sharded-embedding migration, mechanically applied."""
    with fresh_program() as (main, _):
        ids = layers.data(name='ids', shape=[4, 1], dtype='int64')
        with warnings.catch_warnings():
            warnings.simplefilter('ignore')  # inert-annotation warning
            emb = layers.embedding(ids, size=[VOCAB, DIM], is_sparse=True,
                                   is_distributed=True,
                                   param_attr=fluid.ParamAttr(
                                       name='emb_w'))
        pred = layers.fc(input=emb, size=1, num_flatten_dims=2,
                         bias_attr=False)
        loss = layers.mean(layers.square(pred - 1.0))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        with pytest.warns(DeprecationWarning, match='sharded-embedding'):
            fluid.DistributeTranspiler().transpile(trainer_id=0,
                                                   trainers=2)
        w = main.global_block().vars['emb_w']
        assert w.sharding == ('dp', None)
        op = next(o for o in main.global_block().ops
                  if o.type == 'lookup_table')
        assert op.attrs['dist_axis'] == 'dp'
        # and the legacy path still trains (dense grad, wire lookup over
        # the dp mesh), matching the untranspiled single-device numerics
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        out = exe.run(main, feed={'ids': np.zeros((4, 4, 1), 'int64')},
                      fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()


def test_ps_dispatcher_shims_deprecated():
    from paddle_tpu.fluid.transpiler.ps_dispatcher import (HashName,
                                                           RoundRobin)

    class V(object):
        def __init__(self, name):
            self.name = name

    with pytest.warns(DeprecationWarning, match='mesh sharding'):
        rr = RoundRobin(['a:1', 'b:2'])
    assert rr.dispatch([V('x'), V('y'), V('z')]) == ['a:1', 'b:2', 'a:1']
    with pytest.warns(DeprecationWarning):
        HashName(['a:1', 'b:2'])


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_embedding_obs_events_and_rows_counter(tmp_path):
    import json

    from paddle_tpu import obs
    obs.enable(str(tmp_path))
    try:
        sgd = lambda: fluid.optimizer.SGD(learning_rate=0.1)
        base = obs.REGISTRY.total('embedding.rows_touched') or 0
        _train(True, True, sgd, _batches(n=2))
        delta = obs.REGISTRY.total('embedding.rows_touched') - base
        assert delta == 2 * 6 * 4          # 2 steps x 24 ids
    finally:
        obs._reset()
    events = []
    for p in tmp_path.glob('*.jsonl'):
        with open(p) as f:
            events.extend(json.loads(l) for l in f if l.strip())
    lookups = [e for e in events if e.get('name') == 'embedding.lookup']
    updates = [e for e in events
               if e.get('name') == 'embedding.update_rows']
    assert lookups and lookups[0]['fields']['axis_size'] == 8
    assert updates and updates[0]['fields']['rows_per_step'] == 24
    assert updates[0]['fields']['tables'] == ['emb_w']


# ---------------------------------------------------------------------------
# movielens end-to-end (slow): sharded train -> export -> serve
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_movielens_sharded_train_export_serve(tmp_path):
    """The pipeline the subsystem exists for: recommender_system with
    row-sharded user/movie/title tables trained on an 8-shard mesh
    (sharded-sparse), tables gathered at the export seam, the inference
    tower exported via export_compiled, and ONE batch served through the
    ServingEngine."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models import recommender_system as rs

    with fresh_program() as (main, startup):
        main.random_seed = 5
        startup.random_seed = 5
        scale_infer, avg_cost = rs.model(emb_dim=8, tower_dim=16,
                                         dist_axis=AXIS, axis_size=8,
                                         is_sparse=True)
        infer_prog = main.clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(avg_cost)
        main.set_mesh({AXIS: 8})
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        reader = paddle.batch(paddle.dataset.movielens.train(),
                              batch_size=16)
        feeder = fluid.DataFeeder(
            feed_list=[main.global_block().vars[n]
                       for n in rs.FEED_ORDER], place=fluid.CPUPlace())
        losses = []
        for i, batch in enumerate(reader()):
            out = exe.run(main, feed=feeder.feed(batch),
                          fetch_list=[avg_cost])
            losses.append(float(np.asarray(out[0]).reshape(())))
            if i >= 1:
                break
        assert np.isfinite(losses).all()
        assert any(c.sparse_plan for c in exe._cache.values())

        # export seam: gather the sharded tables to host values so the
        # (un-meshed) inference tower traces single-device
        from paddle_tpu.fluid.executor import global_scope
        scope = global_scope()
        for v in main.list_vars():
            if v.persistable and scope._chain_get(v.name) is not None:
                scope._chain_set(
                    v.name, jnp.asarray(emb_mod.gather_table(scope,
                                                             v.name)))
        infer_prog.set_mesh(None)
        feed_example = {}
        example = feeder.feed(batch)
        for n in rs.FEED_ORDER[:-1]:   # every input but the score label
            val = example[n]
            arr = np.asarray(val.data if hasattr(val, 'data') else val)
            feed_example[n] = arr
        from paddle_tpu import inference
        inference.export_compiled(
            str(tmp_path / 'model'), feed_example, [scale_infer], exe,
            main_program=infer_prog)
        runner = inference.load_compiled(str(tmp_path / 'model'))

        # the exported module is fixed-shape (batch 16): one bucket
        engine = serving.ServingEngine(
            runner, serving.ServingConfig(max_batch_size=16,
                                          buckets=[16],
                                          max_queue_delay_ms=1.0))
        try:
            engine.warmup()
            fut = engine.submit({n: feed_example[n]
                                 for n in feed_example})
            scores = fut.result(timeout=60)[0]
            assert np.isfinite(np.asarray(scores)).all()
        finally:
            engine.shutdown()
