"""Optimizer-pass tier (docs/passes.md).

Per-pass unit drills (DCE, constant folding, CSE, the donation/memory
plan), the PADDLE_TPU_OPT executor wiring (once-per-cache-key, key
separation, crash fallback), and the A/B equivalence contract:
`PADDLE_TPU_OPT=default` must be FETCH-EQUIVALENT to `off` — bit-exact,
AMP or not (RNG streams included: op removal must not shift another
op's dropout mask) — across the program-fuzz generator and the book
models.
"""
import contextlib
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, passes
from paddle_tpu.fluid import analysis
from paddle_tpu.fluid.executor import Scope, _switch_scope
from paddle_tpu import obs

from util import fresh_program

pytestmark = pytest.mark.passes


@contextlib.contextmanager
def _opt_env(mode):
    prev = os.environ.get(passes.ENV_OPT)
    os.environ[passes.ENV_OPT] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(passes.ENV_OPT, None)
        else:
            os.environ[passes.ENV_OPT] = prev


def _run_arm(main, startup, feed, fetch_list, mode, n=3, run=None):
    """One A/B arm: fresh scope + fresh executor (so RNG counters align
    across arms), `n` runs of the same feed under PADDLE_TPU_OPT=mode."""
    with _opt_env(mode):
        sc = Scope()
        prev = _switch_scope(sc)
        try:
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            if run is not None:
                return run(exe, sc)
            return [np.asarray(exe.run(main, feed=feed,
                                       fetch_list=fetch_list)[0])
                    for _ in range(n)]
        finally:
            _switch_scope(prev)


# ------------------------------------------------------------- unit: dce

def test_dce_removes_dead_ops_keeps_persistable_writers():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        h = layers.fc(input=x, size=8, act='relu')
        layers.exp(h)                      # dead: never fetched
        layers.softmax(h)                  # dead
        pred = layers.fc(input=h, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)

        n0 = len(main.global_block().ops)
        opt, report = passes.optimize(main, fetches=[cost.name])
        assert report.ops_after < report.ops_before == n0
        assert report.passes['dce']['ops_removed'] >= 2
        types = [op.type for op in opt.global_block().ops]
        assert 'exp' not in types and 'softmax' not in types
        # optimizer ops (persistable writers) all survive
        assert types.count('sgd') == [op.type for op in
                                      main.global_block().ops].count('sgd')
        # the original program is untouched
        assert len(main.global_block().ops) == n0
        # the optimized clone still verifies clean for this fetch set
        assert analysis.analyze(opt, fetches=[cost.name],
                                dead_ops=False) == []


def test_dce_empty_fetch_list_keeps_training_step():
    """fetch_list=[] (a pure training step): everything reaching the
    persistable updates stays, exactly like the startup program."""
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        pred = layers.fc(input=x, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        opt, report = passes.optimize(main, fetches=[])
        types = [op.type for op in opt.global_block().ops]
        assert 'autodiff' in types and 'sgd' in types
        feed = {'x': np.ones((2, 4), 'float32'),
                'y': np.ones((2, 1), 'float32')}
        a = _run_arm(main, startup, feed, [cost], 'off')
        b = _run_arm(main, startup, feed, [cost], 'default')
        np.testing.assert_array_equal(a, b)


def test_dce_kept_effectful_op_pins_its_producers():
    """A retained print op's whole producer chain must survive DCE (a
    kept op reading a pruned name would KeyError at trace time), and the
    program still runs under OPT=default."""
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        h = layers.relu(x)
        layers.Print(h)                       # effectful, not fetched
        out = layers.scale(x, scale=2.0)
        opt, report = passes.optimize(main, fetches=[out.name])
        types = [op.type for op in opt.global_block().ops]
        assert 'print' in types and 'relu' in types
        feed = {'x': np.ones((2, 4), 'float32')}
        a = _run_arm(main, startup, feed, [out], 'off', n=1)
        b = _run_arm(main, startup, feed, [out], 'default', n=1)
    np.testing.assert_array_equal(a[0], b[0])


def test_optimizer_self_check_falls_back_not_crashes():
    """A pass bug that corrupts the graph must surface as the executor's
    documented fallback (warn + unoptimized lowering), never a raw trace
    error: drill it by breaking the optimized clone via a monkeypatched
    pass."""
    import paddle_tpu.fluid.passes.dce as dce_mod
    orig = dce_mod.run

    def broken(program, report, fetches):
        block = program.global_block()
        block.ops = [op for op in block.ops if op.type != 'relu']
        return 1

    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        h = layers.relu(x)
        out = layers.scale(h, scale=2.0)
        feed = {'x': np.ones((2, 4), 'float32')}
        a = _run_arm(main, startup, feed, [out], 'off', n=1)
        dce_mod.run = broken
        try:
            with pytest.warns(RuntimeWarning, match='optimization failed'):
                b = _run_arm(main, startup, feed, [out], 'default', n=1)
        finally:
            dce_mod.run = orig
    np.testing.assert_array_equal(a[0], b[0])


# ------------------------------------------------------------ unit: fold

def test_fold_constant_chain_bit_exact():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        c = layers.fill_constant(shape=[4], dtype='float32', value=2.5)
        c2 = layers.scale(c, scale=3.0, bias=1.0)     # foldable
        c3 = layers.elementwise_add(c2, c2)           # foldable
        out = layers.elementwise_add(x, c3)
        opt, report = passes.optimize(main, fetches=[out.name])
        assert report.passes['fold']['ops_folded'] >= 2
        types = [op.type for op in opt.global_block().ops]
        assert 'scale' not in types
        assert 'assign_value' in types
        # fill_constant + intermediate folds are dead afterwards: swept
        assert report.passes['dce']['ops_removed'] >= 1
        feed = {'x': np.arange(8, dtype='float32').reshape(2, 4)}
        a = _run_arm(main, startup, feed, [out], 'off', n=1)
        b = _run_arm(main, startup, feed, [out], 'default', n=1)
        np.testing.assert_array_equal(a, b)


def test_fold_skips_rng_and_respects_cap():
    with fresh_program() as (main, startup):
        r = layers.uniform_random([4, 4], dtype='float32')
        out1 = layers.scale(r, scale=2.0)             # rng upstream
        big = layers.fill_constant(shape=[128, 128], dtype='float32',
                                   value=1.0)
        out2 = layers.scale(big, scale=2.0)           # 16384 > default cap
        opt, report = passes.optimize(
            main, fetches=[out1.name, out2.name])
        types = [op.type for op in opt.global_block().ops]
        assert 'uniform_random' in types
        assert types.count('scale') == 2              # neither folded
        opt2, report2 = passes.optimize(
            main, fetches=[out1.name, out2.name], level='aggressive')
        assert report2.passes['fold']['ops_folded'] == 1   # big one folds


def test_fold_leaves_the_rules_that_cast_under_amp():
    """Folding evaluates a rule with amp off, so a rule that calls
    `amp_cast` is not folded: a product of two constants in an AMP program
    stays an op of the step, which runs it in bf16 either way."""
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        c = layers.fill_constant(shape=[4, 4], dtype='float32', value=1.1)
        cc = layers.matmul(c, layers.scale(c, scale=3.0))   # scale folds
        out = layers.elementwise_add(x, cc)
        fluid.amp.decorate_program(main)
        opt, report = passes.optimize(main, fetches=[out.name])
        assert report.passes['fold']['ops_folded'] == 1
        types = [op.type for op in opt.global_block().ops]
        assert 'matmul' in types and 'scale' not in types
        assert opt._amp
        feed = {'x': np.ones((4, 4), 'float32')}
        a = _run_arm(main, startup, feed, [out], 'off', n=1)
        b = _run_arm(main, startup, feed, [out], 'default', n=1)
    np.testing.assert_array_equal(a, b)
    # 1.1 is not a bf16 number: the product did run in bf16
    assert not np.allclose(a[0], 1.0 + 4 * 1.1 * 3.3, rtol=1e-6)


# ------------------------------------------------------------- unit: cse

def test_cse_merges_duplicates_not_rng():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        a = layers.tanh(x)
        b = layers.tanh(x)                  # duplicate
        d1 = layers.dropout(x, dropout_prob=0.5)
        d2 = layers.dropout(x, dropout_prob=0.5)   # NOT a duplicate (rng)
        out = layers.elementwise_add(layers.elementwise_add(a, b),
                                     layers.elementwise_add(d1, d2))
        opt, report = passes.optimize(main, fetches=[out.name])
        assert report.passes['cse']['ops_merged'] == 1
        types = [op.type for op in opt.global_block().ops]
        assert types.count('tanh') == 1
        assert types.count('dropout') == 2
        feed = {'x': np.random.RandomState(3).rand(4, 8).astype('float32')}
        a_ = _run_arm(main, startup, feed, [out], 'off', n=2)
        b_ = _run_arm(main, startup, feed, [out], 'default', n=2)
        np.testing.assert_array_equal(a_, b_)      # dropout masks included


def test_cse_protects_attr_referenced_names():
    """Control-flow rules resolve some env names from ATTRS (switch
    cond_names, static_rnn step_ins/mems) — the rename walk cannot see
    those, so a duplicate whose output is attr-referenced must never be
    merged (previously: KeyError at trace time under OPT=default)."""
    with fresh_program() as (main, startup):
        i = layers.fill_constant(shape=[1], dtype='float32', value=3.0)
        n = layers.data(name='n', shape=[1], dtype='float32')
        c1 = layers.less_than(i, n)
        c2 = layers.less_than(i, n)            # duplicate, feeds Switch
        out = layers.create_global_var(shape=[1], value=0.0,
                                       dtype='float32',
                                       persistable=False, name='sw_out')
        with layers.Switch() as switch:
            with switch.case(c2):
                layers.assign(layers.fill_constant(
                    shape=[1], dtype='float32', value=1.0), out)
            with switch.default():
                layers.assign(layers.fill_constant(
                    shape=[1], dtype='float32', value=2.0), out)
        _ = c1
        feed = {'n': np.full((1, 1), 5.0, 'float32')}
        a = _run_arm(main, startup, feed, [out], 'off', n=1)
        b = _run_arm(main, startup, feed, [out], 'default', n=1)
    np.testing.assert_array_equal(a[0], b[0])


def _append_undeclared_write_loop(main, target):
    """Hand-append a `while` op whose body writes `target` WITHOUT
    listing it in the op's outputs — the write class the layer builders
    always declare but hand-built / deserialized programs may not
    (analysis models it via dataflow._block_writes). Returns the while op."""
    cond = layers.fill_constant(shape=[1], dtype='bool', value=False)
    sub = main.create_block()
    five = sub.create_var(name='five@sbw', shape=[1], dtype='float32')
    sub.append_op(type='fill_constant', inputs={}, outputs={'Out': [five]},
                  attrs={'shape': [1], 'dtype': 'float32', 'value': 5.0},
                  infer_shape=False)
    sub.append_op(type='assign', inputs={'X': [five]},
                  outputs={'Out': [target]}, infer_shape=False)
    main.rollback()
    return main.current_block().append_op(
        type='while', inputs={'Condition': [cond], 'X': []},
        outputs={'Out': [cond]}, attrs={'sub_block': sub.idx},
        infer_shape=False)


def test_cse_sees_undeclared_sub_block_writes():
    """Two identical pure reads straddling a sub-block that writes their
    input without declaring it as the loop op's output must NOT merge:
    CSE's version map bumps written_names (declared outputs + sub-block
    writes), matching the analysis layer's write model, so the second
    read is never proven to be the same value."""
    with fresh_program() as (main, _):
        w = layers.create_global_var(shape=[1], value=3.0, dtype='float32',
                                     persistable=True, name='w@sbw')
        pre = layers.scale(w, scale=2.0)
        _append_undeclared_write_loop(main, w)
        post = layers.scale(w, scale=2.0)
        out = layers.elementwise_add(pre, post)
        opt, report = passes.optimize(main, fetches=[out.name])
        assert report.passes['cse']['ops_merged'] == 0
        types = [op.type for op in opt.global_block().ops]
        assert types.count('scale') == 2


def test_cse_skips_fetched_and_persistable_outputs():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        a = layers.tanh(x)
        b = layers.tanh(x)
        opt, report = passes.optimize(main, fetches=[a.name, b.name])
        # both tanh outputs are fetch targets: neither may disappear
        assert report.passes['cse']['ops_merged'] == 0
        types = [op.type for op in opt.global_block().ops]
        assert types.count('tanh') == 2


# ----------------------------------------------------- donation/memory plan

def test_memory_plan_train_vs_inference():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        pred = layers.fc(input=x, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        infer = main.clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        train_plan = passes.memory_plan(main)
        infer_plan = passes.memory_plan(infer)
    assert train_plan.donates and train_plan.write_set
    assert not infer_plan.donates and not infer_plan.write_set
    assert infer_plan.readonly_names(['a', 'b']) == ['a', 'b']
    assert train_plan.persist_out() == sorted(train_plan.write_set)


def test_plan_readonly_persistables_not_donated_or_refreshed():
    """A persistable the step only READS keeps its scope buffer: it is
    neither donated (stays valid) nor re-exposed as an output (no
    passthrough copy per step)."""
    import jax.numpy as jnp
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        table = layers.create_parameter([4], 'float32', name='frozen_w')
        table.stop_gradient = True
        xx = layers.elementwise_add(x, table)
        pred = layers.fc(input=xx, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)

        sc = Scope()
        prev = _switch_scope(sc)
        try:
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            frozen_before = sc.vars['frozen_w']
            feed = {'x': np.ones((2, 4), 'float32'),
                    'y': np.ones((2, 1), 'float32')}
            exe.run(main, feed=feed, fetch_list=[cost])
            (compiled,) = [c for c in exe._cache.values()
                           if c.ad_idx is not None]
            assert compiled.plan.donates
            assert 'frozen_w' in compiled.readonly_names
            assert 'frozen_w' not in compiled.donate_names
            assert 'frozen_w' not in compiled.persist_out
            # buffer identity preserved AND still readable (not donated)
            assert sc.vars['frozen_w'] is frozen_before
            np.testing.assert_array_equal(np.asarray(frozen_before),
                                          np.asarray(sc.vars['frozen_w']))
            # while the written params DID refresh
            w = [n for n in compiled.donate_names if n.endswith('.w_0')]
            assert w
            exe.run(main, feed=feed, fetch_list=[cost])
        finally:
            _switch_scope(prev)


# ------------------------------------------------------- executor wiring

def test_opt_env_knob_once_per_cache_key():
    hist = obs.REGISTRY.histogram('passes.optimize.seconds')
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=2.0)
        feed = {'x': np.ones((2, 4), 'float32')}
        with _opt_env('default'):
            sc = Scope()
            prev = _switch_scope(sc)
            try:
                exe = fluid.Executor(fluid.CPUPlace())
                before = hist.snapshot()['count']
                r1 = exe.run(main, feed=feed, fetch_list=[out])
                r2 = exe.run(main, feed=feed, fetch_list=[out])
                # ONE passes.optimize span for two runs of the same key
                assert hist.snapshot()['count'] == before + 1
            finally:
                _switch_scope(prev)


def test_opt_mode_is_part_of_the_cache_key():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=2.0)
        feed = {'x': np.ones((2, 4), 'float32')}
        sc = Scope()
        prev = _switch_scope(sc)
        try:
            exe = fluid.Executor(fluid.CPUPlace())
            with _opt_env('off'):
                exe.run(main, feed=feed, fetch_list=[out])
            n_off = exe.cache_stats['entries']
            with _opt_env('default'):
                exe.run(main, feed=feed, fetch_list=[out])
            assert exe.cache_stats['entries'] == n_off + 1
        finally:
            _switch_scope(prev)


def test_opt_counters_report_op_deltas():
    c_removed = obs.REGISTRY.counter('passes.dce.ops_removed')
    c_progs = obs.REGISTRY.counter('passes.programs_optimized')
    before = c_removed.snapshot()['value']
    before_p = c_progs.snapshot()['value']
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        layers.exp(x)     # dead
        out = layers.scale(x, scale=2.0)
        passes.optimize(main, fetches=[out.name])
    assert c_removed.snapshot()['value'] > before
    assert c_progs.snapshot()['value'] == before_p + 1


def test_program_optimize_api():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        layers.exp(x)
        out = layers.scale(x, scale=2.0)
        opt = main.optimize(fetches=[out.name])
        assert opt is not main
        assert opt._opt_report.ops_after < opt._opt_report.ops_before
        assert len(opt.global_block().ops) < len(main.global_block().ops)


def test_program_optimize_returns_owned_clone_on_skip():
    """Program.optimize() promises a program the caller owns even when
    the pipeline skips (level='off'): mutating the result must never
    corrupt the original. (passes.optimize itself keeps the aliasing —
    the executor wants no extra clone on its fallback path.)"""
    with fresh_program() as (main, _):
        x = layers.data(name='x', shape=[4], dtype='float32')
        layers.scale(x, scale=2.0)
        q = main.optimize(level='off')
        assert q is not main
        assert q._opt_report.skipped == 'level=off'
        n = len(main.global_block().ops)
        q.global_block().ops.pop()
        assert len(main.global_block().ops) == n


def test_pipeline_programs_are_left_alone():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        out = layers.scale(x, scale=2.0)
        main._pipeline_config = {'sentinel': True}   # transpiled marker
        opt, report = passes.optimize(main, fetches=[out.name])
        assert opt is main
        assert 'pipeline' in report.skipped


# ------------------------------------------------- A/B: fuzz + bundling

def test_fuzz_graphs_bit_exact_off_vs_default():
    from test_program_fuzz import _random_graph
    for seed in range(6):
        rng = np.random.RandomState(seed)
        feed = {'x': rng.randn(4, 8).astype('float32')}
        with fresh_program() as (main, startup):
            x = layers.data(name='x', shape=[8], dtype='float32')
            out = _random_graph(rng, x)
            a = _run_arm(main, startup, feed, [out], 'off', n=1)
            b = _run_arm(main, startup, feed, [out], 'default', n=1)
        np.testing.assert_array_equal(
            a[0], b[0], err_msg='seed %d diverged under optimization'
            % seed)


def test_training_with_dropout_bit_exact_off_vs_default():
    """The strictest RNG drill: a trained-through dropout program with a
    dead branch — DCE removes an op BEFORE the dropout, and the mask
    stream must not move (op_seq stamping)."""
    feed = {'x': np.random.RandomState(0).rand(8, 8).astype('float32'),
            'y': np.random.RandomState(1).rand(8, 1).astype('float32')}

    def build():
        x = layers.data(name='x', shape=[8], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        h = layers.fc(input=x, size=16, act='relu')
        layers.exp(h)                          # dead
        d = layers.dropout(h, dropout_prob=0.3)
        pred = layers.fc(input=d, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
        return cost

    with fresh_program() as (main, startup):
        cost = build()
        a = _run_arm(main, startup, feed, [cost], 'off', n=4)
        b = _run_arm(main, startup, feed, [cost], 'default', n=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_bundle_off_vs_default_bit_exact():
    feeds = [{'x': np.random.RandomState(i).rand(4, 4).astype('float32'),
              'y': np.random.RandomState(100 + i).rand(4, 1)
              .astype('float32')} for i in range(4)]
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        y = layers.data(name='y', shape=[1], dtype='float32')
        pred = layers.fc(input=x, size=1)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)

        def bundle_arm(exe, sc):
            out, = exe.run_bundle(main, feeds=feeds, fetch_list=[cost])
            return [np.asarray(out)]

        a = _run_arm(main, startup, None, None, 'off', run=bundle_arm)
        b = _run_arm(main, startup, None, None, 'default', run=bundle_arm)
    np.testing.assert_array_equal(a[0], b[0])


# ------------------------------------------------------ transpiler shims

def test_transpiler_shims_deprecate_over_passes():
    with fresh_program() as (main, startup):
        x = layers.data(name='x', shape=[4], dtype='float32')
        pred = layers.fc(input=x, size=1)
        with pytest.warns(DeprecationWarning, match='memory_optimize'):
            fluid.memory_optimize(main)
        assert main._use_remat
        with pytest.warns(DeprecationWarning, match='fold_batch_norm'):
            fluid.InferenceTranspiler().transpile(main, fluid.CPUPlace())


# ----------------------------------------------------- book-model sweep

_SWEEP = {
    'fit_a_line': dict(kwargs=dict(batch_size=4), feeds=['x', 'y']),
    'mnist': dict(kwargs=dict(batch_size=4), feeds=['pixel', 'label'],
                  transform=lambda b: [(np.reshape(i, (1, 28, 28)), l)
                                       for i, l in b]),
    'vgg': dict(kwargs=dict(batch_size=2), feeds=['data', 'label'],
                transform=lambda b: [(np.reshape(i, (3, 32, 32)), l)
                                     for i, l in b], slow=True),
    'resnet': dict(kwargs=dict(depth=8, batch_size=2),
                   feeds=['data', 'label'],
                   transform=lambda b: [(np.reshape(i, (3, 32, 32)), l)
                                        for i, l in b], slow=True),
    'stacked_dynamic_lstm': dict(
        kwargs=dict(batch_size=2, lstm_size=16, emb_dim=16),
        feeds=['words', 'label']),
    'machine_translation': dict(
        kwargs=dict(batch_size=2, embedding_dim=16, encoder_size=16,
                    decoder_size=16, dict_size=40), feeds_idx=4),
    'transformer': dict(
        kwargs=dict(batch_size=2, max_length=8, n_layer=1, d_model=32,
                    n_head=2, d_inner=32, dict_size=60, warmup_steps=50),
        feeds_idx=4, stack=True),
    'deepfm': dict(kwargs=dict(batch_size=4, embed_dim=4), feeds_idx=4),
    'word2vec': dict(kwargs=dict(batch_size=4), feeds_idx=4),
    'se_resnext': dict(kwargs=dict(batch_size=2, class_dim=4),
                       feeds_idx=4, slow=True),
    'understand_sentiment': dict(kwargs=dict(batch_size=4), feeds_idx=4),
    'label_semantic_roles': dict(
        kwargs=dict(batch_size=2, word_dim=8, mark_dim=2, hidden_dim=16,
                    depth=2), reader_idx=2, feeds_idx=3),
    'recommender_system': dict(
        kwargs=dict(batch_size=4, emb_dim=8, tower_dim=16),
        reader_idx=3, feeds_idx=5),
    'olmoe': dict(
        kwargs=dict(batch_size=2, seq_len=16, vocab_size=64, hidden=32,
                    n_expert=4, expert_width=16), feeds_idx=4, stack=True),
    'qwen3_next': dict(
        kwargs=dict(batch_size=2, seq_len=16, vocab_size=64, hidden=32,
                    n_expert=8, expert_width=16, experts_held=(2, 4)),
        feeds_idx=4, stack=True),
    'granitemoehybrid': dict(
        kwargs=dict(batch_size=2, seq_len=16, vocab_size=64, hidden=32,
                    ssm_heads=2, ssm_head_dim=16, ssm_state=8, chunk_size=8,
                    n_head=2, n_kv_head=1, d_head=16, mlp_width=64,
                    layer_types=('mamba', 'attention')),
        feeds_idx=4, stack=True),
    'bailing_hybrid': dict(
        kwargs=dict(batch_size=2, seq_len=16, vocab_size=64, hidden=32,
                    dense_width=64, n_head=2, head_dim=16, kv_rank=8,
                    d_nope=16, d_rope=8, d_v=16, n_expert=8, top_k=2,
                    n_group=4, topk_group=2, expert_width=16,
                    experts_held=(2, 4)),
        feeds_idx=4, stack=True),
    'evabyte': dict(
        kwargs=dict(batch_size=2, seq_len=32, vocab_size=64, n_layer=1,
                    hidden=32, n_head=2, d_head=16, mlp_width=64,
                    chunk_size=4, window_size=16, n_pred_heads=4),
        feeds_idx=4, stack=True),
}


def _sweep_params():
    from paddle_tpu import models
    assert set(_SWEEP) == set(models.model_list)
    return [pytest.param(n, False, id=n, marks=pytest.mark.slow
                         if _SWEEP[n].get('slow') else ())
            for n in models.model_list] + [
        pytest.param('transformer', True, id='transformer-amp')]


@pytest.mark.parametrize('name,amp', _sweep_params())
def test_book_model_off_vs_default_equivalent(name, amp):
    """Acceptance: PADDLE_TPU_OPT=default is fetch-equivalent to off on
    every book model — bit-exact, the AMP-decorated Transformer included
    (bf16 is cast by the rules on both arms), across two training steps
    including every dropout mask and optimizer update."""
    from paddle_tpu import models
    mod = models.get_model_module(name)
    spec = _SWEEP[name]
    with fresh_program() as (main, startup):
        ret = mod.get_model(**spec.get('kwargs', {}))
        cost = ret[0]
        if amp:
            fluid.amp.decorate_program(main)
        reader = ret[spec.get('reader_idx', 2)]
        feeds = spec.get('feeds') or ret[spec['feeds_idx']]
        batch = next(iter(reader()))
        if spec.get('transform'):
            batch = spec['transform'](batch)
        if spec.get('stack'):
            feed = {n: np.stack([r[i] for r in batch])
                    for i, n in enumerate(feeds)}
        else:
            feeder = fluid.DataFeeder(
                place=fluid.CPUPlace(),
                feed_list=[main.global_block().var(f) for f in feeds])
            feed = feeder.feed(batch)
        a = _run_arm(main, startup, feed, [cost], 'off', n=2)
        b = _run_arm(main, startup, feed, [cost], 'default', n=2)
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b),
        err_msg='%s diverged under PADDLE_TPU_OPT=default' % name)


def test_book_model_op_count_reduction_reported():
    """At least one real model must show an op-count REDUCTION, reported
    through the passes.* obs counters (the attribution contract for
    obs_report): label_semantic_roles builds a CRF
    decode path the training fetch never uses — dead for the cost-only
    fetch set the trainer runs."""
    from paddle_tpu import models
    c_removed = obs.REGISTRY.counter('passes.ops_removed')
    before = c_removed.snapshot()['value']
    mod = models.get_model_module('label_semantic_roles')
    with fresh_program() as (main, startup):
        ret = mod.get_model(**_SWEEP['label_semantic_roles']['kwargs'])
        cost = ret[0]
        opt, report = passes.optimize(main, fetches=[cost.name])
    assert report.ops_after < report.ops_before, report
    assert c_removed.snapshot()['value'] > before
