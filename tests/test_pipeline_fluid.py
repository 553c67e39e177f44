"""Fluid-level pipeline parallelism: device_guard('pipe:K') stages +
PipelineTranspiler == sequential execution of the same program.

The GPipe schedule (parallel/pipeline.py) is driven from a Fluid Program:
the transpiler aligns the stamped stages, stacks per-stage parameters,
identifies the flow activation and the shared extras, and the Executor runs
the region as one pipeline_apply inside the jitted train step — forward AND
backward (jax.grad differentiates through scan+ppermute), with the
program's own optimizer updating the per-stage parameters.
"""
import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

from util import fresh_program

S, NMICRO, BATCH, D = 4, 4, 8, 12


def _build(lr=0.05):
    """Prologue -> S stamped residual stages (each with its own params and
    a shared 'mask' extra) -> loss. Distinct per-stage constants so a
    stage/parameter misrouting changes the numbers."""
    x = fluid.layers.data(name='x', shape=[D], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    h = layers.fc(input=x, size=D, act='tanh',
                  param_attr=fluid.ParamAttr(
                      initializer=fluid.initializer.Constant(0.05)))
    mask = layers.fc(input=x, size=D, act='sigmoid',
                     param_attr=fluid.ParamAttr(
                         initializer=fluid.initializer.Constant(-0.03)))
    for k in range(S):
        with fluid.device_guard('pipe:%d' % k):
            f = layers.fc(input=h, size=D, act='tanh',
                          param_attr=fluid.ParamAttr(
                              initializer=fluid.initializer.Constant(
                                  0.01 * (k + 1))),
                          bias_attr=False)
            f = layers.elementwise_mul(f, mask)
            h = layers.elementwise_add(f, h)
    pred = layers.fc(input=h, size=1,
                     param_attr=fluid.ParamAttr(
                         initializer=fluid.initializer.Constant(0.07)))
    cost = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=lr).minimize(cost)
    return cost, pred


def _data():
    rng = np.random.RandomState(7)
    return (rng.rand(BATCH, D).astype('float32'),
            rng.rand(BATCH, 1).astype('float32'))


def _train(transpile, steps=4, n_virtual=1):
    xs, ys = _data()
    with fresh_program() as (main, startup):
        cost, _ = _build()
        params = [p.name for p in main.global_block().all_parameters()]
        if transpile:
            fluid.PipelineTranspiler(n_micro=NMICRO,
                                     n_virtual=n_virtual).transpile(main)
            cfg = main._pipeline_config
            assert cfg['n_stages'] == S
            assert main._dist_config['pp_size'] == S // n_virtual
            assert len(cfg['param_names'][0]) == 1      # one fc.w per stage
            assert cfg['extra_names'] == []
            assert len(cfg['extra_stream_names']) == 1   # the shared mask
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(main, feed={'x': xs, 'y': ys},
                                fetch_list=[cost])[0]) for _ in range(steps)]
        finals = [np.asarray(v) for v in
                  exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=params)]
    return losses, dict(zip(params, finals))


def test_pipeline_matches_sequential_training():
    seq_losses, seq_params = _train(transpile=False)
    pp_losses, pp_params = _train(transpile=True)
    np.testing.assert_allclose(pp_losses, seq_losses, rtol=1e-4)
    assert seq_losses[-1] < seq_losses[0]   # it actually trains
    for name in seq_params:
        np.testing.assert_allclose(pp_params[name], seq_params[name],
                                   rtol=1e-4, atol=1e-6,
                                   err_msg='parameter %s diverged' % name)


def test_circular_pipeline_matches_sequential_training():
    """n_virtual=2: the 4 stamped stages run as 2 chunks per device on a
    pp=2 mesh (each microbatch rides the ring twice); losses AND updated
    parameters match the sequential run."""
    seq_losses, seq_params = _train(transpile=False)
    pp_losses, pp_params = _train(transpile=True, n_virtual=2)
    np.testing.assert_allclose(pp_losses, seq_losses, rtol=1e-4)
    for name in seq_params:
        np.testing.assert_allclose(pp_params[name], seq_params[name],
                                   rtol=1e-4, atol=1e-6,
                                   err_msg='parameter %s diverged' % name)


def test_circular_pipeline_validation():
    with fresh_program() as (main, startup):
        _build()
        # 4 stages / n_virtual=3 does not divide
        with pytest.raises(ValueError, match='n_virtual'):
            fluid.PipelineTranspiler(n_micro=NMICRO,
                                     n_virtual=3).transpile(main)
        # 4 stages / n_virtual=4 leaves a 1-device pipeline
        with pytest.raises(ValueError, match='n_virtual'):
            fluid.PipelineTranspiler(n_micro=NMICRO,
                                     n_virtual=4).transpile(main)
    with pytest.raises(ValueError, match='n_virtual'):
        fluid.PipelineTranspiler(n_micro=2, n_virtual=0)


def test_pipeline_validation_errors():
    # stages out of order
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[D], dtype='float32')
        h = layers.fc(input=x, size=D)
        with fluid.device_guard('pipe:1'):
            h = layers.fc(input=h, size=D, bias_attr=False)
        with fluid.device_guard('pipe:0'):
            h = layers.fc(input=h, size=D, bias_attr=False)
        with pytest.raises(ValueError, match='increasing order'):
            fluid.PipelineTranspiler(n_micro=2).transpile(main)

    # structurally different stages
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[D], dtype='float32')
        h = layers.fc(input=x, size=D)
        with fluid.device_guard('pipe:0'):
            h = layers.fc(input=h, size=D, bias_attr=False)
        with fluid.device_guard('pipe:1'):
            h = layers.fc(input=h, size=D, bias_attr=False)
            h = layers.relu(h)
        with pytest.raises(ValueError, match='structurally identical'):
            fluid.PipelineTranspiler(n_micro=2).transpile(main)

    # no stamps at all
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[D], dtype='float32')
        layers.fc(input=x, size=D)
        with pytest.raises(ValueError, match='no device_guard'):
            fluid.PipelineTranspiler(n_micro=2).transpile(main)


def test_pipeline_rejects_indivisible_batch():
    with fresh_program() as (main, startup):
        _build()
        fluid.PipelineTranspiler(n_micro=3).transpile(main)  # 3 !| 8
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xs, ys = _data()
        with pytest.raises(ValueError, match='divide batch'):
            exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=[])


def _train_transformer(pp, steps=2):
    """One small Fluid Transformer (dropout off for determinism), decoder
    stack pipelined when pp=True."""
    from paddle_tpu.models import transformer as T
    rng = np.random.RandomState(11)
    vocab, seq, batch = 32, 8, 4
    feed_ids = {n: rng.randint(1, vocab, size=(batch, seq)).astype('int64')
                for n in ('src_word', 'trg_word', 'lbl_word')}
    with fresh_program() as (main, startup):
        avg_cost, _, feeds = T.transformer(
            vocab, vocab, seq, n_layer=4, d_model=16, n_head=2, d_inner=32,
            dropout_rate=0.0, pp_decoder=pp)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
        if pp:
            fluid.PipelineTranspiler(n_micro=2).transpile(main)
            cfg = main._pipeline_config
            assert cfg['n_stages'] == 4
            # enc output + the two pad biases stream per microbatch
            assert len(cfg['extra_stream_names']) == 3
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [float(exe.run(main, feed=feed_ids,
                              fetch_list=[avg_cost])[0])
                for _ in range(steps)]


def test_pipeline_transformer_matches_sequential():
    """The equality IS the contract: the pipelined decoder stack computes
    bit-near-identical losses and updates to sequential execution."""
    seq = _train_transformer(pp=False)
    pip = _train_transformer(pp=True)
    assert seq[0] != seq[1]   # the step changed the parameters
    np.testing.assert_allclose(pip, seq, rtol=2e-4)


def test_pipeline_region_internal_fetch_raises():
    """Fetching a var produced inside the GPipe region gives a clear error
    (the region runs as one pipeline_apply; internals don't exist in env)."""
    xs, ys = _data()
    with fresh_program() as (main, startup):
        cost, _ = _build()
        cfg_internal = None
        for op in main.global_block().ops:
            if str(op.attrs.get('op_device', '')).startswith('pipe:1'):
                cfg_internal = op.output_arg_names[0]
                break
        fluid.PipelineTranspiler(n_micro=NMICRO).transpile(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(ValueError, match='pipeline region'):
            exe.run(main, feed={'x': xs, 'y': ys},
                    fetch_list=[cost, cfg_internal])


def test_pipeline_custom_axis_name():
    """axis= plumbs through to the executor mesh (not hardcoded 'pp')."""
    xs, ys = _data()
    with fresh_program() as (main, startup):
        cost, _ = _build()
        fluid.PipelineTranspiler(n_micro=NMICRO, axis='stage').transpile(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        loss = float(exe.run(main, feed={'x': xs, 'y': ys},
                             fetch_list=[cost])[0])
        assert 'stage' in main._dist_mesh.shape
        # and it genuinely engaged the pipelined step
        compiled = next(c for c in exe._cache.values() if c.pipe is not None)
        assert compiled.pipe['axis'] == 'stage'
    seq_losses, _ = _train(transpile=False, steps=1)
    np.testing.assert_allclose(loss, seq_losses[0], rtol=1e-4)


def test_pipeline_clone_and_inference_model_roundtrip(tmp_path):
    """clone(for_test=True) keeps the mesh annotation (re-transpiled on the
    clone), and save/load_inference_model works from a transpiled program —
    the loaded, pruned program needs no label feed."""
    xs, ys = _data()
    with fresh_program() as (main, startup):
        cost, pred = _build()
        fluid.PipelineTranspiler(n_micro=NMICRO).transpile(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=[cost])

        infer = main.clone(for_test=True)
        assert infer._pipeline_config is not None          # re-derived
        l1, = exe.run(infer, feed={'x': xs, 'y': ys}, fetch_list=[cost])
        l2, = exe.run(infer, feed={'x': xs, 'y': ys}, fetch_list=[cost])
        assert float(np.asarray(l1)) == float(np.asarray(l2))

        d = str(tmp_path / 'inf')
        fluid.io.save_inference_model(d, ['x'], [pred], exe,
                                      main_program=main)
        prog, feed_names, fetch_targets = fluid.io.load_inference_model(
            d, exe)
        assert feed_names == ['x']
        out, = exe.run(prog, feed={'x': xs}, fetch_list=fetch_targets)
        assert np.asarray(out).shape == (BATCH, 1)
        # and the mesh'd training program still runs after the load
        exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=[cost])


@pytest.mark.parametrize('order', ['dp_first', 'pp_first'])
def test_pipeline_composes_with_dp(order):
    """dp x pp: DistributeTranspiler + PipelineTranspiler in either
    order — feeds shard over dp, each dp slice runs its own GPipe ring;
    losses AND final parameters == sequential."""
    seq_losses, seq_params = _train(transpile=False)
    xs, ys = _data()
    with fresh_program() as (main, startup):
        cost, _ = _build()
        params = [p.name for p in main.global_block().all_parameters()]
        if order == 'dp_first':
            fluid.DistributeTranspiler().transpile(trainer_id=0, trainers=2)
            fluid.PipelineTranspiler(n_micro=NMICRO).transpile(main)
        else:
            fluid.PipelineTranspiler(n_micro=NMICRO).transpile(main)
            fluid.DistributeTranspiler().transpile(trainer_id=0, trainers=2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(main, feed={'x': xs, 'y': ys},
                                fetch_list=[cost])[0]) for _ in range(4)]
        assert set(main._dist_mesh.shape) == {'dp', 'pp'}
        finals = [np.asarray(v) for v in
                  exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=params)]
    np.testing.assert_allclose(losses, seq_losses, rtol=1e-4)
    for name, got in zip(params, finals):
        np.testing.assert_allclose(got, seq_params[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)


def test_pipeline_multi_layer_stages():
    """4 decoder layers packed into 2 stages (pp_decoder=2): fewer chips
    than layers, the standard GPipe packing — still == sequential."""
    from paddle_tpu.models import transformer as T
    rng = np.random.RandomState(91)
    vocab, seq, batch = 32, 8, 4
    feed_ids = {n: rng.randint(1, vocab, size=(batch, seq)).astype('int64')
                for n in ('src_word', 'trg_word', 'lbl_word')}

    def run(pp):
        with fresh_program() as (main, startup):
            avg_cost, _, feeds = T.transformer(
                vocab, vocab, seq, n_layer=4, d_model=16, n_head=2,
                d_inner=32, dropout_rate=0.0, pp_decoder=pp)
            fluid.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
            if pp:
                fluid.PipelineTranspiler(n_micro=2).transpile(main)
                assert main._pipeline_config['n_stages'] == 2
                # 2 layers' worth of params per stage (4 fc in mha x2 +
                # 2 ffn fc + 3 layer_norm scale/bias pairs, x2 layers)
                assert len(main._pipeline_config['param_names'][0]) > 10
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [float(exe.run(main, feed=feed_ids,
                                  fetch_list=[avg_cost])[0])
                    for _ in range(2)]

    base = run(False)
    got = run(2)
    assert base[0] != base[1]
    np.testing.assert_allclose(got, base, rtol=2e-4)

    with pytest.raises(ValueError, match='divide n_layer'):
        T.transformer(32, 32, 8, n_layer=4, d_model=16, n_head=2,
                      d_inner=32, pp_decoder=3)


def test_rejected_transpile_leaves_program_unmodified():
    """A rejected transpile must not leave a stale _pipeline_config behind
    (clone()'s _retranspile_pipeline would silently re-run it): every
    validation error fires before the program is annotated."""
    with fresh_program() as (main, startup):
        _build()
        main._dist_config = {'sp_size': 2, 'mesh_axes': ('sp',)}
        with pytest.raises(ValueError, match='n_virtual'):
            fluid.PipelineTranspiler(n_micro=2, n_virtual=3).transpile(main)
        assert getattr(main, '_pipeline_config', None) is None
        assert 'pp_size' not in main._dist_config


def test_pipeline_rejects_extra_slot_in_later_stage():
    """The executor replays stage 0's op list for every stage: an extra
    input/output slot present only in a later stage must be rejected, not
    silently dropped."""
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[D], dtype='float32')
        h = layers.fc(input=x, size=D)
        blk = main.global_block()
        bonus = blk.create_var(name='bonus', shape=[-1, D], dtype='float32')
        s0 = blk.create_var(name='s0_out', shape=[-1, D], dtype='float32')
        s1 = blk.create_var(name='s1_out', shape=[-1, D], dtype='float32')
        with fluid.device_guard('pipe:0'):
            blk.append_op(type='scale', inputs={'X': [h]},
                          outputs={'Out': [s0]}, attrs={'scale': 2.0})
        with fluid.device_guard('pipe:1'):
            blk.append_op(type='scale', inputs={'X': [s0], 'Bonus': [bonus]},
                          outputs={'Out': [s1]}, attrs={'scale': 2.0})
        with pytest.raises(ValueError, match='input slots'):
            fluid.PipelineTranspiler(n_micro=2).transpile(main)


def test_pipeline_rejects_dtype_changing_region():
    """Boundary dtype mismatch surfaces as a transpile-time error, not an
    opaque lax.scan carry mismatch (AMP-boundary case)."""
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[D], dtype='float32')
        h = layers.fc(input=x, size=D)
        blk = main.global_block()
        s0 = blk.create_var(name='s0_outb', shape=[-1, D], dtype='bfloat16')
        s1 = blk.create_var(name='s1_outb', shape=[-1, D], dtype='bfloat16')
        # infer_shape=False keeps the declared bf16 outputs (a dtype
        # mismatch at the region boundary)
        with fluid.device_guard('pipe:0'):
            blk.append_op(type='scale', inputs={'X': [h]},
                          outputs={'Out': [s0]}, attrs={'scale': 2.0},
                          infer_shape=False)
        with fluid.device_guard('pipe:1'):
            blk.append_op(type='scale', inputs={'X': [s0]},
                          outputs={'Out': [s1]}, attrs={'scale': 2.0},
                          infer_shape=False)
        with pytest.raises(ValueError, match='activation dtype'):
            fluid.PipelineTranspiler(n_micro=2).transpile(main)


def test_distribute_after_pipeline_keeps_pp_in_mesh_axes():
    """DistributeTranspiler run AFTER PipelineTranspiler must recompute
    mesh_axes from the merged sizes, not claim a dp-only mesh."""
    with fresh_program() as (main, startup):
        _build()
        fluid.PipelineTranspiler(n_micro=NMICRO).transpile(main)
        fluid.DistributeTranspiler().transpile(
            trainer_id=0, trainers=2, program=main)
        assert main._dist_config['mesh_axes'] == ('dp', 'pp')
