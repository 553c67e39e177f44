"""bf16 mixed precision: numerics stay close to fp32, dtype stays fp32."""
import re

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.ops as tpu_ops

from util import fresh_program


def _build_and_train(amp, steps=10):
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=32, act='relu')
        pred = fluid.layers.fc(input=h, size=1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
        if amp:
            fluid.amp.decorate_program(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(3)
        xs = rng.rand(32, 16).astype('float32')
        ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype('float32')
        losses = []
        for _ in range(steps):
            loss, = exe.run(main, feed={'x': xs, 'y': ys},
                            fetch_list=[cost])
            losses.append(float(loss))
        return losses


def test_amp_matches_fp32_closely():
    fp32 = _build_and_train(amp=False)
    bf16 = _build_and_train(amp=True)
    assert bf16[-1] < bf16[0], "amp training diverged"
    # same trajectory within bf16 tolerance
    np.testing.assert_allclose(fp32, bf16, rtol=0.1, atol=1e-2)


def test_amp_output_dtype_stays_fp32():
    with fresh_program() as (main, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        out = fluid.layers.fc(input=x, size=4)
        fluid.amp.decorate_program(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res, = exe.run(main, feed={'x': np.ones((2, 8), 'float32')},
                       fetch_list=[out])
        assert res.dtype == np.float32


def _attention(x):
    q = fluid.layers.reshape(x, [-1, 2, 128, 64])
    return fluid.layers.fused_attention(q, q, q, causal=True)


def _delta_rule(x):
    q = fluid.layers.reshape(x, [-1, 16, 2, 8])
    beta = fluid.layers.sigmoid(fluid.layers.reduce_mean(q, dim=-1))
    return fluid.layers.gated_delta_rule(
        q, q, q, fluid.layers.scale(beta, scale=-1.0), beta, chunk_size=16,
        qk_l2norm=True)


def _delta_rule_by_channel(x):
    """A decay a channel: g [B, T, H, Dk] within its floor of -5."""
    q = fluid.layers.reshape(x, [-1, 16, 2, 8])
    beta = fluid.layers.sigmoid(fluid.layers.reduce_mean(q, dim=-1))
    return fluid.layers.gated_delta_rule(
        q, q, q, fluid.layers.scale(fluid.layers.sigmoid(q), scale=-5.0),
        beta, chunk_size=16, qk_l2norm=True, gate_floor=-5.0)


def _ssd(x):
    v = fluid.layers.reshape(x, [-1, 16, 2, 8])
    dt = fluid.layers.sigmoid(fluid.layers.reduce_mean(v, dim=-1))
    a = fluid.layers.scale(fluid.layers.exp(fluid.layers.create_parameter(
        [2], 'float32')), scale=-1.0)
    return fluid.layers.ssd_scan(v, dt, a, v, v, chunk_size=8)


# op type -> (feed shape, the layer that appends it to the program, the
# dtype its output has under AMP): the rules whose matmuls take operands
# that lowering.amp_cast cast, each built the way a model does. Six give
# their result back in the dtype they were fed; attention gives it in the
# dtype of its operands, and the output projection that follows takes it
# so. (causal_conv1d, gated_rms_norm and chunk_softmax_pool call amp_cast
# too, for what their backward keeps, and multiply nothing on the MXU.)
_MXU_OPS = {
    'mul': ((4, 8), lambda x: fluid.layers.fc(input=x, size=16), 'float32'),
    'matmul': ((4, 8), lambda x: fluid.layers.matmul(x, x, transpose_y=True),
               'float32'),
    'conv2d': ((2, 3, 8, 8), lambda x: fluid.layers.conv2d(
        x, num_filters=4, filter_size=3), 'float32'),
    'flash_attention': ((1, 2 * 128 * 64), _attention, 'bfloat16'),
    'moe_mlp': ((16, 8), lambda x: fluid.layers.moe_mlp(
        x, num_experts=4, hidden_size=16, act='swish', gated=True, top_k=2,
        capacity_factor=None, bias_attr=False), 'float32'),
    'gated_delta_rule': ((2, 16 * 2 * 8), _delta_rule, 'float32'),
    # the same rule on a decay a channel (a case of the op after the colon)
    'gated_delta_rule:channel': ((2, 16 * 2 * 8), _delta_rule_by_channel,
                                 'float32'),
    'ssd_scan': ((2, 16 * 2 * 8), _ssd, 'float32'),
}


@pytest.mark.parametrize('op_type', sorted(_MXU_OPS))
def test_mxu_ops_take_bf16_operands_only_under_amp(op_type, monkeypatch):
    """The list of MXU ops is the rules that amp_cast their operands: under
    decorate_program the lowered step's dot or convolution takes bf16
    operands and the output has the dtype the table names; without it the
    step holds no bf16 at all."""
    # off the TPU the rule takes the dense XLA chain, which upcasts what it
    # is handed; the kernel's own dots are what a cell runs, so this test
    # gives the rule the interpreted kernel in the chain's place
    monkeypatch.setattr(
        tpu_ops, 'reference_attention',
        lambda *a, **kw: tpu_ops.flash_attention(*a, interpret=True, **kw))
    shape, layer, amp_dtype = _MXU_OPS[op_type]
    xs = np.random.RandomState(5).rand(*shape).astype('float32')
    hlo = {}
    for amp in (False, True):
        with fresh_program() as (main, startup):
            x = fluid.layers.data(name='x', shape=list(shape[1:]),
                                  dtype='float32')
            out = layer(x)
            assert op_type.split(':')[0] in [
                op.type for op in main.global_block().ops]
            assert out.dtype == 'float32'
            if amp:
                fluid.amp.decorate_program(main)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            res, = exe.run(main, feed={'x': xs}, fetch_list=[out])
            assert res.dtype.name == (amp_dtype if amp else 'float32')
            hlo[amp] = exe.lowered_hlo(main, {'x': xs}, [out])
    mxu = re.compile(r'stablehlo\.(dot_general|convolution).*: '
                     r'\(tensor<[^>]*xbf16>, tensor<[^>]*xbf16>\)')
    assert 'bf16' not in hlo[False]
    assert [l for l in hlo[True].splitlines() if mxu.search(l)], hlo[True]
