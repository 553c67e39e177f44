"""The depthwise causal convolution as a Pallas kernel (ISSUE 36): the
kernel bodies in the interpreter against the composition they replace on
the TPU (`linear_attention_ops._conv` and `jax.vjp` of it), the kernel's
`usable`, and the rule's choice between the two. Tiles of 32 x 128 keep
the interpreter cheap and put several of them in a row. On the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu import fluid
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.ops.kernels import causal_conv1d as cc

from util import grads_of as _grads_of, input_parameter as _input

BF16_ULP = 2.0 ** -8
TILE = (32, 128)


def _f32(a):
    return np.asarray(a, np.float32)


def _operands(seed, b, t, c, taps, dtype):
    rng = np.random.default_rng(seed)
    x, g = (jnp.asarray(rng.normal(size=(b, t, c)), dtype)
            for _ in range(2))
    return x, jnp.asarray(rng.normal(size=(taps, c)), jnp.float32), g


# (B, T): one tile; three tiles (rows whose window crosses a tile's edge);
# two rows of the batch of two tiles each (the first K - 1 tokens of the
# second row see zeros, not the first row's last)
ROWS = {'one_tile': (1, 32), 'three_tiles': (1, 96), 'two_rows': (2, 64)}


@pytest.mark.parametrize('rows', list(ROWS))
@pytest.mark.parametrize('taps', [2, 3, 4])
@pytest.mark.parametrize('act', ['', 'silu'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_kernel_is_the_composition(dtype, act, taps, rows):
    """y, dx and dw against `_conv` and its `jax.vjp`: float32 to 1e-5 of
    the largest value (the compilers contract multiply-adds differently),
    bf16 to one step of bf16 at each element (a tie rounded the other
    way); dw, a float32 sum over B x T both ways, to 1e-5 of its norm."""
    dtype = jnp.dtype(dtype)
    b, t = ROWS[rows]
    x, w, g = _operands(len(rows) + taps, b, t, 256, taps, dtype)
    want, pull = jax.vjp(lambda x, w: la._conv(x, w, act), x, w)
    dx_want, dw_want = pull(g)
    got = cc.causal_conv1d_fwd(x, w, act=act, interpret=True, tile=TILE)
    dx, dw = cc.causal_conv1d_bwd(x, w, g, act=act, interpret=True,
                                  tile=TILE)
    for name, a, ref in (('y', got, want), ('dx', dx, dx_want)):
        assert a.dtype == ref.dtype and a.shape == ref.shape, name
        a, ref = _f32(a), _f32(ref)
        tol = (1e-5 * np.abs(ref).max() if dtype == jnp.float32
               else 2 * BF16_ULP * np.abs(ref) + 1e-6)
        assert np.all(np.abs(a - ref) <= tol), (
            name, float(np.abs(a - ref).max()))
    assert dw.dtype == dw_want.dtype and dw.shape == dw_want.shape
    assert np.linalg.norm(_f32(dw) - _f32(dw_want)) \
        <= 1e-5 * np.linalg.norm(_f32(dw_want))


@pytest.mark.parametrize('rows', list(ROWS))
@pytest.mark.parametrize('act', ['', 'silu'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_kernel_with_a_bias_is_the_composition_plus_the_bias(dtype, act,
                                                             rows):
    """The optional bias (ISSUE 40: Mamba-2's convolution has one): y, dx,
    dw and db against `_conv(..., b)` and its `jax.vjp`, at the tolerances
    of the test above; db, a float32 sum over B x T, to 1e-5 of its norm.
    Without a bias the backward still gives two values."""
    dtype = jnp.dtype(dtype)
    b, t = ROWS[rows]
    x, w, g = _operands(len(rows) + 7, b, t, 256, 4, dtype)
    bias = jnp.asarray(np.random.default_rng(1).normal(size=256),
                       jnp.float32)
    want, pull = jax.vjp(lambda x, w, b: la._conv(x, w, act, b), x, w, bias)
    dx_want, dw_want, db_want = pull(g)
    got = cc.causal_conv1d_fwd(x, w, bias, act=act, interpret=True,
                               tile=TILE)
    dx, dw, db = cc.causal_conv1d_bwd(x, w, g, bias, act=act,
                                      interpret=True, tile=TILE)
    for name, a, ref in (('y', got, want), ('dx', dx, dx_want)):
        assert a.dtype == ref.dtype and a.shape == ref.shape, name
        a, ref = _f32(a), _f32(ref)
        tol = (1e-5 * np.abs(ref).max() if dtype == jnp.float32
               else 2 * BF16_ULP * np.abs(ref) + 1e-6)
        assert np.all(np.abs(a - ref) <= tol), (
            name, float(np.abs(a - ref).max()))
    for a, ref in ((dw, dw_want), (db, db_want)):
        assert a.dtype == ref.dtype and a.shape == ref.shape
        assert np.linalg.norm(_f32(a) - _f32(ref)) \
            <= 1e-5 * np.linalg.norm(_f32(ref))
    # the bias moved the result, and no bias is the kernel it was
    bare = cc.causal_conv1d_fwd(x, w, act=act, interpret=True, tile=TILE)
    assert np.abs(_f32(bare) - _f32(got)).max() > 0.1
    assert len(cc.causal_conv1d_bwd(x, w, g, act=act, interpret=True,
                                    tile=TILE)) == 2


def test_rows_of_a_batch_do_not_see_each_other():
    """Row 1 of a batch of two is what it is alone, and its first K - 1
    tokens are the last taps' terms only; the gradient of row 0's last
    tokens has nothing from row 1."""
    x, w, g = _operands(3, 2, 64, 128, 4, jnp.float32)
    both = cc.causal_conv1d_fwd(x, w, act='', interpret=True, tile=TILE)
    alone = cc.causal_conv1d_fwd(x[1:], w, act='', interpret=True,
                                 tile=TILE)
    np.testing.assert_array_equal(_f32(both[1:]), _f32(alone))
    np.testing.assert_allclose(_f32(both[1, 0]), _f32(w[3] * x[1, 0]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        _f32(both[1, 1]), _f32(w[2] * x[1, 0] + w[3] * x[1, 1]),
        rtol=1e-5, atol=1e-6)
    dx, _ = cc.causal_conv1d_bwd(x, w, g, act='', interpret=True,
                                 tile=TILE)
    dx0, _ = cc.causal_conv1d_bwd(x[:1], w, g[:1], act='', interpret=True,
                                  tile=TILE)
    np.testing.assert_array_equal(_f32(dx[:1]), _f32(dx0))
    np.testing.assert_allclose(_f32(dx[0, -1]), _f32(w[3] * g[0, -1]),
                               rtol=1e-6)


@pytest.mark.parametrize('tile', [(16, 128), (32, 256), (64, 128)])
def test_every_tile_gives_the_same_rows(tile):
    """A tile only groups rows and channels into grid steps (and its
    pieces into vregs): bf16 results equal to the bit, dw to float32's
    rounding of another order of summation."""
    x, w, g = _operands(5, 1, 64, 256, 4, jnp.bfloat16)
    want = cc.causal_conv1d_fwd(x, w, act='silu', interpret=True,
                                tile=(64, 256))
    dx_want, dw_want = cc.causal_conv1d_bwd(x, w, g, act='silu',
                                            interpret=True, tile=(64, 256))
    got = cc.causal_conv1d_fwd(x, w, act='silu', interpret=True, tile=tile)
    dx, dw = cc.causal_conv1d_bwd(x, w, g, act='silu', interpret=True,
                                  tile=tile)
    np.testing.assert_array_equal(_f32(got), _f32(want))
    np.testing.assert_array_equal(_f32(dx), _f32(dx_want))
    np.testing.assert_allclose(_f32(dw), _f32(dw_want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_the_pieces_of_a_tile_hand_their_edges_on(dtype):
    """A tile of 128 x 512 is walked in four pieces of 32 rows by a
    `fori_loop` (forward from its start, backward from its end): a piece's
    first K - 1 windows are the piece before's last rows and its dx reads
    the dpre of the piece after, across tiles and not across rows of the
    batch."""
    dtype = jnp.dtype(dtype)
    assert cc._rows_of(128, 512, cc._edge(dtype)) == 32
    x, w, g = _operands(9, 2, 256, 512, 4, dtype)
    want, pull = jax.vjp(lambda x, w: la._conv(x, w, 'silu'), x, w)
    dx_want, dw_want = pull(g)
    got = cc.causal_conv1d_fwd(x, w, act='silu', interpret=True,
                               tile=(128, 512))
    dx, dw = cc.causal_conv1d_bwd(x, w, g, act='silu', interpret=True,
                                  tile=(128, 512))
    tol = 1e-5 if dtype == jnp.float32 else 2 * BF16_ULP
    for a, ref in ((got, want), (dx, dx_want)):
        a, ref = _f32(a), _f32(ref)
        assert np.all(np.abs(a - ref) <= tol * np.maximum(
            np.abs(ref), 1.0 if dtype == jnp.float32 else 1e-4))
    np.testing.assert_allclose(_f32(dw), _f32(dw_want), rtol=1e-4,
                               atol=1e-4)


def test_usable_at_its_boundaries():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert cc.usable(8192, 8192, 4, bf16) and cc.usable(8192, 8192, 4, f32)
    assert cc.usable(4096, 128, 2, np.dtype('float32'))
    assert not cc.usable(8192, 8192 + 64, 4, bf16)    # half a lane tile
    assert not cc.usable(8192, 96, 4, bf16)
    # whole tiles of tokens; a row shorter than a tile is one tile of
    # whole sublane tiles (16 rows at two bytes, 8 at four)
    tt = cc.tile_of(8192, 8192, bf16)[0]
    assert cc.usable(2 * tt, 128, 4, bf16)
    assert not cc.usable(tt + tt // 2, 128, 4, bf16)
    assert cc.usable(48, 128, 4, bf16) and cc.usable(40, 128, 4, f32)
    assert not cc.usable(40, 128, 4, bf16) and not cc.usable(11, 128, 4, f32)
    # a filter looks back at most one float32 sublane tile, and at least
    # one token
    assert cc.usable(8192, 128, 9, bf16) and not cc.usable(8192, 128, 10,
                                                          bf16)
    assert not cc.usable(8192, 128, 1, bf16)
    assert not cc.usable(8192, 128, 4, jnp.float16)
    # 4-byte operands take half the rows, the widest lanes that divide C
    assert cc.tile_of(8192, 8192, f32)[0] * 2 == tt
    assert cc.tile_of(8192, 384, bf16)[1] == 128
    assert cc.tile_of(8192, 768, bf16)[1] == 256
    assert cc.tile_of(32, 128, bf16) == (32, 128)


@pytest.fixture
def interpreted(monkeypatch):
    """The rule hands the kernels `interpret=False` (Mosaic); here their
    bodies run in the Pallas interpreter."""
    for name in ('causal_conv1d_fwd', 'causal_conv1d_bwd'):
        real = getattr(cc, name)
        monkeypatch.setattr(
            cc, name, lambda *a, _real=real, **kw: _real(
                *a, **dict(kw, interpret=True)))


def _ways():
    return {w: obs.counter('conv1d.way', way=w).value
            for w in ('kernel', 'composed')}


def _lowered(taps=4, act='silu', **labels):
    return obs.counter('conv1d.lowered', taps=taps, act=act, **labels).value


@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_rule_chooses_on_platform_and_shape(platform, monkeypatch,
                                                interpreted):
    """Through the Executor: on the CPU the composition, with the platform
    reported as `tpu` the kernels (here in the interpreter), counted once
    per op per trace beside `conv1d.lowered`; a shape outside `usable`
    keeps the composition on either; the values and both gradients are the
    formula's both ways."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    rng = np.random.default_rng(11)

    def run(t, c):
        x = rng.normal(size=(2, t, c)).astype('float32')
        f = rng.normal(size=(4, c)).astype('float32')
        w = rng.normal(size=(2, t, c)).astype('float32')

        def build():
            return layers.causal_conv1d(
                _input('x', x), 4, act='silu', param_attr=fluid.ParamAttr(
                    name='f', initializer=fluid.initializer
                    .NumpyArrayInitializer(f)))

        before = _ways(), _lowered()
        got, (gx, gf), text = _grads_of(build, {'w': w}, ['x', 'f'],
                                        optimized=True)
        after = _ways(), _lowered()
        want, pull = jax.vjp(lambda x, f: la._conv(x, f, 'silu'),
                             jnp.asarray(x), jnp.asarray(f))
        wx, wf = pull(jnp.asarray(w))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(gx, wx, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gf, wf, rtol=1e-4, atol=1e-5)
        assert 'causal_conv1d_' in text
        moved = {w: after[0][w] - before[0][w] for w in before[0]}
        assert sum(moved.values()) == after[1] - before[1] >= 1
        return moved

    moved = run(24, 128)
    took, other = (('kernel', 'composed') if platform == 'tpu'
                   else ('composed', 'kernel'))
    assert moved[took] >= 1 and moved[other] == 0
    # 11 tokens are no whole sublane tile and 6 channels no lane tile:
    # the composition, whatever the platform
    for shape in ((11, 128), (24, 6)):
        moved = run(*shape)
        assert moved['composed'] >= 1 and moved['kernel'] == 0


@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_layers_bias_reaches_either_way(platform, monkeypatch,
                                            interpreted):
    """`layers.causal_conv1d(bias_attr=...)` through the Executor: the
    composition on the CPU, the kernels with the platform reported as
    `tpu`; value and the three gradients are the formula's with the bias,
    and the lowering counts `conv1d.lowered{bias=true}`. The default is no
    bias and no `Bias` input."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    rng = np.random.default_rng(13)
    x = rng.normal(size=(2, 24, 128)).astype('float32')
    f = rng.normal(size=(4, 128)).astype('float32')
    b = rng.normal(size=128).astype('float32')
    w = rng.normal(size=(2, 24, 128)).astype('float32')

    def attr(name, value):
        return fluid.ParamAttr(
            name=name,
            initializer=fluid.initializer.NumpyArrayInitializer(value))

    def build():
        return layers.causal_conv1d(_input('x', x), 4, act='silu',
                                    param_attr=attr('f', f),
                                    bias_attr=attr('b', b))

    before = _lowered(bias='true'), _ways()
    got, (gx, gf, gb), _ = _grads_of(build, {'w': w}, ['x', 'f', 'b'],
                                     optimized=True)
    assert _lowered(bias='true') > before[0]
    assert _ways()['kernel' if platform == 'tpu' else 'composed'] \
        > before[1]['kernel' if platform == 'tpu' else 'composed']
    want, pull = jax.vjp(lambda x, f, b: la._conv(x, f, 'silu', b),
                         *map(jnp.asarray, (x, f, b)))
    wx, wf, wb = pull(jnp.asarray(w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, ref in ((gx, wx), (gf, wf), (gb, wb)):
        np.testing.assert_allclose(a, ref, rtol=1e-4, atol=1e-5)
    assert np.abs(wb).max() > 0.1
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        layers.causal_conv1d(layers.data(name='x', shape=[24, 128],
                                         dtype='float32'), 4)
        op, = [o for o in fluid.default_main_program().global_block().ops
               if o.type == 'causal_conv1d']
        assert not op.input('Bias')


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_two_gates_make_the_short_convolution_either_way(platform, amp,
                                                         monkeypatch,
                                                         interpreted):
    """`layers.causal_conv1d(in_gate=, out_gate=)` through the Executor
    (ISSUE 44: LFM2's mixer): out_gate * conv3(in_gate * x) with NO
    activation against three shifted multiply-adds, the value and the
    gradient of x, both gates and the filter; the composition on the CPU,
    the kernels (K = 3, in the interpreter) with the platform reported as
    `tpu`, two rows; under AMP the three arrays are read in bf16. Counted
    `conv1d.lowered{taps=3, act=none, gates=2}` and, by token,
    `shortconv.tokens`. Without the gates the op is what it was."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    rng = np.random.default_rng(44)
    x, b, c, w = (rng.normal(size=(2, 48, 128)).astype('float32')
                  for _ in range(4))
    f = rng.normal(size=(3, 128)).astype('float32')

    def attr(name, value):
        return fluid.ParamAttr(name=name, initializer=fluid.initializer
                               .NumpyArrayInitializer(value))

    def build():
        return layers.causal_conv1d(
            _input('x', x), 3, param_attr=attr('f', f),
            in_gate=_input('b', b), out_gate=_input('c', c))

    def formula(x, b, c, f):
        if amp:
            x, b, c = (t.astype(jnp.bfloat16).astype(jnp.float32)
                       for t in (x, b, c))
        p = jnp.pad(b * x, ((0, 0), (2, 0), (0, 0)))
        return c * (f[0] * p[:, 0:48] + f[1] * p[:, 1:49] + f[2] * p[:, 2:50])

    before = (_lowered(3, 'none', gates='2'), _ways(),
              obs.counter('shortconv.tokens').value)
    got, grads, _ = _grads_of(build, {'w': w}, ['x', 'b', 'c', 'f'], amp=amp)
    assert _lowered(3, 'none', gates='2') > before[0]
    assert _ways()['kernel' if platform == 'tpu' else 'composed'] \
        > before[1]['kernel' if platform == 'tpu' else 'composed']
    assert (obs.counter('shortconv.tokens').value - before[2]) % 96 == 0
    assert obs.counter('shortconv.tokens').value > before[2]
    want = formula(x, b, c, f)
    wants = jax.grad(lambda *a: jnp.sum(formula(*a) * w),
                     argnums=range(4))(x, b, c, f)
    tol = 2.0 ** -6 if amp else 1e-5
    assert np.abs(np.asarray(got, np.float32) - want).max() \
        <= tol * np.abs(want).max()
    for name, a, ref in zip('x b c f'.split(), grads, wants):
        assert np.linalg.norm(np.asarray(a, np.float32) - ref) \
            <= 2 * tol * np.linalg.norm(ref), name
    # a gate of another shape is refused where the layer is built
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match='a gate of shape'):
            layers.causal_conv1d(_input('x', x), 3,
                                 in_gate=_input('g', x[:, :, :64]))
