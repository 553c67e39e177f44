"""The gated RMS norm as a Pallas kernel (ISSUE 48): the kernel bodies in
the interpreter against the composition they replace on the TPU
(`linear_attention_ops._gated_norm` and `jax.vjp` of it), the kernel's
`usable`, the rule's choice between the two and what the kernel path keeps
for its backward. Blocks of 32 rows keep the interpreter cheap and put
several of them, the last one partial, in an array. On the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals

from paddle_tpu import obs
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.ops.kernels import gated_norm as gn

from util import grads_of as _grads_of, input_parameter as _input

BF16_ULP = 2.0 ** -8
EPS = 1e-5
TILE = 32
FORMS = {'norm_first': True, 'gate_first': False}
DTYPES = {'f32_f32': ('float32', 'float32'), 'f32_bf16': ('float32',
                                                           'bfloat16'),
          'bf16_bf16': ('bfloat16', 'bfloat16')}


def _f32(a):
    return np.asarray(a, np.float32)


def _operands(seed, shape, x_dtype, gate_dtype):
    rng = np.random.default_rng(seed)
    x, g = (jnp.asarray(rng.normal(size=shape), x_dtype) for _ in range(2))
    z = jnp.asarray(rng.normal(size=shape), gate_dtype)
    return x, z, jnp.asarray(rng.normal(size=shape[-1]), jnp.float32), g


def _composed(x, z, w, g, first, groups, act='silu'):
    """(y, (dx, dgate, dw)) as the rule's composed path gives them: the
    float32 result rounded to x's dtype."""
    y, pull = jax.vjp(lambda *a: la._gated_norm(*a, (EPS, first, groups,
                                                     act))
                      .astype(x.dtype), x, z, w)
    return y, pull(g)


def _kernel(x, z, w, g, first, groups, tile=TILE, act='silu'):
    kw = dict(eps=EPS, norm_first=first, groups=groups, interpret=True,
              tile=tile, gate_act=act)
    return (gn.gated_norm_fwd(x, z, w, **kw),
            gn.gated_norm_bwd(x, z, w, g, **kw))


def _assert_is(got, want, x_dtype):
    """y, dx and dgate: float32 to 1e-5 of the largest value (another
    order of a float32 sum over the group), an array rounded to bf16 to
    two steps of bf16 at each element; dw, a float32 sum over the rows
    both ways, to 1e-5 of its norm."""
    (y, (dx, dz, dw)), (y_want, (dx_want, dz_want, dw_want)) = got, want
    for name, a, ref in (('y', y, y_want), ('dx', dx, dx_want),
                         ('dgate', dz, dz_want)):
        assert a.dtype == ref.dtype and a.shape == ref.shape, name
        exact = a.dtype == jnp.float32
        a, ref = _f32(a), _f32(ref)
        tol = (1e-5 * np.abs(ref).max() if exact
               else 2 * BF16_ULP * np.abs(ref) + 1e-5 * np.abs(ref).max())
        assert np.all(np.abs(a - ref) <= tol), (
            name, float(np.abs(a - ref).max()))
    assert dw.dtype == dw_want.dtype and dw.shape == dw_want.shape
    assert np.linalg.norm(_f32(dw) - _f32(dw_want)) \
        <= (1e-5 if x_dtype == 'float32' else 2 * BF16_ULP) \
        * np.linalg.norm(_f32(dw_want))


@pytest.mark.parametrize('dtypes', list(DTYPES))
@pytest.mark.parametrize('width', [128, 512])
@pytest.mark.parametrize('groups', [1, 4, 8])
@pytest.mark.parametrize('form', list(FORMS))
def test_kernel_is_the_composition(form, groups, width, dtypes):
    """y and the three gradients of a 3-D [B, T, G x width] input against
    `_gated_norm` and its `jax.vjp`: 80 rows in blocks of 32, so the last
    block holds 16 rows of the array and 16 of nothing, which must add
    nothing to dw."""
    x_dtype, gate_dtype = DTYPES[dtypes]
    x, z, w, g = _operands(groups + width, (2, 40, groups * width),
                           x_dtype, gate_dtype)
    _assert_is(_kernel(x, z, w, g, FORMS[form], groups),
               _composed(x, z, w, g, FORMS[form], groups), x_dtype)


@pytest.mark.parametrize('dtypes', list(DTYPES))
@pytest.mark.parametrize('form', list(FORMS))
def test_a_head_is_the_last_axis_of_a_4d_input(form, dtypes):
    """[B, T, H, 128] with one group: the mean is a head's, w a head's 128
    weights shared by every head. A float32 x is read BY HEAD (a block is
    32 tokens, x's [32 x 8, 128] beside the others' [32, 8 x 128], a piece
    a head of them; 80 tokens, so the last block is partial), a bf16 x
    flat, with the heads among the rows."""
    x_dtype, gate_dtype = DTYPES[dtypes]
    x, z, w, g = _operands(4, (2, 40, 8, 128), x_dtype, gate_dtype)
    assert gn.by_head(x.shape, 1, x.dtype) == (x_dtype == 'float32')
    _assert_is(_kernel(x, z, w, g, FORMS[form], 1),
               _composed(x, z, w, g, FORMS[form], 1), x_dtype)


@pytest.mark.parametrize('dtypes', list(DTYPES))
@pytest.mark.parametrize('form', list(FORMS))
def test_a_sigmoid_gate_is_the_composition_and_the_formula(form, dtypes):
    """`gate_act='sigmoid'` (Kimi Delta Attention's output gate): the
    kernels against `_gated_norm` with the same activation, by head and
    flat, and the composition against the formula written out; a cfg that
    names no activation is the SiLU it always was."""
    x_dtype, gate_dtype = DTYPES[dtypes]
    x, z, w, g = _operands(9, (2, 40, 8, 128), x_dtype, gate_dtype)
    first = FORMS[form]
    want = _composed(x, z, w, g, first, 1, 'sigmoid')
    _assert_is(_kernel(x, z, w, g, first, 1, act='sigmoid'), want, x_dtype)
    xf, gate = _f32(x), 1.0 / (1.0 + np.exp(-_f32(z)))
    u = xf if first else xf * gate
    y = _f32(w) * u / np.sqrt(np.mean(u * u, -1, keepdims=True) + EPS)
    np.testing.assert_allclose(
        _f32(want[0]), y * gate if first else y,
        rtol=1e-5 if x_dtype == 'float32' else 2e-2, atol=1e-5)
    np.testing.assert_array_equal(
        la._gated_norm(x, z, w, (EPS, first, 1)),
        la._gated_norm(x, z, w, (EPS, first, 1, 'silu')))
    with pytest.raises(ValueError, match='gate_act'):
        gn.gated_norm_fwd(x, z, w, eps=EPS, norm_first=first, groups=1,
                          interpret=True, gate_act='tanh')


@pytest.mark.parametrize('tile', [16, 32, 4096])
@pytest.mark.parametrize('form', list(FORMS))
def test_by_head_gives_the_rows_the_flat_view_gives(form, tile):
    """The same heads as [B, T, H, 128] (by head) and as [B, T x H, 128]
    (flat, a head a row): y, dx and dgate equal to the bit at every block,
    dw to float32's rounding of another order of summation."""
    x, z, w, g = _operands(9, (1, 48, 16, 128), 'float32', 'bfloat16')
    assert gn.by_head(x.shape, 1, x.dtype)
    flat = tuple(a.reshape(1, -1, 128) for a in (x, z, g))
    assert not gn.by_head(flat[0].shape, 1, x.dtype)
    y_want, (dx_want, dz_want, dw_want) = _kernel(
        flat[0], flat[1], w, flat[2], FORMS[form], 1)
    y, (dx, dz, dw) = _kernel(x, z, w, g, FORMS[form], 1, tile=tile)
    for a, ref in ((y, y_want), (dx, dx_want), (dz, dz_want)):
        np.testing.assert_array_equal(_f32(a).reshape(ref.shape), _f32(ref))
    np.testing.assert_allclose(_f32(dw), _f32(dw_want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('form', list(FORMS))
def test_two_groups_of_a_4d_input(form):
    """Groups of the last axis of a 4-D input: [B, T, H, 2 x 128]."""
    x, z, w, g = _operands(5, (1, 4, 16, 256), 'float32', 'bfloat16')
    _assert_is(_kernel(x, z, w, g, FORMS[form], 2),
               _composed(x, z, w, g, FORMS[form], 2), 'float32')


@pytest.mark.parametrize('tile', [16, 48, 64, 96, 4096])
@pytest.mark.parametrize('form', list(FORMS))
def test_every_tile_gives_the_same_rows(form, tile):
    """The rows of a block only group rows into grid steps (and its pieces
    into vregs): y, dx and dgate equal to the bit whether the blocks
    divide the 96 rows or not (64: a last block of 32; 4096: one block of
    all the rows), dw to float32's rounding of another order of
    summation."""
    x, z, w, g = _operands(6, (1, 96, 512), 'float32', 'bfloat16')
    y_want, (dx_want, dz_want, dw_want) = _kernel(
        x, z, w, g, FORMS[form], 4, tile=32)
    y, (dx, dz, dw) = _kernel(x, z, w, g, FORMS[form], 4, tile=tile)
    for a, ref in ((y, y_want), (dx, dx_want), (dz, dz_want)):
        np.testing.assert_array_equal(_f32(a), _f32(ref))
    np.testing.assert_allclose(_f32(dw), _f32(dw_want), rtol=1e-5,
                               atol=1e-5)


def test_rows_past_the_end_of_a_last_block_add_nothing():
    """80 rows in blocks of 64: whatever the 48 rows past the end hold,
    dw is the sum over the array's rows (the blocks of 16 divide them)."""
    x, z, w, g = _operands(7, (1, 80, 256), 'float32', 'float32')
    for first in (True, False):
        whole = _kernel(x, z, w, g, first, 2, tile=16)[1][2]
        partial = _kernel(x, z, w, g, first, 2, tile=64)[1][2]
        assert np.all(np.isfinite(_f32(partial)))
        np.testing.assert_allclose(_f32(partial), _f32(whole), rtol=1e-5,
                                   atol=1e-5)


def test_usable_at_its_boundaries():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # the two cells' ops, in the step's dtypes and the float32 check's
    for gate in (bf16, f32):
        assert gn.usable((1, 8192, 4096), 8, f32, gate)
        assert gn.usable((1, 8192, 32, 128), 1, f32, gate)
    assert gn.usable((8192, 4096), 8, np.dtype('float32'), bf16)
    assert gn.usable((1, 64, 128), 1, bf16, bf16)
    # a group is whole lane tiles
    assert not gn.usable((1, 8192, 512), 8, f32, bf16)       # 64 a group
    assert not gn.usable((1, 8192, 768), 4, f32, bf16)       # 192
    assert not gn.usable((1, 8192, 64), 1, f32, f32)
    assert gn.usable((1, 8192, 768), 3, f32, bf16)           # 256
    assert not gn.usable((1, 8192, 512), 3, f32, bf16)       # no equal parts
    # whole sublane tiles of rows: 8 at four bytes, 16 where an operand
    # has two
    assert gn.usable((1, 40, 128), 1, f32, f32)
    assert not gn.usable((1, 40, 128), 1, f32, bf16)
    assert not gn.usable((1, 11, 128), 1, f32, f32)
    # rows that merge without a copy: one row before the last two axes, or
    # a second-last axis of whole sublane tiles
    assert gn.usable((4, 48, 128), 1, f32, bf16)
    assert gn.usable((1, 1, 48, 256), 1, f32, bf16)
    assert not gn.usable((4, 12, 128), 1, f32, bf16)
    assert not gn.usable((2, 8192, 4, 256), 1, f32, f32)
    assert gn.usable((2, 8192, 8, 256), 1, f32, f32)
    assert not gn.usable((2, 8192, 8, 256), 1, f32, bf16)
    # by head (float32 x, one group, heads of one lane tile: what Mosaic's
    # strided load takes): whole sublane tiles of tokens, and a token's
    # heads whole sublane tiles of x, whatever the gate's dtype
    assert gn.by_head((1, 8192, 32, 128), 1, f32)
    assert not gn.by_head((1, 8192, 32, 128), 1, bf16)
    assert not gn.by_head((1, 8192, 32, 256), 1, f32)
    assert not gn.by_head((1, 8192, 32, 256), 2, f32)
    assert not gn.by_head((8192, 32, 128), 1, f32)
    assert not gn.usable((2, 8192, 4, 128), 1, f32, f32)
    assert gn.usable((2, 8192, 8, 128), 1, f32, f32)
    assert gn.usable((2, 8192, 8, 128), 1, f32, bf16)
    assert not gn.usable((1, 8, 8, 128), 1, f32, bf16)
    assert gn.usable((1, 8, 8, 128), 1, f32, f32)
    assert not gn.usable((128,), 1, f32, f32)
    assert not gn.usable((1, 64, 128), 1, jnp.float16, f32)
    assert not gn.usable((1, 64, 128), 1, f32, jnp.float16)
    # a block: BLOCK elements in whole sublane tiles, or all of a shorter
    # array; a piece divides it
    assert gn.rows_of(8192, 512) * 512 == gn.BLOCK
    assert gn.rows_of(262144, 128) * 128 == gn.BLOCK
    assert gn.rows_of(8192, 4096) * 4096 == gn.BLOCK
    assert gn.rows_of(80, 128) == 80
    assert gn.rows_of(8192, 1 << 20) == 16
    assert gn._piece_rows(512, 512, 16) == 16
    assert gn._piece_rows(2048, 128, 16) == 64
    assert gn._piece_rows(80, 128, 16) == 16
    assert gn._piece_rows(40, 128, 8) == 40


@pytest.fixture
def interpreted(monkeypatch):
    """The rule hands the kernels `interpret=False` (Mosaic); here their
    bodies run in the Pallas interpreter."""
    for name in ('gated_norm_fwd', 'gated_norm_bwd'):
        real = getattr(gn, name)
        monkeypatch.setattr(
            gn, name, lambda *a, _real=real, **kw: _real(
                *a, **dict(kw, interpret=True)))


def _ways():
    return {w: obs.counter('gated_rms_norm.way', way=w).value
            for w in ('kernel', 'composed')}


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_rule_chooses_on_platform_and_shape(platform, amp, monkeypatch,
                                                interpreted):
    """Through the Executor: on the CPU the composition, with the platform
    reported as `tpu` the kernels (here in the interpreter), counted once
    per op per trace beside `gated_rms_norm.lowered`; a shape outside
    `usable` keeps the composition on either; the value and the three
    gradients are the formula's both ways, under AMP with the gate read in
    bf16."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    rng = np.random.default_rng(11)

    def run(shape, first, groups):
        x, z, t = (rng.normal(size=shape).astype('float32')
                   for _ in range(3))
        w = rng.normal(size=shape[-1]).astype('float32')

        def build():
            from paddle_tpu import fluid
            return layers.gated_rms_norm(
                _input('x', x), _input('z', z), epsilon=EPS,
                norm_before_gate=first, groups=groups,
                param_attr=fluid.ParamAttr(
                    name='s', initializer=fluid.initializer
                    .NumpyArrayInitializer(w)))

        def formula(x, z, w):
            if amp:
                z = z.astype(jnp.bfloat16)
            return la._gated_norm(x, z, w, (EPS, first, groups))

        before = _ways(), obs.counter('gated_rms_norm.lowered').value
        got, grads, text = _grads_of(build, {'w': t}, ['x', 'z', 's'],
                                     amp=amp, optimized=True)
        after = _ways(), obs.counter('gated_rms_norm.lowered').value
        want, pull = jax.vjp(formula, *map(jnp.asarray, (x, z, w)))
        tol = 2.0 ** -6 if amp else 1e-5
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        for name, a, ref in zip('x z s'.split(), grads,
                                pull(jnp.asarray(t))):
            assert np.linalg.norm(_f32(a) - _f32(ref)) \
                <= 2 * tol * np.linalg.norm(_f32(ref)), name
        assert 'gated_rms_norm_' in text
        moved = {w: after[0][w] - before[0][w] for w in before[0]}
        assert sum(moved.values()) == after[1] - before[1] >= 1
        return moved

    took, other = (('kernel', 'composed') if platform == 'tpu'
                   else ('composed', 'kernel'))
    for shape, first, groups in (((1, 48, 1024), False, 8),
                                 ((1, 16, 8, 128), True, 1)):
        moved = run(shape, first, groups)
        assert moved[took] >= 1 and moved[other] == 0
    # a group of 64 is no lane tile and 11 rows no sublane tile: the
    # composition, whatever the platform
    for shape, groups in (((1, 48, 512), 8), ((1, 11, 128), 1)):
        moved = run(shape, False, groups)
        assert moved['composed'] >= 1 and moved['kernel'] == 0


def _saved(first, groups, kernel, shape, gate_dtype):
    x, z, w, g = _operands(8, shape, 'float32', gate_dtype)

    def op(x, z, w):
        return la.gated_rms_norm(x, z, w, (EPS, first, groups), kernel)

    jaxpr = str(jax.make_jaxpr(lambda *a: jax.vjp(op, *a)[1](g))(x, z, w))
    return ([(tuple(aval.shape), str(aval.dtype))
             for aval, _ in saved_residuals(op, x, z, w)], jaxpr)


@pytest.mark.parametrize('form', list(FORMS))
def test_the_kernel_path_keeps_the_three_inputs_and_nothing_else(
        form, interpreted):
    """What is alive between the forward and the backward is (x, gate, w)
    on either path. The composition computes its forward again behind an
    `optimization_barrier`; the kernel path has no forward to run again,
    so no barrier either: one Pallas call forward, one backward."""
    shape = (1, 64, 512)
    want = sorted([(shape, 'float32'), (shape, 'bfloat16'),
                   ((512,), 'float32')])
    kept, jaxpr = _saved(FORMS[form], 4, True, shape, 'bfloat16')
    assert sorted(kept) == want
    assert 'optimization_barrier' not in jaxpr
    assert jaxpr.count('pallas_call') == 2
    assert 'gated_norm_fwd' in jaxpr and 'gated_norm_bwd' in jaxpr
    kept, jaxpr = _saved(FORMS[form], 4, False, shape, 'bfloat16')
    assert sorted(kept) == want
    assert 'optimization_barrier' in jaxpr and 'pallas_call' not in jaxpr
