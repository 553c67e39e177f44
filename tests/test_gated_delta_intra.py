"""Stage `gdn_intra` of the gated delta rule as a Pallas kernel (ISSUE 34):
the kernel bodies in the interpreter against the composition they replace
on the TPU (`linear_attention_ops._intra` and `jax.vjp` of it), the whole
op through the kernel against the token-by-token recurrence, and the
rule's choice between the two. Heads of 128, which the kernel asks for;
short rows and few heads keep the interpreter cheap. On the CPU.
With a decay a channel the kernels enter through the TOKEN layout (ISSUE
58): q, k, v and g [B, T, H, D] as the op holds them, the chunks cut by
the kernels' index maps, against `_intra_channel` behind `_to_chunks`."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.ops.kernels import gated_delta_intra as gdi
from paddle_tpu.ops.kernels import gated_delta_scan as gds

from test_gated_delta_rule import plain_delta_net
from util import grads_of as _grads_of, input_parameter as _input

BF16_ULP = 2.0 ** -8


@pytest.fixture
def interpreted(monkeypatch):
    """The rule hands the kernel `interpret=False` (Mosaic); here its
    bodies run in the Pallas interpreter (and those of stage `gdn_scan`,
    which the rule takes wherever it takes this one: ISSUE 50)."""
    real, scan = gdi.gated_delta_intra, gds.gated_delta_scan
    monkeypatch.setattr(
        gdi, 'gated_delta_intra',
        lambda q, k, v, g_sum, beta, interpret, heads=None, **kw: real(
            q, k, v, g_sum, beta, True, heads, **kw))
    tokens = gdi.gated_delta_intra_tokens
    monkeypatch.setattr(
        gdi, 'gated_delta_intra_tokens',
        lambda q, k, v, g, beta, interpret, heads=None, **kw: tokens(
            q, k, v, g, beta, True, heads, **kw))
    monkeypatch.setattr(
        gds, 'gated_delta_scan',
        lambda xs, dtype, interpret: scan(xs, dtype, True))


def op_inputs(seed, t, hk, hv, gates, dtype=jnp.float32, b=1):
    """As test_gated_delta_rule.delta_inputs draws them, at heads of 128."""
    rng = np.random.default_rng(seed)
    d = 128
    q, k = (jnp.asarray(rng.normal(size=(b, t, hk, d)), dtype)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, t, hv, d)), dtype)
    if gates.startswith('channel'):
        # a decay a CHANNEL within its floor of -5; 'channel_floor' has
        # most of it AT the floor (a saturated gate). On a grid of 2^-10,
        # on which 64 of them sum exactly in float32 in ANY order: the
        # per-channel kernel sums g itself (a product with a triangle of
        # ones) where the composition calls jnp.cumsum, and G near -160
        # rounds to 1.5e-5, so that on free gates a comparison reads the
        # order of the additions ('channel_free': at that rounding) and
        # here, at the tolerances of the per-head cases, the arithmetic
        g = -jnp.asarray(rng.uniform(0, 5, size=(b, t, hv, d)), jnp.float32)
        if gates != 'channel_free':
            g = jnp.round(g * 1024) / 1024
        if gates == 'channel_floor':
            g = jnp.where(jnp.asarray(rng.uniform(size=g.shape)) < 0.7,
                          -5.0, g)
    else:
        lo, hi = (5, 12) if gates == 'strong' else (0, 0.3)
        g = -jnp.asarray(rng.uniform(lo, hi, size=(b, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(b, t, hv)), jnp.float32)
    return q, k, v, g, beta


def _stage(kernel, dtype, l2norm=True):
    """Stage `gdn_intra` from the op's inputs (norm, scale, the key heads'
    repeat, the chunks and their padding included), its six outputs in
    the dtypes the kernel hands the scan. With a decay a channel the
    kernel reads those inputs cut into chunks and nothing else (the norm,
    the scale and G's running sum are its own), the composition stands
    behind `_stage_intra`'s prologue in XLA."""
    cfg = (64, 128 ** -0.5, l2norm, 1e-6, kernel)       # no floor to hold

    def stage(*args):
        w, u, qg, kd, p, decay = la._stage_intra(*args, cfg)
        return (w.astype(dtype), u, qg.astype(dtype), kd.astype(dtype),
                p.astype(dtype), decay)
    return stage


# (T, key heads, value heads): a row whose last chunk is 36 tokens and 28
# of padding, one key head serving two value heads; whole chunks, a key
# head a value head
ROWS = {'padded_shared_keys': (100, 1, 2), 'whole_chunks': (128, 2, 2)}


def _compare_stages(args, dtype, l2norm=True, rounding=0.0):
    """The kernel's six outputs and five gradients against the
    composition's on `args`: an element against its own size at float32's
    1e-5 or 2 ulp of bf16, a gradient against its norm. `rounding`: what
    G's float32 rounding may differ by between two orders of summation,
    taken of the row's largest beside each element (zero where both sides
    hold the same G)."""
    names = ('w', 'u', 'qg', 'kd', 'p', 'decay')
    with jax.default_matmul_precision('highest'):
        want, pull_want = jax.vjp(_stage(False, dtype, l2norm), *args)
        got, pull_got = jax.vjp(_stage(True, dtype, l2norm), *args)
        cts = tuple(jnp.asarray(np.random.default_rng(i).normal(
            size=o.shape), o.dtype) for i, o in enumerate(want))
        g_want, g_got = pull_want(cts), pull_got(cts)
    tol = 1e-5 if dtype == jnp.float32 else 2 * BF16_ULP
    for name, a, b in zip(names, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        # an element against its own size, or where sums cancel against
        # the float32 rounding of the row's largest
        slack = (1e-6 + rounding) * np.abs(b).max(-1, keepdims=True) + 1e-30
        assert np.all(np.abs(a - b) <= (tol + rounding) * np.abs(b)
                      + slack), name
    for name, a, b in zip(('q', 'k', 'v', 'g', 'beta'), g_got, g_want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        # 'strong' gates leave g's gradient to what rounding leaves of
        # decays of e^-5 and less: against the largest gradient there
        scale = np.linalg.norm(b) if name != 'g' else max(
            np.linalg.norm(b), 1e-3 * np.linalg.norm(np.asarray(
                g_want[4], np.float32)))
        assert np.linalg.norm(a - b) <= (tol + rounding) * scale, (
            name, np.linalg.norm(a - b) / scale)


@pytest.mark.parametrize('gates', ['mild', 'strong', 'channel',
                                   'channel_floor'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('rows', list(ROWS))
def test_kernel_is_the_composition(rows, dtype, gates, interpreted):
    """The six outputs and the gradients pulled back to the op's five
    inputs: float32 to 1e-5, bf16 to 2 ulp of bf16. With a decay a channel
    (the per-channel kernel on the op's raw operands against
    `_intra_channel` behind the XLA prologue) a key head a value head;
    the padded row's keys are exactly 0 there and nothing is NaN out of
    `rsqrt(eps)`."""
    dtype = jnp.dtype(dtype)
    t, hk, hv = ROWS[rows]
    if gates.startswith('channel'):
        hk = hv
    _compare_stages(op_inputs(len(rows) + len(gates), t, hk, hv, gates,
                              dtype), dtype)


# (rows, T, heads) of a decay a channel through the token layout (ISSUE
# 58): two rows of two chunks (chunk n of row b is the scan's n x B + b);
# 32 heads, the cell's, four grid steps of eight (eight of four in
# float32); 6 heads, one step of six (two of three in float32: blocks of
# 768 and 384 lanes); 2 heads, fewer than a step takes.
# The draw is named: the float32 comparison holds every element to its OWN
# 1e-5, and one seed in four (at 32 heads 4 and 8 of 1 to 8) has 2 to 6 of
# W's 262144 elements, of 1e-23 in rows of 1e-20, off the composition by up
# to 1.8 times that
LAYOUTS = {'two_rows': (2, 128, 2, 8), 'heads_32': (1, 64, 32, 2),
           'heads_6': (1, 64, 6, 8), 'padded_two_rows': (2, 100, 2, 15)}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('layout', list(LAYOUTS))
def test_channel_kernel_cuts_the_chunks_where_the_op_holds_them(
        layout, dtype, interpreted):
    """W, U, Qg, Kd, P, the chunk's decay and the five cotangents of the
    per-channel kernels on [B, T, H, D] operands against `_intra_channel`
    behind `_to_chunks`, at the tolerances of the chunked cases: the row
    and chunk index maps, the lanes of every head of a grid step, and a T
    that is no whole number of chunks (padded first, then read in
    place)."""
    dtype = jnp.dtype(dtype)
    b, t, h, seed = LAYOUTS[layout]
    _compare_stages(op_inputs(seed, t, h, h, 'channel', dtype, b=b), dtype)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_a_padded_row_is_the_row_padded_by_hand(dtype):
    """T = 100: the entry pads to 128 and the kernels read in place. The
    same numbers, bit for bit, as 128 tokens of which the last 28 are
    zeros (k = 0, beta = 0, g = 0), forward and backward; the padded rows
    are finite (0 * rsqrt(eps), exp(0)) and hand nothing back."""
    dtype = jnp.dtype(dtype)
    args = op_inputs(23, 100, 2, 2, 'channel', dtype)
    padded = tuple(jnp.pad(x, [(0, 0), (0, 28)] + [(0, 0)] * (x.ndim - 2))
                   for x in args)
    norm = (True, 1e-6, 128 ** -0.5)

    def stage(*a):
        return gdi.gated_delta_intra_tokens(*a, True, norm=norm, floor=-5.0)

    with jax.default_matmul_precision('highest'):
        got, pull_got = jax.vjp(stage, *args)
        want, pull_want = jax.vjp(stage, *padded)
        cts = tuple(jnp.asarray(np.random.default_rng(i).normal(
            size=o.shape), o.dtype) for i, o in enumerate(want))
        g_got, g_want = pull_got(cts), pull_want(cts)
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.all(np.isfinite(
            np.asarray(a, np.float32)))
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for a, b, x in zip(g_got, g_want, args):
        assert a.shape == x.shape and a.dtype == x.dtype
        b = np.asarray(b, np.float32)
        np.testing.assert_array_equal(np.asarray(a, np.float32), b[:, :100])
        assert np.all(np.isfinite(b[:, 100:]))
    # the padding tokens write nothing: k = 0 and beta = 0
    w, u, qg, kd = (np.asarray(x, np.float32)[-1, :, :, 36:] for x in got[:4])
    assert not w.any() and not u.any() and not qg.any() and not kd.any()


def test_the_kernels_hold_g_to_its_floor(interpreted):
    """g as a producer that does not keep its bound might leave it: a
    third of it UNDER the floor of -5. The per-channel kernels hold it as
    they load it, so the op is what XLA's select ahead of the composition
    makes it, values and all five gradients; g's is exactly 0 under the
    floor and whole at it."""
    q, k, v, g, beta = op_inputs(29, 100, 2, 2, 'channel')
    under = np.random.default_rng(4).uniform(size=g.shape) < 0.33
    g = jnp.where(under, g - 5.0, g)
    g = g.at[0, :, 0, :7].set(-5.0)             # and some AT it
    weight = jnp.asarray(np.random.default_rng(2).normal(size=v.shape),
                         jnp.float32)

    def loss(kernel):
        return lambda *a: jnp.sum(la.gated_delta_rule(
            *a, chunk_size=64, qk_l2norm=True, kernel=kernel,
            gate_floor=-5.0) * weight)

    with jax.default_matmul_precision('highest'):
        got = jax.value_and_grad(loss(True), argnums=range(5))(
            q, k, v, g, beta)
        want = jax.value_and_grad(loss(False), argnums=range(5))(
            q, k, v, g, beta)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip('q k v g beta'.split(), got[1], want[1]):
        err = float(jnp.linalg.norm(a - b))
        assert err < 3e-4 * float(jnp.linalg.norm(b)) + 1e-7, (name, err)
    dg = np.asarray(got[1][3])
    assert not dg[np.asarray(g) < -5.0].any()
    assert dg[0, :, 0, :7].any()


def _scaled(args, rng, low, high):
    """q and k's rows at norms from `low` to `high` times what they were"""
    q, k = (x * jnp.asarray(np.exp(rng.uniform(
        np.log(low), np.log(high), size=x.shape[:3] + (1,))), x.dtype)
        for x in args[:2])
    return (q, k) + tuple(args[2:])


# what the per-channel kernels do in VMEM to the op's own operands
# (ISSUE 56): rows of q and k of any norm, none taken, gates off the grid
@pytest.mark.parametrize('case', ['norms_0.1_to_30', 'no_l2norm',
                                  'channel_free'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_channel_kernel_takes_the_ops_own_operands(case, dtype, interpreted):
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(len(case))
    gates = 'channel_free' if case == 'channel_free' else 'channel'
    args = op_inputs(len(case), 100, 2, 2, gates, dtype)
    if case == 'norms_0.1_to_30':
        # rows of q and k from 0.1 to 30 times a unit normal's 11.3: the
        # norm divides them out forward, and 1 / norm scales the pull-back
        _compare_stages(_scaled(args, rng, 0.1, 30.0), dtype)
    elif case == 'no_l2norm':
        # q and k as they are: of unit size, so that the solve stays tame
        q, k = ((x.astype(jnp.float32) * 128 ** -0.5).astype(dtype)
                for x in args[:2])
        _compare_stages((q, k) + args[2:], dtype, l2norm=False)
    else:
        # G near -160 a chunk's end is rounded to 1.5e-5 (float32's step
        # between 128 and 256) and twice in a difference: four such steps.
        # Under bf16 a factor that moves by that much takes one operand in
        # a hundred to the next bf16 number: an ulp of the row's largest
        _compare_stages(args, dtype, rounding=6e-5 if dtype == jnp.float32
                        else BF16_ULP)


def test_a_gate_at_the_floor_keeps_its_whole_gradient(interpreted):
    """Every g AT the floor, through the op and the per-channel kernels:
    the select that holds g passes the whole cotangent (a `maximum` would
    halve it) and the kernel's reverse in-chunk sum hands back g's, not
    G's; as tests/test_bailing_hybrid.py holds of the op's composition."""
    q, k, v, g, beta = op_inputs(13, 100, 2, 2, 'channel')
    g = jnp.full_like(g, -5.0)
    weight = jnp.asarray(np.random.default_rng(2).normal(size=v.shape),
                         jnp.float32)
    with jax.default_matmul_precision('highest'):
        got = jax.grad(lambda g: jnp.sum(la.gated_delta_rule(
            q, k, v, g, beta, chunk_size=64, qk_l2norm=True, kernel=True,
            gate_floor=-5.0) * weight))(g)
        want = jax.grad(lambda g: jnp.sum(plain_delta_net(
            q, k, v, g, beta) * weight))(g)
    assert float(jnp.linalg.norm(want)) > 0
    err = float(jnp.linalg.norm(got - want))
    assert err < 3e-4 * float(jnp.linalg.norm(want)), err


@pytest.mark.parametrize('gates', ['mild', 'strong', 'channel',
                                   'channel_floor'])
def test_the_op_through_the_kernel_is_the_recurrence(gates, interpreted):
    """Values and all five gradients against the token-by-token
    definition, float32; the row ends in a padded chunk and each key head
    serves two value heads (one, with a decay a channel)."""
    channel = gates.startswith('channel')
    args = op_inputs(7, 100, 2 if channel else 1, 2, gates)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    with jax.default_matmul_precision('highest'):
        def through_kernel(*a):
            return la.gated_delta_rule(*a, chunk_size=64, qk_l2norm=True,
                                       kernel=True,
                                       gate_floor=-5.0 if channel else None)

        got, want = through_kernel(*args), plain_delta_net(*args)
        g_got = jax.grad(lambda *a: jnp.sum(through_kernel(*a) * weight),
                         argnums=range(5))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(plain_delta_net(*a) * weight),
                          argnums=range(5))(*args)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    for name, a, b in zip('q k v g beta'.split(), g_got, g_want):
        err = float(jnp.linalg.norm(a - b))
        assert err < 3e-4 * float(jnp.linalg.norm(b)) + 1e-7, (name, err)


def test_usable_at_its_boundaries():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert gdi.usable(64, 128, 128, bf16) and gdi.usable(64, 128, 128, f32)
    assert gdi.usable(64, 256, 128, np.dtype('float32'))
    assert not gdi.usable(16, 128, 128, bf16)       # the toy cells' chunk
    assert not gdi.usable(128, 128, 128, bf16)
    assert not gdi.usable(64, 96, 128, bf16)
    assert not gdi.usable(64, 128, 64, f32)
    assert not gdi.usable(64, 128, 128, jnp.float16)
    # a row shorter than a chunk is cut to the power of two that holds it
    assert la._chunk_of(64, 8192) == 64 and la._chunk_of(64, 20) == 32


def _ways():
    return {w: obs.counter('gdn.intra', way=w).value
            for w in ('kernel', 'composed')}


@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_rule_chooses_on_platform_and_shape(platform, monkeypatch,
                                                interpreted):
    """Through the Executor: on the CPU the composition, with the platform
    reported as `tpu` the kernel (here in the interpreter), counted once
    per op per trace; a shape outside `usable` keeps the composition on
    either; both stages' scopes are in the compiled module's metadata and
    the values are the recurrence's both ways."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    args = op_inputs(11, 100, 1, 2, 'mild')
    names = ['q', 'k', 'v', 'g', 'beta']
    w = np.random.default_rng(3).normal(size=args[2].shape).astype('float32')

    def build(chunk):
        return lambda: layers.gated_delta_rule(
            *(_input(n, a) for n, a in zip(names, args)), chunk_size=chunk,
            qk_l2norm=True)

    before = _ways()
    got, grads, text = _grads_of(build(64), {'w': w}, names, optimized=True)
    after = _ways()
    took, other = (('kernel', 'composed') if platform == 'tpu'
                   else ('composed', 'kernel'))
    # one op: the forward and the backward programs are one trace each
    assert 1 <= after[took] - before[took] <= 2
    assert after[other] == before[other]
    want = plain_delta_net(*args)
    g_want = jax.grad(lambda *a: jnp.sum(plain_delta_net(*a) * w),
                      argnums=range(5))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(grads, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
    scoped = [l for l in text.splitlines() if 'gated_delta_rule_' in l]
    assert any('gdn_intra' in l for l in scoped)
    assert any('gdn_scan' in l for l in scoped)
    assert any('transpose' in l and 'gdn_intra' in l for l in scoped)
    # a chunk of 16 is not the kernel's, whatever the platform
    before = _ways()
    _grads_of(build(16), {'w': w}, names)
    after = _ways()
    assert after['kernel'] == before['kernel']
    assert after['composed'] > before['composed']


def _counted_where(counter, platform, gates, where, tokens, monkeypatch):
    """One op through the Executor with the platform reported as
    `platform`: `counter{where=}` rises once an op a trace beside
    `gdn.intra{way=}`, the other label stays, and the values are the
    recurrence's. Returns the compiled module's text."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    channel = gates == 'channel'
    args = op_inputs(17, tokens, 2 if channel else 1, 2, gates)
    names = ['q', 'k', 'v', 'g', 'beta']
    w = np.random.default_rng(3).normal(size=args[2].shape).astype('float32')

    def count():
        return {x: obs.counter(counter, where=x).value
                for x in ('kernel', 'xla')}, sum(_ways().values())

    before, ops_before = count()
    got, _, text = _grads_of(lambda: layers.gated_delta_rule(
        *(_input(n, a) for n, a in zip(names, args)), chunk_size=64,
        qk_l2norm=True, gate_floor=-5.0 if channel else None),
        {'w': w}, names, optimized=True)
    after, ops_after = count()
    other = 'xla' if where == 'kernel' else 'kernel'
    assert after[where] - before[where] == ops_after - ops_before >= 1
    assert after[other] == before[other]
    np.testing.assert_allclose(got, plain_delta_net(*args), rtol=1e-4,
                               atol=1e-5)
    return text


@pytest.mark.parametrize('platform,gates,where', [
    ('tpu', 'channel', 'kernel'), ('tpu', 'mild', 'xla'),
    ('cpu', 'channel', 'xla')])
def test_the_prologue_is_counted_where_it_is_taken(platform, gates, where,
                                                   monkeypatch, interpreted):
    """`gdn.prologue{where=}` once an op a trace beside `gdn.intra{way=}`:
    `kernel` where the per-channel kernels take the raw operands (rank-4 g
    on the TPU), `xla` with a decay a head and on every other platform."""
    _counted_where('gdn.prologue', platform, gates, where, 100, monkeypatch)


@pytest.mark.parametrize('platform,gates,where', [
    ('tpu', 'channel', 'kernel'), ('tpu', 'mild', 'xla'),
    ('cpu', 'channel', 'xla'), ('cpu', 'mild', 'xla')])
def test_the_chunks_are_counted_where_they_are_cut(platform, gates, where,
                                                   monkeypatch, interpreted):
    """`gdn.chunks{where=}` likewise: `kernel` where the per-channel
    kernels' index maps cut the chunks out of the arrays the op holds
    (rank-4 g on the TPU; the compiled module then holds no transposed
    array of heads of 128 under the op's `gdn_intra`: beta's,
    [.., 64, H], is XLA's), `xla` where `_to_chunks` does: a decay a
    head, and every other platform."""
    text = _counted_where('gdn.chunks', platform, gates, where, 128,
                          monkeypatch)
    moved = [l for l in text.splitlines() if 'gated_delta_rule_' in l
             and 'gdn_intra' in l and re.search(
                 r',128\]\S* transpose\(', l.split('metadata')[0])]
    assert bool(moved) == (where == 'xla'), moved[:2]


def test_every_heads_a_grid_step_gives_the_same_chunks():
    """`heads` only groups chunk-heads into grid steps: whole groups of
    the value heads that share a key head."""
    rng = np.random.default_rng(5)
    shape = (2, 1, 4, 64)
    q, k = (jnp.asarray(rng.normal(size=(2, 1, 2, 64, 128)) * 0.1,
                        jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(rng.normal(size=shape + (128,)), jnp.bfloat16)
    g_sum = jnp.cumsum(-jnp.asarray(rng.uniform(0, 0.3, size=shape),
                                    jnp.float32), axis=-1)
    beta = jnp.asarray(rng.uniform(0, 1, size=shape), jnp.float32)
    two = gdi.gated_delta_intra(q, k, v, g_sum, beta, True, 2)
    four = gdi.gated_delta_intra(q, k, v, g_sum, beta, True, 4)
    for a, b in zip(two, four):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert gdi._heads(32, 2, bf16) == gdi.HEADS
    assert gdi._heads(32, 2, f32) == gdi.HEADS // 2
    assert gdi._heads(6, 1, bf16) == 6 and gdi._heads(7, 1, bf16) == 7
    assert gdi._heads(12, 3, bf16) == 6 and gdi._heads(32, 16, f32) == 16
