"""Stage `gdn_scan` of the gated delta rule as Pallas kernels (ISSUE 50):
the kernel bodies in the interpreter against the composition they replace
on the TPU (a `lax.scan` of `linear_attention_ops._chunk_step` and
`jax.vjp` of it), the whole op through both stages' kernels against the
composition and the token-by-token recurrence, the calls a lowered op
holds, the type of the state the kernels carry and the rule's choice
between the two ways. Heads of 128, which the kernels ask for; short rows
and few heads keep the interpreter cheap. On the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.ops.kernels import gated_delta_intra as gdi
from paddle_tpu.ops.kernels import gated_delta_scan as gds

from test_gated_delta_intra import op_inputs as _op_inputs
from test_gated_delta_rule import plain_delta_net
from util import (grads_of as _grads_of, input_parameter as _input,
                  out_and_grads)
from test_ssd_scan_kernel import _pallas_calls

BF16_ULP = 2.0 ** -8


@pytest.fixture
def interpreted(monkeypatch):
    """The rule hands both stages' kernels `interpret=False` (Mosaic); here
    their bodies run in the Pallas interpreter."""
    intra, scan = gdi.gated_delta_intra, gds.gated_delta_scan
    monkeypatch.setattr(
        gdi, 'gated_delta_intra',
        lambda q, k, v, g_sum, beta, interpret, heads=None, **kw: intra(
            q, k, v, g_sum, beta, True, heads, **kw))
    tokens = gdi.gated_delta_intra_tokens
    monkeypatch.setattr(
        gdi, 'gated_delta_intra_tokens',
        lambda q, k, v, g, beta, interpret, heads=None, **kw: tokens(
            q, k, v, g, beta, True, heads, **kw))
    monkeypatch.setattr(
        gds, 'gated_delta_scan',
        lambda xs, dtype, interpret: scan(xs, dtype, True))


def op_inputs(seed, b, t, hk, hv, gates, dtype=jnp.float32):
    return _op_inputs(seed, t, hk, hv, gates, dtype, b=b)


def chunks_of(seed, b, t, hk, hv, dtype, gates='mild'):
    """What stage `gdn_intra` hands the scan of such a row (`_intra`, the
    norm, the key heads' repeat, the chunks and their padding included), in
    the dtypes the kernel of that stage leaves them in; with `gates`
    'channel' the chunk's decay is [N, B, H, Dk]."""
    cfg = (64, 128 ** -0.5, True, 1e-6, False)
    w, u, qg, kd, p, decay = jax.jit(lambda *a: la._stage_intra(*a, cfg))(
        *op_inputs(seed, b, t, hk, hv, gates, dtype))
    return (w.astype(dtype), u, qg.astype(dtype), kd.astype(dtype),
            p.astype(dtype), decay)


# (rows, tokens, key heads, value heads, value heads a grid step): a row
# of one head whose third chunk is 36 tokens and 28 of padding (a chunk's
# decay has a cotangent only between two others); two rows of three whole
# chunks, two heads the whole axis; sixteen heads in two steps of eight
ROWS = {'padded_a_head': (1, 164, 1, 1, 1), 'two_rows': (2, 192, 1, 2, 2),
        'two_steps_of_heads': (1, 192, 8, 16, 8)}


@pytest.mark.parametrize('gates', ['mild', 'channel'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('rows', list(ROWS))
def test_the_kernels_are_the_scan_and_its_transposition(rows, dtype, gates):
    """The forward kernel against a `lax.scan` of `_chunk_step`, the
    reverse kernel (behind the forward-again walk) against `jax.vjp` of
    that scan, at one head, at several and at several steps of heads a
    row: float32 to 1e-5, bf16 to 2 ulp of bf16 (a cotangent is rounded
    where a product reads it). With a decay a channel the state's rows are
    scaled each by its own factor and the decay's cotangent is
    [N, B, H, Dk]."""
    dtype = jnp.dtype(dtype)
    b, t, hk, hv, heads = ROWS[rows]
    if gates == 'channel':
        hk = hv
    xs = chunks_of(len(rows), b, t, hk, hv, dtype, gates)
    assert xs[5].ndim == (4 if gates == 'channel' else 3)
    n, b, h, c, d = xs[1].shape
    assert gds._heads(h) == heads
    do = jnp.asarray(np.random.default_rng(1).normal(
        size=(b, n * c, h, d)), jnp.float32)

    def pulled(fn):
        """fn's output and `do` pulled back through it, in one compile."""
        def both(do, *x):
            out, pull = jax.vjp(fn, *x)
            return out, pull(do)
        return jax.jit(both)(do, *xs)

    with jax.default_matmul_precision('highest'):
        want, g_want = pulled(lambda *x: la._scan(x, dtype, False))
        got, g_got = pulled(lambda *x: gds.gated_delta_scan(x, dtype, True))
        # the composition's own backward, in its two walks, is that vjp
        g_walks = jax.jit(lambda do, *x: la._scan_bwd(x, do, dtype, False))(
            do, *xs)
    tol = 1e-5 if dtype == jnp.float32 else 2 * BF16_ULP
    assert got.dtype == jnp.float32 and got.shape == do.shape
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    for name, a, b, c in zip(('w', 'u', 'qg', 'kd', 'p', 'decay'), g_got,
                             g_want, g_walks):
        assert a.dtype == b.dtype == c.dtype and a.shape == b.shape, name
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        assert np.linalg.norm(b) > 0, name
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), (
            name, np.linalg.norm(a - b) / np.linalg.norm(b))
        assert np.linalg.norm(c - b) <= 1e-6 * np.linalg.norm(b), name


def test_the_padded_tokens_change_nothing():
    """A row of 100 tokens and the same row with 28 more tokens of k = 0,
    beta = 0, g = 0: the first 100 outputs and every gradient are equal,
    and the added tokens' gradients of v are zero."""
    args = op_inputs(3, 1, 100, 1, 2, 'mild')
    pad = [(0, 0), (0, 28), (0, 0)]
    longer = tuple(jnp.pad(a, pad + [(0, 0)] * (a.ndim - 3)) for a in args)
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=longer[2].shape), jnp.float32)

    def op(*a):
        cfg = (64, 128 ** -0.5, False, 1e-6, False)
        xs = la._stage_intra(*a, cfg)
        return gds.gated_delta_scan(xs, jnp.float32, True)[:, :a[0].shape[1]]

    with jax.default_matmul_precision('highest'):
        short, g_short = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(op(*a) * w[:, :100]),
            argnums=(0, 1, 2)))(*args)
        long_, g_long = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(op(*a)[:, :100] * w[:, :100]),
            argnums=(0, 1, 2)))(*longer)
    np.testing.assert_allclose(short, long_, rtol=1e-6)
    for a, b in zip(g_short, g_long):
        np.testing.assert_allclose(a, b[:, :100], rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(g_long[2][:, 100:]).max()) == 0.0


@pytest.mark.parametrize('gates', ['mild', 'strong', 'channel',
                                   'channel_floor'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_the_op_through_the_kernels_is_the_composition(dtype, gates,
                                                       interpreted):
    """Values and all five gradients of `gated_delta_rule`, both stages'
    kernels against both compositions, two rows that end in a padded chunk
    and key heads that serve two value heads (one, with a decay a
    channel); in float32 against the token-by-token definition too."""
    dtype = jnp.dtype(dtype)
    channel = gates.startswith('channel')
    args = op_inputs(7, 2, 100, 2 if channel else 1, 2, gates, dtype)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)

    def through(kernels):
        def op(*a):
            return la.gated_delta_rule(*a, chunk_size=64, qk_l2norm=True,
                                       kernel=kernels, scan_kernel=kernels,
                                       gate_floor=-5.0 if channel else None)
        return out_and_grads(op, args, weight)

    with jax.default_matmul_precision('highest'):
        got, g_got = through(True)
        want, g_want = through(False)
    tol = 1e-5 if dtype == jnp.float32 else 2 * BF16_ULP
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) <= tol * float(
        jnp.abs(want).max())
    # 'strong' gates leave g's gradient to what rounding leaves of decays
    # of e^-5 and less: against the largest gradient there
    floor = 1e-3 * float(jnp.linalg.norm(g_want[4].astype(jnp.float32)))
    for name, a, b in zip('q k v g beta'.split(), g_got, g_want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        scale = max(np.linalg.norm(b), floor if name == 'g' else 0.0)
        assert np.linalg.norm(a - b) <= 2 * tol * scale, (
            name, np.linalg.norm(a - b) / scale)
    if dtype != jnp.float32:
        return
    with jax.default_matmul_precision('highest'):
        exact, g_exact = out_and_grads(plain_delta_net, args, weight)
    assert float(jnp.abs(got - exact).max()) < 2e-5 * float(
        jnp.abs(exact).max())
    for name, a, b in zip('q k v g beta'.split(), g_got, g_exact):
        err = float(jnp.linalg.norm(a - b))
        assert err < 3e-4 * float(jnp.linalg.norm(b)) + 1e-7, (name, err)


def _shapes(dtype, n=2, b=1, h=4):
    like = jax.ShapeDtypeStruct
    wide, f32 = (n, b, h, 64, 128), jnp.float32
    return (like(wide, dtype), like(wide, f32), like(wide, dtype),
            like(wide, dtype), like((n, b, h, 64, 64), dtype),
            like((n, b, h), f32))


def test_the_starts_are_written_only_where_something_reads_them():
    """For the TPU (`jax.export`, no chip): the op's forward is ONE Mosaic
    call that writes O and no S; a backward is two more, the walk that
    writes S at each chunk's start (and no O: it reads neither Qg nor P)
    and the reverse walk; no loop of XLA's around or beside them. The
    composition has the scans' loops and no call."""
    from jax import export
    shapes = _shapes(jnp.bfloat16)
    starts = 'tensor<2x1x4x128x128xf32>'

    def lowered(fn):
        return export.export(jax.jit(fn), platforms=['tpu'])(
            *shapes).mlir_module()

    def scan(*x):
        return gds.gated_delta_scan(x, jnp.bfloat16, False)

    text = lowered(scan)
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 1
    assert starts not in text and 'stablehlo.while' not in text
    # a loss that needs O for its cotangent: forward, again, reverse
    text = lowered(jax.grad(lambda *x: jnp.sum(scan(*x) ** 2),
                            argnums=range(6)))
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 3
    assert 'stablehlo.while' not in text
    # one that does not (what `_chunked_bwd` asks): the forward that
    # jax.vjp runs first has no reader and leaves no call
    ct = jnp.ones((1, 128, 4, 128), jnp.float32)
    text = lowered(lambda *x: la._scan_bwd(x, ct, jnp.bfloat16, True))
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 2
    calls = [l for l in text.splitlines() if '@tpu_custom_call' in l]
    again = [l for l in calls if '-> ' + starts in l]
    assert len(again) == 1 and again[0].count('tensor<2x1x4x64x128x') == 3
    text = lowered(lambda *x: la._scan_bwd(x, ct, jnp.bfloat16, False))
    assert 'tpu_custom_call' not in text and 'stablehlo.while' in text


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_the_state_between_chunks_is_float32_in_both_kernels(dtype):
    """Whatever the operands: the one scratch of each of the three calls,
    which the sequential axis walks, is float32 [heads, Dk, Dv]; so is S
    at the chunks' starts between the two walks of the backward."""
    dtype = jnp.dtype(dtype)
    shapes = _shapes(dtype, h=16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *x: jnp.sum(gds.gated_delta_scan(x, dtype, True) ** 2),
        argnums=range(6)))(*shapes)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 3
    for eqn in calls:
        mapping = eqn.params['grid_mapping']
        assert mapping.grid == (1, 2, 2)
        body = eqn.params['jaxpr']
        scratch = [v.aval for v in body.invars[-mapping.num_scratch_operands:]]
        assert [(s.shape, s.dtype) for s in scratch] == [
            ((8, 128, 128), jnp.float32)]
        assert eqn.invars[0].aval.dtype == dtype
    starts = [v.aval for eqn in calls for v in eqn.outvars
              if v.aval.shape == (2, 1, 16, 128, 128)]
    assert starts and all(s.dtype == jnp.float32 for s in starts)


def test_usable_at_its_boundaries():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # (chunk, Dk, Dv, value heads, dtype): the cell's, and its check's
    assert gds.usable(64, 128, 128, 32, bf16)
    assert gds.usable(64, 128, 128, 32, f32)
    assert gds.usable(64, 256, 128, 16, np.dtype('bfloat16'))
    assert gds.usable(64, 128, 128, 6, bf16)          # fewer than a step's
    assert not gds.usable(64, 128, 128, 12, bf16)     # a step and a half
    assert not gds.usable(64, 256, 128, 16, f32)      # the reverse walk's
    assert not gds.usable(64, 256, 256, 16, bf16)     # VMEM (AOT, PR 50)
    assert not gds.usable(16, 128, 128, 32, bf16)     # the toy cells' chunk
    assert not gds.usable(128, 128, 128, 32, bf16)
    assert not gds.usable(64, 96, 128, 32, bf16)
    assert not gds.usable(64, 128, 64, 32, f32)
    assert not gds.usable(64, 128, 128, 32, jnp.float16)
    # heads a grid step: HEADS, or the whole axis of fewer
    assert gds._heads(32) == gds.HEADS == 8
    assert gds._heads(8) == 8 and gds._heads(6) == 6 and gds._heads(1) == 1


def _ways(stage):
    return {w: obs.counter('gdn.' + stage, way=w).value
            for w in ('kernel', 'composed')}


@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_rule_chooses_on_platform_and_shape(platform, monkeypatch,
                                                interpreted):
    """Through the Executor: on the CPU the composition, with the platform
    reported as `tpu` the kernels (here in the interpreter), `gdn.scan`
    counted once an op a trace beside `gdn.intra`; a shape outside `usable`
    keeps the composition on either; the stage's scope is in the compiled
    module's metadata forward and backward and the values are the
    recurrence's both ways."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    args = op_inputs(11, 1, 100, 1, 2, 'mild')
    names = ['q', 'k', 'v', 'g', 'beta']
    w = np.random.default_rng(3).normal(size=args[2].shape).astype('float32')

    def build(chunk):
        return lambda: layers.gated_delta_rule(
            *(_input(n, a) for n, a in zip(names, args)), chunk_size=chunk,
            qk_l2norm=True)

    before, intra = _ways('scan'), _ways('intra')
    got, grads, text = _grads_of(build(64), {'w': w}, names, optimized=True)
    after = _ways('scan')
    took, other = (('kernel', 'composed') if platform == 'tpu'
                   else ('composed', 'kernel'))
    assert 1 <= after[took] - before[took] == \
        _ways('intra')[took] - intra[took]
    assert after[other] == before[other]
    want = plain_delta_net(*args)
    g_want = jax.grad(lambda *a: jnp.sum(plain_delta_net(*a) * w),
                      argnums=range(5))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(grads, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
    scoped = [l for l in text.splitlines() if 'gated_delta_rule_' in l]
    assert any('gdn_scan' in l and 'transpose' not in l for l in scoped)
    assert any('gdn_scan' in l and 'transpose' in l for l in scoped)
    # a chunk of 16 is not the kernels', whatever the platform
    before = _ways('scan')
    _grads_of(build(16), {'w': w}, names)
    after = _ways('scan')
    assert after['kernel'] == before['kernel']
    assert after['composed'] > before['composed']
