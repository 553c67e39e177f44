"""Mamba-2's state-space scan as Pallas kernels (ISSUE 41): both bodies in
the interpreter against the composition they replace on the TPU
(`linear_attention_ops._ssd` and `jax.vjp` of it) and against the
token-by-token recurrence of the benchmark's plain reference, the shapes
they take and refuse, the type of the state they carry, the rule's choice
between the two ways and the calls a lowered step holds. Chunks of 128 or
256 (ISSUE 53: the chunk is an argument of both kernels) and states of 128,
which the kernels ask for; few heads and two or three chunks keep the
interpreter cheap. On the CPU."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
from paddle_tpu.ops.kernels import ssd_scan as sk

from test_nemotron_h import _recurrence, _scan_inputs
from util import (grads_of as _grads_of, input_parameter as _input,
                  out_and_grads)


@pytest.fixture
def interpreted(monkeypatch):
    """The rule hands the kernels `interpret=False` (Mosaic); here their
    bodies run in the Pallas interpreter."""
    for name in ('ssd_scan_fwd', 'ssd_scan_bwd'):
        real = getattr(sk, name)
        monkeypatch.setattr(
            sk, name, lambda *a, _real=real, **kw: _real(
                *a, **dict(kw, interpret=True)))


# (batch, tokens, heads, head width, groups, state, chunk): two rows whose
# last chunk is 44 tokens and 84 of padding, a group serving two heads of
# 64 (two heads a lane tile); a head a group, heads of a whole lane tile;
# four heads a group in steps of two (dB and dC summed over the steps);
# the published chunk of 256: a row of two chunks whose second is 44
# tokens and 212 of padding, two groups; and ONE group for sixteen heads,
# so two grid steps of eight (four in float32) read the same B and C and
# hand dB and dC on in parts
_SCANS = {
    'ragged_two_rows': (2, 172, 4, 64, 2, 128, 128),
    'a_head_a_group': (1, 200, 2, 128, 2, 128, 128),
    'whole_chunks_one_group': (1, 256, 4, 64, 1, 128, 128),
    'chunk_256_ragged': (1, 300, 4, 64, 2, 128, 256),
    'chunk_256_one_group_in_steps': (1, 512, 16, 64, 1, 128, 256),
}


def _op(kernel, amp, chunk=128):
    def op(x, dt, a, b, c, d=None):
        if amp:
            x, b, c = (v.astype(jnp.bfloat16) for v in (x, b, c))
        return la.ssd_scan(x, dt, a, b, c, d, chunk_size=chunk,
                           kernel=kernel)
    return op


# every case both ways and with and without the skip at a chunk of 128;
# the skip is the same lines of the bodies at 256, so those cases keep it
@pytest.mark.parametrize('case,amp,skip', [
    pytest.param(case, amp, skip, id='%s-%s-%s' % (
        case, 'bf16' if amp else 'float32', 'd' if skip else 'no_d'))
    for case in sorted(_SCANS) for amp in (False, True)
    for skip in (True, False) if skip or _SCANS[case][-1] == 128])
def test_the_kernels_are_the_composition_and_the_recurrence(
        case, amp, skip, interpreted):
    """The output and the gradient of all six inputs (five without D):
    against the recurrence at `test_ssd_scan_is_the_recurrence`'s
    tolerances, and against `_ssd`, the same chunked arithmetic with its
    sums in another order (A's gradient is a sum of cancelling terms a
    token: 3e-5 between the two where either is 2e-5 from the
    recurrence)."""
    *shape, chunk = _SCANS[case]
    args = _scan_inputs(*shape, seed=len(case))
    if not skip:
        args = args[:5]
    w = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape),
                    jnp.float32)
    with jax.default_matmul_precision('highest'):
        y, got = out_and_grads(_op(True, amp, chunk), args, w)
        near_y, near = out_and_grads(_op(False, amp, chunk), args, w)
        want_y, want = out_and_grads(
            _recurrence if skip else lambda *v: _recurrence(
                *v, jnp.zeros_like(v[2])), args, w)
    assert y.dtype == jnp.float32 and y.shape == args[0].shape
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y).max()) <= (
        2.0 ** -6 if amp else 2e-5) * scale
    assert float(jnp.abs(y - near_y).max()) <= (
        2.0 ** -9 if amp else 2e-6) * scale
    if amp:
        assert float(jnp.abs(y - want_y).max()) > 0        # bf16 did run
    for name, a, b, c in zip(('x', 'dt', 'a', 'b', 'c', 'd'), got, near,
                             want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert float(jnp.abs(c).max()) > 0, name
        rel = float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c))
        assert rel <= (2.0 ** -5 if amp else 2e-5), (name, rel)
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel <= (2.0 ** -5 if amp else 5e-5), (name, rel)


def test_every_heads_a_grid_step_gives_the_same_scan(monkeypatch):
    """`HEADS` only groups a group's heads into grid steps; a step of
    fewer heads than the group hands dB and dC on in parts."""
    args = _scan_inputs(1, 256, 8, 64, 2, 128, seed=3)
    w = jnp.asarray(np.random.default_rng(2).normal(size=args[0].shape),
                    jnp.float32)
    x, dt, b, c = (v.astype(t) for v, t in zip(
        (args[0], args[1], args[3], args[4]),
        (jnp.bfloat16, jnp.float32, jnp.bfloat16, jnp.bfloat16)))
    got = []
    for heads in (4, 2):
        monkeypatch.setattr(sk, 'HEADS', heads)
        y, starts = sk.ssd_scan_fwd(x, dt, args[2], b, c, args[5],
                                    chunk=128, interpret=True)
        assert starts.shape == (1, 8 // heads, 2, 128, heads * 64)
        assert starts.dtype == jnp.float32
        got.append((y,) + sk.ssd_scan_bwd(x, dt, args[2], b, c, args[5],
                                          starts, w, chunk=128,
                                          interpret=True))
    for name, a, b in zip('y x dt a b c d'.split(), *got):
        assert a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 2.0 ** -8 * np.linalg.norm(b), name
    monkeypatch.undo()
    assert sk._heads(8, 64) == sk.HEADS == 8
    assert sk._heads(16, 64) == 8 and sk._heads(4, 64) == 4
    assert sk._heads(6, 64) == 6 and sk._heads(3, 128) == 3
    assert sk._heads(1, 128) == 1 and sk._heads(12, 128) == 6
    # a chunk of 256: eight heads of bf16 operands, four of float32 (the
    # backward's scoped VMEM, tests/test_flash_aot.py compiles both)
    assert sk._heads(64, 64, 256, 2) == 8 and sk._heads(64, 64, 256, 4) == 4
    assert sk._heads(64, 64, 128, 4) == 8 and sk._heads(2, 64, 256, 4) == 2


def test_usable_at_its_boundaries():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # (chunk, P, N, heads a group, dtype): the cell's, and its check's
    assert sk.usable(128, 64, 128, 8, bf16) and sk.usable(128, 64, 128, 8, f32)
    assert sk.usable(128, 128, 256, 1, np.dtype('float32'))
    assert sk.usable(128, 64, 128, 2, bf16)
    assert not sk.usable(128, 64, 128, 1, bf16)   # half a lane tile a group
    assert not sk.usable(128, 64, 128, 3, bf16)
    assert not sk.usable(64, 64, 128, 8, bf16)
    # the published chunk of 256, one group for all 64 heads (ISSUE 53)
    assert sk.usable(256, 64, 128, 64, bf16) and sk.usable(256, 64, 128, 64, f32)
    assert sk.usable(128, 64, 128, 64, bf16)
    assert not sk.usable(512, 64, 128, 64, bf16)
    assert not sk.usable(192, 64, 128, 64, bf16)
    assert not sk.usable(16, 8, 16, 2, f32)       # the toy cell's
    assert not sk.usable(128, 32, 128, 8, bf16)
    assert not sk.usable(128, 64, 64, 8, bf16)
    assert not sk.usable(128, 64, 128, 8, jnp.float16)
    # a row shorter than a chunk is cut to the power of two that holds it
    assert la._chunk_of(128, 8192) == 128 and la._chunk_of(128, 100) == 128
    assert la._chunk_of(128, 40) == 64
    assert la._chunk_of(256, 8192) == 256 and la._chunk_of(256, 200) == 256


def _pallas_calls(jaxpr, found=None):
    """Every pallas_call equation of a jaxpr, the nested ones included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
def test_the_state_between_chunks_is_float32_in_both_kernels(amp):
    """Whatever the operands: the one scratch of either kernel, which the
    sequential axis walks, is float32 [N, heads P]; so is what the forward
    keeps of it for the backward."""
    args = _scan_inputs(1, 256, 4, 64, 2, 128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *v: jnp.sum(_op(True, amp)(*v)), argnums=range(6)))(*args)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 2
    operands = jnp.bfloat16 if amp else jnp.float32
    for eqn in calls:
        mapping = eqn.params['grid_mapping']
        assert mapping.grid == (1, 2, 2)
        body = eqn.params['jaxpr']
        scratch = [v.aval for v in body.invars[-mapping.num_scratch_operands:]]
        assert [(s.shape, s.dtype) for s in scratch] == [
            ((128, 128), jnp.float32)]
        assert eqn.invars[0].aval.dtype == operands
    forward, backward = calls
    starts = [v.aval for v in forward.outvars if v.aval.ndim == 5]
    assert [(s.shape, s.dtype) for s in starts] == [
        ((1, 2, 2, 128, 128), jnp.float32)]
    assert any(v.aval.shape == (1, 2, 2, 128, 128)
               for v in backward.invars)


def test_a_lowered_backward_is_one_forward_call_and_one_backward_call():
    """For the TPU (`jax.export`, no chip): the gradient of the op the
    kernel way is two Mosaic calls, the forward that keeps the starts and
    the backward, with no loop of XLA's around or beside them; the forward
    alone is one. The composed way has the scan's loop and no call."""
    from jax import export
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((1, 512, 16, 64), jnp.bfloat16), ((1, 512, 16), jnp.float32),
        ((16,), jnp.float32), ((1, 512, 2, 128), jnp.bfloat16),
        ((1, 512, 2, 128), jnp.bfloat16), ((16,), jnp.float32))]

    def lowered(kernel, grad):
        def op(*v):
            return la.ssd_scan(*v, chunk_size=128, kernel=kernel)
        fn = jax.grad(lambda *v: jnp.sum(op(*v) ** 2),
                      argnums=range(6)) if grad else op
        return export.export(jax.jit(fn), platforms=['tpu'])(
            *shapes).mlir_module()

    text = lowered(True, True)
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 2
    assert 'stablehlo.while' not in text
    # nothing [.., 128, 128] float32 a chunk-head between the calls
    assert not re.search(r'tensor<[0-9x]*x128x128xf32>', text.replace(
        'tensor<1x2x4x128x512xf32>', ''))
    # a forward that no backward follows does not write the starts
    text = lowered(True, False)
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 1
    assert 'stablehlo.while' not in text and 'x128x512xf32' not in text
    text = lowered(False, True)
    assert 'tpu_custom_call' not in text and 'stablehlo.while' in text


def _ways():
    return {w: obs.counter('ssd.way', way=w).value
            for w in ('kernel', 'composed')}


@pytest.mark.parametrize('platform', ['cpu', 'tpu'])
def test_the_rule_chooses_on_platform_and_shape(platform, monkeypatch,
                                                interpreted):
    """Through the Executor: on the CPU the composition, with the platform
    reported as `tpu` the kernels (here in the interpreter), `ssd.way`
    counted once a lowering beside `ssd.lowered` on either way; a shape
    outside `usable` keeps the composition on either; the values are the
    recurrence's both ways and the op's scope holds what ran."""
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform=platform)))
    args = _scan_inputs(1, 200, 4, 64, 2, 128, seed=5)
    names = ['x', 'dt', 'a', 'b', 'c', 'd']
    w = np.random.default_rng(3).normal(size=args[0].shape).astype('float32')

    def build(chunk):
        return lambda: layers.ssd_scan(
            *(_input(n, v) for n, v in zip(names, args)), chunk_size=chunk)

    label = dict(chunk=128, heads=4, groups=2)
    before, lowered = _ways(), obs.counter('ssd.lowered', **label).value
    got, grads, text = _grads_of(build(128), {'w': w}, names, optimized=True)
    after = _ways()
    took, other = (('kernel', 'composed') if platform == 'tpu'
                   else ('composed', 'kernel'))
    assert after[took] - before[took] == \
        obs.counter('ssd.lowered', **label).value - lowered >= 1
    assert after[other] == before[other]
    want_y, want = out_and_grads(_recurrence, args, jnp.asarray(w))
    assert np.abs(got - want_y).max() <= 2e-5 * np.abs(want_y).max()
    for name, a, b in zip(names, grads, want):
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), name
    scoped = set(re.findall(r'op_name="([^"]*\(ssd_scan_\d+\)[^"]*)"', text))
    assert any(n.startswith('jit(step)/jvp(ssd_scan_') for n in scoped)
    assert any('transpose(jvp(ssd_scan_' in n for n in scoped)
    composed = [n for n in scoped if re.search(r'[/(]ssd_intra[/)]', n)]
    assert bool(composed) == (platform == 'cpu')
    # a chunk of 64 is not the kernels', whatever the platform
    before = _ways()
    _grads_of(build(64), {'w': w}, names)
    after = _ways()
    assert after['kernel'] == before['kernel']
    assert after['composed'] > before['composed']


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'bf16'])
def test_the_rule_asks_the_kernels_at_the_published_chunk_of_256(
        amp, monkeypatch, interpreted):
    """granite4hmicro_s8192's scan as the model hands it over (ISSUE 53):
    64 heads of 64, ONE group of state 128, chunk_size 256. On the TPU the
    rule asks the kernels, in the step's bf16 and in the float32 check's
    arithmetic, and counts the lowering under its own labels; only
    lowered here (two chunks), nothing runs."""
    from paddle_tpu import fluid
    from paddle_tpu.fluid import framework, unique_name
    init = lowering.Ctx.__init__
    monkeypatch.setattr(
        lowering.Ctx, '__init__',
        lambda self, *a, **kw: init(self, *a, **dict(kw, platform='tpu')))
    args = _scan_inputs(1, 512, 64, 64, 1, 128, seed=7)
    label = dict(chunk=256, heads=64, groups=1)
    before, lowered = _ways(), obs.counter('ssd.lowered', **label).value
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out = layers.ssd_scan(*(_input(n, v) for n, v in zip('xtabcd', args)),
                              chunk_size=256)
        loss = layers.reduce_sum(out)
        grads = [g for _, g in fluid.backward.append_backward(loss)]
        if amp:
            fluid.amp.decorate_program(main)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        text = exe.lowered_hlo(main, {}, [loss] + grads)
    after = _ways()
    assert after['kernel'] - before['kernel'] == \
        obs.counter('ssd.lowered', **label).value - lowered >= 1
    assert after['composed'] == before['composed']
    assert not re.search(r'[/(]ssd_intra[/)]', text)
    # the starts the backward reads: two chunks of eight steps of eight
    # heads in bf16, sixteen steps of four in float32
    assert ('tensor<1x8x2x128x512xf32>' if amp
            else 'tensor<1x16x2x128x256xf32>') in text
