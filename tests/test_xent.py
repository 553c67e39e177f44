"""The `softmax_with_cross_entropy` rule in closed form with its own
backward (ISSUE 29): what it computes, against `log_softmax` followed by a
pick written out here; the shape of what it lowers to, so that the extra
walks over the `[tokens, vocab]` logits stay away; and its counter.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
from jax.interpreters import partial_eval as pe

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import layers, lowering
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.fluid.executor import global_scope

from util import fresh_program

V = 11
EPS = 0.1


def _rule(name, ins, attrs=None, amp=False):
    ctx = lowering.Ctx(jax.random.key(0), amp=amp)
    return lowering.get_rule(name)(ins, attrs or {}, ctx)


def _logits(lead, seed=0):
    """Rows of ordinary logits, and the extreme ones: a logit of +80, one
    of -80, a row of equal logits."""
    x = np.random.RandomState(seed).randn(*lead, V).astype('float32') * 3
    flat = x.reshape(-1, V)
    flat[0, 3] = 80.0
    flat[1, 2] = -80.0
    flat[2] = 1.5
    return jnp.asarray(x)


def _ids(lead, seed=1):
    return np.random.RandomState(seed).randint(0, V, lead).astype('int64')


def _label(kind, lead):
    """(the rule's Label input, its soft_label attribute)."""
    ids = _ids(lead)
    if kind == 'hard':
        return jnp.asarray(ids[..., None]), False
    if kind == 'hard_squeezed':
        return jnp.asarray(ids), False
    if kind == 'smoothed':
        hot = _rule('one_hot', {'X': [jnp.asarray(ids[..., None])]},
                    {'depth': V})['Out']
        return _rule('label_smooth', {'X': [hot]}, {'epsilon': EPS})['Out'], \
            True
    rows = np.random.RandomState(2).rand(*lead, V).astype('float32')
    if kind == 'soft':
        rows /= rows.sum(-1, keepdims=True)
    return jnp.asarray(rows), True          # 'unnormalised': sums near V / 2


def _reference(x, label, soft):
    """The plain formulation: log_softmax, then a pick or a weighted sum."""
    logp = jax.nn.log_softmax(x, axis=-1)
    if soft:
        return -jnp.sum(label * logp, axis=-1, keepdims=True)
    ids = label.astype(jnp.int32)
    if ids.ndim == x.ndim:
        ids = jnp.squeeze(ids, -1)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1)


LABELS = ('hard', 'hard_squeezed', 'soft', 'smoothed', 'unnormalised')
FORMS = {'2d': (6,), '3d': (2, 3), 'seq': (2, 3)}


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'amp'])
@pytest.mark.parametrize('form', sorted(FORMS))
@pytest.mark.parametrize('kind', LABELS)
def test_rule_equals_log_softmax_then_pick(kind, form, amp):
    lead = FORMS[form]
    x = _logits(lead)
    label, soft = _label(kind, lead)
    g = jnp.asarray(np.random.RandomState(3).randn(*lead, 1)
                    .astype('float32'))
    lengths = jnp.asarray([3, 2], jnp.int32)

    def wrap(a):
        return lowering.SeqValue(a, lengths) if form == 'seq' else a

    def rule(x):
        outs = _rule('softmax_with_cross_entropy',
                     {'Logits': [wrap(x)], 'Label': [wrap(label)]},
                     {'soft_label': soft}, amp=amp)
        if form == 'seq':
            assert isinstance(outs['Loss'], lowering.SeqValue)
            assert outs['Loss'].lengths is lengths
        return lowering.data_of(outs['Loss']), outs['Softmax']

    (loss, sm), vjp = jax.vjp(rule, x)
    dx, = vjp((g, jnp.zeros_like(sm)))
    want, ref_vjp = jax.vjp(lambda x: _reference(x, label, soft), x)
    want_dx, = ref_vjp(g)
    assert loss.shape == lead + (1,) and loss.dtype == jnp.float32
    # float32 rounding: both sides compute (x - max) - log(sum exp) in
    # float32 and differ only in the order of a row's sums
    np.testing.assert_allclose(loss, want, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(dx, want_dx, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(sm, jax.nn.softmax(x, axis=-1), rtol=1e-6,
                               atol=1e-7)
    # a fetched Softmax that something differentiates still has a gradient
    dsm, = vjp((jnp.zeros_like(g), jnp.ones_like(sm) * x))
    want_dsm, = jax.vjp(lambda x: jax.nn.softmax(x, axis=-1), x)[1](
        jnp.ones_like(sm) * x)
    np.testing.assert_allclose(dsm, want_dsm, rtol=1e-5, atol=1e-6)


def test_a_soft_label_that_depends_on_a_parameter_keeps_its_gradient():
    x = _logits((6,))
    label, _ = _label('soft', (6,))

    def through(f):
        return jax.grad(lambda lab: f(x, lab * lab).sum())(label)

    got = through(lambda x, lab: lowering.data_of(_rule(
        'softmax_with_cross_entropy', {'Logits': [x], 'Label': [lab]},
        {'soft_label': True})['Loss']))
    want = through(lambda x, lab: _reference(x, lab, True))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_a_negative_id_counts_from_the_end_in_both_passes():
    x = _logits((6,))
    ids = jnp.asarray([[-1], [3], [-V], [0], [-2], [5]], jnp.int32)

    def loss(f):
        return jax.value_and_grad(lambda x: f(x).sum())(x)

    got = loss(lambda x: _rule('softmax_with_cross_entropy',
                               {'Logits': [x], 'Label': [ids]})['Loss'])
    want = loss(lambda x: _reference(x, ids, False))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# through a Program: Executor.run with append_backward
# ---------------------------------------------------------------------------

def _head_program(kind):
    """fc over [B, T, D] -> softmax_with_cross_entropy -> mean, the shape
    of the language models' heads (models/transformer.py,
    models/olmoe.py)."""
    x = layers.data(name='x', shape=[3, 4], dtype='float32')
    ids = layers.data(name='ids', shape=[3, 1], dtype='int64')
    logits = layers.fc(input=x, size=V, num_flatten_dims=2)
    if kind == 'hard':
        cost = layers.softmax_with_cross_entropy(logits, ids)
    else:
        soft = layers.label_smooth(layers.one_hot(ids, depth=V), epsilon=EPS)
        cost = layers.softmax_with_cross_entropy(logits, soft,
                                                 soft_label=True)
    return layers.mean(cost), logits


@pytest.mark.parametrize('amp', [False, True], ids=['float32', 'amp'])
@pytest.mark.parametrize('kind', ['hard', 'smoothed'])
def test_program_parameter_gradients_are_the_plain_formulations(kind, amp):
    rng = np.random.RandomState(5)
    feed = {'x': rng.randn(2, 3, 4).astype('float32'),
            'ids': _ids((2, 3, 1))}
    with fresh_program() as (main, startup):
        loss, logits = _head_program(kind)
        append_backward(loss)
        if amp:
            fluid.amp.decorate_program(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = global_scope()
        w, b = (np.asarray(scope.vars[n]) for n in ('fc_0.w_0', 'fc_0.b_0'))
        got = exe.run(main, feed=feed, fetch_list=[
            loss, logits, 'fc_0.w_0@GRAD', 'fc_0.b_0@GRAD'])
    got_loss, got_logits, got_dw, got_db = (np.asarray(a) for a in got)

    # the plain head from the logits the Program computed (under AMP its
    # matmuls are bf16; the loss rule itself stays float32)
    ids = jnp.asarray(feed['ids'])
    hot = jax.nn.one_hot(ids[..., 0], V, dtype=jnp.float32)
    label = ids if kind == 'hard' else (1 - EPS) * hot + EPS / V

    def plain(z):
        return jnp.mean(_reference(z, label, kind != 'hard'))

    want_loss, dz = jax.value_and_grad(plain)(jnp.asarray(got_logits))
    np.testing.assert_allclose(got_loss.reshape(()), want_loss, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(got_db, dz.sum((0, 1)), rtol=1e-5, atol=1e-6)
    if not amp:
        np.testing.assert_allclose(
            got_logits, feed['x'] @ w + b, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            got_dw, np.einsum('btd,btv->dv', feed['x'], np.asarray(dz)),
            rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the mechanism: what a lowering of the rule holds
# ---------------------------------------------------------------------------

_REDUCES = ('reduce_sum', 'reduce_max', 'reduce_min', 'reduce_prod',
            'argmax', 'argmin', 'cumsum', 'cumlogsumexp', 'dot_general')
_PICKS = ('gather', 'scatter', 'scatter-add', 'scatter_add',
          'dynamic_slice')
_WRITES = ('optimization_barrier',)     # its operand is an array in memory


def _walks(closed, wide):
    """Every reduction over and pick from an array of `wide` elements in a
    jaxpr, sub-jaxprs inlined: [(primitive, the operand depends on a SUM
    over such an array)]. A row maximum is the projection's by-product
    and taints nothing."""
    found = []

    def scan(jaxpr, tainted_in):
        taint = dict(zip(jaxpr.invars, tainted_in))

        def of(a):
            return isinstance(a, jex_core.Var) and taint.get(a, False)

        for e in jaxpr.eqns:
            ins = [of(a) for a in e.invars]
            # a call (pjit, a custom rule's body) has its operands' arity;
            # a scatter's combiner has not and is part of the primitive
            subs = [getattr(p, 'jaxpr', p) for p in e.params.values()
                    if isinstance(p, (jex_core.Jaxpr, jex_core.ClosedJaxpr))]
            subs = [sub for sub in subs if len(sub.invars) == len(ins)]
            if subs:
                outs = scan(subs[0], ins)
            else:
                big = [t for a, t in zip(e.invars, ins)
                       if np.prod(np.shape(a.aval), dtype=int) == wide]
                name = e.primitive.name
                if big and name in _REDUCES + _PICKS + _WRITES:
                    found.append((name, any(big)))
                summed = bool(big) and name in _REDUCES \
                    and name != 'reduce_max'
                outs = [any(ins) or summed] * len(e.outvars)
            taint.update(zip(e.outvars, outs))
        return [of(a) for a in jaxpr.outvars]

    scan(closed.jaxpr, [False] * len(closed.jaxpr.invars))
    return found


def _jaxpr(loss_of, x, label, grad):
    def total(x, label):
        return loss_of(x, label).sum()

    closed = jax.make_jaxpr(jax.value_and_grad(total) if grad else total)(
        x, label)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return jex_core.ClosedJaxpr(jaxpr, closed.consts)


@pytest.mark.parametrize('kind', ['hard', 'smoothed'])
def test_no_walk_over_the_logits_waits_for_a_sum_and_none_is_backward(kind):
    N = 16
    x = jnp.asarray(np.random.RandomState(0).randn(N, V).astype('float32'))
    ids = jnp.asarray(_ids((N, 1)))
    soft = kind != 'hard'

    def label_of(ids):
        if not soft:
            return ids
        hot = _rule('one_hot', {'X': [ids]}, {'depth': V})['Out']
        return _rule('label_smooth', {'X': [hot]}, {'epsilon': EPS})['Out']

    def rule(x, ids):
        return _rule('softmax_with_cross_entropy',
                     {'Logits': [x], 'Label': [label_of(ids)]},
                     {'soft_label': soft})['Loss']

    # the row maximum and ONE pass of sibling sums (hard: sum exp and the
    # gather; soft: sum exp, sum label * z, sum label), none of which
    # reads what another sum produced; the backward adds no reduction and
    # no pick. Hard labels write dx once (it waited for sum exp, as any
    # dx must) before the projection's two matmuls read it; soft labels
    # leave it to the matmuls' operand fusions
    want = ([('reduce_max', False), ('reduce_sum', False), ('gather', False)]
            if not soft else
            [('reduce_max', False)] + [('reduce_sum', False)] * 3)
    written = [] if soft else [('optimization_barrier', True)]
    for grad in (False, True):
        walks = _walks(_jaxpr(rule, x, ids, grad), N * V)
        assert sorted(walks) == sorted(want + written * grad), (grad, walks)

    # the yardstick: the plain formulation picks from log-probabilities
    # that waited for the row's sum, and its backward reduces (soft) or
    # scatters (hard) once more
    def plain(x, ids):
        return _reference(x, label_of(ids), soft)

    forward, both = (_walks(_jaxpr(plain, x, ids, grad), N * V)
                     for grad in (False, True))
    assert any(waited for _, waited in forward), forward
    assert len(both) > len(forward), both


def test_xent_lowered_counts_once_per_op_per_lowering_by_label_form():
    def count():
        return {k: obs.counter('xent.lowered', label=k).value
                for k in ('hard', 'soft')}

    def step(x, ids, soft_label):
        hard = _rule('softmax_with_cross_entropy',
                     {'Logits': [x], 'Label': [ids]})['Loss']
        soft = _rule('softmax_with_cross_entropy',
                     {'Logits': [x], 'Label': [soft_label]},
                     {'soft_label': True})['Loss']
        return (hard + soft + soft).sum()

    jitted = jax.jit(jax.grad(step))
    x = _logits((6,))
    args = (x, jnp.asarray(_ids((6, 1))), _label('soft', (6,))[0])
    before = count()
    for _ in range(3):                  # three steps, one lowering
        jitted(*args)
    after = count()
    assert after['hard'] - before['hard'] == 1
    assert after['soft'] - before['soft'] == 1      # one op, used twice
    jitted(x[:4], args[1][:4], args[2][:4])         # another shape
    assert count()['hard'] - before['hard'] == 2
