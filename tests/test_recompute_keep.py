"""A recompute region keeps what its model marks (`fluid.recompute_keep`):
the mark lives on the producing op's attrs, a region hands every marked
output to `jax.checkpoint` under one shared name that the one policy
object saves, the backward pass then runs neither the marked projections
nor the mixer's output projection again, and no number changes. CPU, the
toy presets of the three models that carry marks (granitemoehybrid,
lfm2_moe, smallthinker) and of two that carry none (nemotron_h, which
shares its mixers with granitemoehybrid, and glm4_moe_lite).
"""
import functools

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, layers, step_artifact, unique_name
from paddle_tpu.models import (glm4_moe_lite, granitemoehybrid, lfm2_moe,
                               nemotron_h, smallthinker)

BATCH, SEQ, VOCAB = 2, 32, 256
KEPT = ('recompute.kept_values', 'recompute.kept_bytes')


def _granite():
    return granitemoehybrid.granitemoehybrid(
        VOCAB, SEQ, layer_types=('mamba', 'mamba', 'mamba', 'attention'),
        hidden=64, ssm_heads=4, ssm_head_dim=16, ssm_groups=1, ssm_state=16,
        chunk_size=16, n_head=4, n_kv_head=2, d_head=16, mlp_width=128)[0]


def _lfm2():
    return lfm2_moe.lfm2_moe(
        VOCAB, SEQ, layer_types=('conv', 'full_attention', 'conv', 'conv'),
        n_dense=1, hidden=64, n_head=4, n_kv_head=2, d_head=16,
        dense_width=128, n_expert=16, top_k=2, expert_width=32)[0]


def _smallthinker():
    return smallthinker.smallthinker(
        VOCAB, SEQ, n_layer=4, hidden=64, n_head=4, n_kv_head=2, d_head=16,
        window=8, n_expert=16, top_k=2, expert_width=32)[0]


def _nemotron():
    return nemotron_h.nemotron_h(
        VOCAB, SEQ, pattern='MEM*E', hidden=64, ssm_heads=4,
        ssm_head_dim=16, ssm_groups=2, ssm_state=16, chunk_size=16, n_head=4,
        n_kv_head=2, d_head=16, n_expert=16, top_k=2, expert_width=32,
        shared_width=64)[0]


def _glm():
    return glm4_moe_lite.glm4_moe_lite(
        VOCAB, SEQ, n_layer=3, hidden=64, dense_width=128, n_head=4,
        q_rank=24, kv_rank=16, d_nope=12, d_rope=4, d_v=16, n_expert=16,
        top_k=2, expert_width=32, shared_width=32)[0]


# model -> (its builder, the matmuls its marks take out of the backward
# pass: a layer's mixer's output projection and input projections, which
# are 1 + 1 for a Mamba-2 or short-convolution layer and 1 + 3 for an
# attention layer, and the values it keeps: `h` a layer beside them)
MARKED = {
    'granitemoehybrid': (_granite, 3 * 2 + 4, 3 * 2 + 4),
    'lfm2_moe': (_lfm2, 3 * 2 + 4, 3 * 2 + 4),
    'smallthinker': (_smallthinker, 4 * 4, 4 * 4),
}
UNMARKED = {'nemotron_h': _nemotron, 'glm4_moe_lite': _glm}


def _feed():
    rows = np.random.default_rng(0).integers(0, VOCAB, size=(BATCH, SEQ + 1))
    return {'input_ids': rows[:, :-1].astype('int64'),
            'labels': rows[:, 1:].astype('int64')}


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    yield from _eqns(sub)


@functools.lru_cache(maxsize=None)
def _step(model, strip=False):
    """The model's loss and every gradient on one batch, the jaxpr of the
    step that gave them (forward and backward), what the trace counted,
    and the Program. `strip` takes the marks off first."""
    build = MARKED[model][0] if model in MARKED else UNMARKED[model]
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss = build()
        grads = fluid.backward.append_backward(loss)
    if strip:
        for op in main.global_block().ops:
            op.attrs.pop('recompute_keep', None)
    feed = _feed()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetch = [loss] + [g for _, g in grads]
        # the first run is the step's one trace: what it counted
        before = [obs.counter(n).value for n in KEPT]
        values = exe.run(main, feed=feed, fetch_list=fetch)
        counted = [obs.counter(n).value - b for n, b in zip(KEPT, before)]
        scope = fluid.global_scope()
        compiled = exe.step_artifact(main, feed, fetch, scope)
        donated, readonly = compiled.plan.split(compiled.state_dict(scope))
        jaxpr = compiled._jitted.trace(
            donated, readonly, {n: jax.numpy.asarray(v)
                                for n, v in feed.items()},
            jax.random.key(0)).jaxpr
    return values, list(_eqns(jaxpr)), counted, main, len(compiled.regions)


def _marked_vars(main):
    block = main.global_block()
    return [block.var(name) for op in block.ops
            for name in op.attrs.get('recompute_keep', ())]


@pytest.mark.parametrize('model', sorted(MARKED))
def test_the_marks_change_no_number(model):
    marked, plain = _step(model)[0], _step(model, strip=True)[0]
    assert len(marked) == len(plain) > 10
    for a, b in zip(marked, plain):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


@pytest.mark.parametrize('model', sorted(MARKED))
def test_the_backward_pass_runs_the_marked_projections_once(model):
    """The step's jaxpr holds fewer `dot_general` by a layer's mixer's
    output projection and input projections."""
    dots = [sum(e.primitive.name == 'dot_general' for e in _step(model, s)[1])
            for s in (False, True)]
    assert dots[1] - dots[0] == MARKED[model][1]


@pytest.mark.parametrize('model', sorted(MARKED))
def test_every_region_of_a_step_shares_one_policy_object(model):
    _, eqns, _, _, regions = _step(model)
    policies = [e.params['policy'] for e in eqns
                if e.primitive.name == 'remat2']
    assert len(policies) >= regions == 4
    assert all(p is step_artifact._REGION_KEEPS for p in policies)


@pytest.mark.parametrize('model', sorted(MARKED))
def test_the_trace_counts_the_marked_arrays_and_their_bytes(model):
    _, eqns, (values, nbytes), main, _ = _step(model)
    kept = _marked_vars(main)
    assert values == len(kept) == MARKED[model][2]
    assert nbytes == sum(
        4 * BATCH * int(np.prod(v.shape[1:])) for v in kept)
    names = [e for e in eqns if e.primitive.name == 'name'
             and e.params['name'] == step_artifact.REGION_KEEP]
    assert len(names) >= len(kept)
    # a kept value is an array its readers read: a barrier each in the
    # forward, in the region's second forward, where the kept array comes
    # in, and on the cotangent's way back
    barriers = sum(e.primitive.name == 'optimization_barrier' for e in eqns)
    # stripped, the Program traces none and counts nothing
    _, eqns, counted, main, _ = _step(model, strip=True)
    assert counted == [0, 0] and not _marked_vars(main)
    assert barriers - sum(e.primitive.name == 'optimization_barrier'
                          for e in eqns) == 3 * len(kept)


@pytest.mark.parametrize('model', sorted(UNMARKED))
def test_a_program_without_marks_traces_no_keep(model):
    """nemotron_h builds the mixers granitemoehybrid marks and passes no
    marker; glm4_moe_lite's regions have none either. What the name keeps
    there all the same is the third kind of kept value, an expert layer's
    route stage (ops_impl/moe_ops.py `_routed`): integers and the [E]
    vector `f`, no activation, and none of it counted as a mark."""
    _, eqns, counted, main, regions = _step(model)
    assert regions >= 3 and not _marked_vars(main)
    assert counted == [0, 0]
    kept = [e.outvars[0].aval for e in eqns if e.primitive.name == 'name'
            and e.params['name'] == step_artifact.REGION_KEEP]
    experts = [op for op in main.global_block().ops if op.type == 'moe_mlp']
    # the choice and the counts; `f` where the model reads AuxLoss
    assert 0 < 2 * len(experts) <= len(kept) <= 3 * len(experts)
    # both presets route among 16 experts
    assert all(a.dtype == 'int32' or a.shape == (16,) for a in kept)
    assert all(e.params['policy'] is step_artifact._REGION_KEEPS
               for e in eqns if e.primitive.name == 'remat2')


def _two_layers(keep):
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        with fluid.recompute_guard():
            h = layers.fc(x, 8, act='relu')
            if keep:
                fluid.recompute_keep(h)
            y = layers.fc(h, 8)
        loss = layers.mean(y)
    return main, h, loss


def test_the_mark_is_an_attr_of_the_producing_op_and_of_the_fingerprint():
    main, h, _ = _two_layers(keep=True)
    plain, _, _ = _two_layers(keep=False)
    assert h.op.attrs['recompute_keep'] == [h.name]
    assert h.op.attrs['recompute'] is not None
    assert step_artifact.program_fingerprint(main) \
        != step_artifact.program_fingerprint(plain)
    # marking again changes nothing; the Program's version moves with a
    # mark, so a step cached before it is not the step run after it
    version = main._version
    assert fluid.recompute_keep(h) is h
    assert h.op.attrs['recompute_keep'] == [h.name]
    assert main._version == version
    del h.op.attrs['recompute_keep']
    fluid.recompute_keep(h)
    assert main._version == version + 1
    assert step_artifact.program_fingerprint(main.clone()) \
        == step_artifact.program_fingerprint(main)


def test_a_mark_outside_any_region_raises_the_typed_error():
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[8], dtype='float32')
        outside = layers.fc(x, 8)
        with fluid.recompute_guard():
            inside = layers.fc(outside, 8)
        for var in (outside, x):
            with pytest.raises(fluid.RecomputeKeepError,
                               match='recompute_guard'):
                fluid.recompute_keep(var)
        # built inside: marked after the guard has closed just as well
        assert fluid.recompute_keep(inside) is inside
    assert issubclass(fluid.RecomputeKeepError, ValueError)
    assert 'recompute_keep' not in outside.op.attrs
