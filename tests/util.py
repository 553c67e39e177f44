"""Shared test helpers: fresh programs per test, an op's inputs as
parameters and a small Program run forward and backward on them, a jax
function's output and gradients in one compile, a held
expert layer's paths forced or poisoned, the flash backward's schedule
counters."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, layers, unique_name
from paddle_tpu.fluid.executor import Scope, _switch_scope


@contextlib.contextmanager
def fresh_program():
    """Isolated main/startup program + scope + name generator."""
    main = framework.Program()
    startup = framework.Program()
    scope = Scope()
    prev_scope = _switch_scope(scope)
    with unique_name.guard():
        with framework.program_guard(main, startup):
            try:
                yield main, startup
            finally:
                _switch_scope(prev_scope)


def input_parameter(name, value):
    """A Program input whose gradient append_backward returns: a parameter
    initialised to `value`."""
    return layers.create_parameter(
        list(value.shape), 'float32', name=name,
        default_initializer=fluid.initializer.NumpyArrayInitializer(
            np.asarray(value)))


def grads_of(build, feed, wrt, amp=False, optimized=False):
    """Runs a small Program forward and backward; returns (out, grads,
    the lowered text)."""
    main, startup = framework.Program(), framework.Program()
    with unique_name.guard(), framework.program_guard(main, startup):
        out = build()
        loss = layers.reduce_sum(layers.elementwise_mul(
            out, layers.data(name='w', shape=list(out.shape),
                             dtype='float32', append_batch_size=False)))
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
        if amp:
            fluid.amp.decorate_program(main)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetch = [out] + [grads[n] for n in wrt]
        res = exe.run(main, feed=feed, fetch_list=fetch)
        text = exe.lowered_hlo(main, feed, fetch, optimized=optimized)
    return res[0], res[1:], text


def out_and_grads(fn, args, weight):
    """fn(*args) and the gradient of sum(fn(*args) * weight) to every
    argument: one trace and ONE compile, not an op-by-op walk."""
    def loss(*a):
        out = fn(*a)
        return jnp.sum(out * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(len(args)), has_aux=True))(*args)
    return out, grads


def nan_path(params, x, *_):
    """In place of the path of a held expert layer (ops_impl/moe_ops.py
    `_compact_moe`, `_held_blocks`) that a test expects the device NOT to
    take."""
    return jnp.full((x.shape[0], params['w2'].shape[-1]), jnp.nan,
                    jnp.float32)


def held_way(monkeypatch, way):
    """A held expert layer under its layout takes the compact path with
    the blocks poisoned (`compact`) or `_held_blocks` in the compact
    path's place (`blocks`); one over its layout takes the blocks with
    the compact path poisoned (`overflow`)."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    blocks = moe_ops._held_blocks
    if way == 'blocks':
        monkeypatch.setattr(
            moe_ops, '_compact_moe',
            lambda p, x, key, gate, sizes, cap, act, ctx:
            blocks(p, x, key, gate, act, ctx))
    else:
        monkeypatch.setattr(moe_ops, '_held_blocks' if way == 'compact'
                            else '_compact_moe', nan_path)


def flash_schedules():
    """Attention calls by the backward's schedule as the lowering counts
    them (`flash.backward`): one pass over a 'tile' or over a 'head', or
    'two' passes."""
    from paddle_tpu import obs
    return {'tile': obs.counter('flash.backward', passes='one',
                                span='tile').value,
            'head': obs.counter('flash.backward', passes='one',
                                span='head').value,
            'two': obs.counter('flash.backward', passes='two').value}
