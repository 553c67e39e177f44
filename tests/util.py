"""Shared test helpers: fresh programs per test, a held expert layer's
paths forced or poisoned, the flash backward's schedule counters."""
import contextlib

import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
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
