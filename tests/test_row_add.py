"""The add of a held share's rows to their tokens (`moe_ops._add_up`) as
the Pallas kernel of ops/kernels/row_add.py, in the interpreter, against
the scatter-add it replaces on the TPU; the plan that walks the live rows
only; the rule's choice between the two and its counter."""
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import obs
from paddle_tpu.fluid.ops_impl import moe_ops
from paddle_tpu.ops.kernels import row_add

# tokens, top k, held experts: two tiles of 256 tokens, and a layout of
# eight chunks of 128 rows, twice the rows a quarter share expects
N, K, HELD = 512, 4, 4
CAP = 1024
# live rows as a share of the expected N x K / 4, and the edges: none, one
# row, a range that ends inside a chunk, every assignment of the first
# tile's tokens (K rows a token, the second tile empty)
LOADS = {'none': 0, 'one': 1, 'expected': 512, 'half_more': 768,
         'off_a_chunk': 517, 'full': CAP}


def _layout(load, rng):
    """(key [N, K], src [cap], live) as `_compact_moe` has them: each
    assignment's held expert or HELD, and the laid-out assignments'
    positions among the token-major N x K, sorted by expert and an
    expert's by position, `live` of them held and the rest (expert HELD,
    any position) after them."""
    flat = np.full(N * K, HELD, np.int32)
    if load == 'first_tile':
        live = 256 * K
        flat[:live] = rng.integers(0, HELD, size=live)
    else:
        live = LOADS[load]
        at = rng.choice(N * K, size=live, replace=False)
        flat[at] = rng.integers(0, HELD, size=live)
    key = jnp.asarray(flat.reshape(N, K))
    src = moe_ops._argsort(key.reshape(-1), HELD + 1)[:CAP]
    return key, src, live


def _operands(rng, live, width, dtype):
    rows = moe_ops._keep(live)(
        jnp.asarray(rng.normal(size=(CAP, width)), dtype))
    gate = jnp.asarray(rng.uniform(0.05, 1.0, size=(CAP, 1)), jnp.float32)
    return rows, gate


@pytest.mark.parametrize('load', list(LOADS) + ['first_tile'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('width', [2048, 2560, 2688])
def test_the_kernel_adds_what_the_scatter_adds(width, dtype, load):
    """At the held cells' widths, in the step's bf16 and the checks'
    float32, at every load of the layout, with the gates and without (the
    forward's add and the transpose's): the kernel's sum a token is the
    scatter's (the order of a token's few float32 adds and a product's
    rounding may differ) and numpy's, and a token with no live row gets
    zeros."""
    rng = np.random.default_rng(len(load) + width)
    key, src, live = _layout(load, rng)
    assert row_add.usable(CAP, N, width, dtype)
    rows, gate = _operands(rng, live, width, jnp.dtype(dtype))
    kernel = moe_ops._index(src, key, HELD, width)
    scatter = moe_ops._index(src, key, HELD)
    token = np.asarray(src)[:live] // K
    for g in (gate, None):
        got = np.asarray(moe_ops._add_up(rows, g, kernel, N, True))
        ref = np.asarray(moe_ops._add_up(rows, g, scatter, N, None))
        want = np.zeros((N, width), np.float32)
        np.add.at(want, token, (np.asarray(rows.astype(jnp.float32))
                                * (1.0 if g is None else np.asarray(g))
                                )[:live])
        assert got.dtype == np.float32 and got.shape == (N, width)
        np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        empty = np.setdiff1d(np.arange(N), token)
        assert len(empty) or load == 'full'
        assert not got[empty].any()
        assert live == 0 or np.abs(got).max() > 0.5


@pytest.mark.parametrize('load', ['none', 'expected', 'full'])
def test_the_plan_lists_the_live_chunks_of_each_tile(load):
    """The plan alone: every tile has a step, the tiles come in order, a
    step's range lies inside its chunk's reach and inside the live rows,
    the ranges of a tile hold each of its rows once, and the steps never
    pass the static length."""
    rng = np.random.default_rng(3)
    key, src, live = _layout(load, rng)
    t, r = row_add.tiles(2048)
    tile, chunk, lo, hi, steps = (np.asarray(a) for a in
                                  row_add.plan(key, HELD, CAP, 2048))
    steps = int(steps[0])
    assert len(tile) == N // t * HELD + CAP // r and 0 < steps <= len(tile)
    assert list(np.unique(tile[:steps])) == list(range(N // t))
    assert (np.diff(tile[:steps]) >= 0).all()
    # past the last step: the last step's blocks again
    assert (tile[steps:] == tile[steps - 1]).all()
    assert (chunk[steps:] == chunk[steps - 1]).all()
    token = np.asarray(src) // K
    seen = np.zeros(CAP, int)
    for w in range(steps):
        assert 0 <= lo[w] <= hi[w] <= live
        a, b = max(lo[w], chunk[w] * r), min(hi[w], (chunk[w] + 1) * r)
        if lo[w] < hi[w]:
            assert a < b            # a listed chunk holds rows of the range
            assert (token[a:b] // t == tile[w]).all()
            seen[a:b] += 1
    assert (seen[:live] == 1).all() and not seen[live:].any()


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_both_moves_differentiate_as_the_scatter_does(dtype):
    """`jax.grad` through `_lay_out` (whose transpose is the add) and
    through `_add_up` (whose transpose is the gather; the gates' gradient
    is the rows' product with it), the kernel's way against the
    scatter's."""
    width = 256
    rng = np.random.default_rng(11)
    key, src, live = _layout('half_more', rng)
    keep = moe_ops._keep(live)
    rows, gate = _operands(rng, live, width, jnp.dtype(dtype))
    x = jnp.asarray(rng.normal(size=(N, width)), jnp.dtype(dtype))
    w_rows = jnp.asarray(rng.normal(size=(CAP, width)), jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(N, width)), jnp.float32)

    def grads(at, interpret):
        def laid(x):
            got = keep(moe_ops._lay_out(x, at, interpret))
            return jnp.sum(got.astype(jnp.float32) * w_rows)

        def added(rows, gate):
            return jnp.sum(moe_ops._add_up(keep(rows), gate, at, N,
                                           interpret) * w_out)

        return (jax.grad(laid)(x),) + jax.grad(added, argnums=(0, 1))(
            rows, gate)

    kernel = grads(moe_ops._index(src, key, HELD, width), True)
    scatter = grads(moe_ops._index(src, key, HELD), None)
    for got, ref, like in zip(kernel, scatter, (x, rows, gate)):
        assert got.dtype == like.dtype and got.shape == like.shape
        ref = np.asarray(ref.astype(jnp.float32))
        assert np.abs(ref).max() > 0
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), ref,
                                   rtol=1e-5, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize('way', ['scatter', 'kernel'])
def test_the_compact_path_is_the_blocks_path(way, monkeypatch):
    """`_compact_moe` on a layer's keys against `_held_blocks` on the same
    keys, forward and gradient, with the add each way (the kernel in the
    interpreter)."""
    width, hidden = 128, 64
    monkeypatch.setattr(moe_ops, '_add_kernel',
                        lambda *a: way == 'kernel')
    ctx = types.SimpleNamespace(platform='cpu', pallas_interpret=True)
    rng = np.random.default_rng(7)
    key, _, live = _layout('half_more', rng)
    sizes = jnp.bincount(key.reshape(-1), length=HELD + 1)[:HELD].astype(
        jnp.int32)
    assert int(sizes.sum()) == live
    params = {k: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for k, s in (('w1', (HELD, width, hidden)),
                           ('w3', (HELD, width, hidden)),
                           ('w2', (HELD, hidden, width)))}
    x = jnp.asarray(rng.normal(size=(N, width)), jnp.float32)
    gate = jnp.asarray(rng.uniform(size=(N, K)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(N, width)), jnp.float32)

    def compact(params, x, gate):
        return jnp.sum(weight * moe_ops._compact_moe(
            params, x, key, gate, sizes, CAP, 'swish', ctx))

    def blocks(params, x, gate):
        return jnp.sum(weight * moe_ops._held_blocks(
            params, x, key, gate, 'swish', ctx))

    got, ref = (jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        params, x, gate) for f in (compact, blocks))
    for a, b in zip(*(jax.tree_util.tree_leaves(t) for t in (got, ref))):
        assert np.abs(b).max() > 0
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max())
    # the kernel's way is one Mosaic call an add: forward, and the
    # transpose in the backward pass
    text = str(jax.make_jaxpr(jax.grad(compact, argnums=(0, 1, 2)))(
        params, x, gate))
    assert len(re.findall(r'jit\[\s*name=row_add\b', text)) == (
        2 if way == 'kernel' else 0)
    assert way == 'kernel' or 'scatter-add' in text


@pytest.mark.parametrize('cap,n,d,dtype,takes', [
    (49152, 16384, 2560, 'bfloat16', True),    # smallthinker_s16384
    (32768, 16384, 2048, 'bfloat16', True),    # lfm2_s16384
    (25600, 8192, 2048, 'bfloat16', True),     # qwen3next_s8192
    (24576, 8192, 2688, 'float32', True),      # nemotron3nano_s8192: 21
                                               # lane tiles, its check's rows
    (16384, 8192, 2048, 'float32', True),      # glm47flash_s8192
    (16384, 8192, 2000, 'bfloat16', False),    # no whole lane tiles
    (16392, 8192, 2048, 'bfloat16', False),    # no whole chunks
    (16384, 8256, 2048, 'bfloat16', False),    # no whole tiles of tokens
    (16384, 8192, 2048, 'float16', False),     # neither bf16 nor float32
    (16384, 8192, 1 << 20, 'bfloat16', False),  # no tile's block fits VMEM
], ids=lambda v: str(v))
def test_usable(cap, n, d, dtype, takes):
    assert row_add.usable(cap, n, d, dtype) is takes
    t, r = row_add.tiles(d)
    assert t % 8 == 0 and r % 128 == 0
    if takes:
        # the call's blocks, within the default 16 MiB of scoped VMEM
        assert row_add._held(t, r, d) <= 12 << 20


@pytest.mark.parametrize('platform,width,way', [
    ('tpu', 256, 'kernel'), ('cpu', 256, 'scatter'), ('tpu', 200, 'scatter'),
])
def test_the_rule_counts_its_choice_once_a_call_site(platform, width, way,
                                                     monkeypatch):
    """`_held_moe` chooses by the platform and the kernel's `usable`, and
    counts `moe.add{way=}` once for each of the layout's two adds, a trace
    of the rule; a layer without a layout counts nothing."""
    seen = []
    monkeypatch.setattr(
        moe_ops, 'traced_once', lambda ctx, fn, cap, act:
        lambda params, x, *a: seen.append(cap) or jnp.zeros(
            x.shape, jnp.float32))
    ctx = types.SimpleNamespace(platform=platform,
                                pallas_interpret=platform != 'tpu')

    def trace(tokens):
        x = jnp.zeros((tokens, width), jnp.bfloat16)
        expert = jnp.zeros((tokens, K), jnp.int32)
        moe_ops._held_moe({}, x, expert, jnp.ones((tokens, K)),
                          jnp.zeros((16,), jnp.int32), (0, HELD), 'relu',
                          ctx)

    counts = {w: obs.counter('moe.add', way=w) for w in ('kernel', 'scatter')}
    before = {w: c.value for w, c in counts.items()}
    trace(N)                      # a layout of half the rows
    assert seen == [CAP]
    after = {w: c.value - before[w] for w, c in counts.items()}
    assert after == {w: 2 * (w == way) for w in counts}
    trace(64)                     # half the rows are under one tile
    assert seen == [CAP, None]
    assert {w: c.value - before[w] for w, c in counts.items()} == after
