"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments, no switches: Transformer-base at full width is
built through the Fluid surface a user calls (models.transformer +
optimizer.Adam + amp.decorate_program + Executor(TPUPlace(0))), compiled
and stepped on host numpy batches; every Pallas kernel the repo ships is
compiled by Mosaic and checked against its own XLA reference; a toy step
is checked against the same Program on the host; and on a host with four
or more chips the same Program trains over a dp=2 x tp=2 mesh. Any failure
in any leg is an exception and a non-zero exit: nothing here turns a leg
into a "skipped" line. What was measured is printed as one `summary {...}`
line; the last line of stdout is the verdict the driver reads,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`, with
exactly those keys and the device as jax reports it.

    python3 chip_smoke.py          # on the machine with the chip

The legs are plain functions so tier-1 can call the training leg at a toy
width on CPUPlace (tests/test_chip_smoke.py); main() itself runs only on a
TPU. The compile cache lives where JAX_COMPILATION_CACHE_DIR says, else in
<checkout>/.jax_cache (paddle_tpu/utils/compile_cache.py): a second run
against the same directory reports "online_compiles": 0.
"""
import json
import math
import os
import sys
import time

import numpy as np

# Transformer-base (BASELINE.json) at 8 x 1024, the long-sequence shape
BASE = dict(vocab=30000, seq=1024, batch=8, n_layer=6, d_model=512,
            n_head=8, d_inner=2048)
TOY = dict(vocab=1000, seq=256, batch=2, n_layer=1, d_model=128, n_head=2,
           d_inner=256)
# bf16 activations: one rounding is 2^-8 of the value; a handful of them
# between the two sides of a comparison
BF16_TOL = 3e-2


def log(msg):
    print(msg, flush=True)


def device_report():
    """Print what jax found and return it in the contract's form. Exits
    non-zero, naming the platform, when it is not a TPU."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log('jax %s' % jax.__version__)
    log('devices %r' % (devs,))
    log('platform %s' % d0.platform)
    log('device_kind %s' % d0.device_kind)
    log('device_count %d' % len(devs))
    if d0.platform != 'tpu':
        raise SystemExit(
            'chip_smoke: no TPU — jax.devices()[0].platform is %r (%r); '
            'this script only runs on the chip' % (d0.platform, devs))
    return {'platform': d0.platform, 'kind': d0.device_kind,
            'count': len(devs)}


def _build(cfg, dropout):
    """Transformer + Adam + AMP through the Fluid surface, in a fresh
    scope. Returns (main, startup, avg_cost, feed_names)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.executor import Scope, _switch_scope
    from paddle_tpu.models import transformer as T
    _switch_scope(Scope())
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        avg_cost, _, feeds = T.transformer(
            cfg['vocab'], cfg['vocab'], cfg['seq'], n_layer=cfg['n_layer'],
            d_model=cfg['d_model'], n_head=cfg['n_head'],
            d_inner=cfg['d_inner'], dropout_rate=dropout)
        fluid.optimizer.Adam(learning_rate=2e-4, beta1=0.9, beta2=0.98,
                             epsilon=1e-9).minimize(avg_cost)
        fluid.amp.decorate_program(main)
    return main, startup, avg_cost, feeds


def _batch(cfg, feeds, repeat=1):
    """One seeded host batch; `repeat` stacks it so a larger global batch
    has the same per-token mean loss."""
    rng = np.random.RandomState(0)
    return {n: np.tile(rng.randint(1, cfg['vocab'],
                                   size=(cfg['batch'], cfg['seq'])),
                       (repeat, 1)).astype('int64') for n in feeds}


def _params(main):
    from paddle_tpu.fluid import framework
    return sum(int(np.prod(v.shape)) for v in main.list_vars()
               if isinstance(v, framework.Parameter))


def _run_steps(exe, main, feed, avg_cost, steps):
    """Compile on step 1, then `steps - 1` more. Returns (losses, seconds
    of the first step, seconds per later step) and asserts the contract:
    finite every step, and nothing compiles after the first."""
    t0 = time.perf_counter()
    losses = [float(np.asarray(
        exe.run(main, feed=feed, fetch_list=[avg_cost])[0]).reshape(-1)[0])]
    first_s = time.perf_counter() - t0
    after_first = dict(exe.cache_stats)
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        out, = exe.run(main, feed=feed, fetch_list=[avg_cost])
        losses.append(float(np.asarray(out).reshape(-1)[0]))
    later_s = (time.perf_counter() - t0) / max(1, steps - 1)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError('non-finite loss: %r' % (losses,))
    stats = exe.cache_stats
    for k in ('misses', 'online_compiles', 'persistent_hits'):
        if stats[k] != after_first[k]:
            raise AssertionError(
                'a step after the first compiled: %s went %r -> %r'
                % (k, after_first[k], stats[k]))
    return losses, first_s, later_s


def train_leg(place, cfg=BASE, steps=20, expect_kernel=True):
    """Leg 1: build, compile, step on one repeated seeded host batch.
    Loss finite every step and lower at the end than at the start; with
    expect_kernel the step's HLO must carry Mosaic custom calls for the
    flash forward and the one or two backward kernels of every attention
    op, the AMP step must have lowered them with bf16 operands, and some
    backward must run in one pass (at Transformer-base's lengths all do)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import obs
    main, startup, avg_cost, feeds = _build(cfg, dropout=0.1)
    exe = fluid.Executor(place)
    exe.run(startup)
    feed = _batch(cfg, feeds)

    def lowered():
        return ({d: sum(obs.counter('flash.lowered', operands=d,
                                    grid=g).value
                        for g in ('band', 'triangle', 'rect'))
                 for d in ('bfloat16', 'float32')},
                {'one': sum(obs.counter('flash.backward', passes='one',
                                        span=s).value
                            for s in ('tile', 'head')),
                 'two': obs.counter('flash.backward', passes='two').value},
                {k: obs.counter('xent.lowered', label=k).value
                 for k in ('hard', 'soft')})

    before = lowered()
    losses, first_s, later_s = _run_steps(exe, main, feed, avg_cost, steps)
    flash_lowered, flash_backward, xent_lowered = (
        {key: int(n - was[key]) for key, n in now.items()}
        for was, now in zip(before, lowered()))
    if not losses[-1] < losses[0]:
        raise AssertionError('loss did not fall: %r' % (losses,))
    if xent_lowered != {'hard': 0, 'soft': 1}:
        raise AssertionError(
            'the label-smoothed head did not lower through the closed-form '
            'cross-entropy rule once: xent.lowered counted %r'
            % (xent_lowered,))
    hlo = exe.lowered_hlo(main, feed, [avg_cost])
    n_calls = hlo.count('tpu_custom_call')
    if expect_kernel:
        # 3 attention ops a layer pair, each a forward and a backward of
        # one call or two
        want = 2 * flash_backward['one'] + 3 * flash_backward['two']
        if not flash_backward['one']:
            raise AssertionError(
                'no attention op took the one-pass backward: flash.backward '
                'counted %r' % (flash_backward,))
        if n_calls < want:
            raise AssertionError(
                'step HLO has %d tpu_custom_call(s), expected >= %d: the '
                'flash kernels did not lower through Mosaic' % (n_calls, want))
        if flash_lowered['float32'] or not flash_lowered['bfloat16']:
            raise AssertionError(
                'the AMP step did not hand its flash kernels bf16 tiles: '
                'flash.lowered counted %r' % (flash_lowered,))
    stats = exe.cache_stats
    exe.close()
    n_params = _params(main)
    log('train: params %.1fM, batch %dx%d, first step %.1fs, then %.3fs/step'
        ', loss %.4f -> %.4f, tpu_custom_call x%d, flash.lowered %r'
        ', flash.backward %r, xent.lowered %r'
        % (n_params / 1e6, cfg['batch'], cfg['seq'], first_s, later_s,
           losses[0], losses[-1], n_calls, flash_lowered, flash_backward,
           xent_lowered))
    return {'params': n_params, 'steps': steps,
            'first_step_seconds': round(first_s, 2),
            'first_loss': losses[0], 'last_loss': losses[-1],
            'tpu_custom_calls': n_calls, 'flash_lowered': flash_lowered,
            'flash_backward': flash_backward, 'xent_lowered': xent_lowered,
            'online_compiles': stats['online_compiles'],
            'persistent_hits': stats['persistent_hits'],
            'cache_dir': stats['compile_cache_dir']}


def reference_leg(cfg=TOY):
    """One step of the SAME toy Program on TPUPlace(0) (flash kernels) and
    on CPUPlace (XLA reference chain), same seed, dropout off: the two
    losses must agree within bf16 tolerance."""
    import paddle_tpu.fluid as fluid
    got = {}
    for name, place in (('tpu', fluid.TPUPlace(0)), ('cpu', fluid.CPUPlace())):
        main, startup, avg_cost, feeds = _build(cfg, dropout=0.0)
        exe = fluid.Executor(place)
        exe.run(startup)
        out, = exe.run(main, feed=_batch(cfg, feeds), fetch_list=[avg_cost])
        got[name] = float(np.asarray(out).reshape(-1)[0])
        exe.close()
    rel = abs(got['tpu'] - got['cpu']) / abs(got['cpu'])
    log('reference: toy step loss tpu %.5f vs host %.5f (rel %.2e)'
        % (got['tpu'], got['cpu'], rel))
    if not rel <= BF16_TOL:
        raise AssertionError('TPU step disagrees with the host reference: %r'
                             % (got,))
    return {'tpu_loss': got['tpu'], 'host_loss': got['cpu']}


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def _check_flash(causal, shape=(8, 8, 1024, 64)):
    """Flash forward + gradients at (8, 8, 1024, 64) bf16 with a pad bias
    (one tile a head: the one-pass backward) or at (2, 8, 2048, 64) (four
    tiles a side: not causal the two backward kernels on the rectangular
    grid, causal the one pass over the head's triangle, batch 2 under a
    pad bias), against reference_attention."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    r = np.random.RandomState(1)
    B, H, T, D = shape
    q, k, v, w = [jnp.asarray(r.randn(B, H, T, D), jnp.bfloat16)
                  for _ in range(4)]
    kb = np.zeros((B, T), np.float32)
    kb[:, T - 100:] = -1e9                      # padded tail keys
    kb = jnp.asarray(kb)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def kern(q, k, v):
        return ops.flash_attention(q, k, v, key_bias=kb, causal=causal,
                                   interpret=False)

    def ref(q, k, v):
        return ops.reference_attention(q, k, v, key_bias=kb, causal=causal)

    errs = [_rel_err(jax.jit(kern)(q, k, v), jax.jit(ref)(q, k, v))]
    g_k = jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(q, k, v)
    g_r = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    errs += [_rel_err(a, b) for a, b in zip(g_k, g_r)]
    return max(errs), BF16_TOL


def _check_paged_attention():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kernels
    r = np.random.RandomState(2)
    C, beam, D, ps, NPE, Pe = 8, 8, 128, 128, 4, 64
    q = jnp.asarray(r.randn(C * beam, D), jnp.float32)
    pages = jnp.asarray(r.randn(Pe, ps, D), jnp.float32)
    mask = jnp.asarray((r.rand(Pe, ps) > 0.2).astype(np.float32))
    pt = jnp.asarray(r.permutation(Pe)[:C * NPE].reshape(C, NPE), jnp.int32)
    cap = NPE * ps - 5
    got = jax.jit(lambda *a: kernels.paged_attention(
        *a, cap, interpret=False))(q, pages, mask, pt)
    with jax.default_matmul_precision('highest'):
        want = jax.jit(lambda *a: kernels.paged_attention_reference(
            *a, cap))(q, pages, mask, pt)
    # f32 in, f32 out — but each side picks its own number of bf16 MXU
    # passes, so the bound is the bf16 one
    return _rel_err(got, want), BF16_TOL


def _sparse_inputs():
    import jax.numpy as jnp
    r = np.random.RandomState(3)
    V, N, D, n_valid = 4096, 256, 128, 200
    uids = np.zeros(N, np.int32)
    uids[:n_valid] = np.sort(r.permutation(V)[:n_valid])
    valid = (np.arange(N) < n_valid).astype(np.int32)
    gm = r.randn(N, D).astype(np.float32) * valid[:, None]
    tables = [jnp.asarray(np.abs(r.randn(V, D)).astype(np.float32) + 0.1)
              for _ in range(3)]
    return (tables, jnp.asarray(uids), jnp.asarray(gm), jnp.asarray(valid),
            jnp.float32(0.05))


def _check_sparse_adagrad():
    import jax
    from paddle_tpu.ops import kernels
    (p, m, _), uids, gm, valid, lr = _sparse_inputs()
    got = jax.jit(lambda *a: kernels.fused_sparse_adagrad(
        *a, 1e-6, interpret=False))(p, m, uids, gm, valid, lr)
    want = jax.jit(lambda *a: kernels.sparse_adagrad_reference(
        *a, 1e-6))(p, m, uids, gm, valid, lr)
    return max(_rel_err(a, b) for a, b in zip(got, want)), 1e-5


def _check_sparse_adam():
    import jax
    from paddle_tpu.ops import kernels
    (p, m1, m2), uids, gm, valid, lr = _sparse_inputs()
    hyper = (0.9, 0.999, 1e-8)
    got = jax.jit(lambda *a: kernels.fused_sparse_adam(
        *a, *hyper, interpret=False))(p, m1, m2, uids, gm, valid, lr)
    want = jax.jit(lambda *a: kernels.sparse_adam_reference(
        *a, *hyper))(p, m1, m2, uids, gm, valid, lr)
    return max(_rel_err(a, b) for a, b in zip(got, want)), 1e-5


def _check_grouped_matmul():
    """The dropless expert layer's kernel, forward and both gradients at
    (8192 rows, 16 uneven groups, 1024 -> 512) bf16, against
    lax.ragged_dot."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels.grouped_matmul import grouped_matmul
    r = np.random.RandomState(4)
    m, k, n, groups = 8192, 1024, 512, 16
    lhs = jnp.asarray(r.randn(m, k), jnp.bfloat16)
    rhs = jnp.asarray(r.randn(groups, k, n), jnp.bfloat16)
    w = jnp.asarray(r.randn(m, n), jnp.float32)
    cuts = np.sort(r.randint(0, m, size=groups - 1))
    sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [m]])), jnp.int32)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b).astype(jnp.float32) * w)

    def kern(a, b):
        return grouped_matmul(a, b, sizes, False)

    def ref(a, b):
        return jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=jnp.float32).astype(a.dtype)

    errs = [_rel_err(jax.jit(kern)(lhs, rhs), jax.jit(ref)(lhs, rhs))]
    g_k = jax.jit(jax.grad(loss(kern), argnums=(0, 1)))(lhs, rhs)
    g_r = jax.jit(jax.grad(loss(ref), argnums=(0, 1)))(lhs, rhs)
    errs += [_rel_err(a, b) for a, b in zip(g_k, g_r)]
    return max(errs), BF16_TOL


def _check_row_add():
    """A held share's add of its laid-out rows to their tokens, with the
    gates and as the row gather's transpose, at (4096 tokens x 4, 8 held
    experts, a layout of 8192 rows of 2048 with 3000 live) bf16, against
    the scatter-add."""
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops_impl import moe_ops
    r = np.random.RandomState(5)
    n, k, held, cap, d, live = 4096, 4, 8, 8192, 2048, 3000
    flat = np.full(n * k, held, np.int32)
    flat[r.choice(n * k, size=live, replace=False)] = r.randint(
        0, held, size=live)
    key = jnp.asarray(flat.reshape(n, k))
    src = moe_ops._argsort(key.reshape(-1), held + 1)[:cap]
    rows = moe_ops._keep(live)(jnp.asarray(r.randn(cap, d), jnp.bfloat16))
    gate = jnp.asarray(r.rand(cap, 1), jnp.float32)
    kernel = moe_ops._index(src, key, held, d)
    scatter = moe_ops._index(src, key, held)
    return max(_rel_err(moe_ops._add_up(rows, g, kernel, n, False),
                        moe_ops._add_up(rows, g, scatter, n, None))
               for g in (gate, None)), 1e-5


KERNEL_CHECKS = {
    'flash_attention': lambda: _check_flash(False),
    'flash_attention_causal': lambda: _check_flash(True),
    'flash_attention_2048': lambda: _check_flash(False, (2, 8, 2048, 64)),
    'flash_attention_causal_2048': lambda: _check_flash(True,
                                                        (2, 8, 2048, 64)),
    'paged_attention': _check_paged_attention,
    'sparse_adagrad': _check_sparse_adagrad,
    'sparse_adam': _check_sparse_adam,
    'grouped_matmul': _check_grouped_matmul,
    'row_add': _check_row_add,
}


def kernel_leg():
    """Compile every Pallas kernel the repo ships with Mosaic
    (interpret=False) at a production-aligned shape and compare it with
    its XLA reference. A registered kernel with no check here is an error:
    none ships that the chip has not accepted."""
    from paddle_tpu.ops import kernels
    missing = set(kernels.available()) - set(KERNEL_CHECKS)
    if missing:
        raise AssertionError('registered kernels without a chip check: %r'
                             % sorted(missing))
    out = {}
    for name, check in KERNEL_CHECKS.items():
        t0 = time.perf_counter()
        err, tol = check()
        log('kernel %-24s rel err %.2e (tol %.0e)  %.1fs'
            % (name, err, tol, time.perf_counter() - t0))
        if not err <= tol:
            raise AssertionError('%s: rel err %.3e exceeds %.0e'
                                 % (name, err, tol))
        out[name] = err
    return out


def _custom_call_shapes(hlo):
    """{result-shape text: count} of the tpu_custom_call instructions in a
    compiled (partitioned) HLO module, plus its collective counts."""
    import collections
    import re
    shapes = collections.Counter()
    for line in hlo.splitlines():
        if 'tpu_custom_call' in line and ' custom-call(' in line:
            m = re.search(r'=\s*(.*?)\s+custom-call\(', line)
            # result types without their {layout} suffixes
            shapes[re.sub(r'\{[^}]*\}', '', m.group(1)) if m else '?'] += 1
    coll = {op: len(re.findall(r'\s%s(?:-start)?\(' % op, hlo))
            for op in ('all-gather', 'all-reduce', 'reduce-scatter',
                       'all-to-all', 'collective-permute')}
    return dict(shapes), coll


def mesh_leg(place, one_chip_first_loss, cfg=BASE, steps=3):
    """Leg 3 (four or more devices): the same Program under
    DistributeTranspiler(trainers=2) + TensorParallelTranspiler(tp=2),
    global batch 2 x cfg['batch'] (the one-chip batch twice, so the
    per-token mean loss is the one-chip loss)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import global_scope
    main, startup, avg_cost, feeds = _build(cfg, dropout=0.1)
    fluid.DistributeTranspiler().transpile(trainer_id=0, trainers=2,
                                           program=main)
    fluid.TensorParallelTranspiler(tp=2).transpile(main)
    exe = fluid.Executor(place)
    exe.run(startup)
    feed = _batch(cfg, feeds, repeat=2)
    losses, first_s, later_s = _run_steps(exe, main, feed, avg_cost, steps)
    mesh = main._dist_mesh
    if dict(mesh.shape) != {'dp': 2, 'tp': 2}:
        raise AssertionError('asked for dp=2 x tp=2, executor built %r'
                             % (dict(mesh.shape),))
    devs = list(mesh.devices.flat)
    if place.jax_device() not in devs:
        raise AssertionError('mesh %r does not hold the place %r'
                             % (devs, place))
    # parameters: every device holds data, and some of it is a strict slice
    scope = global_scope()
    held = {d: 0 for d in devs}
    sliced = {d: 0 for d in devs}
    for v in main.list_vars():
        arr = scope.vars.get(v.name) if v.persistable else None
        if not isinstance(arr, jax.Array):
            continue
        for s in arr.addressable_shards:
            held[s.device] += 1
            sliced[s.device] += s.data.shape != arr.shape
    # the batch, as the Executor itself places it
    placed = exe._place_feed(main, feed, mesh)
    batch_rows = {d: 0 for d in devs}
    for s in placed[feeds[0]].addressable_shards:
        batch_rows[s.device] += s.data.shape[0]
    mem = {d: (d.memory_stats() or {}).get('bytes_in_use', 0) for d in devs}
    for d in devs:
        log('mesh device %s: %d arrays (%d sliced), %d batch rows, '
            '%.2f GiB in use' % (d, held[d], sliced[d], batch_rows[d],
                                 mem[d] / 2 ** 30))
        if not (sliced[d] and batch_rows[d] == cfg['batch'] and mem[d] > 0):
            raise AssertionError('device %s holds no shard of the parameters'
                                 ', of the batch, or no memory' % (d,))
    rel = abs(losses[0] - one_chip_first_loss) / abs(one_chip_first_loss)
    if not rel <= BF16_TOL:
        raise AssertionError(
            'mesh first-step loss %.5f vs one chip %.5f (rel %.2e)'
            % (losses[0], one_chip_first_loss, rel))
    # what the partitioner left around the Mosaic calls: each must work on
    # its own (batch / dp, heads / tp) shard, not on gathered operands
    shapes, coll = _custom_call_shapes(
        exe.lowered_hlo(main, feed, [avg_cost], optimized=True))
    per_shard = '[%d,%d,%d,' % (cfg['batch'], cfg['n_head'] // 2, cfg['seq'])
    if not shapes or not all(per_shard in s for s in shapes):
        raise AssertionError(
            'flash calls in the partitioned step are not per shard '
            '(want results shaped %s...]): %r' % (per_shard, shapes))
    remat = exe.cache_stats['remat_detected']
    exe.close()
    log('mesh: dp=2 x tp=2, batch %dx%d, first step %.1fs, then %.3fs/step, '
        'loss %.4f -> %.4f (one chip %.4f, rel %.2e)'
        % (2 * cfg['batch'], cfg['seq'], first_s, later_s, losses[0],
           losses[-1], one_chip_first_loss, rel))
    log('mesh step custom-call results %r, collectives %r, involuntary '
        'remat %d' % (shapes, coll, remat))
    return {'mesh': dict(mesh.shape), 'steps': steps,
            'first_step_seconds': round(first_s, 2),
            'first_loss': losses[0], 'last_loss': losses[-1],
            'custom_calls': shapes, 'collectives': coll,
            'remat_detected': remat}


def main():
    t_start = time.perf_counter()
    # ask for the TPU by name before any backend exists, so that a failed
    # libtpu start-up raises instead of handing back the host; an explicit
    # JAX_PLATFORMS is the operator's and is reported, then refused
    os.environ.setdefault('JAX_PLATFORMS', 'tpu,cpu')
    device = device_report()
    init_s = time.perf_counter() - t_start
    import paddle_tpu.fluid as fluid
    from paddle_tpu.utils import compile_cache, native
    cache_dir = compile_cache.enable()
    log('backend init %.1fs; compile cache %s; native reader library %s'
        % (init_s, cache_dir,
           'built' if native.available() else 'unavailable (pure Python)'))

    place = fluid.TPUPlace(0)
    train = train_leg(place)
    reference = reference_leg()
    kernels = kernel_leg()
    summary = {'backend_init_seconds': round(init_s, 2),
               'train': train, 'reference': reference,
               'kernels_compiled': sorted(kernels),
               'native_available': bool(native.available())}
    if device['count'] >= 4:
        summary['mesh'] = mesh_leg(place, train['first_loss'])
    summary['seconds'] = round(time.perf_counter() - t_start, 1)
    log('summary ' + json.dumps(summary))
    # every leg raised on failure, so reaching this line is the verdict
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    sys.exit(main())
