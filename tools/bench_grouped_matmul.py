"""Times the grouped matmuls of a dropless expert layer on the chip, alone.

    python tools/bench_grouped_matmul.py [--rows 65536] [--experts 64]
        [--d 2048] [--hidden 1024] [--iters 20]

Three implementations of `[rows, k] x [experts, k, n]` over ragged groups
of rows, forward plus backward (gradients of both operands), bf16 in and
float32 accumulation, at an even split and at a skewed one:

  dense     einsum over [experts, rows / experts, k]: the same FLOPs with no
            raggedness, the yardstick ISSUE 26 names (even split only)
  ragged    jax.lax.ragged_dot, what ops_impl/moe_ops.py lowers to
  kernel    paddle_tpu.ops.kernels.grouped_matmul (megablox's Pallas gmm and
            tgmm, one tile a call)

for both shapes an expert layer multiplies (d -> hidden, hidden -> d).
`--sweep` instead times the kernel's three calls ALONE (forward, gradient
of the rows, gradient of the stack) over candidate tiles, skewed split.
Prints one JSON line a measurement. Exits non-zero off the chip: a time
from the CPU is no device number.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sizes(rows, experts, skew, seed=0):
    if not skew:
        return np.full(experts, rows // experts, np.int32)
    p = 1.0 / np.arange(1, experts + 1) ** skew
    rng = np.random.default_rng(seed)
    got = rng.multinomial(rows, p / p.sum()).astype(np.int32)
    return rng.permutation(got)


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _fwd_bwd(matmul):
    def loss(lhs, rhs, sizes):
        return jnp.sum(matmul(lhs, rhs, sizes).astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1)))


# `fwd`/`dlhs` want the whole contraction in a tile, `drhs` (tgmm) keeps a
# [tk, tn] float32 accumulator and runs out of VMEM above 1024 x 1024
CANDIDATES = {
    'fwd': [(tm, tk, tn) for tm in (256, 512) for tk in (1024, 2048)
            for tn in (1024, 2048)],
    'drhs': [(512, 512, 512), (512, 1024, 512), (512, 512, 1024),
             (1024, 512, 512), (1024, 512, 1024), (256, 1024, 1024)],
}
CANDIDATES['dlhs'] = CANDIDATES['fwd']


def sweep(args, gm, dev):
    """Each of the three calls alone, each candidate tile, both shapes."""
    mblx = gm._megablox
    key = jax.random.key(0)
    sizes = jnp.asarray(_sizes(args.rows, args.experts, 1.0))
    for k, n in ((args.d, args.hidden), (args.hidden, args.d)):
        lhs = jax.random.normal(key, (args.rows, k), jnp.bfloat16)
        rhs = jax.random.normal(key, (args.experts, k, n), jnp.bfloat16)
        g = jax.random.normal(key, (args.rows, n), jnp.bfloat16)
        # operands as arguments: closed over, they would be compiled into
        # each candidate's executable as constants (half a minute each)
        calls = {
            'fwd': (lambda t: jax.jit(lambda a, b, c: mblx.gmm(
                a, b, sizes, a.dtype, gm._fit(t, args.rows, k, n, 2))),
                (lhs, rhs, g)),
            'dlhs': (lambda t: jax.jit(lambda a, b, c: mblx.gmm(
                c, b, sizes, a.dtype, gm._fit(t, args.rows, n, k, 2),
                transpose_rhs=True)), (lhs, rhs, g)),
            'drhs': (lambda t: jax.jit(lambda a, b, c: mblx.tgmm(
                a.swapaxes(0, 1), c, sizes, b.dtype,
                gm._fit(t, args.rows, k, n, 2),
                num_actual_groups=args.experts)), (lhs, rhs, g)),
        }
        for call, (make, operands) in calls.items():
            if call not in args.calls:
                continue
            for t in CANDIDATES[call]:
                try:
                    s, err = _time(make(t), operands, args.iters), None
                except Exception as e:              # noqa: BLE001
                    s, err = None, type(e).__name__ + ': ' + str(e)[:120]
                print(json.dumps({
                    'call': call, 'k': k, 'n': n, 'rows': args.rows,
                    'tile': t, 'ms': None if s is None else 1e3 * s,
                    'tflops': None if s is None
                    else 2.0 * args.rows * k * n / s / 1e12,
                    'error': err, 'device': dev.device_kind}), flush=True)
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--rows', type=int, default=65536)
    p.add_argument('--experts', type=int, default=64)
    p.add_argument('--d', type=int, default=2048)
    p.add_argument('--hidden', type=int, default=1024)
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--sweep', action='store_true')
    p.add_argument('--calls', default='fwd,dlhs,drhs',
                   type=lambda v: v.split(','))
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_grouped_matmul: no TPU (%r)' % (dev,))
    from paddle_tpu.ops.kernels import grouped_matmul as gm
    if args.sweep:
        return sweep(args, gm, dev)

    def dense(lhs, rhs, sizes):
        e = rhs.shape[0]
        out = jnp.einsum('eck,ekn->ecn', lhs.reshape(e, -1, lhs.shape[-1]),
                         rhs, preferred_element_type=jnp.float32)
        return out.reshape(lhs.shape[0], -1).astype(lhs.dtype)

    def ragged(lhs, rhs, sizes):
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)

    def kernel(lhs, rhs, sizes):
        return gm.grouped_matmul(lhs, rhs, sizes, False)

    impls = {'dense': dense, 'ragged': ragged, 'kernel': kernel}
    key = jax.random.key(0)
    for k, n in ((args.d, args.hidden), (args.hidden, args.d)):
        lhs = jax.random.normal(key, (args.rows, k), jnp.bfloat16)
        rhs = jax.random.normal(key, (args.experts, k, n), jnp.bfloat16)
        flops = 3 * 2.0 * args.rows * k * n
        for skew in (0.0, 1.0):
            sizes = jnp.asarray(_sizes(args.rows, args.experts, skew))
            for name, fn in impls.items():
                if name == 'dense' and skew:
                    continue
                try:
                    s = _time(_fwd_bwd(fn), (lhs, rhs, sizes), args.iters)
                    err = None
                except Exception as e:              # noqa: BLE001
                    s, err = None, '%s: %s' % (type(e).__name__,
                                               str(e)[:300])
                print(json.dumps({
                    'impl': name, 'k': k, 'n': n, 'rows': args.rows,
                    'experts': args.experts, 'skew': skew,
                    'largest_group': int(sizes.max()),
                    'ms_fwd_bwd': None if s is None else 1e3 * s,
                    'tflops': None if s is None else flops / s / 1e12,
                    'error': err, 'device': dev.device_kind}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
