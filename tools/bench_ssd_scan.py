"""Times Mamba-2's selective state-space scan on the chip, alone, and
holds it to the recurrence there.

    python tools/bench_ssd_scan.py [--batch 1] [--tokens 8192] [--heads 64]
        [--head-dim 64] [--groups 8] [--state 128] [--chunk 128]
        [--iters 10] [--dtype bfloat16] [--no-recurrence]

One layer's shape of `nemotron3nano_s8192` by default. Inputs are drawn at
the scales a mixer hands the op at random weights (x, B, C of 0.4, dt the
softplus of a normal around the inverse softplus of a log-uniform step in
[0.001, 0.1], A in [-16, -1]). Prints one JSON line a measurement:

  forward, forward + backward of `linear_attention_ops.ssd_scan` (the op
  as the rule calls it; with --dtype bfloat16 its x, B, C are bf16), each
  with the required bytes and FLOPs of `chipbench/flops/nemotron_h.py`'s
  model over its time as a share of the chip's peaks;
  the largest difference, over the largest value, and the relative norm of
  the difference between the op's output (and its gradients) and the
  token-by-token recurrence of `chipbench/references/nemotron_h.py` in
  float32 at jax's highest matmul precision (which walks the tokens one
  by one: some 10 s a pass at 8192; --no-recurrence leaves it out).

Exits non-zero off the chip: a time from the CPU is no device number.
"""
import argparse
import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def inputs(args, seed=0):
    """(x, dt, a, b, c, d) in float32 at a mixer's scales."""
    rng = np.random.default_rng(seed)
    b, t, h = args.batch, args.tokens, args.heads

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), h))
    bias = jnp.asarray(step + np.log(-np.expm1(-step)), jnp.float32)
    return (normal(b, t, h, args.head_dim, scale=0.4),
            jax.nn.softplus(normal(b, t, h) + bias),
            -jnp.asarray(rng.uniform(1.0, 16.0, h), jnp.float32),
            normal(b, t, args.groups, args.state, scale=0.4),
            normal(b, t, args.groups, args.state, scale=0.4),
            jnp.ones(h, jnp.float32))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--batch', type=int, default=1)
    p.add_argument('--tokens', type=int, default=8192)
    p.add_argument('--heads', type=int, default=64)
    p.add_argument('--head-dim', type=int, default=64)
    p.add_argument('--groups', type=int, default=8)
    p.add_argument('--state', type=int, default=128)
    p.add_argument('--chunk', type=int, default=128)
    p.add_argument('--iters', type=int, default=10)
    p.add_argument('--dtype', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--no-recurrence', action='store_true')
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_ssd_scan: no TPU (%r)' % (dev,))
    from chipbench.harness import catalog, peaks
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    dtype = jnp.dtype(args.dtype)
    vals = inputs(args)
    w = jnp.asarray(np.random.default_rng(1).normal(size=vals[0].shape),
                    jnp.float32)

    def op(x, dt, a, b, c, d):
        x, b, c = (v.astype(dtype) for v in (x, b, c))
        return la.ssd_scan(x, dt, a, b, c, d, chunk_size=args.chunk)

    def loss(fn):
        return lambda *v: jnp.sum(fn(*v) * w)

    # what the recurrence requires of the chip (flops/nemotron_h.py's model)
    tokens = args.batch * args.tokens
    inner = args.heads * args.head_dim
    flops = tokens * 5 * inner * args.state
    nbytes = tokens * (2 * (2 * inner + 2 * args.groups * args.state)
                       + 4 * args.heads)
    peak = peaks.peaks_for(dev.device_kind)
    precision = 'highest' if dtype == jnp.float32 else None
    with jax.default_matmul_precision(precision) if precision \
            else contextlib.nullcontext():
        fwd = jax.jit(op)
        both = jax.jit(jax.value_and_grad(loss(op), argnums=range(6)))
        for name, fn, passes in (('forward', fwd, 1),
                                 ('forward_backward', both, 3)):
            s = _time(fn, vals, args.iters)
            least = max(passes * flops / peak['flops_per_s'],
                        passes * nbytes / peak['hbm_bytes_per_s'])
            print(json.dumps({
                'measure': name, 'dtype': args.dtype, 'tokens': tokens,
                'heads': args.heads, 'chunk': args.chunk, 'ms': 1e3 * s,
                'least_ms': 1e3 * least, 'roofline_pct': 100 * least / s,
                'device': dev.device_kind}), flush=True)
        got_y, got = fwd(*vals), both(*vals)[1]
    if args.no_recurrence:
        return
    reference = catalog.load_module(catalog.ROOT, 'references', 'nemotron_h')
    rep = args.heads // args.groups

    def plain(x, dt, a, b, c, d):
        return reference.selective_scan(x, dt, a, jnp.repeat(b, rep, 2),
                                        jnp.repeat(c, rep, 2), d)

    with jax.default_matmul_precision('highest'):
        want_y = jax.jit(plain)(*vals)
        want = jax.jit(jax.grad(loss(plain), argnums=range(6)))(*vals)
    for name, a, b in zip(('y', 'dx', 'ddt', 'da', 'db', 'dc', 'dd'),
                          (got_y,) + tuple(got), (want_y,) + tuple(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        print(json.dumps({
            'against_the_recurrence': name, 'dtype': args.dtype,
            'max_abs_over_max': float(np.abs(a - b).max() / np.abs(b).max()),
            'rel_norm': float(np.linalg.norm(a - b) / np.linalg.norm(b))}),
            flush=True)


if __name__ == '__main__':
    main()
