"""Times Mamba-2's selective state-space scan on the chip, alone, and
holds it to the recurrence there.

    python tools/bench_ssd_scan.py [--batch 1] [--tokens 8192] [--heads 64]
        [--head-dim 64] [--groups 8] [--state 128] [--chunk 128]
        [--iters 10] [--dtype bfloat16] [--no-recurrence]
        [--way kernel|composed|both] [--sweep]

One layer's shape of `nemotron3nano_s8192` by default. Inputs are drawn at
the scales a mixer hands the op at random weights (x, B, C of 0.4, dt the
softplus of a normal around the inverse softplus of a log-uniform step in
[0.001, 0.1], A in [-16, -1]). Prints one JSON line a measurement:

  forward, forward + backward of `linear_attention_ops.ssd_scan` (the op
  as the rule calls it; with --dtype bfloat16 its x, B, C are bf16) each
  `--way`: the Pallas kernels of `ops/kernels/ssd_scan.py`, which the rule
  takes on the TPU, the composition `_ssd_stages`, or both in turn, each
  with the required bytes and FLOPs of `chipbench/flops/nemotron_h.py`'s
  model over its time as a share of the chip's peaks;
  the largest difference, over the largest value, and the relative norm of
  the difference between the op's output (and its gradients) and the
  token-by-token recurrence of `chipbench/references/nemotron_h.py` in
  float32 at jax's highest matmul precision (which walks the tokens one
  by one: some 10 s a pass at 8192; --no-recurrence leaves it out);
  with --sweep, the kernels' two times again for every number of heads a
  grid step may take (the divisors of a group's heads that fill whole
  lane tiles; `HEADS` of the kernel file is the winner; a number Mosaic
  refuses, for the scoped VMEM a chunk of 256 asks, prints `refused`).

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
    p.add_argument('--way', default='both',
                   choices=['kernel', 'composed', 'both'])
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_ssd_scan: no TPU (%r)' % (dev,))
    from chipbench.harness import catalog, peaks
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    from paddle_tpu.ops.kernels import ssd_scan as ssd_kernel
    dtype = jnp.dtype(args.dtype)
    vals = inputs(args)
    w = jnp.asarray(np.random.default_rng(1).normal(size=vals[0].shape),
                    jnp.float32)

    # x, B, C reach the op in the matmuls' dtype, as the convolution hands
    # them over: the cast is not the op's time
    cast = tuple(v.astype(dtype) if i in (0, 3, 4) else v
                 for i, v in enumerate(vals))

    def op_of(kernel):
        return lambda *v: la.ssd_scan(*v, chunk_size=args.chunk,
                                      kernel=kernel)

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
    rep = args.heads // args.groups
    ways = ['kernel', 'composed'] if args.way == 'both' else [args.way]
    if 'kernel' in ways and not ssd_kernel.usable(
            la._chunk_of(args.chunk, args.tokens), args.head_dim, args.state,
            rep, dtype):
        raise SystemExit('bench_ssd_scan: the kernels do not take this shape')

    def measure(way, **more):
        op = op_of(way == 'kernel')
        fwd = jax.jit(op)
        both = jax.jit(jax.value_and_grad(loss(op), argnums=range(6)))
        for name, fn, passes in (('forward', fwd, 1),
                                 ('forward_backward', both, 3)):
            s = _time(fn, cast, args.iters)
            least, _ = peaks.roofline((passes * flops, passes * nbytes),
                                      peak)
            print(json.dumps(dict({
                'measure': name, 'way': way, 'dtype': args.dtype,
                'tokens': tokens, 'heads': args.heads, 'chunk': args.chunk,
                'ms': 1e3 * s, 'least_ms': 1e3 * least,
                'roofline_pct': 100 * least / s,
                'device': dev.device_kind}, **more)), flush=True)
        return fwd(*cast), both(*cast)[1]

    with jax.default_matmul_precision(precision) if precision \
            else contextlib.nullcontext():
        got = {way: measure(way) for way in ways}
        if args.sweep:
            default = ssd_kernel.HEADS
            for n in range(1, rep + 1):
                if rep % n or n * args.head_dim % 128:
                    continue
                ssd_kernel.HEADS = n
                try:
                    measure('kernel', heads_a_step=n)
                except Exception as e:      # Mosaic refuses it: scoped VMEM
                    print(json.dumps({
                        'way': 'kernel', 'heads_a_step': n,
                        'chunk': args.chunk,
                        'refused': str(e).strip().splitlines()[-1][:300]}),
                        flush=True)
            ssd_kernel.HEADS = default
    if args.no_recurrence:
        return
    reference = catalog.load_module(catalog.ROOT, 'references', 'nemotron_h')

    def plain(x, dt, a, b, c, d):
        return reference.selective_scan(x, dt, a, jnp.repeat(b, rep, 2),
                                        jnp.repeat(c, rep, 2), d)

    with jax.default_matmul_precision('highest'):
        want_y = jax.jit(plain)(*vals)
        want = jax.jit(jax.grad(loss(plain), argnums=range(6)))(*vals)
    for way, (got_y, grads) in got.items():
        for name, a, b in zip(('y', 'dx', 'ddt', 'da', 'db', 'dc', 'dd'),
                              (got_y,) + tuple(grads),
                              (want_y,) + tuple(want)):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            print(json.dumps({
                'against_the_recurrence': name, 'way': way,
                'dtype': args.dtype,
                'max_abs_over_max': float(np.abs(a - b).max()
                                          / np.abs(b).max()),
                'rel_norm': float(np.linalg.norm(a - b)
                                  / np.linalg.norm(b))}), flush=True)


if __name__ == '__main__':
    main()
