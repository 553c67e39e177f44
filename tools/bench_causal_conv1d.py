"""Times the depthwise causal convolution on the chip, alone.

    python tools/bench_causal_conv1d.py [--batch 1] [--tokens 8192]
        [--channels 8192] [--taps 4] [--act silu] [--iters 30]
        [--dtype bfloat16] [--sweep]

Two implementations at one layer's shape of `qwen3next_s8192` (one row of
8192 tokens, 8192 channels, four taps, silu), forward alone and the
backward as the op runs it (the sum computed again, then pulled back):

  composed  fluid/ops_impl/linear_attention_ops.py `_conv` and `jax.vjp` of
            it behind the barrier: what every platform but the TPU lowers to
  kernel    paddle_tpu.ops.kernels.causal_conv1d (one Pallas kernel forward,
            one backward; the shifts in VMEM)

with each pass's required bytes over its time as a share of the chip's
HBM peak (chipbench/harness/peaks.py: 819 GB/s), and the largest
difference between the two over each result's largest value. `--sweep`
instead times the kernel's two calls over tiles [tT, tC]. Prints one JSON
line a measurement. Exits non-zero off the chip: a time from the CPU is
no device number.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SWEEP_T = (256, 512, 1024, 2048)
SWEEP_C = (128, 256, 512, 1024)


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--batch', type=int, default=1)
    p.add_argument('--tokens', type=int, default=8192)
    p.add_argument('--channels', type=int, default=8192)
    p.add_argument('--taps', type=int, default=4)
    p.add_argument('--act', default='silu', choices=['', 'silu'])
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--dtype', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_causal_conv1d: no TPU (%r)' % (dev,))
    from chipbench.harness import peaks
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    from paddle_tpu.ops.kernels import causal_conv1d as cc
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    shape = (args.batch, args.tokens, args.channels)
    x, g = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    w = jnp.asarray(rng.normal(size=(args.taps, args.channels)) * 0.5,
                    jnp.float32)
    # the least time of each pass: x in and y out; x, g in and dx out
    hbm = peaks.peaks_for(dev.device_kind)['hbm_bytes_per_s']
    least_fwd = 2 * x.size * dtype.itemsize / hbm
    least_bwd = 3 * x.size * dtype.itemsize / hbm

    def composed():
        return (lambda x, w: la.causal_conv1d(x, w, args.act, False),
                lambda x, w, g: jax.vjp(
                    lambda x, w: la.causal_conv1d(x, w, args.act, False),
                    x, w)[1](g))

    def kernel(tile):
        return (lambda x, w: cc.causal_conv1d_fwd(
                    x, w, act=args.act, interpret=False, tile=tile),
                lambda x, w, g: cc.causal_conv1d_bwd(
                    x, w, g, act=args.act, interpret=False, tile=tile))

    base = {'shape': list(shape), 'taps': args.taps, 'act': args.act,
            'dtype': args.dtype, 'device': dev.device_kind}
    default = list(cc.tile_of(args.tokens, args.channels, dtype))
    if args.sweep:
        ways = [('kernel', [tt, tc], kernel((tt, tc)))
                for tt in SWEEP_T for tc in SWEEP_C
                if args.tokens % tt == 0 and args.channels % tc == 0]
    else:
        ways = [('composed', None, composed()),
                ('kernel', default, kernel(None))]
    results = {}    # impl -> (y, dx, dw) of its last measurement
    for name, tile, (fwd, bwd) in ways:
        row = dict(base, impl=name, tile=tile)
        try:
            fwd, bwd = jax.jit(fwd), jax.jit(bwd)
            row['ms_fwd'] = 1e3 * _time(fwd, (x, w), args.iters)
            row['ms_bwd'] = 1e3 * _time(bwd, (x, w, g), args.iters)
            row['hbm_share_fwd'] = least_fwd / (row['ms_fwd'] * 1e-3)
            row['hbm_share_bwd'] = least_bwd / (row['ms_bwd'] * 1e-3)
            results[name] = (fwd(x, w),) + tuple(bwd(x, w, g))
        except Exception as e:                  # noqa: BLE001
            row['error'] = '%s: %s' % (type(e).__name__, str(e)[:300])
        print(json.dumps(row), flush=True)
    if not args.sweep and len(results) == 2:
        print(json.dumps(dict(base, largest_difference={
            n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for n, a, b in zip(('y', 'dx', 'dw'), results['kernel'],
                               results['composed'])})), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
