"""Times the gated RMS norm on the chip, alone.

    python tools/bench_gated_norm.py [--cell NAME ...] [--gate bfloat16]
        [--iters 30] [--sweep]

Two implementations at one layer's shape of each cell that builds the op
(`nemotron3nano_s8192`: gate first, 8 groups of 512 columns of
[1, 8192, 4096]; `qwen3next_s8192`: norm first, a head of 128 as the last
axis of [1, 8192, 32, 128]; `granite4hmicro_s8192`: gate first, ONE group
of all 4096 columns of [1, 8192, 4096]), x float32 and the gate in `--gate` (bf16 in a
cell's step, float32 in its float32 check), forward alone and the backward
as the op runs it. The gate and the cotangent come, and the result and the
gate's gradient go, as the cell's step has them: [B, T, all the columns],
a matmul's on both sides of the op, reshaped to x's shape inside the timed
function:

  composed  fluid/ops_impl/linear_attention_ops.py `_gated_norm` and
            `jax.vjp` of it behind the barrier: what every platform but
            the TPU lowers to
  kernel    paddle_tpu.ops.kernels.gated_norm (one Pallas kernel forward,
            one backward; a block holds a group's columns)

with each pass's required bytes (forward: x and the gate in, y out;
backward: x, the gate and the cotangent in, dx and dgate out) over its
time as a share of the chip's HBM peak (chipbench/harness/peaks.py: 819
GB/s), and `max_abs_diff`, the largest difference between the two in each
result. `--sweep` instead times the kernel's two calls over the
rows of a block. Prints one JSON line a measurement. Exits non-zero off
the chip: a time from the CPU is no device number.
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

# a cell's op: x's shape, groups, norm_before_gate
CELLS = {
    'nemotron3nano_s8192': ((1, 8192, 4096), 8, False),
    'qwen3next_s8192': ((1, 8192, 32, 128), 1, True),
    'granite4hmicro_s8192': ((1, 8192, 4096), 1, False),
}
SWEEP_ELEMENTS = (1 << 15, 1 << 16, 1 << 17, 1 << 18, 1 << 19)
EPS = 1e-5


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
    p.add_argument('--cell', nargs='*', default=sorted(CELLS),
                   choices=sorted(CELLS))
    p.add_argument('--gate', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_gated_norm: no TPU (%r)' % (dev,))
    from chipbench.harness import peaks
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    from paddle_tpu.ops.kernels import gated_norm as gn
    hbm = peaks.peaks_for(dev.device_kind)['hbm_bytes_per_s']
    gate_dtype = jnp.dtype(args.gate)
    rng = np.random.default_rng(0)
    for cell in args.cell:
        shape, groups, first = CELLS[cell]
        width = shape[-1] // groups
        flat = shape[:2] + (int(np.prod(shape[2:])),)
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        g = jnp.asarray(rng.normal(size=flat), jnp.float32)
        z = jnp.asarray(rng.normal(size=flat), gate_dtype)
        w = jnp.asarray(1.0 + 0.1 * rng.normal(size=shape[-1]), jnp.float32)
        cfg = (EPS, first, groups)
        least_fwd = x.size * (8 + gate_dtype.itemsize) / hbm
        least_bwd = x.size * (12 + 2 * gate_dtype.itemsize) / hbm

        def between(fwd, bwd):
            """The two calls between the step's matmuls."""
            def back(x, z, w, g):
                dx, dz, dw = bwd(x, z.reshape(shape), w, g.reshape(shape))
                return dx, dz.reshape(flat), dw
            return (lambda x, z, w: fwd(x, z.reshape(shape), w).reshape(flat),
                    back)

        def composed():
            def op(x, z, w):
                return la.gated_rms_norm(x, z, w, cfg, False)
            return between(op, lambda x, z, w, g: jax.vjp(op, x, z, w)[1](g))

        def kernel(tile):
            kw = dict(eps=EPS, norm_first=first, groups=groups,
                      interpret=False, tile=tile)
            return between(
                lambda x, z, w: gn.gated_norm_fwd(x, z, w, **kw),
                lambda x, z, w, g: gn.gated_norm_bwd(x, z, w, g, **kw))

        base = {'cell': cell, 'shape': list(shape), 'groups': groups,
                'norm_before_gate': first, 'gate': args.gate,
                'device': dev.device_kind}
        # a block: rows of a group's columns, or by head tokens of all
        # their columns
        heads = gn.by_head(shape, groups, x.dtype)
        cols = flat[-1] if heads else width
        rows = x.size // (flat[-1] if heads else shape[-1])
        base['block_cols'] = cols
        if args.sweep:
            ways = [('kernel', n // cols, kernel(n // cols))
                    for n in SWEEP_ELEMENTS if 16 <= n // cols <= rows]
        else:
            ways = [('composed', None, composed()),
                    ('kernel', gn.rows_of(rows, cols), kernel(None))]
        results = {}    # impl -> (y, dx, dgate, dw) of its measurement
        for name, tile, (fwd, bwd) in ways:
            row = dict(base, impl=name, block_rows=tile)
            try:
                fwd, bwd = jax.jit(fwd), jax.jit(bwd)
                row['ms_fwd'] = 1e3 * _time(fwd, (x, z, w), args.iters)
                row['ms_bwd'] = 1e3 * _time(bwd, (x, z, w, g), args.iters)
                row['hbm_share_fwd'] = least_fwd / (row['ms_fwd'] * 1e-3)
                row['hbm_share_bwd'] = least_bwd / (row['ms_bwd'] * 1e-3)
                results[name] = (fwd(x, z, w),) + tuple(bwd(x, z, w, g))
            except Exception as e:                  # noqa: BLE001
                row['error'] = '%s: %s' % (type(e).__name__, str(e)[:300])
            print(json.dumps(row), flush=True)
        if not args.sweep and len(results) == 2:
            print(json.dumps(dict(base, max_abs_diff={
                n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32))))
                for n, a, b in zip(('y', 'dx', 'dgate', 'dw'),
                                   results['kernel'],
                                   results['composed'])})), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
