"""Times the add of a held share's rows to their tokens on the chip,
alone: `moe_ops._add_up` as the Pallas kernel of `ops/kernels/row_add.py`
beside the scatter-add it replaces there.

    python tools/bench_row_add.py [--cell NAME ...] [--live 1.0 1.5 4]
        [--iters 20] [--dtype bfloat16] [--sweep]

One layer's layout of each held cell by default (tokens, top k, held of
routed experts, width; the layout's rows are `moe_ops._held_layout`'s),
with the router made to send 1.0, 1.5 and 4 times the expected number of
assignments to the held experts (never more than the layout holds). The
index (`moe_ops._index`: the kernel's plan, or the scatter's rows by
token) is built once a load, outside the other times, as a layer builds it
once for both adds. Prints one JSON line a measurement, `ms` and `gb_s`
(the bytes of the live rows and of the float32 result over the time):

  `forward`: the add as `moe_combine` runs it, the experts' rows [cap, d]
  in `--dtype` times their float32 gates, added a token;
  `transpose`: the add as the backward pass runs it, the gradient of
  `_lay_out` for a cotangent [cap, d] in `--dtype`, cast back to it;
  `plan`: `_index` itself, the kernel's plan or the scatter's sort;
  each `way`: `kernel` and `scatter`, and `max_abs_diff` between them;
  with --sweep, the kernel's times again for every (tile of tokens,
  chunk of rows) that fits VMEM (`tiles` of the kernel file is the
  winner).

Exits non-zero off the chip: a time from the CPU is no device number.
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

# a held cell's layer: tokens, top k, held, routed experts, width
CELLS = {
    'smallthinker_s16384': (16384, 6, 8, 64, 2560),
    'lfm2_s16384': (16384, 4, 8, 32, 2048),
    'qwen3next_s8192': (8192, 10, 16, 512, 2048),
    'nemotron3nano_s8192': (8192, 6, 8, 128, 2688),
    'glm47flash_s8192': (8192, 4, 8, 64, 2048),
}
SWEEP_T = (64, 128, 256, 512)
SWEEP_R = (32, 64, 128, 256, 512)


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def keys(rng, n, k, held, live):
    """Token-major keys [n, k]: `live` assignments, anywhere, go to a held
    expert (its index); the rest carry `held`, the absent experts' key."""
    flat = np.full(n * k, held, np.int32)
    at = rng.choice(n * k, size=live, replace=False)
    flat[at] = rng.integers(0, held, size=live)
    return jnp.asarray(flat.reshape(n, k))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--cell', nargs='*', default=sorted(CELLS),
                   choices=sorted(CELLS))
    p.add_argument('--live', nargs='*', type=float, default=[1.0, 1.5, 4.0])
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--dtype', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_row_add: no TPU (%r)' % (dev,))
    from paddle_tpu.fluid.ops_impl import moe_ops
    from paddle_tpu.ops.kernels import row_add
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)

    def measures(n, kernel, tile):
        """(forward, transpose) of one index, each a jitted function of
        the rows (and the gates); `tile`: the kernel's, None for its own."""
        def add(rows, gate, at):
            if not kernel:
                return moe_ops._add_up(rows, gate, at, n, None)
            return row_add.row_add(
                rows, at[0], None if gate is None else gate.reshape(-1),
                at[1], n=n, interpret=False, tile=tile)
        return (jax.jit(add),
                jax.jit(lambda g, at: add(g, None, at).astype(g.dtype)))

    for cell in args.cell:
        n, k, held, routed, d = CELLS[cell]
        cap = moe_ops._held_layout(n * k, held, routed)
        for times in args.live:
            live = min(cap, int(times * n * k * held // routed))
            key = keys(rng, n, k, held, live)
            src = moe_ops._argsort(key.reshape(-1), held + 1)[:cap]
            rows = moe_ops._keep(live)(
                jnp.asarray(rng.normal(size=(cap, d)), dtype))
            gate = jnp.asarray(rng.uniform(size=(cap, 1)), jnp.float32)
            base = {'cell': cell, 'cap': cap, 'tokens': n, 'width': d,
                    'live': live, 'live_x': times, 'dtype': args.dtype,
                    'device': dev.device_kind}
            moved = live * d * dtype.itemsize + n * d * 4

            def run(way, tile=None):
                """One way's three times, and its two results."""
                kernel = way == 'kernel'
                # as `moe_ops._index` builds it, at the sweep's tile
                index = jax.jit(lambda src, key: (
                    (src // k, row_add.plan(key, held, cap, d, tile))
                    if kernel else moe_ops._index(src, key, held)))
                at = index(src, key)
                forward, transpose = measures(n, kernel, tile)
                extra = {'tile': tile[0], 'chunk': tile[1]} if tile else {}
                for name, fn, operands in (
                        ('forward', forward, (rows, gate, at)),
                        ('transpose', transpose, (rows, at)),
                        ('plan', index, (src, key))):
                    s = _time(fn, operands, args.iters)
                    line = dict(base, measure=name, way=way, ms=1e3 * s,
                                **extra)
                    if name != 'plan':
                        line['gb_s'] = moved / s / 1e9
                    print(json.dumps(line), flush=True)
                return forward(rows, gate, at), transpose(rows, at)

            if row_add.usable(cap, n, d, dtype):
                got, ref = run('kernel'), run('scatter')
                print(json.dumps(dict(base, max_abs_diff=[
                    float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(got, ref)])), flush=True)
            else:
                run('scatter')
            if args.sweep:
                for t in SWEEP_T:
                    for r in SWEEP_R:
                        if row_add.usable(cap, n, d, dtype, (t, r)):
                            run('kernel', (t, r))


if __name__ == '__main__':
    main()
