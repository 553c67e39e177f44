"""Times stage `gdn_intra` of the gated delta rule on the chip, alone.

    python tools/bench_gated_delta_intra.py [--chunks 128] [--heads 32]
        [--key-heads 16] [--d 128] [--iters 30] [--dtype bfloat16]
        [--gate channel] [--sweep]

Two implementations of the stage at one layer's shape of `qwen3next_s8192`
(128 chunks of 64 tokens, 16 key heads serving 32 value heads of 128),
forward alone and the backward as the op runs it (the stage recomputed,
then pulled back):

  composed  fluid/ops_impl/linear_attention_ops.py `_intra` on repeated
            key heads and `jax.vjp` of it: what every platform but the TPU
            lowers to
  kernel    paddle_tpu.ops.kernels.gated_delta_intra (one Pallas kernel
            forward, one backward; a key head read in place)

and the largest difference between the two, over each output's and each
gradient's largest value. `--sweep` instead times the kernel's two calls
over the heads a grid step takes. `--gate channel` times the form with a
decay a CHANNEL (`ling3flash_s8192`: g in (-5, 0), a key head a value
head) from the OP's operands on both sides, WHERE THE OP HOLDS THEM: q, k,
v and g arrive as a layer's projections leave them, [1, T, H x D], are
viewed [1, T, H, D] as the model's reshape does, not normalised and not
summed, and the five gradients are taken there, so that "alone" holds what
ISSUEs 56 and 58 moved. `composed` is all of `_stage_intra` off the TPU's
path (the floor's select, two l2 norms, q's scale, the rounding to the
matmuls' dtype and `_to_chunks` in XLA, then `_intra_channel` with its
`cumsum`; its pull-back the chunks' transposes), `kernel` the per-channel
kernels, which do all of it in VMEM on blocks their index maps cut.
Prints one JSON line a measurement.
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

SWEEP = (2, 4, 8, 16, 32)


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _inputs(args, dtype):
    """A layer's chunked operands as the op hands them over: k of unit
    length, q scaled, gates as a trained layer's (a decay of a few
    percent a token). With a decay a channel: the op's own, q, k, v, g
    [1, T, H x D] and beta [1, T, H] as the projections leave them (no
    norm taken, no scale, no chunks), g within its floor."""
    rng = np.random.default_rng(0)
    shape = (args.chunks, 1, args.heads, 64)
    keys = (args.chunks, 1, args.key_heads, 64, args.d)

    def unit(x):
        return x / np.sqrt(np.sum(x * x, -1, keepdims=True))

    q = unit(rng.normal(size=keys)) * args.d ** -0.5
    k = unit(rng.normal(size=keys))
    v = rng.normal(size=shape + (args.d,))
    g = -rng.uniform(0.0, 0.1, size=shape)
    beta = rng.uniform(0.0, 1.0, size=shape)
    if getattr(args, 'gate', 'head') == 'channel':
        tokens = (1, 64 * args.chunks, args.heads * args.d)
        q, k, v = (rng.normal(size=tokens) for _ in range(3))
        # a decay a channel within its floor of -5: a slow decay in a
        # quarter of the channels, near the floor in the rest
        g = -rng.uniform(0.0, 0.1, size=tokens)
        g = np.where(rng.uniform(size=g.shape) < 0.75,
                     -rng.uniform(4.0, 5.0, size=g.shape), g)
        beta = rng.uniform(0.0, 1.0, size=tokens[:2] + (args.heads,))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v)) \
        + tuple(jnp.asarray(x, jnp.float32) for x in (g, beta))


def _cotangents(outs):
    rng = np.random.default_rng(1)
    return tuple(jnp.asarray(rng.normal(size=o.shape), o.dtype)
                 for o in outs)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--chunks', type=int, default=128)
    p.add_argument('--heads', type=int, default=32)
    p.add_argument('--key-heads', type=int, default=16)
    p.add_argument('--d', type=int, default=128)
    p.add_argument('--iters', type=int, default=30)
    p.add_argument('--dtype', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--gate', default='head', choices=['head', 'channel'])
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    channel = args.gate == 'channel'
    if channel:
        args.key_heads = args.heads         # a key head a value head
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_gated_delta_intra: no TPU (%r)' % (dev,))
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    from paddle_tpu.ops.kernels import gated_delta_intra as gdi
    dtype = jnp.dtype(args.dtype)
    operands = _inputs(args, dtype)

    rep = args.heads // args.key_heads

    norm, floor = (True, 1e-6, args.d ** -0.5), -5.0

    def by_head(*xs):       # the model's reshape of a projection
        return (x.reshape(x.shape[:2] + (args.heads, args.d)) for x in xs)

    def composed(q, k, v, g, beta):
        if channel:         # all of `_stage_intra`, the select before it
            q, k, v, g = by_head(q, k, v, g)
            w, u, qg, kd, p_, decay = la._stage_intra(
                q, k, v, jnp.where(g < floor, floor, g), beta,
                (64, norm[2], norm[0], norm[1], False))
        else:
            w, u, qg, kd, p_, decay = la._intra(
                jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v,
                g, beta)
        return (w.astype(dtype), u, qg.astype(dtype), kd.astype(dtype),
                p_.astype(dtype), decay)

    def kernel(heads):
        if channel:
            return lambda q, k, v, g, beta: gdi.gated_delta_intra_tokens(
                *by_head(q, k, v, g), beta, False, heads, norm=norm,
                floor=floor)
        return lambda q, k, v, g, beta: gdi.gated_delta_intra(
            q, k, v, jnp.cumsum(g, axis=-1), beta, False, heads)

    def backward(fn):
        return lambda cts, *a: jax.vjp(fn, *a)[1](cts)

    cts = _cotangents(jax.eval_shape(kernel(None), *operands))
    base = {'chunks': args.chunks, 'heads': args.heads,
            'key_heads': args.key_heads, 'd': args.d, 'gate': args.gate,
            'dtype': args.dtype, 'device': dev.device_kind}
    ways = [('kernel', n, kernel(n)) for n in SWEEP
            if args.heads % n == 0 and n % rep == 0] if args.sweep else \
        [('composed', None, composed), ('kernel', None, kernel(None))]
    results = {}
    with jax.default_matmul_precision(
            'highest' if dtype == jnp.float32 else 'default'):
        for name, heads, fn in ways:
            row = dict(base, impl=name,
                       heads_a_step=heads or (
                           gdi._heads(args.heads, rep, dtype)
                           if name == 'kernel' else None))
            try:
                fwd, bwd = jax.jit(fn), jax.jit(backward(fn))
                row['ms_fwd'] = 1e3 * _time(fwd, operands, args.iters)
                # the stage again and its pull-back, as `_chunked_bwd` runs
                row['ms_bwd'] = 1e3 * _time(bwd, (cts,) + operands,
                                            args.iters)
                results[name] = fwd(*operands) + bwd(cts, *operands)
            except Exception as e:                  # noqa: BLE001
                row['error'] = '%s: %s' % (type(e).__name__, str(e)[:300])
            print(json.dumps(row), flush=True)
    if len(results) == 2:
        names = ('w', 'u', 'qg', 'kd', 'p', 'decay', 'dq', 'dk', 'dv', 'dg',
                 'dbeta')
        print(json.dumps(dict(base, largest_difference={
            # (a chunk's decay by channel is exp(-200) = 0 on both sides
            # at these gates: over the smallest normal number, not 0 / 0)
            n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.maximum(jnp.max(jnp.abs(b.astype(jnp.float32))),
                                   jnp.finfo(jnp.float32).tiny))
            for n, a, b in zip(names, results['kernel'],
                               results['composed'])})), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
