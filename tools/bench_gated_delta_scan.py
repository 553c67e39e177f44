"""Times stage `gdn_scan` of the gated delta rule on the chip, alone.

    python tools/bench_gated_delta_scan.py [--chunks 128] [--heads 32]
        [--key-heads 16] [--d 128] [--iters 20] [--dtype bfloat16]
        [--gate channel] [--sweep]

The stage's three walks over one layer's chunks at the shape of
`qwen3next_s8192` (128 chunks of 64 tokens, 32 value heads of 128 x 128),
on what the `gdn_intra` kernel hands over of a layer's operands:

  forward   the tokens' outputs [B, T, H, Dv] from S = 0 (the op's forward)
  again     S at each chunk's start (the op's backward, first walk)
  reverse   their cotangents pulled back from the last chunk (second walk)

each two ways:

  composed  fluid/ops_impl/linear_attention_ops.py: `lax.scan`s of
            `_chunk_step` and of `jax.vjp` of it: what every platform but
            the TPU lowers to
  kernel    paddle_tpu.ops.kernels.gated_delta_scan (the chunks the
            grid's sequential axis, S a float32 VMEM scratch)

in ms a call and us a chunk, and the largest difference between the two,
over each output's and each cotangent's largest value. `--sweep` instead
times the kernels over the value heads a grid step takes (whole sublane
tiles of the output: multiples of 8). `--gate channel` walks with a decay
a CHANNEL (`ling3flash_s8192`: the chunk's decay [N, B, H, Dk], the state's
rows each at its own rate). Prints one JSON
line a measurement. Exits non-zero off the chip: a time from the CPU is no
device number.
"""
import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_gated_delta_intra import _inputs, _time     # noqa: E402

SWEEP = (8, 16)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--chunks', type=int, default=128)
    p.add_argument('--heads', type=int, default=32)
    p.add_argument('--key-heads', type=int, default=16)
    p.add_argument('--d', type=int, default=128)
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--dtype', default='bfloat16',
                   choices=['bfloat16', 'float32'])
    p.add_argument('--gate', default='head', choices=['head', 'channel'])
    p.add_argument('--sweep', action='store_true')
    args = p.parse_args(argv)
    if args.gate == 'channel':
        args.key_heads = args.heads         # a key head a value head
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit('bench_gated_delta_scan: no TPU (%r)' % (dev,))
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    from paddle_tpu.ops.kernels import gated_delta_intra as gdi
    from paddle_tpu.ops.kernels import gated_delta_scan as gds
    dtype = jnp.dtype(args.dtype)
    q, k, v, g, beta = _inputs(args, dtype)
    # what stage `gdn_intra` hands over; with a decay a channel its
    # kernel takes the op's own operands where the op holds them (no norm
    # taken, g not summed, no chunks cut)
    xs = jax.jit(
        (lambda *a: gdi.gated_delta_intra_tokens(
            *(x.reshape(x.shape[:2] + (args.heads, args.d)) for x in a[:4]),
            a[4], False, norm=(True, 1e-6, args.d ** -0.5)))
        if args.gate == 'channel' else
        (lambda *a: gdi.gated_delta_intra(
            *a[:3], jnp.cumsum(a[3], axis=-1), a[4], False)))(
        q, k, v, g, beta)
    # O's cotangent where the op's neighbours hold it: [B, T, H, Dv]
    do = jnp.asarray(np.random.default_rng(1).normal(
        size=(1, 64 * args.chunks, args.heads, args.d)), jnp.float32)
    step = functools.partial(la._chunk_step, dtype=dtype)

    def again(*x):
        return lax.scan(lambda s, c: (step(s, c)[0], s),
                        la._zero_state(x), x)[1]

    def reverse(starts, do, *x):
        def body(ds, inp):
            s, c, do_c = inp
            return jax.vjp(step, s, c)[1]((ds, do_c))
        return lax.scan(body, jnp.zeros_like(starts[0]),
                        (starts, x, la._to_chunks(do, 64)), reverse=True)[1]

    def walks(heads):
        """(forward, again, reverse) of the kernels at `heads` a step."""
        kw = dict(dtype=dtype, heads=heads, interpret=False)
        return (lambda *x: gds._forward(*x, out=True, save=False, **kw)[0],
                lambda *x: gds._forward(*x, out=False, save=True, **kw)[1],
                lambda starts, do, *x: gds._backward(*x, starts, do, **kw))

    own = gds._heads(args.heads)
    base = {'chunks': args.chunks, 'heads': args.heads, 'd': args.d,
            'gate': args.gate, 'dtype': args.dtype,
            'device': dev.device_kind}
    ways = [('kernel', n, walks(n)) for n in SWEEP
            if args.heads % n == 0] if args.sweep else \
        [('composed', None, (lambda *x: la._scan(x, dtype, False), again,
                             reverse)),
         ('kernel', own, walks(own))]
    results = {}
    with jax.default_matmul_precision(
            'highest' if dtype == jnp.float32 else 'default'):
        for name, heads, fns in ways:
            row = dict(base, impl=name, heads_a_step=heads)
            try:
                fwd, nxt, rev = (jax.jit(f) for f in fns)
                starts = nxt(*xs)
                for walk, fn, operands in (
                        ('forward', fwd, xs), ('again', nxt, xs),
                        ('reverse', rev, (starts, do) + tuple(xs))):
                    ms = 1e3 * _time(fn, operands, args.iters)
                    row['ms_' + walk] = ms
                    row['us_a_chunk_' + walk] = 1e3 * ms / args.chunks
                results[name] = (fwd(*xs), starts) + tuple(
                    rev(starts, do, *xs))
            except Exception as e:                  # noqa: BLE001
                row['error'] = '%s: %s' % (type(e).__name__, str(e)[:300])
            print(json.dumps(row), flush=True)
    if len(results) == 2:
        names = ('o', 'starts', 'dw', 'du', 'dqg', 'dkd', 'dp', 'ddecay')
        print(json.dumps(dict(base, largest_difference={
            n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for n, a, b in zip(names, results['kernel'],
                               results['composed'])})), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
