"""Times a large host array's way to the device, by the SHAPE it is put in.

    python tools/bench_feed_put.py [--shape 256,224,224,3] [--iters 20]
        [--pieces 2,4,8] [--views declared,rows,rows/8]
        [--sweep [--inner 32,32,3]]

`jax.device_put` of a float32 NHWC batch spends most of its time in the
host's relayout for the device's tiling, not on the link (PERF.md section
6, PRs 51 and 52). The same C-contiguous buffer is put here as views of
itself (no host copy) and given its declared shape by a jitted reshape on
the device; four host arrays are cycled as `chipbench`'s pool does:

  declared   the array as it is (what `Executor._to_device` did to PR 51)
  rows       [shape[0], the rest]
  flat       [size]
  lanes      [size / 128, 128]
  rows/K, flat/K   the view cut along its leading dimension into K pieces
             put one after the other, concatenated on the device
  rows/Ke    the same pieces, each reshaped as it lands, then concatenated

`put_ms` is `device_put` to landed, `reshape_ms` the device's reshape
alone, `chained_ms` the reshape dispatched right behind the put and one
wait (what a step pays), medians of `--iters`. `--sweep` instead times
`declared` against `rows` and its pieces for [n, `--inner`] from 1/4 to
32 MiB: where the view starts to win is `executor._VIEW_FEED_BYTES`, where
pieces do `executor._VIEW_PIECE_BYTES`. Prints one JSON line a
measurement. Exits non-zero off the chip: a time from the CPU is no device
number.
"""
import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _views(shape, pieces=(2, 4, 8)):
    size = int(np.prod(shape))
    views = {'declared': [shape], 'rows': [(shape[0], size // shape[0])],
             'flat': [(size,)]}
    if size % 128 == 0:
        views['lanes'] = [(size // 128, 128)]
    for name in ('rows', 'flat'):
        lead = views[name][0]
        for k in pieces:
            if lead[0] % k == 0:
                views['%s/%d' % (name, k)] = [
                    (lead[0] // k,) + lead[1:]] * k
                if name == 'rows':
                    views['rows/%de' % k] = views['rows/%d' % k]
    return views


def _pieces(a, piece_shapes):
    """Views of `a`'s buffer, one a piece, in order."""
    flat = a.reshape(-1)
    out, at = [], 0
    for s in piece_shapes:
        n = int(np.prod(s))
        out.append(flat[at:at + n].reshape(s))
        at += n
    assert all(np.shares_memory(p, a) for p in out)
    return out


def _ms(seconds):
    return round(1e3 * statistics.median(seconds), 3)


def measure(name, piece_shapes, hosts, dev, iters):
    shape = hosts[0].shape
    if name == 'declared':
        to_shape = None
    elif name.endswith('e'):
        # each piece takes its declared shape as it lands; one
        # concatenation behind the last
        each = jax.jit(lambda x: x.reshape((-1,) + shape[1:]))
        join = jax.jit(lambda *xs: jnp.concatenate(xs))
        to_shape = lambda *xs: join(*[each(x) for x in xs])  # noqa: E731
    else:
        to_shape = jax.jit(lambda *xs: (
            xs[0] if len(xs) == 1 else jnp.concatenate(xs)).reshape(shape))
    put, reshape, chained = [], [], []
    for i in range(iters + 3):
        views = _pieces(hosts[i % len(hosts)], piece_shapes)
        t0 = time.perf_counter()
        placed = [jax.device_put(v, dev) for v in views]
        jax.block_until_ready(placed)
        t1 = time.perf_counter()
        out = placed[0] if to_shape is None else to_shape(*placed)
        out.block_until_ready()
        t2 = time.perf_counter()
        del placed, out
        placed = [jax.device_put(v, dev) for v in views]
        out = placed[0] if to_shape is None else to_shape(*placed)
        out.block_until_ready()
        t3 = time.perf_counter()
        if i == 0:
            assert out.shape == shape and np.array_equal(
                np.asarray(out), hosts[0]), name
        del placed, out
        if i >= 3:
            put.append(t1 - t0)
            reshape.append(t2 - t1)
            chained.append(t3 - t2)
    nbytes = hosts[0].nbytes
    return {'view': name, 'shape': list(shape), 'mb': round(nbytes / 1e6, 3),
            'put_ms': _ms(put), 'reshape_ms': _ms(reshape),
            'chained_ms': _ms(chained),
            'chained_gbps': round(nbytes / statistics.median(chained) / 1e9,
                                  2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--shape', default='256,224,224,3')
    ap.add_argument('--iters', type=int, default=20)
    ap.add_argument('--pieces', default='2,4,8')
    ap.add_argument('--views', default='', help='only these, by name')
    ap.add_argument('--sweep', action='store_true')
    ap.add_argument('--inner', default='32,32,3', help="the sweep's rows")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        sys.exit('no TPU here (%s): a time from the CPU is no device number'
                 % dev.platform)
    rng = np.random.default_rng(0)
    pieces = tuple(int(k) for k in args.pieces.split(','))
    only = set(args.views.split(',')) - {''}
    if args.sweep:
        inner = tuple(int(n) for n in args.inner.split(','))
        shapes = [(8 * max(1, round(mib * (1 << 20)
                                    / (32 * int(np.prod(inner))))),) + inner
                  for mib in (0.25, 0.5, 1, 2, 4, 8, 16, 32)]
        only = only or {'declared', 'rows'} | {'rows/%d' % k for k in pieces}
    else:
        shapes = [tuple(int(s) for s in args.shape.split(','))]
    for shape in shapes:
        hosts = [rng.random(shape, np.float32) for _ in range(4)]
        for name, piece_shapes in _views(shape, pieces).items():
            if not only or name in only:
                print(json.dumps(measure(name, piece_shapes, hosts, dev,
                                         args.iters)), flush=True)


if __name__ == '__main__':
    main()
