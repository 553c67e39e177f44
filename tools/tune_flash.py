"""Sweep flash-attention kernel block sizes on the real chip.

Times forward+backward through the pallas kernel at Transformer-base-like
shapes for each (block_q, block_k) candidate and prints a ranked table plus
the winning tiles as the `_TUNED_BQ_BK` entry of
paddle_tpu/ops/flash_attention.py to edit. Run on TPU:

    python tools/tune_flash.py [--seq 256] [--batch 64] [--heads 8] [--dim 64]

--parts also times the kernels ALONE (one `part ...` line each, with the
time per grid step): the forward, the one-pass backward (`bwd`) where the
rule gives one (a head's scores in one tile, or a causal head of many on
the triangular grid or its band with its dq in VMEM: the line says which,
and for the second the VMEM bytes the call holds and the limit it
states), and the two kernels (`dq`, `dkv`) at the same tiles, with the
largest difference between the two schedules' gradients; what to read
before and after a change to a kernel body. --tile N forces the parts'
tiles: with --causal --seq 1024, --tile 1024 is one masked tile in one
pass and --tile 512 the triangular grid, against the default's one pass
in 512 sub-tiles. --window N (with --causal --parts) times the same
kernels on the BAND of tiles a sliding window touches (PR 37): `--batch 1
--heads 28 --seq 16384 --dim 128 --causal --parts --no-sweep` with and
without `--window 4096` is smallthinker_s16384's windowed and global call,
`--batch 1 --heads 20 --seq 8192 --dim 256 --causal` glm47flash_s8192's.
docs/perf.md has the last sweep's rows and the commands that gave them.
"""
import argparse
import functools
import itertools
import os
import sys

import numpy as np

# make paddle_tpu importable when run as `python tools/tune_flash.py`
# (sys.path gets tools/, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def time_parts(q, k, v, causal, iters, tile=None, window=None):
    """[(kernel, seconds per call, grid steps per call)] of the forward,
    the one-pass backward where the rule gives one, and the dq and dk/dv
    kernels alone, at the default tiles or at `tile`. The dq and dk/dv
    chains consume one kernel's outputs only, so XLA removes the other
    call."""
    import importlib
    import jax
    import jax.numpy as jnp
    from paddle_tpu.utils.timing import time_chained
    # the package's attribute of that name is the function
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    window = fa._window_of(window, causal, q.shape[2])
    q, k, v, kb, scale, bq, bk, schedule, interp, _, _ = fa._prep(
        q, k, v, None, None, tile, tile, False, causal=causal,
        window=window)
    o, lse = fa._fwd_call(q, k, v, kb, causal, scale, bq, bk, interp, window)
    delta = jnp.sum(o.astype(jnp.float32) ** 2, axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))

    def nudge(x, dx):
        return x + (1e-6 * dx).astype(x.dtype)

    # what a step only reads is an argument of the timed program
    # (time_chained's consts), not a constant compiled into it
    consts = dict(q=q, k=k, v=v, kb=kb, o=o, lse=lse, delta=delta)

    def bwd(schedule, q, k, v, kb, o, lse, delta):
        # the cotangent is o itself: bf16, full rank
        return fa._bwd_call(q, k, v, kb, o, lse, delta, causal, scale,
                            bq, bk, schedule, interp, window)

    def fwd_step(x, k, v, kb, **_):
        return (nudge(x[0], fa._fwd_call(x[0], k, v, kb, causal, scale,
                                         bq, bk, interp, window)[0]),)

    def bwd_step(x, q, k, v, **rest):
        return tuple(nudge(a, d)
                     for a, d in zip(x, bwd(schedule, *x, **rest)))

    def dq_step(x, q, **rest):
        return (nudge(x[0], bwd(None, x[0], **rest)[0]),)

    def dkv_step(x, k, v, **rest):
        _, dk, dv = bwd(None, k=x[0], v=x[1], **rest)
        return nudge(x[0], dk), nudge(x[1], dv)

    B, H, T, D = q.shape
    nq = T // bq
    blocks = fa._tile_pairs(nq, fa._band(window, bk, nq)) \
        if fa._use_tri(causal, T, T, bq, bk) else nq * (T // bk)
    parts = [('fwd', fwd_step, (q,), blocks)]
    said = 'two passes'
    if schedule:
        parts.append(('bwd', bwd_step, (q, k, v),
                      blocks if schedule == 'head' else 1))
        said = 'one pass (sub-tiles of %d)' % bq
        if schedule == 'head':
            limit = fa._head_vmem_limit(T, D, bq, bk, q.dtype.itemsize)
            said = ('one pass over the head: %.2f MiB of VMEM held, limit '
                    '%.2f MiB stated' % (
                        (limit - fa._MOSAIC_SCOPE_BYTES) / 2 ** 20,
                        limit / 2 ** 20))
        one, two = (jax.jit(functools.partial(bwd, s))(**consts)
                    for s in (schedule, None))
        print('one pass against two, largest difference: '
              + ', '.join('%s %.3g (of %.3g)' % (
                  n, abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max(),
                  abs(b.astype(jnp.float32)).max())
                  for n, a, b in zip(('dq', 'dk', 'dv'), one, two)))
    parts += [('dq', dq_step, (q,), blocks), ('dkv', dkv_step, (k, v), blocks)]
    print('parts at tiles %d x %d, backward in %s' % (bq, bk, said))
    return [(name, time_chained(step, x, iters, consts=consts), B * H * n)
            for name, step, x, n in parts]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--seq', type=int, default=256)
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--dim', type=int, default=64)
    ap.add_argument('--causal', action='store_true')
    ap.add_argument('--iters', type=int, default=20)
    ap.add_argument('--blocks', type=str, default='128,256,512',
                    help='comma-separated candidate tile sizes')
    ap.add_argument('--parts', action='store_true',
                    help='also time fwd, the one-pass bwd, dq and dkv alone')
    ap.add_argument('--tile', type=int, default=None,
                    help='tiles of --parts (default: the table\'s)')
    ap.add_argument('--window', type=int, default=None,
                    help='a sliding window for --parts (with --causal)')
    ap.add_argument('--no-sweep', action='store_true',
                    help='stop after --parts')
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import flash_attention

    if jax.devices()[0].platform != 'tpu':
        raise SystemExit('tune_flash needs the real chip (jax.devices() '
                         'returned %r)' % (jax.devices(),))

    B, H, T, D = args.batch, args.heads, args.seq, args.dim
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype('float32'),
                    dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D).astype('float32'),
                    dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, D).astype('float32'),
                    dtype=jnp.bfloat16)

    if args.parts:
        for name, dt, steps in time_parts(q, k, v, args.causal, args.iters,
                                          args.tile, args.window):
            print('part %-3s %.3f ms/call, %d grid steps, %.3f us/grid step'
                  % (name, dt * 1e3, steps, dt * 1e6 / steps))
    if args.no_sweep:
        return

    cands = sorted({min(int(b), T) for b in args.blocks.split(',')})
    results = []
    for bq, bk in itertools.product(cands, cands):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=args.causal,
                                block_q=bq, block_k=bk, interpret=False)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        from paddle_tpu.utils.timing import time_fwd_bwd_chained
        try:
            dt = time_fwd_bwd_chained(loss, q, k, v, args.iters)
        except Exception as e:
            print('bq=%-4d bk=%-4d FAILED: %s' % (bq, bk, str(e)[:80]))
            continue
        results.append((dt, bq, bk))
        print('bq=%-4d bk=%-4d %.3f ms/step' % (bq, bk, dt * 1e3))

    if not results:
        raise SystemExit('no candidate compiled')
    results.sort()
    dt, bq, bk = results[0]
    print('\nbest: _TUNED_BQ_BK[%r] = (%d, %d) '
          '(%.3f ms/step fwd+bwd @ B%d H%d T%d D%d)'
          % (bool(args.causal), bq, bk, dt * 1e3, B, H, T, D))


if __name__ == '__main__':
    main()
