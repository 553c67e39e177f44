"""Sweep flash-attention kernel block sizes on the real chip.

Times forward+backward through the pallas kernel at Transformer-base-like
shapes for each (block_q, block_k) candidate and prints a ranked table plus
the winning env setting (PADDLE_TPU_FLASH_BQ/BK consumed by
paddle_tpu.ops.flash_attention). Run on TPU:

    python tools/tune_flash.py [--seq 256] [--batch 64] [--heads 8] [--dim 64]
"""
import argparse
import itertools
import os
import sys

import numpy as np

# make paddle_tpu importable when run as `python tools/tune_flash.py`
# (sys.path gets tools/, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
    print('\nbest: PADDLE_TPU_FLASH_BQ=%d PADDLE_TPU_FLASH_BK=%d '
          '(%.3f ms/step fwd+bwd @ B%d H%d T%d D%d)'
          % (bq, bk, dt * 1e3, B, H, T, D))


if __name__ == '__main__':
    main()
