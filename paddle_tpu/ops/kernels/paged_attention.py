"""Fused paged decode-attention kernel (docs/perf.md#kernel-layer).

The paged decode ops (ops_impl/sampled_ops.py, PR 11) assemble each
slot's encoder rows from fixed-size pages through an int32 page table —
`_gather_paged_enc` materializes [slots, src_cap, D] in HBM (then
`jnp.repeat`s it per beam!) before the attention consumes it. This
kernel fuses the page-table lookup + QK scores + masking + softmax + PV
context into ONE pallas call: pages stream through VMEM via a
scalar-prefetch-indexed BlockSpec (the page table IS the index map —
exactly the shape pltpu.PrefetchScalarGridSpec exists for), the softmax
runs online across a slot's pages (flash-attention style), and the
gathered [slots, src_cap, D] buffer — let alone its beam-replicated
[slots*beam, src_cap, D] copy — never exists in HBM. Per-dispatch HBM
traffic drops from O(C*beam*S*D) to O(C*beam*D + pages-touched), which
is what pays at serving batch sizes.

Numerics: masked positions score `jnp.finfo(f32).min` (the value the
XLA lowering uses), so a fully-masked row degrades to the same
uniform-softmax the oracle produces; positions at or past `src_cap`
score -inf (they are SLICED off in the oracle — exp(-inf)=0 reproduces
the slice). Online vs one-shot softmax reassociates the sum, so parity
vs `paged_attention_reference` is tolerance-bounded, not bitwise:
|kernel - oracle| <= 1e-5 + 1e-5*|oracle| on fp32 (tests/test_kernels.py
drills it; docs/perf.md carries the table).

Mosaic shape rules this file is written around (jax 0.9 / libtpu 0.0.34,
tests/test_kernels.py export-lowering test + chip_smoke.py): the last two
dims of every block must be (8, 128)-divisible or equal the array's, so
the [Pe, ps] mask pool rides as [Pe, 1, ps] with a leading blocked dim;
and the online-softmax statistics stay 2-D ([beam, 1] slices of the
lane-broadcast scratch) — Mosaic has no layout for the 1-D vectors a
`m_s[:, 0]` read would make. `interpret` is the caller's decision (the
dispatch sites pass `ctx.pallas_interpret`; tests pass True).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import register_kernel

PAGED_ATTENTION = register_kernel(
    'paged_attention',
    'page-table gather + attention + masking fused for the paged decode '
    'ops')

NEG_MASKED = float(jnp.finfo(jnp.float32).min)   # oracle's mask value
LANES = 128


def paged_attention_reference(q, enc_pages, mask_pages, pt_enc, src_cap):
    """XLA oracle: the exact gather + attend math of the paged decode
    lowering (sampled_ops._gather_paged_enc + lod_beam's attend lines),
    kept verbatim so the kernel has a bit-true fallback to A/B against.

    q [B, D] with B = slots*beam (beam rows of one slot contiguous);
    enc_pages [Pe, ps, D]; mask_pages [Pe, ps]; pt_enc [slots, NPE]
    int32. Returns ctx [B, D] float32."""
    pt = pt_enc.astype(jnp.int32)
    C, NPE = pt.shape
    ps, D = enc_pages.shape[1], enc_pages.shape[2]
    enc = jnp.take(enc_pages, pt, axis=0).reshape(C, NPE * ps, D)
    enc = enc[:, :src_cap]
    mask = jnp.take(mask_pages, pt, axis=0).reshape(C, NPE * ps)
    mask = mask[:, :src_cap]
    beam = q.shape[0] // C
    enc_t = jnp.repeat(enc, beam, axis=0)
    mask_t = jnp.repeat(mask, beam, axis=0)
    scores = jnp.einsum('bd,bsd->bs', q, enc_t)
    scores = jnp.where(mask_t > 0, scores, NEG_MASKED)
    alpha = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bs,bsd->bd', alpha, enc_t)


def _kernel(pt_ref, q_ref, page_ref, mask_ref, o_ref, m_s, l_s, acc_s, *,
            page_size, src_cap):
    j = pl.program_id(1)
    npe = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                    # [beam, D]
    kpage = page_ref[0].astype(jnp.float32)             # [ps, D]
    mrow = mask_ref[0].astype(jnp.float32)              # [1, ps]
    beam = q.shape[0]
    s = lax.dot_general(q, kpage, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = jnp.where(mrow > 0, s, NEG_MASKED)
    # positions >= src_cap are SLICED off by the oracle; -inf contributes
    # exp(-inf)=0 to the online sum (every page starts below src_cap, so
    # the running max never stays -inf)
    pos = j * page_size + lax.broadcasted_iota(
        jnp.int32, (beam, page_size), 1)
    s = jnp.where(pos < src_cap, s, -jnp.inf)

    m_prev = m_s[:, :1]                                 # [beam, 1]
    l_prev = l_s[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_s[:] = acc_s[:] * alpha + jnp.dot(
        p, kpage, preferred_element_type=jnp.float32)
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == npe - 1)
    def _finish():
        o_ref[0] = (acc_s[:] / jnp.maximum(l_new, 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q, enc_pages, mask_pages, pt_enc, src_cap, *,
                    interpret):
    """Fused page-gather attention: ctx [B, D] from q [B, D] against the
    paged encoder pool, one pallas call. Same contract as
    `paged_attention_reference` (the dispatch sites' fallback).
    interpret=False compiles through Mosaic (TPU only); True runs the
    body under the pallas interpreter."""
    pt = pt_enc.astype(jnp.int32)
    C, NPE = pt.shape
    ps, D = enc_pages.shape[1], enc_pages.shape[2]
    B = q.shape[0]
    beam = B // C
    qs = q.astype(jnp.float32).reshape(C, beam, D)
    # [Pe, 1, ps]: the (1, 1, ps) block's last two dims equal the array's
    masks = mask_pages.reshape(mask_pages.shape[0], 1, ps)
    kern = functools.partial(_kernel, page_size=ps, src_cap=int(src_cap))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(C, NPE),
            in_specs=[
                pl.BlockSpec((1, beam, D), lambda c, j, pt: (c, 0, 0)),
                pl.BlockSpec((1, ps, D), lambda c, j, pt: (pt[c, j], 0, 0)),
                pl.BlockSpec((1, 1, ps), lambda c, j, pt: (pt[c, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, beam, D), lambda c, j, pt: (c, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((beam, LANES), jnp.float32),
                pltpu.VMEM((beam, LANES), jnp.float32),
                pltpu.VMEM((beam, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, beam, D), jnp.float32),
        interpret=bool(interpret),
    )(pt, qs, enc_pages, masks)
    return out.reshape(B, D)
