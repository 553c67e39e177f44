"""Fused sharded-sparse optimizer kernels (docs/perf.md#kernel-layer).

The streaming `train_stream` step's hottest op after the lookup is the
sparse optimizer update (ops_impl/optim_ops.py adagrad/adam
SelectedRows branches): after `_merge_sparse` dedups the batch's rows,
XLA emits a gather of the param/moment rows, the moment math, and a
scatter-add of the deltas — three HBM round-trips over [N, D] plus the
table-row traffic. These kernels fuse gather + moment update + scatter
into ONE pallas call: the merged uids ride scalar prefetch and serve as
the BlockSpec index maps for the param/moment ROWS (in and out — the
tables are aliased via `input_output_aliases`, so the update is
in-place row traffic and the [N, D] gathered copies never exist in
HBM). The dedup merge itself (sort/segment-sum, embedding.lookup.
dedup_plan) stays XLA: it is id-space bookkeeping with no row traffic,
and sharing ONE definition of the dedup invariant with the lookup wire
beats fusing it.

Write-hazard analysis (why the grid runs the slots in REVERSE): the
merge clamps its invalid tail slots to row 0, so row 0 can be visited
more than once. Valid uids are unique, and an invalid slot's write is
always value-preserving (its delta is masked to zero — it writes the
row it read). Processing slots back-to-front puts every invalid visit
of row 0 BEFORE the (at most one) valid visit, so no grid step ever
reads a row that an earlier step changed. That makes the kernel correct
under BOTH aliasing semantics in play: the pallas interpreter (tier-1,
CPU), whose input carry is a snapshot that never sees in-grid writes,
and compiled Mosaic, where the aliased buffer is live and input
prefetch may race a write by a few pipeline stages — hazard-free
because the only re-read row only ever received no-op writes first.

Numerics: per-row math is the fallback's elementwise expressions in the
same order on the same f32 rows, so parity is effectively exact;
tests/test_kernels.py pins |kernel - fallback| <= 1e-6 absolute
(docs/perf.md carries the table). Sharded steps (ctx.mesh set) keep the
XLA fallback — the kernel is per-shard-local and its shard_map wiring
is a follow-on; dispatch sites route accordingly.

Mosaic shape rules this file is written around (jax 0.9 / libtpu 0.0.34,
tests/test_kernels.py export-lowering test + chip_smoke.py): a (1, D) row
block of a [V, D] table is refused (the last two block dims must be
(8, 128)-divisible or equal the array's), so tables and merged grads ride
as [V, 1, D] / [N, 1, D] with the ROW as a leading blocked dim; and the
learning rate is read as a scalar, which only SMEM serves. Whether XLA
makes the [V, D] <-> [V, 1, D] reshape a bitcast or a relayout copy of
the table is a layout question no CPU run can answer (ROADMAP Speed
item 8 measures it). `interpret` is the caller's decision (the dispatch
sites pass `ctx.pallas_interpret`; tests pass True).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import register_kernel

SPARSE_ADAGRAD = register_kernel(
    'sparse_adagrad',
    'merged-row gather + adagrad moment update + scatter fused, tables '
    'aliased in-place')
SPARSE_ADAM = register_kernel(
    'sparse_adam',
    'merged-row gather + adam moment update + scatter fused, tables '
    'aliased in-place')


def sparse_adagrad_reference(p, m, uids, gm, valid, lr, eps):
    """The XLA path (reference adagrad_op.h SelectedRows branch: MergeAdd
    then per-row update): gather the merged rows' moments, update, scatter
    DELTAS so the zero-padded invalid merge slots are exact no-ops under
    duplicate indices. The lowering rule's fallback and the oracle the
    kernel is checked against (tests, chip_smoke.py)."""
    vm = valid[:, None].astype(gm.dtype)
    m_rows = m[uids]
    m_new = m_rows + gm * gm
    p_delta = -lr * gm / (jnp.sqrt(m_new) + eps) * vm
    return p.at[uids].add(p_delta), m.at[uids].add((m_new - m_rows) * vm)


def sparse_adam_reference(p, m1, m2, uids, gm, valid, lr, b1, b2, eps):
    """The XLA path (reference adam_op.h sparse branch, lazy semantics:
    only touched rows' moments decay/update); `lr` is bias-corrected.
    Scattered as deltas like sparse_adagrad_reference."""
    vm = valid[:, None].astype(gm.dtype)
    m1_rows, m2_rows = m1[uids], m2[uids]
    m1_new = b1 * m1_rows + (1 - b1) * gm
    m2_new = b2 * m2_rows + (1 - b2) * gm * gm
    p_delta = -lr * m1_new / (jnp.sqrt(m2_new) + eps) * vm
    return (p.at[uids].add(p_delta),
            m1.at[uids].add((m1_new - m1_rows) * vm),
            m2.at[uids].add((m2_new - m2_rows) * vm))


def _adagrad_kernel(uids_ref, valid_ref, lr_ref, gm_ref, p_ref, m_ref,
                    p_out, m_out, *, eps):
    i = pl.program_id(0)
    r = pl.num_programs(0) - 1 - i
    vm = (valid_ref[r] > 0).astype(jnp.float32)
    lr = lr_ref[0, 0]
    g = gm_ref[0]                       # (1, D) merged grad for this slot
    p_row = p_ref[0]
    m_row = m_ref[0]
    m_new = m_row + g * g
    p_delta = -lr * g / (jnp.sqrt(m_new) + eps) * vm
    p_out[0] = p_row + p_delta
    m_out[0] = m_row + (m_new - m_row) * vm


def _adam_kernel(uids_ref, valid_ref, lr_ref, gm_ref, p_ref, m1_ref,
                 m2_ref, p_out, m1_out, m2_out, *, b1, b2, eps):
    i = pl.program_id(0)
    r = pl.num_programs(0) - 1 - i
    vm = (valid_ref[r] > 0).astype(jnp.float32)
    lr = lr_ref[0, 0]
    g = gm_ref[0]
    p_row = p_ref[0]
    m1_row = m1_ref[0]
    m2_row = m2_ref[0]
    m1_new = b1 * m1_row + (1 - b1) * g
    m2_new = b2 * m2_row + (1 - b2) * g * g
    p_delta = -lr * m1_new / (jnp.sqrt(m2_new) + eps) * vm
    p_out[0] = p_row + p_delta
    m1_out[0] = m1_row + (m1_new - m1_row) * vm
    m2_out[0] = m2_row + (m2_new - m2_row) * vm


def _fused_rows_call(kern, tables, uids, gm, valid, lr, interpret):
    """The pallas_call both optimizers share: every table in `tables`
    ([V, D]) is read and written one merged row per grid step, aliased
    in place. Rows ride as a leading blocked dim ([V, 1, D], see the
    module docstring); the uid vector is the index map, walked in
    REVERSE (the hazard analysis above)."""
    n, d = gm.shape
    uids = uids.astype(jnp.int32)
    valid = valid.astype(jnp.int32)
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    row = pl.BlockSpec((1, 1, d), lambda i, u, v: (u[n - 1 - i], 0, 0))
    slot = pl.BlockSpec((1, 1, d), lambda i, u, v: (n - 1 - i, 0, 0))
    k = len(tables)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), slot]
            + [row] * k,
            out_specs=[row] * k,
        ),
        out_shape=[jax.ShapeDtypeStruct((t.shape[0], 1, d), t.dtype)
                   for t in tables],
        # flattened arg indices (scalar prefetch counts): uids 0, valid
        # 1, lr 2, gm 3, then the tables from 4 — updated in place
        input_output_aliases={4 + i: i for i in range(k)},
        interpret=bool(interpret),
    )(uids, valid, lr2, gm.reshape(n, 1, d),
      *[t.reshape(t.shape[0], 1, d) for t in tables])
    return tuple(o.reshape(t.shape) for o, t in zip(out, tables))


def fused_sparse_adagrad(p, m, uids, gm, valid, lr, eps, *, interpret):
    """Apply the merged sparse adagrad update in one pallas call.
    Same contract as the optim_ops fallback: returns (ParamOut,
    MomentOut) full tables; invalid slots are exact no-ops.
    interpret=False compiles through Mosaic (TPU only)."""
    kern = functools.partial(_adagrad_kernel, eps=float(eps))
    return _fused_rows_call(kern, (p, m), uids, gm, valid, lr, interpret)


def fused_sparse_adam(p, m1, m2, uids, gm, valid, lr, b1, b2, eps, *,
                      interpret):
    """Apply the merged sparse adam update in one pallas call. `lr` is
    the bias-corrected rate (the caller applies the beta-pow correction
    exactly as the fallback does). Returns (ParamOut, Moment1Out,
    Moment2Out) full tables. interpret=False compiles through Mosaic
    (TPU only)."""
    kern = functools.partial(_adam_kernel, b1=float(b1), b2=float(b2),
                             eps=float(eps))
    return _fused_rows_call(kern, (p, m1, m2), uids, gm, valid, lr,
                            interpret)
