"""Int8 embedding ROW codec: quantized storage, fp32 optimizer moments.

The embedding tier store (tiers.py, PR 18) and the delta publisher
(streaming/publish.py) both move table ROWS around — HBM bytes on the
serving side, wire bytes on the freshness loop. Row values tolerate
int8 (each row carries its own absmax scale — the per-channel axis-0
scheme of ops_impl/quant_ops.py, ONE definition of the rounding), while
the optimizer MOMENTS that ride next to them in training do not: their
magnitudes span the whole schedule, so moments stay fp32 and only the
VALUE bytes shrink. Note the HostArena (tiers.py) stores a slot's
table+moment rows in one homogeneous block and therefore keeps fp32 —
int8 rows pay off at the two boundaries where values travel ALONE: the
delta push (wire bytes per row: 4*D -> D + 4) and the quantized
serving table (quant_lookup_table's HBM:
docs/perf.md#quantized-inference).
"""
import numpy as np

__all__ = ['quantize_rows', 'dequantize_rows', 'row_bytes',
           'ROW_SCALE_BYTES']

# one f32 absmax scale per row rides with the int8 payload
ROW_SCALE_BYTES = 4


def quantize_rows(vals):
    """[N, D] float rows -> (q int8 [N, D], scale f32 [N, 1]) with
    per-row symmetric absmax scales. Pure numpy (the publisher runs
    host-side, off the step path); same rounding as
    ops_impl.quant_ops.quantize_array(axis=0)."""
    vals = np.asarray(vals, np.float32)
    amax = np.max(np.abs(vals), axis=tuple(range(1, vals.ndim)),
                  keepdims=True)
    scale = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
    q = np.clip(np.round(vals / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_rows(q, scale):
    """Invert quantize_rows: [N, D] int8 + [N, 1] f32 -> f32 rows. The
    round-trip error bound is half a step per element:
    |deq(q(x)) - x| <= max|x_row| / 254."""
    return q.astype(np.float32) * np.asarray(scale, np.float32)


def row_bytes(q, scale):
    """Payload bytes of a quantized row batch (values + scales) — what
    the delta push puts on the wire per table."""
    return int(np.asarray(q).nbytes + np.asarray(scale).nbytes)
