"""The sharded-lookup wire: bucket -> dedup -> all_to_all -> gather -> return.

TPU-native rebuild of the reference's pserver `lookup_table` dispatch
(distribute_transpiler.py split table rows across parameter servers and
issued gRPC prefetch per shard). Here the table is row-sharded over ONE
mesh axis (`ParamAttr(sharding=(axis, None))`) and a lookup is a fixed
four-beat exchange inside a shard_map, the same machinery as
parallel/moe.py's expert dispatch:

  1. bucket  — each shard takes its slice of the flattened id vector and
               computes, per id, the owning shard (id // rows_per_shard);
  2. dedup   — ids are sorted and duplicates collapse onto one wire slot
               (the MergeAdd idea applied to the QUERY side: a hot id
               crosses the ICI once per shard, not once per occurrence);
  3. exchange— ONE lax.all_to_all ships each shard's per-owner query
               buckets; owners gather their local rows; a second
               all_to_all ships the rows back (the moe send/recv pattern,
               parallel/moe.py:165);
  4. return  — rows fan back out over the duplicate map and unsort into
               request order.

Static shapes throughout: per-shard query capacity is ceil(n/ws) ids and
the wire buffers are [ws, cap] / [ws, cap, D] — worst case (every id owned
by one shard) still fits, so unlike MoE packing NOTHING is ever dropped;
dedup narrows the rows actually gathered, not the buffer. All functions
are pure JAX, usable directly or through the `lookup_table` op
(ops_impl/embedding_ops.py). See docs/embedding.md for the wire diagram.
"""
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ['sharded_lookup', 'dedup_plan', 'pad_vocab', 'wire_stats']

# sentinel sorted past every real id so padded query slots never start a
# dedup segment or perturb a real bucket (int32-safe)
_PAD_ID = jnp.iinfo(jnp.int32).max // 2


def pad_vocab(vocab, axis_size):
    """Round a vocab size up to a multiple of the mesh axis so the table
    row-shards evenly (the analysis pass rejects untileable tables —
    EmbeddingShardUntileable). The padding rows are never looked up; their
    optimizer state stays zero under the sparse path."""
    vocab, axis_size = int(vocab), int(axis_size)
    return ((vocab + axis_size - 1) // axis_size) * axis_size


def dedup_plan(ids, valid=None):
    """Collapse duplicate ids onto shared slots (static shapes).

    Returns (uids, seg, order, n_unique):
      uids     int32[c] — unique ids compacted to the front (slots past
                          n_unique hold the _PAD_ID sentinel);
      seg      int32[c] — for each SORTED position, its unique slot;
      order    int32[c] — argsort(ids): sorted position i holds request
                          order[i] (unsort via zeros.at[order].set(...));
      n_unique int32[]  — live unique count.
    `valid` masks padded query slots (they sort last via _PAD_ID and never
    open a segment)."""
    c = ids.shape[0]
    if valid is None:
        valid = jnp.ones((c,), bool)
    keyed = jnp.where(valid, ids, _PAD_ID)
    order = jnp.argsort(keyed)
    sid = keyed[order]
    svalid = valid[order]
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]]) & svalid
    seg = jnp.cumsum(is_first) - 1                    # [c] slot per sorted pos
    # min-scatter: within a real segment every sid is equal, and invalid
    # tails carry the sentinel, which can never undercut a real id
    uids = jnp.full((c,), _PAD_ID, jnp.int32).at[seg].min(
        sid.astype(jnp.int32))
    return uids, seg, order, jnp.sum(is_first)


def _pack_queries(uids, n_unique, ws, rows_per_shard):
    """Bucket unique ids by owning shard into the [ws, c] wire buffer
    (the moe cumsum-slot pack, parallel/moe.py pack_topk — capacity c
    means nothing ever drops). Returns (send_ids, send_valid, owner, slot)
    with owner/slot the return map for the rows coming back."""
    c = uids.shape[0]
    valid_u = jnp.arange(c) < n_unique
    owner = jnp.clip(uids // rows_per_shard, 0, ws - 1)
    onehot = jax.nn.one_hot(owner, ws, dtype=jnp.int32) * \
        valid_u[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot         # 1-based within owner
    slot = jnp.sum(pos, axis=-1) - 1                  # [c]
    # scatter-add: (owner, slot) pairs are unique for live queries by the
    # cumsum construction; dead slots all add zeros at (0, 0)
    o = jnp.where(valid_u, owner, 0)
    s = jnp.where(valid_u, slot, 0)
    send_ids = jnp.zeros((ws, c), jnp.int32).at[o, s].add(
        jnp.where(valid_u, uids, 0))
    send_valid = jnp.zeros((ws, c), jnp.int32).at[o, s].add(
        valid_u.astype(jnp.int32)) > 0
    return send_ids, send_valid, owner, slot


def _exchange(w_local, send_ids, send_valid, axis):
    """The two all_to_alls around the local gather. Device j receives
    every peer's query bucket for j's row block, answers from its local
    shard, and ships the rows back in the same [ws, cap] layout."""
    ws, cap = send_ids.shape
    rows_local = w_local.shape[0]
    base = lax.axis_index(axis) * rows_local
    recv_ids = lax.all_to_all(send_ids, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    recv_valid = lax.all_to_all(send_valid, axis, split_axis=0,
                                concat_axis=0, tiled=True)
    local_idx = jnp.clip(recv_ids - base, 0, rows_local - 1)
    rows = jnp.where(recv_valid[..., None],
                     w_local[local_idx], 0).astype(w_local.dtype)
    return lax.all_to_all(rows, axis, split_axis=0, concat_axis=0,
                          tiled=True)                 # [ws, cap, D]


def _shard_body(axis, ws):
    def body(w_local, ids_local, valid_local):
        rows_per_shard = w_local.shape[0]
        uids, seg, order, n_unique = dedup_plan(ids_local, valid_local)
        send_ids, send_valid, owner, slot = _pack_queries(
            uids, n_unique, ws, rows_per_shard)
        back = _exchange(w_local, send_ids, send_valid, axis)
        urows = back[owner, slot]                     # [c, D] unique rows
        sorted_rows = urows[seg]                      # fan out duplicates
        out = jnp.zeros_like(sorted_rows).at[order].set(sorted_rows)
        return jnp.where(valid_local[:, None], out, 0)
    return body


def sharded_lookup(w, ids, mesh, axis, padding_idx=None):
    """Gather rows of a row-sharded table: `w` [V, D] sharded (axis, None),
    `ids` any int shape; returns ids.shape + [D].

    The flat id vector is split over `axis` (each shard runs the wire on
    its ceil(n/ws) slice, padded with sentinel slots), so query traffic
    scales down with the mesh exactly like the table's rows do. V must be
    a multiple of the axis size (pad_vocab; statically checked by
    fluid.analysis.sharding for annotated programs)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ws = mesh.shape[axis]
    V, D = w.shape
    if V % ws:
        raise ValueError(
            'sharded_lookup: vocab %d is not divisible by mesh axis %r '
            'size %d — pad the table (embedding.pad_vocab)' % (V, axis, ws))
    ids_flat = ids.reshape(-1).astype(jnp.int32)
    n = ids_flat.shape[0]
    n_pad = -(-n // ws) * ws
    valid = jnp.arange(n_pad) < n
    ids_wire = jnp.concatenate(
        [ids_flat, jnp.zeros((n_pad - n,), jnp.int32)]) if n_pad != n \
        else ids_flat

    # manual over the WHOLE mesh with unmentioned axes replicated: on a
    # mixed mesh (dp x model) every dp group therefore repeats the
    # identical full-batch exchange — redundant wire traffic, correct
    # numerics. Going manual over the table axis only (axis_names=
    # {axis}, other axes auto) is the fix once the floor jax supports
    # partial-auto shard_map with all_to_all (0.4.x crashes on it);
    # single-axis meshes — the huge-vocab deployment shape — are
    # unaffected either way.
    fn = shard_map(
        _shard_body(axis, ws), mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis)),
        out_specs=P(axis, None), check_vma=False)
    out = fn(w, ids_wire, valid)[:n]
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids_flat == padding_idx)[:, None], 0.0, out)
    return out.reshape(ids.shape + (D,))


def wire_stats(n_ids, vocab, dim, axis_size, itemsize=4):
    """Static wire accounting for one lookup (docs/embedding.md + the
    embedding.lookup obs event): per-shard query capacity and the bytes
    each device puts on the ICI per exchange direction."""
    cap = -(-int(n_ids) // int(axis_size))
    return {
        'ids': int(n_ids), 'vocab': int(vocab), 'dim': int(dim),
        'axis_size': int(axis_size), 'query_capacity': cap,
        'id_bytes_per_device': cap * int(axis_size) * 4,
        'row_bytes_per_device': cap * int(axis_size) * int(dim) * itemsize,
    }
