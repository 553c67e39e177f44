"""Sampled-softmax-family and beam-search rules.

Parity: reference paddle/fluid/operators/{nce,hierarchical_sigmoid,
beam_search,beam_search_decode}_op.* — the reference implements these as
host-side loops over LoD structures (NCE sampling with a CPU sampler,
hsigmoid via MatrixBitCodeFunctor, beam search via LoD pruning).

TPU-first: NCE samples negatives with the step PRNG and evaluates one
batched [B, k+T] gather-matmul (MXU); hsigmoid turns the complete-binary-
tree path walk into a static [B, max_depth] gather + masked BCE; beam
search is a dense [batch, beam] top-k with explicit parent pointers
(replacing LoD lineage), so the whole decode loop stays on device.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..lowering import register, data_of, like, SeqValue, use_kernel


@register('nce')
def _nce(ins, attrs, ctx):
    """Noise-contrastive estimation with a uniform noise distribution
    (reference nce_op.h defaults): binary logistic loss on the true class
    vs num_neg sampled classes, logits corrected by log(k*q)."""
    x = data_of(ins['Input'][0])                         # [B, D]
    label = data_of(ins['Label'][0]).astype(jnp.int32)   # [B, T]
    if label.ndim == 1:
        label = label[:, None]
    w = data_of(ins['Weight'][0])                        # [N, D]
    b = data_of(ins['Bias'][0]) if ins.get('Bias') else None   # [N, 1]
    N = int(attrs['num_total_classes'])
    k = int(attrs.get('num_neg_samples', 10))
    B, T = label.shape

    neg = jax.random.randint(ctx.rng(), (k,), 0, N)      # shared noise draw
    log_kq = jnp.log(jnp.asarray(k / N, x.dtype))

    def logits_for(idx_2d):
        wr = jnp.take(w, idx_2d, axis=0)                 # [..., D]
        out = jnp.einsum('bd,b...d->b...', x, wr)
        if b is not None:
            out = out + jnp.take(b[:, 0], idx_2d)
        return out

    true_logit = logits_for(label) - log_kq              # [B, T]
    neg_logit = logits_for(jnp.broadcast_to(neg[None, :], (B, k))) - log_kq

    pos_loss = jnp.sum(jax.nn.softplus(-true_logit), axis=1)
    neg_loss = jnp.sum(jax.nn.softplus(neg_logit), axis=1)
    cost = (pos_loss + neg_loss)[:, None]
    if ins.get('SampleWeight'):
        cost = cost * data_of(ins['SampleWeight'][0]).reshape(B, 1)
    return {'Cost': cost,
            'SampleLogits': jnp.concatenate([true_logit, neg_logit], axis=1),
            'SampleLabels': jnp.concatenate(
                [label, jnp.broadcast_to(neg[None, :], (B, k))],
                axis=1).astype(jnp.int64)}


@register('hierarchical_sigmoid')
def _hsigmoid(ins, attrs, ctx):
    """Complete-binary-tree hierarchical sigmoid (reference
    hierarchical_sigmoid_op.h SimpleCode): leaf for class c is heap node
    c + num_classes; the root->leaf internal nodes and branch bits come
    from the binary representation, evaluated as one masked gather."""
    x = data_of(ins['X'][0])                             # [B, D]
    w = data_of(ins['W'][0])                             # [num_classes-1, D]
    label = data_of(ins['Label'][0]).astype(jnp.int32)
    if label.ndim > 1:
        label = label.reshape(label.shape[0])
    bias = data_of(ins['Bias'][0]) if ins.get('Bias') else None
    C = int(attrs['num_classes'])
    B = x.shape[0]
    max_len = max(1, int(np.ceil(np.log2(C))))

    code = label + C                                     # heap leaf id
    # path length = floor(log2(code)); static loop over max depth
    length = jnp.floor(jnp.log2(code.astype(jnp.float32))).astype(jnp.int32)
    j = jnp.arange(max_len)[None, :]                     # [1, L]
    valid = j < length[:, None]
    shift = jnp.maximum(length[:, None] - j, 1)
    anc = jnp.right_shift(code[:, None], shift)          # ancestor heap ids
    bit = jnp.right_shift(code[:, None], shift - 1) & 1
    idx = jnp.clip(anc - 1, 0, C - 2)                    # weight row

    wr = jnp.take(w, idx, axis=0)                        # [B, L, D]
    pre = jnp.einsum('bd,bld->bl', x, wr)
    if bias is not None:
        pre = pre + jnp.take(bias.reshape(-1), idx)
    pre = jnp.clip(pre, -40.0, 40.0)
    # BCE with logits, target = bit
    loss = jax.nn.softplus(pre) - bit * pre
    out = jnp.sum(jnp.where(valid, loss, 0.0), axis=1, keepdims=True)
    return {'Out': out, 'PreOut': pre}


@register('beam_search')
def _beam_search(ins, attrs, ctx):
    """One beam step on dense [batch*beam, K] candidates: joint top-k over
    beam*K per source, with explicit parent pointers instead of the
    reference's LoD lineage. Finished beams (pre_id == end_id) contribute a
    single end_id candidate carrying their accumulated score forward.

    When the inputs are capacity-form 2-level SeqValues — the book's
    While-loop LoD decoder running verbatim — the step instead follows the
    reference beam_search_op.cc algorithm exactly (ops_impl/lod_beam.py)."""
    from ..lowering import SeqValue
    from .lod_beam import normalize_capacity, beam_search_step
    psc = ins['pre_scores'][0] if ins.get('pre_scores') else None
    if isinstance(psc, SeqValue) and psc.outer_lengths:
        p_ids, p_sc, cids, csc = normalize_capacity(
            ins['pre_ids'][0], psc, ins['ids'][0], ins['scores'][0],
            int(attrs['beam_size']))
        sel_ids, sel_scores, parents = beam_search_step(
            p_ids, p_sc, cids, csc, int(attrs['beam_size']),
            int(attrs['end_id']))
        return {'selected_ids': sel_ids, 'selected_scores': sel_scores,
                'parent_idx': parents.astype(jnp.int64)}
    pre_ids = data_of(ins['pre_ids'][0]).astype(jnp.int32)   # [B*b, 1]
    ids = data_of(ins['ids'][0]).astype(jnp.int32)           # [B*b, K]
    scores = data_of(ins['scores'][0]).astype(jnp.float32)   # [B*b, K]
    beam = int(attrs['beam_size'])
    end_id = int(attrs['end_id'])
    Bb, K = ids.shape
    B = Bb // beam

    finished = (pre_ids[:, 0] == end_id)                 # [B*b]
    if not ins.get('pre_scores'):
        raise ValueError(
            "beam_search requires pre_scores (the previous step's "
            "selected_scores) to carry finished beams' scores forward")
    keep_score = data_of(ins['pre_scores'][0]).astype(jnp.float32).reshape(Bb)
    # finished: only candidate 0 is live (end_id, score carried unchanged)
    cand_scores = jnp.where(
        finished[:, None],
        jnp.where(jnp.arange(K)[None, :] == 0,
                  keep_score[:, None], -jnp.inf),
        scores)
    cand_ids = jnp.where(finished[:, None], end_id, ids)

    flat_scores = cand_scores.reshape(B, beam * K)
    top_scores, top_pos = lax.top_k(flat_scores, beam)   # [B, beam]
    # global flat row index into [B*beam]: directly gatherable for
    # dense beam-state reordering (contrib BeamSearchDecoder)
    parent = top_pos // K + jnp.arange(B)[:, None] * beam
    sel_ids = jnp.take_along_axis(cand_ids.reshape(B, beam * K), top_pos,
                                  axis=1)
    return {'selected_ids': sel_ids.reshape(Bb, 1).astype(jnp.int64),
            'selected_scores': top_scores.reshape(Bb, 1),
            'parent_idx': parent.reshape(Bb).astype(jnp.int64)}


@register('attention_lstm_beam_decode')
def _attention_lstm_beam_decode(ins, attrs, ctx):
    """Whole beam-search generation as ONE lax.scan (TPU-first fusion of the
    reference's While-loop decoder in book test_machine_translation.py:
    decode()): embed -> attend -> LSTM cell -> project -> joint top-k ->
    reorder beams, all inside a single XLA while loop. Weights match the
    training-time `attention_lstm_decoder` op, so a trained model decodes
    with no re-plumbing.

    Inputs: EncOut [B,S,D] (SeqValue), WDec [E+D,4H], UDec [H,4H],
    BDec [1,4H], WAttnQ [H,D], WEmb [V,E], WOut [H,V], BOut [1,V].
    Attrs: beam_size, max_len, start_id, end_id.
    Outputs: SentenceIds [B, beam, max_len], SentenceScores [B, beam]."""
    enc = ins['EncOut'][0]
    enc_data = data_of(enc)                              # [B, S, D]
    if isinstance(enc, SeqValue):
        enc_mask = enc.mask(jnp.float32)
    else:
        enc_mask = jnp.ones(enc_data.shape[:2], jnp.float32)
    w_dec = data_of(ins['WDec'][0])
    u_dec = data_of(ins['UDec'][0])
    b_dec = data_of(ins['BDec'][0]) if ins.get('BDec') else 0.0
    w_q = data_of(ins['WAttnQ'][0])
    w_emb = data_of(ins['WEmb'][0])
    w_out = data_of(ins['WOut'][0])
    b_out = data_of(ins['BOut'][0]) if ins.get('BOut') else 0.0

    beam = int(attrs['beam_size'])
    max_len = int(attrs['max_len'])
    start_id = int(attrs.get('start_id', 0))
    end_id = int(attrs['end_id'])
    B, S, D = enc_data.shape
    H = u_dec.shape[0]

    enc_t = jnp.repeat(enc_data, beam, axis=0)           # [Bb, S, D]
    mask_t = jnp.repeat(enc_mask, beam, axis=0)
    params = (w_dec, u_dec, b_dec, w_q, w_emb, w_out, b_out)

    # the scan body IS the step-form decode (lod_beam.attention_beam_step)
    # the continuous-batching engine drives slot by slot — one definition,
    # so serving/decode.py's per-step path and this fused whole-sequence
    # scan are fetch-equivalent by construction
    from .lod_beam import attention_beam_step, beam_init_carry

    def step(carry, _):
        return attention_beam_step(params, enc_t, mask_t, carry, beam,
                                   end_id)

    (_, _, _, accN, _), (ids_seq, par_seq, sc_seq) = lax.scan(
        step, beam_init_carry(B, beam, H, start_id, enc_data.dtype),
        None, length=max_len)

    def back(beam_ptr, xs):
        ids_t, par_t = xs                                 # [B, beam]
        tok = jnp.take_along_axis(ids_t, beam_ptr, axis=1)
        return jnp.take_along_axis(par_t, beam_ptr, axis=1), tok

    init = jnp.broadcast_to(jnp.arange(beam)[None, :], (B, beam))
    _, toks_rev = lax.scan(back, init,
                           (jnp.flip(ids_seq, 0), jnp.flip(par_seq, 0)))
    sent = jnp.flip(jnp.transpose(toks_rev, (1, 2, 0)), -1)
    return {'SentenceIds': sent.astype(jnp.int64),
            'SentenceScores': accN.reshape(B, beam)}


@register('attention_lstm_beam_decode_step')
def _attention_lstm_beam_decode_step(ins, attrs, ctx):
    """A BUNDLE of decode steps (attr `bundle`, default 1) over a fixed
    pool of independent SLOTS — the step-form factoring of
    `attention_lstm_beam_decode`'s scan body that the continuous-batching
    engine (paddle_tpu.serving.decode) drives: sequences join/leave
    between dispatches on the host while this op advances every ACTIVE
    slot's beam state in place. bundle>1 runs that many steps inside one
    XLA module (the PR 4 K-step-bundling move applied to decode: per-call
    dispatch/sync cost is paid once per bundle, not once per token);
    slots that finish mid-bundle freeze in-graph — their state, history
    and step count stop advancing — so results are bit-identical to
    bundle=1, only the host's release granularity coarsens.

    State inputs (all persistable; written ones re-emitted under *Out so
    the memory plan donates them — in-place HBM updates per step):
      H, C        [slots, beam, hidden]   LSTM carry
      PrevIds     [slots, beam] int32     last selected token per beam
      Acc         [slots, beam] float32   accumulated log-probs
      Fin         [slots, beam] bool      beam emitted end_id
      IdsHist     [slots, max_len, beam]  int32 emitted tokens per step
      ParHist     [slots, max_len, beam]  int32 parent pointers per step
      Step        [slots] int32           steps taken by the occupant
      Active      [slots] bool            slot occupied and decoding
    Read-only state (not written, so not donated — no per-step copy):
      Enc [slots, src_cap, D], Mask [slots, src_cap],
      Limit [slots] int32 (per-request max decode length <= max_len).
    Weights: same tensors as attention_lstm_beam_decode.

    Outputs additionally expose Done [slots] (slot finished within THIS
    bundle: all beams ended, its per-request limit hit, or poisoned) and
    Bad [slots] (NaN detected in the slot's new scores — the
    anomaly-guard where-select pattern: every state update is masked by
    Active, so a dead or poisoned slot never perturbs a live one, and a
    poisoned slot is released alone).
    """
    from .lod_beam import attention_beam_step

    h = data_of(ins['H'][0])
    c = data_of(ins['C'][0])
    prev_ids = data_of(ins['PrevIds'][0]).astype(jnp.int32)
    acc = data_of(ins['Acc'][0]).astype(jnp.float32)
    fin = data_of(ins['Fin'][0]).astype(bool)
    enc = data_of(ins['Enc'][0])
    mask = data_of(ins['Mask'][0])
    ids_hist = data_of(ins['IdsHist'][0]).astype(jnp.int32)
    par_hist = data_of(ins['ParHist'][0]).astype(jnp.int32)
    step = data_of(ins['Step'][0]).astype(jnp.int32)
    limit = data_of(ins['Limit'][0]).astype(jnp.int32)
    active_in = data_of(ins['Active'][0]).astype(bool)
    params = (data_of(ins['WDec'][0]), data_of(ins['UDec'][0]),
              data_of(ins['BDec'][0]) if ins.get('BDec') else 0.0,
              data_of(ins['WAttnQ'][0]), data_of(ins['WEmb'][0]),
              data_of(ins['WOut'][0]),
              data_of(ins['BOut'][0]) if ins.get('BOut') else 0.0)

    slots, beam = prev_ids.shape
    t_cap = ids_hist.shape[1]
    end_id = int(attrs['end_id'])
    bundle = int(attrs.get('bundle', 1))

    enc_t = jnp.repeat(enc, beam, axis=0)            # [slots*beam, S, D]
    mask_t = jnp.repeat(mask, beam, axis=0)
    flat = lambda a: a.reshape((slots * beam,) + a.shape[2:])
    unflat = lambda a: a.reshape((slots, beam) + a.shape[1:])

    def one_step(carry, _):
        h, c, prev, acc, fin, ids_h, par_h, step, active, bad_acc = carry
        (h2, c2, ids2, acc2, fin2), (sel_ids, parent) = \
            _masked_beam_advance(params, enc_t, mask_t,
                                 (h, c, prev, acc, fin), active, beam,
                                 end_id)

        # per-slot history write at each slot's OWN step index
        at_t = ((jnp.arange(t_cap)[None, :] == step[:, None])
                & active[:, None])                   # [slots, t_cap]
        ids_h2 = jnp.where(at_t[:, :, None], sel_ids[:, None, :], ids_h)
        par_h2 = jnp.where(at_t[:, :, None], parent[:, None, :], par_h)
        step2 = step + active.astype(jnp.int32)

        acc_s = unflat(acc2)
        fin_s = unflat(fin2)
        bad_t = active & jnp.isnan(acc_s).any(axis=1)
        done_t = active & (fin_s.all(axis=1) | (step2 >= limit) | bad_t)
        return (h2, c2, ids2, acc2, fin2, ids_h2, par_h2, step2,
                active & ~done_t, bad_acc | bad_t), None

    carry0 = (flat(h), flat(c), flat(prev_ids), flat(acc), flat(fin),
              ids_hist, par_hist, step, active_in,
              jnp.zeros((slots,), bool))
    if bundle == 1:
        carry, _ = one_step(carry0, None)
    else:
        carry, _ = lax.scan(one_step, carry0, None, length=bundle)
    (h2, c2, ids2, acc2, fin2, ids_hist2, par_hist2, step2, active2,
     bad) = carry

    return {'HOut': unflat(h2), 'COut': unflat(c2),
            'PrevIdsOut': unflat(ids2), 'AccOut': unflat(acc2),
            'FinOut': unflat(fin2), 'IdsHistOut': ids_hist2,
            'ParHistOut': par_hist2, 'StepOut': step2,
            'ActiveOut': active2, 'Done': active_in & ~active2,
            'Bad': bad}


def _masked_beam_advance(params, enc_t, mask_t, carry5, active, beam,
                         end_id, attend=None):
    """One beam step over the slot pool with where-select masking (the
    anomaly guard's rollback pattern): only ACTIVE slots advance;
    everything else keeps its old state bit for bit, so joins/leaves
    between dispatches — and slots that finished earlier in a bundle —
    never disturb live ones. Shared by the dense and the paged step op
    so the two are bit-exact by construction. `attend` passes the paged
    op's fused-kernel attention through (lod_beam.attention_beam_step)."""
    from .lod_beam import attention_beam_step
    h, c, prev, acc, fin = carry5
    new_carry, (sel_ids, parent, _top) = attention_beam_step(
        params, enc_t, mask_t, carry5, beam, end_id, attend=attend)
    act_row = jnp.repeat(active, beam)               # [slots*beam]
    sel = lambda new, old: jnp.where(
        act_row.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
    return (sel(new_carry[0], h), sel(new_carry[1], c),
            sel(new_carry[2], prev), sel(new_carry[3], acc),
            sel(new_carry[4], fin)), (sel_ids, parent)


def _decode_weight_params(ins, prefix=''):
    """The WEIGHT_KEYS tuple from op inputs (prefix='Draft' pulls the
    draft model's tensors in the speculative step)."""
    return (data_of(ins[prefix + 'WDec'][0]),
            data_of(ins[prefix + 'UDec'][0]),
            data_of(ins[prefix + 'BDec'][0])
            if ins.get(prefix + 'BDec') else 0.0,
            data_of(ins[prefix + 'WAttnQ'][0]),
            data_of(ins[prefix + 'WEmb'][0]),
            data_of(ins[prefix + 'WOut'][0]),
            data_of(ins[prefix + 'BOut'][0])
            if ins.get(prefix + 'BOut') else 0.0)


def _gather_paged_enc(ins, src_cap):
    """Assemble per-slot encoder rows + attention mask from the page
    pools through the slot page tables — ONE in-graph gather per
    dispatch (amortized over the whole bundle), the PagedAttention
    lookup. Tail page-table entries point at the reserved ZERO page, so
    masked-out rows always read finite zeros."""
    pt_enc = data_of(ins['PtEnc'][0]).astype(jnp.int32)    # [C, NPE]
    enc_pages = data_of(ins['EncPages'][0])                # [Pe, ps, D]
    mask_pages = data_of(ins['MaskPages'][0])              # [Pe, ps]
    C, NPE = pt_enc.shape
    ps, D2 = enc_pages.shape[1], enc_pages.shape[2]
    enc = jnp.take(enc_pages, pt_enc, axis=0)              # [C,NPE,ps,D]
    enc = enc.reshape(C, NPE * ps, D2)[:, :src_cap]
    mask = jnp.take(mask_pages, pt_enc, axis=0).reshape(
        C, NPE * ps)[:, :src_cap]
    return enc, mask


def _paged_hist_write(pool, pt_hist, step, page_size, valid, rows,
                      n_pages):
    """Scatter one [slots, beam] history row into the page pool at each
    slot's own (page, offset): physical page = pt_hist[slot,
    step // page_size], offset = step % page_size. Invalid slots are
    redirected to the out-of-range page index and dropped — the page
    analogue of the dense op's where-select write."""
    lp = step // page_size                                 # [C] logical
    phys = jnp.take_along_axis(pt_hist, lp[:, None], axis=1)[:, 0]
    phys = jnp.where(valid, phys, n_pages)                 # drop
    off = step - lp * page_size
    return pool.at[phys, off].set(rows.astype(pool.dtype), mode='drop')


@register('attention_lstm_beam_paged_step')
def _attention_lstm_beam_paged_step(ins, attrs, ctx):
    """The paged-memory form of `attention_lstm_beam_decode_step`: the
    per-slot dense history/encoder buffers are replaced by fixed-size
    PAGES drawn from pool inputs, indexed through per-slot int32 page
    tables (serving/pages.py has the allocator; docs/serving.md the
    diagram). Shapes stay static: encoder rows are assembled by one
    in-graph gather per dispatch, history tokens scatter to
    (page_table[slot, step//page_size], step%page_size) with inactive
    rows dropped. The beam math, masking, bundling and Done/Bad
    semantics are the dense op's, shared code — the paged engine is
    bit-exact against the dense engine by construction
    (tests/test_decode.py's paged family drills it).

    State inputs (written -> donated): H, C, PrevIds, Acc, Fin, Step,
    Active as the dense op; HistIds/HistPar [pages, page_size, beam]
    are the token/parent history POOLS.
    Read-only: PtHist [slots, ceil(T/page_size)], PtEnc [slots,
    ceil(src_cap/page_size)] page tables (written at join time by the
    engine's scatter, constant during decode), EncPages [enc_pages,
    page_size, D], MaskPages [enc_pages, page_size], Limit.
    Attrs: beam_size, end_id, bundle, page_size, src_cap.
    """
    h = data_of(ins['H'][0])
    c = data_of(ins['C'][0])
    prev_ids = data_of(ins['PrevIds'][0]).astype(jnp.int32)
    acc = data_of(ins['Acc'][0]).astype(jnp.float32)
    fin = data_of(ins['Fin'][0]).astype(bool)
    step = data_of(ins['Step'][0]).astype(jnp.int32)
    limit = data_of(ins['Limit'][0]).astype(jnp.int32)
    active_in = data_of(ins['Active'][0]).astype(bool)
    pt_hist = data_of(ins['PtHist'][0]).astype(jnp.int32)
    hist_ids = data_of(ins['HistIds'][0])
    hist_par = data_of(ins['HistPar'][0])
    params = _decode_weight_params(ins)

    slots, beam = prev_ids.shape
    n_pages, page_size = hist_ids.shape[0], int(attrs['page_size'])
    end_id = int(attrs['end_id'])
    bundle = int(attrs.get('bundle', 1))
    src_cap = int(attrs['src_cap'])

    if use_kernel(ctx, 'paged_attention'):
        # fused path: the kernel reads the page POOLS through the page
        # table itself — the gathered [slots, S, D] buffer and its
        # per-beam repeat never materialize
        from ...ops.kernels import paged_attention
        pt_enc = data_of(ins['PtEnc'][0]).astype(jnp.int32)
        enc_pages = data_of(ins['EncPages'][0])
        mask_pages = data_of(ins['MaskPages'][0])
        enc_t = mask_t = None
        attend = lambda q: paged_attention(
            q, enc_pages, mask_pages, pt_enc, src_cap,
            interpret=ctx.pallas_interpret)
    else:
        attend = None
        enc, mask = _gather_paged_enc(ins, src_cap)
        enc_t = jnp.repeat(enc, beam, axis=0)        # [slots*beam, S, D]
        mask_t = jnp.repeat(mask, beam, axis=0)
    flat = lambda a: a.reshape((slots * beam,) + a.shape[2:])
    unflat = lambda a: a.reshape((slots, beam) + a.shape[1:])

    def one_step(carry, _):
        h, c, prev, acc, fin, ids_pool, par_pool, step, active, bad_acc \
            = carry
        (h2, c2, ids2, acc2, fin2), (sel_ids, parent) = \
            _masked_beam_advance(params, enc_t, mask_t,
                                 (h, c, prev, acc, fin), active, beam,
                                 end_id, attend=attend)
        ids_pool2 = _paged_hist_write(ids_pool, pt_hist, step, page_size,
                                      active, sel_ids, n_pages)
        par_pool2 = _paged_hist_write(par_pool, pt_hist, step, page_size,
                                      active, parent, n_pages)
        step2 = step + active.astype(jnp.int32)
        acc_s = unflat(acc2)
        fin_s = unflat(fin2)
        bad_t = active & jnp.isnan(acc_s).any(axis=1)
        done_t = active & (fin_s.all(axis=1) | (step2 >= limit) | bad_t)
        return (h2, c2, ids2, acc2, fin2, ids_pool2, par_pool2, step2,
                active & ~done_t, bad_acc | bad_t), None

    carry0 = (flat(h), flat(c), flat(prev_ids), flat(acc), flat(fin),
              hist_ids, hist_par, step, active_in,
              jnp.zeros((slots,), bool))
    if bundle == 1:
        carry, _ = one_step(carry0, None)
    else:
        carry, _ = lax.scan(one_step, carry0, None, length=bundle)
    (h2, c2, ids2, acc2, fin2, hist_ids2, hist_par2, step2, active2,
     bad) = carry

    return {'HOut': unflat(h2), 'COut': unflat(c2),
            'PrevIdsOut': unflat(ids2), 'AccOut': unflat(acc2),
            'FinOut': unflat(fin2), 'HistIdsOut': hist_ids2,
            'HistParOut': hist_par2, 'StepOut': step2,
            'ActiveOut': active2, 'Done': active_in & ~active2,
            'Bad': bad}


@register('attention_lstm_spec_decode_step')
def _attention_lstm_spec_decode_step(ins, attrs, ctx):
    """Speculative GREEDY decoding over the paged slot pool: a small
    DRAFT proposes spec_k tokens, the TARGET verifies them all in ONE
    dispatched module, accept/rollback entirely in-graph.

    Why it wins even for a recurrent target: the draft's proposals make
    every verify-step's INPUT token known up front, so the expensive
    position-independent work batches across all spec_k+1 positions —
    the embedding gather, the input half of the decoder matmul
    (x @ w_dec[:E]), and above all the [H, V] output projection +
    log-softmax/argmax run as ONE stacked matmul instead of one per
    step. Only the slim recurrence (attention query + ctx @ w_dec[E:] +
    h @ u_dec + cell) stays sequential. docs/serving.md carries the
    acceptance-rate math; the engine reports accept-rate from the
    Accepted output.

    Emission contract (token-exact vs greedy target-only decode, which
    is beam_size=1 through the paged step op): the emitted token at
    every position is the TARGET's own greedy argmax g_t; the draft
    only decides how many positions are valid. Position t is emitted
    iff every earlier proposal matched (d_s == g_s for s < t) and the
    slot is still within its limit and un-finished — so a slot emits
    between 1 and spec_k+1 tokens per dispatch (the +1 is the classic
    bonus token: verifying spec_k proposals yields spec_k+1 target
    distributions). Target, draft hidden state, and the next input
    token all roll back to the last VALID position in-graph
    (where-select gathers over the stacked per-position states).

    Draft forms (attr `draft`): 'weights' — a small attention-LSTM with
    its own Draft* weight inputs (same vocab + enc_dim as the target,
    any hidden/embedding size), state carried per slot in DraftH/DraftC;
    'table' — a [V] int32 next-token table input (DraftTable), the
    n-gram/prompt-lookup speculator: zero proposal cost, no state.

    State inputs as the paged beam op (beam dim fixed at 1) plus
    DraftH/DraftC [slots, draft_hidden] (weights draft only).
    Attrs: end_id, spec_k, page_size, src_cap, draft.
    Outputs additionally: Accepted [slots] int32 — draft proposals
    accepted this dispatch (emitted tokens minus the always-target
    correction/bonus token).
    """
    from .lod_beam import greedy_attend_cell

    h = data_of(ins['H'][0])[:, 0]                   # [C, Ht]
    c = data_of(ins['C'][0])[:, 0]
    prev = data_of(ins['PrevIds'][0]).astype(jnp.int32)[:, 0]
    acc = data_of(ins['Acc'][0]).astype(jnp.float32)[:, 0]
    fin = data_of(ins['Fin'][0]).astype(bool)[:, 0]
    step = data_of(ins['Step'][0]).astype(jnp.int32)
    limit = data_of(ins['Limit'][0]).astype(jnp.int32)
    active = data_of(ins['Active'][0]).astype(bool)
    pt_hist = data_of(ins['PtHist'][0]).astype(jnp.int32)
    hist_ids = data_of(ins['HistIds'][0])
    hist_par = data_of(ins['HistPar'][0])
    w_dec, u_dec, b_dec, w_q, w_emb, w_out, b_out = \
        _decode_weight_params(ins)

    C = prev.shape[0]
    n_pages, page_size = hist_ids.shape[0], int(attrs['page_size'])
    end_id = int(attrs['end_id'])
    spec_k = int(attrs['spec_k'])
    src_cap = int(attrs['src_cap'])
    R = spec_k + 1                       # verify steps = proposals + 1
    E = w_emb.shape[1]
    neg = jnp.finfo(jnp.float32).min

    if use_kernel(ctx, 'paged_attention'):
        # fused path (beam dim is 1 here): both the draft proposals and
        # the verify recurrence attend straight into the page pools
        from ...ops.kernels import paged_attention
        pt_enc = data_of(ins['PtEnc'][0]).astype(jnp.int32)
        enc_pages = data_of(ins['EncPages'][0])
        mask_pages = data_of(ins['MaskPages'][0])
        enc = mask = None
        attend = lambda q: paged_attention(
            q, enc_pages, mask_pages, pt_enc, src_cap,
            interpret=ctx.pallas_interpret)
    else:
        attend = None
        enc, mask = _gather_paged_enc(ins, src_cap)  # [C, S, D]

    # -- draft phase: propose spec_k tokens (and advance one past them,
    # so the draft state can roll back to any accepted position) -------
    if attrs.get('draft', 'weights') == 'table':
        table = data_of(ins['DraftTable'][0]).astype(jnp.int32)
        d_list, tok = [], prev
        for _ in range(R):
            tok = jnp.take(table, tok)
            d_list.append(tok)
        d_seq = jnp.stack(d_list)                    # [R, C]
        hd_seq = cd_seq = None
    else:
        dparams = _decode_weight_params(ins, prefix='Draft')
        h_d = data_of(ins['DraftH'][0])
        c_d = data_of(ins['DraftC'][0])

        def dstep(carry, _):
            hd, cd, tok = carry
            hd2, cd2, logits = greedy_attend_cell(dparams, enc, mask,
                                                  hd, cd, tok,
                                                  attend=attend)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (hd2, cd2, nxt), (nxt, hd2, cd2)

        _, (d_seq, hd_seq, cd_seq) = lax.scan(
            dstep, (h_d, c_d, prev), None, length=R)

    # -- verify phase: ONE bundled target pass over all R positions ----
    # batched (position-independent): embedding + input projection
    tok_in = jnp.concatenate([prev[None], d_seq[:R - 1]])    # [R, C]
    xw = jnp.take(w_emb, tok_in, axis=0) @ w_dec[:E] # [R, C, 4Ht]

    def vstep(carry, xw_t):
        h, c = carry
        q = h @ w_q
        if attend is not None:
            ctx_v = attend(q)
        else:
            scores = jnp.einsum('bd,bsd->bs', q, enc)
            scores = jnp.where(mask > 0, scores, neg)
            alpha = jax.nn.softmax(scores, axis=-1)
            ctx_v = jnp.einsum('bs,bsd->bd', alpha, enc)
        g = xw_t + ctx_v @ w_dec[E:] + h @ u_dec + b_dec
        gi, gf, gc, go = jnp.split(g, 4, axis=-1)
        c2 = jax.nn.sigmoid(gf) * c + jax.nn.sigmoid(gi) * jnp.tanh(gc)
        h2 = jax.nn.sigmoid(go) * jnp.tanh(c2)
        return (h2, c2), (h2, c2)

    _, (h_seq, c_seq) = lax.scan(vstep, (h, c), xw)  # [R, C, Ht]
    # batched: output projection + greedy choice over every position
    logp = jax.nn.log_softmax(
        (h_seq @ w_out + b_out).astype(jnp.float32), axis=-1)
    g_seq = jnp.argmax(logp, axis=-1).astype(jnp.int32)      # [R, C]
    lp_seq = jnp.take_along_axis(logp, g_seq[..., None],
                                 axis=-1)[..., 0]            # [R, C]

    # -- accept/rollback masking (all in-graph) ------------------------
    # position t (0-based) is emitted iff every earlier draft proposal
    # matched the target's own choice AND the slot is still live there
    match = g_seq[:R - 1] == d_seq[:R - 1]           # [R-1, C]
    valid = []
    v = active & ~fin & (step < limit)
    for t in range(R):
        if t > 0:
            v = (v & match[t - 1] & (g_seq[t - 1] != end_id)
                 & (step + t < limit))
        valid.append(v)
    valid = jnp.stack(valid)                         # [R, C] bool
    n_emit = valid.astype(jnp.int32).sum(axis=0)     # [C]
    accepted = (valid[:R - 1] & match).astype(jnp.int32).sum(axis=0)

    # history writes: each emitted token at its own (page, offset)
    ids_pool, par_pool = hist_ids, hist_par
    zero_par = jnp.zeros((C, 1), jnp.int32)          # beam 1: parent 0
    for t in range(R):
        ids_pool = _paged_hist_write(ids_pool, pt_hist, step + t,
                                     page_size, valid[t],
                                     g_seq[t][:, None], n_pages)
        par_pool = _paged_hist_write(par_pool, pt_hist, step + t,
                                     page_size, valid[t], zero_par,
                                     n_pages)

    # score accumulation in strict emission order (the greedy target-
    # only path's left fold)
    acc2 = acc
    for t in range(R):
        acc2 = acc2 + jnp.where(valid[t], lp_seq[t], 0.0)

    # roll back to the state after the LAST emitted token's input was
    # consumed: S_{n_emit} = h_seq[n_emit - 1]
    idx = jnp.maximum(n_emit - 1, 0)
    rows = jnp.arange(C)
    emitted_any = active & (n_emit > 0)
    pick = lambda seq, old: jnp.where(
        emitted_any.reshape((-1,) + (1,) * (old.ndim - 1)),
        seq[idx, rows], old)
    h2 = pick(h_seq, h)
    c2 = pick(c_seq, c)
    prev2 = jnp.where(emitted_any, g_seq[idx, rows], prev)
    acc2 = jnp.where(emitted_any, acc2, acc)
    out = {}
    if hd_seq is not None:
        out['DraftHOut'] = pick(hd_seq, data_of(ins['DraftH'][0]))
        out['DraftCOut'] = pick(cd_seq, data_of(ins['DraftC'][0]))

    fin2 = fin | (valid & (g_seq == end_id)).any(axis=0)
    step2 = step + n_emit
    bad = active & jnp.isnan(acc2)
    done = active & (fin2 | (step2 >= limit) | bad)
    active2 = active & ~done

    out.update({
        'HOut': h2[:, None], 'COut': c2[:, None],
        'PrevIdsOut': prev2[:, None], 'AccOut': acc2[:, None],
        'FinOut': fin2[:, None], 'HistIdsOut': ids_pool,
        'HistParOut': par_pool, 'StepOut': step2, 'ActiveOut': active2,
        'Done': active & ~active2, 'Bad': bad,
        'Accepted': jnp.where(active, accepted, 0)})
    return out


@register('beam_search_decode')
def _beam_search_decode(ins, attrs, ctx):
    """Backtrace stacked per-step beams into sentences.

    Dense contract (replaces the reference's LoDTensorArray walk): Ids and
    Scores are [T, batch, beam]; Parents [T, batch, beam] gives each
    step's source beam. Emits SentenceIds [batch, beam, T] (end_id padded)
    and SentenceScores [batch, beam] final accumulated scores.

    Passed the LoDTensorArrays themselves (the book's While-loop decoder
    verbatim), it backtraces them with the reference Backtrace algorithm
    instead (ops_impl/lod_beam.py) and emits 2-level LoD sentences."""
    from ..lowering import ArrayValue
    if isinstance(ins['Ids'][0], ArrayValue):
        if not ins['Ids'][0].is_seq:
            raise TypeError(
                'beam_search_decode on a LoDTensorArray requires LoD '
                '(beam_search-written) elements; for dense per-step beams '
                'pass stacked [T, batch, beam] tensors + parents instead '
                '(layers.beam_search_decode dense contract)')
        from .lod_beam import beam_search_decode_arrays
        sent_ids, sent_scores = beam_search_decode_arrays(
            ins['Ids'][0], ins['Scores'][0],
            int(attrs.get('beam_size', 0) or 0),
            int(attrs.get('end_id', 0)))
        return {'SentenceIds': sent_ids, 'SentenceScores': sent_scores}
    ids = data_of(ins['Ids'][0]).astype(jnp.int32)        # [T, B, beam]
    scores = data_of(ins['Scores'][0]).astype(jnp.float32)
    T, B, beam = ids.shape
    if ins.get('Parents'):
        # beam_search emits global [B*beam] rows; lineage here is per-source
        parents = data_of(ins['Parents'][0]).astype(jnp.int32) % beam
    else:
        parents = jnp.broadcast_to(jnp.arange(beam)[None, None, :],
                                   (T, B, beam))

    def back(beam_ptr, xs):
        ids_t, par_t = xs                                # [B, beam]
        tok = jnp.take_along_axis(ids_t, beam_ptr, axis=1)
        beam_ptr = jnp.take_along_axis(par_t, beam_ptr, axis=1)
        return beam_ptr, tok

    init = jnp.broadcast_to(jnp.arange(beam)[None, :], (B, beam))
    _, toks_rev = lax.scan(back, init, (jnp.flip(ids, 0), jnp.flip(parents, 0)))
    sent = jnp.flip(jnp.swapaxes(jnp.swapaxes(toks_rev, 0, 1), 1, 2), -1)
    if 'end_id' in attrs:
        end_id = int(attrs['end_id'])
        ended = jnp.cumsum((sent == end_id).astype(jnp.int32), axis=-1) > 0
        prev_ended = jnp.concatenate(
            [jnp.zeros_like(ended[..., :1]), ended[..., :-1]], axis=-1)
        sent = jnp.where(prev_ended, end_id, sent)  # pad past first end_id
    return {'SentenceIds': sent.astype(jnp.int64),
            'SentenceScores': scores[-1].reshape(B, beam)}
