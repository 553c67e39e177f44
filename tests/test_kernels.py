"""Pallas kernel layer + int8 quant drills (docs/perf.md#kernel-layer).

Three contracts, each A/B'd against the code path it replaces:

* registry/knob — the PADDLE_TPU_KERNELS / configure() grammar, and the
  executor compile cache keying on kernels.signature() (a knob flip
  recompiles; flipping back serves the cached module again).
* kernel parity — paged decode-attention and the fused sparse
  optimizers under the pallas INTERPRETER (this suite runs on
  JAX_PLATFORMS=cpu, so the kernel bodies execute for real) against
  their XLA fallbacks, within each kernel's documented tolerance:
  paged_attention <= 1e-5 + 1e-5*|ref| (online softmax reassociates),
  sparse adagrad/adam <= 1e-6 absolute (same per-row expressions).
  Knob-off stays BIT-identical to the pre-kernel lowering (the fallback
  branch IS the original code).
* int8 quant — the quant IR pass (QDQ pipeline form + offline
  quantize_weights) within the documented round-trip bound
  (max|x[ch]|/254 per element), and the DeltaPublisher's int8 wire
  cutting push bytes to <= 0.55x fp32.

Marker: `kernels` (pytest -m kernels; routed through
tools/fault_drill.sh with the other drill families).
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.layers as layers
from paddle_tpu.fluid import passes
from paddle_tpu.fluid.executor import global_scope
from paddle_tpu.fluid.passes import quant_pass
from paddle_tpu.ops import kernels

from util import fresh_program

pytestmark = pytest.mark.kernels

VOCAB, DIM = 16, 4


@pytest.fixture(autouse=True)
def _restore_knob():
    """Every test leaves the process-level knob exactly as it found it
    (enablement is global state; the suite must not leak it)."""
    prev = kernels._CONFIG
    try:
        yield
    finally:
        kernels.configure(prev)


# ---------------------------------------------------------------------------
# registry + knob grammar
# ---------------------------------------------------------------------------

def test_registry_catalog():
    names = kernels.available()
    for n in ('paged_attention', 'sparse_adagrad', 'sparse_adam'):
        assert n in names


def test_knob_grammar(monkeypatch):
    p = kernels._parse
    assert p(None) == frozenset()
    assert p('') == frozenset()
    assert p('0') == frozenset()
    assert p('off') == frozenset()
    assert p(False) == frozenset()
    everything = frozenset(kernels.available())
    assert p(True) == everything
    assert p('1') == everything
    assert p('all') == everything
    assert p('paged_attention') == frozenset(['paged_attention'])
    assert p('all,-sparse_adam') == everything - {'sparse_adam'}
    assert p(['sparse_adam', 'sparse_adagrad']) == frozenset(
        ['sparse_adam', 'sparse_adagrad'])
    # configure overrides the env while set; None hands back to the env
    monkeypatch.setenv(kernels.ENV_KERNELS, 'all')
    kernels.configure(False)
    assert not kernels.enabled('paged_attention')
    kernels.configure(None)
    assert kernels.enabled('paged_attention')
    # signature() is the enabled INTERSECTION of registered names (an
    # unknown name in the spec can never churn compile-cache keys)
    kernels.configure(['paged_attention', 'not_a_kernel'])
    assert kernels.signature() == ('paged_attention',)


# ---------------------------------------------------------------------------
# paged decode-attention parity (interpreter executes the kernel body)
# ---------------------------------------------------------------------------

def _paged_case(rng, C, beam, ps, npe, src_cap, D, masked_slot=None):
    """Random paged-encoder pool: each slot owns `npe` distinct pages,
    a per-slot length in [1, src_cap] sets the mask (0 rows for
    `masked_slot` — the fully-masked degenerate case)."""
    n_pages = C * npe + 2
    enc_pages = (rng.randn(n_pages, ps, D) * 0.5).astype(np.float32)
    mask_pages = np.zeros((n_pages, ps), np.float32)
    pt = rng.permutation(n_pages)[:C * npe].reshape(C, npe).astype(np.int32)
    for c in range(C):
        ln = 0 if masked_slot == c else int(rng.randint(1, src_cap + 1))
        for j in range(npe):
            for k in range(ps):
                if j * ps + k < ln:
                    mask_pages[pt[c, j], k] = 1.0
    q = (rng.randn(C * beam, D) * 0.7).astype(np.float32)
    return q, enc_pages, mask_pages, pt


@pytest.mark.parametrize('C,beam,ps,npe,src_cap,D', [
    (2, 3, 3, 2, 5, 16),
    (1, 1, 4, 3, 10, 8),
    (3, 2, 4, 2, 7, 8),
])
def test_paged_attention_parity(C, beam, ps, npe, src_cap, D):
    from paddle_tpu.ops.kernels import (paged_attention,
                                        paged_attention_reference)
    rng = np.random.RandomState(C * 100 + D)
    q, enc_pages, mask_pages, pt = _paged_case(rng, C, beam, ps, npe,
                                               src_cap, D)
    import jax.numpy as jnp
    args = (jnp.asarray(q), jnp.asarray(enc_pages),
            jnp.asarray(mask_pages), jnp.asarray(pt), src_cap)
    got = np.asarray(paged_attention(*args, interpret=True))
    ref = np.asarray(paged_attention_reference(*args))
    tol = 1e-5 + 1e-5 * np.abs(ref)            # the documented tolerance
    assert (np.abs(got - ref) <= tol).all(), \
        'max err %.3g' % np.abs(got - ref).max()


def test_paged_attention_fully_masked_slot():
    """A slot whose mask is all-zero degrades to the oracle's
    uniform-softmax over NEG_MASKED scores — same value, no NaN."""
    from paddle_tpu.ops.kernels import (paged_attention,
                                        paged_attention_reference)
    rng = np.random.RandomState(9)
    q, enc_pages, mask_pages, pt = _paged_case(rng, 2, 3, 3, 2, 5, 8,
                                               masked_slot=1)
    import jax.numpy as jnp
    args = (jnp.asarray(q), jnp.asarray(enc_pages),
            jnp.asarray(mask_pages), jnp.asarray(pt), 5)
    got = np.asarray(paged_attention(*args, interpret=True))
    ref = np.asarray(paged_attention_reference(*args))
    assert np.isfinite(got).all()
    assert (np.abs(got - ref) <= 1e-5 + 1e-5 * np.abs(ref)).all()


# ---------------------------------------------------------------------------
# fused sparse optimizers: parity vs the optim_ops fallback math
# ---------------------------------------------------------------------------

def _merged_case(rng, V=12, D=8):
    """A merged-row batch shaped like _merge_sparse output, including
    the write hazard the reversed grid exists for: a VALID uid-0 row at
    slot 1 while the invalid tail slots 3..5 are clamped to row 0."""
    import jax.numpy as jnp
    p = jnp.asarray((rng.randn(V, D) * 0.5).astype(np.float32))
    uids = jnp.asarray(np.array([3, 0, 7, 0, 0, 0], np.int32))
    valid = jnp.asarray(np.array([1, 1, 1, 0, 0, 0], np.int32))
    gm = (rng.randn(6, D) * 0.3).astype(np.float32)
    gm[3:] = 0.0                        # invalid merge slots carry zeros
    return p, uids, jnp.asarray(gm), valid


def test_fused_sparse_adagrad_parity():
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import fused_sparse_adagrad
    rng = np.random.RandomState(3)
    p, uids, gm, valid = _merged_case(rng)
    m = jnp.asarray(np.abs(rng.randn(*p.shape)).astype(np.float32))
    lr, eps = 0.1, 1e-6
    # the optim_ops._adagrad SelectedRows fallback, verbatim
    vm = valid.astype(jnp.float32)[:, None]
    m_rows = m[uids]
    m_new = m_rows + gm * gm
    p_delta = -lr * gm / (jnp.sqrt(m_new) + eps) * vm
    p_ref = p.at[uids].add(p_delta)
    m_ref = m.at[uids].add((m_new - m_rows) * vm)
    p_out, m_out = fused_sparse_adagrad(p, m, uids, gm, valid, lr, eps,
                                        interpret=True)
    assert np.abs(np.asarray(p_out) - np.asarray(p_ref)).max() <= 1e-6
    assert np.abs(np.asarray(m_out) - np.asarray(m_ref)).max() <= 1e-6


def test_fused_sparse_adam_parity():
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import fused_sparse_adam
    rng = np.random.RandomState(4)
    p, uids, gm, valid = _merged_case(rng)
    m1 = jnp.asarray((rng.randn(*p.shape) * 0.1).astype(np.float32))
    m2 = jnp.asarray(np.abs(rng.randn(*p.shape) * 0.1).astype(np.float32))
    b1, b2, eps = 0.9, 0.999, 1e-8
    lr = 0.01 * np.sqrt(1 - b2 ** 3) / (1 - b1 ** 3)  # bias-corrected
    vm = valid.astype(jnp.float32)[:, None]
    m1_rows, m2_rows = m1[uids], m2[uids]
    m1_new = b1 * m1_rows + (1 - b1) * gm
    m2_new = b2 * m2_rows + (1 - b2) * gm * gm
    p_delta = -lr * m1_new / (jnp.sqrt(m2_new) + eps) * vm
    p_ref = p.at[uids].add(p_delta)
    m1_ref = m1.at[uids].add((m1_new - m1_rows) * vm)
    m2_ref = m2.at[uids].add((m2_new - m2_rows) * vm)
    p_out, m1_out, m2_out = fused_sparse_adam(
        p, m1, m2, uids, gm, valid, lr, b1, b2, eps, interpret=True)
    for got, ref in ((p_out, p_ref), (m1_out, m1_ref), (m2_out, m2_ref)):
        assert np.abs(np.asarray(got) - np.asarray(ref)).max() <= 1e-6


def test_fused_sparse_all_invalid_is_bitwise_noop():
    """An all-padding merge (empty batch) must leave the tables
    BITWISE untouched — invalid slots write the row they read."""
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import fused_sparse_adagrad
    rng = np.random.RandomState(5)
    p = jnp.asarray((rng.randn(10, 6) * 0.5).astype(np.float32))
    m = jnp.asarray(np.abs(rng.randn(10, 6)).astype(np.float32))
    uids = jnp.zeros((4,), jnp.int32)
    valid = jnp.zeros((4,), jnp.int32)
    gm = jnp.zeros((4, 6), jnp.float32)
    p_out, m_out = fused_sparse_adagrad(p, m, uids, gm, valid, 0.1, 1e-6,
                                        interpret=True)
    assert np.array_equal(np.asarray(p_out), np.asarray(p))
    assert np.array_equal(np.asarray(m_out), np.asarray(m))


# ---------------------------------------------------------------------------
# program-level: knob-off bit-exactness, kernel-on parity, cache keying
# ---------------------------------------------------------------------------

def _sparse_model(opt_factory):
    """Tiny is_sparse embedding model; returns (exe, main, feed, loss)
    ready to run (startup already executed)."""
    ids = layers.data(name='ids', shape=[3, 1], dtype='int64')
    emb = layers.embedding(ids, size=[VOCAB, DIM], is_sparse=True,
                           param_attr=fluid.ParamAttr(name='emb_w'))
    pred = layers.fc(input=emb, size=1, num_flatten_dims=2,
                     bias_attr=False,
                     param_attr=fluid.ParamAttr(name='fc_w'))
    loss = layers.mean(layers.square(pred - 1.0))
    opt_factory().minimize(loss)
    return loss


def _run_sparse(opt_factory, steps=3, seed=0):
    """Train the tiny sparse model `steps` steps under the CURRENT knob
    state; returns (losses, final table, steady-state compile count —
    cache misses AFTER the first step, which must be 0)."""
    rng = np.random.RandomState(seed)
    feeds = [{'ids': rng.randint(0, VOCAB, size=(4, 3, 1)).astype('int64')}
             for _ in range(steps)]
    with fresh_program() as (main, startup):
        loss = _sparse_model(opt_factory)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(np.asarray(exe.run(main, feed=feeds[0],
                                           fetch_list=[loss])[0])
                        .reshape(-1)[0])]
        m1 = exe.cache_stats['misses']
        losses += [float(np.asarray(exe.run(main, feed=f,
                                            fetch_list=[loss])[0])
                         .reshape(-1)[0]) for f in feeds[1:]]
        steady = exe.cache_stats['misses'] - m1
        table = np.asarray(global_scope()._chain_get('emb_w'))
    return losses, table, steady


@pytest.mark.parametrize('opt,kname', [
    (lambda: fluid.optimizer.Adagrad(learning_rate=0.1), 'sparse_adagrad'),
    (lambda: fluid.optimizer.Adam(learning_rate=0.1), 'sparse_adam'),
])
def test_program_knob_off_bit_identical(opt, kname):
    """configure(False) and the default (env unset) lower the SAME
    modules: training is bit-for-bit identical — the fallback branch IS
    the pre-kernel code, and a disabled knob must leave no residue."""
    kernels.configure(None)
    l0, t0, _ = _run_sparse(opt)
    kernels.configure(False)
    l1, t1, _ = _run_sparse(opt)
    assert l0 == l1
    assert np.array_equal(t0, t1)


@pytest.mark.parametrize('opt,kname', [
    (lambda: fluid.optimizer.Adagrad(learning_rate=0.1), 'sparse_adagrad'),
    (lambda: fluid.optimizer.Adam(learning_rate=0.1), 'sparse_adam'),
])
def test_program_kernel_on_parity(opt, kname):
    """Kernel-enabled training (interpreted pallas on this CPU tier)
    matches knob-off within the documented 1e-6/step absolute tolerance,
    dispatches the kernel at trace time, and performs zero steady-state
    compiles after the first step's signature."""
    from paddle_tpu import obs
    kernels.configure(False)
    l_off, t_off, _ = _run_sparse(opt)
    kernels.configure(kname)
    before = float(obs.counter('kernels.%s.dispatch' % kname).value)
    l_on, t_on, steady = _run_sparse(opt)
    after = float(obs.counter('kernels.%s.dispatch' % kname).value)
    assert after > before, 'kernel never dispatched at trace time'
    assert steady == 0, 'steady-state recompile with kernel enabled'
    assert np.abs(t_on - t_off).max() <= 1e-5
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5, atol=1e-6)


def test_signature_in_executor_cache_key():
    """Flipping the knob between runs of ONE executor recompiles (new
    cache entry) instead of serving the other variant's module; flipping
    back hits the original entry again."""
    rng = np.random.RandomState(1)
    feed = {'ids': rng.randint(0, VOCAB, size=(4, 3, 1)).astype('int64')}
    with fresh_program() as (main, startup):
        loss = _sparse_model(
            lambda: fluid.optimizer.Adagrad(learning_rate=0.1))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        kernels.configure(False)
        exe.run(main, feed=feed, fetch_list=[loss])
        m0 = exe.cache_stats['misses']
        kernels.configure('sparse_adagrad')
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe.cache_stats['misses'] == m0 + 1   # knob flip recompiled
        kernels.configure(False)
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe.cache_stats['misses'] == m0 + 1   # flip back: cache hit


def test_decode_engine_paged_attention_kernel_on_parity():
    """The third kernel through ITS caller: the paged continuous-batching
    decoder over the same requests with `paged_attention` off and on, a
    fresh engine a leg. The kernel dispatches at trace time, neither leg
    compiles after warmup(), and the beams' scores agree to the kernel's
    documented tolerance (token ids may flip only at a near-tie, and none
    does at this seed)."""
    from paddle_tpu import obs
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    V, E, D, H, K, SRC, MAX_LEN, SLOTS, PAGE = 24, 8, 16, 8, 3, 6, 8, 2, 3
    rng = np.random.RandomState(0)
    shapes = {'w_dec': (E + D, 4 * H), 'u_dec': (H, 4 * H),
              'b_dec': (1, 4 * H), 'w_q': (H, D), 'w_emb': (V, E),
              'w_out': (H, V), 'b_out': (1, V)}
    weights = {n: (rng.randn(*s) * 0.3).astype(np.float32)
               for n, s in shapes.items()}
    encs = [(rng.randn(rng.randint(2, SRC + 1), D) * 0.5).astype(np.float32)
            for _ in range(4)]
    pages = SLOTS * (-(-MAX_LEN // PAGE) + -(-SRC // PAGE))

    def leg(spec):
        kernels.configure(spec)
        eng = DecodeEngine(weights, DecodeConfig(
            slots=SLOTS, beam_size=K, max_len=MAX_LEN, src_cap=SRC,
            page_size=PAGE, pages=pages))
        try:
            eng.warmup()
            misses = eng.cache_stats()['misses']
            out = [f.result(300) for f in
                   [eng.submit({'enc': e}) for e in encs]]
            return out, eng.cache_stats()['misses'] - misses
        finally:
            eng.shutdown()

    off, steady_off = leg(False)
    before = obs.counter('kernels.paged_attention.dispatch').value
    on, steady_on = leg('paged_attention')
    assert obs.counter('kernels.paged_attention.dispatch').value > before
    assert steady_off == steady_on == 0
    for (tok_on, sc_on), (tok_off, sc_off) in zip(on, off):
        np.testing.assert_allclose(sc_on, sc_off, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(tok_on, tok_off)


# ---------------------------------------------------------------------------
# quant IR pass: QDQ pipeline form + offline weight quantization
# ---------------------------------------------------------------------------

def _quant_model():
    ids = layers.data(name='ids', shape=[3, 1], dtype='int64')
    emb = layers.embedding(ids, size=[VOCAB, DIM], is_sparse=False,
                           param_attr=fluid.ParamAttr(name='emb_w'))
    out = layers.fc(input=emb, size=5, num_flatten_dims=2,
                    param_attr=fluid.ParamAttr(name='fc_w'))
    return out


def test_quant_pass_qdq_pipeline():
    """mark_quant + optimize(): every frozen f32 weight gets explicit
    QDQ ops (lookup_table rewrites to quant_lookup_table), outputs stay
    within the per-channel round-trip tolerance, and the PassReport
    carries the rewrite counts."""
    rng = np.random.RandomState(2)
    feed = {'ids': rng.randint(0, VOCAB, size=(4, 3, 1)).astype('int64')}
    with fresh_program() as (main, startup):
        out = _quant_model()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        base = np.asarray(exe.run(main, feed=feed, fetch_list=[out])[0])
        quant_pass.mark_quant(main)
        opt, report = passes.optimize(main, fetches=[out.name])
        st = report.passes['quant']
        assert st['ops_rewritten'] == 2          # lookup_table + mul
        assert st['qdq_inserted'] == 3           # 2x quantize + 1 dequant
        types = [op.type for op in opt.global_block().ops]
        assert 'quant_lookup_table' in types
        assert 'quantize' in types and 'dequantize' in types
        assert not quant_pass.is_quant(opt)      # flag became IR property
        assert getattr(opt, '_quant_ir', False)
        got = np.asarray(exe.run(opt, feed=feed, fetch_list=[out.name])[0])
    rel = np.abs(got - base).max() / max(np.abs(base).max(), 1e-9)
    assert rel < 0.05, 'quantized output drifted %.4f relative' % rel


def test_quant_pass_runs_inside_executor():
    """The executor's own optimize() call applies the rewrite: running a
    mark_quant'd program directly produces quantized (close, not
    bitwise) results with no manual pass invocation."""
    rng = np.random.RandomState(6)
    feed = {'ids': rng.randint(0, VOCAB, size=(4, 3, 1)).astype('int64')}
    with fresh_program() as (main, startup):
        out = _quant_model()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        base = np.asarray(exe.run(main, feed=feed, fetch_list=[out])[0])
        quant_pass.mark_quant(main)
        got = np.asarray(exe.run(main, feed=feed, fetch_list=[out])[0])
    assert not np.array_equal(got, base)         # the rewrite really ran
    rel = np.abs(got - base).max() / max(np.abs(base).max(), 1e-9)
    assert rel < 0.05


def test_quantize_weights_offline():
    """The deployment form: int8+scale persistables installed, consumers
    repointed, the fp32 weight DROPPED from the block (so
    save_inference_model ships no fp32 bytes), outputs within tolerance
    and the embedding rows within the documented per-element bound."""
    rng = np.random.RandomState(7)
    feed = {'ids': rng.randint(0, VOCAB, size=(4, 3, 1)).astype('int64')}
    with fresh_program() as (main, startup):
        out = _quant_model()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        base = np.asarray(exe.run(main, feed=feed, fetch_list=[out])[0])
        w_emb = np.asarray(global_scope()._chain_get('emb_w'))
        infer = main.clone(for_test=True)
        n = quant_pass.quantize_weights(infer, global_scope())
        assert n == 2
        blk = infer.global_block()
        assert 'emb_w' not in blk.vars           # fp32 table dropped
        assert blk.vars['emb_w@quant.int8'].persistable
        assert blk.vars['emb_w@quant.int8'].dtype == 'int8'
        persist = [v.name for v in infer.list_vars() if v.persistable]
        assert 'emb_w' not in persist            # artifact ships int8 only
        got = np.asarray(exe.run(infer, feed=feed, fetch_list=[out.name])[0])
        # round-trip bound on the rows themselves: half a step per
        # element, per row (axis-0 per-channel scales)
        q = np.asarray(global_scope()._chain_get('emb_w@quant.int8'))
        s = np.asarray(global_scope()._chain_get('emb_w@quant.scale'))
        deq = q.astype(np.float32) * s
        bound = np.abs(w_emb).max(axis=1, keepdims=True) / 254.0
        assert (np.abs(deq - w_emb) <= bound + 1e-7).all()
    rel = np.abs(got - base).max() / max(np.abs(base).max(), 1e-9)
    assert rel < 0.05


# ---------------------------------------------------------------------------
# int8 delta-push wire
# ---------------------------------------------------------------------------

def test_quant_rows_codec_bound():
    from paddle_tpu.embedding import quant_rows as qr
    rng = np.random.RandomState(8)
    vals = (rng.randn(32, DIM) * np.logspace(-3, 2, 32)[:, None]) \
        .astype(np.float32)
    q, scale = qr.quantize_rows(vals)
    assert q.dtype == np.int8 and scale.shape == (32, 1)
    back = qr.dequantize_rows(q, scale)
    bound = np.abs(vals).max(axis=1, keepdims=True) / 254.0
    assert (np.abs(back - vals) <= bound + 1e-9).all()
    assert qr.row_bytes(q, scale) == 32 * DIM + 32 * qr.ROW_SCALE_BYTES


def test_publisher_int8_push_bytes():
    """Same touched rows, fp32 vs int8 wire: value bytes <= 0.55x, the
    plain-sink replica holds round-trip-bounded values, and a
    codec-aware sink receives the (rows, q, scale) form untouched."""
    from paddle_tpu.streaming import DeltaPublisher
    rng = np.random.RandomState(11)
    table = (rng.randn(64, 32) * 0.5).astype(np.float32)
    rows = np.arange(0, 48, 2)

    class Plain(object):
        def __init__(self):
            self.got = {}

        def push_rows(self, deltas):
            for name, (ids, vals) in deltas.items():
                self.got[name] = (np.asarray(ids), np.asarray(vals))

    class Codec(Plain):
        def push_quantized_rows(self, deltas):
            for name, (ids, q, scale) in deltas.items():
                self.got[name] = (np.asarray(ids), np.asarray(q),
                                  np.asarray(scale))

    def push(sink, quant):
        pub = DeltaPublisher(sink, quant=quant)
        pub.collect({'emb_w': rows})
        pub.publish(lambda name: table)
        return pub

    p_fp = push(Plain(), None)
    plain = Plain()
    p_q = push(plain, 'int8')
    assert p_q.last_push_bytes <= 0.55 * p_fp.last_push_bytes
    assert p_fp.last_push_bytes == rows.size * table.shape[1] * 4
    # plain sink got fp32 values carrying exactly the quantized wire's
    # rounding: within half a step of the live rows
    ids, vals = plain.got['emb_w']
    bound = np.abs(table[ids]).max(axis=1, keepdims=True) / 254.0
    assert (np.abs(vals - table[ids]) <= bound + 1e-7).all()
    # codec-aware sink receives the int8 form itself
    codec = Codec()
    push(codec, 'int8')
    cids, q, scale = codec.got['emb_w']
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert np.array_equal(np.sort(cids), np.sort(rows))
    assert p_q.stats()['quant'] == 'int8'


# ---------------------------------------------------------------------------
# observability: dispatch events render the obs_report section
# ---------------------------------------------------------------------------

def test_dispatch_events_and_report_section(tmp_path):
    from paddle_tpu import obs
    from paddle_tpu.obs import report as obs_report
    obs.enable(str(tmp_path / 'obs'))
    try:
        kernels.note_dispatch('paged_attention', True)
        kernels.note_dispatch('paged_attention', True)
        kernels.note_dispatch('sparse_adam', False)
        events, errors = obs_report.load_events(obs.run_log_path())
        assert errors == []
        text = obs_report.summarize(events)
        assert '-- kernels --' in text
        assert 'trace-time dispatches: 2 kernel, 1 fallback' in text
        assert 'paged_attention: 2 kernel trace(s)' in text
    finally:
        obs._reset()


# ---------------------------------------------------------------------------
# TPU lowering without a chip: jax.export for platforms=['tpu'] runs the
# Pallas -> Mosaic lowering (block-shape rules, memory spaces) on the host.
# It is how the (1, ps) mask block and the (1, d) row blocks were found
# refused; what Mosaic itself then accepts is chip_smoke.py's kernel leg.
# ---------------------------------------------------------------------------

def _export_cases():
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    qkv = [S((8, 8, 1024, 64), jnp.bfloat16)] * 3
    tables = [S((4096, 128), f32)] * 3
    rows = [S((256,), i32), S((256, 128), f32), S((256,), i32), S((), f32)]

    def flash(causal):
        return lambda q, k, v: ops.flash_attention(
            q, k, v, causal=causal, interpret=False)

    def flash_grad(causal):
        return jax.grad(lambda q, k, v: flash(causal)(q, k, v).astype(
            f32).sum(), argnums=(0, 1, 2))

    return {
        'flash_fwd': (flash(False), qkv),
        'flash_fwd_causal': (flash(True), qkv),
        'flash_bwd': (flash_grad(False), qkv),
        'flash_bwd_causal': (flash_grad(True), qkv),
        'paged_attention': (
            functools.partial(kernels.paged_attention, src_cap=507,
                              interpret=False),
            [S((64, 128), f32), S((64, 128, 128), f32), S((64, 128), f32),
             S((8, 4), i32)]),
        'sparse_adagrad': (
            lambda p, m, *r: kernels.fused_sparse_adagrad(
                p, m, *r, 1e-6, interpret=False), tables[:2] + rows),
        'sparse_adam': (
            lambda p, m1, m2, *r: kernels.fused_sparse_adam(
                p, m1, m2, *r, 0.9, 0.999, 1e-8, interpret=False),
            tables + rows),
    }


def test_every_kernel_lowers_for_tpu():
    import jax
    from jax import export
    cases = _export_cases()
    assert set(kernels.available()) <= set(cases), \
        'a registered kernel has no TPU lowering case'
    for name, (fn, args) in cases.items():
        exported = export.export(jax.jit(fn), platforms=['tpu'])(*args)
        assert 'tpu_custom_call' in exported.mlir_module(), name


def test_kernels_take_interpret_from_the_caller():
    """No kernel reads the process's default backend: leaving interpret
    out is an error, not a quiet trip through the interpreter."""
    import jax.numpy as jnp
    from paddle_tpu import ops
    q = jnp.zeros((1, 1, 8, 4), jnp.float32)
    with pytest.raises(TypeError):
        ops.flash_attention(q, q, q)
    with pytest.raises(TypeError):
        ops.flash_attention(q, q, q, interpret=None)
    with pytest.raises(TypeError):
        kernels.paged_attention(q, q, q, q, 1)
    assert not hasattr(kernels, 'interpret_default')
