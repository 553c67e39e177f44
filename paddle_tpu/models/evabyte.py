"""EvaByte (`model_type` evabyte, `attention_class` eva; the source's
config.json is chipbench/configs/evabyte.json's): a pre-norm causal decoder
over BYTES (a vocabulary of 320) whose attention is exact inside ALIGNED
windows and, beyond them, runs over ONE learned summary key and value for
every chunk of consecutive positions (EVA, "Efficient Attention via Control
Variates", Zheng, Yuan, Wang and Kong, ICLR 2023, arXiv:2302.04542, section
4, with two learned vectors a head in place of the paper's sampled random
feature), both sets under one softmax; a dense SwiGLU; norms whose weight is
stored as its offset from one; and several next-byte heads from one matrix.
Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden], no bias anywhere:

    norm(t)  = (1 + w) * t * rsqrt(mean(t^2) + eps)       w starts at 0
    x_0      = E[ids]
    layer i:   h = x + EVA_i(norm1_i(x))
               x = h + (silu(u Wg) * (u Wu)) Wd,           u = norm2_i(h)
    logits   = norm_f(x_L) Whead   viewed [B, T, P, vocab]   P = n_pred_heads
    loss     = 1/P sum_j mean over {t : t + j < T} of
               CE(logits[t, j, :], labels[t + j])
      (labels[t] = ids[t + 1] is the feed; head j predicts the byte j + 1
       ahead; a row's last j positions have no target for head j and are
       left out of its mean)

  EVA mixer (u = norm1(x); H heads of D; chunks of c; windows of W;
  s = D^-0.5):
    q, k, v = u Wq, u Wk, u Wv;  q, k <- rotary(q), rotary(k)
    chunk n = positions [c n, c n + c); window w = positions [W w, W w + W)
    per head, learned mu, phi in R^D:
      a_m = softmax over m in chunk n of (mu . k_m)        kbar_n = sum a_m k_m
      b_m = softmax over m in chunk n of (s phi . k_m)     vbar_n = sum b_m v_m
    a query t of window w sees
      E_t = { m in window w, m <= t }                      exact keys
      P_t = { n : chunk n lies in a window before w }      summaries
    o_t = softmax over E_t and P_t TOGETHER of (s q_t . key) applied to the
          values v_m and vbar_n: ONE normaliser
    mixer = concat_h(o) Wo

Each LAYER is one `fluid.recompute_guard()` region. The mixer is built
under `fluid.name_scope('eva_mixer')`, inside it the pooling under
`'eva_summary'`, every feed-forward under `'dense_mlp'`. The pooling is
`layers.chunk_softmax_pool`, the attention ONE op,
`layers.fused_attention(aligned_window=W, summary=(kbar, vbar),
summary_every=c)`: on the TPU the causal flash kernels over rows of W, the
same kernels on a staircase grid over the summaries, and their merge. The
head's projection is the LAST `mul` built (chipbench's loss_head_ms reads
that). The whole train step is one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import layers

__all__ = ['evabyte', 'decoder_layer', 'eva_mixer', 'dense_mlp',
           'next_byte_loss', 'get_model']


def _unmarked(var):
    return var


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _norm(x, c):
    return layers.rms_norm(x, epsilon=c['eps'], unit_offset=True)


def pooling_vectors(n_head, d_head, seed):
    """(mu, phi), [n_head, d_head] each: clamp(N(0, 1), -1, 1) x
    d_head^-0.5, from a numpy generator seeded by the layer's index."""
    rng = np.random.default_rng([seed, 0xe7a])
    return tuple(np.clip(rng.standard_normal((n_head, d_head)), -1.0, 1.0)
                 * d_head ** -0.5 for _ in range(2))


def eva_mixer(u, c, index, keep=_unmarked):
    """The EVA mixer on the normed input `u`. Parameters in creation
    order: Wq, Wk, Wv, mu, phi, Wo. `keep` is called on the outputs of Wq,
    Wk and Wv."""
    h, d = c['n_head'], c['d_head']
    scale = d ** -0.5

    def heads(t):
        return layers.transpose(layers.reshape(t, shape=[0, 0, h, d]),
                                perm=[0, 2, 1, 3])

    with fluid.name_scope('eva_mixer'):
        q, k, v = (heads(keep(_proj(u, h * d, c['std']))) for _ in range(3))
        q, k = (layers.rotary_embedding(t, base=c['rope_theta'])
                for t in (q, k))
        mu, phi = (layers.create_parameter(
            [h, d], 'float32',
            default_initializer=fluid.initializer.NumpyArrayInitializer(
                vec.astype('float32')))
            for vec in pooling_vectors(h, d, index))
        with fluid.name_scope('eva_summary'):
            # after the rotary: a summary key is a mixture of rotated keys
            # and has no position of its own
            summary = layers.chunk_softmax_pool(k, v, mu, phi,
                                                chunk=c['chunk_size'],
                                                scale=scale)
        ctx = layers.fused_attention(
            q, k, v, causal=True, scale=scale,
            aligned_window=c['window_size'], summary=summary,
            summary_every=c['chunk_size'])
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, h * d])
        return _proj(ctx, c['hidden'], c['std'])


def dense_mlp(m, c):
    """(silu(m Wg) * (m Wu)) Wd. Parameters in creation order: Wg, Wu,
    Wd."""
    with fluid.name_scope('dense_mlp'):
        gate, up = (_proj(m, c['mlp_width'], c['std']) for _ in range(2))
        return _proj(layers.elementwise_mul(layers.swish(gate), up),
                     c['hidden'], c['std'])


def decoder_layer(x, index, c, keep=_unmarked):
    """Layer `index`: the EVA mixer, then the SwiGLU, each behind its norm
    and added to the residual. `keep` is called on the residual `h` after
    the mixer and handed on to the mixer."""
    obs.counter('evabyte.layers').inc()                     # build time
    h = keep(layers.elementwise_add(
        x, eva_mixer(_norm(x, c), c, index, keep)))
    return layers.elementwise_add(h, dense_mlp(_norm(h, c), c))


def next_byte_loss(logits, labels, seq_len, vocab_size, n_pred_heads):
    """The mean of the `n_pred_heads` heads' mean cross entropies.
    logits [B, T, P x vocab] (head j the columns [j vocab, (j + 1)
    vocab)), labels [B, T] with labels[t] the id at t + 1. Head j's
    labels are `labels` shifted j to the left and padded on the right;
    ONE closed-form cross entropy over the B x T x P rows of `vocab`, then a
    constant [T, P] weight that is 1 / (P x (T - j)) where t + j < T and 0
    on the row's last j positions (the mean over the batch follows)."""
    t, p = int(seq_len), int(n_pred_heads)
    if not 0 < p <= t:
        raise ValueError('evabyte: %d prediction heads over a row of %d'
                         % (p, t))
    padded = layers.pad(labels, paddings=[0, 0, 0, p - 1])
    shifted = layers.stack(
        [layers.slice(padded, axes=[1], starts=[j], ends=[j + t])
         for j in range(p)], axis=2)                            # [B, T, P]
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[0, 0, p, vocab_size]),
        layers.reshape(shifted, shape=[0, 0, p, 1]))
    weight = np.zeros((t, p), 'float32')
    for j in range(p):
        weight[:t - j, j] = 1.0 / (p * (t - j))
    weight = layers.assign(weight)
    weight.stop_gradient = True
    per_row = layers.reduce_sum(
        layers.elementwise_mul(layers.reshape(cost, shape=[0, 0, p]),
                               weight, axis=1), dim=[1, 2])
    return layers.mean(per_row)


def evabyte(vocab_size, seq_len, n_layer=32, hidden=4096, n_head=32,
            d_head=128, mlp_width=11008, chunk_size=16, window_size=2048,
            num_chunks=None, n_pred_heads=8, rope_theta=100000.0, eps=1e-5,
            std=0.01275):
    """Builds the training loss into the default main program. Returns
    (loss, feed names). Each layer's recompute region keeps, beside its
    input and the attention calls' outputs, the residual after the mixer
    and the outputs of Wq, Wk and Wv (`fluid.recompute_keep`)."""
    c = dict(locals())
    if num_chunks is not None:
        raise ValueError('evabyte: num_chunks is %r; the chunks are sized by '
                         'chunk_size alone (the published null)'
                         % (num_chunks,))
    if chunk_size < 1 or window_size % chunk_size:
        raise ValueError('evabyte: a window of %d is not a whole number of '
                         'chunks of %d' % (window_size, chunk_size))
    if seq_len % window_size:
        raise ValueError('evabyte: a row of %d is not a whole number of '
                         'windows of %d' % (seq_len, window_size))
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std))
    for i in range(n_layer):
        with fluid.recompute_guard():
            x = decoder_layer(x, i, c, keep=fluid.recompute_keep)
    logits = _proj(_norm(x, c), n_pred_heads * vocab_size, std)
    loss = next_byte_loss(logits, labels, seq_len, vocab_size, n_pred_heads)
    return loss, ['input_ids', 'labels']


def get_model(batch_size=2, seq_len=64, vocab_size=320, n_layer=2, hidden=64,
              n_head=4, d_head=16, mlp_width=128, chunk_size=4,
              window_size=16, n_pred_heads=8, learning_rate=4e-4):
    """A small preset by default (the published sizes are
    chipbench/configs/evabyte.json's); Adam without decoupled decay.
    Returns (loss, None, train reader, test reader, feed names); the
    readers yield packed rows of uniform random bytes."""
    loss, feeds = evabyte(vocab_size, seq_len, n_layer=n_layer,
                          hidden=hidden, n_head=n_head, d_head=d_head,
                          mlp_width=mlp_width, chunk_size=chunk_size,
                          window_size=window_size,
                          n_pred_heads=n_pred_heads, std=0.02)
    fluid.optimizer.Adam(learning_rate=learning_rate, beta1=0.9, beta2=0.95,
                         epsilon=1e-8).minimize(loss)

    def reader(seed):
        def read():
            rng = np.random.default_rng(seed)
            for _ in range(16):
                rows = rng.integers(0, vocab_size,
                                    size=(batch_size, seq_len + 1))
                yield [(r[:-1].astype('int64'), r[1:].astype('int64'))
                       for r in rows]
        return read

    return loss, None, reader(0), reader(1), feeds
