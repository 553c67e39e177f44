"""SmallThinker (`model_name` smallthinker_21b_instruct; the source's
config.json is chipbench/configs/smallthinker_21b_a3b.json's; the family's
report is arXiv:2507.20984): a pre-norm causal decoder for local
deployment whose layers differ by KIND, whose router reads the layer's
input BEFORE attention and whose experts are ReGLU, with no shared expert.
Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
rms(t, w) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias
anywhere, for layer l with g = rms(x, w_in):

  Attention:
    q = g Wq  (hidden -> n_head x d_head);  k = g Wk;  v = g Wv
                         (hidden -> n_kv_head x d_head each; no norm on
                         q or k)
    if rope_layout[l]:   q, k = rotary(q), rotary(k) over the whole head,
                         rotate-half pairs (i, i + d_head / 2),
                         inv_freq = theta^(-2i / d_head)
    else:                nothing is added (NoPE)
    query head h reads key-value head h // (n_head / n_kv_head)
    s_ij = q_i . k_j / sqrt(d_head);  position i sees j iff j <= i and,
    where sliding_window_layout[l], i - j < window (the window counts
    the query's own position: keys i - window + 1 .. i)
    a = softmax(s) v;   h = x + a Wo

  Experts (layers.moe_mlp, dropless):
    z = g Wr                          the router reads the PRE-attention
                                      normed input, float32
    chosen = the top_k largest of z;  gates = softmax over the chosen
    logits (= the softmax over all, renormalised over the chosen)
    m = rms(h, w_post)                the experts read the POST-attention
                                      normed state
    E_e(m) = (relu(m Wgate_e) * (m Wup_e)) Wdown_e          ReGLU
    y = h + sum over the chosen e THAT ARE HELD of gate_e E_e(m)

    out = rms(x_L, w_final) Whead (untied)
    loss = mean cross entropy + aux_coef * mean over layers of the
           load-balancing loss (Switch's form over all the experts)

In the source layers 4n are global without positions and layers 4n + 1
.. 4n + 3 windowed with rotary positions (`sliding_window_layout` and
`rope_layout`, [0, 1, 1, 1] repeated). Each layer is one
`fluid.recompute_guard()` region that keeps, besides its input, the
residual after the mixer and q, k and v as Wq, Wk and Wv give them
(`fluid.recompute_keep`: the backward pass runs none of the mixer's four
projections again, and the experts as before); a layer's mixer
(projections, rotary, the attention call) is built under
`fluid.name_scope('window_attention')` or
`fluid.name_scope('global_attention')` by its kind. The whole train
step is one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['smallthinker', 'decoder_layer', 'get_model']


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _unmarked(var):
    return var


def attention(g, windowed, rope, c, keep=_unmarked):
    """The mixer on the normed input `g`. Parameters in creation order:
    Wq, Wk, Wv, Wo. `keep` is called on the outputs of Wq, Wk and Wv
    (`decoder_layer`)."""
    d = c['d_head']

    def heads(t, n):
        return layers.transpose(layers.reshape(t, shape=[0, 0, n, d]),
                                perm=[0, 2, 1, 3])

    q = heads(keep(_proj(g, c['n_head'] * d, c['std'])), c['n_head'])
    k, v = (heads(keep(_proj(g, c['n_kv_head'] * d, c['std'])),
                  c['n_kv_head']) for _ in range(2))
    if rope:
        q, k = (layers.rotary_embedding(t, base=c['rope_theta'])
                for t in (q, k))
    ctx = layers.fused_attention(q, k, v, causal=True, scale=d ** -0.5,
                                 window=c['window'] if windowed else None)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         shape=[0, 0, c['n_head'] * d])
    return _proj(ctx, c['hidden'], c['std'])


def decoder_layer(x, index, c, keep=_unmarked):
    """Layer `index`. Returns (output, load-balancing loss, assignments
    per expert). Parameters in creation order: the input norm, Wq, Wk, Wv,
    Wo, the post-attention norm, the router, the experts' gate, up and
    down stacks. `keep` is called on the residual `h` after the mixer and
    on q, k and v as their projections give them: whoever builds the
    layer inside a recompute region passes `fluid.recompute_keep`
    (`smallthinker`)."""
    windowed = bool(c['sliding_window_layout'][index])
    g = layers.rms_norm(x, epsilon=c['eps'])
    with fluid.name_scope('window_attention' if windowed
                          else 'global_attention'):
        mixed = attention(g, windowed, bool(c['rope_layout'][index]), c,
                          keep)
    h = keep(layers.elementwise_add(x, mixed))
    y, aux, count = layers.moe_mlp(
        layers.rms_norm(h, epsilon=c['eps']), num_experts=c['n_expert'],
        hidden_size=c['expert_width'], act='relu', gated=True,
        top_k=c['top_k'], norm_topk_prob=True, capacity_factor=None,
        experts_held=c['experts_held'], router_input=g,
        gate_param_attr=_weight(c['std']), param_attr=_weight(c['std']),
        bias_attr=False, return_aux_loss=True, return_expert_count=True)
    return layers.elementwise_add(h, y), aux, count


def smallthinker(vocab_size, seq_len, n_layer=52, hidden=2560, n_head=28,
                 n_kv_head=4, d_head=128, window=4096,
                 sliding_window_layout=None, rope_layout=None, n_expert=64,
                 top_k=6, expert_width=768, experts_held=None, eps=1e-6,
                 rope_theta=1.5e6, aux_coef=0.001, std=0.02, emb_std=None):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, feed names). The two layouts give a
    layer's kind (1: windowed / rotary); the source's [0, 1, 1, 1]
    repeated where none is given. `experts_held` = (first, count): the
    chip's share of every layer's experts (layers.moe_mlp). `std` is the
    normal initializer of every matrix; `emb_std` the token embedding's
    where it differs (None: `std`)."""
    c = dict(locals())
    for key in ('sliding_window_layout', 'rope_layout'):
        c[key] = list(c[key] or [0, 1, 1, 1] * (-(-n_layer // 4)))[:n_layer]
        if len(c[key]) != n_layer:
            raise ValueError('smallthinker: %s names %d layers of %d'
                             % (key, len(c[key]), n_layer))
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std if emb_std is None
                                            else emb_std))
    auxes, counts = [], []
    for i in range(n_layer):
        with fluid.recompute_guard():
            x, aux, count = decoder_layer(x, i, c,
                                          keep=fluid.recompute_keep)
        auxes.append(aux)
        counts.append(count)
    # the head is the last fc built (chipbench's loss_head_ms reads that)
    logits = _proj(layers.rms_norm(x, epsilon=eps), vocab_size, std)
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[-1, vocab_size]),
        layers.reshape(labels, shape=[-1, 1]))
    loss = layers.mean(cost)
    if aux_coef:
        loss = loss + (aux_coef / n_layer) * layers.sums(auxes)
    return loss, counts, ['input_ids', 'labels']


def get_model(batch_size=2, seq_len=32, vocab_size=256, n_layer=4, hidden=64,
              n_head=4, n_kv_head=2, d_head=16, window=8, n_expert=16,
              top_k=2, expert_width=32, experts_held=None,
              learning_rate=4e-4):
    """A small preset by default (the published sizes are
    chipbench/configs/smallthinker_21b_a3b.json's); Adam without decoupled
    decay. The readers yield packed rows of uniform random ids."""
    loss, counts, feeds = smallthinker(
        vocab_size, seq_len, n_layer=n_layer, hidden=hidden, n_head=n_head,
        n_kv_head=n_kv_head, d_head=d_head, window=window,
        n_expert=n_expert, top_k=top_k, expert_width=expert_width,
        experts_held=experts_held)
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

    return loss, counts, reader(0), reader(1), feeds
