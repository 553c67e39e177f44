"""GLM-4.7-Flash (`model_type` glm4_moe_lite; the source's config.json is
chipbench/configs/glm_4_7_flash.json's): a pre-norm causal decoder whose
token mixer is multi-head latent attention, whose first layer's
feed-forward is dense and whose other layers' is a sparse-expert block
with a sigmoid router, a selection bias and one shared expert, trained
with a depth-1 multi-token prediction module (DeepSeek-V3,
arXiv:2412.19437, sections 2.1 and 2.2). Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
norm(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias
anywhere:

    x = Emb[ids]
    layer l:  x = x + mla_l(norm(x));   x = x + ffn_l(norm(x))
      ffn_l = Wdown(silu(Wgate m) * Wup m) for l < first_k_dense,
              the expert block after it
    mla: layers.latent_attention (queries through a normed latent of
         q_rank, keys and values from one normed latent of kv_rank and
         one rotary key head shared by all query heads)

  Expert block (m = norm(x)), layers.moe_mlp:
    s = sigmoid(m Wr);  chosen = top_k of (s + b), b the selection bias,
    a persistable that no gradient reaches;  gates = gate_scale * s over
    the chosen, renormalised without b;  routed = the chosen experts THAT
    ARE HELD (`experts_held`), gated SiLU experts, dropless
    block = routed + Wdown_s(silu(Wgate_s m) * Wup_s m)      every token
    after the step (router_bias_updates, built after minimize):
        b_e <- b_e + rate * sign(mean(c) - c_e),  c the step's counts

    L0 = mean CE(norm(x_L) Whead, labels)                    Whead untied

  Multi-token prediction, depth 1 (labels_t = ids_(t+1)):
    h' = [norm_h(x_L) | norm_e(Emb[labels])] Weh       Emb SHARED
    y  = one more layer (latent attention + expert block) on h'
    L1 = sum over t < T-1 of CE(norm_m(y_t) Whead, labels_(t+1)) / (T-1)
                                                       Whead SHARED
    loss = L0 + mtp_weight * L1

The module keeps all T positions: its targets are the labels rolled by
one and the last position's cost is multiplied by 0. Each decoder layer
and the whole module are one `fluid.recompute_guard()` region each (the
step keeps a layer's input and recomputes the rest); every mixer is built
under `fluid.name_scope('latent_attention')`, the module under `mtp`, the
bias update under `router_bias`. The main head's projection is the LAST
`mul` built (chipbench's loss_head_ms reads that), so the shared head is
created by the module's use and bound a second time by the main path's,
the shared embedding the other way round. The whole train step is one XLA
module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['glm4_moe_lite', 'decoder_layer', 'router_bias_updates',
           'get_model']

EMBEDDING, HEAD = 'glm_tok_emb', 'glm_head'


def _weight(std, name=None):
    return fluid.ParamAttr(name=name,
                           initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std, name=None):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std, name), bias_attr=False)


def _gated_mlp(m, hidden, width, std):
    """Wdown(silu(Wgate m) * Wup m); parameters in that order of names:
    gate, up, down."""
    return _proj(layers.elementwise_mul(layers.swish(_proj(m, width, std)),
                                        _proj(m, width, std)), hidden, std)


def mixer(x, c):
    """norm, then latent attention. Parameters in creation order: the
    input norm, Wqa, the query latent's norm, Wqb, Wkva, the key-value
    latent's norm, Wkvb, Wo."""
    with fluid.name_scope('latent_attention'):
        return layers.latent_attention(
            layers.rms_norm(x, epsilon=c['eps']), c['hidden'], c['n_head'],
            c['q_rank'], c['kv_rank'], c['d_nope'], c['d_rope'], c['d_v'],
            rope_theta=c['rope_theta'], epsilon=c['eps'],
            param_attr=_weight(c['std']))


def expert_block(m, c):
    """Returns (output, assignments per expert, the selection bias).
    Parameters in creation order: the router, the experts' gate, up and
    down stacks, the selection bias, the shared expert's gate, up and down
    projections."""
    routed, count, bias = layers.moe_mlp(
        m, num_experts=c['n_expert'], hidden_size=c['expert_width'],
        act='swish', gated=True, top_k=c['top_k'],
        norm_topk_prob=c['norm_topk_prob'], capacity_factor=None,
        experts_held=c['experts_held'], scoring='sigmoid',
        selection_bias=True, gate_scale=c['gate_scale'],
        gate_param_attr=_weight(c['std']), param_attr=_weight(c['std']),
        bias_attr=False, return_expert_count=True)
    shared = _gated_mlp(m, c['hidden'], c['shared_width'], c['std'])
    return layers.elementwise_add(routed, shared), count, bias


def decoder_layer(x, dense, c):
    """One layer: the mixer, then the dense feed-forward (`dense`) or the
    expert block. Returns (output, assignments per expert or None, the
    selection bias or None)."""
    h = layers.elementwise_add(x, mixer(x, c))
    m = layers.rms_norm(h, epsilon=c['eps'])
    if dense:
        y, count, bias = _gated_mlp(m, c['hidden'], c['dense_width'],
                                    c['std']), None, None
    else:
        y, count, bias = expert_block(m, c)
    return layers.elementwise_add(h, y), count, bias


def _mean_cost(logits, labels, vocab_size, weights=None, divisor=None):
    """Mean cross entropy over the positions; with `weights` [T] a
    position's cost is multiplied by its weight and the sum divided by
    `divisor` a row."""
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[-1, vocab_size]),
        layers.reshape(labels, shape=[-1, 1]))
    if weights is None:
        return layers.mean(cost)
    seq = int(weights.shape[0])
    cost = layers.elementwise_mul(layers.reshape(cost, shape=[-1, seq]),
                                  weights, axis=1)
    return layers.scale(layers.mean(cost), scale=seq / float(divisor))


def mtp_module(x, labels, c):
    """The depth-1 multi-token prediction module on the decoder's last
    state `x` (before the final norm). Returns (L1, assignments per
    expert, the selection bias). Parameters in creation order: norm_h,
    norm_e, Weh, the layer's, norm_m, the shared head (this is its first
    use; the embedding is the main path's already)."""
    seq = c['seq_len']
    with fluid.name_scope('mtp'), fluid.recompute_guard():
        nxt = layers.embedding(input=labels,
                               size=[c['vocab_size'], c['hidden']],
                               param_attr=_weight(c['std'], EMBEDDING))
        h = _proj(layers.concat(
            [layers.rms_norm(x, epsilon=c['eps']),
             layers.rms_norm(nxt, epsilon=c['eps'])], axis=-1),
            c['hidden'], c['std'])
        y, count, bias = decoder_layer(h, False, c)
        logits = _proj(layers.rms_norm(y, epsilon=c['eps']),
                       c['vocab_size'], c['std'], HEAD)
        # position t predicts labels[t + 1]; the last has nothing to
        # predict: its target is the row's first label and its weight 0
        first, rest = layers.split(labels, [1, seq - 1], dim=1)
        weights = layers.assign(
            np.concatenate([np.ones(seq - 1), np.zeros(1)]).astype('float32'))
        cost = _mean_cost(logits, layers.concat([rest, first], axis=1),
                          c['vocab_size'], weights, seq - 1)
    return cost, count, bias


def glm4_moe_lite(vocab_size, seq_len, n_layer=47, first_k_dense=1,
                  hidden=2048, dense_width=10240, n_head=20, q_rank=768,
                  kv_rank=512, d_nope=192, d_rope=64, d_v=256, n_expert=64,
                  top_k=4, expert_width=1536, shared_width=1536,
                  experts_held=None, eps=1e-5, rope_theta=1e6,
                  norm_topk_prob=True, gate_scale=1.8, n_mtp=1,
                  mtp_weight=0.3, std=0.02):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, per-layer selection biases, feed
    names); counts and biases are of the expert layers in order, the
    module's last. `experts_held` = (first, count): the chip's share of
    every layer's experts (layers.moe_mlp). `n_mtp` is 0 or 1."""
    if n_mtp not in (0, 1):
        raise ValueError('glm4_moe_lite: one multi-token prediction module '
                         'or none, got %r' % (n_mtp,))
    c = dict(locals())
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std, EMBEDDING))
    counts, biases = [], []
    for i in range(n_layer):
        with fluid.recompute_guard():
            x, count, bias = decoder_layer(x, i < first_k_dense, c)
        if count is not None:
            counts.append(count)
            biases.append(bias)
    extra = None
    if n_mtp:
        extra, count, bias = mtp_module(x, labels, c)
        counts.append(count)
        biases.append(bias)
    # the main head is the last fc built (chipbench's loss_head_ms)
    logits = _proj(layers.rms_norm(x, epsilon=eps), vocab_size, std, HEAD)
    loss = _mean_cost(logits, labels, vocab_size)
    if extra is not None:
        loss = loss + layers.scale(extra, scale=float(mtp_weight))
    return loss, counts, biases, ['input_ids', 'labels']


def router_bias_updates(counts, biases, rate=0.001):
    """Every expert layer's selection bias moved by its step's load
    (layers.router_bias_update). Build AFTER minimize: the ops then follow
    the optimizer's in the one compiled step."""
    with fluid.name_scope('router_bias'):
        for count, bias in zip(counts, biases):
            layers.router_bias_update(bias, count, rate=rate)


def get_model(batch_size=2, seq_len=32, vocab_size=256, n_layer=3, hidden=64,
              dense_width=128, n_head=4, q_rank=24, kv_rank=16, d_nope=12,
              d_rope=4, d_v=16, n_expert=16, top_k=2, expert_width=32,
              experts_held=None, learning_rate=4e-4, bias_rate=0.001):
    """A small preset by default (the published sizes are
    chipbench/configs/glm_4_7_flash.json's); Adam without decoupled decay,
    then the bias update. The readers yield packed rows of uniform random
    ids."""
    loss, counts, biases, feeds = glm4_moe_lite(
        vocab_size, seq_len, n_layer=n_layer, hidden=hidden,
        dense_width=dense_width, n_head=n_head, q_rank=q_rank,
        kv_rank=kv_rank, d_nope=d_nope, d_rope=d_rope, d_v=d_v,
        n_expert=n_expert, top_k=top_k, expert_width=expert_width,
        shared_width=expert_width, experts_held=experts_held)
    fluid.optimizer.Adam(learning_rate=learning_rate, beta1=0.9, beta2=0.95,
                         epsilon=1e-8).minimize(loss)
    router_bias_updates(counts, biases, rate=bias_rate)

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
