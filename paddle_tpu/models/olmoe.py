"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; `model_type` olmoe):
a pre-norm causal decoder whose feed-forward is a dropless sparse-expert
layer, built from fluid.layers.

No reference counterpart: the reference predates every part of this block
(RMS norm, rotary positions, a norm on queries and keys, gated experts).
With h of shape [B, T, hidden]:

    x   = embedding(ids)                          no position table
    a   = rms_norm(x)
    q,k = rms_norm(a Wq), rms_norm(a Wk); v = a Wv
          (no biases; the q/k norm runs over all hidden outputs of the
          projection, BEFORE the split into heads)
    q,k = rotary(q), rotary(k)                    per head, rotate-half
    h   = x + attention(q, k, v, causal) Wo       layers.fused_attention
    m   = rms_norm(h)
    y   = h + sum over the top_k largest p_e of p_e * expert_e(m)
          p = softmax(m Wr) in float32, NOT renormalised over the chosen;
          expert_e(m) = Wdown_e(silu(Wgate_e m) * (Wup_e m)); no token is
          dropped (layers.moe_mlp(capacity_factor=None))
    out = rms_norm(y) Whead                       untied, no bias
    loss = mean cross entropy(out, next id) + aux_coef * load balancing

The whole train step is one XLA module; on the TPU attention lowers to the
flash kernels on their causal path and the experts to the grouped-matmul
kernel (ops/kernels/grouped_matmul.py).
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['olmoe', 'decoder_layer', 'get_model']


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def attention(x, hidden, n_head, eps, rope_theta, std):
    d_head = hidden // n_head
    a = layers.rms_norm(x, epsilon=eps)

    def heads(t):
        t = layers.reshape(t, shape=[0, 0, n_head, d_head])
        return layers.transpose(t, perm=[0, 2, 1, 3])

    q, k = (layers.rotary_embedding(
        heads(layers.rms_norm(_proj(a, hidden, std), epsilon=eps)),
        base=rope_theta) for _ in range(2))
    v = heads(_proj(a, hidden, std))
    ctx = layers.fused_attention(q, k, v, causal=True, scale=d_head ** -0.5)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         shape=[0, 0, hidden])
    return _proj(ctx, hidden, std)


def decoder_layer(x, hidden, n_head, n_expert, top_k, expert_width, eps=1e-5,
                  rope_theta=10000.0, norm_topk_prob=False, std=0.02):
    """One block. Returns (output, load-balancing loss, assignments per
    expert)."""
    h = layers.elementwise_add(
        x, attention(x, hidden, n_head, eps, rope_theta, std))
    y, aux, count = layers.moe_mlp(
        layers.rms_norm(h, epsilon=eps), num_experts=n_expert,
        hidden_size=expert_width, act='swish', gated=True, top_k=top_k,
        norm_topk_prob=norm_topk_prob, capacity_factor=None,
        gate_param_attr=_weight(std), param_attr=_weight(std),
        bias_attr=False, return_aux_loss=True, return_expert_count=True)
    return layers.elementwise_add(h, y), aux, count


def olmoe(vocab_size, seq_len, n_layer=16, hidden=2048, n_head=16,
          n_expert=64, top_k=8, expert_width=1024, eps=1e-5,
          rope_theta=10000.0, norm_topk_prob=False, aux_coef=0.01, std=0.02):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, feed names)."""
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std))
    auxes, counts = [], []
    for _ in range(n_layer):
        x, aux, count = decoder_layer(x, hidden, n_head, n_expert, top_k,
                                      expert_width, eps, rope_theta,
                                      norm_topk_prob, std)
        auxes.append(aux)
        counts.append(count)
    logits = _proj(layers.rms_norm(x, epsilon=eps), vocab_size, std)
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[-1, vocab_size]),
        layers.reshape(labels, shape=[-1, 1]))
    loss = layers.mean(cost)
    if aux_coef:
        # the mean over layers, as the `olmoe` model type averages its
        # router losses before the coefficient
        loss = loss + (aux_coef / n_layer) * layers.sums(auxes)
    return loss, counts, ['input_ids', 'labels']


def get_model(batch_size=4, seq_len=32, vocab_size=256, n_layer=1, hidden=64,
              n_head=2, n_expert=8, top_k=2, expert_width=32,
              learning_rate=4e-4):
    """A small preset by default (the published sizes are
    chipbench/configs/olmoe_1b_7b.json's); Adam as the OLMoE paper has it,
    without the decoupled weight decay. The readers yield packed rows of
    uniform random ids."""
    loss, counts, feeds = olmoe(vocab_size, seq_len, n_layer, hidden, n_head,
                                n_expert, top_k, expert_width)
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
