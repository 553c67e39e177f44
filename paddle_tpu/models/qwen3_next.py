"""Qwen3-Next (`model_type` qwen3_next; the source's config.json is
chipbench/configs/qwen3_next_80b_a3b.json's): a pre-norm causal decoder
whose token mixer is Gated DeltaNet linear attention in three layers of
four and gated softmax attention over grouped key-value heads in the
fourth, each followed by a sparse-expert block with a shared expert.
Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
norm(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm):

    layer l:  x = x + mixer_l(norm(x));   x = x + moe(norm(x))
    mixer_l is attention where (l + 1) % full_attention_interval == 0,
    Gated DeltaNet elsewhere

  Gated DeltaNet (a = norm(x)):
    [q | k | v | z] = a Wqkvz;  [b | al] = a Wba          no biases
    [q | k | v] = silu(causal depthwise conv of kernel 4 over q | k | v)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(al + dt_bias)
    o = gated_delta_rule(q, k, v, g, beta)   q, k l2-normalised over their
                                  head, q / sqrt(Dk), a key head serving
                                  Hv / Hk value heads
    mixer = (norm over each head of o * silu(z)) Wo    layers.gated_rms_norm

  Gated attention:
    [qh | gate] = a Wq, per head;  kh = a Wk;  vh = a Wv   fewer kv heads
    qh, kh = norm over each head;  rotary on the first rotary_dim of it
    mixer = (causal_softmax(qh kh^T / sqrt(D)) vh * sigmoid(gate)) Wo

  Expert block (m = norm(x)):
    routed = layers.moe_mlp: top_k of num_experts by softmax, the gates
             renormalised over the chosen, gated SiLU experts, dropless;
             with `experts_held` this chip's share of them
    shared = sigmoid(m w_sg) * Wdown(silu(Wgate m) * (Wup m))
    moe    = routed + shared

    out = norm(x) Whead (untied);  loss = mean cross entropy
          + aux_coef * mean over layers of the load-balancing loss

Departures from the source's model code, in name only: its norms carry
(1 + w) with w from 0, the same function as w from 1 while nothing decays
the weights; it lays the columns of Wqkvz and Wba out key head by key
head, a permutation of a random matrix. The whole train step is one XLA
module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['qwen3_next', 'decoder_layer', 'get_model']


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def delta_net(x, hidden, n_key, n_value, d_key, d_value, conv_kernel, eps,
              std, chunk_size, a_seed):
    """Gated DeltaNet's mixer. Parameters in creation order: the input
    norm, Wqkvz, Wba, the convolution's filter, dt_bias, A_log, the output
    norm, Wo."""
    key, value = n_key * d_key, n_value * d_value
    a = layers.rms_norm(x, epsilon=eps)
    qkv, z = layers.split(_proj(a, 2 * key + 2 * value, std),
                          [2 * key + value, value], dim=-1)
    b, al = layers.split(_proj(a, 2 * n_value, std), 2, dim=-1)
    q, k, v = layers.split(
        layers.causal_conv1d(qkv, conv_kernel, act='silu',
                             param_attr=_weight(std)),
        [key, key, value], dim=-1)
    dt_bias = layers.create_parameter(
        [n_value], 'float32',
        default_initializer=fluid.initializer.Constant(1.0))
    # A = exp(A_log) from uniform(0, 16), as the source's model code draws
    # it: a fast decay in most heads and a slow one in a few
    a_log = layers.create_parameter(
        [n_value], 'float32',
        default_initializer=fluid.initializer.NumpyArrayInitializer(
            np.log(np.random.default_rng(a_seed).uniform(0.0, 16.0, n_value)
                   ).astype('float32')))
    g = layers.scale(layers.elementwise_mul(
        layers.softplus(layers.elementwise_add(al, dt_bias, axis=-1)),
        layers.exp(a_log), axis=-1), scale=-1.0)
    o = layers.gated_delta_rule(
        layers.reshape(q, shape=[0, 0, n_key, d_key]),
        layers.reshape(k, shape=[0, 0, n_key, d_key]),
        layers.reshape(v, shape=[0, 0, n_value, d_value]),
        g, layers.sigmoid(b), chunk_size=chunk_size, qk_l2norm=True)
    y = layers.gated_rms_norm(
        o, layers.reshape(z, shape=[0, 0, n_value, d_value]), epsilon=eps)
    return _proj(layers.reshape(y, shape=[0, 0, value]), hidden, std)


def gated_attention(x, hidden, n_head, n_kv_head, d_head, rotary_dim, eps,
                    rope_theta, std):
    """Parameters in creation order: the input norm, Wq (queries and
    gates), Wk, Wv, the query norm, the key norm, Wo."""
    a = layers.rms_norm(x, epsilon=eps)
    q, gate = layers.split(
        layers.reshape(_proj(a, n_head * 2 * d_head, std),
                       shape=[0, 0, n_head, 2 * d_head]), 2, dim=-1)
    k, v = (layers.reshape(_proj(a, n_kv_head * d_head, std),
                           shape=[0, 0, n_kv_head, d_head])
            for _ in range(2))

    def heads(t):
        return layers.transpose(t, perm=[0, 2, 1, 3])

    q, k = (layers.rotary_embedding(heads(layers.rms_norm(t, epsilon=eps)),
                                    base=rope_theta, rotary_dim=rotary_dim)
            for t in (q, k))
    ctx = layers.fused_attention(q, k, heads(v), causal=True,
                                 scale=d_head ** -0.5)
    ctx = layers.reshape(heads(ctx), shape=[0, 0, n_head * d_head])
    gate = layers.reshape(gate, shape=[0, 0, n_head * d_head])
    return _proj(layers.elementwise_mul(ctx, layers.sigmoid(gate)), hidden,
                 std)


def expert_block(x, hidden, n_expert, top_k, expert_width, shared_width,
                 experts_held, eps, norm_topk_prob, std):
    """Returns (output, load-balancing loss, assignments per expert).
    Parameters in creation order: the norm, the router, the experts' gate,
    up and down stacks, the shared expert's gate, up and down projections,
    the shared expert's own gate."""
    m = layers.rms_norm(x, epsilon=eps)
    routed, aux, count = layers.moe_mlp(
        m, num_experts=n_expert, hidden_size=expert_width, act='swish',
        gated=True, top_k=top_k, norm_topk_prob=norm_topk_prob,
        capacity_factor=None, experts_held=experts_held,
        gate_param_attr=_weight(std), param_attr=_weight(std),
        bias_attr=False, return_aux_loss=True, return_expert_count=True)
    shared = _proj(layers.elementwise_mul(
        layers.swish(_proj(m, shared_width, std)),
        _proj(m, shared_width, std)), hidden, std)
    shared = layers.elementwise_mul(shared,
                                    layers.sigmoid(_proj(m, 1, std)))
    return layers.elementwise_add(routed, shared), aux, count


def decoder_layer(x, index, cfg):
    """Layer `index` of the pattern. Returns (output, load-balancing loss,
    assignments per expert)."""
    c = cfg
    if (index + 1) % c['full_attention_interval'] == 0:
        mixed = gated_attention(x, c['hidden'], c['n_head'], c['n_kv_head'],
                                c['d_head'], c['rotary_dim'], c['eps'],
                                c['rope_theta'], c['std'])
    else:
        mixed = delta_net(x, c['hidden'], c['n_key'], c['n_value'],
                          c['d_key'], c['d_value'], c['conv_kernel'],
                          c['eps'], c['std'], c['chunk_size'], index)
    h = layers.elementwise_add(x, mixed)
    y, aux, count = expert_block(
        h, c['hidden'], c['n_expert'], c['top_k'], c['expert_width'],
        c['shared_width'], c['experts_held'], c['eps'], c['norm_topk_prob'],
        c['std'])
    return layers.elementwise_add(h, y), aux, count


def qwen3_next(vocab_size, seq_len, n_layer=48, hidden=2048,
               full_attention_interval=4, n_head=16, n_kv_head=2, d_head=256,
               rotary_dim=64, n_key=16, n_value=32, d_key=128, d_value=128,
               conv_kernel=4, n_expert=512, top_k=10, expert_width=512,
               shared_width=512, experts_held=None, eps=1e-6,
               rope_theta=1e7, norm_topk_prob=True, aux_coef=0.001,
               std=0.02, chunk_size=64):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, feed names). `experts_held` = (first,
    count): the chip's share of every layer's experts (layers.moe_mlp)."""
    cfg = dict(locals())
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std))
    auxes, counts = [], []
    for i in range(n_layer):
        x, aux, count = decoder_layer(x, i, cfg)
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
              n_head=4, n_kv_head=2, d_head=16, rotary_dim=4, n_key=2,
              n_value=4, d_key=8, d_value=8, n_expert=16, top_k=2,
              expert_width=32, experts_held=None, learning_rate=4e-4):
    """A small preset by default (the published sizes are
    chipbench/configs/qwen3_next_80b_a3b.json's); Adam without decoupled
    decay. The readers yield packed rows of uniform random ids."""
    loss, counts, feeds = qwen3_next(
        vocab_size, seq_len, n_layer=n_layer, hidden=hidden, n_head=n_head,
        n_kv_head=n_kv_head, d_head=d_head, rotary_dim=rotary_dim,
        n_key=n_key, n_value=n_value, d_key=d_key, d_value=d_value,
        n_expert=n_expert, top_k=top_k, expert_width=expert_width,
        shared_width=expert_width, experts_held=experts_held,
        chunk_size=16)
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
