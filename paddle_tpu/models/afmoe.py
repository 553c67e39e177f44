"""AFMoE (`model_type` afmoe, Trinity-Mini; the source's config.json is
chipbench/configs/trinity_mini_26b_a3b.json's): a causal decoder whose
mixer is grouped-head softmax attention that is GATED, whose queries and
keys are normed a head, and which is of two kinds by layer (`layer_types`:
a sliding window with rotary positions, or every earlier key with no
positional encoding at all); whose two branches are normed on their way IN
and on their way OUT (sandwich norms); whose embedding is scaled by
sqrt(hidden); whose leading layers' feed-forward is dense and whose other
layers' is a sparse-expert block with a sigmoid router and a selection
bias beside one shared expert. Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
rms(t, w) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias
anywhere:

    x_0 = E[ids] * sqrt(hidden)                   mup_enabled: the scale is
                                                  on the lookup's OUTPUT
    layer l:
      g = rms(x, w_in)
      q = g Wq (n_head x d_head);  k = g Wk;  v = g Wv (n_kv_head x d_head)
      q, k = rms over each head's d_head (one weight vector for the
             queries' heads, one for the keys')
      layer_types[l] == 'sliding_attention':
          q, k = rotary(q), rotary(k) over the whole head, rotate-half
          pairs (i, i + d_head / 2);  query i sees keys j with
          0 <= i - j < window (the window counts the query's own position)
      'full_attention':  NO positional encoding;  j <= i
      query head h reads key-value head h // (n_head / n_kv_head);
      s_ij = q_i . k_j / sqrt(d_head);  a = softmax(s) v
      a = a * sigmoid(g Wg)                       Wg: hidden -> n_head x
                                                  d_head, a projection of
                                                  its own of the SAME g
      h = x + rms(a Wo, w_post_attn)              the norm is on the
                                                  branch's OUTPUT
      m = rms(h, w_pre_mlp)
      l < n_dense:  f = (silu(m W1) * (m W3)) W2
      else (layers.moe_mlp):
          s = sigmoid(m Wr) over all n_expert, float32;  chosen = top_k
          of (s + b), b the selection bias (a persistable no gradient
          reaches);  gates = gate_scale * s over the chosen / (their sum
          + norm_eps)
          f = sum over the chosen experts THAT ARE HELD (`experts_held`)
              of gate_e * (silu(m W1_e) * (m W3_e)) W2_e       dropless
            + (silu(m S1) * (m S3)) S2            the shared expert:
                                                  ungated, every token
      x' = h + rms(f, w_post_mlp)
    out = rms(x_L, w_final) Whead (untied);  loss = mean cross entropy
    after the step (router_bias_updates, built after minimize):
        b_e <- b_e + rate * sign(mean(c) - c_e),  c the step's counts

Departures: none from the equations above; what the source's config.json
has no key for (the gate, the per-head norms, which layers turn, the
sandwich order, where the muP scale sits, the bias's rule) is the public
modelling code's and stands under `assumed` in the configuration's file.
No auxiliary loss.

`run_layers` names the layers that RUN by their index in `layer_types` (a
pipeline stage runs a stretch of them); a layer is dense where its index
is under `n_dense`. Each layer is one `fluid.recompute_guard()` region. A
layer's mixer (projections, the per-head norms, rotary, the attention
call, the gate, Wo) is built under `fluid.name_scope('window_attention')`
or `fluid.name_scope('global_attention')` by its kind, the two norms on
the branches' outputs under `'sandwich_norm'`, the shared expert under
`'shared_expert'`, the bias update under `'router_bias'`. The head is the
last fc built (chipbench's loss_head_ms reads that). The whole train step
is one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['afmoe', 'attention', 'decoder_layer', 'router_bias_updates',
           'get_model', 'LAYER_TYPES']

# the published order of the 32 mixers (config.json `layer_types`)
LAYER_TYPES = tuple('full_attention' if i % 4 == 3 else 'sliding_attention'
                    for i in range(32))


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _gated_mlp(m, hidden, width, std):
    """(silu(m W1) * (m W3)) W2; parameters in creation order: W1, W3,
    W2."""
    return _proj(layers.elementwise_mul(layers.swish(_proj(m, width, std)),
                                        _proj(m, width, std)), hidden, std)


def attention(g, windowed, c):
    """The mixer on the normed input `g`: windowed with rotary positions,
    or global without any. Parameters in creation order: Wq, Wk, Wv, the
    query norm, the key norm, Wg, Wo."""
    d = c['d_head']

    def heads(t):
        return layers.transpose(t, perm=[0, 2, 1, 3])

    q = layers.reshape(_proj(g, c['n_head'] * d, c['std']),
                       shape=[0, 0, c['n_head'], d])
    k, v = (layers.reshape(_proj(g, c['n_kv_head'] * d, c['std']),
                           shape=[0, 0, c['n_kv_head'], d])
            for _ in range(2))
    q, k = (heads(layers.rms_norm(t, epsilon=c['eps'])) for t in (q, k))
    if windowed:
        q, k = (layers.rotary_embedding(t, base=c['rope_theta'])
                for t in (q, k))
    ctx = layers.fused_attention(q, k, heads(v), causal=True,
                                 scale=d ** -0.5,
                                 window=c['window'] if windowed else None)
    ctx = layers.reshape(heads(ctx), shape=[0, 0, c['n_head'] * d])
    gate = layers.sigmoid(_proj(g, c['n_head'] * d, c['std']))
    return _proj(layers.elementwise_mul(ctx, gate), c['hidden'], c['std'])


def expert_block(m, c):
    """Returns (output, assignments per expert, the selection bias).
    Parameters in creation order: the router, the experts' W1, W3 and W2
    stacks, the selection bias, the shared expert's W1, W3 and W2."""
    routed, count, bias = layers.moe_mlp(
        m, num_experts=c['n_expert'], hidden_size=c['expert_width'],
        act='swish', gated=True, top_k=c['top_k'],
        norm_topk_prob=c['norm_topk_prob'], capacity_factor=None,
        experts_held=c['experts_held'], scoring='sigmoid',
        selection_bias=True, gate_scale=c['gate_scale'],
        norm_eps=c['norm_eps'], gate_param_attr=_weight(c['std']),
        param_attr=_weight(c['std']), bias_attr=False,
        return_expert_count=True)
    with fluid.name_scope('shared_expert'):
        shared = _gated_mlp(m, c['hidden'], c['shared_width'], c['std'])
    return layers.elementwise_add(routed, shared), count, bias


def _branch_out(y, c):
    """The norm on a branch's output, before it joins the stream."""
    with fluid.name_scope('sandwich_norm'):
        return layers.rms_norm(y, epsilon=c['eps'])


def decoder_layer(x, index, c):
    """Layer `index` of `layer_types`: its mixer, then the dense
    feed-forward (index < n_dense) or the expert block, each between two
    norms. Returns (output, assignments per expert or None, the selection
    bias or None). Parameters in creation order: the input norm, the
    mixer's, the post-attention norm, the pre-feed-forward norm, the
    feed-forward's, the post-feed-forward norm."""
    kind = c['layer_types'][index]
    if kind not in ('sliding_attention', 'full_attention'):
        raise ValueError("afmoe: layer %d is %r; 'sliding_attention' or "
                         "'full_attention'" % (index, kind))
    windowed = kind == 'sliding_attention'
    g = layers.rms_norm(x, epsilon=c['eps'])
    with fluid.name_scope('window_attention' if windowed
                          else 'global_attention'):
        mixed = attention(g, windowed, c)
    h = layers.elementwise_add(x, _branch_out(mixed, c))
    m = layers.rms_norm(h, epsilon=c['eps'])
    if index < c['n_dense']:
        y, count, bias = _gated_mlp(m, c['hidden'], c['dense_width'],
                                    c['std']), None, None
    else:
        y, count, bias = expert_block(m, c)
    return layers.elementwise_add(h, _branch_out(y, c)), count, bias


def afmoe(vocab_size, seq_len, layer_types=LAYER_TYPES, run_layers=None,
          n_dense=2, hidden=2048, n_head=32, n_kv_head=4, d_head=128,
          window=2048, dense_width=6144, n_expert=128, top_k=8,
          expert_width=1024, shared_width=1024, experts_held=None,
          eps=1e-5, rope_theta=1e4, norm_topk_prob=True, gate_scale=2.826,
          norm_eps=1e-20, mup=True, std=0.02, emb_std=None):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, per-layer selection biases, feed
    names); counts and biases are of the expert layers in order.
    `run_layers` are the indices into `layer_types` of the layers that run
    (None: all of them). `experts_held` = (first, count): the chip's share
    of every layer's routed experts (layers.moe_mlp); the shared expert is
    whole on every chip. `mup`: the embedding's output times
    sqrt(hidden). `std` is the normal initializer of every matrix;
    `emb_std` the token embedding's where it differs (None: `std`)."""
    c = dict(locals())
    run_layers = range(len(layer_types)) if run_layers is None \
        else run_layers
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std if emb_std is None
                                            else emb_std))
    if mup:
        x = layers.scale(x, scale=float(hidden) ** 0.5)
    counts, biases = [], []
    for i in run_layers:
        with fluid.recompute_guard():
            x, count, bias = decoder_layer(x, i, c)
        if count is not None:
            counts.append(count)
            biases.append(bias)
    # the head is the last fc built (chipbench's loss_head_ms reads that)
    logits = _proj(layers.rms_norm(x, epsilon=eps), vocab_size, std)
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[-1, vocab_size]),
        layers.reshape(labels, shape=[-1, 1]))
    return layers.mean(cost), counts, biases, ['input_ids', 'labels']


def router_bias_updates(counts, biases, rate=0.001):
    """Every expert layer's selection bias moved by its step's load
    (layers.router_bias_update). Build AFTER minimize: the ops then follow
    the optimizer's in the one compiled step."""
    with fluid.name_scope('router_bias'):
        for count, bias in zip(counts, biases):
            layers.router_bias_update(bias, count, rate=rate)


def get_model(batch_size=2, seq_len=32, vocab_size=256,
              layer_types=('sliding_attention', 'sliding_attention',
                           'full_attention', 'sliding_attention'),
              n_dense=1, hidden=64, n_head=4, n_kv_head=2, d_head=16,
              window=8, dense_width=128, n_expert=16, top_k=2,
              expert_width=32, experts_held=None, learning_rate=4e-4,
              bias_rate=0.001):
    """A small preset by default (the published sizes are
    chipbench/configs/trinity_mini_26b_a3b.json's); Adam without decoupled
    decay, then the bias update. The readers yield packed rows of uniform
    random ids."""
    loss, counts, biases, feeds = afmoe(
        vocab_size, seq_len, layer_types=layer_types, n_dense=n_dense,
        hidden=hidden, n_head=n_head, n_kv_head=n_kv_head, d_head=d_head,
        window=window, dense_width=dense_width, n_expert=n_expert,
        top_k=top_k, expert_width=expert_width, shared_width=expert_width,
        experts_held=experts_held)
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
