"""LFM2-MoE (`model_type` lfm2_moe; the source's config.json is
chipbench/configs/lfm2_8b_a1b.json's): a pre-norm causal decoder whose
token mixer is a double-gated SHORT CONVOLUTION in three layers of four
(`layer_types` says which) and softmax attention over grouped key-value
heads in the others, whose leading layers' feed-forward is dense and whose
other layers' is a sparse-expert block with a sigmoid router and a
selection bias, and whose output head is the token embedding, transposed.
Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
rms(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias
anywhere:

    x = E[ids]
    layer l:  h = x + operator_l(rms(x));   x = h + ff_l(rms(h))
    out = rms(x) E^T (the head is TIED);  loss = mean cross entropy

  Short convolution (`layer_types[l] == 'conv'`, g = rms(x)):
    [B | C | x~] = g Win                 three chunks of hidden, that order
    c[t] = sum_j w[j] (B * x~)[t - (K - 1) + j]    per channel, causal, K
                                          taps, zeros before a row's
                                          first token, NO activation
    operator = (C * c) Wout    layers.causal_conv1d(in_gate=, out_gate=)

  Attention (`'full_attention'`, g = rms(x)):
    q = g Wq (n_head x d_head);  k = g Wk;  v = g Wv (n_kv_head x d_head)
    q, k = rms over each head's d_head (one weight vector for the queries'
    heads, one for the keys'), THEN rotary over the whole head (pairs
    (i, i + d_head / 2)); query head h reads key-value head
    h // (n_head / n_kv_head); causal, scores / sqrt(d_head)
    operator = softmax(s) v Wo

  Dense feed-forward (l < n_dense, m = rms(h)):  (silu(m W1) * (m W3)) W2

  Experts (l >= n_dense, m = rms(h)), layers.moe_mlp:
    s = sigmoid(m Wr) over all n_expert, float32;  chosen = top_k of
    (s + b), b the selection bias (a persistable no gradient reaches);
    gates = s over the chosen / (their sum + norm_eps), times gate_scale
    ff = sum over the chosen experts THAT ARE HELD (`experts_held`) of
         gate_e * (silu(m W1_e) * (m W3_e)) W2_e          dropless
    after the step (router_bias_updates, built after minimize):
        b_e <- b_e + rate * sign(mean(c) - c_e),  c the step's counts

`run_layers` names the layers that RUN by their index in `layer_types` (a
pipeline stage runs a stretch of them); a layer is dense where its index
is under `n_dense`. Each layer is one `fluid.recompute_guard()` region
(the step keeps a layer's input, its residual after the operator and the
outputs of the operator's input projections, `fluid.recompute_keep`, and
recomputes the rest: the feed-forward and the experts); every short
convolution is built under `fluid.name_scope('short_conv_mixer')`, every
attention operator under `'attention_mixer'`, the bias update under
`router_bias`. The head's projection is the LAST `mul` built (chipbench's
loss_head_ms reads that): the embedding parameter goes through
`layers.transpose` into `layers.mul`, so one parameter `[vocab, hidden]`
has two uses, `append_backward` sums the lookup's scattered gradient and
the head's dense one, and the optimizer sees one. The whole train step is
one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import layers

__all__ = ['lfm2_moe', 'decoder_layer', 'short_conv_mixer',
           'router_bias_updates', 'get_model', 'LAYER_TYPES']

EMBEDDING = 'lfm2_tok_emb'

# the published order of the 24 operators (config.json `layer_types`)
LAYER_TYPES = tuple(
    'full_attention' if i in (2, 6, 10, 14, 18, 21) else 'conv'
    for i in range(24))


def _weight(std, name=None):
    return fluid.ParamAttr(name=name,
                           initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _gated_mlp(m, hidden, width, std):
    """(silu(m W1) * (m W3)) W2; parameters in creation order: W1, W3,
    W2."""
    return _proj(layers.elementwise_mul(layers.swish(_proj(m, width, std)),
                                        _proj(m, width, std)), hidden, std)


def _unmarked(var):
    return var


def short_conv_mixer(g, c, keep=_unmarked):
    """The double-gated short convolution on the normed input `g`.
    Parameters in creation order: Win, the filter, Wout. `keep` is called
    on the input projection's output (`decoder_layer`)."""
    with fluid.name_scope('short_conv_mixer'):
        obs.counter('shortconv.mixers').inc()               # build time
        b, gate, x = layers.split(
            keep(_proj(g, 3 * c['hidden'], c['std'])), 3, dim=-1)
        # the filter starts where torch's Conv1d leaves it: uniform within
        # 1 / sqrt(taps) (a depthwise filter's fan-in is its taps)
        bound = c['conv_kernel'] ** -0.5
        y = layers.causal_conv1d(
            x, c['conv_kernel'], act=None, in_gate=b, out_gate=gate,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Uniform(-bound, bound)))
        return _proj(y, c['hidden'], c['std'])


def attention_mixer(g, c, keep=_unmarked):
    """Grouped-head causal attention on the normed input `g`, the queries
    and keys normed a head and then turned. Parameters in creation order:
    Wq, Wk, Wv, the query norm, the key norm, Wo. `keep` is called on the
    outputs of Wq, Wk and Wv."""
    d = c['d_head']

    def heads(t):
        return layers.transpose(t, perm=[0, 2, 1, 3])

    with fluid.name_scope('attention_mixer'):
        q = layers.reshape(keep(_proj(g, c['n_head'] * d, c['std'])),
                           shape=[0, 0, c['n_head'], d])
        k, v = (layers.reshape(keep(_proj(g, c['n_kv_head'] * d, c['std'])),
                               shape=[0, 0, c['n_kv_head'], d])
                for _ in range(2))
        q, k = (layers.rotary_embedding(
            heads(layers.rms_norm(t, epsilon=c['eps'])),
            base=c['rope_theta']) for t in (q, k))
        ctx = layers.fused_attention(q, k, heads(v), causal=True,
                                     scale=d ** -0.5)
        return _proj(layers.reshape(heads(ctx),
                                    shape=[0, 0, c['n_head'] * d]),
                     c['hidden'], c['std'])


def expert_block(m, c):
    """Returns (output, assignments per expert, the selection bias).
    Parameters in creation order: the router, the experts' W1, W3 and W2
    stacks, the selection bias."""
    return layers.moe_mlp(
        m, num_experts=c['n_expert'], hidden_size=c['expert_width'],
        act='swish', gated=True, top_k=c['top_k'],
        norm_topk_prob=c['norm_topk_prob'], capacity_factor=None,
        experts_held=c['experts_held'], scoring='sigmoid',
        selection_bias=True, gate_scale=c['gate_scale'],
        norm_eps=c['norm_eps'], gate_param_attr=_weight(c['std']),
        param_attr=_weight(c['std']), bias_attr=False,
        return_expert_count=True)


def decoder_layer(x, index, c, keep=_unmarked):
    """Layer `index` of `layer_types`: its operator, then the dense
    feed-forward (index < n_dense) or the expert block. Returns (output,
    assignments per expert or None, the selection bias or None). `keep`
    is called on the residual `h` after the operator and on the outputs
    of the operator's input projections: whoever builds the layer inside
    a recompute region passes `fluid.recompute_keep` (`lfm2_moe`), and
    the backward pass then runs no projection of the operator again."""
    g = layers.rms_norm(x, epsilon=c['eps'])
    kind = c['layer_types'][index]
    if kind == 'conv':
        mixed = short_conv_mixer(g, c, keep)
    elif kind == 'full_attention':
        mixed = attention_mixer(g, c, keep)
    else:
        raise ValueError("lfm2_moe: layer %d is %r; 'conv' or "
                         "'full_attention'" % (index, kind))
    h = keep(layers.elementwise_add(x, mixed))
    m = layers.rms_norm(h, epsilon=c['eps'])
    if index < c['n_dense']:
        y, count, bias = _gated_mlp(m, c['hidden'], c['dense_width'],
                                    c['std']), None, None
    else:
        y, count, bias = expert_block(m, c)
    return layers.elementwise_add(h, y), count, bias


def lfm2_moe(vocab_size, seq_len, layer_types=LAYER_TYPES, run_layers=None,
             n_dense=2, hidden=2048, conv_kernel=3, n_head=32, n_kv_head=8,
             d_head=64, dense_width=7168, n_expert=32, top_k=4,
             expert_width=1792, experts_held=None, eps=1e-5, rope_theta=1e6,
             norm_topk_prob=True, gate_scale=1.0, norm_eps=1e-6, std=0.02):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, per-layer selection biases, feed
    names); counts and biases are of the expert layers in order.
    `run_layers` are the indices into `layer_types` of the layers that run
    (None: all of them). `experts_held` = (first, count): the chip's share
    of every layer's experts (layers.moe_mlp)."""
    c = dict(locals())
    run_layers = range(len(layer_types)) if run_layers is None \
        else run_layers
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std, EMBEDDING))
    counts, biases = [], []
    for i in run_layers:
        with fluid.recompute_guard():
            x, count, bias = decoder_layer(x, i, c,
                                           keep=fluid.recompute_keep)
        if count is not None:
            counts.append(count)
            biases.append(bias)
    # the tied head: the embedding's second use (the name bound again),
    # transposed, into the last `mul` built
    table = layers.create_parameter([vocab_size, hidden], 'float32',
                                    attr=_weight(std, EMBEDDING))
    logits = layers.mul(layers.rms_norm(x, epsilon=eps),
                        layers.transpose(table, perm=[1, 0]),
                        x_num_col_dims=2)
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
              layer_types=('conv', 'full_attention', 'conv', 'conv'),
              n_dense=1, hidden=64, n_head=4, n_kv_head=2, d_head=16,
              dense_width=128, n_expert=16, top_k=2, expert_width=32,
              experts_held=None, learning_rate=4e-4, bias_rate=0.001):
    """A small preset by default (the published sizes are
    chipbench/configs/lfm2_8b_a1b.json's); Adam without decoupled decay,
    then the bias update. The readers yield packed rows of uniform random
    ids."""
    loss, counts, biases, feeds = lfm2_moe(
        vocab_size, seq_len, layer_types=layer_types, n_dense=n_dense,
        hidden=hidden, n_head=n_head, n_kv_head=n_kv_head, d_head=d_head,
        dense_width=dense_width, n_expert=n_expert, top_k=top_k,
        expert_width=expert_width, experts_held=experts_held)
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
