"""Ling-3.0-flash (`model_type` bailing_hybrid; the source's config.json is
chipbench/configs/ling_3_0_flash.json's): a pre-norm causal decoder whose
token mixer is Kimi Delta Attention (Kimi Linear, arXiv:2510.26692: the
gated delta rule with a decay a CHANNEL) in five layers of six and
multi-head latent attention without a query latent in the sixth, whose
first layers' feed-forward is dense and whose other layers' is a
sparse-expert block under a sigmoid router with a selection bias, groups
and one shared expert. Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
norm(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias in any
projection:

    x = Emb[ids]
    layer i:  h = x + mixer_i(norm(x));   x = h + ffn_i(norm(h))
      mixer_i = MLA where (i + 1) % layer_group_size == 0, else KDA
      ffn_i   = Wdown(silu(Wgate m) * Wup m) for i < first_k_dense,
                the expert block after it
    loss = mean CE(norm(x_L) Whead, labels)                  Whead untied

  KDA mixer (u = norm(x); H heads of Dk = Dv = head_dim):
    q, k, v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))
              three depthwise causal convolutions of `conv_kernel` taps
    g    = gate_floor * sigmoid(exp(A_log_h) * (u Wf + dt_bias))
           [B, T, H, Dk], in (gate_floor, 0): a decay a channel, bounded
           below (the source's `kda_lower_bound` under `kda_safe_gate`)
    beta = sigmoid(u Wb)                                     [B, T, H]
    o    = layers.gated_delta_rule(q, k, v, g, beta): q and k l2-normalised
           over their head, q / sqrt(Dk), S = diag(exp(g_t)) S, then the
           delta rule
    mixer = (norm over each head of o * sigmoid((u Wg)_h)) Wo
            layers.gated_rms_norm(gate_act='sigmoid')

  MLA mixer: layers.latent_attention with no query latent, keys of
    d_nope + d_rope beside values of d_v, rotary on interleaved pairs and
    a sigmoid gate a head on the output.

  Expert block (m = norm(h)), layers.moe_mlp:
    s = sigmoid(m Wr);  c = s + b, b the selection bias (no gradient);
    the choice confined to the `topk_group` best of `n_group` groups of
    consecutive experts (a group's rank: the sum of its two largest c),
    the top k by c among those;  gates = gate_scale * s over the chosen,
    renormalised without b;  routed = the chosen experts THAT ARE HELD
    (`experts_held`), gated SiLU experts, dropless
    block = routed + Wdown_s(silu(Wgate_s m) * Wup_s m)       every token
    after the step (router_bias_updates, built after minimize):
        b_e <- b_e + rate * sign(mean(c) - c_e),  c the step's counts

Each decoder layer is one `fluid.recompute_guard()` region; the KDA mixer
(its norm included) is built under `fluid.name_scope('kda_mixer')`, the
MLA mixer under `latent_attention`, the shared expert under
`shared_expert`, the bias update under `router_bias`. The builder counts
each layer once as `bailing.layers{mixer=kda|mla, ffn=dense|experts}`. The
source clamps an expert's SwiGLU by layer
(`expert_swiglu_limit_list`); this file builds no clamp and refuses a
nonzero limit. Its multi-token prediction module carries a loss weight of
0 as published and is not built. The whole train step is one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import layers

__all__ = ['bailing_hybrid', 'decoder_layer', 'kda_mixer', 'is_mla',
           'router_bias_updates', 'get_model']


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _gated_mlp(m, hidden, width, std):
    """Wdown(silu(Wgate m) * Wup m); parameters in that order of names:
    gate, up, down."""
    return _proj(layers.elementwise_mul(layers.swish(_proj(m, width, std)),
                                        _proj(m, width, std)), hidden, std)


def is_mla(index, layer_group_size):
    """Layer `index` of the pattern: the last of each group of
    `layer_group_size` is latent attention, the others KDA."""
    return (index + 1) % layer_group_size == 0


def kda_mixer(x, c, index):
    """norm, then Kimi Delta Attention. Parameters in creation order: the
    input norm, Wq and its filter, Wk and its filter, Wv and its filter,
    Wf, dt_bias, A_log, Wb, Wg, the output norm, Wo."""
    heads, d, std = c['n_head'], c['head_dim'], c['std']
    width = heads * d
    with fluid.name_scope('kda_mixer'):
        u = layers.rms_norm(x, epsilon=c['eps'])
        q, k, v = (layers.reshape(
            layers.causal_conv1d(_proj(u, width, std), c['conv_kernel'],
                                 act='silu', param_attr=_weight(std)),
            shape=[0, 0, heads, d]) for _ in range(3))
        f = _proj(u, width, std)
        dt_bias = layers.create_parameter(
            [width], 'float32',
            default_initializer=fluid.initializer.Constant(1.0))
        # A = exp(A_log) from uniform(0, 16), as models/qwen3_next.py draws
        # it: a fast decay in most heads and a slow one in a few
        a_log = layers.create_parameter(
            [heads], 'float32',
            default_initializer=fluid.initializer.NumpyArrayInitializer(
                np.log(np.random.default_rng(index).uniform(0.0, 16.0, heads)
                       ).astype('float32')))
        g = layers.scale(layers.sigmoid(layers.elementwise_mul(
            layers.reshape(layers.elementwise_add(f, dt_bias, axis=-1),
                           shape=[0, 0, heads, d]),
            layers.exp(a_log), axis=2)), scale=float(c['gate_floor']))
        o = layers.gated_delta_rule(
            q, k, v, g, layers.sigmoid(_proj(u, heads, std)),
            chunk_size=c['chunk_size'], qk_l2norm=True,
            gate_floor=c['gate_floor'])
        y = layers.gated_rms_norm(
            o, layers.reshape(_proj(u, width, std), shape=[0, 0, heads, d]),
            epsilon=c['eps'], gate_act='sigmoid')
        return _proj(layers.reshape(y, shape=[0, 0, width]), c['hidden'],
                     std)


def mla_mixer(x, c):
    """norm, then latent attention. Parameters in creation order: the
    input norm, Wq, Wkva, the key-value latent's norm, Wkvb, Wgate, Wo."""
    with fluid.name_scope('latent_attention'):
        return layers.latent_attention(
            layers.rms_norm(x, epsilon=c['eps']), c['hidden'], c['n_head'],
            None, c['kv_rank'], c['d_nope'], c['d_rope'], c['d_v'],
            rope_theta=c['rope_theta'], epsilon=c['eps'],
            param_attr=_weight(c['std']), rope_interleave=True,
            head_gate=True)


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
        n_group=c['n_group'], topk_group=c['topk_group'],
        gate_param_attr=_weight(c['std']), param_attr=_weight(c['std']),
        bias_attr=False, return_expert_count=True)
    with fluid.name_scope('shared_expert'):
        shared = _gated_mlp(m, c['hidden'], c['shared_width'], c['std'])
    return layers.elementwise_add(routed, shared), count, bias


def decoder_layer(x, index, c, dense=None):
    """Layer `index` of the source's pattern: its mixer by the pattern,
    then the dense feed-forward (`dense`; by default the first
    `first_k_dense`) or the expert block. Returns (output, assignments per
    expert or None, the selection bias or None)."""
    mla = is_mla(index, c['layer_group_size'])
    if dense is None:
        dense = index < c['first_k_dense']
    obs.counter('bailing.layers', mixer='mla' if mla else 'kda',    # build
                ffn='dense' if dense else 'experts').inc()
    h = layers.elementwise_add(
        x, mla_mixer(x, c) if mla else kda_mixer(x, c, index))
    m = layers.rms_norm(h, epsilon=c['eps'])
    if dense:
        y, count, bias = _gated_mlp(m, c['hidden'], c['dense_width'],
                                    c['std']), None, None
    else:
        y, count, bias = expert_block(m, c)
    return layers.elementwise_add(h, y), count, bias


def bailing_hybrid(vocab_size, seq_len, n_layer=42, first_k_dense=2,
                   layer_group_size=6, hidden=2560, dense_width=6144,
                   n_head=32, head_dim=128, conv_kernel=4, gate_floor=-5.0,
                   kv_rank=512, d_nope=128, d_rope=64, d_v=128,
                   n_expert=512, top_k=8, n_group=8, topk_group=4,
                   expert_width=768, shared_width=768, experts_held=None,
                   eps=1e-6, rope_theta=6e6, norm_topk_prob=True,
                   gate_scale=2.5, std=0.006, chunk_size=64,
                   swiglu_limits=(), layer_ids=None):
    """Builds the training loss into the default main program. Returns
    (loss, per-layer expert counts, per-layer selection biases, feed
    names); counts and biases are of the expert layers in order.
    `experts_held` = (first, count): the chip's share of every layer's
    experts (layers.moe_mlp). `swiglu_limits`: the source's clamps on an
    expert's SwiGLU, of the layers built: a nonzero one is refused.
    `layer_ids`: the source's indices of the `n_layer` layers built (a
    stage that skips some), which say each one's mixer; the first
    `first_k_dense` BUILT are dense. None: 0 .. n_layer - 1."""
    layer_ids = list(range(n_layer) if layer_ids is None else layer_ids)
    if len(layer_ids) != n_layer:
        raise ValueError('bailing_hybrid: %d layers and layer_ids %r'
                         % (n_layer, layer_ids))
    if any(swiglu_limits):
        raise NotImplementedError(
            'bailing_hybrid: a clamp on the SwiGLU of an expert (limits %r) '
            'is not built; the layers kept must carry 0' % (swiglu_limits,))
    c = dict(locals())
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std))
    counts, biases = [], []
    for i, index in enumerate(layer_ids):
        with fluid.recompute_guard():
            x, count, bias = decoder_layer(x, index, c, i < first_k_dense)
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


def get_model(batch_size=2, seq_len=32, vocab_size=256, n_layer=3,
              first_k_dense=1, layer_group_size=3, hidden=64,
              dense_width=128, n_head=4, head_dim=16, kv_rank=16, d_nope=16,
              d_rope=8, d_v=16, n_expert=16, top_k=2, n_group=4,
              topk_group=2, expert_width=32, experts_held=None,
              learning_rate=4e-4, bias_rate=0.001):
    """A small preset by default (the published sizes are
    chipbench/configs/ling_3_0_flash.json's): layers dense-KDA, KDA, MLA;
    Adam without decoupled decay, then the bias update. The readers yield
    packed rows of uniform random ids."""
    loss, counts, biases, feeds = bailing_hybrid(
        vocab_size, seq_len, n_layer=n_layer, first_k_dense=first_k_dense,
        layer_group_size=layer_group_size, hidden=hidden,
        dense_width=dense_width, n_head=n_head, head_dim=head_dim,
        kv_rank=kv_rank, d_nope=d_nope, d_rope=d_rope, d_v=d_v,
        n_expert=n_expert, top_k=top_k, n_group=n_group,
        topk_group=topk_group, expert_width=expert_width,
        shared_width=expert_width, experts_held=experts_held, std=0.02,
        chunk_size=16)
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
