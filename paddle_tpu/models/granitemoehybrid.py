"""GraniteMoeHybrid (`model_type` granitemoehybrid, IBM Granite 4.0-H; the
source's config.json is chipbench/configs/granite_4_0_h_micro.json's): a
pre-norm causal decoder whose every layer is a token mixer AND a dense
gated feed-forward behind a norm each, whose mixer is a Mamba-2 state-space
layer (Dao and Gu 2024, arXiv:2405.21060) in nine layers of ten and
softmax attention over grouped key-value heads WITHOUT any positional
signal in the tenth (`layer_types` says which), and which carries four
scalar multipliers (the embedding's output, both branches of every layer,
the attention scores, the logits) and a head that is the token embedding,
transposed. With `num_local_experts` 0 (Granite 4.0-H Micro) no layer has
experts. Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
norm(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias in any
projection:

    x_0 = embedding_multiplier * E[ids]
    layer i (kind = layer_types[i]):
        h = x + residual_multiplier * mixer_i(norm1_i(x))
        x = h + residual_multiplier * W_out(silu(a) * b),
                                            [a | b] = norm2_i(h) W_in
    logits = norm_f(x_L) E^T / logits_scaling       the head is TIED
    loss = mean cross entropy

  Mamba-2 mixer (`mamba`; models/nemotron_h.py `mamba_mixer`, which has the
  equations: H heads of P, G groups of state N, the convolution with its
  bias over x | B | C, dt = softplus(dt + dt_bias), the scan with the skip
  D, the gate-first RMS norm by group). Granite 4.0-H Micro has ONE group:
  every head reads the same B and C and the gated norm runs over all H P
  columns.

  Attention (`attention`, u = norm1(x); models/nemotron_h.py
  `attention_mixer` with `attn_scale`):
    q = u Wq (n_head x d_head);  k = u Wk;  v = u Wv (n_kv_head x d_head)
    query head h reads key-value head h // (n_head / n_kv_head); causal;
    scores * attention_multiplier (a number of the configuration's, NOT
    d_head^-0.5); no rotary and no other position (position_embedding_type
    `nope`: the Mamba-2 layers order the tokens)
    mixer = softmax(s) v Wo

`run_layers` names the layers that RUN by their index in `layer_types` (a
pipeline stage runs a stretch of them). Each LAYER is one
`fluid.recompute_guard()` region (the step keeps a layer's input, its
residual after the mixer and the outputs of the mixer's input projections,
`decoder_layer`, and recomputes the rest); every Mamba-2 mixer is built
under `fluid.name_scope('mamba_mixer')`, the attention mixer under
`'attention_mixer'`, every feed-forward under `'dense_mlp'`; the builder
counts `granite.layers{kind=}` once a layer it builds. The multipliers are
`layers.scale` ops. The head's projection is the LAST `mul` built
(chipbench's loss_head_ms reads that): the embedding parameter goes through
`layers.transpose` into `layers.mul`, so one parameter `[vocab, hidden]`
has two uses, `append_backward` sums the lookup's scattered gradient and
the head's dense one, and the optimizer sees one. The whole train step is
one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import layers
from paddle_tpu.models.nemotron_h import (_unmarked, attention_mixer,
                                          mamba_mixer)

__all__ = ['granitemoehybrid', 'decoder_layer', 'dense_mlp', 'get_model',
           'LAYER_TYPES']

EMBEDDING = 'granite_tok_emb'

# the published order of Granite 4.0-H Micro's 40 mixers (config.json
# `layer_types`)
LAYER_TYPES = tuple('attention' if i in (5, 15, 25, 35) else 'mamba'
                    for i in range(40))


def _weight(std, name=None):
    return fluid.ParamAttr(name=name,
                           initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def dense_mlp(m, c):
    """W_out(silu(a) * b) with [a | b] = m W_in: ONE input matrix of
    2 x `mlp_width` columns, the gate's half first. Parameters in creation
    order: W_in, W_out."""
    with fluid.name_scope('dense_mlp'):
        a, b = layers.split(_proj(m, 2 * c['mlp_width'], c['std']), 2,
                            dim=-1)
        return _proj(layers.elementwise_mul(layers.swish(a), b),
                     c['hidden'], c['std'])


def decoder_layer(x, index, c, keep=_unmarked):
    """Layer `index` of `layer_types`: its mixer, then the dense gated
    feed-forward, each behind its norm and scaled into the residual.
    `keep` is called on the residual `h` after the mixer and on the
    outputs of the mixer's input projections: whoever builds the layer
    inside a recompute region passes `fluid.recompute_keep`
    (`granitemoehybrid`), and the backward pass then runs neither those
    projections nor the mixer's output projection again; the
    feed-forward's matmuls are not marked (W_in's output is four times
    `h`)."""
    kind = c['layer_types'][index]
    u = layers.rms_norm(x, epsilon=c['eps'])
    if kind == 'mamba':
        mixed = mamba_mixer(u, c, index, keep)
    elif kind == 'attention':
        mixed = attention_mixer(u, c, keep)
    else:
        raise ValueError("granitemoehybrid: layer %d is %r; 'mamba' or "
                         "'attention'" % (index, kind))
    obs.counter('granite.layers', kind=kind).inc()          # build time
    h = keep(layers.elementwise_add(
        x, layers.scale(mixed, scale=c['residual_scale'])))
    y = dense_mlp(layers.rms_norm(h, epsilon=c['eps']), c)
    return layers.elementwise_add(
        h, layers.scale(y, scale=c['residual_scale']))


def granitemoehybrid(vocab_size, seq_len, layer_types=LAYER_TYPES,
                     run_layers=None, hidden=2048, ssm_heads=64,
                     ssm_head_dim=64, ssm_groups=1, ssm_state=128,
                     conv_kernel=4, chunk_size=256, n_head=32, n_kv_head=8,
                     d_head=64, mlp_width=8192, eps=1e-5,
                     embedding_scale=12.0, residual_scale=0.22,
                     attn_scale=0.015625, logits_scaling=8.0, dt_min=0.001,
                     dt_max=0.1, dt_floor=1e-4, std=0.02):
    """Builds the training loss into the default main program. Returns
    (loss, feed names). `run_layers` are the indices into `layer_types` of
    the layers that run (None: all of them)."""
    c = dict(locals())
    run_layers = range(len(layer_types)) if run_layers is None \
        else run_layers
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.scale(
        layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std, EMBEDDING)),
        scale=embedding_scale)
    for i in run_layers:
        with fluid.recompute_guard():
            x = decoder_layer(x, i, c, keep=fluid.recompute_keep)
    # the tied head: the embedding's second use (the name bound again),
    # transposed, into the last `mul` built
    table = layers.create_parameter([vocab_size, hidden], 'float32',
                                    attr=_weight(std, EMBEDDING))
    logits = layers.scale(
        layers.mul(layers.rms_norm(x, epsilon=eps),
                   layers.transpose(table, perm=[1, 0]), x_num_col_dims=2),
        scale=1.0 / logits_scaling)
    cost = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[-1, vocab_size]),
        layers.reshape(labels, shape=[-1, 1]))
    return layers.mean(cost), ['input_ids', 'labels']


def get_model(batch_size=2, seq_len=32, vocab_size=256,
              layer_types=('mamba', 'mamba', 'mamba', 'attention'),
              hidden=64, ssm_heads=4, ssm_head_dim=16, ssm_groups=1,
              ssm_state=16, chunk_size=16, n_head=4, n_kv_head=2, d_head=16,
              mlp_width=128, learning_rate=4e-4):
    """A small preset by default (the published sizes are
    chipbench/configs/granite_4_0_h_micro.json's; the four multipliers
    stay at theirs); Adam without decoupled decay. Returns (loss, None,
    train reader, test reader, feed names); the readers yield packed rows
    of uniform random ids."""
    loss, feeds = granitemoehybrid(
        vocab_size, seq_len, layer_types=layer_types, hidden=hidden,
        ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
        ssm_groups=ssm_groups, ssm_state=ssm_state, chunk_size=chunk_size,
        n_head=n_head, n_kv_head=n_kv_head, d_head=d_head,
        mlp_width=mlp_width)
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
