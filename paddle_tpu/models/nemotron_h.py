"""Nemotron-H (`model_type` nemotron_h; the source's config.json is
chipbench/configs/nemotron_3_nano_30b_a3b.json's; the family's report is
arXiv:2504.03624): a pre-norm causal decoder whose blocks are ONE mixer or
ONE feed-forward part each, read off a pattern string: `M` a Mamba-2 mixer
(Dao and Gu 2024, arXiv:2405.21060), `*` softmax attention over grouped
key-value heads without any positional signal, `E` a sparse-expert part
with a sigmoid router, a selection bias and one shared expert, the experts
squared-ReLU MLPs of two matrices. Built from fluid.layers.

No reference counterpart. With x of shape [B, T, hidden] and
norm(t) = w * t * rsqrt(mean(t^2) + eps) (layers.rms_norm), no bias in any
projection:

    x = Emb[ids]
    block i:  x = x + part_i(norm(x))          part_i by pattern[i]
    out = norm(x) Whead (untied);  loss = mean cross entropy

  Mamba-2 mixer (`M`, u = norm(x); H heads of P, G groups of state N):
    [z | xBC | dt] = u Win        widths H P | H P + 2 G N | H
    xBC = silu(causal depthwise conv of kernel K over xBC + b_conv)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y = layers.ssd_scan(x, dt, A, B, C, D): per head h of group h // (H/G)
        S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    mixer = (w * rmsnorm over each of G groups of (y * silu(z))) Wout
                          layers.gated_rms_norm(norm_before_gate=False)

  Attention (`*`, u = norm(x)):
    q = u Wq (n_head x d_head);  k = u Wk;  v = u Wv (n_kv_head x d_head)
    query head h reads key-value head h // (n_head / n_kv_head); causal,
    scores / sqrt(d_head), NO rotary and no other position: the Mamba-2
    blocks order the tokens
    mixer = softmax(s) v Wo

  Expert part (`E`, m = norm(x)), layers.moe_mlp:
    s = sigmoid(m Wr) over all n_expert, float32;  chosen = top_k of
    (s + b), b the selection bias (a persistable no gradient reaches);
    gates = gate_scale * s over the chosen, renormalised without b
    routed = sum over the chosen experts THAT ARE HELD (`experts_held`)
             of gate_e * relu(m W1_e)^2 W2_e              dropless
    part = routed + relu(m W1_s)^2 W2_s                   every token
    after the step (router_bias_updates, built after minimize):
        b_e <- b_e + rate * sign(mean(c) - c_e),  c the step's counts

Each block is one `fluid.recompute_guard()` region (the step keeps a
block's input and recomputes the rest); every Mamba-2 mixer is built under
`fluid.name_scope('mamba_mixer')`, the attention block's under
`'attention_mixer'`, the bias update under `router_bias`. The whole train
step is one XLA module.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

__all__ = ['nemotron_h', 'block', 'router_bias_updates', 'get_model']


def _weight(std):
    return fluid.ParamAttr(initializer=fluid.initializer.Normal(0., std))


def _proj(x, size, std):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     param_attr=_weight(std), bias_attr=False)


def _vector(values):
    """A float32 parameter a head from the numbers it starts at."""
    values = np.asarray(values, 'float32')
    return layers.create_parameter(
        [values.size], 'float32',
        default_initializer=fluid.initializer.NumpyArrayInitializer(values))


def head_vectors(n_head, seed, dt_min, dt_max, dt_floor):
    """(dt_bias, A_log, D) as Mamba-2's model code draws them: dt_bias the
    inverse softplus of a log-uniform step in [dt_min, dt_max] floored at
    dt_floor, A = exp(A_log) uniform in [1, 16], D = 1."""
    rng = np.random.default_rng(seed)
    dt = np.maximum(np.exp(rng.uniform(np.log(dt_min), np.log(dt_max),
                                       n_head)), dt_floor)
    return (dt + np.log(-np.expm1(-dt)), np.log(rng.uniform(1.0, 16.0,
                                                            n_head)),
            np.ones(n_head))


def _unmarked(var):
    return var


def mamba_mixer(u, c, index, keep=_unmarked):
    """The Mamba-2 mixer on the normed input `u`. Parameters in creation
    order: Win, the convolution's filter and bias, dt_bias, A_log, D, the
    gated norm's weight, Wout. `keep` is called on the input projection's
    output: a caller whose recompute regions have the room passes
    `fluid.recompute_keep` (GraniteMoeHybrid); this model's own blocks
    pass nothing."""
    h, p, g, n = c['ssm_heads'], c['ssm_head_dim'], c['ssm_groups'], \
        c['ssm_state']
    inner, width = h * p, g * n
    with fluid.name_scope('mamba_mixer'):
        z, xbc, dt = layers.split(keep(_proj(u, 2 * inner + 2 * width + h,
                                             c['std'])),
                                  [inner, inner + 2 * width, h], dim=-1)
        # the filter and its bias start where torch's Conv1d leaves them:
        # uniform within 1 / sqrt(taps)
        bound = c['conv_kernel'] ** -0.5
        taps, shift = (fluid.ParamAttr(
            initializer=fluid.initializer.Uniform(-bound, bound))
            for _ in range(2))
        x, b, cc = layers.split(
            layers.causal_conv1d(xbc, c['conv_kernel'], act='silu',
                                 param_attr=taps, bias_attr=shift),
            [inner, width, width], dim=-1)
        dt_bias, a_log, d = (_vector(v) for v in head_vectors(
            h, index, c['dt_min'], c['dt_max'], c['dt_floor']))
        dt = layers.softplus(layers.elementwise_add(dt, dt_bias, axis=-1))
        y = layers.ssd_scan(
            layers.reshape(x, shape=[0, 0, h, p]), dt,
            layers.scale(layers.exp(a_log), scale=-1.0),
            layers.reshape(b, shape=[0, 0, g, n]),
            layers.reshape(cc, shape=[0, 0, g, n]), d,
            chunk_size=c['chunk_size'])
        y = layers.gated_rms_norm(
            layers.reshape(y, shape=[0, 0, inner]), z, epsilon=c['eps'],
            norm_before_gate=False, groups=g)
        return _proj(y, c['hidden'], c['std'])


def attention_mixer(u, c, keep=_unmarked):
    """Grouped-head causal attention without positions on the normed input
    `u`, its scores scaled by `attn_scale` where the model states one
    (GraniteMoeHybrid), else by d_head^-0.5. Parameters in creation order:
    Wq, Wk, Wv, Wo. `keep` is called on the outputs of Wq, Wk and Wv, as
    `mamba_mixer`'s is on its input projection's."""
    d = c['d_head']

    def heads(t, n):
        return layers.transpose(layers.reshape(t, shape=[0, 0, n, d]),
                                perm=[0, 2, 1, 3])

    with fluid.name_scope('attention_mixer'):
        q = heads(keep(_proj(u, c['n_head'] * d, c['std'])), c['n_head'])
        k, v = (heads(keep(_proj(u, c['n_kv_head'] * d, c['std'])),
                      c['n_kv_head']) for _ in range(2))
        ctx = layers.fused_attention(q, k, v, causal=True,
                                     scale=c.get('attn_scale', d ** -0.5))
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, c['n_head'] * d])
        return _proj(ctx, c['hidden'], c['std'])


def _relu2_mlp(m, hidden, width, std):
    """relu(m W1)^2 W2."""
    return _proj(layers.square(layers.relu(_proj(m, width, std))), hidden,
                 std)


def expert_part(m, c):
    """Returns (output, assignments per expert, the selection bias).
    Parameters in creation order: the router, the experts' W1 and W2
    stacks, the selection bias, the shared expert's W1 and W2."""
    routed, count, bias = layers.moe_mlp(
        m, num_experts=c['n_expert'], hidden_size=c['expert_width'],
        act='relu2', gated=False, top_k=c['top_k'],
        norm_topk_prob=c['norm_topk_prob'], capacity_factor=None,
        experts_held=c['experts_held'], scoring='sigmoid',
        selection_bias=True, gate_scale=c['gate_scale'],
        gate_param_attr=_weight(c['std']), param_attr=_weight(c['std']),
        bias_attr=False, return_expert_count=True)
    shared = _relu2_mlp(m, c['hidden'], c['shared_width'], c['std'])
    return layers.elementwise_add(routed, shared), count, bias


def block(x, kind, index, c):
    """Block `index` of kind `kind` ('M', '*' or 'E'): one norm, one part,
    one residual add. Returns (output, assignments per expert or None, the
    selection bias or None)."""
    u = layers.rms_norm(x, epsilon=c['eps'])
    count = bias = None
    if kind == 'M':
        y = mamba_mixer(u, c, index)
    elif kind == '*':
        y = attention_mixer(u, c)
    elif kind == 'E':
        y, count, bias = expert_part(u, c)
    else:
        raise ValueError("nemotron_h: block %d is %r; 'M', '*' or 'E' (a "
                         "dense '-' part is in no published pattern here)"
                         % (index, kind))
    return layers.elementwise_add(x, y), count, bias


def nemotron_h(vocab_size, seq_len, pattern='MEMEM*EME', hidden=2688,
               ssm_heads=64, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
               conv_kernel=4, chunk_size=128, n_head=32, n_kv_head=2,
               d_head=128, n_expert=128, top_k=6, expert_width=1856,
               shared_width=3712, experts_held=None, eps=1e-5,
               norm_topk_prob=True, gate_scale=2.5, dt_min=0.001,
               dt_max=0.1, dt_floor=1e-4, std=0.02):
    """Builds the training loss into the default main program. Returns
    (loss, per-block expert counts, per-block selection biases, feed
    names); counts and biases are of the expert blocks in order.
    `pattern` gives each block's kind. `experts_held` = (first, count):
    the chip's share of every expert block's experts (layers.moe_mlp)."""
    c = dict(locals())
    ids = layers.data(name='input_ids', shape=[seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=ids, size=[vocab_size, hidden],
                         param_attr=_weight(std))
    counts, biases = [], []
    for i, kind in enumerate(pattern):
        with fluid.recompute_guard():
            x, count, bias = block(x, kind, i, c)
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
    """Every expert block's selection bias moved by its step's load
    (layers.router_bias_update). Build AFTER minimize: the ops then follow
    the optimizer's in the one compiled step."""
    with fluid.name_scope('router_bias'):
        for count, bias in zip(counts, biases):
            layers.router_bias_update(bias, count, rate=rate)


def get_model(batch_size=2, seq_len=32, vocab_size=256, pattern='MEM*E',
              hidden=64, ssm_heads=4, ssm_head_dim=16, ssm_groups=2,
              ssm_state=16, chunk_size=16, n_head=4, n_kv_head=2, d_head=16,
              n_expert=16, top_k=2, expert_width=32, experts_held=None,
              learning_rate=4e-4, bias_rate=0.001):
    """A small preset by default (the published sizes are
    chipbench/configs/nemotron_3_nano_30b_a3b.json's); Adam without
    decoupled decay, then the bias update. The readers yield packed rows
    of uniform random ids."""
    loss, counts, biases, feeds = nemotron_h(
        vocab_size, seq_len, pattern=pattern, hidden=hidden,
        ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
        ssm_groups=ssm_groups, ssm_state=ssm_state, chunk_size=chunk_size,
        n_head=n_head, n_kv_head=n_kv_head, d_head=d_head,
        n_expert=n_expert, top_k=top_k, expert_width=expert_width,
        shared_width=2 * expert_width, experts_held=experts_held)
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
