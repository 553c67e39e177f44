"""Plain reference: the Transformer of Vaswani et al. 2017 (arXiv:1706.03762),
forward pass and loss in straightforward jax.numpy, float32, no kernel, no
Fluid code. Written from the paper, sections 3.1 to 3.5 and 5.4:

  - embeddings scaled by sqrt(d_model) plus the sinusoidal position code;
  - post-norm sublayers, LayerNorm(x + Sublayer(x)), epsilon 1e-5;
  - scaled dot-product attention over n_head heads of d_model / n_head,
    projections without bias; keys at pad positions (id 0) are masked, the
    decoder's self-attention is causal;
  - position-wise feed-forward with ReLU, with biases;
  - an output projection without bias, label smoothing 0.1 against the
    uniform distribution, cross entropy averaged over non-pad labels.

Departures from the paper, each the configuration's (configs/*.json,
`assumed`): the three vocabulary matrices are not shared, and there is no
dropout here at all: the check compares deterministic passes.

Parameters arrive as a flat dict of reference paths (`enc0.self.qkvo`,
`dec3.ffn.w1b1`, ...), each a list of arrays or one array; weights are
[in, out].
"""
import jax
import jax.numpy as jnp
import numpy as np


def position_code(length, d_model):
    pos = np.arange(length)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
    return jnp.asarray(np.where(i % 2 == 0, np.sin(angle), np.cos(angle)),
                       jnp.float32)


def layer_norm(x, scale, shift, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + shift


def attention(p, x_q, x_kv, key_is_pad, n_head, causal):
    wq, wk, wv, wo = p
    b, tq, d = x_q.shape
    tk = x_kv.shape[1]
    dk = d // n_head

    def heads(x, t):
        return x.reshape(b, t, n_head, dk).transpose(0, 2, 1, 3)

    q, k, v = heads(x_q @ wq, tq), heads(x_kv @ wk, tk), heads(x_kv @ wv, tk)
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(dk)
    mask = key_is_pad[:, None, None, :]
    if causal:
        mask = mask | (jnp.arange(tk)[None, :] > jnp.arange(tq)[:, None])
    weights = jax.nn.softmax(jnp.where(mask, -1e9, scores), axis=-1)
    ctx = jnp.einsum('bhqk,bhkd->bhqd', weights, v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, tq, d) @ wo


def feed_forward(w1b1, w2b2, x):
    return jax.nn.relu(x @ w1b1[0] + w1b1[1]) @ w2b2[0] + w2b2[1]


def forward_loss(params, model, src, trg, lbl):
    """Mean smoothed cross entropy over the non-pad labels."""
    n_head, d_model = model['n_head'], model['d_model']
    n_layer, eps = model['n_layer'], model['label_smooth_eps']
    src_pad, trg_pad = src == 0, trg == 0
    pos = position_code(src.shape[1], d_model)

    x = params['src_emb'][src] * np.sqrt(d_model) + pos
    for i in range(n_layer):
        p = 'enc%d.' % i
        a = attention(params[p + 'self.qkvo'], x, x, src_pad, n_head, False)
        x = layer_norm(x + a, *params[p + 'self.ln'])
        f = feed_forward(params[p + 'ffn.w1b1'], params[p + 'ffn.w2b2'], x)
        x = layer_norm(x + f, *params[p + 'ffn.ln'])
    enc = x

    y = params['trg_emb'][trg] * np.sqrt(d_model) + pos
    for i in range(n_layer):
        p = 'dec%d.' % i
        a = attention(params[p + 'self.qkvo'], y, y, trg_pad, n_head, True)
        y = layer_norm(y + a, *params[p + 'self.ln'])
        c = attention(params[p + 'cross.qkvo'], y, enc, src_pad, n_head,
                      False)
        y = layer_norm(y + c, *params[p + 'cross.ln'])
        f = feed_forward(params[p + 'ffn.w1b1'], params[p + 'ffn.w2b2'], y)
        y = layer_norm(y + f, *params[p + 'ffn.ln'])

    logp = jax.nn.log_softmax(y @ params['out_proj'], axis=-1)
    vocab = logp.shape[-1]
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    cost = -((1.0 - eps) * picked + eps / vocab * jnp.sum(logp, axis=-1))
    weight = (lbl != 0).astype(jnp.float32)
    return jnp.sum(cost * weight) / jnp.sum(weight)


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls.
    Every parameter is an argument of the jitted function: closed over,
    90 M parameters would be constants of the module."""
    src, trg, lbl = (jnp.asarray(batch[k], jnp.int32)
                     for k in ('src_word', 'trg_word', 'lbl_word'))
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    wanted = {k: params[k] for k in grad_paths}
    rest = {k: v for k, v in params.items() if k not in wanted}

    def f(wanted, rest, src, trg, lbl):
        return forward_loss({**rest, **wanted}, model, src, trg, lbl)

    with jax.default_matmul_precision('highest'):
        return jax.jit(jax.value_and_grad(f))(wanted, rest, src, trg, lbl)
