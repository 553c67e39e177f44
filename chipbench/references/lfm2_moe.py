"""Plain reference of LFM2-MoE's layers (`model_type` lfm2_moe of the
source's config.json; the keys mean what the source library's
modeling_lfm2_moe.py makes of them): the forward pass and loss in
straightforward jax.numpy, float32, written from the equations below and
from nothing of the program under test: no convolution kernel, no flash
kernel, no sort, no ragged op, no Fluid code. Weights are [in, out]. No
bias anywhere.

    x = E[ids];  rms(t, w) = w * t * rsqrt(mean(t^2) + norm_eps)
    layer l (of `layer_types`, counted from `first_layer`):
        h = x + operator_l(rms(x, w_op));   x = h + ff_l(rms(h, w_ff))

  Short convolution (`layer_types[l] == 'conv'`, g the normed input):
    [B | C | x~] = g Win, three chunks of hidden_size in THAT order
    c[t] = sum_{j < K} w[j] (B * x~)[t - (K - 1) + j] per channel,
    K = conv_L_cache, zeros before a row's first token, no activation
    operator = (C * c) Wout

  Attention (`'full_attention'`):
    q = g Wq (hidden -> H x D);  k = g Wk;  v = g Wv (hidden -> KV x D)
    q, k = rms over each head's D (weights w_qn, w_kn of D), THEN rotary
    over the whole head, pairs (i, i + D / 2), angle t * theta^(-2i / D)
    query head h reads key-value head h // (H / KV); position i sees
    j <= i; s_ij = q_i . k_j / sqrt(D);  operator = softmax(s) v Wo

  Dense feed-forward (the first num_dense_layers layers that run):
    (silu(m W1) * (m W3)) W2

  Experts (every later layer), m the normed state:
    s = sigmoid(m Wr) over ALL the router's experts; the top
    num_experts_per_tok of s + b (b the selection bias, a given array);
    gates = s over the chosen, WITHOUT b, divided by their sum +
    router_norm_eps (norm_topk_prob), times routed_scaling_factor
    ff = sum over the chosen experts THAT ARE HELD (the stacks hold
         experts first .. first + count - 1) of
         gate_e * (silu(m W1_e) * (m W3_e)) W2_e

    loss = mean cross entropy(rms(x_L, w_final) E^T, labels)

The head is E, the embedding, transposed: it is given ONCE, and its
gradient is the sum of the lookup's and the head's.

The share: the model this reference is given holds `num_experts` experts
of the router's E (the stacks' leading dimension against the router's
width), ids from `first_expert_held`; what the absent experts would add is
left out here as it is in the program, and that partial sum goes on to
the next layer (model-configs guide, section 4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time, a block of
    positions at a time;
  - attention is one head at a time over an explicit [rows, keys] score
    matrix with a boolean mask built from the positions, a block of query
    rows at a time (16384 x 16384 scores of 32 heads do not fit at once);
    the dense feed-forward a block of positions at a time; each layer, and
    in it each head, each expert and each such block, and each block of
    the loss is recomputed in the backward pass;
  - rotary angles in float64 on the host (references/olmoe.py says why);
    no mask between packed documents and no reset of the convolution's
    K - 1 carried inputs; the bias is an input here and its update is not
    this function's.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 2048
QUERY_BLOCK = 2048
FF_BLOCK = 2048


def rms(t, w, eps):
    return w * t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps)


def short_conv(w, g, model):
    """The double-gated short convolution on the normed input g
    [B, T, hidden]: K shifted multiply-adds between two gates."""
    taps = model['conv_L_cache']
    gate_in, gate_out, x = jnp.split(g @ w['in'], 3, axis=-1)
    bx = gate_in * x
    t = bx.shape[1]
    # bx[t - s] with zeros before the row's first token
    conv = sum(w['conv'][j]
               * jnp.pad(bx, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
               for j in range(taps))
    return (gate_out * conv) @ w['out']


def rotary(x, theta):
    """x [..., T, D]: element i turns with element i + D / 2 by the angle
    t * theta^(-2i/D)."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1), jnp.float32)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def _head(q, k, v):
    """One head of every row: q, k, v [B, T, D]; the causal softmax over
    all keys, a block of QUERY_BLOCK query rows after the other."""
    b, t, d = q.shape
    size = min(QUERY_BLOCK, t)
    if t % size:
        raise ValueError('rows of %d positions in blocks of %d' % (t, size))
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(start_and_rows):
        start, qb = start_and_rows                             # [B, size, D]
        scores = jnp.einsum('bqd,bkd->bqk', qb, k) / np.sqrt(d)
        seen = (start + jnp.arange(size))[:, None] >= keys[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum('bqk,bkd->bqd', weights, v)

    out = jax.lax.map(block, (
        jnp.arange(0, t, size),
        q.reshape(b, t // size, size, d).transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3).reshape(b, t, d)


def attention(w, g, model):
    """The attention operator on the normed input g [B, T, hidden]."""
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    d, eps = model['head_dim'], model['norm_eps']
    b, t, _ = g.shape

    def heads(x, n):
        return x.reshape(b, t, n, d).transpose(2, 0, 1, 3)      # [n,B,T,D]

    q = rotary(rms(heads(g @ w['q'], n_q), w['q_norm'], eps),
               model['rope_theta'])
    k = rotary(rms(heads(g @ w['k'], n_kv), w['k_norm'], eps),
               model['rope_theta'])
    v = heads(g @ w['v'], n_kv)
    group = n_q // n_kv
    # one head after the other (a lax.map, not 32 unrolled copies), each
    # with its group's keys and values
    ctx = jax.lax.map(jax.checkpoint(lambda t: _head(*t)),
                      (q, jnp.repeat(k, group, axis=0),
                       jnp.repeat(v, group, axis=0)))
    return ctx.transpose(1, 2, 0, 3).reshape(b, t, n_q * d) @ w['out']


def _gated(m, w1, w3, w2):
    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def dense(m, w1, w3, w2):
    """The dense feed-forward on m [B, T, hidden], a block of FF_BLOCK
    positions after the other, each recomputed in the backward pass: at
    16384 positions the three [T, 7168] float32 arrays and their
    cotangents are 2.8 GB, which the chip has not beside the scope."""
    b, t, d = m.shape
    size = min(FF_BLOCK, t)
    if t % size:
        raise ValueError('rows of %d positions in blocks of %d' % (t, size))
    out = jax.lax.map(
        jax.checkpoint(lambda rows: _gated(rows, w1, w3, w2)),
        m.reshape(b, t // size, size, d).transpose(1, 0, 2, 3))
    return out.transpose(1, 0, 2, 3).reshape(b, t, d)


def route(m, w_router, bias, model):
    """gates [N, E]: zero where an expert was not chosen."""
    scores = jax.nn.sigmoid(m @ w_router)                      # all E
    _, top_i = jax.lax.top_k(scores + bias, model['num_experts_per_tok'])
    chosen = jnp.sum(jax.nn.one_hot(top_i, scores.shape[-1],
                                    dtype=scores.dtype), axis=1)
    gates = scores * chosen
    if model['norm_topk_prob']:
        gates = gates / (jnp.sum(gates, -1, keepdims=True)
                         + model['router_norm_eps'])
    return gates * model['routed_scaling_factor']


def experts(w, m, model):
    """The held experts' part of the layer's sum on m [B, T, hidden]: a
    block of FF_BLOCK positions after the other, in it one held expert
    after the other on every position of the block, weighted by the
    position's gate for it; each block recomputed in the backward pass
    (side by side the experts' results over 16384 positions are 3.5 GB)."""
    first = model.get('first_expert_held', 0)
    b, t, d = m.shape
    n = b * t
    m = m.reshape(n, d)
    w1, w3 = w['experts_in']
    held = w1.shape[0]
    gates = route(m, w['router'], w['bias'], model)[:, first:first + held]
    size = min(FF_BLOCK, n)
    if n % size:
        raise ValueError('%d positions in blocks of %d' % (n, size))

    @jax.checkpoint
    def block(cut):
        rows, gate = cut                             # [size, d], [size, held]
        total, _ = jax.lax.scan(
            lambda total, e: (total + e[0][:, None] * _gated(rows, *e[1:]),
                              None),
            jnp.zeros_like(rows), (gate.T, w1, w3, w['experts_down']))
        return total

    routed = jax.lax.map(block, (m.reshape(n // size, size, d),
                                 gates.reshape(n // size, size, held)))
    return routed.reshape(b, t, d)


def layer_kind(model, index):
    """(operator kind, is the feed-forward dense) of the `index`-th layer
    that runs: the stretch starts at `first_layer` of `layer_types`, and
    its first `num_dense_layers` layers are dense."""
    return (model['layer_types'][model.get('first_layer', 0) + index],
            index < model['num_dense_layers'])


def layer(w, x, model, index):
    """The layer's output; the step keeps the layer's input and runs the
    layer again in the backward pass."""
    eps = model['norm_eps']
    kind, is_dense = layer_kind(model, index)

    @jax.checkpoint
    def run(w, x):
        g = rms(x, w['norm_op'], eps)
        h = x + (short_conv(w, g, model) if kind == 'conv'
                 else attention(w, g, model))
        m = rms(h, w['norm_ff'], eps)
        return h + (dense(m, *w['ffn']) if is_dense
                    else experts(w, m, model))

    return run(w, x)


@jax.checkpoint
def _block_loss(y, w_final, table, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_final, eps) @ table.T, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_loss(params, model, ids, labels):
    x = params['tok_emb'][ids]
    for i in range(model['num_hidden_layers']):
        x = layer(sub(params, 'layer%d.' % i), x, model, i)
    # the cross entropy a block of positions after the other; the head is
    # the embedding
    b, t, d = x.shape
    size = min(LOSS_BLOCK, t)
    if t % size:
        raise ValueError('rows of %d positions in blocks of %d' % (t, size))
    total = jnp.sum(jax.lax.map(
        lambda cut: _block_loss(cut[0], params['norm_final'],
                                params['tok_emb'], cut[1],
                                model['norm_eps']),
        (x.reshape(b, t // size, size, d).transpose(1, 0, 2, 3),
         labels.reshape(b, t // size, size).transpose(1, 0, 2))))
    return total / labels.size


_MEMO = {}


def _fingerprint(params, model, batch):
    """What loss_and_grads is a function of, cheaply: the ids, the model's
    sizes, and of every parameter its shape, its sum and its first
    elements."""
    h = hashlib.blake2b(repr(sorted(model.items())).encode())
    for k in ('input_ids', 'labels'):
        h.update(np.ascontiguousarray(batch[k]).tobytes())
    for path, value in sorted(params.items()):
        for a in value if isinstance(value, list) else [value]:
            a = np.asarray(a)
            h.update(repr((path, a.shape, float(a.sum(dtype=np.float64)))
                          ).encode())
            h.update(np.ascontiguousarray(a.reshape(-1)[:64]).tobytes())
    return h.hexdigest()


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls.

    One pass gives the gradient of every parameter, kept on the host for
    the next call on the same parameters and ids: a configuration's
    checks compare different gradients of the same sample. The device's
    copy of the parameters is DONATED, so that a gradient takes its
    parameter's place (references/glm4_moe_lite.py says why)."""
    key = _fingerprint(params, model, batch)
    if key not in _MEMO:
        ids, labels = (jnp.asarray(batch[k], jnp.int32)
                       for k in ('input_ids', 'labels'))
        device = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        with jax.default_matmul_precision('highest'):
            loss, grads = jax.jit(
                jax.value_and_grad(
                    lambda p, ids, labels: forward_loss(p, model, ids,
                                                        labels)),
                donate_argnums=0)(device, ids, labels)
        del device
        _MEMO.clear()
        _MEMO[key] = float(loss), jax.tree_util.tree_map(np.asarray, grads)
    loss, grads = _MEMO[key]
    return loss, {k: grads[k] for k in grad_paths}
