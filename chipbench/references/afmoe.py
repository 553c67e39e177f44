"""Plain reference of AFMoE's layers (`model_type` afmoe of the source's
config.json, Trinity-Mini; the keys mean what the source library's
modeling_afmoe.py makes of them): the forward pass and loss in
straightforward jax.numpy, float32, written from the equations below and
from nothing of the program under test: no flash kernel, no sort, no
ragged op, no Fluid code. Weights are [in, out]. No bias anywhere.

    x_0 = E[ids] * sqrt(hidden_size)      where mup_enabled: the scale is
                                          on the lookup's output only
    rms(t, w) = w * t * rsqrt(mean(t^2) + rms_norm_eps)
    layer l (of `layer_types`, counted from `first_layer`):
      g = rms(x, w_in)
      q = g Wq (hidden -> H x D);  k = g Wk;  v = g Wv (hidden -> KV x D)
      q, k = rms over each head's D (weights w_qn, w_kn of D)
      'sliding_attention': q, k = rotary(q), rotary(k) over all D of each
          head, pairs (i, i + D / 2), angle t * theta^(-2i / D); position i
          sees j iff 0 <= i - j < sliding_window (the window counts the
          query's own position)
      'full_attention': nothing is added (NoPE); i sees every j <= i
      query head h reads key-value head h // (H / KV)
      s_ij = q_i . k_j / sqrt(D);  a = softmax(s) v
      a = a * sigmoid(g Wg)               Wg: hidden -> H x D, of the
                                          SAME g the queries read
      h = x + rms(a Wo, w_post_attn)      the norm on the branch's OUTPUT
      m = rms(h, w_pre_mlp)
      the first num_dense_layers layers that run:
          f = (silu(m W1) * (m W3)) W2
      every later layer:
          s = sigmoid(m Wr) over ALL the router's experts; the top
          num_experts_per_tok of s + b (b the selection bias, a given
          array); gates = route_scale * s over the chosen, WITHOUT b,
          divided by their sum + router_norm_eps (route_norm)
          f = sum over the chosen experts THAT ARE HELD (the stacks hold
              experts first .. first + count - 1) of
              gate_e * (silu(m W1_e) * (m W3_e)) W2_e
            + (silu(m S1) * (m S3)) S2    the shared expert, ungated, on
                                          every token
      x' = h + rms(f, w_post_mlp)
    loss = mean cross entropy(rms(x_L, w_final) Whead, labels)

The share: the model this reference is given holds `num_experts` routed
experts of the router's E (the stacks' leading dimension against the
router's width), ids from `first_expert_held`; what the absent experts
would add is left out here as it is in the program, and that partial sum
(with the WHOLE shared expert) goes through the branch's norm and on to
the next layer (model-configs guide, section 4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time, a block of
    positions at a time;
  - attention is one head at a time over an explicit [rows, keys] score
    matrix with a boolean mask built from the positions, a block of query
    rows at a time; the dense feed-forward and the shared expert a block
    of positions at a time; each layer, and in it each head, each expert
    and each such block, and each block of the loss is recomputed in the
    backward pass;
  - rotary angles in float64 on the host (references/olmoe.py says why);
    no mask between packed documents; the bias is an input here and its
    update is not this function's.
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


def seen(rows, keys, window):
    """[rows, keys] bool: does the query at position rows[i] see the key
    at position keys[j]? `window` None: every earlier position and its
    own."""
    ahead = rows[:, None] - keys[None, :]
    return (ahead >= 0) if window is None \
        else (ahead >= 0) & (ahead < window)


def _blocks(t, size, what):
    size = min(size, t)
    if t % size:
        raise ValueError('%s: %d positions in blocks of %d' % (what, t, size))
    return size


def _head(q, k, v, window):
    """One head of every row: q, k, v [B, T, D]; the masked softmax over
    all keys, a block of QUERY_BLOCK query rows after the other."""
    b, t, d = q.shape
    size = _blocks(t, QUERY_BLOCK, 'attention')
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(start_and_rows):
        start, qb = start_and_rows                             # [B, size, D]
        scores = jnp.einsum('bqd,bkd->bqk', qb, k) / np.sqrt(d)
        mask = seen(start + jnp.arange(size), keys, window)
        weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum('bqk,bkd->bqd', weights, v)

    out = jax.lax.map(block, (
        jnp.arange(0, t, size),
        q.reshape(b, t // size, size, d).transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3).reshape(b, t, d)


def attention(w, g, model, kind):
    """The mixer of a layer of `kind` on the normed input g
    [B, T, hidden]."""
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    d, eps = model['head_dim'], model['rms_norm_eps']
    b, t, _ = g.shape

    def heads(x, n):
        return x.reshape(b, t, n, d).transpose(2, 0, 1, 3)      # [n,B,T,D]

    q = rms(heads(g @ w['q'], n_q), w['q_norm'], eps)
    k = rms(heads(g @ w['k'], n_kv), w['k_norm'], eps)
    v = heads(g @ w['v'], n_kv)
    window = None
    if kind == 'sliding_attention':
        q, k = rotary(q, model['rope_theta']), rotary(k, model['rope_theta'])
        window = model['sliding_window']
    group = n_q // n_kv
    # one head after the other (a lax.map, not 32 unrolled copies), each
    # with its group's keys and values
    ctx = jax.lax.map(jax.checkpoint(lambda t: _head(*t, window)),
                      (q, jnp.repeat(k, group, axis=0),
                       jnp.repeat(v, group, axis=0)))
    ctx = ctx.transpose(1, 2, 0, 3).reshape(b, t, n_q * d)
    return (ctx * jax.nn.sigmoid(g @ w['gate'])) @ w['out']


def _gated(m, w1, w3, w2):
    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def dense(m, w1, w3, w2):
    """A gated feed-forward on m [B, T, hidden], a block of FF_BLOCK
    positions after the other, each recomputed in the backward pass."""
    b, t, d = m.shape
    size = _blocks(t, FF_BLOCK, 'feed-forward')
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
    if model['route_norm']:
        gates = gates / (jnp.sum(gates, -1, keepdims=True)
                         + model['router_norm_eps'])
    return gates * model['route_scale']


def experts(w, m, model):
    """The held routed experts' part of the layer's sum on m
    [B, T, hidden]: a block of FF_BLOCK positions after the other, in it
    one held expert after the other on every position of the block,
    weighted by the position's gate for it; each block recomputed in the
    backward pass."""
    first = model.get('first_expert_held', 0)
    b, t, d = m.shape
    n = b * t
    m = m.reshape(n, d)
    w1, w3 = w['experts_in']
    held = w1.shape[0]
    gates = route(m, w['router'], w['bias'], model)[:, first:first + held]
    size = _blocks(n, FF_BLOCK, 'experts')

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
    """(mixer kind, is the feed-forward dense) of the `index`-th layer
    that runs: the stretch starts at `first_layer` of `layer_types`, and
    its first `num_dense_layers` layers are dense."""
    return (model['layer_types'][model.get('first_layer', 0) + index],
            index < model['num_dense_layers'])


def layer(w, x, model, index):
    """The layer's output; the step keeps the layer's input and runs the
    layer again in the backward pass."""
    eps = model['rms_norm_eps']
    kind, is_dense = layer_kind(model, index)

    @jax.checkpoint
    def run(w, x):
        g = rms(x, w['norm_in'], eps)
        h = x + rms(attention(w, g, model, kind), w['norm_post_attn'], eps)
        m = rms(h, w['norm_pre_mlp'], eps)
        f = dense(m, *w['ffn']) if is_dense \
            else experts(w, m, model) + dense(m, *w['shared'])
        return h + rms(f, w['norm_post_mlp'], eps)

    return run(w, x)


@jax.checkpoint
def _block_loss(y, w_final, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_final, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_loss(params, model, ids, labels):
    x = params['tok_emb'][ids]
    if model['mup_enabled']:
        x = x * np.sqrt(model['hidden_size'])
    for i in range(model['num_hidden_layers']):
        x = layer(sub(params, 'layer%d.' % i), x, model, i)
    # the cross entropy a block of positions after the other
    b, t, d = x.shape
    size = _blocks(t, LOSS_BLOCK, 'loss')
    total = jnp.sum(jax.lax.map(
        lambda cut: _block_loss(cut[0], params['norm_final'], params['head'],
                                cut[1], model['rms_norm_eps']),
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
