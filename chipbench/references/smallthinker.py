"""Plain reference of SmallThinker's layers (`model_name`
smallthinker_21b_instruct of the source's config.json; the family's report
is arXiv:2507.20984): the forward pass and loss in straightforward
jax.numpy, float32, written from the equations below and from nothing of
the program under test: no flash kernel, no sort, no ragged op, no Fluid
code. Weights are [in, out]. No bias anywhere.

    x = E[ids];  rms(t, w) = w * t * rsqrt(mean(t^2) + eps)
    layer l, g = rms(x, w_in):

  Attention:
    q = g Wq (hidden -> H x D);  k = g Wk;  v = g Wv (hidden -> KV x D)
    if rope_layout[l] == 1: q, k = rotary(q), rotary(k) over all D of each
    head, pairs (i, i + D / 2), angle t * theta^(-2i / D); if 0 nothing
    is added (NoPE)
    query head h reads key-value head h // (H / KV)
    s_ij = q_i . k_j / sqrt(D); position i sees j iff j <= i and, where
    sliding_window_layout[l] == 1, i - j < sliding_window_size (the
    window counts the query's own position)
    a = softmax(s) v;   h = x + a Wo

  Experts:
    z = g Wr over ALL the router's experts: the router reads the
    PRE-attention normed input; the top_k largest of z; gates = softmax
    over those top_k logits
    m = rms(h, w_post): the experts read the POST-attention normed state
    E_e(m) = (relu(m Wgate_e) * (m Wup_e)) Wdown_e
    y = h + sum over the chosen experts THAT ARE HELD (the stacks hold
        experts first .. first + count - 1) of gate_e E_e(m)
    aux_l = E * sum_e f_e P_e, f_e the share of the assignments that
        expert e got (no gradient), P_e the mean over tokens of
        softmax(z)_e over all E

    loss = mean cross entropy(rms(x_L, w_final) Whead, labels)
           + router_aux_loss_coef * mean over layers of aux_l

The share: the model this reference is given holds
`moe_num_primary_experts` experts of the router's E (the stacks' leading
dimension against the router's width), ids from `first_expert_held`; what
the absent experts would add is left out here as it is in the program, and
that partial sum goes on to the next layer (model-configs guide, section
4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time;
  - attention is one head at a time over an explicit [rows, keys] score
    matrix with a boolean mask built from the positions, a block of query
    rows at a time (16384 x 16384 scores of 28 heads do not fit at once);
    each layer, and in it each head and each expert, and each block of
    the loss is recomputed in the backward pass;
  - rotary angles in float64 on the host (references/olmoe.py says why);
    no mask between packed documents.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 2048
QUERY_BLOCK = 2048


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
    at position keys[j]? `window` None: every earlier position."""
    ahead = rows[:, None] - keys[None, :]
    return (ahead >= 0) if window is None \
        else (ahead >= 0) & (ahead < window)


def _head(q, k, v, window):
    """One head of every row: q, k, v [B, T, D]; the masked softmax over
    all keys, a block of QUERY_BLOCK query rows after the other (a
    lax.map: side by side the blocks' score matrices are several GB)."""
    b, t, d = q.shape
    size = min(QUERY_BLOCK, t)
    if t % size:
        raise ValueError('rows of %d positions in blocks of %d' % (t, size))
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


def attention(w, g, model, index):
    """The mixer of layer `index` on the normed input g [B, T, hidden]."""
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    d = model['head_dim']
    b, t, _ = g.shape

    def heads(x, n):
        return x.reshape(b, t, n, d).transpose(2, 0, 1, 3)      # [n,B,T,D]

    q, k, v = heads(g @ w['q'], n_q), heads(g @ w['k'], n_kv), \
        heads(g @ w['v'], n_kv)
    if model['rope_layout'][index]:
        q, k = rotary(q, model['rope_theta']), rotary(k, model['rope_theta'])
    window = model['sliding_window_size'] \
        if model['sliding_window_layout'][index] else None
    one = jax.checkpoint(lambda t: _head(*t, window))
    group = n_q // n_kv
    # one head after the other (a lax.map: unrolled, 28 heads of 4 blocks
    # in 4 layers are 2.2 GB of program, which the chip cannot load beside
    # the scope; chip, PR 37), each with its group's keys and values
    ctx = jax.lax.map(one, (q, jnp.repeat(k, group, axis=0),
                            jnp.repeat(v, group, axis=0)))
    return ctx.transpose(1, 2, 0, 3).reshape(b, t, n_q * d) @ w['out']


def route(g, w_router, top_k):
    """(gates [N, E], zero where an expert was not chosen; the layer's
    load-balancing loss) from the router's input g [N, hidden]."""
    z = g @ w_router                                           # all E
    n_exp = z.shape[-1]
    top_z, top_i = jax.lax.top_k(z, top_k)
    chosen = jax.nn.one_hot(top_i, n_exp, dtype=z.dtype)       # [N, k, E]
    gates = jnp.einsum('nk,nke->ne', jax.nn.softmax(top_z, -1), chosen)
    share = jnp.mean(jax.lax.stop_gradient(chosen), axis=(0, 1))
    aux = n_exp * jnp.sum(share * jnp.mean(jax.nn.softmax(z, -1), axis=0))
    return gates, aux


def _expert(m, gate, w_gate, w_up, w_down):
    """One ReGLU expert on every token, weighted by each token's gate for
    it."""
    return gate[:, None] * ((jax.nn.relu(m @ w_gate) * (m @ w_up)) @ w_down)


def experts(w, g, m, model):
    """(the held experts' part of the layer's sum, the load-balancing
    loss): the router reads g, the experts read m, both [B, T, hidden]."""
    first = model.get('first_expert_held', 0)
    b, t, d = m.shape
    g, m = g.reshape(b * t, d), m.reshape(b * t, d)
    gates, aux = route(g, w['router'],
                       model['moe_num_active_primary_experts'])
    w_gate, w_up = w['experts_in']
    held = w_gate.shape[0]
    routed, _ = jax.lax.scan(
        lambda total, e: (total + jax.checkpoint(_expert)(m, *e), None),
        jnp.zeros_like(m),
        (gates.T[first:first + held], w_gate, w_up, w['experts_down']))
    return routed.reshape(b, t, d), aux


def layer(w, x, model, index):
    """(the layer's output, its load-balancing loss); the step keeps the
    layer's input and runs the layer again in the backward pass."""
    eps = model['rms_norm_eps']

    @jax.checkpoint
    def run(w, x):
        g = rms(x, w['norm_in'], eps)
        h = x + attention(w, g, model, index)
        y, aux = experts(w, g, rms(h, w['norm_post'], eps), model)
        return h + y, aux

    return run(w, x)


@jax.checkpoint
def _block_loss(y, w_final, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_final, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_loss(params, model, ids, labels):
    n_layer = model['num_hidden_layers']
    x = params['tok_emb'][ids]
    aux = 0.0
    for i in range(n_layer):
        x, a = layer(sub(params, 'layer%d.' % i), x, model, i)
        aux = aux + a
    # the cross entropy a block of positions after the other
    b, t, d = x.shape
    size = min(LOSS_BLOCK, t)
    if t % size:
        raise ValueError('rows of %d positions in blocks of %d' % (t, size))
    total = jnp.sum(jax.lax.map(
        lambda cut: _block_loss(cut[0], params['norm_final'], params['head'],
                                cut[1], model['rms_norm_eps']),
        (x.reshape(b, t // size, size, d).transpose(1, 0, 2, 3),
         labels.reshape(b, t // size, size).transpose(1, 0, 2))))
    return total / labels.size + model['router_aux_loss_coef'] * aux / n_layer


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
