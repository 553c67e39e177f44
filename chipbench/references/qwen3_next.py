"""Plain reference of Qwen3-Next's block (`model_type` qwen3_next of the
source's config.json; Gated DeltaNet: Yang, Kautz and Hatamizadeh 2024,
arXiv:2412.06464): the forward pass and loss in straightforward jax.numpy,
float32, written from the published equations and from nothing of the
program under test: no chunked form, no sort, no ragged op, no kernel, no
Fluid code. Weights are [in, out].

    x = E[ids];  norm(t, w) = w * t * rsqrt(mean(t^2) + eps)
    layer l:  x = x + mixer_l(norm(x, w_in));  x = x + moe(norm(x, w_post))
    mixer_l: attention where (l + 1) % full_attention_interval == 0

  Gated DeltaNet, a = norm(x):
    [q | k | v | z] = a Wqkvz;  [b | al] = a Wba
    [q | k | v] = silu(c) with c[t] = sum_j w_conv[j] * [q | k | v][t - 3 + j]
    beta = sigmoid(b);  g = -exp(A_log) * softplus(al + dt_bias)
    q, k = x * rsqrt(sum(x^2) + 1e-6) over each head; q = q / sqrt(Dk);
    key head h serves value heads 2h and 2h + 1
    THE RECURRENCE, token by token, per value head, S_0 = 0 [Dk, Dv]:
        S = exp(g_t) S;  S = S + k_t (beta_t (v_t - S^T k_t))^T
        o_t = S^T q_t
    mixer = (norm(o, w_o) over each head * silu(z)) Wo

  Gated attention, a = norm(x):
    [qh | gate] = a Wq per head;  kh = a Wk;  vh = a Wv
    qh, kh = norm over each head; rotary on the first rotary_dim of it
    (pairs (i, i + R/2), angle t * theta^(-2i/R));  key-value head h // 8
    for query head h
    mixer = (causal_softmax(qh kh^T / sqrt(D)) vh * sigmoid(gate)) Wo

  Expert block, m = norm(x):
    p = softmax(m Wr) over ALL the router's experts; the top_k largest;
    gates = p over the chosen, renormalised to sum 1 (norm_topk_prob)
    routed = sum over the chosen experts THAT ARE HELD (the stacks hold
             experts first .. first + count - 1) of
             gate_e * Wdown_e(silu(Wgate_e m) * (Wup_e m))
    shared = sigmoid(m w_sg) * Wdown_s(silu(Wgate_s m) * (Wup_s m))
    moe = routed + shared

    out = norm(x, w_final) Whead
    loss = mean cross entropy(out, labels) + coef * mean over layers of
           E * sum_e f_e P_e      over all E experts of the router

The share: the model this reference is given holds `num_experts` experts
of the router's E (the stacks' leading dimension against the router's
width), ids from `first_expert_held`; what the absent experts would add
is left out here as it is in the program, and that partial sum goes on to
the next layer (model-configs guide, section 4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - the recurrence is the definition, never the chunked form the program
    runs: a lax.scan over tokens inside a lax.scan over blocks of tokens,
    the block recomputed in the backward pass so that a state a block is
    kept and not a state a token (17 GB at 8192 tokens);
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time, so that
    [experts, tokens, hidden] is never held;
  - each mixer, each expert block, each head of attention (in blocks of
    query rows) and each block of the loss is recomputed in the backward
    pass;
  - the norms' weights start at 1 and multiply as they are (the source
    writes 1 + w with w from 0); the columns of Wqkvz and Wba are laid out
    q | k | v | z and b | al (the source interleaves them by key head);
  - f_e, P_e as in references/olmoe.py (Switch's form, 1.0 at a uniform
    router); rotary angles in float64 on the host; no mask and no state
    reset between packed documents; no multi-token prediction module.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024
QUERY_BLOCK = 2048
TOKEN_BLOCK = 128


def rms(t, w, eps):
    return w * t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps)


def rotary(x, theta, rotary_dim):
    """x [..., T, D]: of each head's first `rotary_dim` elements, element i
    turns with element i + rotary_dim / 2 by the angle
    t * theta^(-2i/rotary_dim); the rest pass. Angles in float64 on the
    host, rounded once (references/olmoe.py says why)."""
    t, r = x.shape[-2], rotary_dim
    inv_freq = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1), jnp.float32)
    head, rest = x[..., :r], x[..., r:]
    turned = jnp.concatenate([-head[..., r // 2:], head[..., :r // 2]], -1)
    return jnp.concatenate([head * cos + turned * sin, rest], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence. q, k [B, T, H, Dk], v [B, T, H, Dv], g, beta
    [B, T, H]; returns o [B, T, H, Dv]."""
    b, t, h, dk = q.shape

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum('bhkv,bhk->bhv', s, k_t)
        write = beta_t[..., None] * (v_t - read)
        s = s + k_t[..., :, None] * write[..., None, :]
        return s, jnp.einsum('bhkv,bhk->bhv', s, q_t)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -t % TOKEN_BLOCK

    def blocks(x):
        """[B, T, ...] -> [blocks, TOKEN_BLOCK, B, ...]; the padding
        tokens (k = 0, beta = 0, g = 0) leave the state as it is."""
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, TOKEN_BLOCK) + x.shape[1:])

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, s0, tuple(map(blocks, (q, k, v, g, beta))))
    o = o.reshape((-1,) + o.shape[2:])[:t]
    return jnp.moveaxis(o, 0, 1)


def delta_net(w, x, model):
    eps = model['rms_norm_eps']
    n_k, n_v = model['linear_num_key_heads'], model['linear_num_value_heads']
    d_k, d_v = model['linear_key_head_dim'], model['linear_value_head_dim']
    key, value = n_k * d_k, n_v * d_v
    b, t, _ = x.shape
    a = rms(x, w['norm_in'], eps)
    qkvz, ba = a @ w['qkvz'], a @ w['ba']
    qkv, z = qkvz[..., :2 * key + value], qkvz[..., 2 * key + value:]
    taps = w['conv'].shape[0]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w['conv'][j] * padded[:, j:j + t]
                          for j in range(taps)))
    q = qkv[..., :key].reshape(b, t, n_k, d_k)
    k = qkv[..., key:2 * key].reshape(b, t, n_k, d_k)
    v = qkv[..., 2 * key:].reshape(b, t, n_v, d_v)
    beta = jax.nn.sigmoid(ba[..., :n_v])
    g = -jnp.exp(w['a_log']) * jax.nn.softplus(ba[..., n_v:] + w['dt_bias'])

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k = l2norm(q) / np.sqrt(d_k), l2norm(k)
    q, k = (jnp.repeat(y, n_v // n_k, axis=2) for y in (q, k))
    o = delta_rule(q, k, v, g, beta)
    y = rms(o, w['norm_out'], eps) * jax.nn.silu(z.reshape(b, t, n_v, d_v))
    return y.reshape(b, t, value) @ w['out']


@jax.checkpoint
def _head(q, k, v):
    """One head of every row: q, k, v [B, T, D]; the masked softmax over
    all keys, a block of query rows at a time."""
    t, d = q.shape[-2], q.shape[-1]
    out = []
    for s in range(0, t, QUERY_BLOCK):
        rows = jnp.arange(s, min(s + QUERY_BLOCK, t))
        scores = jnp.einsum('bqd,bkd->bqk', q[:, s:s + QUERY_BLOCK], k) \
            / np.sqrt(d)
        future = jnp.arange(t)[None, :] > rows[:, None]
        weights = jax.nn.softmax(jnp.where(future, -jnp.inf, scores), -1)
        out.append(jnp.einsum('bqk,bkd->bqd', weights, v))
    return jnp.concatenate(out, axis=1)


def attention(w, x, model):
    eps, d = model['rms_norm_eps'], model['head_dim']
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    rotary_dim = int(d * model['partial_rotary_factor'])
    b, t, _ = x.shape
    a = rms(x, w['norm_in'], eps)
    qg = (a @ w['q']).reshape(b, t, n_q, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (a @ w['k']).reshape(b, t, n_kv, d)
    v = (a @ w['v']).reshape(b, t, n_kv, d)

    def heads(y):                                   # [H, B, T, D]
        return y.transpose(2, 0, 1, 3)

    q = rotary(heads(rms(q, w['q_norm'], eps)), model['rope_theta'],
               rotary_dim)
    k = rotary(heads(rms(k, w['k_norm'], eps)), model['rope_theta'],
               rotary_dim)
    v = heads(v)
    group = n_q // n_kv
    ctx = jnp.stack([_head(q[j], k[j // group], v[j // group])
                     for j in range(n_q)])
    ctx = ctx.transpose(1, 2, 0, 3).reshape(b, t, n_q * d)
    return (ctx * jax.nn.sigmoid(gate.reshape(b, t, n_q * d))) @ w['out']


def _expert(m, gate, w_gate, w_up, w_down):
    """One expert on every token, weighted by each token's gate for it."""
    return gate[:, None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


def experts(w, h, model):
    """(the block's output, its load-balancing loss)"""
    top_k = model['num_experts_per_tok']
    first = model.get('first_expert_held', 0)
    b, t, d = h.shape
    m = rms(h, w['norm_post'], model['rms_norm_eps']).reshape(b * t, d)
    probs = jax.nn.softmax(m @ w['router'], axis=-1)          # all E
    n_exp = probs.shape[-1]
    top_p, top_i = jax.lax.top_k(probs, top_k)
    chosen = jax.nn.one_hot(top_i, n_exp, dtype=probs.dtype)  # [N, k, E]
    gates = jnp.einsum('nk,nke->ne', top_p, chosen)
    if model['norm_topk_prob']:
        gates = gates / jnp.sum(top_p, -1, keepdims=True)
    w_gate, w_up = w['experts_in']
    held = w_gate.shape[0]
    # one held expert after the other, each recomputed in the backward pass
    routed, _ = jax.lax.scan(
        lambda total, e: (total + jax.checkpoint(_expert)(m, *e), None),
        jnp.zeros_like(m),
        (gates.T[first:first + held], w_gate, w_up, w['experts_down']))
    s_gate, s_up, s_down = w['shared']
    shared = jax.nn.sigmoid(m @ w['shared_gate']) \
        * ((jax.nn.silu(m @ s_gate) * (m @ s_up)) @ s_down)
    share = jnp.mean(jax.lax.stop_gradient(chosen), axis=(0, 1))
    aux = n_exp * jnp.sum(share * jnp.mean(probs, axis=0))
    return (routed + shared).reshape(b, t, d), aux


@jax.checkpoint
def _block_loss(y, w_final, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_final, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def layer_weights(params, i):
    p = 'layer%d.' % i
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def forward_loss(params, model, ids, labels):
    n_layer = model['num_hidden_layers']
    x = params['tok_emb'][ids]
    aux = 0.0
    for i in range(n_layer):
        w = layer_weights(params, i)
        full = (i + 1) % model['full_attention_interval'] == 0
        mixer = attention if full else delta_net
        x = x + jax.checkpoint(lambda w, x, f=mixer: f(w, x, model))(w, x)
        y, a = jax.checkpoint(lambda w, x: experts(w, x, model))(w, x)
        x, aux = x + y, aux + a
    t = x.shape[1]
    total = 0.0
    for s in range(0, t, LOSS_BLOCK):
        total = total + _block_loss(
            x[:, s:s + LOSS_BLOCK], params['norm_final'], params['head'],
            labels[:, s:s + LOSS_BLOCK], model['rms_norm_eps'])
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
    checks compare different gradients of the same sample, and the
    recurrence's 8192 sequential tokens a layer take the chip 40 s a
    pass."""
    key = _fingerprint(params, model, batch)
    if key not in _MEMO:
        ids, labels = (jnp.asarray(batch[k], jnp.int32)
                       for k in ('input_ids', 'labels'))
        device = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        with jax.default_matmul_precision('highest'):
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p: forward_loss(p, model, ids, labels)))(device)
        _MEMO.clear()
        _MEMO[key] = float(loss), jax.tree_util.tree_map(np.asarray, grads)
    loss, grads = _MEMO[key]
    return loss, {k: grads[k] for k in grad_paths}
