"""Plain reference of GLM-4.7-Flash's blocks (`model_type` glm4_moe_lite of
the source's config.json; latent attention: DeepSeek-V2, arXiv:2405.04434
section 2.1; the sigmoid router with a selection bias and the multi-token
prediction module: DeepSeek-V3, arXiv:2412.19437 sections 2.1.2 and 2.2):
the forward pass and loss in straightforward jax.numpy, float32, written
from the published equations and from nothing of the program under test:
no flash kernel, no sort, no ragged op, no Fluid code. Weights are
[in, out]. H heads, no bias anywhere.

    x = E[ids];  norm(t, w) = w * t * rsqrt(mean(t^2) + eps)
    layer l:  x = x + mla(norm(x, w_in));  x = x + ffn_l(norm(x, w_post))
    ffn_l = Wdown(silu(Wgate m) * Wup m) for l < first_k_dense_replace,
            the expert block after it

  Latent attention, a = norm(x):
    cq = norm(a Wqa, w_q);   q = cq Wqb, per head [q_nope | q_rope]
    [ckv | kr] = a Wkva;     kr is ONE head of qk_rope_head_dim
    [k_nope | v] = norm(ckv, w_kv) Wkvb, per head
    rotary on q_rope and kr over their whole width R (pairs (i, i + R/2),
    angle t * theta^(-2i/R));  q_h = [q_nope_h | q_rope_h],
    k_h = [k_nope_h | kr]
    mixer = concat_h(causal_softmax(q_h k_h^T / sqrt(nope + R)) v_h) Wo

  Expert block, m = norm(x):
    s = sigmoid(m Wr) over ALL the router's experts; the top_k largest of
    s + b (b the selection bias, a given array); gates = s over the
    chosen, WITHOUT b, divided by their sum + 1e-20 (norm_topk_prob) and
    multiplied by routed_scaling_factor
    routed = sum over the chosen experts THAT ARE HELD (the stacks hold
             experts first .. first + count - 1) of
             gate_e * Wdown_e(silu(Wgate_e m) * (Wup_e m))
    block = routed + Wdown_s(silu(Wgate_s m) * (Wup_s m))

    L0 = mean cross entropy(norm(x, w_final) Whead, labels)

  Multi-token prediction, depth 1 (labels[t] = ids[t + 1]):
    h' = [norm(x, w_h) | norm(E[labels], w_e)] Weh
    y  = one more layer (latent attention + expert block) on h'
    L1 = sum over t < T - 1 of CE(norm(y_t, w_m) Whead, labels[t + 1])
         / (T - 1)
    loss = L0 + mtp_loss_weight * L1

E and Whead serve the main path and the module alike: given once, their
gradient is the sum of both uses'. (Given `mtp.tok_emb` or `mtp.head`
besides, the module takes those: the tests untie the weights so and add
the two gradients up themselves.)

The share: the model this reference is given holds `n_routed_experts`
experts of the router's E (the stacks' leading dimension against the
router's width), ids from `first_expert_held`; what the absent experts
would add is left out here as it is in the program, and that partial sum
goes on to the next layer (model-configs guide, section 4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time;
  - each mixer, each feed-forward, each head of attention (in blocks of
    query rows) and each block of a loss is recomputed in the backward
    pass;
  - rotary angles in float64 on the host; the rotate-half pairing (the
    source interleaves the pairs: a permutation of the columns of two
    random matrices); no mask between packed documents; the bias is an
    input here and its update is not this function's.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024
QUERY_BLOCK = 2048


def rms(t, w, eps):
    return w * t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps)


def rotary(x, theta):
    """x [..., T, R]: element i turns with element i + R / 2 by the angle
    t * theta^(-2i/R). Angles in float64 on the host, rounded once
    (references/olmoe.py says why)."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1), jnp.float32)
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + turned * sin


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


def latent_attention(w, x, model):
    eps, theta = model['rms_norm_eps'], model['rope_theta']
    h = model['num_attention_heads']
    nope, rope = model['qk_nope_head_dim'], model['qk_rope_head_dim']
    dv, rank = model['v_head_dim'], model['kv_lora_rank']
    b, t, _ = x.shape
    a = rms(x, w['norm_in'], eps)
    q = (rms(a @ w['q_a'], w['q_norm'], eps) @ w['q_b']
         ).reshape(b, t, h, nope + rope).transpose(2, 0, 1, 3)  # [H,B,T,.]
    kva = a @ w['kv_a']
    kr = rotary(kva[..., rank:], theta)                         # [B, T, R]
    kv = (rms(kva[..., :rank], w['kv_norm'], eps) @ w['kv_b']
          ).reshape(b, t, h, nope + dv).transpose(2, 0, 1, 3)
    # head by head, unrolled: as a lax.map the same loop asks the chip for
    # 8.8 GB of scratch, unrolled for 4.4 (chip, PR 32)
    ctx = jnp.stack([
        _head(jnp.concatenate([q[j, ..., :nope],
                               rotary(q[j, ..., nope:], theta)], -1),
              jnp.concatenate([kv[j, ..., :nope], kr], -1),
              kv[j, ..., nope:]) for j in range(h)])
    return ctx.transpose(1, 2, 0, 3).reshape(b, t, h * dv) @ w['out']


def _gated(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route(m, w_router, bias, model):
    """gates [N, E]: zero where an expert was not chosen."""
    scores = jax.nn.sigmoid(m @ w_router)                      # all E
    _, top_i = jax.lax.top_k(scores + bias, model['num_experts_per_tok'])
    chosen = jnp.sum(jax.nn.one_hot(top_i, scores.shape[-1],
                                    dtype=scores.dtype), axis=1)
    gates = scores * chosen
    if model['norm_topk_prob']:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * model['routed_scaling_factor']


def experts(w, m, model):
    first = model.get('first_expert_held', 0)
    b, t, d = m.shape
    m = m.reshape(b * t, d)
    gates = route(m, w['router'], w['bias'], model)
    w_gate, w_up = w['experts_in']
    held = w_gate.shape[0]
    # one held expert after the other, each recomputed in the backward pass
    routed, _ = jax.lax.scan(
        lambda total, e: (total + e[0][:, None]
                          * jax.checkpoint(_gated)(m, *e[1:]), None),
        jnp.zeros_like(m),
        (gates.T[first:first + held], w_gate, w_up, w['experts_down']))
    return (routed + _gated(m, *w['shared'])).reshape(b, t, d)


def layer(w, x, model, dense):
    x = x + jax.checkpoint(lambda w, x: latent_attention(w, x, model))(w, x)

    def feed_forward(w, x):
        m = rms(x, w['norm_post'], model['rms_norm_eps'])
        return _gated(m, *w['ffn']) if dense else experts(w, m, model)

    return x + jax.checkpoint(feed_forward)(w, x)


@jax.checkpoint
def _block_loss(y, w_norm, w_head, labels, weights, eps):
    logp = jax.nn.log_softmax(rms(y, w_norm, eps) @ w_head, axis=-1)
    return -jnp.sum(weights * jnp.take_along_axis(
        logp, labels[..., None], axis=-1)[..., 0])


def _loss(y, w_norm, w_head, labels, weights, eps):
    """sum over positions of weights * cross entropy, a block of positions
    at a time."""
    total = 0.0
    for s in range(0, y.shape[1], LOSS_BLOCK):
        cut = slice(s, s + LOSS_BLOCK)
        total = total + _block_loss(y[:, cut], w_norm, w_head,
                                    labels[:, cut], weights[cut], eps)
    return total


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_loss(params, model, ids, labels):
    eps = model['rms_norm_eps']
    b, t = ids.shape
    x = params['tok_emb'][ids]
    for i in range(model['num_hidden_layers']):
        x = layer(sub(params, 'layer%d.' % i), x, model,
                  i < model['first_k_dense_replace'])
    loss = _loss(x, params['norm_final'], params['head'], labels,
                 jnp.ones(t), eps) / (b * t)
    if model['num_nextn_predict_layers']:
        emb = params.get('mtp.tok_emb', params['tok_emb'])
        joined = jnp.concatenate([rms(x, params['mtp.norm_h'], eps),
                                  rms(emb[labels], params['mtp.norm_e'],
                                      eps)], -1)
        y = layer(sub(params, 'mtp.layer.'), joined @ params['mtp.proj'],
                  model, False)
        # position t predicts labels[t + 1]; the last predicts nothing
        targets = jnp.concatenate([labels[:, 1:], labels[:, :1]], 1)
        weights = jnp.concatenate([jnp.ones(t - 1), jnp.zeros(1)])
        extra = _loss(y, params['mtp.norm_m'],
                      params.get('mtp.head', params['head']), targets,
                      weights, eps) / (b * (t - 1))
        loss = loss + model['mtp_loss_weight'] * extra
    return loss


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
    parameter's place: beside a scope of 7.9 GiB the chip has no room
    for parameters and gradients both (2.8 GB each; chip, PR 32)."""
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
