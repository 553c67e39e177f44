"""Plain reference of OLMoE's block (Muennighoff et al. 2024,
arXiv:2409.02060; the `olmoe` model type of the source's config.json): the
forward pass and loss in straightforward jax.numpy, float32, written from
the published equations and from nothing of the program under test: no
sort, no ragged op, no kernel, no Fluid code. Weights are [in, out].

    x   = E[ids]
    a   = rms(x, w_in)
    q,k = rms(a Wq, w_q), rms(a Wk, w_k);  v = a Wv      q/k norm over all
                                                         hidden outputs
    q,k = rotary(q), rotary(k)       per head, pairs (i, i + D/2), angles
                                     t * theta^(-2i/D), t = 0..T-1
    h   = x + causal_softmax(q k^T / sqrt(D)) v Wo
    m   = rms(h, w_post)
    p   = softmax(m Wr)
    y   = h + sum over the top_k largest p_e of
              p_e * Wdown_e(silu(Wgate_e m) * (Wup_e m))   p_e as they are
    out = rms(y, w_final) Whead
    loss = mean cross entropy(out, labels) + coef * mean over layers of
           E * sum_e f_e P_e
    rms(t, w) = w * t * rsqrt(mean(t^2) + eps)

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - every expert is applied to EVERY token and weighted by the token's gate
    for it (zero where the expert was not chosen): the same sum, eight
    times the work, and no routing machinery to get wrong. Eight experts
    at a time (a lax.map inside a Python loop), recomputed in the backward
    pass, so [tokens, experts, width] is never held and the program stays
    a tenth of the 0.86 GB that 64 unrolled experts compile to;
  - attention one head at a time and the loss in blocks of positions, both
    recomputed in the backward pass, for the same reason (a 4096 x 4096
    score matrix of 16 heads is 1 GB, the logits of 4096 positions 0.8 GB);
  - f_e is the share of the tokens x top_k assignments that expert e
    received and P_e its mean probability (Switch's form: 1.0 at a uniform
    router); the `olmoe` model code sums its top_k slots instead of
    averaging them, top_k times this (`assumed.router_aux_loss`);
  - rotary angles in float64 on the host (see `rotary`);
  - no mask between packed documents, no router z-loss (`assumed`).
"""
import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024
EXPERT_GROUP = 8


def rms(t, w, eps):
    return w * t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps)


def rotary(x, theta):
    """x [..., T, D]: element i turns with element i + D/2 by the angle
    t * theta^(-2i/D). The angles are taken in float64 on the host and
    their sines and cosines rounded once to float32: the equation's own
    values. (The source's model code multiplies float32 positions by
    float32 frequencies: at position 4095 that product is off by up to
    2e-4 rad, and which way depends on how a backend evaluates the power.)
    """
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1), jnp.float32)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


@jax.checkpoint
def _head(q, k, v):
    """One head of every row: q, k, v [B, T, D]."""
    t, d = q.shape[-2], q.shape[-1]
    scores = jnp.einsum('bqd,bkd->bqk', q, k) / np.sqrt(d)
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(future, -jnp.inf, scores), axis=-1)
    return jnp.einsum('bqk,bkd->bqd', weights, v)


def attention(p, x, model, i):
    eps, n_head = model['rms_norm_eps'], model['num_attention_heads']
    wq, wk, wv, wo = p['layer%d.qkvo' % i]
    w_q, w_k = p['layer%d.qk_norm' % i]
    b, t, d = x.shape
    a = rms(x, p['layer%d.norm_in' % i], eps)

    def heads(y):
        return y.reshape(b, t, n_head, d // n_head).transpose(2, 0, 1, 3)

    q = rotary(heads(rms(a @ wq, w_q, eps)), model['rope_theta'])
    k = rotary(heads(rms(a @ wk, w_k, eps)), model['rope_theta'])
    ctx = jnp.stack([_head(q[j], k[j], v_j)
                     for j, v_j in enumerate(heads(a @ wv))])
    return ctx.transpose(1, 2, 0, 3).reshape(b, t, d) @ wo


def _expert(m, gate, w_gate, w_up, w_down):
    """One expert on every token, weighted by each token's gate for it."""
    return gate[:, None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


@jax.checkpoint
def _expert_group(m, gates, w_gate, w_up, w_down):
    """The sum over a few experts: gates [g, N], weights [g, ...]."""
    return jnp.sum(jax.lax.map(lambda e: _expert(m, *e),
                               (gates, w_gate, w_up, w_down)), axis=0)


def experts(p, h, model, i):
    """(the layer's output, its load-balancing loss)"""
    n_exp, top_k = model['num_experts'], model['num_experts_per_tok']
    b, t, d = h.shape
    m = rms(h, p['layer%d.norm_post' % i], model['rms_norm_eps'])
    m = m.reshape(b * t, d)
    probs = jax.nn.softmax(m @ p['layer%d.router' % i], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    chosen = jax.nn.one_hot(top_i, n_exp, dtype=probs.dtype)   # [N, k, E]
    gates = jnp.einsum('nk,nke->ne', top_p, chosen)
    if model['norm_topk_prob']:
        gates = gates / jnp.sum(top_p, -1, keepdims=True)
    w_gate, w_up = p['layer%d.experts_in' % i]
    w_down = p['layer%d.experts_down' % i]
    out = jnp.zeros_like(m)
    for e in range(0, n_exp, EXPERT_GROUP):
        g = slice(e, e + EXPERT_GROUP)
        out = out + _expert_group(m, gates.T[g], w_gate[g], w_up[g],
                                  w_down[g])
    share = jnp.mean(jax.lax.stop_gradient(chosen), axis=(0, 1))
    aux = n_exp * jnp.sum(share * jnp.mean(probs, axis=0))
    return out.reshape(b, t, d), aux


@jax.checkpoint
def _block_loss(y, w_final, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_final, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward_loss(params, model, ids, labels):
    n_layer = model['num_hidden_layers']
    x = params['tok_emb'][ids]
    aux = 0.0
    for i in range(n_layer):
        h = x + attention(params, x, model, i)
        y, a = experts(params, h, model, i)
        x, aux = h + y, aux + a
    t = x.shape[1]
    total = 0.0
    for s in range(0, t, LOSS_BLOCK):
        total = total + _block_loss(
            x[:, s:s + LOSS_BLOCK], params['norm_final'], params['head'],
            labels[:, s:s + LOSS_BLOCK], model['rms_norm_eps'])
    return total / labels.size + model['router_aux_loss_coef'] * aux / n_layer


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls."""
    ids, labels = (jnp.asarray(batch[k], jnp.int32)
                   for k in ('input_ids', 'labels'))
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    wanted = {k: params[k] for k in grad_paths}
    rest = {k: v for k, v in params.items() if k not in wanted}

    def f(wanted, rest, ids, labels):
        return forward_loss({**rest, **wanted}, model, ids, labels)

    with jax.default_matmul_precision('highest'):
        return jax.jit(jax.value_and_grad(f))(wanted, rest, ids, labels)
