"""Plain reference of Nemotron-H's blocks (`model_type` nemotron_h of the
source's config.json; the family's report: arXiv:2504.03624; Mamba-2:
Dao and Gu 2024, arXiv:2405.21060; the sigmoid router with a selection
bias: DeepSeek-V3, arXiv:2412.19437 section 2.1.2): the forward pass and
loss in straightforward jax.numpy, float32, written from the published
equations and from nothing of the program under test: no chunked form, no
flash kernel, no sort, no ragged op, no Fluid code. Weights are [in, out].
No bias in any projection.

    x = E[ids];  norm(t, w) = w * t * rsqrt(mean(t^2) + eps)
    block i:  x = x + part_i(norm(x, w_i))    part_i by pattern[i], the
              first num_hidden_layers characters of hybrid_override_pattern
    loss = mean cross entropy(norm(x, w_final) Whead, labels)

  `M`, Mamba-2, u = norm(x); H = mamba_num_heads of P = mamba_head_dim,
  G = n_groups of state N = ssm_state_size:
    [z | xBC | dt] = u Win           widths H P | H P + 2 G N | H
    xBC = silu(c) with c[t] = sum_j w_conv[j] * xBC[t - (K - 1) + j] + b_conv
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    THE RECURRENCE, token by token, per head h (its group g = h // (H / G)),
    S_0 = 0 [P, N]:
        S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t B_t^T
        y_t = S_t C_t + D_h x_t
    mixer = (w_o * rmsnorm over each of G groups of (y * silu(z))) Wout

  `*`, attention, u = norm(x):
    q = u Wq (n_q heads of D);  k = u Wk;  v = u Wv (n_kv heads of D)
    key-value head h // (n_q / n_kv) for query head h; causal; scores
    / sqrt(D); NO positional signal of any kind
    mixer = softmax(s) v Wo

  `E`, experts, m = norm(x):
    s = sigmoid(m Wr) over ALL the router's experts; the top_k largest of
    s + b (b the selection bias, a given array); gates = s over the
    chosen, WITHOUT b, divided by their sum + 1e-20 (norm_topk_prob) and
    multiplied by routed_scaling_factor
    routed = sum over the chosen experts THAT ARE HELD (the stacks hold
             experts first .. first + count - 1) of
             gate_e * relu(m W1_e)^2 W2_e
    part = routed + relu(m W1_s)^2 W2_s

The share: the model this reference is given holds `n_routed_experts`
experts of the router's E (the stacks' leading dimension against the
router's width), ids from `first_expert_held`; what the absent experts
would add is left out here as it is in the program, and that partial sum
goes on to the next block (model-configs guide, section 4).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - the scan is the RECURRENCE, never the chunked form the program runs:
    a lax.scan over tokens inside a lax.scan over blocks of tokens, the
    block recomputed in the backward pass so that a state a block is kept
    and not a state a token (17 GB at 8192 tokens);
  - every held expert is applied to EVERY token and weighted by the
    token's gate for it (zero where it was not chosen): the same sum and
    no routing machinery to get wrong; one expert at a time;
  - each block, each head of attention (in blocks of query rows) and each
    block of the loss is recomputed in the backward pass;
  - no clamp on dt (the source's time_step_limit defaults to (0, inf));
    no mask and no state reset between packed documents; the bias is an
    input here and its update is not this function's.
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


def selective_scan(x, dt, a, b, c, d):
    """The recurrence. x [B, T, H, P], dt [B, T, H], a, d [H], b, c
    [B, T, H, N] (a group's B and C repeated for its heads); returns
    y [B, T, H, P]."""
    bsz, t, h, p = x.shape

    def token(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return s, jnp.einsum('bhpn,bhn->bhp', s, c_t) + d[:, None] * x_t

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -t % TOKEN_BLOCK

    def blocks(v):
        """[B, T, ...] -> [blocks, TOKEN_BLOCK, B, ...]; the padding
        tokens (dt = 0) leave the state as it is."""
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((-1, TOKEN_BLOCK) + v.shape[1:])

    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, s0, tuple(map(blocks, (x, dt, b, c))))
    y = y.reshape((-1,) + y.shape[2:])[:t]
    return jnp.moveaxis(y, 0, 1)


def mamba(w, u, model):
    h, p = model['mamba_num_heads'], model['mamba_head_dim']
    g, n = model['n_groups'], model['ssm_state_size']
    inner, width = h * p, g * n
    bsz, t, _ = u.shape
    zxbcdt = u @ w['in']
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * width]
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * width:] + w['dt_bias'])
    taps = w['conv'].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w['conv'][j] * padded[:, j:j + t]
                          for j in range(taps)) + w['conv_bias'])
    x = xbc[..., :inner].reshape(bsz, t, h, p)
    b, c = (jnp.repeat(v.reshape(bsz, t, g, n), h // g, axis=2)
            for v in (xbc[..., inner:inner + width],
                      xbc[..., inner + width:]))
    y = selective_scan(x, dt, -jnp.exp(w['a_log']), b, c, w['d'])
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    parts = y.reshape(bsz, t, g, inner // g)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), -1, keepdims=True)
        + model['layer_norm_epsilon'])
    return (w['norm_out'] * parts.reshape(bsz, t, inner)) @ w['out']


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


def attention(w, u, model):
    d = model['head_dim']
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    bsz, t, _ = u.shape

    def heads(y, n):                                     # [H, B, T, D]
        return y.reshape(bsz, t, n, d).transpose(2, 0, 1, 3)

    q, k, v = heads(u @ w['q'], n_q), heads(u @ w['k'], n_kv), \
        heads(u @ w['v'], n_kv)
    group = n_q // n_kv
    ctx = jnp.stack([_head(q[j], k[j // group], v[j // group])
                     for j in range(n_q)])
    return ctx.transpose(1, 2, 0, 3).reshape(bsz, t, n_q * d) @ w['out']


def _relu2(m, w_in, w_out):
    return jnp.square(jax.nn.relu(m @ w_in)) @ w_out


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


def experts(w, u, model):
    first = model.get('first_expert_held', 0)
    bsz, t, d = u.shape
    m = u.reshape(bsz * t, d)
    gates = route(m, w['router'], w['bias'], model)
    held = w['experts_in'].shape[0]
    # one held expert after the other, each recomputed in the backward pass
    routed, _ = jax.lax.scan(
        lambda total, e: (total + e[0][:, None]
                          * jax.checkpoint(_relu2)(m, *e[1:]), None),
        jnp.zeros_like(m),
        (gates.T[first:first + held], w['experts_in'], w['experts_out']))
    return (routed + _relu2(m, *w['shared'])).reshape(bsz, t, d)


PARTS = {'M': mamba, '*': attention, 'E': experts}


@jax.checkpoint
def _block_loss(y, w_norm, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_norm, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def pattern_of(model):
    return model['hybrid_override_pattern'][:model['num_hidden_layers']]


def forward_loss(params, model, ids, labels):
    eps = model['layer_norm_epsilon']
    x = params['tok_emb'][ids]
    for i, kind in enumerate(pattern_of(model)):
        def part(w, x, kind=kind):
            return PARTS[kind](w, rms(x, w['norm'], eps), model)
        x = x + jax.checkpoint(part)(sub(params, 'block%d.' % i), x)
    total = 0.0
    for s in range(0, x.shape[1], LOSS_BLOCK):
        cut = slice(s, s + LOSS_BLOCK)
        total = total + _block_loss(x[:, cut], params['norm_final'],
                                    params['head'], labels[:, cut], eps)
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
    checks compare different gradients of the same sample, and the
    recurrence walks its 8192 tokens a Mamba-2 block one by one. The
    device's copy of the parameters is DONATED, so that a gradient takes
    its parameter's place beside the scope."""
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
