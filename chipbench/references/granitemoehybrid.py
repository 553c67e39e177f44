"""Plain reference of GraniteMoeHybrid's layers (`model_type`
granitemoehybrid of the source's config.json, IBM Granite 4.0-H; Mamba-2:
Dao and Gu 2024, arXiv:2405.21060): the forward pass and loss in
straightforward jax.numpy, float32, written from the equations below and
from nothing of the program under test: no chunked scan, no convolution,
norm or flash kernel, no Fluid code. Weights are [in, out]. No bias in any
projection. The keys are the source's.

    x_0 = embedding_multiplier * E[ids]
    norm(t, w) = w * t * rsqrt(mean(t^2) + rms_norm_eps)
    layer i (kind = layer_types[i], the first num_hidden_layers of them):
        h = x + residual_multiplier * mixer_i(norm(x, w_mixer))
        x = h + residual_multiplier * (silu(a) * b) W_out,
                            [a | b] = norm(h, w_mlp) W_in, two halves of
                            shared_intermediate_size, the gate's first
    loss = mean cross entropy(norm(x_L, w_final) E^T / logits_scaling)

  `mamba`, u the normed input; H = mamba_n_heads of P = mamba_d_head,
  G = mamba_n_groups of state N = mamba_d_state:
    [z | xBC | dt] = u Win           widths H P | H P + 2 G N | H
    xBC = silu(c) with c[t] = sum_j w_conv[j] * xBC[t - (K - 1) + j] + b_conv
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    THE RECURRENCE, token by token (a plain lax.scan over the tokens, NOT
    a chunked form), per head h (its group g = h // (H / G); with G = 1
    every head reads the one B and C), S_0 = 0 [P, N]:
        S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t B_t^T
        y_t = S_t C_t + D_h x_t
    mixer = (w_o * rmsnorm over each of G groups of (y * silu(z))) Wout

  `attention`, u the normed input:
    q = u Wq (n_q heads of D);  k = u Wk;  v = u Wv (n_kv heads of D)
    key-value head h // (n_q / n_kv) for query head h; position i sees
    j <= i; s_ij = q_i . k_j * attention_multiplier (the configuration's
    number, not D^-0.5); NO positional signal of any kind
    mixer = softmax(s) v Wo

The head is E, the embedding, transposed: it is given ONCE, and its
gradient is the sum of the lookup's and the head's.

THE WEIGHTS STAY ON THE HOST (`loss_and_grads`): 772 M parameters are
3.1 GB and their gradients as much again, beside a scope that holds the
weights and Adam's two moments. The walk goes forward layer by layer
keeping each layer's INPUT on the device (67 MB a layer at one row of
8192), takes loss and cotangent at the head, then goes backward layer by
layer with `jax.vjp` of ONE layer's function: that layer's 0.3 GB is put
on the device for the call, its gradient comes back to the host, and
nothing else of the model is there. `forward_loss` is the same function
in one piece (tier-1 holds the walk to `jax.grad` of it).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`:
  - the scan is the RECURRENCE, never the chunked form the source and the
    program run: a lax.scan over tokens inside a lax.scan over blocks of
    tokens, the block recomputed in the backward pass so that a state a
    block is kept and not a state a token (17 GB at 8192 tokens);
  - attention is one head at a time over an explicit [rows, keys] score
    matrix with a boolean mask built from the positions, a block of query
    rows at a time; the feed-forward and the loss a block of positions at
    a time; each head and each such block is recomputed in the backward
    pass;
  - no clamp on dt (the source's time_step_limit defaults to (0, inf)); no
    mask and no state reset between packed documents.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024
QUERY_BLOCK = 2048
MLP_BLOCK = 2048
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
    h, p = model['mamba_n_heads'], model['mamba_d_head']
    g, n = model['mamba_n_groups'], model['mamba_d_state']
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
        + model['rms_norm_eps'])
    return (w['norm_out'] * parts.reshape(bsz, t, inner)) @ w['out']


@functools.partial(jax.checkpoint, static_argnums=3)
def _head(q, k, v, scale):
    """One head of every row: q, k, v [B, T, D]; the masked softmax over
    all keys, a block of query rows at a time."""
    t = q.shape[-2]
    out = []
    for s in range(0, t, QUERY_BLOCK):
        rows = jnp.arange(s, min(s + QUERY_BLOCK, t))
        scores = jnp.einsum('bqd,bkd->bqk', q[:, s:s + QUERY_BLOCK], k) \
            * scale
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
    ctx = jnp.stack([_head(q[j], k[j // group], v[j // group],
                           model['attention_multiplier'])
                     for j in range(n_q)])
    return ctx.transpose(1, 2, 0, 3).reshape(bsz, t, n_q * d) @ w['out']


@jax.checkpoint
def _gated(m, w_in, w_out):
    a, b = jnp.split(m @ w_in, 2, axis=-1)
    return (jax.nn.silu(a) * b) @ w_out


def mlp(w, m):
    """The dense gated feed-forward, a block of positions at a time."""
    return jnp.concatenate(
        [_gated(m[:, s:s + MLP_BLOCK], w['mlp_in'], w['mlp_out'])
         for s in range(0, m.shape[1], MLP_BLOCK)], axis=1)


MIXERS = {'mamba': mamba, 'attention': attention}


def layer(w, x, model, kind):
    """One layer of kind `kind` on its input x [B, T, hidden]; `w` its
    parameters by their short names."""
    eps, r = model['rms_norm_eps'], model['residual_multiplier']
    h = x + r * MIXERS[kind](w, rms(x, w['norm_mixer'], eps), model)
    return h + r * mlp(w, rms(h, w['norm_mlp'], eps))


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _block_loss(y, w_norm, table, labels, eps, scaling):
    logp = jax.nn.log_softmax(rms(y, w_norm, eps) @ table.T / scaling,
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def head_loss(x, w_norm, table, labels, model):
    """The mean cross entropy of the tied head's logits, a block of
    positions at a time."""
    total = 0.0
    for s in range(0, x.shape[1], LOSS_BLOCK):
        cut = slice(s, s + LOSS_BLOCK)
        total = total + _block_loss(x[:, cut], w_norm, table, labels[:, cut],
                                    model['rms_norm_eps'],
                                    model['logits_scaling'])
    return total / labels.size


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def kinds_of(model):
    return model['layer_types'][:model['num_hidden_layers']]


def forward_loss(params, model, ids, labels):
    """The whole function in one piece."""
    x = model['embedding_multiplier'] * params['tok_emb'][ids]
    for i, kind in enumerate(kinds_of(model)):
        x = layer(sub(params, 'layer%d.' % i), x, model, kind)
    return head_loss(x, params['norm_final'], params['tok_emb'], labels,
                     model)


def pieces(model):
    """The walk's jitted functions: the embedding's lookup and its
    transpose, a layer of either kind forward and pulled back (the
    cotangent's buffer donated to the layer's input's), the head."""
    def forward(kind):
        return jax.jit(lambda w, x: layer(w, x, model, kind))

    def backward(kind):
        def pull(w, x, dy):
            return jax.vjp(lambda w, x: layer(w, x, model, kind), w, x)[1](dy)
        return jax.jit(pull, donate_argnums=2)

    kinds = sorted(set(kinds_of(model)))
    scale = model['embedding_multiplier']
    return {
        'embed': jax.jit(lambda table, ids: scale * table[ids]),
        'embed_back': jax.jit(
            lambda dtable, ids, dx: dtable.at[ids].add(scale * dx),
            donate_argnums=0),
        'forward': {k: forward(k) for k in kinds},
        'backward': {k: backward(k) for k in kinds},
        'head': jax.jit(jax.value_and_grad(
            lambda x, w_norm, table, labels: head_loss(
                x, w_norm, table, labels, model), argnums=(0, 1, 2))),
    }


def walk(params, model, ids, labels):
    """(loss, {path: gradient on the host}) of every parameter; `params`
    on the host, one layer of them on the device at a time."""
    fn = pieces(model)
    put = functools.partial(jax.tree_util.tree_map,
                            lambda a: jnp.asarray(a, jnp.float32))
    kinds = kinds_of(model)
    table = put(params['tok_emb'])
    x, inputs = fn['embed'](table, ids), []
    for i, kind in enumerate(kinds):
        inputs.append(x)
        x = fn['forward'][kind](put(sub(params, 'layer%d.' % i)), x)
    loss, (dx, dnorm, dtable) = fn['head'](x, put(params['norm_final']),
                                           table, labels)
    del x, table
    grads = {'norm_final': np.asarray(dnorm)}
    for i, kind in reversed(list(enumerate(kinds))):
        dw, dx = fn['backward'][kind](put(sub(params, 'layer%d.' % i)),
                                      inputs.pop(), dx)
        grads.update(('layer%d.%s' % (i, k), np.asarray(v))
                     for k, v in dw.items())
        del dw
    grads['tok_emb'] = np.asarray(fn['embed_back'](dtable, ids, dx))
    return float(loss), grads


_MEMO = {}


def _fingerprint(params, model, batch):
    """What loss_and_grads is a function of, cheaply: the ids, the model's
    sizes, and of every parameter its shape, its sum and its first
    elements."""
    h = hashlib.blake2b(repr(sorted(model.items())).encode())
    for k in ('input_ids', 'labels'):
        h.update(np.ascontiguousarray(batch[k]).tobytes())
    for path, value in sorted(params.items()):
        a = np.asarray(value)
        h.update(repr((path, a.shape, float(a.sum(dtype=np.float64)))
                      ).encode())
        h.update(np.ascontiguousarray(a.reshape(-1)[:64]).tobytes())
    return h.hexdigest()


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls.

    One walk gives the gradient of every parameter, kept on the HOST for
    the next call on the same parameters and ids: a configuration's
    checks compare different gradients of the same sample, and the
    recurrence walks its 8192 tokens a Mamba-2 layer one by one."""
    key = _fingerprint(params, model, batch)
    if key not in _MEMO:
        ids, labels = (jnp.asarray(batch[k], jnp.int32)
                       for k in ('input_ids', 'labels'))
        with jax.default_matmul_precision('highest'):
            got = walk(params, model, ids, labels)
        _MEMO.clear()
        _MEMO[key] = got
    loss, grads = _MEMO[key]
    return loss, {k: grads[k] for k in grad_paths}
