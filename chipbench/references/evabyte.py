"""Plain reference of EvaByte's layers (`model_type` evabyte,
`attention_class` eva of the source's config.json; EVA: Zheng, Yuan, Wang
and Kong, "Efficient Attention via Control Variates", ICLR 2023,
arXiv:2302.04542, section 4): the forward pass and loss in straightforward
jax.numpy, float32, written from the equations below and from nothing of
the program under test: no flash kernel, no log-sum-exp merge, no pooling
op, no Fluid code. Weights are [in, out]. No bias anywhere. The keys are
the source's.

    norm(t, w) = (1 + w) * t * rsqrt(mean(t^2) + rms_norm_eps)
                                            norm_add_unit_offset: w is the
                                            weight's offset from one
    x_0 = E[ids]
    layer i:  h = x + EVA_i(norm(x, w_mixer))
              x = h + (silu(u Wg) * (u Wu)) Wd,   u = norm(h, w_mlp)
    logits = norm(x_L, w_final) Whead   viewed [T, P, vocab], P = num_pred_heads
    loss = 1/P sum_j mean over { t : t + j < T } of
           CE(logits[t, j, :], labels[t + j])
        labels[t] = ids[t + 1]; head j predicts the byte j + 1 ahead; the
        row's last j positions have no target for head j and are left out

  EVA, u the normed input; H = num_attention_heads of D = hidden_size / H;
  c = chunk_size; W = window_size; s = D^-0.5:
    q, k, v = u Wq, u Wk, u Wv;  q, k <- rotary(q), rotary(k)
        rotary: element j of a head turns with element j + D/2 by the angle
        t * rope_theta^(-2j/D), over the whole head
    chunk n = positions [c n, c n + c); window w = positions [W w, W w + W)
    per head, learned mu, phi in R^D:
        a_m = softmax over m in chunk n of (mu . k_m)       kbar_n = sum a_m k_m
        b_m = softmax over m in chunk n of (s phi . k_m)    vbar_n = sum b_m v_m
    for a query t in window w:
        E_t = { m in window w, m <= t }
        P_t = { n : chunk n lies in a window before w }
        o_t = (sum_E exp(s q_t . k_m) v_m + sum_P exp(s q_t . kbar_n) vbar_n)
              / (sum_E exp(s q_t . k_m) + sum_P exp(s q_t . kbar_n))
    mixer = concat_h(o) Wo

THE WEIGHTS STAY ON THE HOST (`loss_and_grads`), as
references/granitemoehybrid.py keeps them: 821 M parameters are 3.3 GB and
their gradients as much again, beside a scope that holds the weights and
Adam's two moments. The walk goes forward layer by layer keeping each
layer's INPUT on the device (134 MB a layer at one row of 8192), takes loss
and cotangent at the head, then goes backward layer by layer with `jax.vjp`
of ONE layer's function: that layer's 0.81 GB is put on the device for the
call, its gradient comes back to the host, and nothing else of the model
is there. `forward_loss` is the same function in one piece (tier-1 holds
the walk to `jax.grad` of it).

Departures from the published description, each for the chip's memory or
stated in the configuration's `assumed`:
  - attention is one WINDOW and one HEAD at a time over explicit score
    arrays, [W, W] against the window's own keys with a boolean mask built
    from the positions and [W, summaries before the window] against the
    summaries, joined along the key axis before ONE softmax; each head of
    each window is recomputed in the backward pass, and the feed-forward
    runs a block of positions at a time, recomputed too (a layer's
    backward with every head's scores live at once is 8.7 GB beside a
    scope of 9.86: compiled for a described v5e, PR 59);
  - the sines and cosines are computed on the host in float64 and rounded
    once (a float32 rounding of a frequency turns the angle by 2e-4 rad at
    position 4095);
  - the source's random feature is its two learned vectors; no document
    mask; no dropout.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

MLP_BLOCK = 1024


def rms(t, w, eps):
    return (1.0 + w) * t * jax.lax.rsqrt(
        jnp.mean(jnp.square(t), -1, keepdims=True) + eps)


def rotary(x, theta):
    """x [B, T, H, D]: element j turns with element j + D/2."""
    t, d = x.shape[1], x.shape[-1]
    angle = np.arange(t, dtype=np.float64)[:, None] * theta ** (
        -np.arange(d // 2, dtype=np.float64) * 2.0 / d)[None, :]
    cos, sin = (jnp.asarray(f(angle), jnp.float32)[None, :, None, :]
                for f in (np.cos, np.sin))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def pool(k, v, mu, phi, chunk, scale):
    """k, v [B, T, H, D], mu, phi [H, D] -> kbar, vbar [B, T / chunk, H,
    D]: the two softmaxes over a chunk's positions, both of the KEYS."""
    bsz, t, h, d = k.shape
    kc, vc = (x.reshape(bsz, t // chunk, chunk, h, d) for x in (k, v))
    a = jax.nn.softmax(jnp.sum(kc * mu, -1), axis=2)
    b = jax.nn.softmax(scale * jnp.sum(kc * phi, -1), axis=2)
    return (jnp.sum(a[..., None] * kc, axis=2),
            jnp.sum(b[..., None] * vc, axis=2))


@functools.partial(jax.checkpoint, static_argnums=5)
def _head(q, k, v, kbar, vbar, scale):
    """ONE head of one window: the queries q [B, W, D] against the
    window's own keys k, v [B, W, D] (m <= t) and the summaries kbar, vbar
    [B, N, D] of the windows before it (all of them), under ONE softmax."""
    w = q.shape[1]
    exact = jnp.einsum('bqd,bkd->bqk', q, k) * scale
    future = jnp.arange(w)[None, :] > jnp.arange(w)[:, None]
    exact = jnp.where(future, -jnp.inf, exact)
    summary = jnp.einsum('bqd,bnd->bqn', q, kbar) * scale
    p = jax.nn.softmax(jnp.concatenate([exact, summary], axis=-1), axis=-1)
    return jnp.einsum('bqk,bkd->bqd', p[..., :w], v) \
        + jnp.einsum('bqn,bnd->bqd', p[..., w:], vbar)


def _window(q, k, v, kbar, vbar, scale):
    """One window, [B, W, H, D] against [B, N, H, D] summaries: its heads
    one after another (lax.map: one head's scores at a time)."""
    heads = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, kbar, vbar))
    return jnp.moveaxis(
        jax.lax.map(lambda a: _head(*a, scale), heads), 0, 2)


def eva(w, u, model):
    h = model['num_attention_heads']
    d = model['hidden_size'] // h
    c, win = model['chunk_size'], model['window_size']
    scale = d ** -0.5
    bsz, t, _ = u.shape

    def heads(y):
        return y.reshape(bsz, t, h, d)

    q, k, v = heads(u @ w['q']), heads(u @ w['k']), heads(u @ w['v'])
    q, k = rotary(q, model['rope_theta']), rotary(k, model['rope_theta'])
    kbar, vbar = pool(k, v, w['mu'], w['phi'], c, scale)
    out = []
    for s in range(0, t, win):
        cut, before = slice(s, s + win), slice(0, s // c)
        out.append(_window(q[:, cut], k[:, cut], v[:, cut], kbar[:, before],
                           vbar[:, before], scale))
    return jnp.concatenate(out, axis=1).reshape(bsz, t, h * d) @ w['out']


@jax.checkpoint
def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def mlp(w, m):
    """The SwiGLU, a block of positions after another (lax.map)."""
    bsz, t, d = m.shape
    size = MLP_BLOCK if t % MLP_BLOCK == 0 else t
    blocks = jnp.moveaxis(m.reshape(bsz, t // size, size, d), 1, 0)
    out = jax.lax.map(
        lambda b: _gated(b, w['gate'], w['up'], w['down']), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(bsz, t, d)


def layer(w, x, model, kind='eva'):
    """One layer on its input x [B, T, hidden]; `w` its parameters by
    their short names."""
    eps = model['rms_norm_eps']
    h = x + eva(w, rms(x, w['norm_mixer'], eps), model)
    return h + mlp(w, rms(h, w['norm_mlp'], eps))


def head_loss(x, w_norm, w_head, labels, model):
    """The mean over the prediction heads of each head's mean cross
    entropy: head j at position t against labels[t + j], the row's last j
    positions left out."""
    p, vocab = model['num_pred_heads'], model['vocab_size']
    bsz, t, _ = x.shape
    logits = (rms(x, w_norm, model['rms_norm_eps']) @ w_head).reshape(
        bsz, t, p, vocab)
    logp = jax.nn.log_softmax(logits, axis=-1)
    total = 0.0
    for j in range(p):
        picked = jnp.take_along_axis(logp[:, :t - j, j],
                                     labels[:, j:, None], axis=-1)
        total = total - jnp.mean(picked)
    return total / p


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def kinds_of(model):
    """Every layer is of the one kind (tools/aot_cell.py walks the kinds)."""
    return ['eva'] * model['num_hidden_layers']


def forward_loss(params, model, ids, labels):
    """The whole function in one piece."""
    x = params['tok_emb'][ids]
    for i in range(model['num_hidden_layers']):
        x = layer(sub(params, 'layer%d.' % i), x, model)
    return head_loss(x, params['norm_final'], params['head'], labels, model)


def pieces(model):
    """The walk's jitted functions: the embedding's lookup and its
    transpose, a layer forward and pulled back (the cotangent's buffer
    donated to the layer's input's), the head."""
    def pull(w, x, dy):
        return jax.vjp(lambda w, x: layer(w, x, model), w, x)[1](dy)

    return {
        'embed': jax.jit(lambda table, ids: table[ids]),
        'embed_back': jax.jit(
            lambda ids, dx, rows: jnp.zeros(
                (rows, dx.shape[-1]), jnp.float32).at[ids].add(dx),
            static_argnums=2),
        'forward': {'eva': jax.jit(lambda w, x: layer(w, x, model))},
        'backward': {'eva': jax.jit(pull, donate_argnums=2)},
        'head': jax.jit(jax.value_and_grad(
            lambda x, w_norm, w_head, labels: head_loss(
                x, w_norm, w_head, labels, model), argnums=(0, 1, 2))),
    }


def walk(params, model, ids, labels):
    """(loss, {path: gradient on the host}) of every parameter; `params`
    on the host, one layer of them on the device at a time."""
    fn = pieces(model)
    put = functools.partial(jax.tree_util.tree_map,
                            lambda a: jnp.asarray(a, jnp.float32))
    layers = range(model['num_hidden_layers'])
    x, inputs = fn['embed'](put(params['tok_emb']), ids), []
    for i in layers:
        inputs.append(x)
        x = fn['forward']['eva'](put(sub(params, 'layer%d.' % i)), x)
    loss, (dx, dnorm, dhead) = fn['head'](
        x, put(params['norm_final']), put(params['head']), labels)
    del x
    grads = {'norm_final': np.asarray(dnorm), 'head': np.asarray(dhead)}
    del dnorm, dhead
    for i in reversed(layers):
        dw, dx = fn['backward']['eva'](put(sub(params, 'layer%d.' % i)),
                                       inputs.pop(), dx)
        grads.update(('layer%d.%s' % (i, k), np.asarray(v))
                     for k, v in dw.items())
        del dw
    grads['tok_emb'] = np.asarray(fn['embed_back'](
        ids, dx, params['tok_emb'].shape[0]))
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
    checks compare different gradients of the same sample."""
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
