"""Plain reference of Ling-3.0-flash's layers (`model_type` bailing_hybrid
of the source's config.json, inclusionAI; Kimi Delta Attention: Kimi
Linear, arXiv:2510.26692; latent attention: DeepSeek-V2, arXiv:2405.04434;
the router: DeepSeek-V3, arXiv:2412.19437): the forward pass and loss in
straightforward jax.numpy, float32, written from the equations below and
from nothing of the program under test: no chunked form, no convolution,
norm or flash kernel, no Fluid code. Weights are [in, out]. No bias in any
projection. The keys are the source's.

    x_0 = Emb[ids];  norm(t, w) = w * t * rsqrt(mean(t^2) + rms_norm_eps)
    layer i:  h = x + mixer_i(norm(x, w_in));  x = h + ffn_i(norm(h, w_post))
        mixer_i = MLA where (i + 1) % layer_group_size == 0, else KDA, i the
                  SOURCE's index of the layer (`kept_layers` of a stage
                  that skips some)
        ffn_i = (silu(m Wg) * (m Wu)) Wd for the first first_k_dense_replace
                layers that run, else the expert block
    loss = mean cross entropy(norm(x_L, w_final) Whead)

  KDA, u the normed input; H = num_attention_heads of D = head_dim:
    q, k, v = silu(c(u Wq)), silu(c(u Wk)), silu(c(u Wv)),
              c(y)[t] = sum_j w_conv[j] * y[t - (K - 1) + j], a filter each
    q_h, k_h divided by their norm over D (x * rsqrt(sum x^2 + 1e-6));
    q_h * D^-0.5
    g = kda_lower_bound * sigmoid(exp(A_log_h) * (u Wf + dt_bias))  [T, H, D]
    beta = sigmoid(u Wb)                                            [T, H]
    THE RECURRENCE, token by token (a plain lax.scan over the tokens, NOT
    a chunked form), per head, S_0 = 0 [D, D]:
        S = diag(exp(g_t)) S;  S = S + k_t (beta_t (v_t - S^T k_t))^T
        o_t = S^T q_t
    mixer = concat_h(w_o * rmsnorm_D(o_h) * sigmoid((u Wg)_h)) Wout

  MLA, u the normed input; no query latent:
    q = u Wq, per head [q_nope | q_rope];  [c | kr] = u Wkva
    [k_nope | v] per head = norm(c, w_kv) Wkvb
    rotary (theta rope_theta) on q_rope and kr, NEIGHBOURING pairs
    (2j, 2j + 1) at the angle t * theta^(-2j / R); kr one head for all
    s = [q_nope | q_rope] . [k_nope | kr] / sqrt(qk_nope + qk_rope),
    position i sees j <= i;  o_h = softmax(s) v_h        width v_head_dim
    mixer = concat_h(o_h * sigmoid((u Wgate)_h)) Wout    Wgate [d, heads]

  Expert block, m the normed input, E = the router's width:
    s = sigmoid(m Wr);  c = s + b    b the selection bias, an input here
    groups of E / n_group consecutive experts; a group's rank is the sum
    of its two largest c; the topk_group best groups stay (by SORTING, the
    lower index first among equals); the num_experts_per_tok largest c
    among the experts of those groups are chosen
    gate_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
    block = sum over the chosen e THAT ARE HELD of gate_e expert_e(m)
            + shared(m)
  The gates are over all E; the sum is over the experts this chip holds
  (`first_expert_held` and as many as the stacks have): what the absent
  experts would add is left out, as in the program.

THE WEIGHTS STAY ON THE HOST (`loss_and_grads`), as
references/granitemoehybrid.py keeps them: the walk goes forward layer by
layer keeping each layer's INPUT on the device, takes loss and cotangent
at the head, then goes backward layer by layer with `jax.vjp` of ONE
layer's function; that layer's weights are put on the device for the
call, their gradient comes back to the host. `forward_loss` is the same
function in one piece (tier-1 holds the walk to `jax.grad` of it).

Departures from the source's model code, each for the chip's memory or
stated in the configuration's `assumed`: attention one head at a time over
an explicit [rows, keys] score matrix, a block of query rows at a time;
the dense feed-forward and the loss a block of positions at a time; the
held experts one after the other, the KDA mixer eight heads at a time; each
recomputed in the backward pass;
the recurrence a lax.scan over tokens inside a lax.scan over blocks of
tokens, so that a state a block is kept and not a state a token; rotary
angles in float64 on the host; no mask and no state reset between packed
documents; no clamp on an expert's SwiGLU (the kept layers carry 0); no
multi-token prediction module (its loss weight is 0 as published).
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
HEAD_GROUP = 8


def rms(t, w, eps):
    return w * t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps)


def conv(y, w):
    """y [B, T, C], w [K, C]: the depthwise causal convolution, then SiLU."""
    taps, t = w.shape[0], y.shape[1]
    padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * padded[:, j:j + t] for j in range(taps)))


def delta_scan(q, k, v, g, beta):
    """The recurrence. q, k, g [B, T, H, D], v [B, T, H, Dv], beta
    [B, T, H]; returns o [B, T, H, Dv]."""
    bsz, t, h, d = q.shape

    def token(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[..., None] * s
        write = b_t[..., None] * (v_t - jnp.einsum('bhkv,bhk->bhv', s, k_t))
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

    s0 = jnp.zeros((bsz, h, d, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, s0, tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:t], 0, 1)


def _kda_heads(w, u, model, first, n):
    """Heads first .. first + n - 1 of the mixer: their columns of the
    projections, filters, dt_bias and A_log, their rows of Wout."""
    d = model['head_dim']
    bsz, t, _ = u.shape
    cols, some = slice(first * d, (first + n) * d), slice(first, first + n)

    def heads(y):
        return y.reshape(bsz, t, n, d)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k, v = (heads(conv(u @ w[x][:, cols], w['conv_' + x][:, cols]))
               for x in 'qkv')
    g = model['kda_lower_bound'] * jax.nn.sigmoid(
        jnp.exp(w['a_log'][some])[:, None]
        * heads(u @ w['f'][:, cols] + w['dt_bias'][cols]))
    o = delta_scan(unit(q) * d ** -0.5, unit(k), v, g,
                   jax.nn.sigmoid(u @ w['b'][:, some]))
    o = w['norm_out'] * o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), -1, keepdims=True) + model['rms_norm_eps'])
    o = o * jax.nn.sigmoid(heads(u @ w['g'][:, cols]))
    return o.reshape(bsz, t, n * d) @ w['out'][cols]


def kda(w, u, model):
    """The heads are independent until Wout adds them up: HEAD_GROUP of
    them at a time, each group recomputed in the backward pass, so that a
    quarter of the mixer's [T, 4096] arrays is alive at once."""
    h = model['num_attention_heads']
    n = min(h, HEAD_GROUP)
    part = jax.checkpoint(
        lambda w, u, first: _kda_heads(w, u, model, first, n),
        static_argnums=2)
    return sum(part(w, u, first) for first in range(0, h, n))


def rotary(x, theta):
    """x [..., T, R]: elements 2j and 2j + 1 turn together by the angle
    t * theta^(-2j/R). Angles in float64 on the host, rounded once
    (references/olmoe.py says why)."""
    t, r = x.shape[-2], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.repeat(np.cos(angle), 2, -1), jnp.float32)
    sin = jnp.asarray(np.repeat(np.sin(angle), 2, -1), jnp.float32)
    turned = jnp.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + turned * sin


@jax.checkpoint
def _head(q, k, v):
    """One head of every row: q, k [B, T, D], v [B, T, Dv]; the masked
    softmax over all keys, a block of query rows at a time; the scale is
    the KEYS' width."""
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


def mla(w, u, model):
    eps, theta = model['rms_norm_eps'], model['rope_theta']
    h = model['num_attention_heads']
    nope, rope = model['qk_nope_head_dim'], model['qk_rope_head_dim']
    dv, rank = model['v_head_dim'], model['kv_lora_rank']
    b, t, _ = u.shape
    q = (u @ w['q']).reshape(b, t, h, nope + rope).transpose(2, 0, 1, 3)
    kva = u @ w['kv_a']
    kr = rotary(kva[..., rank:], theta)                         # [B, T, R]
    kv = (rms(kva[..., :rank], w['kv_norm'], eps) @ w['kv_b']
          ).reshape(b, t, h, nope + dv).transpose(2, 0, 1, 3)
    ctx = jnp.stack([
        _head(jnp.concatenate([q[j, ..., :nope],
                               rotary(q[j, ..., nope:], theta)], -1),
              jnp.concatenate([kv[j, ..., :nope], kr], -1),
              kv[j, ..., nope:]) for j in range(h)])            # [H,B,T,dv]
    ctx = ctx.transpose(1, 2, 0, 3) * jax.nn.sigmoid(u @ w['gate'])[..., None]
    return ctx.reshape(b, t, h * dv) @ w['out']


def _gated(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _largest(c, n):
    """[N, n] indices of the n largest of each row of c, by sorting; the
    lower index first among equals."""
    return jnp.argsort(-c, axis=-1, stable=True)[:, :n]


def _marked(idx, width):
    return jnp.sum(jax.nn.one_hot(idx, width, dtype=jnp.float32), axis=1)


def route(m, w_router, bias, model):
    """gates [N, E]: zero where an expert was not chosen."""
    scores = jax.nn.sigmoid(m @ w_router)                      # all E
    c = scores + bias
    n, e = c.shape
    groups = model['n_group']
    rank = jnp.sum(jnp.sort(c.reshape(n, groups, e // groups), -1)[..., -2:],
                   -1)
    stays = _marked(_largest(rank, model['topk_group']), groups)
    c = jnp.where(jnp.repeat(stays, e // groups, axis=-1) > 0, c, -jnp.inf)
    gates = scores * _marked(_largest(c, model['num_experts_per_tok']), e)
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


def dense(w, m):
    """The dense gated feed-forward, a block of positions at a time."""
    gated = jax.checkpoint(_gated)
    return jnp.concatenate(
        [gated(m[:, s:s + MLP_BLOCK], *w['ffn'])
         for s in range(0, m.shape[1], MLP_BLOCK)], axis=1)


def kinds_of(model):
    """`<mixer>_<feed-forward>` (kda or mla, dense or experts) of the
    layers that run: the mixer by the SOURCE's index of the layer
    (`kept_layers`, where the stage skips some), dense the first
    `first_k_dense_replace`."""
    n = model['num_hidden_layers']
    return ['%s_%s' % (
        'mla' if (at + 1) % model['layer_group_size'] == 0 else 'kda',
        'dense' if i < model['first_k_dense_replace'] else 'experts')
        for i, at in enumerate(model.get('kept_layers', range(n)))]


def layer(w, x, model, kind):
    eps = model['rms_norm_eps']
    mixer, ffn = kind.split('_')
    mixer = {'mla': mla, 'kda': kda}[mixer]
    h = x + jax.checkpoint(
        lambda w, x: mixer(w, rms(x, w['norm_in'], eps), model))(w, x)

    def feed_forward(w, h):
        m = rms(h, w['norm_post'], eps)
        return dense(w, m) if ffn == 'dense' else experts(w, m, model)

    return h + jax.checkpoint(feed_forward)(w, h)


@functools.partial(jax.checkpoint, static_argnums=4)
def _block_loss(y, w_norm, w_head, labels, eps):
    logp = jax.nn.log_softmax(rms(y, w_norm, eps) @ w_head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def head_loss(x, w_norm, w_head, labels, model):
    """The mean cross entropy, a block of positions at a time."""
    total = 0.0
    for s in range(0, x.shape[1], LOSS_BLOCK):
        cut = slice(s, s + LOSS_BLOCK)
        total = total + _block_loss(x[:, cut], w_norm, w_head,
                                    labels[:, cut], model['rms_norm_eps'])
    return total / labels.size


def sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_loss(params, model, ids, labels):
    """The whole function in one piece."""
    x = params['tok_emb'][ids]
    for i, kind in enumerate(kinds_of(model)):
        x = layer(sub(params, 'layer%d.' % i), x, model, kind)
    return head_loss(x, params['norm_final'], params['head'], labels, model)


def pieces(model):
    """The walk's jitted functions: the embedding's lookup and its
    transpose, a layer of each kind forward and pulled back (the
    cotangent's buffer donated to the layer's input's), the head."""
    def forward(kind):
        return jax.jit(lambda w, x: layer(w, x, model, kind))

    def backward(kind):
        def pull(w, x, dy):
            return jax.vjp(lambda w, x: layer(w, x, model, kind), w, x)[1](dy)
        return jax.jit(pull, donate_argnums=2)

    kinds = sorted(set(kinds_of(model)))
    return {
        'embed': jax.jit(lambda table, ids: table[ids]),
        'embed_back': jax.jit(
            lambda ids, dx, shape: jnp.zeros(shape, jnp.float32
                                             ).at[ids].add(dx),
            static_argnums=2),
        'forward': {k: forward(k) for k in kinds},
        'backward': {k: backward(k) for k in kinds},
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
    host = functools.partial(jax.tree_util.tree_map, np.asarray)
    kinds = kinds_of(model)
    x, inputs = fn['embed'](put(params['tok_emb']), ids), []
    for i, kind in enumerate(kinds):
        inputs.append(x)
        x = fn['forward'][kind](put(sub(params, 'layer%d.' % i)), x)
    loss, (dx, dnorm, dhead) = fn['head'](
        x, put(params['norm_final']), put(params['head']), labels)
    del x
    grads = {'norm_final': np.asarray(dnorm), 'head': np.asarray(dhead)}
    del dhead
    for i, kind in reversed(list(enumerate(kinds))):
        dw, dx = fn['backward'][kind](
            put(sub(params, 'layer%d.' % i)), inputs.pop(), dx)
        grads.update(('layer%d.%s' % (i, k), v) for k, v in host(dw).items())
        del dw
    grads['tok_emb'] = np.asarray(fn['embed_back'](
        ids, dx, tuple(np.shape(params['tok_emb']))))
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
        for a in value if isinstance(value, list) else [value]:
            a = np.asarray(a)
            h.update(repr((path, a.shape, float(a.sum(dtype=np.float64)))
                          ).encode())
            h.update(np.ascontiguousarray(a.reshape(-1)[:64]).tobytes())
    return h.hexdigest()


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls.

    One walk gives the gradient of every parameter, kept on the HOST for
    the next call on the same parameters and ids: a configuration's
    checks compare different gradients of the same sample, and the
    recurrence walks its 8192 tokens a KDA layer one by one."""
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
