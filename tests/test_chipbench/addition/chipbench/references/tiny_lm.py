"""Plain reference of `tiny_lm`: the forward pass and loss of a pre-norm
causal decoder in straightforward jax.numpy, float32, no kernel, no Fluid
code. x + Attention(LayerNorm(x)), x + FFN(LayerNorm(x)), a final
LayerNorm, an output projection without bias, cross entropy averaged over
every position. Weights are [in, out]; layer norm epsilon 1e-5.
"""
import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, scale, shift, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + shift


def attention(p, x, n_head):
    wq, wk, wv, wo = p
    b, t, d = x.shape

    def heads(y):
        return y.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)

    q, k, v = heads(x @ wq), heads(x @ wk), heads(x @ wv)
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(d // n_head)
    future = jnp.arange(t)[None, :] > jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(future, -1e9, scores), axis=-1)
    ctx = jnp.einsum('bhqk,bhkd->bhqd', weights, v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, d) @ wo


def forward_loss(params, model, ids, labels):
    x = params['tok_emb'][ids]
    for i in range(model['n_layer']):
        p = 'block%d.' % i
        x = x + attention(params[p + 'qkvo'],
                          layer_norm(x, *params[p + 'ln1']), model['n_head'])
        h = layer_norm(x, *params[p + 'ln2'])
        (w1, b1), (w2, b2) = params[p + 'w1b1'], params[p + 'w2b2']
        x = x + jax.nn.relu(h @ w1 + b1) @ w2 + b2
    logp = jax.nn.log_softmax(
        layer_norm(x, *params['ln_out']) @ params['out_proj'], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls."""
    ids, labels = (jnp.asarray(batch[k], jnp.int32)
                   for k in ('ids', 'labels'))
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    wanted = {k: params[k] for k in grad_paths}
    rest = {k: v for k, v in params.items() if k not in wanted}

    def f(wanted, rest, ids, labels):
        return forward_loss({**rest, **wanted}, model, ids, labels)

    with jax.default_matmul_precision('highest'):
        return jax.jit(jax.value_and_grad(f))(wanted, rest, ids, labels)
