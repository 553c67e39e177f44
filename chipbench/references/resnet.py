"""Plain reference: ResNet-50 of He et al. 2015 (arXiv:1512.03385, Table 1 and
section 3.4), forward pass and loss in straightforward jax.numpy, float32,
channels-last, no kernel and no Fluid code.

  - stem: 7 x 7 convolution, 64 wide, stride 2, padding 3; batch norm; ReLU;
  - a 3 x 3 pooling with stride 2 (the configuration's `first_pool`: an
    average pool without padding, where the paper has a max pool);
  - four stages of bottleneck blocks (1 x 1, 3 x 3, 1 x 1 with four times
    the width), the stage's stride on the first block's first 1 x 1, a
    projection shortcut (1 x 1 convolution + batch norm) where the shape
    changes, identity elsewhere; ReLU after the addition;
  - batch norm "right after each convolution and before activation", on the
    statistics of the batch (biased variance), epsilon 1e-5;
  - global average pool, a 1000-way fully connected layer, softmax, cross
    entropy, mean over the batch.

Convolutions carry no bias. Convolution weights arrive as [out, in, h, w].
Parameters: {path: [conv weight, bn scale, bn shift]} and 'fc': [w, b].
"""
import jax
import jax.numpy as jnp
from jax import lax


def conv_bn(p, x, stride, padding, relu, eps):
    w, scale, shift = p
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=('NHWC', 'OIHW', 'NHWC'))
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) / jnp.sqrt(var + eps) * scale + shift
    return jax.nn.relu(y) if relu else y


def forward_loss(params, model, images, labels):
    eps = model['bn_epsilon']
    x = conv_bn(params['stem'], images, 2, 3, True, eps)
    x = lax.reduce_window(x, 0.0, lax.add, (1, 3, 3, 1), (1, 2, 2, 1),
                          'VALID') / 9.0
    for s, count in enumerate(model['stages']):
        for b in range(count):
            p = 's%d.b%d.' % (s, b)
            stride = 2 if (b == 0 and s > 0) else 1
            short = x
            if p + 'proj' in params:
                short = conv_bn(params[p + 'proj'], x, stride, 0, False, eps)
            y = conv_bn(params[p + 'c0'], x, stride, 0, True, eps)
            y = conv_bn(params[p + 'c1'], y, 1, 1, True, eps)
            y = conv_bn(params[p + 'c2'], y, 1, 0, False, eps)
            x = jax.nn.relu(short + y)
    pooled = jnp.mean(x, axis=(1, 2))
    w, b = params['fc']
    logp = jax.nn.log_softmax(pooled @ w + b, axis=-1)
    picked = jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(params, model, batch, grad_paths):
    """(loss, {path: gradient}) at float32 with full-precision matmuls;
    every parameter is an argument of the jitted function."""
    images = jnp.asarray(batch['data'], jnp.float32)
    labels = jnp.asarray(batch['label'], jnp.int32)
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    wanted = {k: params[k] for k in grad_paths}
    rest = {k: v for k, v in params.items() if k not in wanted}

    def f(wanted, rest, images, labels):
        return forward_loss({**rest, **wanted}, model, images, labels)

    with jax.default_matmul_precision('highest'):
        return jax.jit(jax.value_and_grad(f))(wanted, rest, images, labels)
