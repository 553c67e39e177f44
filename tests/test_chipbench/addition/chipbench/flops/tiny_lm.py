"""Operations one training step of `tiny_lm` requires, from its static
shapes: 2 FLOPs a multiply-add, the backward pass at twice the forward,
causal attention at half of a full score matrix.
"""


def forward_flops(model, batch, seq):
    d, n = model['d_model'], model['n_layer']
    per_token = n * (4 * 2 * d * d + 2 * 2 * d * model['d_inner']) \
        + 2 * d * model['vocab']
    return {'matmul': batch * seq * per_token,
            'attention': n * 0.5 * 2 * 2 * batch * seq * seq * d}


def train_step_flops(config, traffic):
    f = forward_flops(config['model'], traffic['batch'], traffic['seq'])
    return 3.0 * (f['matmul'] + f['attention'])


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. Two kernels, as a configuration that brings its own has: the
    flash kernels (12 bf16 tensors of batch x seq x d_model moved a call,
    forward and backward), and a fused cross-entropy kernel over the
    logits (read once forward, their gradient written once, bf16; some
    five operations a logit each way)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    attention = forward_flops(m, batch, seq)['attention']
    tensor = batch * seq * m['d_model'] * 2
    logits = batch * seq * m['vocab']
    return {'flash_attention': (3.0 * attention, m['n_layer'] * 12 * tensor),
            'softmax_with_cross_entropy': (10.0 * logits, 2 * 2 * logits)}
