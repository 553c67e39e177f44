"""Operations one training step of the Transformer requires, from its static
shapes: 2 FLOPs a multiply-add, pads included (the device does the full
work for them today), the backward pass at twice the forward, the flash
kernels' recomputation of the scores not counted. The decoder's causal
self-attention needs half of a full score matrix.
"""


def attention_calls(model):
    """[(causal, count)] attention ops of one forward pass."""
    n = model['n_layer']
    return [(False, n), (True, n), (False, n)]   # encoder, decoder self, cross


def forward_flops(model, batch, seq):
    """{'matmul': weight matmuls, 'attention': score and context matmuls}"""
    d, dff, n = model['d_model'], model['d_inner'], model['n_layer']
    tokens = batch * seq                           # a side
    proj = 4 * 2 * d * d                           # q, k, v, out
    ffn = 2 * 2 * d * dff
    per_src_token = n * (proj + ffn)
    per_trg_token = n * (2 * proj + ffn) + 2 * d * model['trg_vocab']
    attn_full = 2 * 2 * batch * seq * seq * d      # QK^T and PV, all heads
    attention = sum(count * attn_full * (0.5 if causal else 1.0)
                    for causal, count in attention_calls(model))
    return {'matmul': tokens * (per_src_token + per_trg_token),
            'attention': attention}


def train_step_flops(config, traffic):
    """Required FLOPs of one step over the traffic's global batch."""
    f = forward_flops(config['model'], traffic['batch'], traffic['seq'])
    return 3.0 * (f['matmul'] + f['attention'])


def kernel_cost(config, traffic, chips=1):
    """What one chip's Pallas kernels of one step require, by the Fluid op
    type whose scope they run in: {op type: (FLOPs, bytes)}. The flash
    kernels are the only ones here. Forward reads q, k, v and writes the
    output; backward reads q, k, v, the output and its gradient and writes
    three gradients: 12 tensors of batch x seq x d_model in bf16, plus the
    f32 log-sum-exp rows once written and once read. The required FLOPs
    are the forward's two matmuls and the backward's four."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(m, batch, seq)['attention']
    calls = sum(c for _, c in attention_calls(m))
    tensor = batch * seq * m['d_model'] * 2
    lse = batch * m['n_head'] * seq * 4
    return {'flash_attention': (3.0 * f, calls * (12 * tensor + 2 * lse))}
