"""Operations one training step of Ling-3.0-flash's stage requires, from
its static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, causal attention at half of a full score matrix (the scores at
the keys' width, the values at theirs), the recurrence of the delta rule
at what its definition needs (7 FLOPs a state element a token: the decay
1, the read S^T k 2, the write 2, the output S^T q 2; a decay a channel
is still one multiply an element), and the experts THIS CHIP HOLDS at
their EXPECTED share of the tokens x top_k assignments (held / routed: 8
of 512 a uniform router sends here; the share a step really sends is
data): what the mathematics needs, whatever the implementation
multiplies. What the step recomputes in its backward pass
(fluid.recompute_guard) is not counted: `mfu_pct` and the roofline shares
are of the REQUIRED operations.
"""


def routed_experts(config):
    """The router's width: the source's count where this chip holds a
    share (`num_experts` listed under `reduced`), else the model's."""
    if 'num_experts' in config.get('reduced', ()):
        return config['reduced_from']['num_experts']
    return config['model']['num_experts']


def held_rows(config, batch, seq):
    """Expected assignments a layer sends to the experts held here."""
    m = config['model']
    return batch * seq * m['num_experts_per_tok'] \
        * m['num_experts'] / routed_experts(config)


def layer_counts(model):
    """(KDA mixers, MLA mixers, dense layers, expert layers) of the layers
    that run (`kept_layers`: the source's indices of them)."""
    n = model['num_hidden_layers']
    kept = model.get('kept_layers', range(n))
    mla = sum((i + 1) % model['layer_group_size'] == 0 for i in kept)
    dense = model['first_k_dense_replace']
    return n - mla, mla, dense, n - dense


def kda_weights(model):
    """Elements of one KDA mixer's matrices: Wq, Wk, Wv, Wf, Wg and Wo of
    hidden x heads x head_dim, Wb of hidden x heads."""
    d, h = model['hidden_size'], model['num_attention_heads']
    return 6 * d * h * model['head_dim'] + d * h


def mla_weights(model):
    """Elements of one MLA mixer's matrices: Wq (no latent), Wkva, Wkvb,
    the gate a head and Wo."""
    d, h = model['hidden_size'], model['num_attention_heads']
    qk = model['qk_nope_head_dim'] + model['qk_rope_head_dim']
    return (d * h * qk + d * (model['kv_lora_rank']
                              + model['qk_rope_head_dim'])
            + model['kv_lora_rank'] * h
            * (model['qk_nope_head_dim'] + model['v_head_dim'])
            + d * h + h * model['v_head_dim'] * d)


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d, h = m['hidden_size'], m['num_attention_heads']
    kda, mla, dense, sparse = layer_counts(m)
    tokens = batch * seq
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    expert = 3 * 2 * d * m['moe_intermediate_size']
    return {
        'kda_projections': kda * tokens * 2 * kda_weights(m),
        'delta_rule': kda * tokens * h * 7 * m['head_dim'] * m['head_dim'],
        'conv': kda * tokens * 2 * m['short_conv_kernel_size']
        * 3 * h * m['head_dim'],
        'mla_projections': mla * tokens * 2 * mla_weights(m),
        'attention': mla * 0.5 * 2 * batch * seq * seq * h
        * (qk + m['v_head_dim']),
        'dense': dense * tokens * 3 * 2 * d * m['intermediate_size'],
        'experts': sparse * held_rows(config, batch, seq) * expert,
        'router': sparse * tokens * 2 * d * routed_experts(config),
        'shared_expert': sparse * tokens * m['num_shared_experts'] * 3 * 2
        * d * m['moe_shared_expert_intermediate_size'],
        'head': tokens * 2 * d * m['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def expert_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `moe_mlp` ops require of one chip in one step,
    whatever implements them (flops/glm4_moe_lite.py `expert_cost`): the
    router over all its experts and the held experts' matmuls on their
    expected rows, forward and backward; each held weight read once
    forward and once backward and its gradient written once in bf16, each
    expected row read and written once a matmul each way in bf16. The
    shared expert is built from `fc` layers outside the op and is not
    counted here."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    sparse = layer_counts(m)[3]
    weights = sparse * m['num_experts'] * 3 * m['hidden_size'] \
        * m['moe_intermediate_size']
    rows = sparse * held_rows(config, batch, seq)
    row_bytes = 2 * (2 * m['hidden_size'] + 3 * m['moe_intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def delta_rule_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `gated_delta_rule` ops require of one chip in
    one step, whatever implements them: the recurrence's FLOPs forward and
    twice that backward; q, k, v in and o out in bf16, beta [T, H] and the
    decay a channel g [T, H, head_dim] in float32, once forward and twice
    that backward."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    kda = layer_counts(m)[0]
    h, width = m['num_attention_heads'], \
        m['num_attention_heads'] * m['head_dim']
    token_bytes = 2 * 4 * width + 4 * h + 4 * width
    return (3.0 * forward_flops(config, batch, seq)['delta_rule'],
            3 * kda * batch * seq * token_bytes)


def _flash_bytes(model, batch, seq):
    """One attention call on flops/transformer.py's model of bytes:
    forward reads q, k, v and writes the output, backward reads q, k, v,
    the output and its gradient and writes three gradients: six bf16
    tensors at the keys' width (q, k twice, dq, dk) and six at the
    values' (v twice, the output twice, its gradient, dv), whatever the
    kernel pads."""
    qk = model['qk_nope_head_dim'] + model['qk_rope_head_dim']
    return 6 * batch * seq * model['num_attention_heads'] \
        * (qk + model['v_head_dim']) * 2


def latent_attention_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the MLA mixers require of one chip in one step,
    whatever implements them: the projections (the head-wise gate's
    included) and the causal scores at keys of 192 and values of 128,
    forward and backward; each matrix read once forward and once backward
    and its gradient written once in bf16; per token the mixer's input
    and output rows and the two latents (kv_lora_rank, the rotary key)
    written once and read once, forward and twice that backward, in bf16;
    and the attention call's tensors (`_flash_bytes`)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    mla = layer_counts(m)[1]
    latents = m['kv_lora_rank'] + m['qk_rope_head_dim']
    token_bytes = 2 * 2 * (2 * m['hidden_size'] + latents)
    return (3.0 * (f['mla_projections'] + f['attention']),
            mla * (3 * 2 * mla_weights(m) + 3 * batch * seq * token_bytes
                   + _flash_bytes(m, batch, seq)))


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention`: one call an MLA mixer, 32 heads with keys of
    192 and values of 128, on `_flash_bytes`. `moe_mlp`: the
    grouped-matmul kernels as flops/olmoe.py counts them, nine calls a
    layer, on the expected held rows and the held stacks."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, mla, _, sparse = layer_counts(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['num_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                mla * _flash_bytes(m, batch, seq)),
            'moe_mlp': (3.0 * f['experts'], sparse * calls)}
