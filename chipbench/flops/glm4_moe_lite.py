"""Operations one training step of GLM-4.7-Flash's stage requires, from
its static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, causal attention at half of a full score matrix, and the experts
THIS CHIP HOLDS at their EXPECTED share of the tokens x top_k assignments
(held / routed: 8 of 64 a uniform router sends here; the share a step
really sends is data): what the mathematics needs, whatever the
implementation multiplies. What the step recomputes in its backward pass
(fluid.recompute_guard) is not counted: `mfu_pct` and the roofline shares
are of the REQUIRED operations.
"""


def routed_experts(config):
    """The router's width: the source's count where this chip holds a
    share (`n_routed_experts` listed under `reduced`), else the model's."""
    if 'n_routed_experts' in config.get('reduced', ()):
        return config['reduced_from']['n_routed_experts']
    return config['model']['n_routed_experts']


def held_rows(config, batch, seq):
    """Expected assignments a layer sends to the experts held here."""
    m = config['model']
    return batch * seq * m['num_experts_per_tok'] \
        * m['n_routed_experts'] / routed_experts(config)


def layer_counts(model):
    """(dense layers, expert layers, mixers): the module is one more
    expert layer and one more mixer."""
    extra = model['num_nextn_predict_layers']
    dense = model['first_k_dense_replace']
    sparse = model['num_hidden_layers'] - dense + extra
    return dense, sparse, dense + sparse


def mixer_weights(model):
    """Elements of one mixer's five matrices."""
    d, h = model['hidden_size'], model['num_attention_heads']
    qk = model['qk_nope_head_dim'] + model['qk_rope_head_dim']
    return (d * model['q_lora_rank'] + model['q_lora_rank'] * h * qk
            + d * (model['kv_lora_rank'] + model['qk_rope_head_dim'])
            + model['kv_lora_rank'] * h
            * (model['qk_nope_head_dim'] + model['v_head_dim'])
            + h * model['v_head_dim'] * d)


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d = m['hidden_size']
    dense, sparse, mixers = layer_counts(m)
    tokens = batch * seq
    heads = m['num_attention_heads'] * m['v_head_dim']
    expert = 3 * 2 * d * m['moe_intermediate_size']
    extra = m['num_nextn_predict_layers']
    return {
        'mla_projections': mixers * tokens * 2 * mixer_weights(m),
        'attention': mixers * 0.5 * 2 * 2 * batch * seq * seq * heads,
        'dense': dense * tokens * 3 * 2 * d * m['intermediate_size'],
        'experts': sparse * held_rows(config, batch, seq) * expert,
        'router': sparse * tokens * 2 * d * routed_experts(config),
        'shared_expert': sparse * tokens * m['n_shared_experts'] * expert,
        'mtp_projection': extra * tokens * 2 * 2 * d * d,
        'head': (1 + extra) * tokens * 2 * d * m['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def expert_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `moe_mlp` ops require of one chip in one step,
    whatever implements them (flops/qwen3_next.py `expert_cost`): the
    router over all its experts and the held experts' matmuls on their
    expected rows, forward and backward; each held weight read once
    forward and once backward and its gradient written once in bf16, each
    expected row read and written once a matmul each way in bf16. The
    shared expert is built from `fc` layers outside the op and is not
    counted here."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, sparse, _ = layer_counts(m)
    weights = sparse * m['n_routed_experts'] * 3 * m['hidden_size'] \
        * m['moe_intermediate_size']
    rows = sparse * held_rows(config, batch, seq)
    row_bytes = 2 * (2 * m['hidden_size'] + 3 * m['moe_intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def _flash_bytes(model, batch, seq):
    """One attention call on flops/transformer.py's model of bytes:
    forward reads q, k, v and writes the output, backward reads q, k, v,
    the output and its gradient and writes three gradients: 12 bf16
    tensors of batch x seq x heads x width."""
    return 12 * batch * seq * model['num_attention_heads'] \
        * model['v_head_dim'] * 2


def latent_attention_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the mixers require of one chip in one step, whatever
    implements them: the five projections and the causal scores of every
    mixer, forward and backward; each matrix read once forward and once
    backward and its gradient written once in bf16; per token the mixer's
    input and output rows and the three latents (q_lora_rank,
    kv_lora_rank, the rotary key) written once and read once, forward and
    twice that backward, in bf16; and the attention calls' tensors
    (`_flash_bytes`)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, _, mixers = layer_counts(m)
    latents = m['q_lora_rank'] + m['kv_lora_rank'] + m['qk_rope_head_dim']
    token_bytes = 2 * 2 * (2 * m['hidden_size'] + latents)
    return (3.0 * (f['mla_projections'] + f['attention']),
            mixers * (3 * 2 * mixer_weights(m)
                      + 3 * batch * seq * token_bytes
                      + _flash_bytes(m, batch, seq)))


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention`: one call a mixer, 20 heads of 256, on
    `_flash_bytes`. `moe_mlp`: the grouped-matmul kernels as
    flops/olmoe.py counts them, nine calls a layer, on the expected held
    rows and the held stacks."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, sparse, mixers = layer_counts(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['n_routed_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                mixers * _flash_bytes(m, batch, seq)),
            'moe_mlp': (3.0 * f['experts'], sparse * calls)}
