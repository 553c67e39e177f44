"""Operations one training step of Qwen3-Next's period requires, from its
static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, causal attention at half of a full score matrix, the recurrence
of the delta rule at what its definition needs (7 FLOPs a state element a
token: the decay 1, the read S^T k 2, the write 2, the output S^T q 2),
and the experts THIS CHIP HOLDS at their EXPECTED share of the tokens x
top_k assignments (held / routed: 16 of 512 a uniform router sends here;
the share a step really sends is data, and `tools/held_share.py` prints
it beside this one): what the mathematics needs, whatever the
implementation multiplies.
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
    n = model['num_hidden_layers']
    full = n // model['full_attention_interval']
    return n - full, full


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d, n = m['hidden_size'], m['num_hidden_layers']
    linear, full = layer_counts(m)
    tokens = batch * seq
    key = m['linear_num_key_heads'] * m['linear_key_head_dim']
    value = m['linear_num_value_heads'] * m['linear_value_head_dim']
    heads = m['num_attention_heads'] * m['head_dim']
    kv = m['num_key_value_heads'] * m['head_dim']
    expert = 3 * 2 * d * m['moe_intermediate_size']
    return {
        'delta_projections': linear * tokens * 2 * (
            d * (2 * key + 2 * value) + d * 2 * m['linear_num_value_heads']
            + value * d),
        'delta_rule': linear * tokens * m['linear_num_value_heads'] * 7
        * m['linear_key_head_dim'] * m['linear_value_head_dim'],
        'conv': linear * tokens * 2 * m['linear_conv_kernel_dim']
        * (2 * key + value),
        'attention_projections': full * tokens * 2 * (
            d * 2 * heads + 2 * d * kv + heads * d),
        'attention': full * 0.5 * 2 * 2 * batch * seq * seq * heads,
        'experts': n * held_rows(config, batch, seq) * expert,
        'router': n * tokens * 2 * d * routed_experts(config),
        'shared_expert': n * tokens * (
            3 * 2 * d * m['shared_expert_intermediate_size'] + 2 * d),
        'head': tokens * 2 * d * m['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def expert_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `moe_mlp` ops require of one chip in one step,
    whatever implements them (flops/olmoe.py `expert_cost` with the held
    experts in the place of all): the router over all its experts and the
    held experts' matmuls on their expected rows, forward and backward;
    each held weight read once forward and once backward and its gradient
    written once in bf16, each expected row read and written once a matmul
    each way in bf16. The shared expert is built from `fc` layers outside
    the op and is not counted here."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    n = m['num_hidden_layers']
    weights = n * m['num_experts'] * 3 * m['hidden_size'] \
        * m['moe_intermediate_size']
    rows = n * held_rows(config, batch, seq)
    row_bytes = 2 * (2 * m['hidden_size'] + 3 * m['moe_intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def delta_rule_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `gated_delta_rule` ops require of one chip in
    one step, whatever implements them: the recurrence's FLOPs forward and
    twice that backward; q, k, v in and o out in bf16 and g, beta in
    float32, once forward and twice that backward."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    linear, _ = layer_counts(m)
    key = m['linear_num_key_heads'] * m['linear_key_head_dim']
    value = m['linear_num_value_heads'] * m['linear_value_head_dim']
    token_bytes = 2 * (2 * key + 2 * value) \
        + 4 * 2 * m['linear_num_value_heads']
    return (3.0 * forward_flops(config, batch, seq)['delta_rule'],
            3 * linear * batch * seq * token_bytes)


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention` on flops/transformer.py's model of bytes
    (forward reads q, k, v and writes the output; backward reads q, k, v,
    the output and its gradient and writes three gradients; bf16) with the
    key and value tensors at their 2 heads: six tensors of the query
    heads' width and six of the key-value heads'. `moe_mlp`: the
    grouped-matmul kernels as flops/olmoe.py counts them, nine calls a
    layer, on the expected held rows and the held stacks."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, full = layer_counts(m)
    wide = batch * seq * m['num_attention_heads'] * m['head_dim'] * 2
    narrow = batch * seq * m['num_key_value_heads'] * m['head_dim'] * 2
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['num_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                full * 6 * (wide + narrow)),
            'moe_mlp': (3.0 * f['experts'],
                        m['num_hidden_layers'] * calls)}
