"""Operations one training step of Nemotron-3-Nano's stage requires, from
its static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, causal attention over the (query, key) pairs its mask admits,
the state-space recurrence at what its DEFINITION needs (5 FLOPs a state
element a token: the decay 1, the write (dt x) B^T and its add 2, the read
S C 2; dt x is a head's width and a rounding; never the FLOPs of a chunked
form), and the experts THIS CHIP HOLDS at their EXPECTED share of the
tokens x top_k assignments (held / routed: 8 of 128 a uniform router sends
here; the share a step really sends is data): what the mathematics needs,
whatever the implementation multiplies. What the step recomputes in its
backward pass (fluid.recompute_guard, the ops' own backwards) is not
counted: `mfu_pct` and the roofline shares are of the REQUIRED operations.
"""


def routed_experts(config):
    """The router's width: the source's count where this chip holds a
    share (`n_routed_experts` listed under `reduced`), else the model's."""
    if 'n_routed_experts' in config.get('reduced', ()):
        return config['reduced_from']['n_routed_experts']
    return config['model']['n_routed_experts']


def held_rows(config, batch, seq):
    """Expected assignments a block sends to the experts held here."""
    m = config['model']
    return batch * seq * m['num_experts_per_tok'] \
        * m['n_routed_experts'] / routed_experts(config)


def block_counts(model):
    """(Mamba-2 blocks, attention blocks, expert blocks) that run."""
    kinds = model['hybrid_override_pattern'][:model['num_hidden_layers']]
    return kinds.count('M'), kinds.count('*'), kinds.count('E')


def mamba_widths(model):
    """(inner = heads x head width, B and C's width together, heads)"""
    h = model['mamba_num_heads']
    return (h * model['mamba_head_dim'],
            2 * model['n_groups'] * model['ssm_state_size'], h)


def mamba_weights(model):
    """Elements of one mixer's two matrices."""
    inner, bc, h = mamba_widths(model)
    return model['hidden_size'] * (2 * inner + bc + h) \
        + inner * model['hidden_size']


def attention_weights(model):
    d, width = model['hidden_size'], model['head_dim']
    return d * width * 2 * (model['num_attention_heads']
                            + model['num_key_value_heads'])


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d = m['hidden_size']
    n_mamba, n_attn, n_expert = block_counts(m)
    tokens = batch * seq
    inner, bc, h = mamba_widths(m)
    pairs = seq * (seq + 1) // 2
    return {
        'mamba_projections': n_mamba * tokens * 2 * mamba_weights(m),
        'ssd': n_mamba * tokens * 5 * inner * m['ssm_state_size'],
        'conv': n_mamba * tokens * 2 * m['conv_kernel'] * (inner + bc),
        'attention_projections': n_attn * tokens * 2 * attention_weights(m),
        # q k^T and p v: 2 x 2 x head_dim a pair a query head
        'attention': n_attn * batch * pairs * 2 * 2 * m['head_dim']
        * m['num_attention_heads'],
        'experts': n_expert * held_rows(config, batch, seq)
        * 2 * 2 * d * m['moe_intermediate_size'],
        'router': n_expert * tokens * 2 * d * routed_experts(config),
        'shared_expert': n_expert * tokens * 2 * 2 * d
        * m['n_shared_experts'] * m['moe_shared_expert_intermediate_size'],
        'head': tokens * 2 * d * m['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def expert_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `moe_mlp` ops require of one chip in one step,
    whatever implements them (flops/glm4_moe_lite.py `expert_cost` with
    TWO matrices an expert): the router over all its experts and the held
    experts' matmuls on their expected rows, forward and backward; each
    held weight read once forward and once backward and its gradient
    written once in bf16, each expected row read and written once a matmul
    each way in bf16. The shared expert is built from `fc` layers outside
    the op and is not counted here."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, _, n_expert = block_counts(m)
    weights = n_expert * m['n_routed_experts'] * 2 * m['hidden_size'] \
        * m['moe_intermediate_size']
    rows = n_expert * held_rows(config, batch, seq)
    row_bytes = 2 * (2 * m['hidden_size'] + 2 * m['moe_intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def ssd_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `ssd_scan` ops require of one chip in one step,
    whatever implements them: the recurrence's FLOPs forward and twice
    that backward; x, B, C in and y out in bf16 and dt in float32 once a
    pass, their gradients once (the same again), so three times a pass's
    bytes a step; A and D are a head's and a rounding."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    n_mamba, _, _ = block_counts(m)
    inner, bc, h = mamba_widths(m)
    token_bytes = 2 * (2 * inner + bc) + 4 * h
    return (3.0 * forward_flops(config, batch, seq)['ssd'],
            3 * n_mamba * batch * seq * token_bytes)


def _flash_bytes(model, batch, seq):
    """One attention call as flops/smallthinker.py counts its bytes:
    forward reads q, k, v and writes the output, backward reads q, k, v,
    the output and its gradient and writes three gradients, in bf16; the
    keys and values at their own head count (the repeat over a group is
    the implementation's); plus the float32 log-sum-exp rows once written
    and once read."""
    width = batch * seq * model['head_dim'] * 2
    n_q, n_kv = model['num_attention_heads'], model['num_key_value_heads']
    return (4 * n_q + 8 * n_kv) * width + 2 * batch * n_q * seq * 4


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention`: one call an attention block, 32 heads of 128
    over 2 key-value heads, on `_flash_bytes`. `moe_mlp`: the
    grouped-matmul kernels as flops/olmoe.py counts them but TWO matmuls
    an expert a pass, so six calls a block (forward, the rows' gradient,
    the stack's gradient, of W1 and of W2), on the expected held rows (the
    compact layout's tiles past them are skipped) and the held stacks.
    `causal_conv1d`'s kernels are elementwise and have no entry, as in
    flops/qwen3_next.py."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, n_attn, n_expert = block_counts(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['n_routed_experts'] * d * w * 2
    calls = 2 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                n_attn * _flash_bytes(m, batch, seq)),
            'moe_mlp': (3.0 * f['experts'], n_expert * calls)}
