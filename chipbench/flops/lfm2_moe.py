"""Operations one training step of LFM2-8B-A1B's stage requires, from its
static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, attention over the (query, key) pairs its causal mask admits
(T (T + 1) / 2 a row, never the blocks a kernel visits), and the experts
THIS CHIP HOLDS at their EXPECTED share of the tokens x top_k assignments
(held / routed: 8 of 32 a uniform router sends here; the share a step
really sends is data): what the mathematics needs, whatever the
implementation multiplies. The short convolution's K multiply-adds and two
gate products a channel are no matmul and 0.05 % of its projections: not
counted. What the step recomputes in its backward pass
(fluid.recompute_guard) is not counted either: `mfu_pct` and the roofline
shares are of the REQUIRED operations.
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
    return batch * seq * m['num_experts_per_tok'] * m['num_experts'] \
        / routed_experts(config)


def layer_counts(model):
    """(short-convolution layers, attention layers, dense feed-forwards,
    expert layers) of the layers that run: `num_hidden_layers` of
    `layer_types` from `first_layer` on, the first `num_dense_layers` of
    them dense."""
    first = model.get('first_layer', 0)
    kinds = model['layer_types'][first:first + model['num_hidden_layers']]
    n_conv = sum(k == 'conv' for k in kinds)
    n_dense = min(model['num_dense_layers'], len(kinds))
    return n_conv, len(kinds) - n_conv, n_dense, len(kinds) - n_dense


def shortconv_weights(model):
    """Elements of one short-convolution mixer's two projections."""
    return 4 * model['hidden_size'] ** 2


def attention_weights(model):
    """Elements of one attention operator's four matrices."""
    return model['hidden_size'] * model['head_dim'] * 2 * (
        model['num_attention_heads'] + model['num_key_value_heads'])


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d = m['hidden_size']
    n_conv, n_attn, n_dense, n_sparse = layer_counts(m)
    tokens = batch * seq
    return {
        'shortconv_projections': n_conv * tokens * 2 * shortconv_weights(m),
        'attention_projections': n_attn * tokens * 2 * attention_weights(m),
        # q k^T and p v: 2 x 2 x head_dim a pair a query head
        'attention': n_attn * batch * (seq * (seq + 1) // 2) * 2 * 2
        * m['head_dim'] * m['num_attention_heads'],
        'dense': n_dense * tokens * 3 * 2 * d * m['intermediate_size'],
        'experts': n_sparse * held_rows(config, batch, seq)
        * 3 * 2 * d * m['moe_intermediate_size'],
        'router': n_sparse * tokens * 2 * d * routed_experts(config),
        # the tied head: one matrix, two uses, this one a matmul
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
    expected row read and written once a matmul each way in bf16."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    n_sparse = layer_counts(m)[3]
    weights = n_sparse * m['num_experts'] * 3 * m['hidden_size'] \
        * m['moe_intermediate_size']
    rows = n_sparse * held_rows(config, batch, seq)
    row_bytes = 2 * (2 * m['hidden_size'] + 3 * m['moe_intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def shortconv_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the short-convolution mixers require of one chip in
    one step, as the mathematics states them and whatever implements
    them. FLOPs: the two projections (hidden -> 3 hidden and hidden ->
    hidden), forward and backward. Bytes: the stage BETWEEN them, which no
    matmul is: forward it reads [T, 3 hidden] (B, C, x~) and writes
    [T, hidden]; backward it reads those three and the result's cotangent
    and writes the three's cotangents; in bf16, each once. Never what a
    composition moves besides (the product B * x~ through HBM, the
    convolution's result before its gate, a float32 copy) nor what a
    recompute region runs again. layers/shortconv_roofline.py ADDS the two
    times: the projections are the MXU's and the stage is the memory's,
    one after the other."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    n_conv = layer_counts(m)[0]
    stage = (3 + 1) + (3 + 1 + 3)       # arrays of [T, hidden] a mixer
    return (3.0 * forward_flops(config, batch, seq)['shortconv_projections'],
            n_conv * stage * batch * seq * m['hidden_size'] * 2)


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
    step. `flash_attention`: one causal call an attention layer, 32 heads
    of 64 over 8 key-value heads, on `_flash_bytes`. `moe_mlp`: the
    grouped-matmul kernels as flops/olmoe.py counts them, three matmuls
    an expert a pass and so nine calls a layer (forward, the rows'
    gradient, the stack's gradient), on the EXPECTED held rows, which at a
    quarter share are half the compact layout (`_held_layout` lays out
    half the layer's rows; the kernels skip the tiles no assignment
    fills, so the rows that run are the step's held rows and not the
    layout's), and the held stacks. `causal_conv1d`'s kernels are
    elementwise and have no entry, as in flops/qwen3_next.py."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    _, n_attn, _, n_sparse = layer_counts(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['num_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                n_attn * _flash_bytes(m, batch, seq)),
            'moe_mlp': (3.0 * f['experts'], n_sparse * calls)}
