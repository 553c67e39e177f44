"""Operations one training step of OLMoE requires, from its static shapes:
2 FLOPs a multiply-add, the backward pass at twice the forward, causal
attention at half of a full score matrix, the ACTIVE experts only (top_k
of num_experts a token): what the mathematics needs, whatever the
implementation multiplies.
"""


def forward_flops(model, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    d, n = model['hidden_size'], model['num_hidden_layers']
    tokens = batch * seq
    expert = 3 * 2 * d * model['intermediate_size']
    return {
        'experts': n * tokens * model['num_experts_per_tok'] * expert,
        'router': n * tokens * 2 * d * model['num_experts'],
        'projections': n * tokens * 4 * 2 * d * d,
        'attention': n * 0.5 * 2 * 2 * batch * seq * seq * d,
        'head': tokens * 2 * d * model['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config['model'], traffic['batch'],
                                   traffic['seq']).values())


def expert_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the whole expert mechanism requires of one chip in
    one step, whatever implements it: the active experts' matmuls forward
    and backward, and the least bytes: each expert weight read once forward
    and once backward and its gradient written once (bf16 copies in, bf16
    out: 3 x 2 bytes a weight), each of the tokens x top_k rows read and
    written once a matmul each way in bf16 (rows of hidden width in and
    out of the layer, of expert width between its matmuls)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(m, batch, seq)
    weights = m['num_hidden_layers'] * m['num_experts'] * 3 \
        * m['hidden_size'] * m['intermediate_size']
    rows = batch * seq * m['num_experts_per_tok'] * m['num_hidden_layers']
    row_bytes = 2 * (2 * m['hidden_size'] + 3 * m['intermediate_size'])
    return (3.0 * (f['experts'] + f['router']),
            3 * 2 * weights + 3 * rows * row_bytes)


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention` on the same model of bytes as
    flops/transformer.py (12 bf16 tensors of batch x seq x hidden moved a
    call, forward and backward), so that flash_roofline is one yardstick
    across cells. `moe_mlp`: the grouped-matmul kernels, nine calls a layer
    (three matmuls, each forward, gradient of the rows, gradient of the
    stack): the experts' FLOPs without the router's, and per call the rows
    in, the rows out and the stack once, bf16."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(m, batch, seq)
    tensor = batch * seq * m['hidden_size'] * 2
    d, w = m['hidden_size'], m['intermediate_size']
    rows = batch * seq * m['num_experts_per_tok']
    stack = m['num_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * f['attention'],
                                m['num_hidden_layers'] * 12 * tensor),
            'moe_mlp': (3.0 * f['experts'],
                        m['num_hidden_layers'] * calls)}
