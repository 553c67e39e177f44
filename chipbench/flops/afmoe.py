"""Operations one training step of Trinity-Mini's stage requires, from its
static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, attention over the (query, key) pairs its MASK ADMITS (a global
layer T (T + 1) / 2 a row, a windowed layer sum_i min(i + 1, window):
never the whole triangle for a windowed layer, never the blocks a kernel
visits), the routed experts THIS CHIP HOLDS at their EXPECTED share of the
tokens x top_k assignments (held / routed: 16 of 128 a uniform router
sends here; the share a step really sends is data) and the shared expert
on every token: what the mathematics needs, whatever the implementation
multiplies. The six norms a layer, the gate's sigmoid and product, rotary
and the embedding's scale are no matmul and under 0.1 % of the layer's:
not counted. What the step recomputes in its backward pass
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


def admitted_pairs(seq, window=None):
    """(query, key) pairs a causal row of `seq` positions admits: every
    earlier position and the query's own, or the `window` last of them."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_counts(model):
    """(global layers, windowed layers, dense feed-forwards, expert
    layers) of the layers that run: `num_hidden_layers` of `layer_types`
    from `first_layer` on, the first `num_dense_layers` of them dense."""
    first = model.get('first_layer', 0)
    kinds = model['layer_types'][first:first + model['num_hidden_layers']]
    n_window = sum(k == 'sliding_attention' for k in kinds)
    n_dense = min(model['num_dense_layers'], len(kinds))
    return len(kinds) - n_window, n_window, n_dense, len(kinds) - n_dense


def mixer_weights(model):
    """Elements of one mixer's five matrices: Wq, Wk, Wv, Wo and the
    gate's projection, which is as wide as Wq."""
    d, width = model['hidden_size'], model['head_dim']
    return d * width * (3 * model['num_attention_heads']
                        + 2 * model['num_key_value_heads'])


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    d = m['hidden_size']
    n_global, n_window, n_dense, n_sparse = layer_counts(m)
    tokens = batch * seq
    # q k^T and p v: 2 x 2 x head_dim a pair a query head
    pair = 2 * 2 * m['head_dim'] * m['num_attention_heads']
    expert = 3 * 2 * d * m['moe_intermediate_size']
    return {
        'projections': (n_global + n_window) * tokens * 2 * mixer_weights(m),
        'global_scores': n_global * batch * admitted_pairs(seq) * pair,
        'window_scores': n_window * batch * pair
        * admitted_pairs(seq, m['sliding_window']),
        'dense': n_dense * tokens * 3 * 2 * d * m['intermediate_size'],
        'experts': n_sparse * held_rows(config, batch, seq) * expert,
        'shared': n_sparse * tokens * m['num_shared_experts'] * expert,
        'router': n_sparse * tokens * 2 * d * routed_experts(config),
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
    shared expert is plain `mul` ops outside the `moe_mlp` scopes
    (`shared_expert_ms` reads them) and is not in this cost."""
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


def window_attention_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the WINDOWED mixers require of one chip in one step,
    whatever implements them: their five projections (the gate's among
    them) and the scores their mask admits, forward and backward (the
    per-head norms, rotary and the gate's product are no matmul and a
    rounding of these); each matrix read once forward and once backward
    and its gradient written once in bf16; per token the mixer's input and
    output rows written once and read once, forward and twice that
    backward, in bf16; and the attention calls' tensors (`_flash_bytes`)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    n_global, n_window = layer_counts(m)[:2]
    share = n_window / float(n_global + n_window)
    token_bytes = 2 * 2 * 2 * m['hidden_size']
    return (3.0 * (share * f['projections'] + f['window_scores']),
            n_window * (3 * 2 * mixer_weights(m)
                        + 3 * batch * seq * token_bytes
                        + _flash_bytes(m, batch, seq)))


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention`: one call a layer, 32 heads of 128 over 4
    key-value heads, FLOPs over the ADMITTED pairs, on `_flash_bytes`.
    `moe_mlp`: the grouped-matmul kernels as flops/olmoe.py counts them,
    nine calls a layer, on the EXPECTED held rows (the rows the compact
    layout runs: the kernels skip the tiles no assignment fills, so not
    the layout's 32768 nor the layer's 65536) and the held stacks."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    n_global, n_window, _, n_sparse = layer_counts(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    rows = held_rows(config, batch, seq)
    stack = m['num_experts'] * d * w * 2
    calls = 3 * 3 * (rows * (d + w) * 2 + stack)
    return {'flash_attention': (3.0 * (f['global_scores']
                                       + f['window_scores']),
                                (n_global + n_window)
                                * _flash_bytes(m, batch, seq)),
            'moe_mlp': (3.0 * f['experts'], n_sparse * calls)}
