"""Operations one training step of EvaByte's stage requires, from its
static shapes: 2 FLOPs a multiply-add, the backward pass at twice the
forward, attention over the (query, key) pairs its two masks admit (the
exact keys of a query's own aligned window up to the query, and ONE
summary for every chunk of the windows before it): what the mathematics
needs, whatever the implementation multiplies. The model is dense: every
layer's SwiGLU sees every token. The pooling's two dots a key, the
rotary, the norms and the merge are no matmuls and are a rounding of
these (the pooling: 1 GFLOP of 42 TFLOP). What the step recomputes in its
backward pass (fluid.recompute_guard) is not counted: `mfu_pct`,
`dense_mlp_peak_pct` and the roofline shares are of the REQUIRED
operations.
"""


def head_dim(model):
    return model['hidden_size'] // model['num_attention_heads']


def mixer_weights(model):
    """Elements of one mixer's four matrices (as many key-value heads as
    query heads)."""
    d = model['hidden_size']
    return 2 * d * head_dim(model) * (model['num_attention_heads']
                                      + model['num_key_value_heads'])


def mlp_weights(model):
    """Elements of one SwiGLU's three matrices."""
    return 3 * model['hidden_size'] * model['intermediate_size']


def head_weights(model):
    return model['hidden_size'] * model['num_pred_heads'] \
        * model['vocab_size']


def parameters(model):
    """Every parameter of the stage: a layer's matrices, its two norms
    and its two learned vectors a head; the embedding, the final norm and
    the head."""
    d, h = model['hidden_size'], model['num_attention_heads']
    a_layer = mixer_weights(model) + mlp_weights(model) + 2 * d \
        + 2 * h * head_dim(model)
    return model['num_hidden_layers'] * a_layer \
        + model['vocab_size'] * d + d + head_weights(model)


def admitted_pairs(model, seq):
    """(exact, summary): the (query, key) pairs a head of one row of
    `seq` that the two masks admit: seq / W windows of W (W + 1) / 2, and
    for the queries of window w the w W / c summaries before it."""
    w, c = model['window_size'], model['chunk_size']
    windows = seq // w
    return (windows * w * (w + 1) // 2,
            sum(i * (w // c) for i in range(windows)) * w)


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    layers, tokens = m['num_hidden_layers'], batch * seq
    exact, summary = admitted_pairs(m, seq)
    # q k^T and p v: 2 x 2 x head_dim a pair a head
    a_pair = 2 * 2 * head_dim(m) * m['num_attention_heads']
    return {
        'eva_projections': layers * tokens * 2 * mixer_weights(m),
        'attention_exact': layers * batch * exact * a_pair,
        'attention_summary': layers * batch * summary * a_pair,
        'dense_mlp': layers * tokens * 2 * mlp_weights(m),
        'head': tokens * 2 * head_weights(m),
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def dense_mlp_flops(config, traffic, chips=1):
    """FLOPs the SwiGLUs require of one chip in one step: 6 a weight a
    token (forward, and twice that backward)."""
    return 3.0 * forward_flops(config, traffic['batch'] // chips,
                               traffic['seq'])['dense_mlp']


def _flash_bytes(model, batch, queries, keys):
    """One attention call as flops/smallthinker.py counts its bytes, with
    the queries' and the keys' lengths apart: forward reads q, k, v and
    writes the output, backward reads q, k, v, the output and its
    gradient and writes three gradients, in bf16 (six passes over the
    queries' side and six over the keys'); plus the float32 log-sum-exp
    rows once written and once read."""
    h = model['num_attention_heads']
    a_row = batch * h * head_dim(model) * 2
    return 6 * a_row * (queries + keys) + 2 * batch * h * queries * 4


def _calls_bytes(model, batch, seq):
    """Both geometries' calls of one mixer: the exact part over the whole
    row, the summary part over the queries from the second window on
    against the summaries before the last window."""
    w, c = model['window_size'], model['chunk_size']
    return _flash_bytes(model, batch, seq, seq) + (
        _flash_bytes(model, batch, seq - w, (seq - w) // c)
        if seq > w else 0)


def eva_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the EVA mixers require of one chip in one step,
    whatever implements them: the four projections and both sets'
    admitted pairs, forward and backward; each matrix read once forward
    and once backward and its gradient written once in bf16; per token
    the mixer's input and output rows written once and read once, forward
    and twice that backward, in bf16; the two attention calls' tensors
    (`_flash_bytes`); the pooling (k and v read forward, read again and
    their gradients written backward; the summaries written, read and
    their gradients read: six passes over [T, H D] and six over
    [T / c, H D], bf16); the merge (the two partial outputs read and the
    merged one written forward, the three read and two gradients written
    backward, over the T - W positions that see summaries)."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    w, c = m['window_size'], m['chunk_size']
    a_row = batch * m['num_attention_heads'] * head_dim(m) * 2
    token_bytes = 2 * 2 * 2 * m['hidden_size']
    pooling = 6 * a_row * (seq + seq // c)
    merge = 8 * a_row * (seq - w)
    return (3.0 * (f['eva_projections'] + f['attention_exact']
                   + f['attention_summary']),
            m['num_hidden_layers'] * (
                3 * 2 * mixer_weights(m) + 3 * batch * seq * token_bytes
                + _calls_bytes(m, batch, seq) + pooling + merge))


def kernel_cost(config, traffic, chips=1):
    """{Fluid op type: (FLOPs, bytes)} of one chip's Pallas kernels in one
    step. `flash_attention`: the two calls a mixer (the causal kernels
    over the aligned windows, the staircase over the summaries), 32 heads
    of 128, FLOPs over BOTH sets' admitted pairs, on `_flash_bytes`."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    f = forward_flops(config, batch, seq)
    return {'flash_attention': (
        3.0 * (f['attention_exact'] + f['attention_summary']),
        m['num_hidden_layers'] * _calls_bytes(m, batch, seq))}
