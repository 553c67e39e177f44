"""Operations one training step of Granite-4.0-H-Micro's stage requires,
from its static shapes: 2 FLOPs a multiply-add, the backward pass at twice
the forward, causal attention over the (query, key) pairs its mask admits,
the state-space recurrence at what its DEFINITION needs (5 FLOPs a state
element a token: the decay 1, the write (dt x) B^T and its add 2, the read
S C 2; never the FLOPs of a chunked form): what the mathematics needs,
whatever the implementation multiplies. The model is dense: every layer's
gated feed-forward sees every token. What the step recomputes in its
backward pass (fluid.recompute_guard, the ops' own backwards) is not
counted: `mfu_pct`, `dense_mlp_peak_pct` and the roofline shares are of
the REQUIRED operations.
"""


def layer_counts(model):
    """(Mamba-2 layers, attention layers) that run."""
    kinds = model['layer_types'][:model['num_hidden_layers']]
    return kinds.count('mamba'), kinds.count('attention')


def mamba_widths(model):
    """(inner = heads x head width, B and C's width together, heads)"""
    h = model['mamba_n_heads']
    return (h * model['mamba_d_head'],
            2 * model['mamba_n_groups'] * model['mamba_d_state'], h)


def mamba_weights(model):
    """Elements of one mixer's two matrices."""
    inner, bc, h = mamba_widths(model)
    return model['hidden_size'] * (2 * inner + bc + h) \
        + inner * model['hidden_size']


def attention_weights(model):
    d, width = model['hidden_size'], model['head_dim']
    return d * width * 2 * (model['num_attention_heads']
                            + model['num_key_value_heads'])


def mlp_weights(model):
    """Elements of one gated feed-forward's two matrices."""
    return 3 * model['hidden_size'] * model['shared_intermediate_size']


def forward_flops(config, batch, seq):
    """{part: FLOPs of one forward pass over batch x seq tokens}"""
    m = config['model']
    n_mamba, n_attn = layer_counts(m)
    tokens = batch * seq
    inner, bc, h = mamba_widths(m)
    pairs = seq * (seq + 1) // 2
    return {
        'mamba_projections': n_mamba * tokens * 2 * mamba_weights(m),
        'ssd': n_mamba * tokens * 5 * inner * m['mamba_d_state'],
        'conv': n_mamba * tokens * 2 * m['mamba_d_conv'] * (inner + bc),
        'attention_projections': n_attn * tokens * 2 * attention_weights(m),
        # q k^T and p v: 2 x 2 x head_dim a pair a query head
        'attention': n_attn * batch * pairs * 2 * 2 * m['head_dim']
        * m['num_attention_heads'],
        'dense_mlp': (n_mamba + n_attn) * tokens * 2 * mlp_weights(m),
        'head': tokens * 2 * m['hidden_size'] * m['vocab_size'],
    }


def train_step_flops(config, traffic):
    return 3.0 * sum(forward_flops(config, traffic['batch'],
                                   traffic['seq']).values())


def dense_mlp_flops(config, traffic, chips=1):
    """FLOPs the gated feed-forwards require of one chip in one step: 6 a
    weight a token (forward, and twice that backward)."""
    return 3.0 * forward_flops(config, traffic['batch'] // chips,
                               traffic['seq'])['dense_mlp']


def ssd_cost(config, traffic, chips=1):
    """(FLOPs, bytes) the `ssd_scan` ops require of one chip in one step,
    whatever implements them (flops/nemotron_h.py `ssd_cost`, at this
    model's ONE group): the recurrence's FLOPs forward and twice that
    backward; x, B, C in and y out in bf16 and dt in float32 once a pass,
    their gradients once (the same again), so three times a pass's bytes a
    step; A and D are a head's and a rounding."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    n_mamba, _ = layer_counts(m)
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
    step. `flash_attention`: one call an attention layer, 32 heads of 64
    over 8 key-value heads, on `_flash_bytes`. The scan's kernels are
    `ssd_cost`'s; `causal_conv1d`'s and `gated_rms_norm`'s are elementwise
    and have no entry, as in flops/nemotron_h.py."""
    m = config['model']
    batch, seq = traffic['batch'] // chips, traffic['seq']
    _, n_attn = layer_counts(m)
    return {'flash_attention': (
        3.0 * forward_flops(config, batch, seq)['attention'],
        n_attn * _flash_bytes(m, batch, seq))}
