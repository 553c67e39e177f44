"""Builds the Granite-4.0-H-Micro configuration through the public Fluid
surface (paddle_tpu/models/granitemoehybrid.py, from fluid.layers only).
The same contract as builders/lfm2_moe.py: build() returns `main`,
`startup`, `loss`, `feeds` and, for a check Program, `grads`;
reference_params() hands the scope's weights to the plain reference in
the reference's own structure.

The stretch: the model runs the first `num_hidden_layers` entries of the
source's `layer_types`, which stands whole in the file. The model is
dense: there is no share of anything to hold.

train=True is the Program the window steps: Adam under bf16 AMP, at the
configuration's optimizer (builders/adam.py reads its schedule).
train=False is the deterministic check Program in the same scope (same
parameter names; the model has no dropout): append_backward and no
optimizer, so nothing moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import granitemoehybrid as G

from chipbench.builders.adam import adam
from chipbench.harness import check


def kinds(model):
    """The kinds of the layers that run."""
    return model['layer_types'][:model['num_hidden_layers']]


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, feeds = G.granitemoehybrid(
            m['vocab_size'], traffic['seq'], layer_types=m['layer_types'],
            run_layers=range(m['num_hidden_layers']),
            hidden=m['hidden_size'], ssm_heads=m['mamba_n_heads'],
            ssm_head_dim=m['mamba_d_head'], ssm_groups=m['mamba_n_groups'],
            ssm_state=m['mamba_d_state'], conv_kernel=m['mamba_d_conv'],
            chunk_size=m['mamba_chunk_size'],
            n_head=m['num_attention_heads'],
            n_kv_head=m['num_key_value_heads'], d_head=m['head_dim'],
            mlp_width=m['shared_intermediate_size'], eps=m['rms_norm_eps'],
            embedding_scale=m['embedding_multiplier'],
            residual_scale=m['residual_multiplier'],
            attn_scale=m['attention_multiplier'],
            logits_scaling=m['logits_scaling'],
            dt_min=m['time_step_min'], dt_max=m['time_step_max'],
            dt_floor=m['time_step_floor'], std=m['initializer_range'])
        grads = {}
        if train:
            adam(opt).minimize(loss)
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss, 'feeds': feeds,
            'grads': grads}


# a layer's parameters after each of its two norms, in creation order, as
# the reference names them (models/nemotron_h.py mamba_mixer,
# models/granitemoehybrid.py attention_mixer, dense_mlp)
_MIXER = {
    'mamba': ('in', 'conv', 'conv_bias', 'dt_bias', 'a_log', 'd',
              'norm_out', 'out'),
    'attention': ('q', 'k', 'v', 'out'),
}
_MLP = ('mlp_in', 'mlp_out')


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding (which is the head too: ONE entry); per layer its mixer's
    norm and parameters, its feed-forward's norm and parameters; the final
    norm."""
    names = iter(check.parameter_names(main))
    tree = {'tok_emb': next(names)}
    for i, kind in enumerate(kinds(config['model'])):
        for key in ('norm_mixer',) + _MIXER[kind] + ('norm_mlp',) + _MLP:
            tree['layer%d.%s' % (i, key)] = next(names)
    tree['norm_final'] = next(names)
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    return {k: read(v) for k, v in tree.items()}, tree
