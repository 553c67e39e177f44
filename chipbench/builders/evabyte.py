"""Builds the EvaByte configuration through the public Fluid surface
(paddle_tpu/models/evabyte.py, from fluid.layers only). The same contract
as builders/granitemoehybrid.py: build() returns `main`, `startup`,
`loss`, `feeds` and, for a check Program, `grads`; reference_params()
hands the scope's weights to the plain reference in the reference's own
structure.

The stretch: the model runs its first `num_hidden_layers` layers (every
layer is of the one kind). The model is dense: there is no share of
anything to hold, and the vocabulary (320 bytes) and the eight prediction
heads stand whole.

train=True is the Program the window steps: Adam under bf16 AMP, at the
configuration's optimizer (builders/adam.py reads its schedule).
train=False is the deterministic check Program in the same scope (same
parameter names; the model has no dropout): append_backward and no
optimizer, so nothing moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import evabyte as E

from chipbench.builders.adam import adam
from chipbench.harness import check


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, feeds = E.evabyte(
            m['vocab_size'], traffic['seq'], n_layer=m['num_hidden_layers'],
            hidden=m['hidden_size'], n_head=m['num_attention_heads'],
            d_head=m['hidden_size'] // m['num_attention_heads'],
            mlp_width=m['intermediate_size'], chunk_size=m['chunk_size'],
            window_size=m['window_size'], num_chunks=m['num_chunks'],
            n_pred_heads=m['num_pred_heads'], rope_theta=m['rope_theta'],
            eps=m['rms_norm_eps'], std=m['init_std'])
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


# a layer's parameters in creation order, as the reference names them
# (models/evabyte.py decoder_layer, eva_mixer, dense_mlp)
_LAYER = ('norm_mixer', 'q', 'k', 'v', 'mu', 'phi', 'out',
          'norm_mlp', 'gate', 'up', 'down')


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer its mixer's norm and parameters, its
    feed-forward's norm and parameters; the final norm; the head."""
    names = iter(check.parameter_names(main))
    tree = {'tok_emb': next(names)}
    for i in range(config['model']['num_hidden_layers']):
        for key in _LAYER:
            tree['layer%d.%s' % (i, key)] = next(names)
    tree['norm_final'] = next(names)
    tree['head'] = next(names)
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    return {k: read(v) for k, v in tree.items()}, tree
