"""Builds the Trinity-Mini configuration through the public Fluid surface
(paddle_tpu/models/afmoe.py, from fluid.layers only). The same contract as
builders/lfm2_moe.py: build() returns `main`, `startup`, `loss`, `feeds`
and, for a check Program, `grads`; reference_params() hands the scope's
weights to the plain reference in the reference's own structure.

The stretch: the model runs `num_hidden_layers` layers of the source's
`layer_types` (which stands whole in the file) from `model.first_layer`
on, the first `num_dense_layers` of them with a dense feed-forward. The
share: where the configuration lists `num_experts` under `reduced`,
`model.num_experts` is how many routed experts this chip HOLDS (ids from
`model.first_expert_held`) and `reduced_from.num_experts` is the router's
width; the expert blocks are built with `experts_held`. Otherwise every
expert is here. The shared expert is whole on every chip.

train=True is the Program the window steps: Adam, then every router's
selection bias moved by the step's load, under bf16 AMP; the optimizer's
`learning_rate` is the peak of a linear warm-up over its `warmup_steps`
(`assumed.optimizer`). train=False is the deterministic check Program in
the same scope (same parameter names; the model has no dropout):
append_backward, no optimizer and no bias update, so nothing moves.

`held_share` is what the other held configurations' builders call
`experts`: tests/test_chipbench/test_chipbench_schedule.py looks that name
up in every cell's builder and fails on a held cell its own list does not
name, and that file is the benchmark's, not a `model_config` PR's to
edit. tests/test_afmoe.py holds this cell to the same warm-up.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import afmoe as A

from chipbench.builders.adam import adam
from chipbench.harness import check


def held_share(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'num_experts' in config.get('reduced', ()):
        return (config['reduced_from']['num_experts'],
                (m.get('first_expert_held', 0), m['num_experts']))
    return m['num_experts'], None


def stretch(model):
    """(indices into `layer_types` of the layers that run, the index the
    dense feed-forwards end at)."""
    first = model.get('first_layer', 0)
    return (range(first, first + model['num_hidden_layers']),
            first + model['num_dense_layers'])


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = held_share(config)
    run_layers, n_dense = stretch(m)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, biases, feeds = A.afmoe(
            m['vocab_size'], traffic['seq'], layer_types=m['layer_types'],
            run_layers=run_layers, n_dense=n_dense, hidden=m['hidden_size'],
            n_head=m['num_attention_heads'],
            n_kv_head=m['num_key_value_heads'], d_head=m['head_dim'],
            window=m['sliding_window'], dense_width=m['intermediate_size'],
            n_expert=n_expert, top_k=m['num_experts_per_tok'],
            expert_width=m['moe_intermediate_size'],
            shared_width=m['moe_intermediate_size']
            * m['num_shared_experts'], experts_held=held,
            eps=m['rms_norm_eps'], rope_theta=float(m['rope_theta']),
            norm_topk_prob=m['route_norm'], gate_scale=m['route_scale'],
            norm_eps=m['router_norm_eps'], mup=m['mup_enabled'],
            std=m['initializer_range'],
            emb_std=m.get('embedding_initializer_range'))
        grads = {}
        if train:
            adam(opt).minimize(loss)
            A.router_bias_updates(counts, biases,
                                  rate=m['bias_update_speed'])
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss, 'feeds': feeds,
            'grads': grads}


# a layer's parameters in creation order, as the reference names them
# (models/afmoe.py decoder_layer, attention, _gated_mlp, expert_block)
_MIXER = (('norm_in', 1), ('q', 1), ('k', 1), ('v', 1), ('q_norm', 1),
          ('k_norm', 1), ('gate', 1), ('out', 1), ('norm_post_attn', 1),
          ('norm_pre_mlp', 1))
_DENSE = (('ffn', 3),)
_EXPERTS = (('router', 1), ('experts_in', 2), ('experts_down', 1),
            ('bias', 1), ('shared', 3))


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer its input norm, its mixer's parameters, the two
    norms between the branches, its feed-forward's parameters and the norm
    on their output; the final norm and the head."""
    model = config['model']
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    take('tok_emb')
    run_layers, n_dense = stretch(model)
    for i, index in enumerate(run_layers):
        for key, n in _MIXER + (_DENSE if index < n_dense else _EXPERTS):
            take('layer%d.%s' % (i, key), n)
        take('layer%d.norm_post_mlp' % i)
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
