"""Builds the Nemotron-3-Nano configuration through the public Fluid
surface (paddle_tpu/models/nemotron_h.py, from fluid.layers only). The
same contract as builders/glm4_moe_lite.py: build() returns `main`,
`startup`, `loss`, `feeds` and, for a check Program, `grads`;
reference_params() hands the scope's weights to the plain reference in
the reference's own structure.

The pattern: the model runs the first `num_hidden_layers` characters of
the source's `hybrid_override_pattern`, which stands whole in the file.
The share: where the configuration lists `n_routed_experts` under
`reduced`, `model.n_routed_experts` is how many experts this chip HOLDS
(ids from `model.first_expert_held`) and `reduced_from.n_routed_experts`
is the router's width; the expert blocks are built with `experts_held`.
Otherwise every expert is here.

train=True is the Program the window steps: Adam, then every router's
selection bias moved by the step's load, under bf16 AMP; the optimizer's
`learning_rate` is the peak of a linear warm-up over its `warmup_steps`
(`assumed.optimizer`). train=False is the deterministic check Program in
the same scope (same parameter names; the model has no dropout):
append_backward, no optimizer and no bias update, so nothing moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import nemotron_h as N

from chipbench.builders.adam import adam
from chipbench.harness import check


def experts(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'n_routed_experts' in config.get('reduced', ()):
        return (config['reduced_from']['n_routed_experts'],
                (m.get('first_expert_held', 0), m['n_routed_experts']))
    return m['n_routed_experts'], None


def pattern(model):
    """The kinds of the blocks that run."""
    return model['hybrid_override_pattern'][:model['num_hidden_layers']]


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = experts(config)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, biases, feeds = N.nemotron_h(
            m['vocab_size'], traffic['seq'], pattern=pattern(m),
            hidden=m['hidden_size'], ssm_heads=m['mamba_num_heads'],
            ssm_head_dim=m['mamba_head_dim'], ssm_groups=m['n_groups'],
            ssm_state=m['ssm_state_size'], conv_kernel=m['conv_kernel'],
            chunk_size=m['chunk_size'], n_head=m['num_attention_heads'],
            n_kv_head=m['num_key_value_heads'], d_head=m['head_dim'],
            n_expert=n_expert, top_k=m['num_experts_per_tok'],
            expert_width=m['moe_intermediate_size'],
            shared_width=m['n_shared_experts']
            * m['moe_shared_expert_intermediate_size'],
            experts_held=held, eps=m['layer_norm_epsilon'],
            norm_topk_prob=m['norm_topk_prob'],
            gate_scale=m['routed_scaling_factor'],
            dt_min=m['time_step_min'], dt_max=m['time_step_max'],
            dt_floor=m['time_step_floor'], std=m['initializer_range'])
        grads = {}
        if train:
            adam(opt).minimize(loss)
            N.router_bias_updates(counts, biases,
                                  rate=m['bias_update_speed'])
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss, 'feeds': feeds,
            'grads': grads}


# a block's parameters after its norm, in creation order, as the reference
# names them (models/nemotron_h.py mamba_mixer, attention_mixer,
# expert_part)
_PARTS = {
    'M': (('in', 1), ('conv', 1), ('conv_bias', 1), ('dt_bias', 1),
          ('a_log', 1), ('d', 1), ('norm_out', 1), ('out', 1)),
    '*': (('q', 1), ('k', 1), ('v', 1), ('out', 1)),
    'E': (('router', 1), ('experts_in', 1), ('experts_out', 1), ('bias', 1),
          ('shared', 2)),
}


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per block its norm and its part's parameters (`_PARTS`);
    the final norm and the head."""
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    take('tok_emb')
    for i, kind in enumerate(pattern(config['model'])):
        take('block%d.norm' % i)
        for key, n in _PARTS[kind]:
            take('block%d.%s' % (i, key), n)
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
