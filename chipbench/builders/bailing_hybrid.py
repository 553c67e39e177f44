"""Builds the Ling-3.0-flash configuration through the public Fluid surface
(paddle_tpu/models/bailing_hybrid.py, from fluid.layers only). The same
contract as builders/glm4_moe_lite.py: build() returns `main`, `startup`,
`loss`, `feeds` and, for a check Program, `grads`; reference_params()
hands the scope's weights to the plain reference in the reference's own
structure.

The share: where the configuration lists `num_experts` under `reduced`,
`model.num_experts` is how many routed experts this chip HOLDS (ids from
`model.first_expert_held`) and `reduced_from.num_experts` is the router's
width, over which the groups, the top 8 and the bias stay; the expert
blocks are built with `experts_held`. Otherwise every expert is here. The
shared expert is whole on every chip.

The layers that run are `model.kept_layers`, the source's indices of the
`num_hidden_layers` layers of this stage (each one's mixer by the pattern
of `layer_group_size`; the first `first_k_dense_replace` of them dense);
the source's two lists of SwiGLU clamps stand whole in the file, and a
nonzero clamp among the kept layers is refused by the model's builder
function, which builds none.

train=True is the Program the window steps: Adam, then every router's
selection bias moved by the step's load, under bf16 AMP; the optimizer's
`learning_rate` is the peak of a linear warm-up over its `warmup_steps`
(`assumed.optimizer`). train=False is the deterministic check Program in
the same scope (same parameter names; the model has no dropout):
append_backward, no optimizer and no bias update, so nothing moves.

`held_share` is what the older held configurations' builders call
`experts` (builders/afmoe.py says why the name differs).
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import bailing_hybrid as B

from chipbench.builders.adam import adam
from chipbench.harness import check


def held_share(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'num_experts' in config.get('reduced', ()):
        return (config['reduced_from']['num_experts'],
                (m.get('first_expert_held', 0), m['num_experts']))
    return m['num_experts'], None


def swiglu_limits(config):
    """The source's clamps of the layers that run: both lists at the
    published layers `model.kept_layers` stands for (the first
    `num_hidden_layers` where it names none)."""
    m = config['model']
    kept = m.get('kept_layers', range(m['num_hidden_layers']))
    return [config[key][i] for i in kept
            for key in ('expert_swiglu_limit_list',
                        'share_expert_swiglu_limit_list') if key in config]


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = held_share(config)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, biases, feeds = B.bailing_hybrid(
            m['vocab_size'], traffic['seq'],
            n_layer=m['num_hidden_layers'],
            first_k_dense=m['first_k_dense_replace'],
            layer_group_size=m['layer_group_size'],
            hidden=m['hidden_size'], dense_width=m['intermediate_size'],
            n_head=m['num_attention_heads'], head_dim=m['head_dim'],
            conv_kernel=m['short_conv_kernel_size'],
            gate_floor=float(m['kda_lower_bound']),
            kv_rank=m['kv_lora_rank'], d_nope=m['qk_nope_head_dim'],
            d_rope=m['qk_rope_head_dim'], d_v=m['v_head_dim'],
            n_expert=n_expert, top_k=m['num_experts_per_tok'],
            n_group=m['n_group'], topk_group=m['topk_group'],
            expert_width=m['moe_intermediate_size'],
            shared_width=m['num_shared_experts']
            * m['moe_shared_expert_intermediate_size'],
            experts_held=held, eps=m['rms_norm_eps'],
            rope_theta=float(m['rope_theta']),
            norm_topk_prob=m['norm_topk_prob'],
            gate_scale=m['routed_scaling_factor'],
            std=m['initializer_range'], chunk_size=m.get('chunk_size', 64),
            swiglu_limits=swiglu_limits(config),
            layer_ids=m.get('kept_layers'))
        grads = {}
        if train:
            adam(opt).minimize(loss)
            B.router_bias_updates(counts, biases,
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
# (models/bailing_hybrid.py: kda_mixer or mla_mixer, the post norm, then
# the dense feed-forward or expert_block)
_KDA = ('norm_in', 'q', 'conv_q', 'k', 'conv_k', 'v', 'conv_v', 'f',
        'dt_bias', 'a_log', 'b', 'g', 'norm_out', 'out', 'norm_post')
_MLA = ('norm_in', 'q', 'kv_a', 'kv_norm', 'kv_b', 'gate', 'out',
        'norm_post')
_DENSE = (('ffn', 3),)
_EXPERTS = (('router', 1), ('experts_in', 2), ('experts_down', 1),
            ('bias', 1), ('shared', 3))


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer the mixer's parameters, the post norm and the
    feed-forward's (dense: gate, up, down; experts: the router, the gate
    and up stacks, the down stack, the selection bias, the shared
    expert's three); the final norm; the head."""
    m = config['model']
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    kept = m.get('kept_layers', range(m['num_hidden_layers']))
    take('tok_emb')
    for i, index in enumerate(kept):
        prefix = 'layer%d.' % i
        for key in (_MLA if B.is_mla(index, m['layer_group_size'])
                    else _KDA):
            take(prefix + key)
        for key, n in (_DENSE if i < m['first_k_dense_replace']
                       else _EXPERTS):
            take(prefix + key, n)
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
