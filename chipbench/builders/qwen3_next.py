"""Builds the Qwen3-Next configuration through the public Fluid surface
(paddle_tpu/models/qwen3_next.py, from fluid.layers only). The same
contract as builders/olmoe.py: build() returns `main`, `startup`, `loss`,
`feeds` and, for a check Program, `grads`; reference_params() hands the
scope's weights to the plain reference in the reference's own structure.

The share: where the configuration lists `num_experts` under `reduced`,
`model.num_experts` is how many experts this chip HOLDS (ids from
`model.first_expert_held`) and `reduced_from.num_experts` is the router's
width; the layer is built with `experts_held`. Otherwise every expert is
here.

train=True is the Program the window steps: Adam at a constant rate, bf16
AMP. train=False is the deterministic check Program in the same scope
(same parameter names; the model has no dropout): append_backward and no
optimizer, so no weight moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import qwen3_next as Q

from chipbench.builders.adam import adam
from chipbench.harness import check


def experts(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'num_experts' in config.get('reduced', ()):
        return (config['reduced_from']['num_experts'],
                (m.get('first_expert_held', 0), m['num_experts']))
    return m['num_experts'], None


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = experts(config)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, feeds = Q.qwen3_next(
            m['vocab_size'], traffic['seq'],
            n_layer=m['num_hidden_layers'], hidden=m['hidden_size'],
            full_attention_interval=m['full_attention_interval'],
            n_head=m['num_attention_heads'],
            n_kv_head=m['num_key_value_heads'], d_head=m['head_dim'],
            rotary_dim=int(m['head_dim'] * m['partial_rotary_factor']),
            n_key=m['linear_num_key_heads'],
            n_value=m['linear_num_value_heads'],
            d_key=m['linear_key_head_dim'],
            d_value=m['linear_value_head_dim'],
            conv_kernel=m['linear_conv_kernel_dim'], n_expert=n_expert,
            top_k=m['num_experts_per_tok'],
            expert_width=m['moe_intermediate_size'],
            shared_width=m['shared_expert_intermediate_size'],
            experts_held=held, eps=m['rms_norm_eps'],
            rope_theta=float(m['rope_theta']),
            norm_topk_prob=m['norm_topk_prob'],
            aux_coef=m['router_aux_loss_coef'], std=m['initializer_range'])
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
# (models/qwen3_next.py: delta_net, gated_attention, expert_block)
_DELTA_NET = ('norm_in', 'qkvz', 'ba', 'conv', 'dt_bias', 'a_log',
              'norm_out', 'out')
_ATTENTION = ('norm_in', 'q', 'k', 'v', 'q_norm', 'k_norm', 'out')
_EXPERTS = (('norm_post', 1), ('router', 1), ('experts_in', 2),
            ('experts_down', 1), ('shared', 3), ('shared_gate', 1))


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer the mixer's parameters, then the expert block's
    (the norm, the router, the experts' gate and up stacks, their down
    stack, the shared expert's gate, up and down projections, its own
    gate); the final norm and the head."""
    m = config['model']
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    take('tok_emb')
    for i in range(m['num_hidden_layers']):
        p = 'layer%d.' % i
        full = (i + 1) % m['full_attention_interval'] == 0
        for key in (_ATTENTION if full else _DELTA_NET):
            take(p + key)
        for key, n in _EXPERTS:
            take(p + key, n)
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
