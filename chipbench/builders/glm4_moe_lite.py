"""Builds the GLM-4.7-Flash configuration through the public Fluid surface
(paddle_tpu/models/glm4_moe_lite.py, from fluid.layers only). The same
contract as builders/qwen3_next.py: build() returns `main`, `startup`,
`loss`, `feeds` and, for a check Program, `grads`; reference_params()
hands the scope's weights to the plain reference in the reference's own
structure.

The share: where the configuration lists `n_routed_experts` under
`reduced`, `model.n_routed_experts` is how many experts this chip HOLDS
(ids from `model.first_expert_held`) and `reduced_from.n_routed_experts`
is the router's width; the layers are built with `experts_held`.
Otherwise every expert is here.

train=True is the Program the window steps: Adam at a constant rate, then
every router's selection bias moved by the step's load, under bf16 AMP.
train=False is the deterministic check Program in the same scope (same
parameter names; the model has no dropout): append_backward, no
optimizer and no bias update, so nothing moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import glm4_moe_lite as G

from chipbench.builders.adam import adam
from chipbench.harness import check


def experts(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'n_routed_experts' in config.get('reduced', ()):
        return (config['reduced_from']['n_routed_experts'],
                (m.get('first_expert_held', 0), m['n_routed_experts']))
    return m['n_routed_experts'], None


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = experts(config)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, counts, biases, feeds = G.glm4_moe_lite(
            m['vocab_size'], traffic['seq'],
            n_layer=m['num_hidden_layers'],
            first_k_dense=m['first_k_dense_replace'],
            hidden=m['hidden_size'], dense_width=m['intermediate_size'],
            n_head=m['num_attention_heads'], q_rank=m['q_lora_rank'],
            kv_rank=m['kv_lora_rank'], d_nope=m['qk_nope_head_dim'],
            d_rope=m['qk_rope_head_dim'], d_v=m['v_head_dim'],
            n_expert=n_expert, top_k=m['num_experts_per_tok'],
            expert_width=m['moe_intermediate_size'],
            shared_width=m['n_shared_experts'] * m['moe_intermediate_size'],
            experts_held=held, eps=m['rms_norm_eps'],
            rope_theta=float(m['rope_theta']),
            norm_topk_prob=m['norm_topk_prob'],
            gate_scale=m['routed_scaling_factor'],
            n_mtp=m['num_nextn_predict_layers'],
            mtp_weight=m['mtp_loss_weight'], std=m['initializer_range'])
        grads = {}
        if train:
            adam(opt).minimize(loss)
            G.router_bias_updates(counts, biases,
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
# (models/glm4_moe_lite.py: mixer, then the dense feed-forward or
# expert_block)
_MIXER = ('norm_in', 'q_a', 'q_norm', 'q_b', 'kv_a', 'kv_norm', 'kv_b',
          'out', 'norm_post')
_DENSE = (('ffn', 3),)
_EXPERTS = (('router', 1), ('experts_in', 2), ('experts_down', 1),
            ('bias', 1), ('shared', 3))


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer the mixer's parameters, the post norm and the
    feed-forward's (dense: gate, up, down; experts: the router, the gate
    and up stacks, the down stack, the selection bias, the shared
    expert's three); the module's two norms, its projection, its layer,
    its last norm and the head (created by the module, which is built
    before the main head); the final norm."""
    m = config['model']
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    def layer(prefix, dense):
        for key in _MIXER:
            take(prefix + key)
        for key, n in (_DENSE if dense else _EXPERTS):
            take(prefix + key, n)

    take('tok_emb')
    for i in range(m['num_hidden_layers']):
        layer('layer%d.' % i, i < m['first_k_dense_replace'])
    if m['num_nextn_predict_layers']:
        take('mtp.norm_h')
        take('mtp.norm_e')
        take('mtp.proj')
        layer('mtp.layer.', False)
        take('mtp.norm_m')
        take('head')
        take('norm_final')
    else:
        take('norm_final')
        take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
