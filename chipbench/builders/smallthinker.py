"""Builds the SmallThinker configuration through the public Fluid surface
(paddle_tpu/models/smallthinker.py, from fluid.layers only). The same
contract as builders/qwen3_next.py: build() returns `main`, `startup`,
`loss`, `feeds` and, for a check Program, `grads`; reference_params()
hands the scope's weights to the plain reference in the reference's own
structure.

The share: where the configuration lists `moe_num_primary_experts` under
`reduced`, `model.moe_num_primary_experts` is how many experts this chip
HOLDS (ids from `model.first_expert_held`) and
`reduced_from.moe_num_primary_experts` is the router's width; the layers
are built with `experts_held`. Otherwise every expert is here. The two
layouts are the source's whole lists; the model runs their first
`num_hidden_layers` entries.

train=True is the Program the window steps: Adam, bf16 AMP; the
optimizer's `learning_rate` is the peak of a linear warm-up over its
`warmup_steps` (the window is the warm-up's first steps: from step 0 at
the peak the routers collapse within four steps, `assumed.optimizer`). train=False is the deterministic check Program in the same scope
(same parameter names; the model has no dropout): append_backward and no
optimizer, so no weight moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import smallthinker as S

from chipbench.builders.adam import adam
from chipbench.harness import check


def experts(config):
    """(the router's width, experts_held or None)"""
    m = config['model']
    if 'moe_num_primary_experts' in config.get('reduced', ()):
        return (config['reduced_from']['moe_num_primary_experts'],
                (m.get('first_expert_held', 0), m['moe_num_primary_experts']))
    return m['moe_num_primary_experts'], None


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    n_expert, held = experts(config)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, feeds = S.smallthinker(
            m['vocab_size'], traffic['seq'], n_layer=m['num_hidden_layers'],
            hidden=m['hidden_size'], n_head=m['num_attention_heads'],
            n_kv_head=m['num_key_value_heads'], d_head=m['head_dim'],
            window=m['sliding_window_size'],
            sliding_window_layout=m['sliding_window_layout'],
            rope_layout=m['rope_layout'], n_expert=n_expert,
            top_k=m['moe_num_active_primary_experts'],
            expert_width=m['moe_ffn_hidden_size'], experts_held=held,
            eps=m['rms_norm_eps'], rope_theta=float(m['rope_theta']),
            aux_coef=m['router_aux_loss_coef'], std=m['initializer_range'],
            emb_std=m['embedding_initializer_range'])
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
# (models/smallthinker.py decoder_layer)
_LAYER = (('norm_in', 1), ('q', 1), ('k', 1), ('v', 1), ('out', 1),
          ('norm_post', 1), ('router', 1), ('experts_in', 2),
          ('experts_down', 1))


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: the
    embedding; per layer the input norm, Wq, Wk, Wv, Wo, the
    post-attention norm, the router, the experts' gate and up stacks and
    their down stack; the final norm and the head."""
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    take('tok_emb')
    for i in range(config['model']['num_hidden_layers']):
        for key, n in _LAYER:
            take('layer%d.%s' % (i, key), n)
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
