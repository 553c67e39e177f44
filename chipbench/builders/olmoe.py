"""Builds the OLMoE configuration through the public Fluid surface
(paddle_tpu/models/olmoe.py, from fluid.layers only). The same contract as
builders/transformer.py: build() returns `main`, `startup`, `loss`,
`feeds` and, for a check Program, `grads`; reference_params() hands the
scope's weights to the plain reference in the reference's own structure.

train=True is the Program the window steps: Adam at a constant rate, bf16
AMP. train=False is the deterministic check Program in the same scope
(same parameter names; the model has no dropout): append_backward and no
optimizer, so no weight moves.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import olmoe as O

from chipbench.builders.adam import adam
from chipbench.harness import check


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, feeds = O.olmoe(
            m['vocab_size'], traffic['seq'],
            n_layer=m['num_hidden_layers'], hidden=m['hidden_size'],
            n_head=m['num_attention_heads'], n_expert=m['num_experts'],
            top_k=m['num_experts_per_tok'],
            expert_width=m['intermediate_size'], eps=m['rms_norm_eps'],
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


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: embedding;
    per layer the input norm, then q projection, q norm, k projection, k
    norm, v and output projections, the norm before the experts, the
    router, the experts' gate and up stacks, their down stack; the final norm and the
    head."""
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n=1):
        tree.setdefault(path, []).extend(next(names) for _ in range(n))

    take('tok_emb')
    for i in range(config['model']['num_hidden_layers']):
        p = 'layer%d.' % i
        take(p + 'norm_in')
        for _ in range(2):                     # q, then k
            take(p + 'qkvo')
            take(p + 'qk_norm')
        take(p + 'qkvo', 2)                    # v, out
        take(p + 'norm_post')
        take(p + 'router')
        take(p + 'experts_in', 2)              # gate, up
        take(p + 'experts_down')
    take('norm_final')
    take('head')
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    tree = {k: v if len(v) > 1 else v[0] for k, v in tree.items()}
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
