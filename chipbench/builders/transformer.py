"""Builds the Transformer configuration through the public Fluid surface.

build(config, traffic, train) returns a dict with `main`, `startup`, `loss`
(the Variable to fetch), `feeds` (feed names in the traffic's order) and,
for the check Program, `grads` {parameter name: gradient Variable}.

train=True is the Program the window steps: the paper's dropout, Adam
under the noam schedule, bf16 AMP. train=False is the deterministic check
Program: same builder, same parameter names (so it reads the weights the
training Program's start-up wrote), dropout 0, append_backward and no
optimizer, so no weight moves.

reference_params() hands the scope's weights to the plain reference in the
reference's own structure; the mapping from Fluid's creation-order names
lives here so that the reference holds no Fluid name.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import transformer as T

from chipbench.harness import check


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        loss, _, feeds = T.transformer(
            m['src_vocab'], m['trg_vocab'], traffic['seq'],
            n_layer=m['n_layer'], d_model=m['d_model'], n_head=m['n_head'],
            d_inner=m['d_inner'],
            dropout_rate=m['dropout'] if train else 0.0,
            label_smooth_eps=m['label_smooth_eps'])
        grads = {}
        if train:
            lr = fluid.layers.learning_rate_scheduler.noam_decay(
                m['d_model'], opt['warmup_steps'])
            fluid.optimizer.Adam(
                learning_rate=lr, beta1=opt['beta1'], beta2=opt['beta2'],
                epsilon=opt['epsilon']).minimize(loss)
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss, 'feeds': feeds,
            'grads': grads}


def reference_params(config, main, read):
    """The reference's parameter tree from the scope. `read(name)` returns
    one parameter as a host array. Walks the Program's parameters in
    creation order: embedding, then per layer the attention projections
    (q, k, v, out), a layer norm, [cross attention + norm,] the two
    feed-forward layers, a layer norm."""
    m = config['model']
    names = iter(check.parameter_names(main))
    tree = {}          # reference path -> Fluid parameter name

    def take(path, n):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    def attn(path):
        take(path + '.qkvo', 4)
        take(path + '.ln', 2)

    def ffn(path):
        take(path + '.w1b1', 2)
        take(path + '.w2b2', 2)
        take(path + '.ln', 2)

    take('src_emb', 1)
    for i in range(m['n_layer']):
        attn('enc%d.self' % i)
        ffn('enc%d.ffn' % i)
    take('trg_emb', 1)
    for i in range(m['n_layer']):
        attn('dec%d.self' % i)
        attn('dec%d.cross' % i)
        ffn('dec%d.ffn' % i)
    take('out_proj', 1)
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
