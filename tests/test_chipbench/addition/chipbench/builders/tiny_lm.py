"""Builds `tiny_lm`, a pre-norm causal decoder, from fluid.layers: token
embedding, n_layer blocks of causal self-attention (the fused attention op)
and a ReLU feed-forward, a final layer norm, an untied output projection,
cross entropy averaged over every position. The same contract as
builders/transformer.py: build() and reference_params().
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, layers, unique_name

from chipbench.harness import check


def _norm(x):
    return layers.layer_norm(x, begin_norm_axis=2)


def _attention(x, d_model, n_head):
    d_head = d_model // n_head

    def heads(t):
        t = layers.reshape(t, shape=[0, 0, n_head, d_head])
        return layers.transpose(t, perm=[0, 2, 1, 3])

    q, k, v = (heads(layers.fc(input=x, size=d_model, num_flatten_dims=2,
                               bias_attr=False)) for _ in range(3))
    ctx = layers.fused_attention(q, k, v, causal=True, scale=d_head ** -0.5)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         shape=[0, 0, d_model])
    return layers.fc(input=ctx, size=d_model, num_flatten_dims=2,
                     bias_attr=False)


def build(config, traffic, train=True):
    m, seq = config['model'], traffic['seq']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        ids = layers.data(name='ids', shape=[seq], dtype='int64')
        labels = layers.data(name='labels', shape=[seq], dtype='int64')
        x = layers.embedding(
            input=ids, size=[m['vocab'], m['d_model']],
            param_attr=fluid.ParamAttr(
                name='tok_emb',
                initializer=fluid.initializer.Normal(0., 0.05)))
        for _ in range(m['n_layer']):
            x = layers.elementwise_add(
                x, _attention(_norm(x), m['d_model'], m['n_head']))
            hidden = layers.fc(input=_norm(x), size=m['d_inner'],
                               num_flatten_dims=2, act='relu')
            x = layers.elementwise_add(
                x, layers.fc(input=hidden, size=m['d_model'],
                             num_flatten_dims=2))
        logits = layers.fc(input=_norm(x), size=m['vocab'],
                           num_flatten_dims=2, bias_attr=False)
        cost = layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, m['vocab']]),
            layers.reshape(labels, shape=[-1, 1]))
        loss = layers.mean(cost)
        grads = {}
        if train:
            fluid.optimizer.Adam(
                learning_rate=config['optimizer']['learning_rate']
            ).minimize(loss)
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss,
            'feeds': ['ids', 'labels'], 'grads': grads}


def reference_params(config, main, read):
    """The reference's tree from the scope, in creation order: embedding,
    per block norm, q k v out, norm, the two feed-forward layers; the final
    norm and the output projection."""
    names = iter(check.parameter_names(main))
    tree = {}

    def take(path, n):
        got = [next(names) for _ in range(n)]
        tree[path] = got if n > 1 else got[0]

    take('tok_emb', 1)
    for i in range(config['model']['n_layer']):
        p = 'block%d.' % i
        take(p + 'ln1', 2)
        take(p + 'qkvo', 4)
        take(p + 'ln2', 2)
        take(p + 'w1b1', 2)
        take(p + 'w2b2', 2)
    take('ln_out', 2)
    take('out_proj', 1)
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: ([read(n) for n in v] if isinstance(v, list) else read(v))
              for k, v in tree.items()}
    return params, tree
