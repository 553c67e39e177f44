"""Builds the ResNet configuration through the public Fluid surface, as the
reference's benchmark/fluid/models/resnet.py does (resnet_imagenet, softmax
output, cross_entropy, mean, Momentum), channels-last, bf16 AMP over a
float32 input. Same contract as builders/transformer.py.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models.resnet import resnet_imagenet

from chipbench.harness import check


def build(config, traffic, train=True):
    m, opt = config['model'], config['optimizer']
    if m['data_format'] != 'NHWC':
        raise ValueError('the traffic generator makes NHWC images')
    size = m['image_size']
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 7
    with unique_name.guard(), framework.program_guard(main, startup):
        img = fluid.layers.data(name='data', shape=[size, size, 3],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        predict = resnet_imagenet(img, class_dim=m['class_dim'],
                                  depth=m['depth'], data_format='NHWC')
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        grads = {}
        if train:
            fluid.optimizer.Momentum(
                learning_rate=opt['learning_rate'],
                momentum=opt['momentum']).minimize(loss)
        else:
            want = set(config['check']['grads'])
            grads = {p.name: g for p, g in fluid.backward.append_backward(loss)
                     if p.name in want}
        if config['amp'] == 'bf16':
            fluid.amp.decorate_program(main)
    return {'main': main, 'startup': startup, 'loss': loss,
            'feeds': ['data', 'label'], 'grads': grads}


def reference_params(config, main, read):
    """Walks the parameters in creation order: each convolution is followed
    by its batch norm (scale, shift, running mean, running variance; the
    reference takes only scale and shift, it normalises by batch
    statistics). In a block with a projection the shortcut comes first.
    `resnet_imagenet` takes only the depth, so this walk is also where the
    file's stages and widths are held to the Program that was built: a
    filter of another shape than the file implies is an error."""
    m = config['model']
    names = iter(check.parameter_names(main))
    tree, shapes = {}, {}

    def conv_bn(path, c_out, c_in, k):
        got = [next(names) for _ in range(5)]
        tree[path] = got[:3]               # conv weight, bn scale, bn shift
        shapes[path] = (c_out, c_in, k, k)

    conv_bn('stem', m['stem_width'], 3, 7)
    ch_in = m['stem_width']
    for s, (count, width) in enumerate(zip(m['stages'], m['stage_width'])):
        for b in range(count):
            ch_out = width * m['bottleneck_expansion']
            p = 's%d.b%d.' % (s, b)
            if ch_in != ch_out:
                conv_bn(p + 'proj', ch_out, ch_in, 1)
            conv_bn(p + 'c0', width, ch_in, 1)
            conv_bn(p + 'c1', width, width, 3)
            conv_bn(p + 'c2', ch_out, width, 1)
            ch_in = ch_out
    tree['fc'] = [next(names), next(names)]
    left = list(names)
    if left:
        raise ValueError('parameters the reference does not know: %r' % left)
    params = {k: [read(n) for n in v] for k, v in tree.items()}
    wrong = {k: (params[k][0].shape, want) for k, want in shapes.items()
             if tuple(params[k][0].shape) != want}
    if wrong or params['fc'][0].shape != (ch_in, m['class_dim']):
        raise ValueError('the Program is not the model %s describes: %r'
                         % (config['name'], wrong or params['fc'][0].shape))
    return params, tree
