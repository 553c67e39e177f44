"""Operations one training step of ResNet requires, derived from the layer
shapes the configuration gives (not an assumed constant): every
convolution and the classifier at 2 FLOPs a multiply-add, backward at
twice the forward. Batch norm, ReLU, pooling and the additions are not
counted: they are bandwidth, not operations the MXU is there for.
"""


def conv_layers(model):
    """[(name, out_hw, c_in, c_out, k)] of one image's forward pass."""
    size = model['image_size']
    hw = (size + 2 * 3 - 7) // 2 + 1                 # stem, stride 2, pad 3
    layers = [('stem', hw, 3, model['stem_width'], 7)]
    hw = (hw - 3) // 2 + 1                           # 3 x 3 pool, no padding
    c_in = model['stem_width']
    for s, (count, width) in enumerate(zip(model['stages'],
                                           model['stage_width'])):
        c_out = width * model['bottleneck_expansion']
        for b in range(count):
            stride = 2 if (b == 0 and s > 0) else 1
            hw_out = (hw - 1) // stride + 1          # 1 x 1, no padding
            p = 's%d.b%d.' % (s, b)
            if c_in != c_out:
                layers.append((p + 'proj', hw_out, c_in, c_out, 1))
            layers.append((p + 'c0', hw_out, c_in, width, 1))
            layers.append((p + 'c1', hw_out, width, width, 3))
            layers.append((p + 'c2', hw_out, width, c_out, 1))
            hw, c_in = hw_out, c_out
    return layers


def forward_flops_per_image(model):
    convs = sum(2 * hw * hw * c_in * c_out * k * k
                for _, hw, c_in, c_out, k in conv_layers(model))
    c_last = model['stage_width'][-1] * model['bottleneck_expansion']
    return convs + 2 * c_last * model['class_dim']


def train_step_flops(config, traffic):
    return 3.0 * traffic['batch'] * forward_flops_per_image(config['model'])


def kernel_cost(config, traffic, chips=1):
    """No Pallas kernel runs in this configuration."""
    return None
