"""Traffic kind `host_images`: image batches handed over as host arrays.

Parameters (traffic/<name>.json): `batch` images, `pool` batches. Images are
float32, channels-last, uniform in [0, 1), at the configuration's
`image_size`; labels are uniform in [0, class_dim). Every step feeds one
host batch, as a Fluid DataFeeder hands it over: 154 MB a step at batch
256, so the entry layer's transfer shows.

The work unit is the image.
"""
import numpy as np

UNIT = 'images'


def make_pool(params, config, seed):
    rng = np.random.default_rng([seed, 0x1a6e])
    b, model = params['batch'], config['model']
    size = model['image_size']
    pool, units = [], []
    for _ in range(params['pool']):
        pool.append({
            'data': rng.random((b, size, size, 3), dtype=np.float32),
            'label': rng.integers(0, model['class_dim'], size=(b, 1))
            .astype('int64')})
        units.append(b)
    return pool, units


def recount(batch):
    return int(batch['data'].shape[0])
