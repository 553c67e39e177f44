"""Traffic kind `causal_lm`: packed causal language-model rows.

Parameters (traffic/<name>.json): `batch` rows of `seq` positions, `pool`
batches. A row is seq + 1 ids uniform in [0, vocab): `ids` is the row
without its last id, `labels` the row without its first. Rows are packed
full, so every position is work and every seed draws the same amount.

The work unit is the token: batch x seq a step, counted here.
"""
import numpy as np

UNIT = 'tokens'


def make_pool(params, config, seed):
    rng = np.random.default_rng([seed, 0xc1a5])
    b, s = params['batch'], params['seq']
    pool, units = [], []
    for _ in range(params['pool']):
        rows = rng.integers(0, config['model']['vocab'], size=(b, s + 1))
        pool.append({'ids': rows[:, :-1].astype('int64'),
                     'labels': rows[:, 1:].astype('int64')})
        units.append(b * s)
    return pool, units


def recount(batch):
    return int(batch['ids'].size)
