"""Traffic kind `zipf_lm`: packed causal language-model rows of Zipfian
ids.

Parameters (traffic/<name>.json): `batch` rows of `seq` positions, `pool`
batches, `zipf_exponent`. A row is seq + 1 ids packed full (no pads, every
position is work): `input_ids` is the row without its last id, `labels`
the row without its first. An id's rank is drawn from a Zipf law with the
given exponent over the configuration's whole vocabulary (P(rank r)
proportional to r^-exponent: text is Zipfian), and the ranks are laid over
the ids by a seeded permutation, so which ids are frequent differs from
seed to seed and how skewed they are does not.

The work unit is the token: batch x seq a step, counted here.
"""
import numpy as np

UNIT = 'tokens'


def make_pool(params, config, seed):
    rng = np.random.default_rng([seed, 0x21bf])
    b, s = params['batch'], params['seq']
    vocab = config['model']['vocab_size']
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -params['zipf_exponent']
    cdf = np.cumsum(p / p.sum())
    id_of_rank = rng.permutation(vocab)
    pool, units = [], []
    for _ in range(params['pool']):
        ranks = np.searchsorted(cdf, rng.random((b, s + 1)), side='right')
        rows = id_of_rank[np.minimum(ranks, vocab - 1)]
        pool.append({'input_ids': rows[:, :-1].astype('int64'),
                     'labels': rows[:, 1:].astype('int64')})
        units.append(b * s)
    return pool, units


def recount(batch):
    return int(batch['input_ids'].size)
