"""Traffic kind `padded_seq2seq`: padded translation batches.

Parameters (traffic/<name>.json): `batch` rows (even), `seq` positions a
side, `pool` batches. For each row a source length and, independently, a
target length are drawn uniformly from [seq/2, seq]; ids are uniform in
[1, vocab) up to the length and 0 (pad) after it; `lbl_word` is `trg_word`
shifted by one. The pads are real work for the device today: the model
masks them through its key bias and its loss weights.

Lengths are drawn in antithetic pairs: the second half of a batch's rows
gets 3 seq / 2 minus the first half's lengths (and the rows are then
shuffled). Each length is still uniform in [seq/2, seq], but every batch of
every seed fills exactly 75%: a step counts exactly 1.5 x batch x seq
tokens. Without the pairing the count, and with it the rate, moved by 1.2%
(one standard deviation) from seed to seed at 16 rows while the device did
the same work (my chip run, PR 22): the amount of work a seed draws is
fixed, what it draws is not.

The work unit is the token: non-pad source plus non-pad target positions,
counted here from the drawn lengths and never by the program.
"""
import numpy as np

UNIT = 'tokens'


def make_pool(params, config, seed):
    """(pool, units): `pool` host feed dicts, `units[i]` tokens of pool[i]."""
    rng = np.random.default_rng([seed, 0x5e92])
    b, s = params['batch'], params['seq']
    model = config['model']
    pool, units = [], []
    pos = np.arange(s)[None, :]
    if b % 2 or s % 2:
        raise ValueError('padded_seq2seq pairs rows: batch and seq are even')

    def lengths():
        half = rng.integers(s // 2, s + 1, size=b // 2)
        both = np.concatenate([half, 3 * s // 2 - half])
        return rng.permutation(both).reshape(b, 1)

    for _ in range(params['pool']):
        src_len, trg_len = lengths(), lengths()
        src = rng.integers(1, model['src_vocab'], size=(b, s))
        trg = rng.integers(1, model['trg_vocab'], size=(b, s + 1))
        pool.append({
            'src_word': np.where(pos < src_len, src, 0).astype('int64'),
            'trg_word': np.where(pos < trg_len, trg[:, :-1], 0)
            .astype('int64'),
            'lbl_word': np.where(pos < trg_len, trg[:, 1:], 0)
            .astype('int64')})
        units.append(int(src_len.sum() + trg_len.sum()))
    return pool, units


def recount(batch):
    """The same count taken from the arrays (the tests' cross-check)."""
    return int((batch['src_word'] != 0).sum() + (batch['trg_word'] != 0).sum())
