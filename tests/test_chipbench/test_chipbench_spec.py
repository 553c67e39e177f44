"""BENCHMARK.json against the contract, the traffic generators, the FLOP
functions and the Transformer's reference: everything that needs no
compiled training step.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
CELLS = ['tfm_s1024', 'tfm_s256', 'resnet50_b256', 'tfm_s1024_dp4']


@pytest.fixture(scope='module')
def spec():
    with open(os.path.join(toy.REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_benchmark_json_meets_the_contract(spec):
    assert set(spec) == {'command', 'paths', 'run_seconds', 'configs',
                         'workloads', 'end_to_end', 'per_layer'}
    assert os.path.getsize(os.path.join(toy.REPO, 'BENCHMARK.json')) < 65536
    assert spec['paths'] == ['chipbench', 'tests/test_chipbench']
    assert spec['command'][:2] == ['python3', 'chipbench/run.py']
    n = len(spec['workloads'])
    assert 2 <= n <= 24
    assert isinstance(spec['run_seconds'], int)
    assert 1 <= spec['run_seconds'] <= 51
    # the full check, with all 24 cells a later PR may add, fits
    full = (2 + 14 * 24) * (spec['run_seconds'] + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200
    names = set()
    configs = {c['name']: c for c in spec['configs']}
    for c in spec['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(c['name']) and c['file'].startswith('chipbench/')
        with open(os.path.join(toy.REPO, c['file'])) as f:
            held = json.load(f)
        assert held['source'] == c['source']
        assert held['reduced'] == c['reduced'] == []
    pairs = set()
    for w in spec['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['config'] in configs and w['chips'] in (1, 4)
        assert 1 <= len(w['why']) <= 200 and '\n' not in w['why']
        pairs.add((w['config'], w['traffic']))
        names.add(w['name'])
    assert len(pairs) == n and len(names) == n
    assert {w['config'] for w in spec['workloads']} == set(configs)
    four = sum(w['chips'] == 4 for w in spec['workloads'])
    assert four <= max(1, n // 4)
    e2e = {m['name']: m for m in spec['end_to_end']}
    assert 'setup_s' in e2e and e2e['setup_s']['bound'] <= 0.1
    metric_names = [m['name'] for m in spec['end_to_end'] + spec['per_layer']]
    assert len(metric_names) == len(set(metric_names))
    for m in spec['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
    for m in spec['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert m['moves'] in e2e and m['source'] in SOURCES
        assert 1 <= len(m['layer']) <= 200
        # reported only where the metric it moves is
        where = set(m.get('workloads', names))
        assert where <= set(e2e[m['moves']].get('workloads', names)), m
    for m in spec['end_to_end'] + spec['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
        assert set(m.get('workloads', [])) <= names
    for w in names:
        has = [m['name'] for m in spec['end_to_end']
               if w in m.get('workloads', names)]
        assert 'setup_s' in has and len(has) >= 2
        assert any(w in m.get('workloads', names) for m in spec['per_layer'])


def test_every_named_thing_has_its_file(spec):
    """Cells, configurations, traffic mixes and per-layer metrics are found
    by the names BENCHMARK.json gives; files are named from a name's
    characters."""
    from chipbench.harness import catalog
    assert [w['name'] for w in spec['workloads']] == CELLS
    for w in spec['workloads']:
        cell = catalog.load_cell(w['name'])
        assert cell['cell']['config'] == w['config']
        assert cell['cell']['traffic'] == w['traffic']
        assert cell['cell']['chips'] == w['chips']
        assert cell['cell']['why'] == w['why']
        # the rate is named by the generator's unit of work
        assert {m['name'] for m in catalog.metrics_of(
            w['name'], 'end_to_end')} == {
                cell['generator'].UNIT + '_per_s', 'setup_s'}
    for m in spec['per_layer']:
        assert callable(catalog.load_reader(m['name']))
    for path in spec['paths']:
        for d, _, files in os.walk(os.path.join(toy.REPO, path)):
            if '__pycache__' in d:
                continue
            for f in files:
                assert re.match(r'^[A-Za-z0-9_.\-]+$', f), f


@pytest.mark.parametrize('name', CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    cell = toy.load_toy_cell(name)
    gen, traffic, config = cell['generator'], cell['traffic'], cell['config']
    a, units_a = gen.make_pool(traffic, config, 11)
    b, units_b = gen.make_pool(traffic, config, 11)
    c, _ = gen.make_pool(traffic, config, 12)
    assert len(a) == traffic['pool'] and units_a == units_b
    for x, y in zip(a, b):
        assert set(x) == set(y)
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert any(not np.array_equal(a[0][k], c[0][k]) for k in a[0])
    # the count the rate is made of equals a recount from the arrays
    assert units_a == [gen.recount(x) for x in a]
    for x in a:
        assert all(v.shape[0] == traffic['batch'] for v in x.values())


def test_padded_seq2seq_shapes_pads_and_shift():
    cell = toy.load_toy_cell('tfm_s256')
    traffic = dict(cell['traffic'], batch=64, seq=32, pool=4)
    pool, units = cell['generator'].make_pool(traffic, cell['config'], 1)
    vocab = cell['config']['model']['trg_vocab']
    fill = []
    for batch in pool:
        src, trg, lbl = (batch[k] for k in ('src_word', 'trg_word',
                                            'lbl_word'))
        assert src.dtype == trg.dtype == lbl.dtype == np.int64
        for ids in (src, trg, lbl):
            lengths = (ids != 0).sum(1)
            assert lengths.min() >= 16 and lengths.max() <= 32
            # ids up to the length, pads after it
            assert all((row[:n] > 0).all() and (row[n:] == 0).all()
                       for row, n in zip(ids, lengths))
            assert ids.max() < vocab
        # the label is the target shifted by one
        assert np.array_equal(lbl[:, :15], trg[:, 1:16])
        assert np.array_equal((lbl != 0).sum(1), (trg != 0).sum(1))
        fill.append(((src != 0).mean() + (trg != 0).mean()) / 2)
    assert 0.7 < np.mean(fill) < 0.8            # mean fill 75%
    assert sum(units) == sum((b['src_word'] != 0).sum()
                             + (b['trg_word'] != 0).sum() for b in pool)


def test_transformer_flops_meet_the_cross_checks():
    """ISSUE 22: 16 x 1024 needs about 7.4 TFLOP a step (5.85 in weight
    matmuls, 1.55 in attention), 64 x 256 about 6.2 (0.39 in attention)."""
    from chipbench.harness import catalog
    for name, total, attn in (('tfm_s1024', 7.4e12, 1.55e12),
                              ('tfm_s256', 6.2e12, 0.39e12)):
        cell = catalog.load_cell(name)
        t = cell['traffic']
        f = cell['flops'].forward_flops(cell['config']['model'], t['batch'],
                                        t['seq'])
        assert abs(3 * f['matmul'] - 5.85e12) < 0.03e12
        assert abs(3 * f['attention'] - attn) < 0.01e12
        step = cell['flops'].train_step_flops(cell['config'], t)
        assert abs(step - total) < 0.05e12
        flops, nbytes = cell['flops'].kernel_cost(cell['config'], t)
        assert flops == 3 * f['attention'] and nbytes > 0
    dp4 = catalog.load_cell('tfm_s1024_dp4')
    one = catalog.load_cell('tfm_s1024')
    # the same work a chip
    assert dp4['flops'].train_step_flops(dp4['config'], dp4['traffic']) \
        == 4 * one['flops'].train_step_flops(one['config'], one['traffic'])
    assert dp4['flops'].kernel_cost(dp4['config'], dp4['traffic'], 4) \
        == one['flops'].kernel_cost(one['config'], one['traffic'], 1)


def test_transformer_reference_agrees_with_its_program_in_float32():
    import paddle_tpu.fluid as fluid
    from chipbench.harness import catalog, check
    over = toy.toy_overrides('transformer_base')
    over['config']['amp'] = 'none'
    over['config']['checks']['amp']['tolerance'] = {'loss': 1e-5,
                                                    'grad': 1e-3}
    cell = catalog.load_cell('tfm_s256', overrides=over)
    with fluid.scope_guard(fluid.Scope()):
        built = cell['builder'].build(cell['config'], cell['traffic'])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        got = check.run_checks(cell, exe, fluid.global_scope(), seed=5)
        exe.close()
    assert list(got) == ['amp'] and got['amp']['passed'], got


def test_peaks_table_refuses_an_unknown_device():
    from chipbench.harness import peaks
    v5e = peaks.peaks_for('TPU v5 lite')
    assert v5e['bf16_flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9
    with pytest.raises(SystemExit):
        peaks.peaks_for('cpu')


STEADY_CASES = {
    # twenty equal steps: the step itself
    'equal': ([0.1] * 20, 0.1),
    # one step of twenty stalls for 0.1 s: left out, where the plain mean
    # would read 5% more
    'one_stall': ([0.1] * 19 + [0.2], 0.1),
    # every step a tenth slower: one for one
    'all_slower': ([0.11] * 20, 0.11),
    # a fifth of the steps slower, more than the trimmed tenth: it shows
    'a_fifth_slower': ([0.1] * 16 + [0.2] * 4, (14 * 0.1 + 2 * 0.2) / 16),
    # under ten steps nothing is left out
    'few': ([0.1, 0.2, 0.3], 0.2),
}


@pytest.mark.parametrize('case', sorted(STEADY_CASES))
def test_steady_step_is_the_mean_without_the_outer_tenths(case):
    from chipbench.harness import cell
    step_s, want = STEADY_CASES[case]
    rng = np.random.RandomState(0)
    assert cell.steady_step_s(rng.permutation(step_s)) == pytest.approx(want)


def test_steady_step_of_no_steps_is_none():
    from chipbench.harness import cell
    assert cell.steady_step_s([]) is None

