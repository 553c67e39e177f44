"""The dry addition of a configuration: a copy of the benchmark plus the
files of addition/ (a configuration cut in depth, its builder, reference,
FLOP file with two kernels, a traffic kind with feeds of another shape, a
traffic mix, a cell, a toy width, two kernel readers) and the entries of
addition/BENCHMARK.entries.json appended to BENCHMARK.json is a benchmark
with one more runnable cell, and no file that was there is written. What
addition/ holds is what a `model_config` PR adds; addition/README.txt lists
it. In a file of its own so that another worker takes it.
"""
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

ADDITION = os.path.join(toy.HERE, 'addition')


def extended_spec():
    """The repository's BENCHMARK.json with the addition's entries appended:
    nothing that is there changes but the lists of cells of the metrics the
    new cell reports too."""
    spec = toy.repo_spec()
    with open(os.path.join(ADDITION, 'BENCHMARK.entries.json')) as f:
        entries = json.load(f)
    for section in ('configs', 'workloads', 'per_layer'):
        spec[section] += entries[section]
    cells = [w['name'] for w in entries['workloads']]
    for m in spec['end_to_end'] + spec['per_layer']:
        if m['name'] in entries['listed_in']:
            m['workloads'] += cells
    return spec, entries


@pytest.fixture(scope='module')
def extended(tmp_path_factory):
    """(checkout-like directory, its chipbench root, the extended spec, the
    entries, modification times before the addition)"""
    top = tmp_path_factory.mktemp('addition')
    root = toy.copy_benchmark(top)
    before = toy.modification_times(top)
    for tree in ('chipbench', 'tests'):
        for path in toy.modification_times(os.path.join(ADDITION, tree)):
            rel = os.path.relpath(path, ADDITION)
            # new files only: laying one over a file that is there is an
            # edit
            assert not os.path.exists(top / rel), rel
            shutil.copy(path, top / rel)
    spec, entries = extended_spec()
    with open(top / 'BENCHMARK.json', 'w') as f:
        json.dump(spec, f, indent=1)
    return top, root, spec, entries, before


def test_added_configuration_meets_the_contract(extended):
    from chipbench.harness import catalog, contract
    top, root, spec, entries, _ = extended
    contract.check(spec, str(top), root)
    config = entries['configs'][0]
    assert config['reduced'], 'the addition is a cut configuration'
    cell = catalog.load_cell(entries['workloads'][0]['name'], root=root)
    assert cell['config']['deployment'] and cell['config']['checks']
    # feeds of another shape than any cell's that is there
    feeds = set(cell['generator'].make_pool(
        dict(cell['traffic'], batch=2, pool=1), cell['config'], 1)[0][0])
    for name in toy.CELLS:
        other = toy.load_toy_cell(name)
        assert feeds != set(other['generator'].make_pool(
            other['traffic'], other['config'], 1)[0][0])
    # its FLOP file names two kernels, each with a reader pair
    cost = cell['flops'].kernel_cost(cell['config'], cell['traffic'])
    assert len(cost) == 2 and all(f > 0 and b > 0 for f, b in cost.values())
    assert {m['name'] for m in entries['per_layer']} == {
        'xent_kernel_ms', 'xent_kernel_roofline'}


def test_added_cell_runs_at_its_toy_width_and_nothing_there_is_written(
        extended, tmp_path):
    from chipbench.harness import catalog, peaks
    top, root, spec, entries, before = extended
    name = entries['workloads'][0]['name']
    # the spec is read from the copy's BENCHMARK.json, as run.py reads it
    line, summary, _ = toy.run_toy(name, tmp_path, traced=True, root=root)
    assert line['correct'] is True and line['failed'] == 0
    assert summary['unit'] == 'tokens' and summary['tokens_per_s'] > 0
    check = summary['reference_check']['amp']
    assert check['passed'] and check['loss_rel'] <= 1e-3
    assert set(check['grad_rel']) == {'tok_emb', 'fc_0.w_0'}
    assert summary['compiles_in_window'] == 0
    # the span and counter readers report on the host; the host's trace
    # has no device operation, so every kernel's metrics are left out
    for m in ('host_dispatch_ms', 'feed_mb_per_step', 'program_ops',
              'first_step_s', 'compiles_in_window'):
        assert m in line['metrics'], m
    for m in ('flash_ms', 'flash_roofline', 'xent_kernel_ms',
              'xent_kernel_roofline'):
        assert m not in line['metrics'], m
    # where the trace has kernels, each pair reads its own
    cell = toy.load_toy_cell(name, root)
    reading = {
        'trace': {'steps': 5, 'kernel_by_op_s': {
            'flash_attention': 4e-3, 'softmax_with_cross_entropy': 1e-3}},
        'peaks': peaks.PEAKS['TPU v5 lite'],
        'kernel_cost': cell['flops'].kernel_cost(cell['config'],
                                                 cell['traffic'])}
    got = {m['name']: catalog.load_reader(m['name'], root)(reading)
           for m in catalog.metrics_of(name, 'per_layer', root)
           if m['layer'] == 'Pallas kernels'}
    assert got['xent_kernel_ms'] == pytest.approx(0.2)
    assert got['flash_ms'] == pytest.approx(0.8)
    assert 0 < got['xent_kernel_roofline'] < 100
    assert 0 < got['flash_roofline'] < 100
    assert got['flash_roofline'] != got['xent_kernel_roofline']
    after = toy.modification_times(top)
    assert {p: t for p, t in after.items() if p in before} == before
    assert len(after) > len(before)
