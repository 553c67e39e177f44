"""The per-layer metrics that read a step's device counters (PR 35):
`held_rows_x`, `held_blocks_layers`, `held_blocks_layers_window` and
`held_rows_x_peak`, on the traced toy cells of the five configurations
that hold a share of their experts (all five listed since PR 46), on a cell
that holds them all, and on step records made by hand.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

READERS = ['held_rows_x', 'held_blocks_layers', 'held_blocks_layers_window',
           'held_rows_x_peak']
# the held cells in the order BENCHMARK.json lists them, with their
# expert layers
HELD = {'qwen3next_s8192': 4, 'glm47flash_s8192': 5,
        'smallthinker_s16384': 4, 'nemotron3nano_s8192': 4,
        'lfm2_s16384': 4}
STEP_PARTS = ['prepare_ms', 'feed_place_ms', 'rng_ms', 'dispatch_ms',
              'step_self_ms', 'placement_ms']


@pytest.fixture(autouse=True)
def _fresh_obs():
    from paddle_tpu import obs
    obs._reset()
    yield
    obs._reset()


def _spec(everywhere):
    """The repository's BENCHMARK.json with the metrics named in
    `everywhere` listed for every cell."""
    spec = toy.repo_spec()
    for m in spec['per_layer']:
        if m['name'] in everywhere:
            m['workloads'] = [w['name'] for w in spec['workloads']]
    return spec


def test_the_entries_are_the_issues():
    entries = {m['name']: m for m in toy.repo_spec()['per_layer']}
    units = dict(zip(READERS, ['ratio', 'count', 'count', 'ratio']))
    for name in READERS:
        assert entries[name] == {
            'name': name, 'unit': units[name], 'better': 'lower',
            'source': 'program_counter', 'layer': 'Lowering rules',
            'moves': 'tokens_per_s', 'workloads': list(HELD)}
        assert os.path.exists(os.path.join(
            toy.REPO, 'chipbench', 'layers', name + '.py'))


@pytest.mark.parametrize('name', sorted(HELD))
def test_traced_held_toy_cell_reports_its_load_and_its_way(name, tmp_path):
    """The four readers give numbers on the traced line, read from the
    records of the window and of the traced steps; and with the read of
    the counters in place inside `executor.fetch` the step's host time
    still splits into its parts as it did."""
    from paddle_tpu import obs
    from chipbench.harness import cell as cell_runner
    line, summary, _ = toy.run_toy(name, tmp_path, traced=True,
                                   spec=_spec({'placement_ms'}))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    for m in READERS:
        assert m in got, m
    assert line['metrics']['held_rows_x']['unit'] == 'ratio'
    assert line['metrics']['held_blocks_layers']['unit'] == 'count'
    # at the toy widths every layer keeps all its rows, statically: each
    # step counts every expert layer of the cell
    assert got['held_blocks_layers'] == HELD[name]
    assert got['held_blocks_layers_window'] == HELD[name]
    assert 0 < got['held_rows_x'] <= got['held_rows_x_peak'] < 4
    # the same numbers from the records themselves
    steps = [r for r in obs.completed_spans() if r['name'] == 'executor.step'
             and 'device' in r['fields']]
    traced = steps[-cell_runner.TRACED_STEPS:]
    ratios = [e['rows'] / e['expected'] for r in traced
              for e in r['fields']['device']]
    assert got['held_rows_x'] == pytest.approx(sum(ratios) / len(ratios))
    used = steps[-(line['attempted'] + cell_runner.TRACED_STEPS):]
    assert got['held_rows_x_peak'] == max(
        e['rows'] / e['expected'] for r in used for e in r['fields']['device'])
    # the identity of PERF.md section 3, with the read in place
    assert sum(got[m] for m in STEP_PARTS) == pytest.approx(
        got['host_dispatch_ms'], rel=0.01)
    assert line['correct'] is True, summary['reference_check']


def test_a_cell_that_holds_every_expert_leaves_the_metrics_out(tmp_path):
    line, _, _ = toy.run_toy('olmoe_s4096', tmp_path, traced=True,
                             spec=_spec(set(READERS)))
    assert 'prepare_ms' in line['metrics']            # the spans were read
    for m in READERS:
        assert m not in line['metrics']


def _step(obs, key, device):
    with obs.span('executor.step') as sp:
        sp.fields['key'] = key
        with obs.span('executor.fetch'):
            if device is not None:
                sp.fields['device'] = device


def _entry(rows, way, cap=25600):
    return {'op': 'moe_mlp_7', 'rows': rows, 'expected': 2560.0, 'cap': cap,
            'way': way}


def _reading(obs, tmp_path, window, traced, first=None):
    """A first step, a window and the traced steps made by hand, each step
    with the `device` list given (None: a record without the field), and
    the reading the harness would hand a reader."""
    from chipbench.harness import catalog, cell as cell_runner
    assert len(traced) == cell_runner.TRACED_STEPS
    obs.enable(str(tmp_path / 'obs'))
    _step(obs, 'other', None)
    _step(obs, 'train', first)
    before = cell_runner._registry_snapshot()
    for device in window:
        _step(obs, 'train', device)
    registry = cell_runner._delta(cell_runner._registry_snapshot(), before)
    for device in traced:
        _step(obs, 'train', device)
    return {'cell': {'root': catalog.ROOT}, 'registry': registry,
            'window': {'attempted': len(window)}}


@pytest.mark.parametrize('case', ['fits', 'no_field', 'one_step_without',
                                  'count_is_off'])
def test_counter_readers_report_their_steps_or_nothing(case, tmp_path):
    """Window and traced steps are told apart by count from the end, as
    the span readers tell them; a program that keeps no `device` field (a
    parent of PR 35), a step without it, or a window that does not fit
    gives None from every reader."""
    from paddle_tpu import obs
    from chipbench.harness import catalog
    under = [_entry(2560, 'compact'), _entry(5120, 'compact')]
    over = [_entry(2560, 'compact'), _entry(25856, 'blocks')]
    window = [under] * 3 + [over]
    traced = [over] * 2 + [under] * 3
    if case == 'no_field':
        window, traced = [None] * 4, [None] * 5
    if case == 'one_step_without':
        window = window[:2] + [None] + window[3:]
    # the first step's load is no part of either selection
    reading = _reading(obs, tmp_path, window, traced,
                       first=None if case == 'no_field'
                       else [_entry(99999, 'blocks')] * 2)
    if case == 'count_is_off':
        reading['window']['attempted'] = 5
    read = {m: catalog.load_reader(m)(reading) for m in READERS}
    if case != 'fits':
        assert read == dict.fromkeys(READERS)
        return
    assert read['held_blocks_layers'] == 2 / 5
    assert read['held_blocks_layers_window'] == 1 / 4
    assert read['held_rows_x'] == pytest.approx(
        (2 * (1 + 10.1) + 3 * (1 + 2)) / 10)
    assert read['held_rows_x_peak'] == 25856 / 2560


def test_the_helper_says_it_is_no_metric():
    with open(os.path.join(toy.REPO, 'chipbench', 'layers',
                           'step_counter_window.py')) as f:
        text = f.read()
    assert text.startswith('"""Not a metric')
    assert "fields['device']" in text
    names = {m['name'] for m in toy.repo_spec()['per_layer']}
    assert 'step_counter_window' not in names
    assert json.dumps(toy.repo_spec())      # and the spec still parses
