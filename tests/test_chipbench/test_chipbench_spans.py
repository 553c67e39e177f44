"""The per-layer metrics that read the program's completed spans (PR 23):
the split of a step's host time and of the first call, on toy traced
cells on CPUPlace, and the selection of the window's records on spans
made by hand.
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

STEP_PARTS = ['prepare_ms', 'feed_place_ms', 'rng_ms', 'dispatch_ms',
              'step_self_ms', 'placement_ms']
OTHERS = ['gc_pause_ms', 'first_trace_s', 'first_backend_s']


@pytest.fixture(autouse=True)
def _fresh_obs():
    from paddle_tpu import obs
    obs._reset()
    yield
    obs._reset()


@pytest.mark.parametrize('name,tag', [('tfm_s256', ''),
                                      ('tfm_s1024_dp4', ''),
                                      ('resnet50_b256', '.img')])
def test_traced_toy_cell_splits_the_host_time_of_a_step(name, tag, tmp_path):
    with open(os.path.join(toy.REPO, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    listed = {m['name'] for m in spec['per_layer']
              if name in m.get('workloads', [name])}
    for m in STEP_PARTS[:-1] + OTHERS[:1]:
        assert m + tag in listed
    assert {'first_trace_s', 'first_backend_s'} <= listed
    assert ('placement_ms' in listed) == (name == 'tfm_s1024_dp4')
    # the placement's time is a metric under the mesh alone; the sum
    # below needs it in every cell, so this run reports it everywhere
    for m in spec['per_layer']:
        if m['name'] == 'placement_ms':
            m['workloads'] = [w['name'] for w in spec['workloads']]

    line, summary, _ = toy.run_toy(name, tmp_path, traced=True, spec=spec)
    got = {k[:-len(tag)] if tag and k.endswith(tag) else k: v['value']
           for k, v in line['metrics'].items()}
    for m in STEP_PARTS + OTHERS:
        assert m in got, m
        assert got[m] >= 0
    assert sum(got[m] for m in STEP_PARTS) == pytest.approx(
        got['host_dispatch_ms'], rel=0.01)
    for m in STEP_PARTS[:-1]:
        assert got[m] > 0
    if name == 'tfm_s1024_dp4':
        # under the mesh the placement walks every persistable each step
        assert got['placement_ms'] > 0.01
    assert 0 < got['first_trace_s'] and 0 < got['first_backend_s']
    assert got['first_trace_s'] + got['first_backend_s'] \
        < got['first_step_s']


def _step(obs, key, parts):
    with obs.span('executor.step') as sp:
        sp.fields['key'] = key
        with obs.span('executor.prepare'):
            with obs.span('executor.feed'):
                time.sleep(parts['feed'])
            time.sleep(parts['prepare'])
        with obs.span('executor.dispatch'):
            time.sleep(parts['dispatch'])
        with obs.span('executor.fetch'):
            pass


def _reading(obs, steps, tmp_path):
    """A window of `steps` hand-made steps between a first step and the
    traced ones, and the reading the harness would hand a reader."""
    from chipbench.harness import catalog, cell as cell_runner
    obs.enable(str(tmp_path / 'obs'))
    parts = {'feed': 0.002, 'prepare': 0.001, 'dispatch': 0.003}
    _step(obs, 'other', parts)             # another Program's step
    _step(obs, 'train', dict(parts, dispatch=0.05))      # the first step
    before = cell_runner._registry_snapshot()
    for _ in range(steps):
        _step(obs, 'train', parts)
    registry = cell_runner._delta(cell_runner._registry_snapshot(), before)
    for _ in range(cell_runner.TRACED_STEPS):
        _step(obs, 'train', dict(parts, dispatch=0.02))
    return {'cell': {'root': catalog.ROOT}, 'registry': registry,
            'window': {'attempted': steps}}


@pytest.mark.parametrize('case', ['fits', 'count_is_off', 'overflowed',
                                  'program_keeps_no_spans'])
def test_span_readers_report_the_window_or_nothing(case, tmp_path,
                                                   monkeypatch):
    """The window's records are found by count from the end and checked
    against the registry's sum; where the selection cannot be shown to be
    right every reader returns None, so the line leaves the metric out."""
    from paddle_tpu import obs
    from chipbench.harness import catalog
    if case == 'overflowed':
        monkeypatch.setattr(obs, 'SPAN_BUFFER_MAX', 40)
    reading = _reading(obs, 4, tmp_path)
    if case == 'count_is_off':
        reading['window']['attempted'] = 5
    if case == 'program_keeps_no_spans':      # a parent of PR 23
        monkeypatch.delattr(obs, 'completed_spans')
    read = {m: catalog.load_reader(m)(reading)
            for m in STEP_PARTS + OTHERS}
    if case != 'fits':
        assert read == dict.fromkeys(read)
        return
    # sleeps are lower bounds; the traced steps' 20 ms and the first
    # step's 50 ms of dispatch must not leak into the window's 3 ms
    assert 3.0 <= read['dispatch_ms'] < 15
    assert 2.0 <= read['feed_place_ms'] < 10
    assert 1.0 <= read['prepare_ms'] < 10      # its own time, feed out
    assert 0 <= read['step_self_ms'] < 5
    assert read['gc_pause_ms'] == 0
    # spans the steps never opened, and a first call without parts
    for m in ('rng_ms', 'placement_ms', 'first_trace_s', 'first_backend_s'):
        assert read[m] is None
    host = catalog.load_reader('host_dispatch_ms')(reading)
    assert sum(read[m] for m in ('prepare_ms', 'feed_place_ms',
                                 'dispatch_ms', 'step_self_ms')) \
        == pytest.approx(host, rel=1e-6)
