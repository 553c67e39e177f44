"""The readers of the program's `executor.feed_wait` span (PR 51):
`feed_wait_ms`, `fetch_wait_ms`, `feed_gbps` and `feed_wait_traced_ms`
over completed-span records made by hand, so every value is computed by
hand too, and on toy traced cells on CPUPlace, where the two waits and the
host's dispatch must add up to the step.
"""
import itertools
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

READERS = ['feed_wait_ms', 'fetch_wait_ms', 'feed_gbps',
           'feed_wait_traced_ms']
ENTRIES = ['feed_wait_ms', 'feed_wait_ms.img', 'fetch_wait_ms',
           'fetch_wait_ms.img', 'feed_gbps.img', 'feed_wait_traced_ms.img']
NBYTES = 150000000
# a step's parts before its fetch, seconds: the prepare span opens with
# the step, the feed span 1 ms into it, the fetch 4 ms after the step
FEED_AT, FETCH_AT, AFTER_FETCH = 0.001, 0.004, 0.001


@pytest.fixture(autouse=True)
def _fresh_obs():
    from paddle_tpu import obs
    obs._reset()
    yield
    obs._reset()


class _Records(object):
    """Span records as paddle_tpu.obs keeps them, made by hand."""

    def __init__(self):
        self.out, self.ids, self.now = [], itertools.count(1), 100.0

    def span(self, name, parent, t0, t1, **fields):
        rec = {'ts': t1, 'kind': 'span', 'name': name,
               'span': next(self.ids), 'parent': parent, 't0': t0, 't1': t1,
               'dur_s': t1 - t0, 'fields': fields}
        self.out.append(rec)
        return rec['span']

    def step(self, key, wait_s, ready, device_s, with_wait=True):
        """One `executor.step` as Executor.run records it; returns its
        seconds. `wait_s` is the feed's wait, `device_s` what the fetch
        waits for after it."""
        t = self.now
        fetch_t0 = t + FETCH_AT
        fetch_t1 = fetch_t0 + wait_s + device_s
        t1 = fetch_t1 + AFTER_FETCH
        step = next(self.ids)
        prepare = self.span('executor.prepare', step, t, t + 0.003,
                            cache='hit')
        self.span('executor.feed', prepare, t + FEED_AT, t + 0.003,
                  bytes=NBYTES)
        self.span('executor.dispatch', step, t + 0.003, fetch_t0)
        fetch = self.span('executor.fetch', step, fetch_t0, fetch_t1,
                          sync='auto')
        if with_wait:
            self.span('executor.feed_wait', fetch, fetch_t0,
                      fetch_t0 + wait_s, bytes=NBYTES, ready=ready)
        self.out.append({'ts': t1, 'kind': 'span', 'name': 'executor.step',
                         'span': step, 'parent': None, 't0': t, 't1': t1,
                         'dur_s': t1 - t, 'fields': {'key': key}})
        self.now = t1 + 0.0005
        return t1 - t


def _reading(monkeypatch, window, traced_wait_s=0.5, with_wait=True):
    """The reading the harness would hand a reader after another
    Program's step, the first step, a window of the steps `window`
    ([(wait_s, ready)]) and the traced steps."""
    from paddle_tpu import obs
    from chipbench.harness import catalog, cell as cell_runner
    recs = _Records()
    recs.step('other', 0.0, True, 0.01, with_wait)
    recs.step('train', 0.2, False, 0.9, with_wait)       # the first step
    seconds = [recs.step('train', wait_s, ready, 0.12, with_wait)
               for wait_s, ready in window]
    for _ in range(cell_runner.TRACED_STEPS):
        recs.step('train', traced_wait_s, False, 0.12, with_wait)
    monkeypatch.setattr(obs, 'completed_spans', lambda: list(recs.out))
    return {'cell': {'root': catalog.ROOT},
            'registry': {'executor.step': {'count': len(window),
                                           'sum': sum(seconds)}},
            'window': {'attempted': len(window)}}


def _read(reading):
    from chipbench.harness import catalog
    return {m: catalog.load_reader(m)(reading) for m in READERS}


def test_steps_that_waited_give_both_waits_and_the_rate(monkeypatch):
    read = _read(_reading(monkeypatch, [(0.030, False)] * 4))
    assert read['feed_wait_ms'] == pytest.approx(30.0)
    assert read['fetch_wait_ms'] == pytest.approx(120.0)
    # from the feed span's start, 3 ms before the fetch, to the wait's end
    assert read['feed_gbps'] == pytest.approx(
        1e-9 * NBYTES / (FETCH_AT - FEED_AT + 0.030))


def test_the_rate_is_over_the_steps_whose_feed_had_not_landed(monkeypatch):
    window = [(0.030, False), (0.050, False), (0.0, True), (0.0001, True)]
    read = _read(_reading(monkeypatch, window))
    assert read['feed_wait_ms'] == pytest.approx((30 + 50 + 0 + 0.1) / 4)
    assert read['fetch_wait_ms'] == pytest.approx(120.0)
    assert read['feed_gbps'] == pytest.approx(
        1e-9 * NBYTES * (1 / 0.033 + 1 / 0.053) / 2)


def test_a_feed_that_always_landed_in_time_has_no_rate(monkeypatch):
    read = _read(_reading(monkeypatch, [(0.00002, True)] * 4,
                          traced_wait_s=0.00002))
    assert read['feed_gbps'] is None
    assert read['feed_wait_ms'] == pytest.approx(0.02)
    assert read['fetch_wait_ms'] == pytest.approx(120.0)
    assert read['feed_wait_traced_ms'] == pytest.approx(0.02)


def test_the_traced_reader_takes_the_traced_steps_not_the_window(
        monkeypatch):
    read = _read(_reading(monkeypatch, [(0.030, False)] * 7,
                          traced_wait_s=0.55))
    assert read['feed_wait_traced_ms'] == pytest.approx(550.0)
    assert read['feed_wait_ms'] == pytest.approx(30.0)


@pytest.mark.parametrize('case', ['program_records_no_feed_wait',
                                  'count_is_off', 'program_keeps_no_spans',
                                  'buffer_overflowed'])
def test_the_readers_give_nothing_rather_than_a_wrong_number(case,
                                                             monkeypatch):
    """The parent's program opens no `executor.feed_wait`: all four return
    None, `fetch_wait_ms` too, whose span the parent does record but
    there covers both waits. So does a selection that cannot be shown to
    be the window's."""
    from paddle_tpu import obs
    reading = _reading(monkeypatch, [(0.030, False)] * 4,
                       with_wait=case != 'program_records_no_feed_wait')
    if case == 'count_is_off':
        reading['window']['attempted'] = 5
    if case == 'program_keeps_no_spans':
        monkeypatch.delattr(obs, 'completed_spans')
    if case == 'buffer_overflowed':
        kept = obs.completed_spans()
        monkeypatch.setattr(obs, 'completed_spans', lambda: [
            {'kind': 'meta', 'name': 'spans.dropped', 'span': None,
             'fields': {'dropped': 3}}] + kept)
    assert _read(reading) == dict.fromkeys(READERS)


@pytest.mark.parametrize('metric', ENTRIES)
def test_new_entry_names_a_reader_and_cells_that_exist(metric):
    from chipbench.harness import catalog
    spec = toy.repo_spec()
    entry, = [m for m in spec['per_layer'] if m['name'] == metric]
    assert callable(catalog.load_reader(metric))
    assert os.path.exists(os.path.join(
        catalog.ROOT, 'layers', metric.split('.')[0] + '.py'))
    moves, = [m for m in spec['end_to_end'] if m['name'] == entry['moves']]
    assert entry['workloads'] and set(entry['workloads']) <= set(toy.CELLS)
    assert set(entry['workloads']) <= set(moves['workloads'])
    twin, = [m for m in spec['per_layer'] if m['name'] == (
        'feed_place_ms.img' if metric.endswith('.img') else 'feed_place_ms')]
    # the cells whose line has the feed's placement have its wait too
    assert set(entry['workloads']) <= set(twin['workloads'])
    assert set(entry['workloads']) >= set(twin['workloads']) & {
        'tfm_s256', 'tfm_s1024_dp4', 'resnet50_b256'}
    assert (entry['layer'], entry['source']) == ('Entry points',
                                                 'program_span')


@pytest.mark.parametrize('name,tag', [('tfm_s256', ''),
                                      ('resnet50_b256', '.img')])
def test_traced_toy_cell_splits_the_step_into_dispatch_and_two_waits(
        name, tag, tmp_path, monkeypatch):
    """`host_dispatch_ms` + `feed_wait_ms` + `fetch_wait_ms` is the step:
    the registry's `executor.step` over the window, which the program
    feeds whether its child spans exist or not."""
    from chipbench.harness import catalog
    seen, load_reader = {}, catalog.load_reader

    def spy(metric, root=catalog.ROOT):
        def read(reading):
            seen['registry'] = reading['registry']
            return load_reader(metric, root)(reading)
        return read

    monkeypatch.setattr(catalog, 'load_reader', spy)
    line, _, _ = toy.run_toy(name, tmp_path, traced=True)
    got = {k[:-len(tag)] if tag and k.endswith(tag) else k: v['value']
           for k, v in line['metrics'].items()}
    assert got['feed_wait_ms'] >= 0 and got['fetch_wait_ms'] > 0
    step = seen['registry']['executor.step']
    assert got['host_dispatch_ms'] + got['feed_wait_ms'] \
        + got['fetch_wait_ms'] == pytest.approx(
            1e3 * step['sum'] / step['count'], rel=1e-6)
    assert ('feed_wait_traced_ms' in got) == (name == 'resnet50_b256')
    assert 'feed_gbps' not in got or name == 'resnet50_b256'
