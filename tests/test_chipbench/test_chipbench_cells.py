"""Every cell of BENCHMARK.json runs end to end on CPUPlace at its toy width
through the benchmark's own loop, and the harness takes a new cell and a
new per-layer metric as files (the dry addition ISSUE 22 asks for; that of
a whole configuration is test_chipbench_addition.py).

These tests compile three small programs each (check Program, reference,
training step), which is what their seconds are; ResNet-50 keeps its 53
convolutions at any width and takes 20 s and more.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)


@pytest.mark.parametrize('name', toy.CELLS)
def test_cell_runs_end_to_end_at_toy_width(name, tmp_path):
    """Every cell of BENCHMARK.json, whatever its configuration: what
    differs (the rate's name, the checks, the chips) is read from the cell."""
    cell = toy.load_toy_cell(name)
    chips = cell['cell']['chips']
    rate = cell['generator'].UNIT + '_per_s'
    units = {m['name']: m['unit'] for m in toy.repo_spec()['end_to_end']}
    line, summary, _ = toy.run_toy(name, tmp_path)
    assert set(line) == toy.LAST_LINE_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] == summary['steps'] > 0
    assert set(line['metrics']) == {rate, 'setup_s'}
    assert line['metrics'][rate]['unit'] == units[rate]
    # the rate is the generator's count a step over the steady step time;
    # completed work over elapsed time stays on the summary line
    assert line['metrics'][rate]['value'] == pytest.approx(
        summary['units'] / summary['steps'] / summary['step_steady_s']
        / chips)
    assert summary['rate_total'] == pytest.approx(
        summary['units'] / summary['elapsed_s'] / chips)
    assert summary['outside_steady_pct'] < 100
    assert set(line['device']) == {'platform', 'kind', 'count',
                                   'memory_peak_bytes'}
    assert summary['compiles_in_window'] == 0
    # each of the configuration's checks ran and passed inside its own
    # tolerance, on a whole row of its sample for each of the cell's chips
    checks = summary['reference_check']
    assert sorted(checks) == sorted(cell['config']['checks'])
    for key, check in checks.items():
        assert check['passed'], (key, check)
        assert check['loss_rel'] <= check['tolerance']['loss']
        assert set(check['seconds']) == {'build', 'program',
                                         'read_parameters', 'reference'}
        assert check['sample'] % chips == 0
        assert check['sample'] >= cell['config']['checks'][key]['sample']
    # the generator's count, not the program's
    per_batch = summary['units_per_pool_batch']
    steps = summary['steps']
    want = sum(per_batch[i % len(per_batch)] for i in range(steps))
    assert summary['units'] == want


def test_dry_addition_of_a_cell_and_a_metric_needs_only_files(tmp_path):
    """A copy of chipbench/ plus one workload file, one traffic file and
    one reader, and their BENCHMARK.json entries: a new runnable cell and
    a new reported metric, with no existing file edited."""
    from chipbench.harness import catalog, contract
    root = toy.copy_benchmark(tmp_path)
    before = toy.modification_times(tmp_path)
    with open(os.path.join(root, 'workloads', 'tfm_s512.json'), 'w') as f:
        json.dump({'name': 'tfm_s512', 'config': 'transformer_base',
                   'traffic': 'seq2seq_b32_s512', 'chips': 1, 'mesh': None,
                   'loop': 'train_step', 'why': 'dry addition'}, f)
    with open(os.path.join(root, 'traffic', 'seq2seq_b32_s512.json'),
              'w') as f:
        json.dump({'kind': 'padded_seq2seq', 'batch': 32, 'seq': 512,
                   'pool': 8}, f)
    with open(os.path.join(root, 'layers', 'step_max_ms.py'), 'w') as f:
        f.write('def read(reading):\n'
                '    return 1e3 * max(reading["window"]["step_s"])\n')
    spec = catalog.benchmark_json()
    spec['workloads'].append({'name': 'tfm_s512',
                              'config': 'transformer_base',
                              'traffic': 'seq2seq_b32_s512', 'chips': 1,
                              'why': 'dry addition'})
    for m in spec['end_to_end'] + spec['per_layer']:
        if 'workloads' in m and 'tfm_s256' in m['workloads']:
            m['workloads'].append('tfm_s512')
    spec['per_layer'].append({
        'name': 'step_max_ms', 'unit': 'ms', 'better': 'lower',
        'source': 'host_clock', 'layer': 'Entry points',
        'moves': 'tokens_per_s', 'workloads': ['tfm_s512']})
    contract.check(spec, str(tmp_path), root)

    line, summary, _ = toy.run_toy('tfm_s512', tmp_path / 'work',
                                   traced=True, root=root, spec=spec)
    assert line['correct'] is True
    assert line['metrics']['step_max_ms']['value'] > 0
    # the readers that need no device trace report on the host too; the
    # trace's own metrics are left out where no device operation was seen
    for m in ('host_dispatch_ms', 'feed_mb_per_step', 'first_step_s',
              'compiles_in_window', 'program_ops'):
        assert m in line['metrics'], m
    assert 'tokens_per_s' not in line['metrics']       # a traced line
    assert line['metrics']['compiles_in_window']['value'] == 0
    # no pass pipeline by default: the Executor lowers what it is handed
    assert summary['pass_span'] is None
    assert line['metrics']['program_ops']['value'] > 100
    assert set(line) - toy.LAST_LINE_KEYS <= {'breakdown'}
    after = toy.modification_times(tmp_path)
    assert {p: t for p, t in after.items() if p in before} == before


def test_program_ops_counts_the_program_the_executor_lowers(tmp_path,
                                                            monkeypatch):
    """With the program's pass pipeline on, a dead op is in the Program
    the Executor is handed and not in the clone it lowers: the metric
    reads the clone, from the program's own `passes.optimize` span, so a
    pass that removes an op moves it."""
    import time
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from chipbench.harness import catalog, cell as cell_runner
    monkeypatch.setenv(cell_runner.OBS_ENV, str(tmp_path / 'obs'))
    monkeypatch.setenv('PADDLE_TPU_OPT', 'default')
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        kept = fluid.layers.scale(x, scale=2.0)
        fluid.layers.scale(x, scale=3.0)          # dead: nothing fetches it
        out = fluid.layers.mean(kept)
    read = catalog.load_reader('program_ops')
    assert cell_runner._pass_span(time.monotonic()) is None
    assert read({'pass_span': None, 'program_ops_handed': 3}) == 3
    since = time.monotonic()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                fetch_list=[out])
        exe.close()
    span = cell_runner._pass_span(since)
    handed = len(main.global_block().ops)
    assert span['ops_before'] == handed == 3
    assert read({'pass_span': span, 'program_ops_handed': handed}) \
        == handed - 1
    assert cell_runner._pass_span(time.monotonic()) is None


def test_device_rate_run_off_the_chip_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run(
        [sys.executable, os.path.join(toy.REPO, 'chipbench', 'run.py'),
         '--workload', 'tfm_s256', '--seed', '0', '--seconds', '1',
         '--trace', '0'],
        cwd=toy.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert 'no TPU' in p.stderr
    assert not any(l.startswith('{') for l in p.stdout.splitlines())
