"""The Transformer cells run end to end on CPUPlace at a toy width through
the benchmark's own loop, and the harness takes a new cell and a new
per-layer metric as files (the dry addition ISSUE 22 asks for).

These tests compile three small programs each (check Program, reference,
training step), which is what their seconds are.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

TFM_CELLS = ['tfm_s1024', 'tfm_s256', 'tfm_s1024_dp4']


@pytest.mark.parametrize('name', TFM_CELLS)
def test_transformer_cell_runs_end_to_end_at_toy_width(name, tmp_path):
    line, summary, _ = toy.run_toy(name, tmp_path)
    assert set(line) == toy.LAST_LINE_KEYS
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] == summary['steps'] > 0
    assert set(line['metrics']) == {'tokens_per_s', 'setup_s'}
    assert line['metrics']['tokens_per_s']['unit'] == 'tokens/s/chip'
    # the rate is the generator's count a step over the steady step time;
    # completed work over elapsed time stays on the summary line
    chips = 4 if name == 'tfm_s1024_dp4' else 1
    assert line['metrics']['tokens_per_s']['value'] == pytest.approx(
        summary['units'] / summary['steps'] / summary['step_steady_s']
        / chips)
    assert summary['rate_total'] == pytest.approx(
        summary['units'] / summary['elapsed_s'] / chips)
    assert summary['outside_steady_pct'] < 100
    assert set(line['device']) == {'platform', 'kind', 'count',
                                   'memory_peak_bytes'}
    assert summary['compiles_in_window'] == 0
    check = summary['reference_check']['amp']
    assert check['passed'] and check['loss_rel'] < 1e-2
    assert set(check['seconds']) == {'build', 'program', 'read_parameters',
                                     'reference'}
    # the generator's count, not the program's
    per_batch = summary['units_per_pool_batch']
    steps = summary['steps']
    want = sum(per_batch[i % len(per_batch)] for i in range(steps))
    assert summary['units'] == want
    if name == 'tfm_s1024_dp4':
        # a whole row of the check's sample for each of the four chips
        assert check['sample'] == 4


def test_dry_addition_of_a_cell_and_a_metric_needs_only_files(tmp_path):
    """A copy of chipbench/ plus one workload file, one traffic file and
    one reader, and their BENCHMARK.json entries: a new runnable cell and
    a new reported metric, with no existing file edited."""
    from chipbench.harness import catalog
    root = str(tmp_path / 'chipbench')
    shutil.copytree(catalog.ROOT, root, ignore=shutil.ignore_patterns(
        '__pycache__', 'testdata'))
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(root) for p in fs}
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

    line, summary, _ = toy.run_toy('tfm_s512', tmp_path, traced=True,
                                   root=root, spec=spec)
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
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, fs in os.walk(root) for p in fs if p in before}
    assert after == before


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
