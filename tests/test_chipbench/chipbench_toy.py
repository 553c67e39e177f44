"""Shared by the chipbench tests: every cell of BENCHMARK.json shrunk to a
toy width (toy.json) and run on CPUPlace through the benchmark's own loop.
Not a test file (pytest collects test_chipbench_*.py)."""
import contextlib
import io
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
LAST_LINE_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def toy_overrides(config_name):
    with open(os.path.join(HERE, 'toy.json')) as f:
        return json.load(f)[config_name]


def load_toy_cell(name, root=None):
    from chipbench.harness import catalog
    root = root or catalog.ROOT
    config_name = catalog._json(root, 'workloads', name + '.json')['config']
    return catalog.load_cell(name, root=root,
                             overrides=toy_overrides(config_name))


def run_toy(name, tmp_path, traced=False, root=None, spec=None, seconds=0.3):
    """Runs the cell and returns (last line parsed, summary, stdout)."""
    import jax
    import paddle_tpu.fluid as fluid
    from chipbench import run
    from chipbench.harness import cell as cell_runner
    cell = load_toy_cell(name, root)
    with fluid.scope_guard(fluid.Scope()):
        result = cell_runner.run_cell(
            cell, seed=3, seconds=seconds, traced=traced,
            place=fluid.CPUPlace(), t_start=time.perf_counter(),
            work_dir=str(tmp_path),
            devices=jax.devices()[:cell['cell']['chips']], spec=spec)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.emit(result)
    lines = out.getvalue().strip().splitlines()
    assert lines[-2].startswith('summary ')
    return json.loads(lines[-1]), json.loads(lines[-2][8:]), lines
