"""Shared by the chipbench tests: every cell of BENCHMARK.json shrunk to a
toy width (toy/<config>.json, one file a configuration) and run on CPUPlace
through the benchmark's own loop. Not a test file (pytest collects
test_chipbench_*.py)."""
import contextlib
import io
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
LAST_LINE_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def repo_spec():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


# the cells of the repository's BENCHMARK.json, read when the tests are
# collected: a cell a later PR appends is a new case of every test
# parametrised over them, with no edit here
CELLS = [w['name'] for w in repo_spec()['workloads']]


def toy_dir(root=None):
    """The toy widths beside `root` (a chipbench directory of a checkout
    or of a copy laid out as one); the repository's own by default."""
    from chipbench.harness import contract
    return os.path.join(os.path.dirname(root) if root else REPO,
                        contract.TOY_DIR)


def toy_overrides(config_name, root=None):
    path = os.path.join(toy_dir(root), config_name + '.json')
    if not os.path.exists(path):
        raise FileNotFoundError(
            'configuration %r has no toy width for the CPU tests: missing '
            'file %s' % (config_name, path))
    with open(path) as f:
        return json.load(f)


def copy_benchmark(to):
    """Copies the benchmark into directory `to`, laid out as a checkout:
    chipbench/ and the toy widths (the dry additions add files to it).
    Returns the copy's chipbench root."""
    from chipbench.harness import catalog
    root = os.path.join(str(to), 'chipbench')
    shutil.copytree(catalog.ROOT, root, ignore=shutil.ignore_patterns(
        '__pycache__', 'testdata'))
    shutil.copytree(toy_dir(), toy_dir(root))
    return root


def modification_times(top):
    return {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
            for d, _, files in os.walk(str(top)) for f in files}


def load_toy_cell(name, root=None):
    from chipbench.harness import catalog
    config_name = catalog._json(root or catalog.ROOT, 'workloads',
                                name + '.json')['config']
    return catalog.load_cell(name, root=root or catalog.ROOT,
                             overrides=toy_overrides(config_name, root))


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
