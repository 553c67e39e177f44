"""What a decoder model's test file shares (tests/test_<model>.py, one a
configuration of chipbench/configs): the plain reference as a fresh
module, the cell at its toy width, the toy Program built outside a check,
and harness/check.py's comparison of the toy Program with the reference on
every trainable parameter.

The Program's side of that comparison (the initialised scope, the
trainable names, the loss and every gradient of one check entry at one
seed) is computed ONCE a process for a configuration: `check.run_check`
builds a new Program every call, the Executor's cache is keyed by the
Program, and a test that moves one function of the REFERENCE changes
nothing of the Program. Later calls hand `run_check` the recorded fetches
of the one real `Executor.run`; the verdict is still `run_check`'s own.
Not a test file."""
import json
import os
import sys

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, 'tests', 'test_chipbench'))


def reference_module(name):
    """chipbench/references/<name>.py as a fresh module: a test may move
    one of its functions and no other test sees it."""
    from chipbench.harness import catalog
    return catalog.load_module(catalog.ROOT, 'references', name)


def toy_cell(cell, **model):
    """The toy cell; `model` overrides keys of its model."""
    import chipbench_toy as toy
    cell = toy.load_toy_cell(cell)
    if model:
        cell = dict(cell, config=dict(
            cell['config'], model=dict(cell['config']['model'], **model)))
    return cell


def build_toy(cell, train):
    """(config, built): the toy Program in float32 with no gradient
    fetched, for training or as a check builds it."""
    config = dict(cell['config'], check={'grads': []}, amp='none')
    return config, cell['builder'].build(config, cell['traffic'],
                                         train=train)


class RecordedRun:
    """In the Executor's place before `check.run_check`: the fetches of
    the one real run, to a caller that asks for the same fetches of the
    same feed and to no other."""

    def __init__(self, feed, fetch_list, fetched):
        self.feed = {k: np.asarray(v) for k, v in feed.items()}
        self.fetch_names = _names(fetch_list)
        self.fetched = fetched

    def run(self, program, feed, fetch_list):
        assert _names(fetch_list) == self.fetch_names, (
            'not the recorded fetch list', _names(fetch_list))
        assert sorted(feed) == sorted(self.feed) and all(
            np.array_equal(np.asarray(feed[k]), v)
            for k, v in self.feed.items()), 'not the recorded feed'
        return list(self.fetched)


def _names(fetch_list):
    return [v if isinstance(v, str) else v.name for v in fetch_list]


class _Recording:
    """The real Executor, keeping what its one run was asked and gave."""

    def __init__(self, exe):
        self.exe, self.recorded = exe, None

    def run(self, program, feed, fetch_list):
        fetched = self.exe.run(program, feed=feed, fetch_list=fetch_list)
        self.recorded = RecordedRun(feed, fetch_list, fetched)
        return fetched


_STARTED = {}        # configuration -> (initialised scope, training build)
_RECORDED = {}       # (configuration, check entry, seed) -> RecordedRun


def _key(cell):
    return json.dumps([cell['config'], cell['traffic']], sort_keys=True)


def started(cell):
    """(scope, built): the toy cell's training Program built and its
    start-up program run, once a configuration. Both are shared: read the
    Program and the scope, do not train in them."""
    key = _key(cell)
    if key not in _STARTED:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            built = cell['builder'].build(cell['config'], cell['traffic'])
            fluid.Executor(fluid.CPUPlace()).run(built['startup'])
        _STARTED[key] = scope, built
    return _STARTED[key]


def one_step_hlo(cell, config, built):
    """`build_toy`'s Program started and run ONE step on a seeded batch,
    in a scope of its own: the step's optimized HLO (the name scopes in
    `op_name`; the lowering's counters rise on the way)."""
    pool, _ = cell['generator'].make_pool(dict(cell['traffic'], pool=1),
                                          config, 5)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        exe.run(built['main'], feed=pool[0], fetch_list=[built['loss']])
        return exe.lowered_hlo(built['main'], pool[0], [built['loss']],
                               optimized=True)


def rates_of_training(cell, steps):
    """The learning rate every Adam op read at each of `steps` training
    steps of the toy cell under the builder's own optimizer, in a scope of
    its own."""
    config = cell['config']
    with fluid.scope_guard(fluid.Scope()):
        built = cell['builder'].build(config, cell['traffic'])
        rate, = {op.input('LearningRate')[0]
                 for op in built['main'].global_block().ops
                 if op.type == 'adam'}
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        pool, _ = cell['generator'].make_pool(cell['traffic'], config, 3)
        got = [float(np.asarray(exe.run(
            built['main'], feed=pool[0],
            fetch_list=[built['loss'], rate])[1]).reshape(-1)[0])
            for _ in range(steps)]
        exe.close()
    return got


def check_all(cell, tolerance, seed=5, amp=None):
    """harness/check.py's comparison of the toy cell's Program with the
    plain reference (`cell['reference']`, which a test may have moved) on
    EVERY trainable parameter: (names, run_check's result)."""
    from chipbench.harness import check
    scope, built = started(cell)
    names = [v.name for v in built['main'].list_vars()
             if isinstance(v, framework.Parameter) and v.trainable]
    entry = dict(cell['config']['checks'][amp or 'float32'], grads=names,
                 tolerance=tolerance)
    key = _key(cell), amp or 'float32', seed
    with fluid.scope_guard(scope):
        if key in _RECORDED:
            return names, check.run_check(cell, _RECORDED[key], scope, seed,
                                          entry)
        exe = _Recording(fluid.Executor(fluid.CPUPlace()))
        got = check.run_check(cell, exe, scope, seed, entry)
        _RECORDED[key] = exe.recorded
        return names, got
