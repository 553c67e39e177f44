"""The learning-rate schedule of the cells that hold a share of their
experts (ISSUE 46): all five name the same linear warm-up, ONE function
(builders/adam.py) builds it into each builder's training Program, the
check Programs carry none of it, and a configuration that names no
schedule keeps its constant with no op added.

At a constant 4e-4 from step 0 Adam collapses the routers of a held share
within the window and the held rows become the seed's draw (PERF.md
section 6, PRs 37 and 46): a cell that is to judge later PRs cannot spread
so, and these tests hold the cure in place. Toy widths on the host; the
warm-up is the published files' own (toy/ lays no optimizer over it).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)
from chipbench.harness import catalog  # noqa: E402

HELD = {'qwen3next_s8192': 'qwen3_next_80b_a3b',
        'glm47flash_s8192': 'glm_4_7_flash',
        'smallthinker_s16384': 'smallthinker_21b_a3b',
        'nemotron3nano_s8192': 'nemotron_3_nano_30b_a3b',
        'lfm2_s16384': 'lfm2_8b_a1b'}
# what noam_decay builds: the step counter and its increment, the two
# branches and their minimum
SCHEDULE_OPS = {'increment', 'elementwise_pow', 'elementwise_min'}
PEAK, WARMUP = 4e-4, 2000


def _types(program):
    return [op.type for op in program.global_block().ops]


def _rates(program):
    """The names of the variables the Program's adam ops take their rate
    from."""
    return {name for op in program.global_block().ops if op.type == 'adam'
            for name in op.input('LearningRate')}


def test_the_held_cells_are_the_benchmarks():
    """Every cell whose configuration holds a share is in HELD, so a held
    cell a later PR adds is a case here or fails this test."""
    held = set()
    for name in toy.CELLS:
        cell = toy.load_toy_cell(name)
        experts = getattr(cell['builder'], 'experts', None)
        if experts and experts(cell['config'])[1] is not None:
            held.add(name)
            assert HELD[name] == cell['config']['name']
    assert held == set(HELD)


@pytest.mark.parametrize('name', sorted(HELD))
def test_a_held_cell_trains_through_the_warm_up_and_checks_without(name):
    import paddle_tpu.fluid as fluid
    published = catalog.load_cell(name)['config']['optimizer']
    assert published == {
        'kind': 'adam', 'beta1': 0.9, 'beta2': 0.95, 'epsilon': 1e-08,
        'learning_rate': PEAK, 'schedule': 'linear_warmup',
        'warmup_steps': WARMUP}
    cell = toy.load_toy_cell(name)
    config, traffic = cell['config'], cell['traffic']
    assert config['optimizer'] == published        # the toy takes the keys
    assert 'optimizer' in config['assumed']

    built = cell['builder'].build(config, traffic, train=True)
    main = built['main']
    types = _types(main)
    assert SCHEDULE_OPS <= set(types)
    assert types.count('increment') == 1           # one counter, one schedule
    # every parameter's Adam reads ONE rate, and it is a variable an op of
    # the Program writes: the schedule's, no constant of the startup's
    rates = _rates(main)
    assert len(rates) == 1
    rate, = rates
    written = {n for op in main.global_block().ops
               for n in op.output_arg_names}
    assert rate in written
    assert types.count('adam') == len(
        [p for p in main.global_block().all_parameters() if p.trainable])

    # the check Programs: no optimizer, no counter, nothing that moves
    for entry in config['checks'].values():
        check = cell['builder'].build(dict(config, check=entry), traffic,
                                      train=False)
        assert not (SCHEDULE_OPS | {'adam'}) & set(_types(check['main']))

    # the first steps run at peak x step / warmup
    pool, _ = cell['generator'].make_pool(traffic, config, 3)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        got = [float(np.asarray(exe.run(
            main, feed=pool[i % len(pool)],
            fetch_list=[main.global_block().var(rate)])[0]).reshape(-1)[0])
            for i in range(3)]
        exe.close()
    assert got == pytest.approx([PEAK * step / WARMUP for step in (1, 2, 3)],
                                rel=1e-5)


def test_a_configuration_without_a_schedule_keeps_its_constant():
    """olmoe_1b_7b holds every expert and names no schedule: the shared
    function hands Adam the float, and the Program gains no op."""
    from chipbench.builders import adam
    cell = toy.load_toy_cell('olmoe_s4096')
    opt = cell['config']['optimizer']
    assert 'schedule' not in opt and 'warmup_steps' not in opt
    assert adam.learning_rate(opt) == opt['learning_rate'] == PEAK
    built = cell['builder'].build(cell['config'], cell['traffic'],
                                  train=True)
    types = _types(built['main'])
    assert not SCHEDULE_OPS & set(types)
    # the constant is a variable the startup Program fills, which no op
    # of the main Program writes
    rate, = _rates(built['main'])
    assert rate not in {n for op in built['main'].global_block().ops
                        for n in op.output_arg_names}
    with pytest.raises(ValueError, match='cosine'):
        adam.learning_rate(dict(opt, schedule='cosine'))


def test_no_builder_keeps_a_copy_of_the_schedule():
    """One function builds the rate: the builders call it, and none names
    the scheduler or an optimizer class itself."""
    root = os.path.join(catalog.ROOT, 'builders')
    users = set()
    for name in sorted(os.listdir(root)):
        if not name.endswith('.py') or name == 'adam.py':
            continue
        with open(os.path.join(root, name)) as f:
            text = f.read()
        if 'from chipbench.builders.adam import adam' in text:
            users.add(name[:-3])
            assert 'noam_decay' not in text, name
            assert 'optimizer.Adam' not in text, name
    configs = {catalog.load_cell(n)['config']['builder'] for n in HELD}
    assert configs <= users and 'olmoe' in users
