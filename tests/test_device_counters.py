"""A step's device counters (PR 35): what a held share's expert layers did
with their data leaves the compiled step as one packed int32 vector and,
while observability is on, lands on the step's own `executor.step` record
and in the registry. On the toy widths of the two held configurations and
on one layer wide enough for the `lax.cond`, on CPUPlace.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid import framework, layers, unique_name
from paddle_tpu.fluid.ops_impl import moe_ops

from decoder_toy import REPO, toy_cell

HELD_CELLS = ['qwen3next_s8192', 'glm47flash_s8192']


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset()
    yield
    obs._reset()


def _step_records():
    return [r for r in obs.completed_spans() if r['name'] == 'executor.step']


def _registry(label):
    """[rows, compact layer-steps, blocks layer-steps] the registry holds
    for a held op: it outlives a test, so the tests take differences."""
    return [obs.counter('moe.held.rows', op=label).value] + [
        obs.counter('moe.held.layer_steps', op=label, way=way).value
        for way in ('compact', 'blocks')]


def _toy(name, seed=3):
    """(cell, built, pool, the moe ops) of a toy held cell, built in the
    current scope; the start-up Program has run."""
    cell = toy_cell(name)
    built = cell['builder'].build(cell['config'], cell['traffic'], train=True)
    pool, _ = cell['generator'].make_pool(cell['traffic'], cell['config'],
                                          seed)
    moes = [op for op in built['main'].global_block().ops
            if op.type == 'moe_mlp']
    return cell, built, pool, moes


def _reads(monkeypatch):
    """Counts the host reads of a step's counters: Executor._read_device
    is the one place that makes one."""
    calls = []
    read = executor_mod.Executor._read_device
    monkeypatch.setattr(
        executor_mod.Executor, '_read_device',
        lambda self, *a: (calls.append(1), read(self, *a))[1])
    return calls


@pytest.mark.parametrize('name', HELD_CELLS)
def test_every_step_record_carries_the_rows_the_step_counted(name, tmp_path):
    """With observability on, each `executor.step` record of the training
    key has `fields['device']`, an entry a held op in op order, whose
    `rows` are the sum of the held slice of the `ExpertCount` fetched in
    the same step; the facts beside it are the rule's own."""
    obs.enable(str(tmp_path))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        cell, built, pool, moes = _toy(name)
        exe.run(built['startup'])
        routed, (first, count) = cell['builder'].experts(cell['config'])
        fetch = [built['loss']] + [op.output('ExpertCount')[0]
                                   for op in moes]
        labels = ['moe_mlp_%d' % built['main'].global_block().ops.index(op)
                  for op in moes]
        before = [_registry(label) for label in labels]
        held_rows = np.zeros(len(moes), int)
        for i in range(3):
            counts = exe.run(built['main'], feed=pool[i % len(pool)],
                             fetch_list=fetch)[1:]
            rec = _step_records()[-1]
            device = rec['fields']['device']
            rows = [int(c[first:first + count].sum()) for c in counts]
            held_rows += rows
            assert [e['rows'] for e in device] == rows
            assert [e['op'] for e in device] == labels
            tokens = cell['traffic']['batch'] * cell['traffic']['seq']
            k = cell['config']['model']['num_experts_per_tok']
            for e in device:
                assert e['expected'] == tokens * k * count / routed
                # at the toy widths half the layer's rows (480 / 2) are
                # under one 256-row tile: no layout, the layer keeps them
                # all, statically
                assert e['cap'] is None and e['way'] == 'blocks'
        keys = {r['fields'].get('key') for r in _step_records()
                if 'device' in r['fields']}
        assert len(keys) == 1
    assert len(moes) == (4 if name == 'qwen3next_s8192' else 5)
    # the registry, for an operator: rows summed over steps, layer-steps
    # by the way they took
    for label, was, rows in zip(labels, before, held_rows):
        assert _registry(label) == [was[0] + rows, was[1], was[2] + 3]


# ------------------------------------------------- one layer with the cond

N, D, E, H, K = 4096, 16, 32, 12, 4
HELD = (6, 1)               # 1 of 32 held: a layout of the slack pays


def _build_layer(held):
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, count = layers.moe_mlp(
            x, num_experts=E, hidden_size=H, act='swish', gated=True,
            top_k=K, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held)
        loss = layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    return main, startup, loss, count


def _skewed_router():
    """Router weights that send every token of x > 0 to experts 6, 7, 0
    and 1, in that order."""
    router = np.zeros((D, E), 'float32')
    for j, e in enumerate((6, 7, 0, 1)):
        router[:, e] = 4.0 - j
    return router


def test_a_layer_under_its_layout_reads_compact(tmp_path):
    """One of 32 experts held over 4096 tokens x 4: 512 rows expected, a
    layout of 5120, chosen on the device. The router as initialised stays
    under it, and so does one that sends every token's first choice to
    the held expert (4096 rows): `compact` both times, with the rule's
    own `cap` and `expected` beside the count."""
    obs.enable(str(tmp_path))
    xs = np.abs(np.random.default_rng(4).normal(size=(N, D))
                ).astype('float32') + 0.1
    main, startup, loss, count = _build_layer(HELD)
    cap = moe_ops._held_cap(N * K, 1, E)
    assert cap == 5120 and 2 * cap <= N * K
    op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
    label = 'moe_mlp_%d' % main.global_block().ops.index(op)

    was = _registry(label)

    def ways():
        return [int(b - a) for a, b in zip(was[1:], _registry(label)[1:])]

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _, counts = exe.run(main, feed={'x': xs}, fetch_list=[loss, count])
        entry, = _step_records()[-1]['fields']['device']
        assert entry == {'op': label, 'rows': int(counts[6]),
                         'expected': N * K / E, 'cap': cap,
                         'way': 'compact'}
        assert ways() == [1, 0]
        fluid.global_scope().find_var('moe_mlp_0.w_0').get_tensor().set(
            _skewed_router(), fluid.CPUPlace())
        _, counts = exe.run(main, feed={'x': xs}, fetch_list=[loss, count])
        entry, = _step_records()[-1]['fields']['device']
        # every token's first choice: 4096 rows, still a layout's worth
        assert counts[6] == N and entry['rows'] == N
        assert entry['way'] == 'compact' and ways() == [2, 0]


def test_an_eighth_held_reads_the_layout_of_half_its_rows(tmp_path):
    """2 of 16 held over 2048 tokens x 2: ten times the expected 512 rows
    are more than all 4096, so the layout is half of them, 2048, chosen
    on the device: the entry reads that `cap`, `way` follows `rows <=
    cap` (the router as initialised: compact; every first choice and
    every second to the two held experts: 4096 rows, blocks), and
    tools/held_share.py counts such a layer among those that can go over
    their layout (its `static` is False: `layers_over_slack` counts the
    second step)."""
    obs.enable(str(tmp_path))
    tokens, experts, held = 2048, 16, (6, 2)
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with unique_name.guard(), framework.program_guard(main, startup):
        x = layers.data(name='x', shape=[D], dtype='float32')
        out, count = layers.moe_mlp(
            x, num_experts=experts, hidden_size=H, act='swish', gated=True,
            top_k=2, norm_topk_prob=True, capacity_factor=None,
            bias_attr=False, return_expert_count=True, experts_held=held)
        loss = layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    assert moe_ops._held_cap(tokens * 2, 2, experts) == 5120
    assert moe_ops._held_layout(tokens * 2, 2, experts) == 2048
    xs = np.abs(np.random.default_rng(4).normal(size=(tokens, D))
                ).astype('float32') + 0.1
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        entries = []
        for skewed in (False, True):
            if skewed:
                router = np.zeros((D, experts), 'float32')
                router[:, 6], router[:, 7] = 4.0, 3.0
                fluid.global_scope().find_var('moe_mlp_0.w_0').get_tensor(
                    ).set(router, fluid.CPUPlace())
            got, counts = exe.run(main, feed={'x': xs},
                                  fetch_list=[loss, count])
            assert np.isfinite(got).all()
            entry, = _step_records()[-1]['fields']['device']
            assert entry['rows'] == counts[6] + counts[7]
            assert entry['cap'] == 2048 and entry['expected'] == 512
            entries.append(entry)
    assert 0 < entries[0]['rows'] <= 2048 and entries[0]['way'] == 'compact'
    assert entries[1]['rows'] == 4096 and entries[1]['way'] == 'blocks'
    # the tool: no layer-step here is `static` (`cap` None), so the one
    # over its layout is counted as over the slack
    sys.path.insert(0, os.path.join(REPO, 'tools'))
    import held_share
    line = held_share.summary([[e] for e in entries], 2, experts)
    assert line['layers_over_slack'] == 1 and line['compact_share'] == 0.5
    assert line['layer_over_expected_max_by_step'][1] == 8.0


def test_the_way_read_is_the_way_taken_beyond_the_slack(tmp_path,
                                                        monkeypatch):
    """A slack of 4 lays out 2048 rows for the one held expert; the skewed
    router sends it 4096. The counter reads `blocks`, the registry counts
    a `blocks` layer-step a step, and the device did take `_held_blocks`:
    the compact path is poisoned here and the loss stays finite."""
    import jax.numpy as jnp
    obs.enable(str(tmp_path))
    monkeypatch.setattr(moe_ops, '_HELD_SLACK', 4)
    monkeypatch.setattr(
        moe_ops, '_compact_moe', lambda params, x, *_: jnp.full(
            (x.shape[0], params['w2'].shape[-1]), jnp.nan, jnp.float32))
    xs = np.abs(np.random.default_rng(4).normal(size=(N, D))
                ).astype('float32') + 0.1
    main, startup, loss, count = _build_layer(HELD)
    op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
    label = 'moe_mlp_%d' % main.global_block().ops.index(op)
    was = _registry(label)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.global_scope().find_var('moe_mlp_0.w_0').get_tensor().set(
            _skewed_router(), fluid.CPUPlace())
        for step in (1, 2):
            got, counts = exe.run(main, feed={'x': xs},
                                  fetch_list=[loss, count])
            entry, = _step_records()[-1]['fields']['device']
            assert entry['cap'] == 2048 and entry['rows'] == N == counts[6]
            assert entry['way'] == 'blocks'
            assert _registry(label) == [was[0] + step * N, was[1],
                                        was[2] + step]
            assert np.isfinite(got).all()


@pytest.mark.parametrize('name', HELD_CELLS)
def test_off_reads_nothing_and_lowers_the_same_module(name, tmp_path,
                                                      monkeypatch):
    """With observability off the vector is never read: no call of
    Executor._read_device, no `device` field, no registry count; and the
    step lowers to the same HLO text on and off, so one executable and
    one compile-cache entry serve the traced run and the untraced one."""
    def _held_metrics():
        return [m for m in obs.REGISTRY.snapshot()
                if m['name'].startswith('moe.held.')]

    calls = _reads(monkeypatch)
    texts = []
    held = _held_metrics()
    for on in (False, True):
        obs._reset()
        if on:
            obs.enable(str(tmp_path))
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            _, built, pool, moes = _toy(name)
            exe.run(built['startup'])
            for i in range(2):
                exe.run(built['main'], feed=pool[i], fetch_list=[built['loss']])
            texts.append(exe.lowered_hlo(built['main'], pool[0],
                                         [built['loss']]))
            compiled = [c for c in exe._cache.values() if c.counters]
            assert len(compiled) == 1
            assert len(compiled[0].counters) == len(moes)
        assert len(calls) == (2 if on else 0)
        if not on:
            assert obs.completed_spans() == []
            assert held == _held_metrics()
    assert texts[0] == texts[1]
    # the module hands the host one more result: the counters, int32
    assert 'xi32>' in texts[0].split('func.func public @main')[1] \
        .split('\n')[0].split('->')[1]


def test_async_and_a_pinned_handle_leave_the_vector_unread(tmp_path,
                                                           monkeypatch):
    """sync='async' returns before the step is done and StepHandle.step
    hands back raw device fetches: neither blocks, so neither reads."""
    calls = _reads(monkeypatch)
    obs.enable(str(tmp_path))
    xs = np.ones((64, D), 'float32')
    main, startup, loss, _ = _build_layer((8, 8))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out, = exe.run(main, feed={'x': xs}, fetch_list=[loss], sync='async')
        assert np.isfinite(np.asarray(out)).all()
        assert 'device' not in _step_records()[-1]['fields']
        handle = exe.acquire_step(main, feed={'x': xs}, fetch_list=[loss])
        import jax.numpy as jnp
        handle.step({'x': jnp.asarray(xs)})
        assert not calls
        exe.run(main, feed={'x': xs}, fetch_list=[loss])
        assert len(calls) == 1


def test_a_bundle_records_a_list_a_step(tmp_path):
    """run_bundle reads the [K, counters] once and records through the
    same function: the bundle's record carries K lists, the registry K
    layer-steps."""
    obs.enable(str(tmp_path))
    xs = np.ones((64, D), 'float32')
    main, startup, loss, count = _build_layer((8, 8))
    op = [o for o in main.global_block().ops if o.type == 'moe_mlp'][0]
    label = 'moe_mlp_%d' % main.global_block().ops.index(op)
    was = _registry(label)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _, counts = exe.run_bundle(main, feeds=[{'x': xs}] * 3,
                                   fetch_list=[loss, count])
    rec = [r for r in obs.completed_spans()
           if r['name'] == 'executor.bundle'][-1]
    device = rec['fields']['device']
    assert [[e['rows'] for e in step] for step in device] == [
        [int(c[8:16].sum())] for c in counts]
    assert _registry(label)[2] == was[2] + 3


def _hlo_of(build):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        main, startup, loss, feed = build()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        compiled = [c for c in exe._cache.values() if c.ad_idx is not None]
        return exe.lowered_hlo(main, feed, [loss]), compiled[0]


def test_a_program_without_a_share_returns_nothing_more():
    """No `experts_held`: the step declares no counter, its module has the
    results it had (the fetches and the written persistables, nothing of
    int32 beside them), and a trained dropless layer's text does not
    mention a counter's reduction."""
    def whole():
        main, startup, loss, _ = _build_layer(None)
        return main, startup, loss, {'x': np.ones((64, D), 'float32')}

    def held():
        main, startup, loss, _ = _build_layer((8, 8))
        return main, startup, loss, {'x': np.ones((64, D), 'float32')}

    def results(text):
        head = text.split('func.func public @main')[1].split('\n')[0]
        return head.split('->')[1]

    text, compiled = _hlo_of(whole)
    assert compiled.counters == [] and compiled.counter_facts == {}
    text_held, compiled_held = _hlo_of(held)
    assert len(compiled_held.counters) == 1
    assert compiled_held.counter_facts
    # the share's module has ONE result more, a tensor<1xi32>
    assert results(text_held).count('tensor<') \
        == results(text).count('tensor<') + 1
    assert 'tensor<1xi32>' in results(text_held)
    assert 'xi32>' not in results(text)


def test_toy_cell_without_a_share_lowers_as_it_did():
    """`olmoe_s4096`'s toy: every expert held, so no counter, and the
    step's call returns None in the counters' place."""
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        cell = toy_cell('olmoe_s4096')
        built = cell['builder'].build(cell['config'], cell['traffic'],
                                      train=True)
        exe.run(built['startup'])
        pool, _ = cell['generator'].make_pool(cell['traffic'],
                                              cell['config'], 3)
        exe.run(built['main'], feed=pool[0], fetch_list=[built['loss']])
        compiled, = [c for c in exe._cache.values() if c.ad_idx is not None]
    assert compiled.counters == []
    assert any(op.type == 'moe_mlp' for op in compiled.ops)
