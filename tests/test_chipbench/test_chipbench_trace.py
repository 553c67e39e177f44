"""The reduction from a trace to numbers: interval arithmetic on hand-built
cases, the scope parser and the HLO join, and the whole reduction on a
small trace recorded on the chip (chipbench/testdata/).
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

from chipbench.harness import intervals as iv  # noqa: E402
from chipbench.harness import scopes, trace  # noqa: E402

TESTDATA = os.path.join(toy.REPO, 'chipbench', 'testdata')


def test_union_of_overlapping_nested_and_touching_intervals():
    got = iv.union([(5, 9), (0, 2), (1, 3), (6, 7), (9, 10), (20, 20)])
    assert got == [(0, 3), (5, 10)]
    assert iv.total(got) == 8
    assert iv.clip(got, 2, 6) == [(2, 3), (5, 6)]
    assert iv.gaps(got, 0, 12) == [(3, 5), (10, 12)]
    assert iv.gaps([], 0, 4) == [(0, 4)]


def test_subtract():
    a = [(0, 10), (20, 30)]
    assert iv.subtract(a, []) == a
    assert iv.subtract(a, [(0, 10)]) == [(20, 30)]
    assert iv.subtract(a, [(2, 4), (8, 22), (29, 40)]) == [
        (0, 2), (4, 8), (22, 29)]


def test_self_times_of_nested_events():
    events = [(0, 100, 'while'), (10, 30, 'a'), (40, 90, 'b'),
              (50, 60, 'c'), (100, 110, 'd')]
    got = dict(iv.self_times(events))
    assert got == {'while': 30, 'a': 20, 'b': 40, 'c': 10, 'd': 10}
    assert sum(got.values()) == iv.total(iv.union((s, e)
                                                  for s, e, _ in events))


def _raw(device_events, spans=(), second_device=None):
    devices = {'/device:TPU:0': device_events}
    if second_device is not None:
        devices['/device:TPU:1'] = second_device
    return {'devices': devices, 'spans': list(spans)}


def test_reduce_busy_idle_scopes_and_gap_attribution():
    instr = {'fusion.1': 'jit(step)/jvp(mul_3)/dot_general',
             'fusion.2': 'jit(step)/transpose(jvp(mul_3))/dot_general',
             'fusion.3': 'jit(step)/adam_7/sub',
             'copy.4': ''}
    instr['custom.5'] = 'jit(step)/jvp(fused_attention_9)/pallas_call'
    events = [(100, 200, 'fusion.1', False), (150, 260, 'fusion.2', False),
              (400, 500, 'fusion.3', False), (600, 700, 'custom.5', True)]
    spans = [(0, 1000, trace.WINDOW_SPAN), (0, 990, 'chipbench.step'),
             (0, 90, 'executor.lowering'), (705, 980, 'executor.fetch')]
    red = trace.reduce(_raw(events, spans), instr, steps=2)
    assert red['window_s'] == pytest.approx(1000e-9)
    # union, not sum: the two overlapping fusions cover 160 ns
    assert red['busy0_s'] == pytest.approx((160 + 100 + 100) * 1e-9)
    assert red['busy_s'] == red['busy0_s']
    assert red['fluid_op_s']['adam'] == pytest.approx(100e-9)
    assert red['fluid_op_s']['fused_attention'] == pytest.approx(100e-9)
    assert red['kernel_s'] == pytest.approx(100e-9)
    assert red['kernel_by_op_s'] == {
        'fused_attention': pytest.approx(100e-9)}
    assert red['fluid_scope_s']['mul_3'] == pytest.approx(
        red['fluid_op_s']['mul'])
    # idle 640 ns: 100 before the first op under the lowering span and
    # the step, 300 at the end mostly under the blocking fetch
    idle = red['idle_by_span_s']
    assert sum(idle.values()) == pytest.approx(640e-9)
    assert idle['executor.fetch'] == pytest.approx(300e-9)
    assert idle['executor.lowering'] == pytest.approx(100e-9)
    assert idle['chipbench.step'] == pytest.approx(240e-9)
    assert red['longest_gaps'][0] == ('executor.fetch',
                                      pytest.approx(300e-9))
    out = trace.breakdown(red)
    assert set(out) == {'device_ops', 'idle_gaps'}
    assert out['device_ops'][0][0] == 'mul'
    assert len(out['device_ops']) <= 10 and len(out['idle_gaps']) <= 10


def test_reduce_tells_the_kernels_of_two_op_types_apart():
    """Mosaic events under scopes of two op types, and one outside any
    scope: each under its own key, their union under kernel_s; an event
    that is no kernel is in neither."""
    instr = {'custom.1': 'jit(step)/jvp(flash_attention_4)/pallas_call',
             'custom.2': 'jit(step)/transpose(jvp(flash_attention_4))/'
                         'pallas_call',
             'custom.3': 'jit(step)/moe_mlp_9/pallas_call',
             'custom.4': 'jit(step)/pallas_call',
             'fusion.5': 'jit(step)/moe_mlp_9/dot_general'}
    events = [(0, 100, 'custom.1', True), (100, 250, 'custom.2', True),
              (300, 340, 'custom.3', True), (400, 407, 'custom.4', True),
              (500, 900, 'fusion.5', False)]
    red = trace.reduce(_raw(events), instr, steps=1)
    assert red['kernel_by_op_s'] == {
        'flash_attention': pytest.approx(250e-9),
        'moe_mlp': pytest.approx(40e-9),
        'unattributed': pytest.approx(7e-9)}
    assert red['kernel_s'] == pytest.approx(297e-9)
    assert red['fluid_op_s']['moe_mlp'] == pytest.approx(440e-9)
    # the readers of one kernel take its entry and its cost, and return
    # nothing for a kernel the trace or the FLOP file does not have
    from chipbench.harness import catalog, kernels, peaks
    v5e = peaks.PEAKS['TPU v5 lite']
    reading = {'trace': red, 'peaks': v5e, 'kernel_cost': {
        'flash_attention': (197e12 * 50e-9, 1.0),      # flops-bound, 50 ns
        'moe_mlp': (1.0, 819e9 * 10e-9)}}              # bytes-bound, 10 ns
    assert catalog.load_reader('flash_ms')(reading) == pytest.approx(250e-6)
    assert catalog.load_reader('flash_roofline')(reading) \
        == pytest.approx(20.0)
    assert kernels.ms(reading, 'moe_mlp') == pytest.approx(40e-6)
    assert kernels.roofline_pct(reading, 'moe_mlp') == pytest.approx(25.0)
    assert kernels.ms(reading, 'sparse_adam') is None
    assert kernels.roofline_pct(reading, 'sparse_adam') is None
    assert kernels.roofline_pct(dict(reading, kernel_cost=None),
                                'moe_mlp') is None
    assert kernels.ms(dict(reading, trace=None), 'moe_mlp') is None


@pytest.mark.parametrize('op_name, callee', [
    ('jit(step)/jvp(flash_attention_4)/pallas_call', ''),
    ('jit(step)/moe_mlp_9/jvp(jit(gmm))/pallas_call', 'gmm'),
    ('jit(step)/moe_mlp_9/transpose(moe_mlp_9)/jvp(jit(tgmm))/pallas_call',
     'tgmm'),
    # a layer's paths are one jitted function a step (`_held_paths`), a
    # recompute region wraps it: the innermost function is the kernel's
    ('jit(step)/checkpoint/jit(_held_paths)/moe_mlp_3/jit(gmm)/pallas_call',
     'gmm'),
    ('jit(step)/jit(_held_paths)/moe_mlp_3/pallas_call', '_held_paths'),
    ('jit(step)', ''), ('', '')])
def test_callee_of_is_the_innermost_function_after_the_steps_own(op_name,
                                                                 callee):
    assert scopes.callee_of(op_name) == callee


def test_grouped_matmul_readers_take_the_megablox_kernels_alone():
    """Three kinds of Mosaic events in `moe_mlp` scopes: megablox's pair,
    a kernel the rule calls itself and one in a jitted function of its
    own (PR 45's row add was read as grouped matmul: ledger, PR 45). The
    op's reading holds them all; `grouped_matmul_ms` and
    `grouped_matmul_roofline` the first two."""
    from chipbench.harness import catalog, kernels, peaks
    instr = {'custom.1': 'jit(step)/jvp(moe_mlp_9)/jit(gmm)/pallas_call',
             'custom.2': 'jit(step)/transpose(jvp(moe_mlp_9))/jit(gmm)/'
                         'pallas_call',
             'custom.3': 'jit(step)/transpose(jvp(moe_mlp_9))/jit(tgmm)/'
                         'pallas_call',
             'custom.4': 'jit(step)/jvp(moe_mlp_9)/pallas_call',
             'custom.5': 'jit(step)/jvp(moe_mlp_9)/jit(row_add)/pallas_call',
             'custom.6': 'jit(step)/jvp(flash_attention_2)/pallas_call'}
    events = [(0, 100, 'custom.1', True), (100, 250, 'custom.2', True),
              (300, 350, 'custom.3', True), (400, 430, 'custom.4', True),
              (500, 570, 'custom.5', True), (600, 700, 'custom.6', True)]
    red = trace.reduce(_raw(events), instr, steps=2)
    assert red['kernel_by_op_s']['moe_mlp'] == pytest.approx(400e-9)
    assert red['kernel_by_callee_s'] == {
        'moe_mlp': {'gmm': pytest.approx(250e-9),
                    'tgmm': pytest.approx(50e-9), '': pytest.approx(30e-9),
                    'row_add': pytest.approx(70e-9)},
        'flash_attention': {'': pytest.approx(100e-9)}}
    # the parts of an op add up to the op
    for op, by in red['kernel_by_callee_s'].items():
        assert sum(by.values()) == pytest.approx(red['kernel_by_op_s'][op])
    reading = {'trace': red, 'peaks': peaks.PEAKS['TPU v5 lite'],
               'kernel_cost': {'moe_mlp': (1.0, 819e9 * 15e-9)}}
    assert kernels.ms(reading, 'moe_mlp') == pytest.approx(200e-6)
    assert catalog.load_reader('grouped_matmul_ms')(reading) \
        == pytest.approx(150e-6)
    assert catalog.load_reader('grouped_matmul_roofline')(reading) \
        == pytest.approx(10.0)
    assert kernels.ms(reading, 'moe_mlp', ('row_add',)) \
        == pytest.approx(35e-6)
    # nothing of that name in the trace: nothing to read, never 0
    assert kernels.ms(reading, 'moe_mlp', ('ragged_dot',)) is None
    assert kernels.roofline_pct(reading, 'flash_attention',
                                kernels.MEGABLOX) is None
    # without the megablox pair the two readers are silent
    only = trace.reduce(_raw(events[3:]), instr, steps=2)
    assert catalog.load_reader('grouped_matmul_ms')(
        dict(reading, trace=only)) is None


def test_reduce_exposed_collective_time_half_under_compute():
    events = [(0, 100, 'fusion.1', False),
              (50, 52, 'all-reduce-start.1', False),
              (148, 150, 'all-reduce-done.1', False),
              (300, 340, 'all-gather.2', False),       # all exposed
              (400, 500, 'fusion.2', False)]
    raw = _raw(events, second_device=[(0, 50, 'fusion.1', False)])
    # the async pair's span, start to done: half of it under fusion.1
    raw['async'] = {'/device:TPU:0': [(50, 150, 'all-reduce-start.1'),
                                      (10, 20, 'copy-start.7')]}
    red = trace.reduce(raw, {}, steps=1)
    assert red['collective_s'] == pytest.approx(140e-9)
    assert red['collective_exposed_s'] == pytest.approx(90e-9)
    # no window span in the trace: the devices' own extent
    assert red['window_s'] == pytest.approx(500e-9)
    assert red['busy_s_by_device']['/device:TPU:1'] == pytest.approx(50e-9)
    # busy is what ran on the op queue: the pair counts by its two ends
    assert red["busy_s"] == pytest.approx((242 + 50) / 2 * 1e-9)


def test_reduce_returns_nothing_without_device_events():
    assert trace.reduce({'devices': {}, 'spans': []}, {}, 5) is None
    assert trace.reduce(_raw([]), {}, 5) is None


def test_scope_parser_and_hlo_join():
    assert scopes.scope_of('jit(step)/jvp(mul_3)/dot_general') == ('mul', 3)
    assert scopes.scope_of('jit(step)/while_5/mul_3/add') == ('mul', 3)
    assert scopes.scope_of('jit(step)/transpose(jvp(layer_norm_12))/sub') \
        == ('layer_norm', 12)
    assert scopes.scope_of('jit(step)/convert_element_type') is None
    hlo = '''
HloModule jit_step
%fused_computation.1 (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  ROOT %mul.7 = bf16[8,4]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/elementwise_mul_4/mul" source_file="x.py"}
}
ENTRY %main {
  %fusion.1 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/elementwise_mul_4/mul"}
  %all-reduce-start.3 = f32[4]{0} all-reduce-start(%g), replica_groups={}
  %all-reduce-done.3 = f32[4]{0} all-reduce-done(%all-reduce-start.3)
  %all-gather.9 = f32[16]{0} all-gather(%g), dimensions={0}
  %custom-call.2 = bf16[8,4]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_attention_2/pallas_call"}
}
'''
    instr = scopes.instruction_scopes(hlo)
    assert instr['fusion.1'] == 'jit(step)/elementwise_mul_4/mul'
    assert instr['mul.7'] == instr['fusion.1']
    text = ('%fusion.1 = bf16[8,4]{1,0:T(8,128)} fusion(bf16[8,4] %a), '
            'kind=kLoop, calls=%fused_computation.1')
    assert trace.instruction_name(text) == 'fusion.1'
    assert scopes.scope_of(instr[trace.instruction_name(text)]) == (
        'elementwise_mul', 4)
    assert scopes.collective_counts(hlo) == {
        'all-gather': 1, 'all-reduce': 1, 'reduce-scatter': 0,
        'all-to-all': 0, 'collective-permute': 0}
    assert scopes.is_collective('all-reduce-done.3')
    assert not scopes.is_collective('fusion.1')


def _recorded(name):
    raw = trace.read_xplane(os.path.join(TESTDATA, name + '.xplane.pb'))
    with open(os.path.join(TESTDATA, name + '_instr.json')) as f:
        return raw, json.load(f)


# flash_ms over the recorded traces as the reader of PR 24 returned it
# from the one kernel_s, computed once on that commit
PARENT_FLASH_MS = {'toy_tfm': 0.1401352, 'toy_dp4': 0.035394}


@pytest.mark.parametrize('name', sorted(PARENT_FLASH_MS))
def test_kernels_of_the_recorded_traces_all_lie_in_flash_scopes(name):
    from chipbench.harness import catalog
    raw, instr = _recorded(name)
    red = trace.reduce(raw, instr, steps=5)
    by_op = red['kernel_by_op_s']
    assert list(by_op) == ['flash_attention']
    # to the nanosecond
    assert round(sum(by_op.values()) * 1e9) == round(red['kernel_s'] * 1e9)
    assert catalog.load_reader('flash_ms')({'trace': red}) \
        == PARENT_FLASH_MS[name]


def test_reduction_of_the_trace_recorded_on_four_chips():
    raw, instr = _recorded('toy_dp4')
    assert sorted(raw['devices']) == ['/device:TPU:%d' % i for i in range(4)]
    assert {s[2] for s in raw['spans']} == {
        'chipbench.traced_steps', 'chipbench.step', 'executor.step',
        'executor.fetch'}
    red = trace.reduce(raw, instr, steps=5)
    assert red['window_s'] == pytest.approx(0.047110655)
    assert red['busy0_s'] == pytest.approx(0.001197449)
    assert red['busy_s'] == pytest.approx(
        sum(red['busy_s_by_device'].values()) / 4)
    assert red['busy_s'] == pytest.approx(0.0011919375)
    # a toy step keeps the chip busy 2.5% of the time: the rest is host,
    # and all of it inside the program's executor.step span
    assert red['idle_by_span_s'] == {
        'executor.step': pytest.approx(0.045913206)}
    assert red['longest_gaps'][0] == ('executor.step',
                                      pytest.approx(0.007232075))
    # three Mosaic calls for each of three attention ops, five steps
    kernels = [e for e in raw['devices']['/device:TPU:0'] if e[3]]
    assert len(kernels) == 3 * 3 * 5
    assert red['kernel_s'] == pytest.approx(0.00017697)
    # two all-reduces a step, none of it under a compute operation here
    coll = [e for e in raw['devices']['/device:TPU:0']
            if scopes.is_collective(e[2])]
    assert len(coll) == 2 * 5
    assert red['collective_s'] == pytest.approx(0.000293258)
    assert red['collective_exposed_s'] == pytest.approx(0.000293258)
    # the join with the compiled module's metadata names the Fluid ops
    ops = red['fluid_op_s']
    assert ops['flash_attention'] == pytest.approx(0.000204954)
    assert ops['adam'] == pytest.approx(0.000128632)
    assert ops['unattributed'] < 0.07 * red['busy0_s']
    assert sum(ops.values()) == pytest.approx(red['busy0_s'])
    assert sum(red['fluid_scope_s'].values()) == pytest.approx(
        red['busy0_s'] - ops['unattributed'])
    # a chip's share of the step's FLOPs over the chips' mean busy time
    from chipbench.harness import catalog, peaks
    assert catalog.load_reader('mfu_pct')({
        'trace': red, 'chips': 4, 'peaks': peaks.PEAKS['TPU v5 lite'],
        'step_flops': 4e10}) == pytest.approx(
            100 * 1e10 / (0.0011919375 / 5) / 197e12)
    out = trace.breakdown(red)
    assert out['idle_gaps'] == [['executor.step',
                                 pytest.approx(0.045913206)]]
    assert len(out['device_ops']) == 10


def test_reduction_of_the_trace_recorded_on_one_chip():
    raw, instr = _recorded('toy_tfm')
    assert list(raw['devices']) == ['/device:TPU:0']
    assert len(raw['async']['/device:TPU:0']) > 0     # copies, start to done
    red = trace.reduce(raw, instr, steps=5)
    assert red['window_s'] == pytest.approx(0.028119658)
    assert red['busy_s'] == red['busy0_s'] == pytest.approx(0.002548091)
    assert red['kernel_s'] == pytest.approx(0.000700676)
    assert red['collective_s'] == red['collective_exposed_s'] == 0.0
    ops = red['fluid_op_s']
    assert max(ops, key=ops.get) == 'flash_attention'
    assert ops['flash_attention'] == pytest.approx(0.000765417)
    assert ops['mul'] == pytest.approx(0.000670585)
    assert ops['unattributed'] < 0.07 * red['busy0_s']
    # the kernels' own events are inside the flash_attention scopes, which
    # also hold the transposes and masks around them
    assert red['kernel_s'] < ops['flash_attention']
    assert red['idle_by_span_s'] == {
        'executor.step': pytest.approx(0.025571567)}
    # the per-layer readers on this reduction
    from chipbench.harness import catalog, peaks
    reading = {'trace': red, 'chips': 1, 'peaks': peaks.PEAKS['TPU v5 lite'],
               'kernel_cost': {'flash_attention': (
                   3 * 4 * 8 * 256 * 256 * 128 * 2.5, 1e6)},
               'step_flops': 1e10,
               'window': {'step_s': [0.0051, 0.0049, 0.0050, 0.0080]}}
    assert catalog.load_reader('flash_ms')(reading) == pytest.approx(
        1e3 * 0.000700676 / 5)
    # idle over the measured window's median step, not the traced steps'
    assert catalog.load_reader('device_idle_pct')(reading) == pytest.approx(
        100 * (1 - 0.002548091 / 5 / 0.00505))
    assert catalog.load_reader('device_idle_pct')(
        dict(reading, window={'step_s': []})) is None
    assert catalog.load_reader('optimizer_ms')(reading) == pytest.approx(
        1e3 * ops['adam'] / 5)
    assert catalog.load_reader('loss_head_ms')(reading) > 0
    assert 0 < catalog.load_reader('flash_roofline')(reading) < 100
    assert catalog.load_reader('collective_exposed_ms')(reading) is None
    # MFU over the device's busy time, not the wall clock: FLOPs of a
    # step over busy seconds of a step over the peak
    assert catalog.load_reader('mfu_pct')(reading) == pytest.approx(
        100 * 1e10 / (0.002548091 / 5) / 197e12)
    assert catalog.load_reader('mfu_pct')(dict(reading, trace=None)) is None
    assert catalog.load_reader('device_idle_pct.img')(reading) \
        == catalog.load_reader('device_idle_pct')(reading)
