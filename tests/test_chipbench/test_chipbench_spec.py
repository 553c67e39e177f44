"""BENCHMARK.json against the contract, the traffic generators, the FLOP
functions and the Transformer's reference: everything that needs no
compiled training step.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)


@pytest.fixture(scope='module')
def spec():
    return toy.repo_spec()


def test_benchmark_json_meets_the_contract(spec):
    from chipbench.harness import contract
    assert os.path.getsize(os.path.join(toy.REPO, 'BENCHMARK.json')) < 65536
    contract.check_spec(spec)


def test_every_named_thing_has_its_file(spec):
    """Cells, configurations with their toy widths, traffic mixes and
    per-layer metrics are found by the names BENCHMARK.json gives; files
    are named from a name's characters."""
    from chipbench.harness import catalog, contract
    contract.check_files(spec, toy.REPO, catalog.ROOT)


def _cut(**changes):
    """A configuration cut in depth, as BENCHMARK.json's entry and as its
    file: what a model larger than one chip looks like."""
    held = {'model': {'n_layer': 1, 'd_model': 2048, 'num_experts': 64},
            'reduced': ['n_layer'], 'reduced_from': {'n_layer': 16},
            'deployment': 'one of 16 layers on one chip, all 64 experts'}
    listed = changes.pop('listed', None)
    held.update(changes)
    return {'name': 'cut', 'reduced': held['reduced'] if listed is None
            else listed}, held


def test_a_configuration_cut_in_depth_is_legal():
    from chipbench.harness import contract
    contract.check_reduced(*_cut())
    contract.check_reduced(*_cut(
        reduced=['n_layer', 'num_experts'],
        reduced_from={'n_layer': 16, 'num_experts': 256}))
    # a key of the source's config that the model here leaves out
    contract.check_reduced(*_cut(reduced=['mtp_layers'],
                                 reduced_from={'mtp_layers': 1}))


BAD_REDUCED = {
    'not_a_key': (dict(reduced=['n_layer 16 -> 1'],
                       reduced_from={'n_layer 16 -> 1': 16}), 'a name'),
    'file_differs': (dict(listed=[]), "not BENCHMARK.json's"),
    'no_source_value': (dict(reduced_from=None), 'reduced_from'),
    'source_value_of_another_key': (
        dict(reduced_from={'n_layer': 16, 'num_experts': 256}),
        'reduced_from'),
    'not_changed': (dict(reduced_from={'n_layer': 1}), "equals the source's"),
    'no_deployment': (dict(deployment=' '), 'deployment'),
    'listed_twice': (dict(reduced=['n_layer', 'n_layer']), 'distinct'),
}


@pytest.mark.parametrize('case', sorted(BAD_REDUCED))
def test_a_bad_reduced_entry_is_refused(case):
    from chipbench.harness import contract
    changes, message = BAD_REDUCED[case]
    with pytest.raises(contract.ContractError, match=message):
        contract.check_reduced(*_cut(**changes))


@pytest.mark.parametrize('key', [
    'd_model', 'hidden_size', 'moe_intermediate_size', 'head_dim',
    'kv_lora_rank', 'num_experts_per_tok', 'sliding_window',
    'ssm_state_size', 'expand', 'stage_width', 'q_proj_size'])
def test_a_width_in_reduced_is_refused(key):
    from chipbench.harness import contract
    assert contract.names_a_width(key)
    with pytest.raises(contract.ContractError, match='width'):
        contract.check_reduced(*_cut(reduced=[key], reduced_from={key: 1}))


@pytest.mark.parametrize('key', [
    'n_layer', 'num_hidden_layers', 'num_attention_heads',
    'num_key_value_heads', 'num_experts', 'n_routed_experts', 'vocab_size',
    'depth'])
def test_depth_and_counts_may_be_cut(key):
    from chipbench.harness import contract
    assert not contract.names_a_width(key)


def test_an_accepted_cell_may_not_go_and_additions_keep_the_chip_share(spec):
    import copy
    from chipbench.harness import contract
    gone = copy.deepcopy(spec)
    gone['workloads'] = [w for w in gone['workloads']
                         if w['name'] != 'tfm_s256']
    for m in gone['end_to_end'] + gone['per_layer']:
        if 'workloads' in m:
            m['workloads'] = [w for w in m['workloads'] if w != 'tfm_s256']
    with pytest.raises(contract.ContractError, match='accepted cell'):
        contract.check_spec(gone)
    # a fifth cell is legal; a second one on four chips is not yet
    more = copy.deepcopy(spec)
    more['workloads'].append(dict(more['workloads'][0], name='fifth',
                                  traffic='another'))
    for m in more['end_to_end'] + more['per_layer']:
        if 'workloads' in m and more['workloads'][0]['name'] in m['workloads']:
            m['workloads'].append('fifth')
    contract.check_spec(more)
    more['workloads'][-1]['chips'] = 4
    with pytest.raises(contract.ContractError, match='four chips'):
        contract.check_spec(more)


def test_a_configuration_without_its_toy_file_is_refused(spec, tmp_path):
    """The contract names the missing file, and so does the toy run of a
    cell of that configuration."""
    import shutil
    from chipbench.harness import catalog, contract
    os.symlink(catalog.ROOT, tmp_path / 'chipbench')
    toys = tmp_path / 'tests' / 'test_chipbench' / 'toy'
    shutil.copytree(toy.toy_dir(), toys)
    root = str(tmp_path / 'chipbench')
    contract.check_files(spec, str(tmp_path), root)
    config = spec['configs'][-1]['name']
    cell = [w['name'] for w in spec['workloads'] if w['config'] == config][0]
    os.remove(toys / (config + '.json'))
    missing = os.path.join('toy', config + '.json')
    with pytest.raises(contract.ContractError, match=missing):
        contract.check_files(spec, str(tmp_path), root)
    with pytest.raises(FileNotFoundError, match=missing):
        toy.load_toy_cell(cell, root=root)


@pytest.mark.parametrize('name', toy.CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    cell = toy.load_toy_cell(name)
    gen, traffic, config = cell['generator'], cell['traffic'], cell['config']
    a, units_a = gen.make_pool(traffic, config, 11)
    b, units_b = gen.make_pool(traffic, config, 11)
    c, _ = gen.make_pool(traffic, config, 12)
    assert len(a) == traffic['pool'] and units_a == units_b
    for x, y in zip(a, b):
        assert set(x) == set(y)
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert any(not np.array_equal(a[0][k], c[0][k]) for k in a[0])
    # the count the rate is made of equals a recount from the arrays
    assert units_a == [gen.recount(x) for x in a]
    for x in a:
        assert all(v.shape[0] == traffic['batch'] for v in x.values())


def test_padded_seq2seq_shapes_pads_and_shift():
    cell = toy.load_toy_cell('tfm_s256')
    traffic = dict(cell['traffic'], batch=64, seq=32, pool=4)
    pool, units = cell['generator'].make_pool(traffic, cell['config'], 1)
    vocab = cell['config']['model']['trg_vocab']
    fill = []
    for batch in pool:
        src, trg, lbl = (batch[k] for k in ('src_word', 'trg_word',
                                            'lbl_word'))
        assert src.dtype == trg.dtype == lbl.dtype == np.int64
        for ids in (src, trg, lbl):
            lengths = (ids != 0).sum(1)
            assert lengths.min() >= 16 and lengths.max() <= 32
            # ids up to the length, pads after it
            assert all((row[:n] > 0).all() and (row[n:] == 0).all()
                       for row, n in zip(ids, lengths))
            assert ids.max() < vocab
        # the label is the target shifted by one
        assert np.array_equal(lbl[:, :15], trg[:, 1:16])
        assert np.array_equal((lbl != 0).sum(1), (trg != 0).sum(1))
        fill.append(((src != 0).mean() + (trg != 0).mean()) / 2)
    assert 0.7 < np.mean(fill) < 0.8            # mean fill 75%
    assert sum(units) == sum((b['src_word'] != 0).sum()
                             + (b['trg_word'] != 0).sum() for b in pool)


def test_transformer_flops_meet_the_cross_checks():
    """ISSUE 22: 16 x 1024 needs about 7.4 TFLOP a step (5.85 in weight
    matmuls, 1.55 in attention), 64 x 256 about 6.2 (0.39 in attention)."""
    from chipbench.harness import catalog
    for name, total, attn in (('tfm_s1024', 7.4e12, 1.55e12),
                              ('tfm_s256', 6.2e12, 0.39e12)):
        cell = catalog.load_cell(name)
        t = cell['traffic']
        f = cell['flops'].forward_flops(cell['config']['model'], t['batch'],
                                        t['seq'])
        assert abs(3 * f['matmul'] - 5.85e12) < 0.03e12
        assert abs(3 * f['attention'] - attn) < 0.01e12
        step = cell['flops'].train_step_flops(cell['config'], t)
        assert abs(step - total) < 0.05e12
        cost = cell['flops'].kernel_cost(cell['config'], t)
        assert list(cost) == ['flash_attention']
        flops, nbytes = cost['flash_attention']
        assert flops == 3 * f['attention'] and nbytes > 0
    dp4 = catalog.load_cell('tfm_s1024_dp4')
    one = catalog.load_cell('tfm_s1024')
    # the same work a chip
    assert dp4['flops'].train_step_flops(dp4['config'], dp4['traffic']) \
        == 4 * one['flops'].train_step_flops(one['config'], one['traffic'])
    assert dp4['flops'].kernel_cost(dp4['config'], dp4['traffic'], 4) \
        == one['flops'].kernel_cost(one['config'], one['traffic'], 1)


def test_transformer_reference_agrees_with_its_program_in_float32():
    import paddle_tpu.fluid as fluid
    from chipbench.harness import catalog, check
    over = toy.toy_overrides('transformer_base')
    over['config']['amp'] = 'none'
    over['config']['checks']['amp']['tolerance'] = {'loss': 1e-5,
                                                    'grad': 1e-3}
    cell = catalog.load_cell('tfm_s256', overrides=over)
    with fluid.scope_guard(fluid.Scope()):
        built = cell['builder'].build(cell['config'], cell['traffic'])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        got = check.run_checks(cell, exe, fluid.global_scope(), seed=5)
        exe.close()
    assert list(got) == ['amp'] and got['amp']['passed'], got


def test_peaks_table_refuses_an_unknown_device():
    from chipbench.harness import peaks
    v5e = peaks.peaks_for('TPU v5 lite')
    assert v5e['bf16_flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9
    with pytest.raises(SystemExit):
        peaks.peaks_for('cpu')


STEADY_CASES = {
    # twenty equal steps: the step itself
    'equal': ([0.1] * 20, 0.1),
    # one step of twenty stalls for 0.1 s: left out, where the plain mean
    # would read 5% more
    'one_stall': ([0.1] * 19 + [0.2], 0.1),
    # every step a tenth slower: one for one
    'all_slower': ([0.11] * 20, 0.11),
    # a fifth of the steps slower, more than the trimmed tenth: it shows
    'a_fifth_slower': ([0.1] * 16 + [0.2] * 4, (14 * 0.1 + 2 * 0.2) / 16),
    # under ten steps nothing is left out
    'few': ([0.1, 0.2, 0.3], 0.2),
}


@pytest.mark.parametrize('case', sorted(STEADY_CASES))
def test_steady_step_is_the_mean_without_the_outer_tenths(case):
    from chipbench.harness import cell
    step_s, want = STEADY_CASES[case]
    rng = np.random.RandomState(0)
    assert cell.steady_step_s(rng.permutation(step_s)) == pytest.approx(want)


def test_steady_step_of_no_steps_is_none():
    from chipbench.harness import cell
    assert cell.steady_step_s([]) is None

