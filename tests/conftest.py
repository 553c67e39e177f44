"""Force a deterministic 8-virtual-device CPU platform for all tests."""
import jax

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)

# ---------------------------------------------------------------------------
# Tier-1 is the driver's command: `pytest tests/ -m "not slow" -n 6 --dist
# loadfile` under `timeout 1470` (/root/TESTS_LAST_RUN.json holds it whole),
# and a run that is cut counts only as far as it got. Its last readings on
# the driver's host: 1233 s at PR 56; this PR's own run of it, PERF.md
# section 6, PR 57. A file goes to ONE worker, so no run is shorter than its
# longest file, and six balanced workers leave only the cases' seconds to
# cut: ROADMAP.md Design 14 has them by file.
#
# `slow` is for a test that the fast tier can do without because a faster
# sibling of its module covers the same subsystem there (an end-to-end book
# model beside its op tests, a three-way mesh composition beside the
# two-way ones): the entries below were measured over 8 s on one core when
# they were marked. It is NOT how tier-1 is kept inside its limit: many
# unmarked cases take longer than that under six workers, and a model's or
# a kernel's own comparison stays in tier-1 and is made cheaper instead
# (one compile a comparison, tests/decoder_toy.py's recorded run).
# Re-measure with `pytest --durations=60`.
# ---------------------------------------------------------------------------
_SLOW_TESTS = {
    'test_flash_attention.py::test_ring_attention_flash_impl_matches_dense_and_full',
    'test_reference_book_compat.py::test_reference_image_classification_vgg_runs_verbatim',
    'test_reference_book_compat.py::test_reference_image_classification_resnet_runs_verbatim',
    'test_reference_book_compat.py::test_reference_rnn_encoder_decoder_runs_verbatim',
    'test_reference_book_compat.py::test_reference_label_semantic_roles_runs_verbatim',
    'test_reference_book_compat.py::test_reference_machine_translation_train_runs_verbatim',
    'test_reference_book_compat.py::test_reference_machine_translation_decode_runs_verbatim',
    'test_reference_book_compat.py::test_reference_recommender_system_runs_verbatim',
    'test_reference_book_compat.py::test_reference_word2vec_runs_verbatim',
    'test_reference_book_compat.py::test_reference_hl_recognize_digits_conv_runs_verbatim',
    'test_reference_book_compat.py::test_reference_hl_sentiment_conv_runs_verbatim',
    'test_reference_book_compat.py::test_reference_hl_sentiment_dynamic_rnn_runs_verbatim',
    'test_reference_book_compat.py::test_reference_hl_sentiment_stacked_lstm_runs_verbatim',
    'test_examples.py::test_parallelism_example',
    'test_fluid_benchmark.py::test_transformer_model_with_sequence_parallel',
    'test_parallel.py::test_dryrun_multichip',
    'test_parallel.py::test_three_way_composition_compiles_remat_free',
    'test_pipeline_fluid.py::test_pipeline_transformer_matches_sequential',
    'test_nhwc.py::test_resnet18_nhwc_matches_nchw',
    'test_pipeline_fluid.py::test_pipeline_multi_layer_stages',
    'test_sp_fluid.py::test_sp_and_pp_compose_with_amp',
    'test_sp_fluid.py::test_pp_sp_composition_matches_single_device',
    'test_sp_fluid.py::test_three_way_dp_pp_sp_composition',
    'test_sp_fluid.py::test_pp_sp_ulysses_strategy',
    'test_tp_fluid.py::test_dp_pp_tp_three_way_matches_single_device[pp_first]',
    'test_sp_fluid.py::test_sp_transformer_matches_single_device',
    'test_tp_fluid.py::test_dp_pp_tp_three_way_matches_single_device[tp_first]',
    'test_models.py::test_vgg_cifar10_step',
    'test_sp_fluid.py::test_sp_dp_composition_matches_single_device',
    'test_models.py::test_transformer_overfits_batch',
    'test_flash_attention.py::test_ulysses_attention_matches_full_and_ring',
    'test_sp_fluid.py::test_sp_ulysses_strategy_matches_single_device',
    'test_tp_fluid.py::test_dp_tp_matches_single_device',
    'test_flash_attention.py::test_ring_attention_matches_full',
    'test_ops_sampled.py::test_seq2seq_generation',
    'test_sp_fluid.py::test_three_way_dp_tp_sp_composition',
    'test_models.py::test_seq2seq_attention_step',
    'test_integration_stack.py::test_trainer_moe_amp_checkpoint_resume',
    'test_book_label_semantic_roles.py::test_label_semantic_roles_trains_and_decodes',
    'test_tp_fluid.py::test_tp_matches_single_device_and_actually_shards',
    'test_multihost.py::test_two_process_loopback_cluster',
    'test_fluid_benchmark.py::test_mnist_local_runs_and_learns',
    'test_ssd_integration.py::test_ssd_trains_and_infers',
    'test_models.py::test_resnet_cifar10_step',
    'test_fluid_benchmark.py::test_mnist_pserver_transpiled',
    'test_fluid_benchmark.py::test_mnist_parallel_chips',
    'test_tp_fluid.py::test_tp_with_zero_composes_dp_sharding',
    'test_models.py::test_deepfm_steps',
    'test_models.py::test_stacked_lstm_step',
    'test_fluid_benchmark.py::test_mnist_tensor_parallel_flag',
    'test_layers.py::test_conv_family_shapes',
    'test_models.py::test_understand_sentiment_steps',
    'test_flash_attention.py::test_causal_triangular_grid_3x3_forward_and_grads',
    'test_ops_sampled.py::test_nce_hsigmoid_layers_build_and_run',
    'test_book_recognize_digits.py::test_mnist_lenet_trains',
    'test_nhwc.py::test_conv_pool_bn_nhwc_matches_nchw',
    'test_examples.py::test_recognize_digits_example',
    'test_book_recommender_system.py::test_recommender_system_converges',
    'test_ops_sampled.py::test_nce_trains_down',
    'test_ops_nn.py::test_conv2d_forward_and_grads_vs_torch',
    'test_contrib.py::test_training_decoder_converges',
    'test_nets.py::test_scaled_dot_product_attention_fused_matches_chain',
    'test_pipeline_moe.py::test_moe_capacity_drops_overflow',
    'test_pipeline_moe.py::test_circular_schedule_matches_sequential',
    'test_pipeline_fluid.py::test_circular_pipeline_matches_sequential_training',
}


# One test of the benchmark's own cannot pass from the seventh cell on, by
# the contract's own arithmetic: it appends ONE cell on four chips to the
# repository's cells and expects the contract to refuse it, and the
# contract allows a quarter of the cells, rounded down (7 + 1 = 8: two).
# tests/test_chipbench is the benchmark's, and only a `benchmark` PR edits
# a file there, so the test stands as it is and is EXPECTED to fail, by
# that cause alone (strict: the day it passes again, at any cell count,
# this marker fails the run and goes). Everything it held is held, at any
# number of cells, by tests/test_chipbench/test_chipbench_share.py: an
# accepted cell may not go, a further one-chip cell is legal, four-chip
# cells are refused exactly where the quarter is passed.
_HOLDS_BELOW_SEVEN_CELLS = ('test_chipbench_spec.py::test_an_accepted_cell_'
                            'may_not_go_and_additions_keep_the_chip_share')


def _benchmark_cells():
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'BENCHMARK.json')) as f:
        return len(json.load(f)['workloads'])


def pytest_collection_modifyitems(config, items):
    import pytest
    import warnings
    matched = set()
    for item in items:
        name = '%s::%s' % (item.path.name, item.name)
        if name in _SLOW_TESTS:
            matched.add(name)
            item.add_marker(pytest.mark.slow)
        if name == _HOLDS_BELOW_SEVEN_CELLS and _benchmark_cells() >= 7:
            item.add_marker(pytest.mark.xfail(
                reason='one more cell makes %d: two four-chip cells are '
                'within the contract\'s quarter; held by '
                'test_chipbench_share.py' % (_benchmark_cells() + 1),
                strict=True))
    # a renamed/deleted test would silently fall back into the fast tier;
    # surface stale entries at collection time (only when the whole suite
    # was collected — a -k/path-filtered run legitimately matches fewer)
    stale = _SLOW_TESTS - matched
    if stale and len(items) > 400:
        warnings.warn('stale _SLOW_TESTS entries (renamed/deleted?): %s'
                      % sorted(stale))
