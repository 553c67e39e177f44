"""ResNet-50's reference against its Program at a toy width on CPUPlace, and
its FLOP count against the Program's shapes (the cell's toy run is a case of
test_chipbench_cells.py's test_cell_runs_end_to_end_at_toy_width). In a
file of its own so that another worker takes it: ResNet-50 keeps its 53
convolutions at any width, and compiling them is what these tests' seconds
are.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)


def test_resnet_reference_agrees_with_its_program_in_float32():
    """The configuration's own `float32` check at a toy image size: with
    AMP off the Program and the plain reference are the same mathematics.
    The loss agrees to 1e-4 and the classifier-side gradients tightly;
    a convolution's gradient agrees only to a few percent even in
    float32 (under batch norm it is what four to five digits of
    cancellation leave, PERF.md PR 22), which is why the check under
    bf16 AMP holds the classifier's gradients and this one the
    convolutions'."""
    import paddle_tpu.fluid as fluid
    from chipbench.harness import catalog, check
    over = toy.toy_overrides('resnet50')
    del over['config']['checks']['float32']          # keep the file's own
    cell = catalog.load_cell('resnet50_b256', overrides=over)
    entry = cell['config']['checks']['float32']
    assert entry['amp'] == 'none' and entry['matmul_precision'] == 'highest'
    assert {'conv2d_0.w_0', 'conv2d_45.w_0'} <= set(entry['grads'])
    with fluid.scope_guard(fluid.Scope()):
        built = cell['builder'].build(cell['config'], cell['traffic'])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built['startup'])
        got = check.run_check(cell, exe, fluid.global_scope(), 5, entry)
        # the file's widths are held to the Program that was built
        scope = fluid.global_scope()
        wrong = dict(cell['config'],
                     model=dict(cell['config']['model'], stem_width=32))
        with pytest.raises(ValueError, match='not the model'):
            cell['builder'].reference_params(
                wrong, built['main'],
                lambda n: np.asarray(scope.find_var(n).get_tensor()))
        exe.close()
    assert got['passed'] and got['loss_rel'] < 1e-4, got
    assert got['grad_rel']['batch_norm_52.w_0'] < 0.05, got


def test_resnet_flops_follow_the_layer_shapes():
    """The count is derived from the shapes, and the shapes are the
    Program's: every convolution the builder makes is in the list with
    its output size, and the total meets ISSUE 22's cross-check (3.8 to
    4.1 G multiply-adds an image forward; 7.67 G FLOPs here because the
    model's first pool has no padding, so stage 1 runs at 55 x 55)."""
    from chipbench.harness import catalog
    cell = catalog.load_cell('resnet50_b256')
    model = cell['config']['model']
    layers = cell['flops'].conv_layers(model)
    assert len(layers) == 53
    built = cell['builder'].build(cell['config'], cell['traffic'])
    convs = [op for op in built['main'].global_block().ops
             if op.type == 'conv2d']
    assert len(convs) == 53
    for op, (_, hw, c_in, c_out, k) in zip(convs, layers):
        out = op.outputs['Output'][0].shape
        w = op.inputs['Filter'][0].shape
        assert tuple(out[1:]) == (hw, hw, c_out), (op, out)
        assert tuple(w) == (c_out, c_in, k, k)
    fwd = cell['flops'].forward_flops_per_image(model)
    assert 7.6e9 < fwd < 8.2e9
    step = cell['flops'].train_step_flops(cell['config'], cell['traffic'])
    assert np.isclose(step, 3 * 256 * fwd)
    assert cell['flops'].kernel_cost(cell['config'], cell['traffic']) is None
