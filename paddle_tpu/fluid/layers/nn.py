"""Neural-network layers. Parity: reference python/paddle/fluid/layers/nn.py
(all 76 public functions + relu/log). Each appends op symbols lowered by
ops_impl/ into the single fused XLA step.
"""
import copy

import numpy as np

from ..layer_helper import LayerHelper
from ..framework import Variable
from ..initializer import Normal, Constant
from ..param_attr import ParamAttr
from .. import unique_name
from . import tensor as tensor_mod

__all__ = [
    'fc', 'embedding', 'moe_mlp', 'router_bias_update', 'latent_attention',
    'dynamic_lstm', 'dynamic_lstmp', 'dynamic_gru',
    'gru_unit', 'linear_chain_crf', 'crf_decoding', 'cos_sim',
    'cross_entropy', 'square_error_cost', 'chunk_eval', 'sequence_conv',
    'conv2d', 'conv3d', 'sequence_pool', 'sequence_softmax', 'softmax',
    'pool2d', 'pool3d', 'batch_norm', 'beam_search_decode',
    'conv2d_transpose', 'conv3d_transpose', 'sequence_expand', 'lstm_unit',
    'reduce_sum', 'reduce_mean', 'reduce_max', 'reduce_min', 'reduce_prod',
    'sequence_first_step', 'sequence_last_step', 'dropout', 'split',
    'ctc_greedy_decoder', 'edit_distance', 'l2_normalize', 'matmul', 'topk',
    'warpctc', 'sequence_reshape', 'transpose', 'im2sequence', 'nce',
    'hsigmoid', 'beam_search', 'row_conv', 'multiplex', 'layer_norm',
    'softmax_with_cross_entropy', 'smooth_l1', 'one_hot',
    'autoincreased_step_counter', 'reshape', 'lod_reset', 'lrn', 'pad',
    'label_smooth', 'roi_pool', 'dice_loss', 'image_resize',
    'image_resize_short', 'resize_bilinear', 'gather', 'scatter', 'expand',
    'random_crop', 'mean_iou', 'relu', 'log', 'crop', 'rank_loss', 'prelu',
    'flatten', 'sequence_mask', 'stack', 'fused_attention', 'rms_norm',
    'rotary_embedding', 'gated_delta_rule', 'causal_conv1d',
    'gated_rms_norm', 'ssd_scan', 'chunk_softmax_pool',
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       use_mkldnn=False, act=None, is_test=False, name=None):
    """Fully connected (reference nn.py:fc): one mul per input + sum +
    bias + act. The muls land on the MXU."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, param_attr_ in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        if input_var.lod_level > 0 and num_flatten_dims == 1:
            # sequence input [B, T, d]: apply fc per step
            flat_dims = 2
        else:
            flat_dims = num_flatten_dims
        param_shape = [
            int(np.prod(input_shape[flat_dims:]))
        ] + [size]
        w = helper.create_parameter(attr=param_attr_, shape=param_shape,
                                    dtype=dtype, is_bias=False)
        # static out shape (reference mul_op InferShape with
        # y_num_col_dims=1): X.dims[:k] + [size] — bias append and any
        # downstream fc read it (input_shape is non-None here: param_shape
        # above already dereferenced it)
        out_shape = list(input_shape[:flat_dims]) + [size]
        tmp = helper.create_variable_for_type_inference(
            dtype, shape=out_shape,
            lod_level=getattr(input_var, 'lod_level', 0) or 0)
        helper.append_op(
            type="mul", inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": flat_dims, "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            dtype, shape=mul_results[0].shape,
            lod_level=mul_results[0].lod_level)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]}, attrs={})
    pre_act = helper.append_bias_op(pre_bias, dim_start=-1, dim_end=None)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype='float32'):
    """reference nn.py:embedding (lookup_table op).

    is_sparse=True routes the table gradient through the touched-rows-only
    SparseRows path (executor sparse plan; reference SelectedRows) when
    the program shape allows it; otherwise the gradient is a dense
    scatter-add fused by XLA.

    is_distributed=True is the pserver row-split rebuilt TPU-native
    (docs/embedding.md): annotate the table row-sharded over a mesh axis
    — ``param_attr=ParamAttr(..., sharding=('model', None))`` — and
    declare the mesh with ``Program.set_mesh``; the lookup then lowers to
    the all_to_all exchange wire (ops_impl/embedding_ops.py) and, with
    is_sparse=True as well (the supported sharded-sparse combination),
    updates stay touched-rows-only per shard. Without the annotation or
    the mesh the flag is INERT — warned about loudly below, since the
    reference accepted it silently while this framework used to too."""
    helper = LayerHelper('embedding', **locals())
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype, is_bias=False)
    dist_axis = None
    if is_distributed:
        spec = getattr(w, 'sharding', None)
        row = spec[0] if spec else None
        if row is not None and isinstance(row, tuple):
            # annotated, but over an axis PRODUCT: GSPMD will still
            # shard the table, only the lookup wire stays dense — a
            # different situation from no annotation at all
            import warnings
            warnings.warn(
                "embedding(is_distributed=True) on table %r row-shards "
                "over the axis product %r — the all_to_all lookup wire "
                "supports a SINGLE row axis, so lookups stay dense "
                "gathers (the table itself still shards). Use one axis, "
                "e.g. sharding=('model', None) (docs/embedding.md)."
                % (w.name, row), UserWarning, stacklevel=2)
            row = None
        elif row is None:
            import warnings
            warnings.warn(
                "embedding(is_distributed=True) on table %r has no row-"
                "sharding annotation — unless one is stamped later (the "
                "DistributeTranspiler shim does, on transpile()), the "
                "flag is INERT and the table will be replicated. Declare "
                "ParamAttr(sharding=('<axis>', None)) on the table and "
                "Program.set_mesh({'<axis>': N, ...}); is_sparse=True + "
                "is_distributed=True is the supported sharded-sparse "
                "combination (docs/embedding.md)." % w.name,
                UserWarning, stacklevel=2)
        else:
            # set_mesh() may legitimately come after the layer calls; a
            # program that still has no mesh (or no such axis) when it
            # COMPILES is warned about there (StepArtifact)
            dist_axis = row
    # static out shape (reference lookup_table_op InferShape): an id
    # column [..., 1] embeds to [..., emb_dim] — downstream layers (fc)
    # read .shape for their own parameter shapes
    in_shape = getattr(input, 'shape', None)
    out_shape = None
    if in_shape is not None and len(in_shape):
        base = list(in_shape[:-1]) if in_shape[-1] == 1 else list(in_shape)
        out_shape = base + [size[-1]]
    tmp = helper.create_variable_for_type_inference(
        dtype, shape=out_shape,
        lod_level=getattr(input, 'lod_level', 0) or 0)
    padding_idx = -1 if padding_idx is None else \
        padding_idx if padding_idx >= 0 else (size[0] + padding_idx)
    attrs = {'is_sparse': is_sparse,
             'is_distributed': is_distributed,
             'padding_idx': padding_idx}
    if dist_axis is not None:
        # static routing for the lowering rule: the table's row axis,
        # resolved here where the annotation is in hand (the rule sees
        # values, not Variables)
        attrs['dist_axis'] = dist_axis
    helper.append_op(type='lookup_table',
                     inputs={'Ids': [input], 'W': [w]},
                     outputs={'Out': [tmp]},
                     attrs=attrs)
    return tmp


def _create_rnn_bias_param(helper, attr, shape, dtype):
    return helper.create_parameter(attr=attr, shape=shape, dtype=dtype,
                                   is_bias=True)


def dynamic_lstm(input, size, h_0=None, c_0=None, param_attr=None,
                 bias_attr=None, use_peepholes=True, is_reverse=False,
                 gate_activation='sigmoid', cell_activation='tanh',
                 candidate_activation='tanh', dtype='float32', name=None):
    """reference nn.py:dynamic_lstm — input is the pre-projected gates
    [*, 4*hidden]; lowers to one lax.scan."""
    helper = LayerHelper('lstm', **locals())
    size = size // 4
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 4 * size], dtype=dtype)
    bias_size = [1, 7 * size] if use_peepholes else [1, 4 * size]
    bias = _create_rnn_bias_param(helper, helper.bias_attr, bias_size, dtype)
    hidden = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    inputs = {'Input': [input], 'Weight': [weight], 'Bias': [bias]}
    if h_0 is not None:
        inputs['H0'] = [h_0]
    if c_0 is not None:
        inputs['C0'] = [c_0]
    helper.append_op(type='lstm', inputs=inputs,
                     outputs={'Hidden': [hidden], 'Cell': [cell]},
                     attrs={'use_peepholes': use_peepholes,
                            'is_reverse': is_reverse,
                            'gate_activation': gate_activation,
                            'cell_activation': cell_activation,
                            'candidate_activation': candidate_activation})
    return hidden, cell


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=True, is_reverse=False,
                  gate_activation='sigmoid', cell_activation='tanh',
                  candidate_activation='tanh', proj_activation='tanh',
                  dtype='float32', name=None):
    helper = LayerHelper('lstmp', **locals())
    size = size // 4
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[proj_size, 4 * size], dtype=dtype)
    proj_weight = helper.create_parameter(
        attr=ParamAttr(name=None), shape=[size, proj_size], dtype=dtype)
    bias_size = [1, 7 * size] if use_peepholes else [1, 4 * size]
    bias = _create_rnn_bias_param(helper, helper.bias_attr, bias_size, dtype)
    projection = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='lstmp',
                     inputs={'Input': [input], 'Weight': [weight],
                             'ProjWeight': [proj_weight], 'Bias': [bias]},
                     outputs={'Projection': [projection], 'Cell': [cell]},
                     attrs={'use_peepholes': use_peepholes,
                            'is_reverse': is_reverse,
                            'gate_activation': gate_activation,
                            'cell_activation': cell_activation,
                            'candidate_activation': candidate_activation,
                            'proj_activation': proj_activation})
    return projection, cell


def dynamic_gru(input, size, param_attr=None, bias_attr=None,
                is_reverse=False, gate_activation='sigmoid',
                candidate_activation='tanh', h_0=None):
    helper = LayerHelper('gru', **locals())
    dtype = helper.input_dtype()
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 3 * size], dtype=dtype)
    bias = _create_rnn_bias_param(helper, helper.bias_attr, [1, 3 * size], dtype)
    hidden = helper.create_variable_for_type_inference(dtype)
    inputs = {'Input': [input], 'Weight': [weight], 'Bias': [bias]}
    if h_0 is not None:
        inputs['H0'] = [h_0]
    helper.append_op(type='gru', inputs=inputs, outputs={'Hidden': [hidden]},
                     attrs={'is_reverse': is_reverse,
                            'gate_activation': gate_activation,
                            'activation': candidate_activation})
    return hidden


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation='tanh', gate_activation='sigmoid'):
    helper = LayerHelper('gru_unit', **locals())
    dtype = helper.input_dtype()
    size = size // 3
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 3 * size], dtype=dtype)
    gate = helper.create_variable_for_type_inference(dtype)
    reset_hidden_pre = helper.create_variable_for_type_inference(dtype)
    updated_hidden = helper.create_variable_for_type_inference(dtype)
    inputs = {'Input': [input], 'HiddenPrev': [hidden], 'Weight': [weight]}
    if helper.bias_attr:
        bias = helper.create_parameter(attr=helper.bias_attr,
                                       shape=[1, 3 * size], dtype=dtype,
                                       is_bias=True)
        inputs['Bias'] = [bias]
    helper.append_op(type='gru_unit', inputs=inputs,
                     outputs={'Hidden': [updated_hidden],
                              'ResetHiddenPrev': [reset_hidden_pre],
                              'Gate': [gate]},
                     attrs={'activation': activation,
                            'gate_activation': gate_activation})
    return updated_hidden, reset_hidden_pre, gate


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """reference nn.py:lstm_unit — fc([x, h]) then fused lstm cell."""
    helper = LayerHelper('lstm_unit', **locals())
    if len(x_t.shape) != 2:
        raise ValueError("x_t must be 2-D")
    size = cell_t_prev.shape[1]
    concat_out = tensor_mod.concat(input=[x_t, hidden_t_prev], axis=1)
    fc_out = fc(input=concat_out, size=4 * size, param_attr=param_attr,
                bias_attr=bias_attr)
    dtype = x_t.dtype
    c = helper.create_variable_for_type_inference(dtype)
    h = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='lstm_unit',
                     inputs={"X": [fc_out], "C_prev": [cell_t_prev]},
                     outputs={"C": [c], "H": [h]},
                     attrs={"forget_bias": forget_bias})
    return h, c


def linear_chain_crf(input, label, param_attr=None):
    helper = LayerHelper('linear_chain_crf', **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(attr=helper.param_attr,
                                         shape=[size + 2, size],
                                         dtype=helper.input_dtype())
    alpha = helper.create_variable_for_type_inference(helper.input_dtype())
    emission_exps = helper.create_variable_for_type_inference(helper.input_dtype())
    transition_exps = helper.create_variable_for_type_inference(helper.input_dtype())
    log_likelihood = helper.create_variable_for_type_inference(helper.input_dtype())
    helper.append_op(type='linear_chain_crf',
                     inputs={"Emission": [input], "Transition": [transition],
                             "Label": [label]},
                     outputs={"Alpha": [alpha], "EmissionExps": [emission_exps],
                              "TransitionExps": [transition_exps],
                              "LogLikelihood": [log_likelihood]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    helper = LayerHelper('crf_decoding', **locals())
    transition = helper.get_parameter(param_attr.name)
    viterbi_path = helper.create_variable_for_type_inference('int64')
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type='crf_decoding', inputs=inputs,
                     outputs={"ViterbiPath": [viterbi_path]})
    return viterbi_path


def cos_sim(X, Y):
    helper = LayerHelper('cos_sim', **locals())
    out = helper.create_variable_for_type_inference(dtype=X.dtype)
    xnorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    ynorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    helper.append_op(type='cos_sim', inputs={'X': [X], 'Y': [Y]},
                     outputs={'Out': [out], 'XNorm': [xnorm],
                              'YNorm': [ynorm]})
    return out


def cross_entropy(input, label, soft_label=False):
    helper = LayerHelper('cross_entropy', **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type='cross_entropy',
                     inputs={'X': [input], 'Label': [label]},
                     outputs={'Y': [out]}, attrs={'soft_label': soft_label})
    return out


def square_error_cost(input, label):
    """reference nn.py:square_error_cost = (input - label)^2."""
    helper = LayerHelper('square_error_cost', **locals())
    minus_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type='elementwise_sub',
                     inputs={'X': [input], 'Y': [label]},
                     outputs={'Out': [minus_out]}, attrs={'axis': -1})
    square_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type='square', inputs={'X': [minus_out]},
                     outputs={'Out': [square_out]})
    return square_out


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    helper = LayerHelper("chunk_eval", **locals())
    precision = helper.create_variable_for_type_inference(dtype="float32")
    recall = helper.create_variable_for_type_inference(dtype="float32")
    f1_score = helper.create_variable_for_type_inference(dtype="float32")
    num_infer_chunks = helper.create_variable_for_type_inference(dtype="int64")
    num_label_chunks = helper.create_variable_for_type_inference(dtype="int64")
    num_correct_chunks = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="chunk_eval",
        inputs={"Inference": [input], "Label": [label]},
        outputs={"Precision": [precision], "Recall": [recall],
                 "F1-Score": [f1_score],
                 "NumInferChunks": [num_infer_chunks],
                 "NumLabelChunks": [num_label_chunks],
                 "NumCorrectChunks": [num_correct_chunks]},
        attrs={"num_chunk_types": num_chunk_types,
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": excluded_chunk_types or []})
    return (precision, recall, f1_score, num_infer_chunks, num_label_chunks,
            num_correct_chunks)


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None):
    helper = LayerHelper('sequence_conv', **locals())
    dtype = helper.input_dtype()
    filter_shape = [filter_size * input.shape[-1], num_filters]
    filter_param = helper.create_parameter(attr=helper.param_attr,
                                           shape=filter_shape, dtype=dtype)
    # out shape: input with the feature axis -> num_filters (reference
    # sequence_conv_op InferShape; input.shape is non-None here —
    # filter_shape above already dereferenced it)
    out_shape = list(input.shape[:-1]) + [num_filters]
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=out_shape, lod_level=input.lod_level)
    helper.append_op(type='sequence_conv',
                     inputs={'X': [input], 'Filter': [filter_param]},
                     outputs={'Out': [pre_bias]},
                     attrs={'contextStride': filter_stride,
                            'contextStart': -int(filter_size // 2),
                            'contextLength': filter_size})
    pre_act = helper.append_bias_op(pre_bias, dim_start=-1)
    return helper.append_activation(pre_act)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           use_mkldnn=False, act=None, name=None, data_format='NCHW'):
    """reference nn.py:conv2d (NCHW); data_format='NHWC' runs
    channels-last — the native XLA:TPU layout — with the SAME OIHW filter
    params, so a model switches layout without touching checkpoints."""
    if data_format not in ('NCHW', 'NHWC'):
        raise ValueError("data_format must be 'NCHW' or 'NHWC', got %r"
                         % (data_format,))
    num_channels = (input.shape[-1] if data_format == 'NHWC'
                    else input.shape[1])
    helper = LayerHelper('conv2d', **locals())
    dtype = helper.input_dtype()
    groups = groups or 1
    if num_channels % groups != 0:
        raise ValueError("num_channels must be divisible by groups")
    num_filter_channels = num_channels // groups

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, num_filter_channels] + filter_size

    def _get_default_param_initializer():
        std = (2.0 / (filter_size[0] ** 2 * num_channels)) ** 0.5
        return Normal(0.0, std, 0)

    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=_get_default_param_initializer())
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv2d',
        inputs={'Input': [input], 'Filter': [filter_param]},
        outputs={"Output": [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups, 'use_cudnn': use_cudnn,
               'data_format': data_format})
    if data_format == 'NHWC':
        pre_act = helper.append_bias_op(pre_bias, dim_start=-1)
    else:
        pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           use_mkldnn=False, act=None, name=None):
    num_channels = input.shape[1]
    helper = LayerHelper('conv3d', **locals())
    dtype = helper.input_dtype()
    groups = groups or 1
    num_filter_channels = num_channels // groups

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    filter_size = _triple(filter_size)
    stride = _triple(stride)
    padding = _triple(padding)
    dilation = _triple(dilation)
    filter_shape = [num_filters, num_filter_channels] + filter_size
    std = (2.0 / (int(np.prod(filter_size)) * num_channels)) ** 0.5
    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=Normal(0.0, std, 0))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='conv3d',
        inputs={'Input': [input], 'Filter': [filter_param]},
        outputs={"Output": [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def sequence_pool(input, pool_type):
    helper = LayerHelper('sequence_pool', **locals())
    dtype = helper.input_dtype()
    # pooling consumes the innermost LoD level: one output row per inner
    # sequence, same trailing feature dims (reference sequence_pool_op).
    # In the padded [B, T, ...] SeqValue convention that drops the time
    # dim (rank - 1); the batch dim stays dynamic.
    lod = getattr(input, 'lod_level', 0) or 0
    shape = None
    if input.shape is not None:
        shape = (list(input.shape[:1]) + list(input.shape[2:])
                 if lod > 0 and len(input.shape) >= 3 else
                 list(input.shape))
    pool_out = helper.create_variable_for_type_inference(
        dtype, shape=shape, lod_level=max(lod - 1, 0))
    max_index = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="sequence_pool", inputs={"X": [input]},
                     outputs={"Out": [pool_out], "MaxIndex": [max_index]},
                     attrs={"pooltype": pool_type.upper()})
    return pool_out


def sequence_first_step(input):
    return sequence_pool(input=input, pool_type="first")


def sequence_last_step(input):
    return sequence_pool(input=input, pool_type="last")


def sequence_softmax(input, param_attr=None, bias_attr=None, use_cudnn=True):
    helper = LayerHelper('sequence_softmax', **locals())
    dtype = helper.input_dtype()
    softmax_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="sequence_softmax", inputs={"X": [input]},
                     outputs={"Out": [softmax_out]}, attrs={})
    return softmax_out


def softmax(input, param_attr=None, bias_attr=None, use_cudnn=True,
            name=None):
    helper = LayerHelper('softmax', **locals())
    dtype = helper.input_dtype()
    softmax_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [softmax_out]}, attrs={})
    return softmax_out


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, use_mkldnn=False, name=None,
           data_format='NCHW'):
    if pool_type not in ["max", "avg"]:
        raise ValueError("pool_type must be 'max' or 'avg'")
    if data_format not in ('NCHW', 'NHWC'):
        raise ValueError("data_format must be 'NCHW' or 'NHWC', got %r"
                         % (data_format,))
    if global_pooling is False and pool_size == -1:
        raise ValueError("pool_size must be set without global pooling")

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    helper = LayerHelper('pool2d', **locals())
    dtype = helper.input_dtype()
    pool_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='pool2d', inputs={"X": [input]}, outputs={"Out": [pool_out]},
        attrs={"pooling_type": pool_type, "ksize": _pair(pool_size),
               "global_pooling": global_pooling,
               "strides": _pair(pool_stride),
               "paddings": _pair(pool_padding), "ceil_mode": ceil_mode,
               "data_format": data_format})
    return pool_out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, use_mkldnn=False, name=None):
    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    helper = LayerHelper('pool3d', **locals())
    dtype = helper.input_dtype()
    pool_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type='pool3d', inputs={"X": [input]}, outputs={"Out": [pool_out]},
        attrs={"pooling_type": pool_type, "ksize": _triple(pool_size),
               "global_pooling": global_pooling,
               "strides": _triple(pool_stride),
               "paddings": _triple(pool_padding), "ceil_mode": ceil_mode})
    return pool_out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-05,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               in_place=False, use_mkldnn=False, name=None,
               moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=False):
    """reference nn.py:batch_norm."""
    if data_layout not in ('NCHW', 'NHWC'):
        raise ValueError("data_layout must be 'NCHW' or 'NHWC', got %r"
                         % (data_layout,))
    helper = LayerHelper('batch_norm', **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    if data_layout == 'NCHW':
        channel_num = input_shape[1]
    else:
        channel_num = input_shape[-1]
    param_shape = [channel_num]

    scale = helper.create_parameter(attr=helper.param_attr, shape=param_shape,
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                   dtype=dtype, is_bias=True)
    mean = helper.create_parameter(
        attr=ParamAttr(name=moving_mean_name, initializer=Constant(0.0),
                       trainable=False), shape=param_shape, dtype=dtype)
    variance = helper.create_parameter(
        attr=ParamAttr(name=moving_variance_name, initializer=Constant(1.0),
                       trainable=False), shape=param_shape, dtype=dtype)
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype,
                                                           stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    batch_norm_out = input if in_place else \
        helper.create_variable_for_type_inference(dtype)

    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [batch_norm_out], "MeanOut": [mean],
                 "VarianceOut": [variance], "SavedMean": [saved_mean],
                 "SavedVariance": [saved_variance]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout})
    return helper.append_activation(batch_norm_out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper('layer_norm', **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    param_shape = [int(np.prod(input_shape[begin_norm_axis:]))]
    inputs = {'X': [input]}
    if scale:
        scale_p = helper.create_parameter(attr=helper.param_attr,
                                          shape=param_shape, dtype=dtype,
                                          default_initializer=Constant(1.0))
        inputs['Scale'] = [scale_p]
    if shift:
        bias_p = helper.create_parameter(attr=helper.bias_attr,
                                         shape=param_shape, dtype=dtype,
                                         is_bias=True)
        inputs['Bias'] = [bias_p]
    mean_out = helper.create_variable_for_type_inference(dtype,
                                                         stop_gradient=True)
    variance_out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    layer_norm_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [layer_norm_out], "Mean": [mean_out],
                 "Variance": [variance_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(layer_norm_out)


def rms_norm(input, epsilon=1e-05, param_attr=None, name=None,
             unit_offset=False):
    """RMS norm over the last axis: ``scale * x * rsqrt(mean(x^2) +
    epsilon)`` with a learned `scale` of the last axis' width (initialised
    to 1), no mean subtraction and no shift. One Program op; statistics in
    float32 under AMP too. ``unit_offset=True`` stores the weight as its
    offset from one (EvaByte's `norm_add_unit_offset`): ``(1 + w) * x *
    rsqrt(...)``, `w` initialised to 0. TPU extension (the reference
    predates it)."""
    helper = LayerHelper('rms_norm', **locals())
    dtype = helper.input_dtype()
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])], dtype=dtype,
        default_initializer=Constant(0.0 if unit_offset else 1.0))
    out = helper.create_variable_for_type_inference(dtype)
    # the mode is written only where it departs from the op as it was
    attrs = {'epsilon': float(epsilon)}
    if unit_offset:
        attrs['unit_offset'] = True
    helper.append_op(type='rms_norm', inputs={'X': [input],
                                              'Scale': [scale]},
                     outputs={'Y': [out]}, attrs=attrs)
    return out


def gated_rms_norm(input, gate, epsilon=1e-05, param_attr=None, name=None,
                   norm_before_gate=True, groups=1, gate_act='silu'):
    """RMS norm over the last axis times a SiLU gate of the same shape:
    ``scale * x * rsqrt(mean(x^2) + epsilon) * silu(gate)``, `scale` as
    layers.rms_norm's. One Program op whose backward keeps `input` and
    `gate` and recomputes the rest (layers.rms_norm, layers.swish and a
    multiply keep three more arrays of the same size). TPU extension.

    ``norm_before_gate=False`` gates FIRST and normalises the product
    (Mamba-2): ``scale * u * rsqrt(mean(u^2) + epsilon)`` with ``u = x *
    silu(gate)``. ``groups`` G > 1 takes the mean of squares over each of
    G equal parts of the last axis by itself (`scale` stays one weight an
    element of the whole axis). ``gate_act='sigmoid'`` gates by
    ``sigmoid(gate)`` wherever ``silu(gate)`` stands above."""
    if gate_act not in ('silu', 'sigmoid'):
        raise ValueError('gated_rms_norm: gate_act is silu or sigmoid, got '
                         '%r' % (gate_act,))
    if int(groups) < 1 or int(input.shape[-1]) % int(groups):
        raise ValueError('gated_rms_norm: %r groups do not divide the last '
                         'axis of %r' % (groups, int(input.shape[-1])))
    helper = LayerHelper('gated_rms_norm', **locals())
    dtype = helper.input_dtype()
    scale = helper.create_parameter(attr=helper.param_attr,
                                    shape=[int(input.shape[-1])],
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(dtype)
    # the mode is written only where it departs from the op as it was
    attrs = {'epsilon': float(epsilon)}
    if not norm_before_gate:
        attrs['norm_before_gate'] = False
    if int(groups) > 1:
        attrs['groups'] = int(groups)
    if gate_act != 'silu':
        attrs['gate_act'] = gate_act
    helper.append_op(type='gated_rms_norm',
                     inputs={'X': [input], 'Gate': [gate],
                             'Scale': [scale]},
                     outputs={'Y': [out]}, attrs=attrs)
    return out


def rotary_embedding(x, base=10000.0, rotary_dim=None, name=None,
                     interleave=False):
    """Rotary position embedding of heads ``x`` [B, H, T, D] at positions
    0..T-1: element i of a head is rotated with element i + D/2 (the
    rotate-half pairing) by the angle ``t * base**(-2i/D)``. With
    `rotary_dim` R < D (a partial rotary factor) only the FIRST R elements
    of each head turn, paired (i, i + R/2) at ``t * base**(-2i/R)``; the
    rest pass through. ``interleave=True`` pairs NEIGHBOURS instead:
    elements 2i and 2i + 1 turn together by the same angle, each staying
    where it is (DeepSeek's `rope_interleave`). No parameter. One Program
    op. TPU extension (the reference predates it)."""
    width = int(x.shape[-1])
    rotary_dim = width if rotary_dim is None else int(rotary_dim)
    if rotary_dim % 2 or not 0 < rotary_dim <= width:
        raise ValueError('rotary_embedding turns an even number of a '
                         "head's %d elements, got %r" % (width, rotary_dim))
    helper = LayerHelper('rotary_embedding', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {'base': float(base)}
    if rotary_dim != width:
        attrs['rotary_dim'] = rotary_dim
    if interleave:
        attrs['interleave'] = True
    helper.append_op(type='rotary_embedding', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs=attrs)
    return out


def gated_delta_rule(q, k, v, g, beta, chunk_size=64, scale=None,
                     qk_l2norm=False, name=None, gate_floor=None):
    """The gated delta rule, Gated DeltaNet's linear attention (Yang et
    al. 2024, arXiv:2412.06464), in ONE op. Per head a [Dk, Dv] float32
    state S, zero at a row's first token, and for each token t

        S = exp(g_t) S;  S = S + k_t (beta_t (v_t - S^T k_t))^T;
        o_t = S^T q_t.

    q, k: [B, T, Hk, Dk]; v: [B, T, Hv, Dv] with Hk dividing Hv (key head
    h serves value heads h * Hv/Hk and following); g (<= 0, the log of
    the decay) and beta (the write strength): [B, T, Hv]. A g of
    [B, T, Hv, Dk] is a decay a CHANNEL (Kimi Delta Attention,
    arXiv:2510.26692): ``S = diag(exp(g_t)) S``, each row of the state at
    its own rate; it needs ``gate_floor``, the bound ``g >= gate_floor``
    its producer keeps (-5 for ``-5 * sigmoid(.)``), which the rule holds g
    to and refuses where 16 rows of it overflow a float32. With
    ``qk_l2norm`` q and k are first divided by their norm over Dk
    (``x * rsqrt(sum x^2 + 1e-6)``); q is then multiplied by `scale`
    (default ``Dk ** -0.5``). Returns o [B, T, Hv, Dv].

    Computed in chunks of `chunk_size` tokens (a power of two times 16
    solves its chunks blockwise; T need not be a multiple): matmuls inside
    a chunk, a scan carrying S across chunks, its own backward that keeps
    S at chunk boundaries only (ops_impl/linear_attention_ops.py). No
    state enters or leaves the op: a row is one stream, with no reset
    between packed documents. TPU extension (the reference predates it).
    """
    if int(v.shape[2]) % int(q.shape[2]) or q.shape[2] != k.shape[2]:
        raise ValueError('gated_delta_rule: %r key heads do not divide %r '
                         'value heads' % (q.shape[2], v.shape[2]))
    if len(g.shape) == 4 and gate_floor is None:
        raise ValueError('gated_delta_rule: a decay a channel (g %r) needs '
                         'gate_floor' % (tuple(g.shape),))
    helper = LayerHelper('gated_delta_rule', **locals())
    out = helper.create_variable_for_type_inference(v.dtype)
    attrs = {'chunk_size': int(chunk_size),
             'scale': float(scale) if scale is not None else -1.0,
             'qk_l2norm': bool(qk_l2norm)}
    if gate_floor is not None:
        attrs['gate_floor'] = float(gate_floor)
    helper.append_op(
        type='gated_delta_rule',
        inputs={'Q': [q], 'K': [k], 'V': [v], 'G': [g], 'Beta': [beta]},
        outputs={'Out': [out]}, attrs=attrs)
    return out


def ssd_scan(x, dt, a, b, c, d=None, chunk_size=128, name=None):
    """Mamba-2's selective state-space scan (SSD: Dao and Gu 2024,
    arXiv:2405.21060) in ONE op. Per head h a [P, N] float32 state S, zero
    at a row's first token, and for each token t

        S = exp(dt_t a_h) S + dt_t x_t B_t^T;   y_t = S C_t + d_h x_t.

    x: [B, T, H, P]; dt (the step, > 0: the model's softplus): [B, T, H];
    a (< 0, a scalar decay a head) and the optional skip d: [H]; b, c:
    [B, T, G, N] with G dividing H (group g serves heads g * H/G and
    following). Returns y [B, T, H, P].

    Computed in chunks of `chunk_size` tokens (T need not be a multiple):
    matmuls inside a chunk, a scan carrying S in float32 across chunks,
    its own backward that keeps the op's inputs alone
    (ops_impl/linear_attention_ops.py). No state enters or leaves the op:
    a row is one stream, with no reset between packed documents. TPU
    extension (the reference predates it)."""
    if int(x.shape[2]) % int(b.shape[2]) or tuple(b.shape) != tuple(c.shape):
        raise ValueError('ssd_scan: %r groups of b %r and c %r do not '
                         'divide %r heads' % (b.shape[2], tuple(b.shape),
                                              tuple(c.shape), x.shape[2]))
    helper = LayerHelper('ssd_scan', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {'X': [x], 'Dt': [dt], 'A': [a], 'B': [b], 'C': [c]}
    if d is not None:
        inputs['D'] = [d]
    helper.append_op(type='ssd_scan', inputs=inputs, outputs={'Out': [out]},
                     attrs={'chunk_size': int(chunk_size)})
    return out


def causal_conv1d(input, kernel_size, act=None, param_attr=None, name=None,
                  bias_attr=False, in_gate=None, out_gate=None):
    """Depthwise causal convolution along the time axis of ``input``
    [B, T, C]: ``y[t] = act(sum_j w[j] * x[t - (kernel_size - 1) + j] +
    bias)`` per channel, zeros before the first token (left padding).
    The filter is a parameter [kernel_size, C]; `act` is None or 'silu';
    ``bias_attr`` False (the default) is no bias, anything else a
    parameter [C] from 0 (a ParamAttr names or initialises it).
    ``in_gate`` and ``out_gate`` (tensors of ``input``'s shape, each
    optional) multiply the convolution's input and its result:
    ``out_gate * conv(in_gate * input)``, LFM2's double-gated short
    convolution with both. One Program op. TPU extension (the reference's
    sequence_conv mixes channels and looks both ways)."""
    if act not in (None, 'silu', 'swish'):
        raise ValueError("causal_conv1d act=%r: None or 'silu'" % (act,))
    for gate in (in_gate, out_gate):
        if gate is not None and tuple(gate.shape) != tuple(input.shape):
            raise ValueError('causal_conv1d: a gate of shape %r on an input '
                             'of %r' % (tuple(gate.shape),
                                        tuple(input.shape)))
    helper = LayerHelper('causal_conv1d', **locals())
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[int(kernel_size), int(input.shape[-1])], dtype=dtype)
    inputs = {'X': [input], 'Filter': [w]}
    if bias_attr is not False:
        inputs['Bias'] = [helper.create_parameter(
            attr=helper.bias_attr, shape=[int(input.shape[-1])],
            dtype=dtype, is_bias=True)]
    if in_gate is not None:
        inputs['InGate'] = [in_gate]
    if out_gate is not None:
        inputs['OutGate'] = [out_gate]
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='causal_conv1d', inputs=inputs,
                     outputs={'Out': [out]}, attrs={'act': act or ''})
    return out


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", **locals())
    input_channel = input.shape[1]

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    padding = _pair(padding)
    stride = _pair(stride)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size must be set when filter_size is None")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size_h = (output_size[0] - (h_in - 1) * stride[0] +
                         2 * padding[0] - 1) // dilation[0] + 1
        filter_size_w = (output_size[1] - (w_in - 1) * stride[1] +
                         2 * padding[1] - 1) // dilation[1] + 1
        filter_size = [filter_size_h, filter_size_w]
    else:
        filter_size = _pair(filter_size)
    groups = 1 if groups is None else groups
    filter_shape = [input_channel, num_filters // groups] + filter_size
    img_filter = helper.create_parameter(dtype=input.dtype,
                                         shape=filter_shape,
                                         attr=helper.param_attr)
    pre_bias = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type='conv2d_transpose',
        inputs={'Input': [input], 'Filter': [img_filter]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv3d_transpose", **locals())
    input_channel = input.shape[1]

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    padding = _triple(padding)
    stride = _triple(stride)
    dilation = _triple(dilation)
    if filter_size is None:
        raise ValueError("filter_size is required for conv3d_transpose")
    filter_size = _triple(filter_size)
    groups = 1 if groups is None else groups
    filter_shape = [input_channel, num_filters // groups] + filter_size
    img_filter = helper.create_parameter(dtype=input.dtype,
                                         shape=filter_shape,
                                         attr=helper.param_attr)
    pre_bias = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type='conv3d_transpose',
        inputs={'Input': [input], 'Filter': [img_filter]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper('sequence_expand', **locals())
    dtype = helper.input_dtype('x')
    # out is a SEQUENCE [rows, T(dynamic), features...]: the lowering
    # broadcasts each x row across y's time axis (dense x gains a time
    # dim; sequence x keeps rank with a new T)
    shape = None
    if x.shape is not None:
        feat = (list(x.shape[2:]) if (x.lod_level or 0) > 0
                and len(x.shape) >= 3 else list(x.shape[1:]))
        shape = [x.shape[0], -1] + feat
    tmp = helper.create_variable_for_type_inference(
        dtype, shape=shape,
        lod_level=max(1, getattr(y, 'lod_level', 0) or 0))
    helper.append_op(type='sequence_expand',
                     inputs={'X': [x], 'Y': [y]}, outputs={'Out': [tmp]},
                     attrs={'ref_level': ref_level})
    return tmp


def _reduce_layer(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    helper.append_op(
        type=op_type, inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'dim': dim if dim is not None else [0],
               'keep_dim': keep_dim,
               'reduce_all': True if dim is None else False})
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer('reduce_sum', input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer('reduce_mean', input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer('reduce_max', input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer('reduce_min', input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer('reduce_prod', input, dim, keep_dim, name)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper('dropout', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     stop_gradient=True)
    helper.append_op(type='dropout', inputs={'X': [x]},
                     outputs={'Out': [out], 'Mask': [mask]},
                     attrs={'dropout_prob': dropout_prob, 'is_test': is_test,
                            'fix_seed': seed is not None, 'seed': seed or 0})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper('split', **locals())
    input_shape = input.shape
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = None
    else:
        num = len(num_or_sections)
        sections = list(num_or_sections)
    outs = [helper.create_variable_for_type_inference(dtype=input.dtype)
            for _ in range(num)]
    helper.append_op(
        type='split', inputs={'X': [input]}, outputs={'Out': outs},
        attrs={'num': num_or_sections if isinstance(num_or_sections, int) else 0,
               'sections': sections or [], 'axis': dim})
    return outs


def ctc_greedy_decoder(input, blank, name=None):
    helper = LayerHelper("ctc_greedy_decoder", **locals())
    ctc_out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(type="ctc_align", inputs={"Input": [input]},
                     outputs={"Output": [ctc_out]},
                     attrs={"merge_repeated": True, "blank": blank})
    return ctc_out


def edit_distance(input, label, normalized=True, ignored_tokens=None):
    helper = LayerHelper("edit_distance", **locals())
    edit_distance_out = helper.create_variable_for_type_inference(dtype="float32")
    sequence_num = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(type="edit_distance",
                     inputs={"Hyps": [input], "Refs": [label]},
                     outputs={"Out": [edit_distance_out],
                              "SequenceNum": [sequence_num]},
                     attrs={"normalized": normalized,
                            "ignored_tokens": ignored_tokens or []})
    return edit_distance_out, sequence_num


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    if len(x.shape) == 1:
        axis = 0
    helper = LayerHelper("l2_normalize", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="norm" if False else "l2_normalize",
                     inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": 1 if axis is None else axis,
                            "epsilon": epsilon})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper('matmul', **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type='matmul', inputs={'X': [x], 'Y': [y]},
                     outputs={'Out': [out]},
                     attrs={'transpose_X': transpose_x,
                            'transpose_Y': transpose_y,
                            'alpha': float(alpha)})
    return out


def fused_attention(q, k, v, key_bias=None, causal=False, scale=None,
                    name=None, window=None, aligned_window=None,
                    summary=None, summary_every=None):
    """Whole-attention fused op: softmax(q k^T * scale + bias) v in ONE op.

    q/k/v: [B, H, T, D]; k and v may have FEWER heads than q (grouped
    key-value heads: H_q a multiple of H_kv, key-value head h serving query
    heads h * H_q/H_kv and following; the rule repeats them, so the
    kernels see equal counts). key_bias: optional [B, Tk] (or [B,1,1,Tk]) additive
    bias for padded keys; causal adds lower-triangular masking. ``window``
    (causal only) is a sliding window: query i sees the keys
    i - window + 1 .. i, its own position counting as one; None, or a
    window as long as the sequence, is plain causal attention. On TPU this
    lowers to the pallas flash-attention kernel (paddle_tpu.ops), which
    never materializes the [B,H,Tq,Tk] score matrix in HBM and visits only
    the band of blocks a window touches; elsewhere it falls back to the
    XLA chain with the same mask. Replaces the reference's
    matmul->softmax->matmul op sequence (nets.py
    scaled_dot_product_attention).

    ``aligned_window`` W (causal only, in place of ``window``) cuts the row
    into ALIGNED windows of W positions, which do not slide: query t sees
    the keys of its own window up to t. ``summary=(kbar, vbar)``, both
    [B, H_kv, T / summary_every, D] (layers.chunk_softmax_pool makes them),
    adds one summary key and value for every ``summary_every`` consecutive
    positions: a query also sees every summary whose positions lie in a
    window BEFORE its own, and its output is ONE softmax over both sets
    (EVA, arXiv:2302.04542, as EvaByte sizes it). On TPU the exact part is
    the causal kernels over rows of W, the summary part the same kernels on
    a staircase grid that holds only admitted blocks, merged by their
    log-sum-exp; neither set's scores reach HBM.
    """
    # refused here as the kernels would refuse it at the lowering
    from ...ops.flash_attention import _window_of
    _window_of(window, causal, int(q.shape[2]))
    if aligned_window is None:
        if summary is not None or summary_every is not None:
            raise ValueError('fused_attention: summaries belong to aligned '
                             'windows; give aligned_window')
    else:
        t = int(q.shape[2])
        if not causal or window is not None or key_bias is not None \
                or int(k.shape[2]) != t:
            raise ValueError(
                'fused_attention: aligned_window is causal self-attention '
                'without a sliding window or a key bias')
        if int(aligned_window) < 1 or t % int(aligned_window):
            raise ValueError('fused_attention: aligned windows of %r do not '
                             'divide a row of %d' % (aligned_window, t))
        if (summary is None) != (summary_every is None):
            raise ValueError('fused_attention: summary=(kbar, vbar) and '
                             'summary_every come together')
        if summary is not None and (
                int(summary_every) < 1
                or int(aligned_window) % int(summary_every)
                or any(int(s.shape[2]) * int(summary_every) != t
                       or int(s.shape[1]) != int(k.shape[1])
                       for s in summary)):
            raise ValueError(
                'fused_attention: one summary every %r positions of a row '
                'of %d in windows of %d, a head a key head: got %r'
                % (summary_every, t, aligned_window,
                   [tuple(s.shape) for s in summary]))
    h_q, h_kv = int(q.shape[1]), int(k.shape[1])
    if h_kv != int(v.shape[1]) or h_kv <= 0 or h_q % h_kv:
        raise ValueError('fused_attention: %d query heads over %d key and '
                         '%d value heads' % (h_q, h_kv, int(v.shape[1])))
    helper = LayerHelper('fused_attention', **locals())
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    inputs = {'Q': [q], 'K': [k], 'V': [v]}
    if key_bias is not None:
        inputs['KeyBias'] = [key_bias]
    attrs = {'causal': bool(causal),
             'scale': float(scale) if scale is not None else -1.0}
    if window is not None:
        attrs['window'] = int(window)
    if aligned_window is not None:
        attrs['aligned_window'] = int(aligned_window)
    if summary is not None:
        inputs['SummaryK'], inputs['SummaryV'] = [summary[0]], [summary[1]]
        attrs['summary_every'] = int(summary_every)
    helper.append_op(type='flash_attention', inputs=inputs,
                     outputs={'Out': [out]}, attrs=attrs)
    return out


def chunk_softmax_pool(k, v, mu, phi, chunk=16, scale=None, name=None):
    """One learned SUMMARY key and value for every ``chunk`` consecutive
    positions of the heads ``k``, ``v`` [B, H, T, D] (EVA's pooled key and
    control-variate value, arXiv:2302.04542 section 4, with EvaByte's two
    learned vectors a head, ``mu`` and ``phi`` [H, D], in place of the
    paper's sampled feature), in ONE op:

        a_m = softmax over the chunk of (mu . k_m)            kbar = sum a_m k_m
        b_m = softmax over the chunk of (scale * phi . k_m)   vbar = sum b_m v_m

    Both softmaxes read the KEYS; ``scale`` (default D^-0.5) is on phi's
    logits alone. Returns ``(kbar, vbar)``, [B, H, T / chunk, D] each, for
    ``fused_attention(summary=(kbar, vbar), summary_every=chunk)``.
    Logits, weights and sums in float32 under AMP too; the backward keeps
    k, v and the two [B, H, T] weight arrays (reshape, softmax,
    elementwise_mul and reduce_sum would keep eight arrays of k's size).
    TPU extension (the reference predates it)."""
    t, h, d = int(k.shape[2]), int(k.shape[1]), int(k.shape[3])
    chunk = int(chunk)
    if chunk < 1 or t % chunk or tuple(k.shape[:3]) != tuple(v.shape[:3]):
        raise ValueError('chunk_softmax_pool: chunks of %r over keys %r and '
                         'values %r' % (chunk, tuple(k.shape),
                                        tuple(v.shape)))
    for vec in (mu, phi):
        if tuple(int(n) for n in vec.shape) != (h, d):
            raise ValueError('chunk_softmax_pool: a learned vector a head, '
                             '[%d, %d], got %r' % (h, d, tuple(vec.shape)))
    helper = LayerHelper('chunk_softmax_pool', **locals())
    kbar = helper.create_variable_for_type_inference(dtype=k.dtype)
    vbar = helper.create_variable_for_type_inference(dtype=v.dtype)
    helper.append_op(
        type='chunk_softmax_pool',
        inputs={'K': [k], 'V': [v], 'Mu': [mu], 'Phi': [phi]},
        outputs={'KBar': [kbar], 'VBar': [vbar]},
        attrs={'chunk': chunk,
               'scale': float(scale) if scale is not None else -1.0})
    return kbar, vbar


def latent_attention(input, size, num_heads, q_lora_rank, kv_lora_rank,
                     qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                     rope_theta=10000.0, epsilon=1e-05, param_attr=None,
                     name=None, rope_interleave=False, head_gate=False):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section
    2.1), causal, over ``input`` [B, T, d]: queries, keys and values are
    made from low-rank latents, and the rotary part of a key is ONE head
    that all query heads share.

        cq           = rms_norm(input Wqa)              d -> q_lora_rank
        q            = cq Wqb      per head [q_nope | q_rope]
        [ckv | kr]   = input Wkva             d -> kv_lora_rank + rope dim
        [k_nope | v] = rms_norm(ckv) Wkvb     per head
        q_rope, kr   = rotary (rotate-half over the whole rope part)
        q_h = [q_nope_h | q_rope_h];  k_h = [k_nope_h | kr]
        out = concat_h(causal_softmax(q_h k_h^T / sqrt(width)) v_h) Wo

    ``q_lora_rank=None``: no query latent, ``q = input Wq`` (no norm).
    `v_head_dim` is free of the keys' width ``qk_nope_head_dim +
    qk_rope_head_dim``: fused_attention and the flash kernels take the
    values and the output at their own width. ``rope_interleave`` turns
    neighbouring pairs (2j, 2j + 1) of the rope part
    (layers.rotary_embedding). ``head_gate=True`` multiplies each head's
    output, before Wo, by ``sigmoid(input Wgate)``, Wgate [d, heads]: one
    gate a head a token.

    No biases. Built from fc, rms_norm, split, rotary_embedding, expand,
    concat and fused_attention (the flash kernels on the TPU). What the
    training step keeps of it is the recompute region's to say
    (fluid.recompute_guard). Parameters in creation order: Wqa, the query
    latent's norm, Wqb (or Wq alone), Wkva, the key-value latent's norm,
    Wkvb, Wgate where there is one, Wo; `param_attr` (its initializer)
    serves the matrices. Returns [B, T, size]. TPU extension (the
    reference predates it)."""
    h, nope, rope = int(num_heads), int(qk_nope_head_dim), \
        int(qk_rope_head_dim)
    width, dv = nope + rope, int(v_head_dim)

    def proj(x, n):
        return fc(input=x, size=n, num_flatten_dims=2, bias_attr=False,
                  param_attr=copy.deepcopy(ParamAttr.to_attr(param_attr)))

    def heads(x, n, d):
        return transpose(reshape(x, shape=[0, 0, n, d]), perm=[0, 2, 1, 3])

    def rotary(x):
        return rotary_embedding(x, base=rope_theta,
                                interleave=bool(rope_interleave))

    cq = input if q_lora_rank is None else \
        rms_norm(proj(input, int(q_lora_rank)), epsilon=epsilon)
    q_nope, q_rope = split(heads(proj(cq, h * width), h, width),
                           [nope, rope], dim=-1)
    ckv, kr = split(proj(input, int(kv_lora_rank) + rope),
                    [int(kv_lora_rank), rope], dim=-1)
    k_nope, v = split(
        heads(proj(rms_norm(ckv, epsilon=epsilon), h * (nope + dv)), h,
              nope + dv), [nope, dv], dim=-1)
    q_rope = rotary(q_rope)
    kr = expand(rotary(heads(kr, 1, rope)), expand_times=[1, h, 1, 1])
    ctx = fused_attention(
        tensor_mod.concat([q_nope, q_rope], axis=-1),
        tensor_mod.concat([k_nope, kr], axis=-1), v, causal=True,
        scale=width ** -0.5)
    ctx = transpose(ctx, perm=[0, 2, 1, 3])                  # [B, T, h, dv]
    if head_gate:
        from . import ops
        ctx = ops.elementwise_mul(ctx, ops.sigmoid(proj(input, h)), axis=0)
    return proj(reshape(ctx, shape=[0, 0, h * dv]), int(size))


def topk(input, k, name=None):
    helper = LayerHelper("top_k", **locals())
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def warpctc(input, label, blank=0, norm_by_times=False):
    helper = LayerHelper('warpctc', **locals())
    loss_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    grad_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type='warpctc',
                     inputs={'Logits': [input], 'Label': [label]},
                     outputs={'WarpCTCGrad': [grad_out], 'Loss': [loss_out]},
                     attrs={'blank': blank, 'norm_by_times': norm_by_times})
    return loss_out


def sequence_reshape(input, new_dim):
    helper = LayerHelper('sequence_reshape', **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    helper.append_op(type='sequence_reshape', inputs={'X': [input]},
                     outputs={'Out': [out]}, attrs={'new_dim': new_dim})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper('transpose', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='transpose', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs={'axis': perm})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    padding = _pair(padding)
    if len(padding) == 2:
        padding = [padding[0], padding[1], padding[0], padding[1]]
    helper = LayerHelper('im2sequence', **locals())
    out = helper.create_variable_for_type_inference(dtype=helper.input_dtype())
    helper.append_op(type='im2sequence', inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'kernels': _pair(filter_size),
                            'strides': _pair(stride), 'paddings': padding})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper('row_conv', **locals())
    dtype = helper.input_dtype()
    filter_shape = [future_context_size + 1, input.shape[-1]]
    filter_param = helper.create_parameter(attr=helper.param_attr,
                                           shape=filter_shape, dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='row_conv',
                     inputs={'X': [input], 'Filter': [filter_param]},
                     outputs={'Out': [out]})
    return helper.append_activation(out)


def multiplex(inputs, index):
    helper = LayerHelper('multiplex', **locals())
    if not isinstance(inputs, list) or len(inputs) < 2:
        raise ValueError("multiplex needs >= 2 inputs")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type='multiplex',
                     inputs={'X': inputs, 'Ids': [index]},
                     outputs={'Out': [out]})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper('softmax_with_cross_entropy', **locals())
    softmax = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    helper.append_op(type='softmax_with_cross_entropy',
                     inputs={'Logits': [logits], 'Label': [label]},
                     outputs={'Softmax': [softmax], 'Loss': [loss]},
                     attrs={'soft_label': soft_label})
    return loss


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper('smooth_l1_loss', **locals())
    diff = helper.create_variable_for_type_inference(dtype=x.dtype)
    loss = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {'X': [x], 'Y': [y]}
    if inside_weight is not None:
        inputs['InsideWeight'] = [inside_weight]
    if outside_weight is not None:
        inputs['OutsideWeight'] = [outside_weight]
    helper.append_op(type='smooth_l1_loss', inputs=inputs,
                     outputs={'Diff': [diff], 'Out': [loss]},
                     attrs={'sigma': sigma if sigma is not None else 1.0})
    return loss


def one_hot(input, depth):
    helper = LayerHelper("one_hot", **locals())
    one_hot_out = helper.create_variable_for_type_inference(dtype='float32')
    helper.append_op(type="one_hot", inputs={'X': [input]},
                     attrs={'depth': depth},
                     outputs={'Out': [one_hot_out]})
    return one_hot_out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference nn.py:autoincreased_step_counter."""
    helper = LayerHelper('global_step_counter')
    counter_name = counter_name or '@STEP_COUNTER@'
    blk = helper.main_program.global_block()
    if counter_name in blk.vars:
        counter = blk.vars[counter_name]
    else:
        counter = helper.create_global_variable(
            name=counter_name, dtype='int64', shape=[1], persistable=True)
        helper.set_variable_initializer(
            counter, initializer=Constant(value=float(begin - 1)))
    helper.append_op(type='increment', inputs={'X': [counter]},
                     outputs={'Out': [counter]}, attrs={'step': float(step)},
                     infer_shape=False)
    counter.stop_gradient = True
    return counter


def reshape(x, shape, actual_shape=None, act=None, inplace=True, name=None):
    helper = LayerHelper("reshape", **locals())
    reshaped = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="reshape", inputs={"X": [x]},
                     outputs={"Out": [reshaped]},
                     attrs={"shape": [int(d) for d in shape]})
    return helper.append_activation(reshaped)


def lod_reset(x, y=None, target_lod=None):
    helper = LayerHelper("lod_reset", **locals())
    # the token buffer REGROUPS under the new lod: out is a sequence
    # [n_seqs(dynamic), T(dynamic), features...] where the features are
    # x's trailing dims (lowering flattens valid tokens and re-pads)
    new_lod = (getattr(y, 'lod_level', 0) or 1) if y is not None else 1
    shape = None
    if x.shape is not None:
        feat = (list(x.shape[2:]) if (x.lod_level or 0) > 0
                and len(x.shape) >= 3 else list(x.shape[1:]))
        shape = [-1, -1] + feat
    out = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=shape, lod_level=new_lod)
    if y is not None:
        helper.append_op(type="lod_reset", inputs={'X': [x], 'Y': [y]},
                         outputs={'Out': [out]})
    elif target_lod is not None:
        helper.append_op(type="lod_reset", inputs={'X': [x]},
                         attrs={'target_lod': list(target_lod)},
                         outputs={'Out': [out]})
    else:
        raise ValueError("y or target_lod must be set")
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper('lrn', **locals())
    dtype = helper.input_dtype()
    if len(input.shape) != 4:
        raise ValueError("Input of lrn must be 4-D (NCHW)")
    mid_out = helper.create_variable_for_type_inference(dtype,
                                                        stop_gradient=True)
    lrn_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type='lrn', inputs={'X': [input]},
                     outputs={'Out': [lrn_out], 'MidOut': [mid_out]},
                     attrs={'n': n, 'k': k, 'alpha': alpha, 'beta': beta})
    return lrn_out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper('pad', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='pad', inputs={'X': [x]}, outputs={'Out': [out]},
                     attrs={'paddings': list(paddings),
                            'pad_value': float(pad_value)})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    if epsilon > 1.0 or epsilon < 0.0:
        raise ValueError("epsilon must be in [0, 1]")
    helper = LayerHelper("label_smooth", **locals())
    label.stop_gradient = True
    smooth_label = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [smooth_label]},
                     attrs={"epsilon": float(epsilon)})
    return smooth_label


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    helper = LayerHelper('roi_pool', **locals())
    dtype = helper.input_dtype()
    pool_out = helper.create_variable_for_type_inference(dtype)
    argmaxes = helper.create_variable_for_type_inference(dtype='int32')
    helper.append_op(type="roi_pool",
                     inputs={"X": [input], "ROIs": [rois]},
                     outputs={"Out": [pool_out], "Argmax": [argmaxes]},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale})
    return pool_out


def dice_loss(input, label, epsilon=0.00001):
    helper = LayerHelper('dice_loss', **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(type="dice_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample='BILINEAR'):
    resample_methods = {'BILINEAR': 'bilinear_interp',
                        'NEAREST': 'nearest_interp'}
    if resample not in resample_methods:
        raise ValueError("resample must be BILINEAR or NEAREST")
    if out_shape is None and scale is None:
        raise ValueError("one of out_shape and scale must be set")
    helper = LayerHelper(resample_methods[resample], **locals())
    dtype = helper.input_dtype()
    inputs = {"X": [input]}
    if out_shape is not None:
        if isinstance(out_shape, Variable):
            inputs['OutSize'] = [out_shape]
            out_h = out_w = 0
        else:
            out_h, out_w = int(out_shape[0]), int(out_shape[1])
    else:
        out_h = int(input.shape[2] * scale)
        out_w = int(input.shape[3] * scale)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type=resample_methods[resample], inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"out_h": out_h, "out_w": out_w})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, 'BILINEAR')


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError("image_resize_short needs a 4-D (NCHW) input")
    hw = in_shape[2:4]
    short_idx = hw.index(min(hw))
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[1 - short_idx] = int(
        float(out_shape[1 - short_idx]) *
        (float(out_short_len) / float(hw[short_idx])) + 0.5)
    return image_resize(input=input, out_shape=out_shape, resample=resample)


def gather(input, index):
    helper = LayerHelper('gather', **locals())
    dtype = helper.input_dtype()
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="gather",
                     inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def expand(x, expand_times, name=None):
    """Tile each dim of x by expand_times (reference
    operators/expand_op.cc; the Python layer landed just after v0.14)."""
    helper = LayerHelper('expand', **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='expand', inputs={'X': [x]},
                     outputs={'Out': [out]},
                     attrs={'expand_times': list(expand_times)})
    return out


def scatter(input, index, updates, name=None):
    helper = LayerHelper('scatter', **locals())
    dtype = helper.input_dtype()
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="scatter",
                     inputs={"X": [input], "Ids": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]})
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="random_crop", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper('mean_iou', **locals())
    dtype = helper.input_dtype()
    out_mean_iou = helper.create_variable_for_type_inference(dtype='float32')
    out_wrong = helper.create_variable_for_type_inference(dtype='int32')
    out_correct = helper.create_variable_for_type_inference(dtype='int32')
    helper.append_op(type="mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [out_mean_iou],
                              "OutWrong": [out_wrong],
                              "OutCorrect": [out_correct]},
                     attrs={"num_classes": num_classes})
    return out_mean_iou, out_wrong, out_correct


def relu(x, name=None):
    helper = LayerHelper('relu', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="relu", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def log(x, name=None):
    helper = LayerHelper('log', name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="log", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper('crop', **locals())
    if offsets is None:
        offsets = [0] * len(x.shape)
    out = helper.create_variable_for_type_inference(x.dtype)
    ipts = {'X': [x]}
    attrs = {'offsets': list(offsets)}
    if isinstance(shape, Variable):
        ipts['Y'] = [shape]
    else:
        attrs['shape'] = list(shape)
    helper.append_op(type='crop', inputs=ipts, outputs={'Out': [out]},
                     attrs=attrs)
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper('rank_loss', **locals())
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type='rank_loss',
                     inputs={"Label": [label], "Left": [left],
                             "Right": [right]},
                     outputs={'Out': [out]})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper('prelu', **locals())
    if mode not in ['all', 'channel', 'element']:
        raise ValueError('mode should be one of all, channel, element')
    alpha_shape = [1]
    if mode == 'channel':
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == 'element':
        alpha_shape = list(x.shape)
        alpha_shape[0] = 1
    dtype = 'float32'
    alpha = helper.create_parameter(attr=ParamAttr.to_attr(param_attr),
                                    shape=alpha_shape, dtype='float32',
                                    is_bias=False,
                                    default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="prelu", inputs={"X": [x], 'Alpha': [alpha]},
                     attrs={"mode": mode}, outputs={"Out": [out]})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper('flatten', **locals())
    if not (isinstance(axis, int)) or axis > len(x.shape) or axis < 0:
        raise ValueError("axis must be in [0, rank(x)]")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type='flatten', inputs={"X": [x]},
                     outputs={'Out': [out]}, attrs={"axis": axis})
    return out


def sequence_mask(x, maxlen=None, dtype='int64', name=None):
    helper = LayerHelper('sequence_mask', **locals())
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type='sequence_mask', inputs={'X': [x]},
                     outputs={'Y': [out]},
                     attrs={'maxlen': maxlen if maxlen is not None else -1,
                            'out_dtype': dtype})
    return out


def stack(x, axis=0):
    helper = LayerHelper('stack', **locals())
    if not isinstance(x, list) and not isinstance(x, tuple):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type='stack', inputs={'X': x}, outputs={'Y': [out]},
                     attrs={'axis': axis})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None):
    """Noise-contrastive estimation (reference nn.py:nce)."""
    helper = LayerHelper('nce', **locals())
    dim = input.shape[1]
    num_true_class = label.shape[1] if len(label.shape) > 1 else 1
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    b = helper.create_parameter(attr=helper.bias_attr,
                                shape=[num_total_classes, 1],
                                dtype=input.dtype, is_bias=True)
    cost = helper.create_variable_for_type_inference(dtype=input.dtype)
    sample_logits = helper.create_variable_for_type_inference(dtype=input.dtype)
    sample_labels = helper.create_variable_for_type_inference(dtype=label.dtype)
    num_neg_samples = 10 if num_neg_samples is None else int(num_neg_samples)
    inputs = {'Input': [input], 'Label': [label], 'Weight': [w], 'Bias': [b]}
    if sample_weight is not None:
        inputs['SampleWeight'] = [sample_weight]
    helper.append_op(type='nce', inputs=inputs,
                     outputs={'Cost': [cost], 'SampleLogits': [sample_logits],
                              'SampleLabels': [sample_labels]},
                     attrs={'num_total_classes': int(num_total_classes),
                            'num_neg_samples': num_neg_samples,
                            'num_true_classes': num_true_class})
    return cost / (num_neg_samples + 1)


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None):
    """Hierarchical sigmoid (reference nn.py:hsigmoid)."""
    helper = LayerHelper('hierarchical_sigmoid', **locals())
    dim = input.shape[1]
    weights = helper.create_parameter(attr=helper.param_attr,
                                      shape=[num_classes - 1, dim],
                                      dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    pre_out = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"X": [input], "W": [weights], "Label": [label]}
    if helper.bias_attr:
        bias = helper.create_parameter(attr=helper.bias_attr,
                                       shape=[1, num_classes - 1],
                                       dtype=input.dtype, is_bias=True)
        inputs['Bias'] = [bias]
    helper.append_op(type="hierarchical_sigmoid", inputs=inputs,
                     outputs={"Out": [out], "PreOut": [pre_out]},
                     attrs={"num_classes": num_classes})
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id, level=0,
                name=None, return_parent_idx=False):
    """One beam-search step (reference nn.py:2658 +
    operators/beam_search_op.cc): dense [batch*beam] layout on TPU with
    explicit parent pointers instead of LoD lineage."""
    helper = LayerHelper('beam_search', **locals())
    selected_scores = helper.create_variable_for_type_inference('float32')
    selected_ids = helper.create_variable_for_type_inference('int64')
    parent_idx = helper.create_variable_for_type_inference('int64')
    helper.append_op(type='beam_search',
                     inputs={'pre_ids': [pre_ids],
                             'pre_scores': [pre_scores],
                             'ids': [ids], 'scores': [scores]},
                     outputs={'selected_ids': [selected_ids],
                              'selected_scores': [selected_scores],
                              'parent_idx': [parent_idx]},
                     attrs={'level': level, 'beam_size': beam_size,
                            'end_id': end_id})
    if return_parent_idx:
        return selected_ids, selected_scores, parent_idx
    return selected_ids, selected_scores


def beam_search_decode(ids, scores, beam_size=None, end_id=0, parents=None,
                       name=None):
    """reference nn.py:2770. Dense contract: ids/scores are stacked
    [T, batch, beam] tensors (use layers.stack over per-step outputs);
    `parents` carries the beam lineage emitted by beam_search. Tokens past
    each sentence's first end_id come out as end_id (padding). beam_size is
    taken from the tensor shape; the arg is accepted for API parity."""
    helper = LayerHelper('beam_search_decode', **locals())
    sentence_ids = helper.create_variable_for_type_inference('int64')
    sentence_scores = helper.create_variable_for_type_inference('float32')
    inputs = {"Ids": [ids], "Scores": [scores]}
    if parents is not None:
        inputs["Parents"] = [parents]
    helper.append_op(type="beam_search_decode",
                     inputs=inputs,
                     outputs={"SentenceIds": [sentence_ids],
                              "SentenceScores": [sentence_scores]},
                     attrs={'end_id': end_id})
    return sentence_ids, sentence_scores


def moe_mlp(input, num_experts, hidden_size, size=None, act='relu',
            capacity_factor=2.0, gate_param_attr=None, param_attr=None,
            bias_attr=None, name=None, top_k=1, return_aux_loss=False,
            gated=False, norm_topk_prob=True, return_expert_count=False,
            experts_held=None, scoring='softmax', selection_bias=False,
            gate_scale=1.0, router_input=None, norm_eps=None, n_group=1,
            topk_group=1):
    """Top-k gated mixture-of-experts FFN (TPU extension; the reference
    predates MoE — its conditional-computation ancestor is layers.Switch).

    Each of `num_experts` experts is a two-layer MLP
    ``act(x @ w1 + b1) @ w2 + b2`` with hidden width `hidden_size`, or with
    ``gated=True`` the three-matrix form ``(act(x @ w1) * (x @ w3)) @ w2``
    (SwiGLU with ``act='swish'``); ``bias_attr=False`` gives experts
    without biases. Tokens are routed top-k by a learned linear gate whose
    logits and softmax stay float32 under AMP. top_k=1 uses Switch-style
    raw-probability gates; top_k>=2 renormalizes the selected gates per
    token (GShard) unless ``norm_topk_prob=False`` (OLMoE: raw
    probabilities).

    `capacity_factor` a number: fixed capacity (capacity_factor * top_k *
    tokens / experts slots an expert; overflow DROPPED, all first choices
    claiming slots before any second choice). Under ParallelExecutor or a
    DistributeTranspiler mesh whose dp size divides num_experts, experts
    are sharded num_experts/dp-per-device and dispatch rides two
    all_to_alls (paddle_tpu.parallel.moe); otherwise experts run locally
    with identical semantics.

    ``capacity_factor=None``: dropless. Every assignment is computed,
    however uneven the router: the tokens x top_k assignments are sorted
    by expert and run as grouped matmuls. One device only; on a mesh that
    would shard the experts the step raises
    ``paddle_tpu.parallel.moe.DroplessOnMeshError``.

    ``experts_held=(first, count)`` (dropless only): this device's SHARE
    of an expert-parallel layer. The router, the top-k, the gates'
    renormalisation, the auxiliary loss and `expert_count` stay over all
    `num_experts`; the weight stacks are ``[count, ...]`` and hold experts
    ``first .. first + count - 1``; the output is the part of the layer's
    sum that those experts give (what the absent experts would add is
    left out: on the pod the shares are summed by the exchange, which one
    chip runs without). No assignment to a held expert is dropped at any
    imbalance, and assignments to absent experts cost no matmul tile.
    ``None``: every expert is here.

    ``scoring='sigmoid'`` (dropless only) scores each expert by itself,
    sigmoid(logit), in place of the softmax over all (DeepSeek-V3,
    arXiv:2412.19437); the gates are the chosen scores, renormalised over
    the chosen under `norm_topk_prob`. ``selection_bias=True`` creates a
    float32 ``[num_experts]`` persistable that starts at 0, is no
    trainable parameter (no gradient, no optimizer state) and is added to
    the scores for the CHOICE of the top k alone: the gates are taken
    from the scores without it. It is returned last, for the model to
    move by the experts' load (layers.router_bias_update). `gate_scale`
    multiplies the gates after the renormalisation. Both come with the
    sigmoid router and are refused under ``scoring='softmax'``.
    ``norm_eps`` is what the renormalisation adds to the chosen scores'
    sum (``None``: the scoring's own, 0 under 'softmax' and DeepSeek-V3's
    1e-20 under 'sigmoid'; LFM2's router carries 1e-6).

    ``n_group`` G > 1 with ``topk_group`` (the sigmoid router's:
    DeepSeek-V3's group-limited routing) confines the choice to the best
    `topk_group` of G groups of ``num_experts / G`` consecutive experts, a
    group ranked by the sum of its two largest score + bias; the gates, a
    held share and `expert_count` are what they are without groups.

    ``router_input`` (dropless only): a tensor of `input`'s shape that the
    ROUTER reads in place of `input`: the logits are ``router_input @
    gate_w`` and `input` feeds the experts alone (SmallThinker,
    arXiv:2507.20984: the router reads the layer's normed input BEFORE
    attention, so that a deployment fetches the experts while attention
    runs; the experts read the normed state after it). The router's
    gradient then reaches `router_input` and the experts' reaches
    `input`. The auxiliary loss, `expert_count`, a held share and either
    scoring take the logits wherever they came from. ``None``: one tensor
    serves both.

    With return_aux_loss=True, also returns the scalar Switch/GShard
    load-balancing auxiliary loss (E * sum_e f_e * P_e, minimized at 1.0
    by a uniform router) to add to the training objective with a small
    weight, e.g. ``cost = cost + 0.01 * aux``. With
    return_expert_count=True, also the step's assignments per expert
    ([num_experts] int32, summing to tokens x top_k where nothing drops).

    input: [N, d] tokens or [B, T, d] sequence activations.
    Returns the same shape with the last dim `size` (default d); with the
    return_* flags or `selection_bias` a tuple (out[, aux_loss]
    [, expert_count][, selection_bias]).
    """
    from ..ops_impl.moe_ops import supported_acts
    if (act or None) is not None and act not in supported_acts():
        raise ValueError(
            "moe_mlp act=%r is not supported; pick one of %s"
            % (act, sorted(a for a in supported_acts() if a)))
    if not 1 <= int(top_k) <= int(num_experts):
        raise ValueError('moe_mlp top_k=%r must be in [1, num_experts=%d]'
                         % (top_k, num_experts))
    if scoring not in ('softmax', 'sigmoid'):
        raise ValueError("moe_mlp scoring=%r: 'softmax' or 'sigmoid'"
                         % (scoring,))
    if capacity_factor is not None and (
            scoring != 'softmax' or selection_bias or gate_scale != 1.0):
        raise ValueError('moe_mlp: scoring, selection_bias and gate_scale '
                         "are the dropless layer's; pass "
                         'capacity_factor=None')
    if scoring == 'softmax' and (selection_bias or gate_scale != 1.0):
        raise ValueError("moe_mlp: selection_bias and gate_scale belong to "
                         "scoring='sigmoid' (no model here has them under "
                         'a softmax router)')
    if int(n_group) > 1:
        if scoring != 'sigmoid':
            raise ValueError("moe_mlp: n_group belongs to scoring='sigmoid' "
                             '(no model here has groups under a softmax '
                             'router)')
        per_group = int(num_experts) // int(n_group)
        if per_group * int(n_group) != int(num_experts) or not (
                1 <= int(topk_group) <= int(n_group)) or \
                int(top_k) > int(topk_group) * per_group:
            raise ValueError('moe_mlp: %r groups of which %r stay do not '
                             'hold the top %r of %r experts'
                             % (n_group, topk_group, top_k, num_experts))
    if router_input is not None:
        if capacity_factor is not None:
            raise ValueError("moe_mlp: router_input is the dropless "
                             "layer's; pass capacity_factor=None")
        if tuple(router_input.shape) != tuple(input.shape):
            raise ValueError('moe_mlp: router_input %r has not the shape '
                             'of input %r' % (tuple(router_input.shape),
                                              tuple(input.shape)))
    n_held = int(num_experts)
    if experts_held is not None:
        first, n_held = (int(i) for i in experts_held)
        if capacity_factor is not None:
            raise ValueError('moe_mlp: experts_held is the dropless '
                             "layer's; pass capacity_factor=None")
        if not (0 <= first and 0 < n_held
                and first + n_held <= int(num_experts)):
            raise ValueError('moe_mlp experts_held=%r is not a range of '
                             'the %d experts' % (experts_held, num_experts))
    helper = LayerHelper('moe_mlp', **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    out_d = int(size) if size is not None else d
    from ..param_attr import ParamAttr
    gate_w = helper.create_parameter(attr=ParamAttr.to_attr(gate_param_attr),
                                     shape=[d, num_experts], dtype=dtype,
                                     is_bias=False)

    def weight(shape):
        # a copy a weight: one ParamAttr object names one parameter
        return helper.create_parameter(
            attr=copy.deepcopy(ParamAttr.to_attr(param_attr)), shape=shape,
            dtype=dtype, is_bias=False)

    inputs = {'X': [input], 'GateW': [gate_w],
              'W1': [weight([n_held, d, hidden_size])]}
    if router_input is not None:
        inputs['RouterX'] = [router_input]
    if gated:
        inputs['W3'] = [weight([n_held, d, hidden_size])]
    inputs['W2'] = [weight([n_held, hidden_size, out_d])]
    if bias_attr is not False:
        if gated:
            raise ValueError('moe_mlp: gated experts have no biases; pass '
                             'bias_attr=False')
        for slot, width in (('B1', hidden_size), ('B2', out_d)):
            inputs[slot] = [helper.create_parameter(
                attr=copy.deepcopy(ParamAttr.to_attr(bias_attr)),
                shape=[n_held, width], dtype=dtype, is_bias=True)]
    bias = None
    if selection_bias:
        bias = helper.create_parameter(
            attr=ParamAttr(trainable=False), shape=[int(num_experts)],
            dtype='float32', default_initializer=Constant(0.0))
        bias.stop_gradient = True
        inputs['SelectionBias'] = [bias]
    # shapes declared here: inference stands the dynamic batch in by a
    # large prime, and at real widths batch x seq x top_k assignments pass
    # what the dropless path's int32 sort indices hold
    out = helper.create_variable_for_type_inference(
        dtype, shape=list(input.shape[:-1]) + [out_d])
    aux = helper.create_variable_for_type_inference('float32', shape=[])
    outputs = {'Out': [out], 'AuxLoss': [aux]}
    count = None
    if return_expert_count:
        count = helper.create_variable_for_type_inference(
            'int32', shape=[int(num_experts)], stop_gradient=True)
        outputs['ExpertCount'] = [count]
    attrs = {'num_experts': int(num_experts),
             'dropless': capacity_factor is None,
             'capacity_factor': float(capacity_factor or 0.0),
             'top_k': int(top_k),
             'norm_topk_prob': bool(norm_topk_prob),
             'act': act or ''}
    if experts_held is not None:
        attrs['experts_held'] = [first, n_held]
    if scoring != 'softmax':
        attrs['scoring'] = scoring
    if gate_scale != 1.0:
        attrs['gate_scale'] = float(gate_scale)
    if norm_eps is not None:
        attrs['norm_eps'] = float(norm_eps)
    if int(n_group) > 1:
        attrs['n_group'], attrs['topk_group'] = int(n_group), int(topk_group)
    helper.append_op(type='moe_mlp', inputs=inputs, outputs=outputs,
                     attrs=attrs)
    got = (out,) + ((aux,) if return_aux_loss else ()) \
        + ((count,) if return_expert_count else ()) \
        + ((bias,) if selection_bias else ())
    return got if len(got) > 1 else out


def router_bias_update(bias, expert_count, rate=0.001):
    """Moves a router's selection bias by the step's load, the
    auxiliary-loss-free balancing of DeepSeek-V3 (arXiv:2412.19437,
    section 2.1.2): ``b_e <- b_e + rate * sign(mean(c) - c_e)`` with `c`
    the step's assignments per expert (`moe_mlp`'s expert count): an
    expert that got less than the mean is chosen a little more easily the
    next step. Plain ops (cast, reduce_mean, elementwise_sub, sign, scale,
    assign) written back into `bias`, so the update is part of the one
    compiled step; build it after ``minimize``. No gradient and no
    optimizer is involved. Build-time counter `moe.bias_updates`."""
    from ... import obs
    from . import ops as ops_mod
    obs.counter('moe.bias_updates').inc()
    load = tensor_mod.cast(expert_count, 'float32')
    over = ops_mod.sign(ops_mod.elementwise_sub(
        load, reduce_mean(load, keep_dim=True)))
    return tensor_mod.assign(
        ops_mod.elementwise_sub(bias, ops_mod.scale(over, scale=float(rate))),
        output=bias)
