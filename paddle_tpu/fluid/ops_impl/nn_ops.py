"""NN rules: conv/pool/norm/dropout/softmax/losses/metrics/image.

Parity: reference paddle/fluid/operators/{conv,pool,batch_norm,layer_norm,
dropout,softmax,cross_entropy,accuracy,auc,lrn,prelu,interpolate,...}_op.* —
cuDNN descriptors replaced by lax.conv_general_dilated / reduce_window, which
XLA tiles directly onto the TPU MXU.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ... import obs
from ..lowering import register, data_of, like, amp_cast


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


@register('conv2d')
def _conv2d(ins, attrs, ctx):
    """Conv in NCHW (reference operators/conv_op.cc) or NHWC
    (`data_format` attr — the layout XLA:TPU lays out natively, so NHWC
    feeds skip the compiler's transposes). Filter is always OIHW
    [out_c, in_c/groups, kh, kw] so weights are layout-portable."""
    x = data_of(ins['Input'][0])
    w = data_of(ins['Filter'][0])
    strides = _pair(attrs.get('strides', 1))
    pads = _pair(attrs.get('paddings', 0))
    dilations = _pair(attrs.get('dilations', 1))
    groups = attrs.get('groups', 1) or 1
    fmt = attrs.get('data_format', 'NCHW')
    in_dtype = x.dtype
    xc, wc = amp_cast(ctx, x, w.astype(x.dtype))
    # no preferred_element_type here: conv_general_dilated's transpose
    # (grad) rule feeds the f32 cotangent straight back into a bf16 conv
    # and trips a dtype mismatch; XLA:TPU accumulates bf16 convs in f32
    # internally regardless, so a plain bf16 conv + cast is equivalent
    out = lax.conv_general_dilated(
        xc, wc,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=(fmt, 'OIHW', fmt))
    return {'Output': out.astype(in_dtype)}


@register('conv3d')
def _conv3d(ins, attrs, ctx):
    x = data_of(ins['Input'][0])
    w = data_of(ins['Filter'][0])
    strides = _pair(attrs.get('strides', 1), 3)
    pads = _pair(attrs.get('paddings', 0), 3)
    dilations = _pair(attrs.get('dilations', 1), 3)
    groups = attrs.get('groups', 1) or 1
    out = lax.conv_general_dilated(
        x, w.astype(x.dtype), strides,
        [(p, p) for p in pads], rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    return {'Output': out}


@register('conv2d_transpose')
def _conv2d_transpose(ins, attrs, ctx):
    """reference operators/conv_transpose_op.cc. Filter [in_c, out_c/g, kh, kw].
    Implemented as lhs-dilated conv (the XLA-native transposed conv)."""
    x = data_of(ins['Input'][0])
    w = data_of(ins['Filter'][0])
    strides = _pair(attrs.get('strides', 1))
    pads = _pair(attrs.get('paddings', 0))
    dilations = _pair(attrs.get('dilations', 1))
    groups = attrs.get('groups', 1) or 1
    kh = (w.shape[2] - 1) * dilations[0] + 1
    kw = (w.shape[3] - 1) * dilations[1] + 1
    # flip spatial dims, swap in/out channel axes -> OIHW for the fwd conv
    wt = jnp.flip(w, axis=(2, 3))
    if groups > 1:
        ci, co_g = w.shape[0], w.shape[1]
        wt = wt.reshape(groups, ci // groups, co_g, w.shape[2], w.shape[3])
        wt = jnp.swapaxes(wt, 1, 2).reshape(groups * co_g, ci // groups,
                                            w.shape[2], w.shape[3])
    else:
        wt = jnp.swapaxes(wt, 0, 1)
    out = lax.conv_general_dilated(
        x, wt.astype(x.dtype),
        window_strides=(1, 1),
        padding=[(kh - 1 - pads[0], kh - 1 - pads[0]),
                 (kw - 1 - pads[1], kw - 1 - pads[1])],
        lhs_dilation=strides,
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    return {'Output': out}


@register('conv3d_transpose')
def _conv3d_transpose(ins, attrs, ctx):
    x = data_of(ins['Input'][0])
    w = data_of(ins['Filter'][0])
    strides = _pair(attrs.get('strides', 1), 3)
    pads = _pair(attrs.get('paddings', 0), 3)
    dilations = _pair(attrs.get('dilations', 1), 3)
    ks = [(w.shape[2 + i] - 1) * dilations[i] + 1 for i in range(3)]
    wt = jnp.flip(w, axis=(2, 3, 4))
    wt = jnp.swapaxes(wt, 0, 1)
    out = lax.conv_general_dilated(
        x, wt.astype(x.dtype), (1, 1, 1),
        [(k - 1 - p, k - 1 - p) for k, p in zip(ks, pads)],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    return {'Output': out}


def _pool(x, pool_type, ksize, strides, pads, global_pooling, exclusive=True,
          ceil_mode=False, channels_last=False):
    nd = len(ksize)
    if global_pooling:
        ksize = x.shape[1:1 + nd] if channels_last else x.shape[2:]
        pads = (0,) * nd
        strides = (1,) * nd

    def full(spatial, fill):
        # spatial window dims sit at [1..nd] for NHWC, [2..nd+1] for NCHW
        return ((fill,) + tuple(spatial) + (fill,)) if channels_last \
            else ((fill, fill) + tuple(spatial))

    window = full(ksize, 1)
    strides_full = full(strides, 1)
    pad_full = full(((p, p) for p in pads), (0, 0))
    if ceil_mode:
        pad_full = full(((p, p + s - 1) for p, s in zip(pads, strides)),
                        (0, 0))
    if pool_type == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides_full, pad_full)
    ssum = lax.reduce_window(x, 0.0, lax.add, window, strides_full, pad_full)
    if exclusive:
        # valid-count divisor: identical for every batch/channel, so count
        # over a singleton-batch/channel ones array and let broadcasting
        # expand it. Counting over full x.shape makes XLA constant-fold a
        # [B, C, H, W] reduce_window at COMPILE time — tens of seconds per
        # pool layer in a ResNet compile.
        shape1 = (1,) + tuple(x.shape[1:1 + nd]) + (1,) if channels_last \
            else (1, 1) + x.shape[2:]
        ones = jnp.ones(shape1, dtype=x.dtype)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides_full,
                                pad_full)
        return ssum / cnt
    return ssum / float(np.prod(ksize))


@register('pool2d')
def _pool2d(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    out = _pool(x, attrs.get('pooling_type', 'max'),
                _pair(attrs['ksize']), _pair(attrs.get('strides', 1)),
                _pair(attrs.get('paddings', 0)),
                attrs.get('global_pooling', False),
                attrs.get('exclusive', True), attrs.get('ceil_mode', False),
                channels_last=attrs.get('data_format', 'NCHW') == 'NHWC')
    return {'Out': out}


@register('pool3d')
def _pool3d(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    out = _pool(x, attrs.get('pooling_type', 'max'),
                _pair(attrs['ksize'], 3), _pair(attrs.get('strides', 1), 3),
                _pair(attrs.get('paddings', 0), 3),
                attrs.get('global_pooling', False),
                attrs.get('exclusive', True), attrs.get('ceil_mode', False))
    return {'Out': out}


@register('batch_norm')
def _batch_norm(ins, attrs, ctx):
    """reference operators/batch_norm_op.cc. Train: batch stats + running
    update; test: running stats. NCHW or NHWC via data_layout."""
    x = data_of(ins['X'][0])
    scale = data_of(ins['Scale'][0])
    bias = data_of(ins['Bias'][0])
    mean = data_of(ins['Mean'][0])
    var = data_of(ins['Variance'][0])
    eps = attrs.get('epsilon', 1e-5)
    momentum = attrs.get('momentum', 0.9)
    is_test = attrs.get('is_test', False) or ctx.is_test
    layout = attrs.get('data_layout', 'NCHW')
    c_axis = 1 if layout == 'NCHW' else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        xf = x.astype(jnp.float32)
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(use_mean)
        mean_out = mean * momentum + use_mean * (1 - momentum)
        var_out = var * momentum + use_var * (1 - momentum)
        saved_mean = use_mean
        saved_var = use_var
    inv = lax.rsqrt(use_var + eps)
    y = (x - use_mean.reshape(bshape).astype(x.dtype)) * \
        (inv * scale).reshape(bshape).astype(x.dtype) + \
        bias.reshape(bshape).astype(x.dtype)
    return {'Y': y, 'MeanOut': mean_out, 'VarianceOut': var_out,
            'SavedMean': saved_mean, 'SavedVariance': saved_var}


@register('layer_norm')
def _layer_norm(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    eps = attrs.get('epsilon', 1e-5)
    axis = attrs.get('begin_norm_axis', 1)
    red = tuple(range(axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=red, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    if ins.get('Scale'):
        scale = data_of(ins['Scale'][0]).reshape((1,) * axis + x.shape[axis:])
        y = y * scale
    if ins.get('Bias'):
        bias = data_of(ins['Bias'][0]).reshape((1,) * axis + x.shape[axis:])
        y = y + bias
    return {'Y': like(ins['X'][0], y.astype(x.dtype)),
            'Mean': mean.reshape(x.shape[:axis]),
            'Variance': var.reshape(x.shape[:axis])}


@register('rms_norm')
def _rms_norm(ins, attrs, ctx):
    """y = scale * x * rsqrt(mean(x^2) + epsilon) over the last axis (Zhang
    and Sennrich 2019; no reference counterpart). The statistics are taken
    in float32 whatever the input's dtype, under AMP too; the result has
    the input's dtype. With the attribute `unit_offset` the parameter is
    the scale's offset from one: y = (1 + scale) * x * rsqrt(...)."""
    x = data_of(ins['X'][0])
    obs.counter('rms_norm.lowered').inc()          # trace time
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                    + attrs.get('epsilon', 1e-5))
    scale = data_of(ins['Scale'][0]).astype(jnp.float32)
    if attrs.get('unit_offset', False):
        scale = 1.0 + scale
    y = xf * inv * scale
    return {'Y': like(ins['X'][0], y.astype(x.dtype))}


@register('rotary_embedding')
def _rotary_embedding(ins, attrs, ctx):
    """Rotary position embedding (Su et al. 2021) of X [B, H, T, D] at
    positions 0..T-1, rotate-half pairing: element i of a head turns with
    element i + D/2 by the angle t * base^(-2i/D). With `rotary_dim` R < D
    the first R elements turn among themselves (pairs (i, i + R/2), angle
    t * base^(-2i/R)) and the other D - R pass through. The tables of
    sines and cosines are constants of the step, computed on the host in
    float64 and rounded once: at position 4095 one float32 rounding of a
    frequency turns the angle by 2e-4 rad, which moved OLMoE's attention
    output by 1e-4 between two float32 programs on the chip (PR 26) and
    with it a few tokens' choice of experts. The product is float32; the
    result has the input's dtype. The attribute `interleave` pairs
    neighbours, (2i, 2i + 1) at the same angle, in place of (i, i + R/2).
    Trace-time counter `rotary.lowered`,
    labelled `rotary_dim=` where the rotation is partial."""
    x = data_of(ins['X'][0])
    t, d = x.shape[-2], x.shape[-1]
    rd = int(attrs.get('rotary_dim') or d)
    obs.counter('rotary.lowered',
                **({'rotary_dim': rd} if rd != d else {})).inc()
    half = rd // 2
    inv_freq = float(attrs.get('base', 10000.0)) ** (
        -np.arange(half, dtype=np.float64) * 2.0 / rd)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)  # [T, R/2]
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    xf = x.astype(jnp.float32)
    if attrs.get('interleave'):
        # neighbours (2i, 2i + 1) turn together, each where it is
        x1, x2 = xf[..., 0:rd:2], xf[..., 1:rd:2]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(xf.shape[:-1] + (rd,))
        y = jnp.concatenate([turned, xf[..., rd:]], axis=-1)
        return {'Out': y.astype(x.dtype)}
    x1, x2 = xf[..., :half], xf[..., half:rd]
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                         xf[..., rd:]], axis=-1)
    return {'Out': y.astype(x.dtype)}


@register('dropout')
def _dropout(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    p = attrs.get('dropout_prob', 0.5)
    is_test = attrs.get('is_test', False) or ctx.is_test
    if is_test:
        # downgrade_in_infer (default impl in the reference)
        return {'Out': like(ins['X'][0], x * (1.0 - p)), 'Mask': None}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    return {'Out': like(ins['X'][0], x * mask), 'Mask': mask}


@register('softmax')
def _softmax(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    return {'Out': like(ins['X'][0], jax.nn.softmax(x, axis=-1))}


@register('cross_entropy')
def _cross_entropy(ins, attrs, ctx):
    """X: probs [N, C]; Label int64 [N, 1] (or probs if soft_label)."""
    x = data_of(ins['X'][0])
    label = data_of(ins['Label'][0])
    eps = 1e-8
    if attrs.get('soft_label', False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        li = label.astype(jnp.int32)
        if li.ndim == x.ndim:
            li = jnp.squeeze(li, -1)
        picked = jnp.take_along_axis(x, li[..., None], axis=-1)
        loss = -jnp.log(picked + eps)
    return {'Y': like(ins['X'][0], loss)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xent(x, label, soft):
    """-sum_v label * log_softmax(x) in closed form, with its own backward:
    the row maximum, then ONE pass over the logits whose sibling reductions
    are sum exp(x - m) and, for soft labels, sum label * (x - m) and
    sum label (a hard label's term is gathered from the logits). No
    [N, V] array but the logits is a result of the forward, and the
    backward holds no reduction: `log_softmax` followed by a pick writes
    the whole log-probability array to pick one number a row, and its
    gradient walks the logits once more for a sum that is the label row's
    (PERF.md, PR 29)."""
    return _xent_fwd(x, label, soft)[0]


def _xent_fwd(x, label, soft):
    m = jnp.max(x, axis=-1, keepdims=True)
    z = x - m
    s = jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    if soft:
        # computed, not assumed 1: rows that do not sum to 1 keep the loss
        # and the gradient log_softmax gave them
        w = jnp.sum(label, axis=-1, keepdims=True)
        loss = w * jnp.log(s) - jnp.sum(label * z, axis=-1, keepdims=True)
    else:
        w = None
        loss = jnp.log(s) - (
            jnp.take_along_axis(x, label[..., None], axis=-1) - m)
    return loss, (x, label, m, s, w)


def _xent_bwd(soft, res, g):
    """dx = g * (softmax(x) * w - label), what a row shares folded into
    [N] vectors first. The projection's two backward matmuls take an
    elementwise dx into their operand fusions and rebuild it there, exp
    and all, once per output tile. Soft labels leave it so: that is one
    walk over the logits fewer than `log_softmax` made, whatever the
    projection's width. Hard labels write dx once, which moves no more
    bytes than the old rule's log-probability array did: at OLMoE's width
    (2048) the matmuls then run 7 ms a step faster than rebuilding it, at
    Transformer-base's (512) they would not (chip, PR 29; PERF.md has
    both sides, and why the width is not this rule's to see)."""
    x, label, m, s, w = res
    z = x - m
    if soft:
        dx = jnp.exp(z) * (g * w / s) - g * label
        dlabel = -g * (z - jnp.log(s))
        return dx.astype(x.dtype), dlabel.astype(label.dtype)
    # the gather's own convention: a negative id counts from the end
    y = jnp.where(label < 0, label + x.shape[-1], label)[..., None]
    hit = lax.broadcasted_iota(y.dtype, x.shape, x.ndim - 1) == y
    dx = jnp.exp(z) * (g / s) - jnp.where(hit, g, 0)
    return lax.optimization_barrier(dx), None


_xent.defvjp(_xent_fwd, _xent_bwd)


@register('softmax_with_cross_entropy')
def _softmax_with_cross_entropy(ins, attrs, ctx):
    logits = data_of(ins['Logits'][0])
    label = data_of(ins['Label'][0])
    soft = bool(attrs.get('soft_label', False))
    # trace time: once per op per lowering, never per step
    obs.counter('xent.lowered', label='soft' if soft else 'hard').inc()
    if not soft:
        label = label.astype(jnp.int32)
        if label.ndim == logits.ndim:
            label = jnp.squeeze(label, -1)
    # dead unless fetched: XLA drops it
    sm = jax.nn.softmax(logits, axis=-1)
    return {'Softmax': sm,
            'Loss': like(ins['Logits'][0], _xent(logits, label, soft))}


@register('sigmoid_cross_entropy_with_logits')
def _sigmoid_xent(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    label = data_of(ins['Label'][0])
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {'Out': like(ins['X'][0], loss)}


@register('smooth_l1_loss')
def _smooth_l1(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    y = data_of(ins['Y'][0])
    sigma = attrs.get('sigma', 1.0)
    s2 = sigma * sigma
    d = x - y
    if ins.get('InsideWeight'):
        d = d * data_of(ins['InsideWeight'][0])
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    if ins.get('OutsideWeight'):
        loss = loss * data_of(ins['OutsideWeight'][0])
    out = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {'Out': out, 'Diff': d}


@register('rank_loss')
def _rank_loss(ins, attrs, ctx):
    label = data_of(ins['Label'][0])
    left = data_of(ins['Left'][0])
    right = data_of(ins['Right'][0])
    d = left - right
    out = jnp.log1p(jnp.exp(d)) - label * d
    return {'Out': out}


@register('dice_loss')
def _dice_loss(ins, attrs, ctx):
    x = data_of(ins['X'][0])  # probs
    label = data_of(ins['Label'][0]).astype(x.dtype)
    eps = attrs.get('epsilon', 1e-5)
    red = tuple(range(1, x.ndim))
    inter = 2.0 * jnp.sum(x * label, axis=red)
    union = jnp.sum(x, axis=red) + jnp.sum(label, axis=red)
    return {'Out': jnp.mean(1.0 - (inter + eps) / (union + eps))}


@register('huber_loss')
def _huber_loss(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    y = data_of(ins['Y'][0])
    delta = attrs.get('delta', 1.0)
    d = jnp.abs(y - x)
    loss = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return {'Out': loss, 'Residual': y - x}


@register('accuracy')
def _accuracy(ins, attrs, ctx):
    """inputs: Out (topk values), Indices (topk ids), Label. reference
    operators/accuracy_op.cu."""
    idx = data_of(ins['Indices'][0]).astype(jnp.int64)
    label = data_of(ins['Label'][0]).astype(jnp.int64)
    if label.ndim < idx.ndim:
        label = label[..., None]
    correct = jnp.any(idx == label, axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.int32))
    total = correct.size
    # shape [1] like the reference (accuracy_op InferShape dims {1}):
    # verbatim scripts index the fetched value as acc_np[0]
    acc = (num_correct.astype(jnp.float32) / float(total)).reshape(1)
    return {'Accuracy': acc, 'Correct': num_correct,
            'Total': jnp.asarray(total, dtype=jnp.int32)}


@register('auc')
def _auc(ins, attrs, ctx):
    """Streaming AUC over persistable confusion buckets (reference
    operators/auc_op.cc). States: StatPos/StatNeg histograms."""
    probs = data_of(ins['Predict'][0])
    label = data_of(ins['Label'][0]).reshape(-1)
    stat_pos = data_of(ins['StatPos'][0])
    stat_neg = data_of(ins['StatNeg'][0])
    num_t = stat_pos.shape[0]
    p1 = probs[:, 1] if probs.ndim == 2 and probs.shape[1] >= 2 else probs.reshape(-1)
    bucket = jnp.clip((p1 * num_t).astype(jnp.int32), 0, num_t - 1)
    is_pos = (label > 0)
    pos_hist = jnp.zeros((num_t,), jnp.int64).at[bucket].add(is_pos.astype(jnp.int64))
    neg_hist = jnp.zeros((num_t,), jnp.int64).at[bucket].add((~is_pos).astype(jnp.int64))
    new_pos = stat_pos + pos_hist
    new_neg = stat_neg + neg_hist
    # AUC = (sum over thresholds of neg_below * pos_at + .5*neg_at*pos_at)/(P*N)
    pos = new_pos.astype(jnp.float64)
    neg = new_neg.astype(jnp.float64)
    tot_pos = jnp.cumsum(pos)
    tot_neg = jnp.cumsum(neg)
    area = jnp.sum((tot_neg - neg * 0.5) * pos)
    denom = jnp.maximum(tot_pos[-1] * tot_neg[-1], 1.0)
    auc = (area / denom).astype(jnp.float32)
    return {'AUC': auc, 'StatPosOut': new_pos, 'StatNegOut': new_neg}


@register('lrn')
def _lrn(ins, attrs, ctx):
    x = data_of(ins['X'][0])  # NCHW
    n = attrs.get('n', 5)
    k = attrs.get('k', 2.0)
    alpha = attrs.get('alpha', 1e-4)
    beta = attrs.get('beta', 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {'Out': x / jnp.power(mid, beta), 'MidOut': mid}


@register('prelu')
def _prelu(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    alpha = data_of(ins['Alpha'][0])
    mode = attrs.get('mode', 'all')
    if mode == 'all':
        a = alpha.reshape(())
    elif mode == 'channel':
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        a = alpha.reshape((1,) + x.shape[1:])
    return {'Out': jnp.where(x >= 0, x, a * x)}


def _resize(x, out_h, out_w, method):
    n, c, h, w = x.shape
    return jax.image.resize(x, (n, c, out_h, out_w), method=method)


@register('bilinear_interp')
def _bilinear_interp(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    if ins.get('OutSize'):
        raise ValueError(
            "image_resize with a runtime OutSize tensor is data-dependent "
            "shape — unsupported under XLA; pass a static out_shape list")
    out_h, out_w = attrs['out_h'], attrs['out_w']
    return {'Out': _resize(x, out_h, out_w, 'bilinear')}


@register('nearest_interp')
def _nearest_interp(ins, attrs, ctx):
    x = data_of(ins['X'][0])
    out_h, out_w = attrs['out_h'], attrs['out_w']
    return {'Out': _resize(x, out_h, out_w, 'nearest')}


@register('roi_pool')
def _roi_pool(ins, attrs, ctx):
    """reference operators/roi_pool_op.cc. ROIs: [R, 4] (x1,y1,x2,y2) with
    batch id in RoisLod-free single-image mode; here ROIs carry batch index
    via first column when 5-wide."""
    x = data_of(ins['X'][0])
    rois = data_of(ins['ROIs'][0])
    ph = attrs['pooled_height']
    pw = attrs['pooled_width']
    scale = attrs.get('spatial_scale', 1.0)
    n, c, h, w = x.shape

    if rois.shape[-1] == 5:
        batch_ids = rois[:, 0].astype(jnp.int32)
        boxes = rois[:, 1:]
    else:
        batch_ids = jnp.zeros((rois.shape[0],), jnp.int32)
        boxes = rois

    def pool_one(bid, box):
        img = x[bid]
        x1 = jnp.round(box[0] * scale).astype(jnp.int32)
        y1 = jnp.round(box[1] * scale).astype(jnp.int32)
        x2 = jnp.round(box[2] * scale).astype(jnp.int32)
        y2 = jnp.round(box[3] * scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)
        ys = jnp.arange(h)
        xs = jnp.arange(w)
        # bin index of each pixel, -1 if outside roi
        ybin = jnp.floor((ys - y1).astype(jnp.float32) / (rh / ph)).astype(jnp.int32)
        xbin = jnp.floor((xs - x1).astype(jnp.float32) / (rw / pw)).astype(jnp.int32)
        yvalid = (ys >= y1) & (ys <= y2)
        xvalid = (xs >= x1) & (xs <= x2)
        ybin = jnp.clip(ybin, 0, ph - 1)
        xbin = jnp.clip(xbin, 0, pw - 1)
        neg = jnp.full(img.shape, -jnp.inf, img.dtype)
        masked = jnp.where(yvalid[None, :, None] & xvalid[None, None, :], img, neg)
        out = jnp.full((c, ph, pw), -jnp.inf, img.dtype)
        out = out.at[:, ybin[:, None], xbin[None, :]].max(masked)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    out = jax.vmap(pool_one)(batch_ids, boxes)
    return {'Out': out, 'Argmax': None}


@register('mean_iou')
def _mean_iou(ins, attrs, ctx):
    pred = data_of(ins['Predictions'][0]).reshape(-1).astype(jnp.int32)
    label = data_of(ins['Labels'][0]).reshape(-1).astype(jnp.int32)
    num_classes = attrs['num_classes']
    idx = label * num_classes + pred
    cm = jnp.zeros((num_classes * num_classes,), jnp.float32).at[idx].add(1.0)
    cm = cm.reshape(num_classes, num_classes)
    inter = jnp.diag(cm)
    union = cm.sum(0) + cm.sum(1) - inter
    valid = union > 0
    iou = jnp.where(valid, inter / jnp.maximum(union, 1.0), 0.0)
    mean_iou = jnp.sum(iou) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return {'OutMeanIou': mean_iou, 'OutWrong': jnp.sum(cm, axis=1) - inter,
            'OutCorrect': inter}


@register('im2sequence')
def _im2sequence(ins, attrs, ctx):
    """reference operators/im2sequence_op.cc: NCHW image -> sequence of
    flattened patches [N, out_h*out_w, C*kh*kw] (dense-padded layout)."""
    x = data_of(ins['X'][0])
    kh, kw = _pair(attrs['kernels'])
    sh, sw = _pair(attrs.get('strides', 1))
    p = attrs.get('paddings', [0, 0, 0, 0])
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[2] if len(p) > 2 else p[0]),
                     (p[1] if len(p) > 1 else p[0], p[3] if len(p) > 3 else p[0])))
    patches = lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), 'VALID',
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))  # [N, C*kh*kw, oh, ow]
    ckk = patches.shape[1]
    seq = patches.reshape(n, ckk, -1).transpose(0, 2, 1)  # [N, oh*ow, C*kh*kw]
    from ..lowering import SeqValue
    lengths = jnp.full((n,), seq.shape[1], jnp.int32)
    return {'Out': SeqValue(seq, lengths)}


def _aligned_attention(ctx, q, k, v, summary, attrs, scale):
    """`flash_attention` with the attribute `aligned_window`: causal
    attention exact inside aligned windows, and over the summaries of the
    windows before a query's own where the op has them (`SummaryK`,
    `SummaryV`, one every `summary_every` positions), under one softmax.
    On the TPU the two geometries of the flash kernels and their merge
    (ops/flash_attention.py flash_attention_summary) where the staircase
    has tiles for the sizes; elsewhere the XLA chain over the same masks,
    dense."""
    from ...ops.flash_attention import (
        flash_attention_summary, reference_attention_summary,
        summary_blocks)
    window = int(attrs['aligned_window'])
    every = attrs.get('summary_every')
    if getattr(ctx, 'mesh', None) is not None:
        raise ValueError(
            'flash_attention: aligned windows (aligned_window=%d) on a mesh '
            'are not built: the per-shard call and the sp bodies know the '
            'causal edge and the sliding window alone' % window)
    kbar, vbar = amp_cast(ctx, *summary) if summary else (None, None)
    kernel = ctx.platform == 'tpu' and (
        not summary or summary_blocks(window, int(every)) is not None)
    obs.counter('flash.aligned', way='kernel' if kernel else 'xla',
                summaries='true' if summary else 'false').inc()  # trace time
    if kernel:
        return flash_attention_summary(
            q, k, v, kbar, vbar, window=window, every=every, sm_scale=scale,
            interpret=False)
    return reference_attention_summary(
        q, k, v, kbar, vbar, window=window, every=every, sm_scale=scale)


@register('flash_attention')
def _flash_attention(ins, attrs, ctx):
    """Fused attention: pallas flash kernel on TPU, XLA chain elsewhere.
    Replaces the reference's matmul+softmax+matmul op sequence — see
    paddle_tpu/ops/flash_attention.py for the kernel."""
    from ... import ops as tpu_ops
    q = data_of(ins['Q'][0])
    k = data_of(ins['K'][0])
    v = data_of(ins['V'][0])
    kb = ins.get('KeyBias')
    kb = data_of(kb[0]) if kb else None
    if kb is not None:
        kb = kb.reshape(kb.shape[0], kb.shape[-1])
    scale = attrs.get('scale', -1.0)
    scale = None if scale is None or scale < 0 else float(scale)
    causal = bool(attrs.get('causal', False))
    window = attrs.get('window')
    window = None if window is None else int(window)
    aligned = attrs.get('aligned_window')
    summary = [data_of(ins[s][0]) for s in ('SummaryK', 'SummaryV')] \
        if ins.get('SummaryK') else None
    if k.shape[1] != q.shape[1]:
        # grouped key-value heads: key-value head h serves the query heads
        # h * group and following. A repeat, whose transpose sums a group's
        # gradients; the kernels see equal head counts, as before
        group = q.shape[1] // k.shape[1]
        obs.counter('flash.grouped', q_heads=q.shape[1],
                    kv_heads=k.shape[1], head_dim=q.shape[3]).inc()
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        if summary:
            summary = [jnp.repeat(t, group, axis=1) for t in summary]
    q, k, v = amp_cast(ctx, q, k, v)
    if aligned is not None:
        return {'Out': _aligned_attention(ctx, q, k, v, summary, attrs,
                                          scale)}
    # off the TPU the sp bodies run their kernels interpreted and the
    # plain op takes the XLA chain
    interpret = ctx.pallas_interpret
    mesh = getattr(ctx, 'mesh', None)
    if mesh is not None and 'sp' in getattr(mesh, 'shape', {}):
        # sequence-parallel mesh (SequenceParallelTranspiler): the O(T^2)
        # attention distributes over the sp axis as a ppermute ring; each
        # device holds O(T/sp) keys (flash blocks on TPU, dense on CPU)
        sp = mesh.shape['sp']
        strategy = attrs.get('sp_strategy', 'ring')
        if window is not None:
            raise ValueError(
                'flash_attention: a sliding window (window=%d) on a '
                'sequence-parallel mesh is not built: the ring and ulysses '
                'bodies know the causal edge alone' % window)
        if 'sp' in getattr(ctx, 'manual_axes', ()):
            # already INSIDE a shard_map manual over sp (the pipeline
            # region): q/k/v arrive sequence-LOCAL [B, H, T/sp, D]; call
            # the per-shard collective bodies directly — nesting another
            # shard_map here would be invalid
            if strategy == 'ulysses':
                from ...parallel.ulysses import ulysses_attention
                out = ulysses_attention(q, k, v, 'sp', key_bias=kb,
                                        causal=causal, sm_scale=scale,
                                        interpret=interpret)
            else:
                from ...parallel.ring_attention import ring_attention
                out = ring_attention(q, k, v, 'sp', key_bias=kb,
                                     causal=causal, sm_scale=scale,
                                     interpret=interpret)
            return {'Out': out}
        if q.shape[2] % sp or k.shape[2] % sp:
            raise ValueError(
                'sequence parallelism: the sp mesh axis size %d must '
                'divide the seq lens %d/%d'
                % (sp, q.shape[2], k.shape[2]))
        if strategy == 'ulysses':
            from ...parallel.ulysses import ulysses_self_attention
            out = ulysses_self_attention(mesh, q, k, v, axis='sp',
                                         key_bias=kb, causal=causal,
                                         sm_scale=scale,
                                         interpret=interpret)
        else:
            from ...parallel.ring_attention import ring_self_attention
            out = ring_self_attention(mesh, q, k, v, axis='sp', key_bias=kb,
                                      causal=causal, sm_scale=scale,
                                      interpret=interpret)
    elif ctx.platform == 'tpu':
        if mesh is not None and not ctx.manual_axes:
            # GSPMD has no partitioning rule for the bare Mosaic call
            out = tpu_ops.flash_attention_sharded(
                mesh, q, k, v, key_bias=kb, causal=causal, sm_scale=scale,
                window=window, interpret=False)
        else:
            out = tpu_ops.flash_attention(q, k, v, key_bias=kb,
                                          causal=causal, sm_scale=scale,
                                          window=window, interpret=False)
    else:
        out = tpu_ops.reference_attention(q, k, v, key_bias=kb,
                                          causal=causal, sm_scale=scale,
                                          window=window)
    return {'Out': out}
