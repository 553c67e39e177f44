"""The flash kernels at their default tiles, the grouped-matmul kernels at
theirs, the delta rule's chunk kernels, the causal convolution's, the
state-space scan's and a held share's row add, compiled by Mosaic for a DESCRIBED v5e (no chip,
nothing runs): what the
interpreter and jax.export cannot refuse — VMEM the kernel may not have,
slices Mosaic will not tile — is refused here. The cells' shapes, and the
shapes on either side of the default-tile rule (_default_tile) and of the
backward's schedule. The
topology is described inside a fixture and in this file only: one process
at a time may load the TPU's library."""
import functools
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops

from util import flash_schedules as _schedules


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile_grad(one_chip, dtype, shape, causal):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    kb = jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.float32,
                              sharding=one_chip)

    def loss(q, k, v, kb):
        o = ops.flash_attention(q, k, v, key_bias=kb, causal=causal,
                                interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, kb).compile()


# The backward's schedule (not causal, causal) that the rule gives each
# shape: 'tile' where a head's scores are one tile (PR 27), 'head' on the
# triangular grid (PR 42), 'two' passes. Mosaic calls of a forward and
# backward: 2 in one pass, 3 in two.
@pytest.mark.parametrize('causal', [False, True], ids=['full', 'causal'])
@pytest.mark.parametrize('dtype,shape,schedules', [
    ('bfloat16', (16, 8, 1024, 64), ('tile', 'tile')),  # tfm_s1024: one 1024
                                          # tile; causal forward 512,
                                          # backward in one pass
    ('bfloat16', (64, 8, 256, 64), ('tile', 'tile')),   # tfm_s256: one 256
    ('bfloat16', (2, 8, 1536, 64), ('two', 'head')),    # 1024 would pad to
                                                        # 2048: 512
    ('bfloat16', (4, 8, 1024, 256), ('tile', 'tile')),  # the widest rows
                                                        # 1024 tiles take
    ('float32', (2, 8, 2048, 128), ('two', 'head')),    # the same 512 bytes
    ('float32', (4, 8, 1024, 256), ('two', 'head')),    # wider: refused at
                                                        # 1024, so 512 (256)
    ('float32', (16, 8, 1024, 64), ('tile', 'tile')),   # the cells' shapes in
    ('float32', (64, 8, 256, 64), ('tile', 'tile')),    # a Program without
                                                        # AMP: one pass too
    ('float32', (2, 8, 1024, 128), ('tile', 'tile')),   # 512 bytes a row
    ('float32', (2, 8, 512, 512), ('tile', 'tile')),    # the widest one 512
                                                        # tile holds
    ('bfloat16', (2, 16, 4096, 128), ('two', 'head')),  # olmoe_s4096: 36
                                                        # tile pairs
], ids=lambda x: x if isinstance(x, str) else
    'x'.join(map(str, x)) if isinstance(x[0], int) else None)
def test_default_tiles_compile_for_v5e(one_chip, dtype, shape, schedules,
                                       causal):
    before = _schedules()
    compiled = _compile_grad(one_chip, dtype, shape, causal)
    after = _schedules()
    schedule = schedules[causal]
    assert {s: after[s] - before[s] for s in after} == {
        s: int(s == schedule) for s in after}
    assert compiled.as_text().count('tpu_custom_call') == (
        3 if schedule == 'two' else 2)


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('rows', [32768, 65536], ids=['b1', 'b2'])
def test_grouped_matmul_tiles_compile_for_v5e(one_chip, dtype, rows):
    """olmoe_s4096's expert matmuls (rows = batch x 4096 x 8 assignments,
    64 experts of 2048 x 1024 and back), forward and both gradients, in the
    cell's bf16 and in its float32 check's arithmetic: megablox's tgmm
    runs out of VMEM at the tile its gmm takes (chip, PR 26), so each call
    has its own (ops/kernels/grouped_matmul.py TILES)."""
    from paddle_tpu.ops.kernels.grouped_matmul import grouped_matmul
    dt = jnp.dtype(dtype)
    x = jax.ShapeDtypeStruct((rows, 2048), dt, sharding=one_chip)
    up = jax.ShapeDtypeStruct((64, 2048, 1024), dt, sharding=one_chip)
    down = jax.ShapeDtypeStruct((64, 1024, 2048), dt, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)

    def loss(x, up, down, sizes):
        h = grouped_matmul(x, up, sizes, False)
        return jnp.sum(grouped_matmul(h, down, sizes, False)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, up, down, sizes).compile()
    assert compiled.as_text().count('tpu_custom_call') == 6


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_grouped_matmul_takes_a_width_of_no_power_of_two(one_chip, dtype):
    """nemotron3nano_s8192's expert matmuls (the compact layout's 24576
    rows, 8 held experts of 2688 x 1856 and back, TWO matrices), forward
    and both gradients, in the cell's bf16 and in its float32 check's
    arithmetic: 1856 = 14.5 lane tiles, and a tile `_fit` halves must stay
    whole lane tiles (928 was refused: AOT, PR 40)."""
    from paddle_tpu.ops.kernels import grouped_matmul as gm
    dt = jnp.dtype(dtype)
    for tiles, k, n in ((gm.TILES[0], 2688, 1856), (gm.TILES[1], 1856, 2688),
                        (gm.TILES[2], 2688, 1856)):
        _, tk, tn = gm._fit(tiles, 24576, k, n, dt.itemsize)
        assert (tk % 128 == 0 or tk == k) and (tn % 128 == 0 or tn == n)
    x = jax.ShapeDtypeStruct((24576, 2688), dt, sharding=one_chip)
    up = jax.ShapeDtypeStruct((8, 2688, 1856), dt, sharding=one_chip)
    down = jax.ShapeDtypeStruct((8, 1856, 2688), dt, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def loss(x, up, down, sizes):
        h = gm.grouped_matmul(x, up, sizes, False)
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(dt)
        return jnp.sum(gm.grouped_matmul(h, down, sizes, False)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, up, down, sizes).compile()
    assert compiled.as_text().count('tpu_custom_call') == 6


@pytest.mark.parametrize('dtype,precision,schedule', [
    ('bfloat16', None, 'head'), ('float32', 'highest', 'head')],
    ids=['bf16', 'float32_highest'])
def test_keys_of_192_beside_values_of_128_compile_for_v5e(one_chip, dtype,
                                                          precision,
                                                          schedule):
    """ling3flash_s8192's one attention call (latent attention without a
    query latent: 32 heads, keys of 128 + 64 rotary, values of 128, one
    causal row of 8192) in the cell's bf16 and in its float32 check's
    arithmetic: Mosaic tiles the 192-wide q and k blocks as they are (no
    padding to 256), v, o, do and dv at 128; one pass over the head's
    triangle either way (float32 rows of 768 bytes take 256-tiles)."""
    dt = jnp.dtype(dtype)
    qk = jax.ShapeDtypeStruct((1, 4, 8192, 192), dt, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4, 8192, 128), dt, sharding=one_chip)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, sm_scale=192 ** -0.5,
                                interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    before = _schedules()
    with jax.default_matmul_precision(precision or 'default'):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            qk, qk, v).compile()
    after = _schedules()
    assert {k: after[k] - before[k] for k in after} == {
        'tile': 0, 'head': 1, 'two': 0, schedule: 1}
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 2
    assert 'bf16[1,4,8192,256]' not in text and 'f32[1,4,8192,256]' not in text


def _mosaic_calls(text):
    return [l for l in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in l]


def _scoped_vmem(calls):
    """What each Mosaic call uses of scoped VMEM, bytes."""
    return [int(n) for n in re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"0","size":"(\d+)"', '\n'.join(calls))]


@pytest.mark.parametrize('gate', ['head', 'channel'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_gated_delta_intra_compiles_for_v5e(one_chip, dtype, gate):
    """qwen3next_s8192's stage `gdn_intra` (128 chunks of 64 tokens, 16
    key heads serving 32 value heads of 128), forward and backward, in the cell's bf16 and in its
    float32 check's arithmetic, at the heads a grid step each takes:
    Mosaic slices no iota and broadcasts no [1, 1] both ways, which the
    interpreter lets pass (AOT, PR 34). `channel`: ling3flash_s8192's,
    a decay a channel and a key head a value head, from the op's own
    operands WHERE THE OP HOLDS THEM (ISSUE 58: one row of 8192 tokens,
    [1, 8192, 32, 128]; a block (1, 64, heads x 128) whose lanes the
    bodies slice by head; g not summed, q and k not normalised: the l2
    norm, q's scale and the running sum in VMEM, forward and backward; the
    sixth output G's last row and its cotangent the backward's operand),
    within Mosaic's default 16 MiB with no limit stated."""
    from paddle_tpu.ops.kernels import gated_delta_intra as kernel
    dt = jnp.dtype(dtype)
    channel = gate == 'channel'
    like = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    if channel:
        keys = x = like((1, 8192, 32, 128), dt)
        g_sum = like((1, 8192, 32, 128), jnp.float32)
        gate = like((1, 8192, 32), jnp.float32)
        stage = functools.partial(kernel.gated_delta_intra_tokens,
                                  norm=(True, 1e-6, 128 ** -0.5))
    else:
        keys = like((128, 1, 16, 64, 128), dt)
        x = like((128, 1, 32, 64, 128), dt)
        g_sum = gate = like((128, 1, 32, 64), jnp.float32)
        stage = kernel.gated_delta_intra

    def loss(q, k, v, g_sum, beta):
        return sum(jnp.sum(o.astype(jnp.float32)) for o in
                   stage(q, k, v, g_sum, beta, False))

    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        keys, keys, x, g_sum, gate).compile()
    # the forward (it writes T for the backward) and the backward
    calls = _mosaic_calls(compiled.as_text())
    assert len(calls) == 2
    assert all('"scoped_memory_configs":[]' in l for l in calls)
    used = _scoped_vmem(calls)
    assert len(used) == 2 and max(used) < 12 * 2 ** 20, used


def _delta_rule_vjp_rows(one_chip, dtype, channel):
    """`jax.vjp` of the whole op at a layer's shape (one row of 8192
    tokens, 32 value heads of 128; both stages as their kernels), compiled
    under the scope a Program gives it, and what
    tools/hlo_scope_bytes.py counts of XLA's own instructions there:
    (a row an instruction that moves bytes, the scope's marks). The
    Mosaic calls' lines stay out: their bodies are serialized there. With
    a decay a channel q, k, v and g come and their cotangents go as the
    model's projections and convolutions hold them, [1, 8192, 32 x 128],
    through the model's `reshape` (an op of its own, outside the scope):
    on the TPU a [.., 32, 128] array is tiled by (32, 128) and one
    [.., 4096] by (tokens, 4096), so the view is where the operands
    are."""
    import importlib.util
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    spec = importlib.util.spec_from_file_location(
        'hlo_scope_bytes', os.path.join(os.path.dirname(__file__), '..',
                                        'tools', 'hlo_scope_bytes.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    dt = jnp.dtype(dtype)
    like = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    keys = like((1, 8192, 32 * 128) if channel else (1, 8192, 16, 128), dt)
    v = like((1, 8192, 32 * 128) if channel else (1, 8192, 32, 128), dt)
    beta = like((1, 8192, 32), jnp.float32)
    g = like((1, 8192, 32 * 128), jnp.float32) if channel else beta
    out = like((1, 8192, 32, 128), jnp.float32)

    def both(do, *a):
        by_head = a if not channel else tuple(
            x.reshape(x.shape[:2] + (32, 128)) for x in a[:4]) + a[4:]
        with jax.named_scope('gated_delta_rule_0'):
            o, pull = jax.vjp(lambda *a: la.gated_delta_rule(
                *a, chunk_size=64, qk_l2norm=True, kernel=True,
                scan_kernel=True, gate_floor=-5.0 if channel else None),
                *by_head)
            cts = pull(do)
        return (o,) + tuple(c.reshape(x.shape) for c, x in zip(cts, a))

    text = jax.jit(both).lower(out, keys, keys, v, g, beta).compile() \
        .as_text()
    # gdn_intra forward, forward again with T and backward; three walks
    calls = _mosaic_calls(text)
    assert len(calls) == 6
    # none states a limit, and all are under Mosaic's default
    assert all('"scoped_memory_configs":[]' in l for l in calls)
    assert max(_scoped_vmem(calls)) < (12 if dtype == 'bfloat16'
                                       else 14) * 2 ** 20
    return tool.account(text, r'gated_delta_rule_\d+')


def _delta_rule_vjp_account(one_chip, dtype, channel):
    """(XLA's bytes in the op's scope, how many of its instructions are a
    `reduce-window`, how many an `rsqrt`)."""
    rows, marks = _delta_rule_vjp_rows(one_chip, dtype, channel)
    return sum(r[3] for r in rows), marks['reduce_window'], marks['rsqrt']


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_per_channel_delta_rule_leaves_xla_no_sum_and_no_norm(one_chip,
                                                              dtype):
    """ling3flash_s8192's op, forward and backward: the per-channel
    kernels take g's running sum and q and k's l2 norms in VMEM, so XLA's
    part holds no `reduce-window` (jnp.cumsum on the TPU) and no `rsqrt`
    (PR 56: 6.23 GB an op in bf16 and 7.97 in float32 -> 2.71 and 3.92,
    the chunks' copies), and since ISSUE 58, whose kernels' index maps cut
    the chunks out of the arrays the op holds and hold g to its floor,
    no copy either: beta's layout, the chunk decay's `exp` and its
    cotangent, 0.03 GB an op either way (AOT, PR 58)."""
    moved, sums, norms = _delta_rule_vjp_account(one_chip, dtype, True)
    assert sums == 0 and norms == 0
    assert moved < 1.0e9, moved


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_per_channel_delta_rule_leaves_xla_no_chunk_copy(one_chip, dtype):
    """The same op's scope holds no instruction of XLA's that reads or
    writes an array of heads of 128 (a `[.., 64, 128]` chunked operand, a
    `[.., 32, 128]` or `[.., 4096]` one): no `transpose`, `copy`,
    `reshape` or select of q, k, v, g or of a cotangent is left between
    the op's neighbours and its six Mosaic calls, whose scoped VMEM is
    under 12 MiB in bf16 (14 in float32: the reverse walk's) with no limit
    stated. What is left is beta's and the chunk decay's, [.., 32] and
    [128, 1, 32, 128]."""
    rows, _ = _delta_rule_vjp_rows(one_chip, dtype, True)
    assert rows and not [r for r in rows if r[3] > 2 ** 24], rows
    assert not [r for r in rows if r[1] in ('transpose', 'copy')
                and r[3] > 2 ** 22], rows


def test_per_head_delta_rule_still_sums_and_norms_in_xla(one_chip):
    """qwen3next_s8192's op is the path ISSUE 56 leaves alone: G's sum
    and the norms of q and k are XLA's, ahead of the per-head kernel."""
    _, sums, norms = _delta_rule_vjp_account(one_chip, 'bfloat16', False)
    assert sums > 0 and norms > 0


@pytest.mark.parametrize('gate', ['head', 'channel'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_gated_delta_scan_compiles_for_v5e(one_chip, dtype, gate):
    """qwen3next_s8192's stage `gdn_scan` (one row of 128 chunks of 64
    tokens, 32 value heads of 128 x 128) on what the `gdn_intra` kernel
    hands over, in the cell's bf16 and in its float32 check's arithmetic,
    at eight heads a grid step: three Mosaic calls (forward, forward again
    for S at the chunks' starts, reverse), whose blocks twice over and
    whose scratch Mosaic's DEFAULT limit of 16 MiB holds (5.6 MiB in bf16;
    13.0 in float32, whose products at full precision are six passes of
    split operands): no call states a `vmem_limit_bytes` (ROADMAP.md Speed
    3 (c)), so a compile that passes here is under it. O is written and
    its cotangent read by head, as [B, T, H, Dv]. S at the starts (256
    MiB) is the one temporary larger than an operand. `channel`:
    ling3flash_s8192's walk, the chunk's decay a head's row of 128 lanes
    that the kernels turn to a column in VMEM, under the same limits."""
    from paddle_tpu.ops.kernels import gated_delta_scan as kernel
    dt = jnp.dtype(dtype)
    assert kernel.usable(64, 128, 128, 32, dt)
    assert kernel._heads(32) == 8
    wide = (128, 1, 32, 64, 128)
    like = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    xs = (like(wide, dt), like(wide, jnp.float32), like(wide, dt),
          like(wide, dt), like((128, 1, 32, 64, 64), dt),
          like((128, 1, 32) + ((128,) if gate == 'channel' else ()),
               jnp.float32))

    def loss(*xs):
        return jnp.sum(kernel.gated_delta_scan(xs, dt, False) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(*xs).compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 3
    # no limit stated, and what each call uses of VMEM under the default
    assert all('"scoped_memory_configs":[]' in l for l in calls)
    used = _scoped_vmem(calls)
    assert len(used) == 3 and max(used) < (
        6 if dtype == 'bfloat16' else 14) * 2 ** 20, used
    # the starts 256 MiB, O and its cotangent 128 each, the decays' rows
    assert compiled.memory_analysis().temp_size_in_bytes < 4.1 * 2 ** 27 + (
        0 if dtype == 'bfloat16' else 2 ** 28)


# a cell's depthwise convolution as its rule hands it to the op: tokens a
# row, channels, taps, activation
_CONVS = {'qwen3next': (8192, 8192, 4, 'silu'),
          'lfm2': (16384, 2048, 3, '')}


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('cell', sorted(_CONVS))
def test_causal_conv1d_compiles_for_v5e(one_chip, cell, dtype):
    """qwen3next_s8192's depthwise convolution (one row of 8192 tokens,
    8192 channels, four taps, silu) and lfm2_s16384's (one row of 16384,
    2048 channels, THREE taps, no activation: the kernel's first K = 3 and
    its first row of 16384) as the rule hands them to the op, forward and
    backward, in the cell's bf16 and in its float32 check's arithmetic, at
    the tile `tile_of` gives each: three blocks of a tile twice over are
    what the backward asks of VMEM."""
    from paddle_tpu.fluid.ops_impl.linear_attention_ops import causal_conv1d
    from paddle_tpu.ops.kernels import causal_conv1d as kernel
    tokens, channels, taps, act = _CONVS[cell]
    dt = jnp.dtype(dtype)
    assert kernel.usable(tokens, channels, taps, dt)
    x = jax.ShapeDtypeStruct((1, tokens, channels), dt, sharding=one_chip)
    w = jax.ShapeDtypeStruct((taps, channels), jnp.float32,
                             sharding=one_chip)

    def loss(x, w):
        return jnp.sum(causal_conv1d(x, w, act, True)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile()
    assert compiled.as_text().count('tpu_custom_call') == 2


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_causal_conv1d_with_a_bias_compiles_for_v5e(one_chip, dtype):
    """nemotron3nano_s8192's depthwise convolution (one row of 8192
    tokens, 6144 channels = 4096 + 2 x 8 x 128, four taps, a BIAS, silu),
    forward and backward: the bias is one more [1, tC] block each way and
    its gradient a third output of the backward's one call."""
    from paddle_tpu.fluid.ops_impl.linear_attention_ops import causal_conv1d
    from paddle_tpu.ops.kernels import causal_conv1d as kernel
    dt = jnp.dtype(dtype)
    assert kernel.usable(8192, 6144, 4, dt)
    x = jax.ShapeDtypeStruct((1, 8192, 6144), dt, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, 6144), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=one_chip)

    def loss(x, w, b):
        return jnp.sum(causal_conv1d(x, w, 'silu', True, b)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, w, b).compile()
    assert compiled.as_text().count('tpu_custom_call') == 2


# a cell's scan as its rule hands it to the op: groups, chunk
_SCANS = {'nemotron3nano': (8, 128), 'granite4hmicro': (1, 256)}


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('cell', sorted(_SCANS))
def test_ssd_scan_compiles_for_v5e(one_chip, cell, dtype):
    """nemotron3nano_s8192's state-space scan (one row of 8192 tokens, 64
    heads of 64 in 8 groups of state 128, chunks of 128, the skip) and
    granite4hmicro_s8192's (ONE group, chunks of 256) as the rule hands
    them to the op, forward and backward, in the cell's bf16 and in its
    float32 check's arithmetic, at the heads a grid step takes: two Mosaic
    calls within the default scoped VMEM (the kernels state no limit), the
    forward that keeps the starts and the backward, and the starts are all
    the float32 the op keeps between them."""
    from paddle_tpu.fluid.ops_impl.linear_attention_ops import ssd_scan
    from paddle_tpu.ops.kernels import ssd_scan as kernel
    groups, chunk = _SCANS[cell]
    dt = jnp.dtype(dtype)
    assert kernel.usable(chunk, 64, 128, 64 // groups, dt)
    assert kernel._heads(64 // groups, 64, chunk, dt.itemsize) == (
        4 if (chunk, dtype) == (256, 'float32') else 8)
    x = jax.ShapeDtypeStruct((1, 8192, 64, 64), dt, sharding=one_chip)
    bc = jax.ShapeDtypeStruct((1, 8192, groups, 128), dt, sharding=one_chip)
    step = jax.ShapeDtypeStruct((1, 8192, 64), jnp.float32,
                                sharding=one_chip)
    head = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)

    def loss(x, step, a, b, c, d):
        return jnp.sum(ssd_scan(x, step, a, b, c, d, chunk_size=chunk,
                                kernel=True) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        x, step, head, bc, bc, head).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 2
    assert 'vmem_limit_bytes' not in text
    # y, its cotangent and the starts (134 MB each at 128, the starts half
    # that at 256), and no decays; where one group's heads take several
    # grid steps, dB and dC besides as float32 parts a step (sixteen steps
    # of four heads in float32: 2 x 67 MB)
    parts = 1.0 if (cell, dtype) == ('granite4hmicro', 'float32') else 0.0
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4.2 + parts) * 2 ** 27


# a cell's gated norm as its rule hands it to the op: x's shape, groups,
# norm_before_gate
_NORMS = {'nemotron3nano': ((1, 8192, 4096), 8, False),
          'granite4hmicro': ((1, 8192, 4096), 1, False),
          'qwen3next': ((1, 8192, 32, 128), 1, True)}


# the (x, gate) dtypes of a cell's three Programs: the step and the AMP
# checks read the gate in bf16, the float32 check in float32
@pytest.mark.parametrize('gate', ['bfloat16', 'float32'])
@pytest.mark.parametrize('cell', sorted(_NORMS))
def test_gated_norm_compiles_for_v5e(one_chip, cell, gate):
    """nemotron3nano_s8192's gated norm (gate first, 8 groups of 512
    columns of [8192, 4096]) and qwen3next_s8192's (norm first, a head of
    128 as the last axis of [8192, 32, 128]) as the rule hands them to the
    op, forward and backward, x float32 and the gate in the step's bf16 and
    in the float32 check's: two Mosaic calls, no copy round them (the rows
    merge as a bitcast), within the default scoped VMEM (the kernels state
    no limit), and nothing of x's size kept between them but the three
    inputs."""
    from paddle_tpu.fluid.ops_impl.linear_attention_ops import gated_rms_norm
    from paddle_tpu.ops.kernels import gated_norm as kernel
    shape, groups, first = _NORMS[cell]
    assert kernel.usable(shape, groups, jnp.float32, jnp.dtype(gate))

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    # as in the cells' steps: the gate comes from a matmul and the result
    # goes to one, [B, T, all the columns] on both sides of the op
    flat = shape[:2] + (math.prod(shape[2:]),)

    def loss(x, z, w):
        y = gated_rms_norm(x, z.reshape(shape), w, (1e-5, first, groups),
                           True)
        return jnp.sum(y.reshape(flat) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        like(shape, 'float32'),
        like(flat, gate),
        like(shape[-1:], 'float32')).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 2
    # a call that states a `vmem_limit_bytes` carries a scoped memory
    # config; these calls' lists stay empty
    assert '"scoped_memory_configs":[{' not in text
    assert ' copy(' not in text and ' transpose(' not in text
    # y and its cotangent beside the gradients, which are outputs: no
    # third array of x's size is alive
    assert compiled.memory_analysis().temp_size_in_bytes < 2.1 * 2 ** 27


# the causal attention calls of the five language-model cells as their
# builders make them (key-value heads repeated over their groups before the
# call): shape, window, the tile pairs a head of the grid they take
_CELL_CALLS = {
    'smallthinker_window': ((1, 28, 16384, 128), 4096, 'band', 252),
    'smallthinker_global': ((1, 28, 16384, 128), None, 'triangle', 528),
    'glm47flash': ((1, 20, 8192, 256), None, 'triangle', 136),
    'qwen3next': ((1, 16, 8192, 256), None, 'triangle', 136),
    'nemotron3nano': ((1, 32, 8192, 128), None, 'triangle', 136),
    'olmoe': ((2, 16, 4096, 128), None, 'triangle', 36),
    # heads of HALF a lane tile over 64 tiles: 16384 x 64 (PR 44)
    'lfm2': ((1, 32, 16384, 64), None, 'triangle', 528),
    # the same heads over 16 tiles of a row of 8192 (PR 53)
    'granite4hmicro': ((1, 32, 8192, 64), None, 'triangle', 136),
    # a window of four tiles over 16: rows of 1, 2, 3, 4 and twelve of 5
    'trinitymini_window': ((1, 32, 8192, 128), 2048, 'band', 70),
}
# of a grid's tile pairs, those that add a mask tile which is not zeros
# (PR 60): the diagonal's, and under a window of whole tiles the band's
# lower edge's (32 + 24, 16 + 12)
_MASKED_PAIRS = {252: 56, 528: 32, 136: 16, 36: 8, 70: 28}


@pytest.mark.parametrize('dtype,precision', [
    ('bfloat16', None), ('float32', 'highest')],
    ids=['bf16_the_cell', 'float32_the_check'])
@pytest.mark.parametrize('call', sorted(_CELL_CALLS))
def test_head_backward_compiles_for_the_cells(one_chip, call, dtype,
                                              precision):
    """Every causal attention call of the six language-model cells, the
    forward and the ONE-pass backward over the head (PR 42), in the cell's
    bf16 and in its float32 check's arithmetic (traced under jax's highest
    matmul precision, as harness/check.py traces it; rows of D = 256 in
    256-tiles there, 528 pairs), inside the VMEM limit the call states.
    The counters say off the chip which schedule the rule chose, that a
    window took the band, and what it spared: 252 of the triangle's 528
    tile pairs a head in each of the two grids. A shape that fell back to
    two passes would fail here and not on the chip."""
    import contextlib
    from paddle_tpu import obs
    shape, window, grid, pairs = _CELL_CALLS[call]
    dt = jnp.dtype(dtype)
    if dt.itemsize * shape[3] > 512:
        pairs = {136: 528}[pairs]
    x = jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def tiles(name='flash.tiles'):
        return {g: obs.counter(name, grid=g).value
                for g in ('band', 'triangle', 'rect')}

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, window=window,
                                interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    before, was, masked = tiles(), _schedules(), tiles('flash.tiles_masked')
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile()
    assert compiled.as_text().count('tpu_custom_call') == 2
    after, now = tiles(), _schedules()
    assert {s: now[s] - was[s] for s in now} == {
        'tile': 0, 'head': 1, 'two': 0}
    assert {g: after[g] - before[g] for g in after} == {
        g: 2 * pairs * (g == grid) for g in after}
    after = tiles('flash.tiles_masked')
    assert {g: after[g] - masked[g] for g in after} == {
        g: 2 * _MASKED_PAIRS[pairs] * (g == grid) for g in after}


@pytest.mark.parametrize('dtype,precision', [
    ('bfloat16', None), ('float32', 'highest')],
    ids=['bf16_the_cell', 'float32_the_check'])
@pytest.mark.parametrize('call', ['smallthinker_window', 'glm47flash',
                                  'lfm2'])
def test_two_passes_compile_beside_the_mask_tiles(one_chip, call, dtype,
                                                  precision):
    """The dq and dk/dv kernels on the triangle and the band, which a head
    over the one pass's budget still takes and tools/tune_flash.py --parts
    times: they state no VMEM limit, so Mosaic's default 16 MiB has to
    hold their blocks, the body's score tiles and the mask's two or three
    tiles (PR 60: 3 MiB at 512 x 512 under a window), with no key bias
    handed in."""
    import contextlib
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    shape, window, _, _ = _CELL_CALLS[call]
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    def loss(q, k, v):
        q, k, v, kb, scale, bq, bk, _, interp, _, _ = fa._prep(
            q, k, v, None, None, None, None, False, causal=True,
            window=window)
        assert kb is None
        o, _ = fa._flash_lse(q, k, v, kb, True, window, scale, bq, bk, None,
                             interp)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 3
    assert '"scoped_memory_configs":[{' not in text


# a held cell's layer: tokens, top k, held, routed experts, width
_HELD = {'smallthinker': (16384, 6, 8, 64, 2560),
         'lfm2': (16384, 4, 8, 32, 2048),
         'nemotron3nano': (8192, 6, 8, 128, 2688),
         'qwen3next': (8192, 10, 16, 512, 2048),
         'glm47flash': (8192, 4, 8, 64, 2048)}


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('cell', sorted(_HELD))
def test_row_add_compiles_for_v5e(one_chip, cell, dtype):
    """The add of a held share's rows to their tokens at each held cell's
    layout, in the step's bf16 and in its float32 check's rows, forward
    (the rows times their gates) and as the row gather's transpose: one
    Mosaic call each over one plan, and within the default scoped VMEM
    (the kernel states no limit)."""
    from paddle_tpu.fluid.ops_impl import moe_ops
    from paddle_tpu.ops.kernels import row_add
    tokens, k, held, routed, width = _HELD[cell]
    cap = moe_ops._held_layout(tokens * k, held, routed)
    dt = jnp.dtype(dtype)
    assert row_add.usable(cap, tokens, width, dt)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(key, src, out, gate, g):
        at = moe_ops._index(src, key, held, width)
        return (moe_ops._add_up(out, gate, at, tokens, False),
                moe_ops._add_up(g, None, at, tokens, False))

    text = jax.jit(both).lower(
        like((tokens, k), jnp.int32), like((cap,), jnp.int32),
        like((cap, width), dt), like((cap, 1), jnp.float32),
        like((cap, width), dt)).compile().as_text()
    assert text.count('tpu_custom_call') == 2
    # a call that states a `vmem_limit_bytes` carries a scoped memory
    # config; this one's list stays empty
    assert '"scoped_memory_configs":[{' not in text


@pytest.mark.parametrize('cell,tokens,k,experts,width,hidden,cap,dtype', [
    ('smallthinker_s16384', 16384, 6, 64, 2560, 768, 49152, 'bfloat16'),
    ('glm47flash_s8192', 8192, 4, 64, 2048, 1536, 16384, 'float32'),
    ('lfm2_s16384', 16384, 4, 32, 2048, 1792, 32768, 'bfloat16'),
], ids=['smallthinker', 'glm47flash_float32', 'lfm2_a_quarter'])
def test_an_eighth_held_compiles_both_paths_for_v5e(
        one_chip, cell, tokens, k, experts, width, hidden, cap, dtype):
    """A held expert layer of the two 8-of-64 cells, and of the cell that
    holds a QUARTER (8 of 32: half the layer's rows are twice the expected
    ones, PR 44), from its keys on
    (`_held_paths`: the conditional, the layout of half the rows and the
    blocks behind it), forward and backward at the cell's shapes, in one
    cell's bf16 and in the other's float32 check's arithmetic: the
    layout's size is the rule's, both paths take the Mosaic grouped
    matmuls (3 forward, 3 again, 3 + 3 backward, a path), the compact
    path's two adds are the row-add kernel (forward, and the row gather's
    transpose in the backward pass), and the compact path sorts one operand (`_argsort`: the TPU's compiler takes
    seven times as long over a stable sort of keys beside their
    positions)."""
    import re
    import types
    from paddle_tpu.fluid.ops_impl import moe_ops
    assert moe_ops._held_layout(tokens * k, 8, experts) == cap
    dt = jnp.dtype(dtype)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {'w1': like((8, width, hidden), dt),
              'w3': like((8, width, hidden), dt),
              'w2': like((8, hidden, width), dt)}
    ctx = types.SimpleNamespace(platform='tpu', pallas_interpret=False)

    def loss(params, x, gate, key, sizes):
        y = moe_ops._held_paths(ctx, params, x, key, gate, sizes,
                                jnp.sum(sizes), cap=cap, act='relu')
        return jnp.sum(y * y)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, like((tokens, width), dt), like((tokens, k), jnp.float32),
        like((tokens, k), jnp.int32), like((8,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 26
    # the compact path sorts its tokens x k keys as ONE operand (a result
    # that is an array, not argsort's pair)
    assert re.search(r'= s32\[%d\]\S* sort\(' % (tokens * k), text)


# EvaByte's attention (PR 59): exact inside aligned windows of 2048 and,
# beyond them, over one summary a chunk of 16. The staircase's tiles are
# 512 queries x 128 summaries (a window's summaries are ONE lane tile of
# keys), far inside Mosaic's default scope; the aligned part is a causal
# call over rows of 2048 (four 512-tiles, the one-pass `head` backward at
# its stated limit). Mosaic calls of a forward and backward: the aligned
# part's 2 and the staircase's 3 (forward, dq, dk/dv).
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('seq', [8192, 32768],
                         ids=['s8192_512_summaries', 's32768_2048_summaries'])
def test_staircase_and_aligned_windows_compile_for_v5e(one_chip, dtype, seq):
    """`flash_attention_summary` forward and backward at (1, 32, 8192,
    128) against 512 summaries (evabyte_s8192) and at the published row
    of 32768 against 2048, in the cell's bf16 and in its float32 check's
    arithmetic (traced under jax's highest matmul precision, as that
    check is), with the staircase's grids holding only admitted blocks."""
    from paddle_tpu.ops import flash_attention_summary
    from paddle_tpu.ops.flash_attention import (_stair_maps, _stair_maps_kv,
                                                summary_blocks)
    window, every = 2048, 16
    assert summary_blocks(window, every) == (512, 128)
    dt = jnp.dtype(dtype)
    x = jax.ShapeDtypeStruct((1, 32, seq, 128), dt, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, 32, seq // every, 128), dt,
                             sharding=one_chip)

    def loss(q, k, v, kbar, vbar):
        o = flash_attention_summary(q, k, v, kbar, vbar, window=window,
                                    every=every, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    before = _schedules()
    with jax.default_matmul_precision(
            'highest' if dtype == 'float32' else 'default'):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, x, x, s, s).compile()
    after = _schedules()
    assert after['head'] - before['head'] == 1         # the aligned part
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 5
    # no array of a head's scores: [.., 8192, 8192] or [.., 8192, 512]
    assert not re.search(r'\[(?:\d+,)*%d,(?:%d|%d)\]' % (
        seq, seq, seq // every), text)
    # the staircase visits the admitted (q-block, summary-block) pairs and
    # no other: windows - 1 windows of queries, window w seeing w windows
    # of summaries
    windows = seq // window
    nq = (windows - 1) * 4
    pairs = sum(w * 4 for w in range(1, windows))
    for maps in (_stair_maps(nq, 4, 1), _stair_maps_kv(nq, 4, 1)):
        got = set(zip(maps[0].tolist(), maps[1].tolist()))
        assert len(got) == len(maps[0]) == pairs
        assert got == {(i, j) for i in range(nq) for j in range(i // 4 + 1)}
