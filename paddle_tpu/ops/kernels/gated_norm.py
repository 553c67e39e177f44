"""The gated RMS norm as one Pallas kernel forward and one backward whose
block holds a group's columns: the statistics are a group's own, so a
block [rows, group width] takes its mean of squares in VMEM, along the
lanes, and each pass moves its arrays once.

    norm first:  y = w * x * inv(x) * silu(gate)
    gate first:  y = w * u * inv(u),  u = x * silu(gate)       (Mamba-2)
    inv(v) = rsqrt(mean(v^2 over the group) + eps)

The arithmetic, its order and its precisions are those of
fluid/ops_impl/linear_attention_ops.py `_gated_norm`, which stays as the
composition this is tested against and as every other platform's and
shape's path: x and the gate read in their dtypes, `silu`, the statistics
and every product in float32, the result rounded to x's dtype. Where the
mean is over one of G > 1 parts of the last axis, XLA's `_gated_norm`
reshapes [.., G x width] to [.., G, width]: the groups land where the
TPU's tiles keep eight ROWS, and the array moves through HBM round a sum
its bytes do not need (docs/perf.md "The gated norm in one pass").

Both calls see x as [R, G x width] (every axis but the last merged into
rows: a bitcast where `usable` says yes) and walk a grid of (blocks of
rows, groups). A block is walked in pieces of eight float32 vregs an
operand (a `lax.fori_loop`, so the body is traced and compiled once); a
piece's rows are whole, so nothing crosses a piece, a block or a call.

BY HEAD, for a float32 x [.., heads, 128] with one group (`by_head`): x
and dx are [R x heads, 128], where the producer's heads left them, and
the gate, the result, the cotangent and dgate [R, heads x 128], what the
matmuls on both sides of the op hold; to XLA each view is a bitcast, and
no array moves through HBM round the calls to meet the others' layout. A
block is some rows of all the heads, a piece ONE head of them: a strided
load from x (a head's rows lie `heads` apart) beside a lane tile of the
others' columns. The weight is a head's, shared: dw sums over the heads
too.

Forward (`gated_norm_fwd`): reads x and the gate once, writes y once.

Backward (`gated_norm_bwd`): ONE kernel reads x, the gate, w and the
cotangent g once, computes the group's `inv` again (the block holds the
whole group: no byte), and with v the normed rows, dv their cotangent:

    d(rows) = inv * (dv - v * mean(dv * v))
    norm first:  dv = g w silu(gate),  dx = d(rows),
                 dgate = g w v silu'(gate),           dw = sum g v silu(gate)
    gate first:  dv = g w,  dx = d(rows) silu(gate),
                 dgate = d(rows) x silu'(gate),       dw = sum g v

dx and dgate in their inputs' dtypes. dw is summed in float32, in vregs
along a block and then into a [1, width] block that stays where it is
while the grid walks the rows (its last, sequential axis); rows past the
array's end in a last, partial block add nothing. It keeps (x, gate, w)
and no forward runs again inside it, so there is nothing to hold from XLA
behind an `optimization_barrier`.

Within the default scoped VMEM and with no `vmem_limit_bytes`: a Mosaic
call that states one makes XLA plan the whole step anew (PR 42).

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the causal
convolution it is what the op lowers to on the TPU.
"""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['gated_norm_fwd', 'gated_norm_bwd', 'usable', 'by_head',
           'rows_of']

# elements of a block [rows, width] (tools/bench_gated_norm.py --sweep;
# docs/perf.md has the rows): 1 MiB of float32 an operand
BLOCK = 1 << 18
_PIECE = 8192        # elements a piece: eight float32 vregs an operand
_LANES = 128
_F32_ROWS = 8

_F32 = jnp.float32
_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def _edge(*dtypes):
    """Rows of the tallest sublane tile among `dtypes`: 8 at four bytes,
    16 at two."""
    return max(32 // jnp.dtype(d).itemsize for d in dtypes)


def rows_of(rows, width, block=BLOCK):
    """The rows a grid step takes of [rows, width]: `block` elements'
    worth in whole sublane tiles of either dtype, or all of a shorter
    array."""
    return min(max(16, block // width // 16 * 16), rows)


def by_head(shape, groups, x_dtype):
    """Whether x is read by head: float32 [.., heads, 128] with the mean
    over a head's whole last axis. The gate, the result and the cotangent
    touch matmuls whose rows are tokens, [rows, heads x 128] to the
    compiler, and x is where its producer's heads left it, [rows x heads,
    128]: a kernel that saw all of them one way would have XLA move the
    others through HBM round the call (docs/perf.md "The gated norm in one
    pass"). Mosaic's strided load, which reads a head's rows, takes 32-bit
    data in rows of one lane tile; any other [.., heads, width] is read
    flat, its heads merged into the rows."""
    return (len(shape) >= 4 and groups == 1 and shape[-1] == _LANES
            and jnp.dtype(x_dtype) == _F32)


def usable(shape, groups, x_dtype, gate_dtype):
    """A group of whole lane tiles, bf16 or float32 operands, whole
    sublane tiles of rows, and views that are the arrays as they lie:
    rows that merge without a copy (one row before the last two axes, or a
    second-last axis of whole sublane tiles), by head a token's heads in
    whole sublane tiles of x."""
    dtypes = jnp.dtype(x_dtype), jnp.dtype(gate_dtype)
    if any(d not in _DTYPES for d in dtypes) or len(shape) < 2:
        return False
    edge = _edge(*dtypes)
    if shape[-1] % groups or shape[-1] // groups % _LANES:
        return False
    if by_head(shape, groups, x_dtype):
        return (int(np.prod(shape[:-2])) % edge == 0
                and shape[-2] % _edge(x_dtype) == 0)
    rows = int(np.prod(shape[:-1]))
    return rows % edge == 0 and (rows == shape[-2] or shape[-2] % edge == 0)


def _piece_rows(tr, width, edge):
    """Rows a piece: about `_PIECE` elements, whole sublane tiles, a
    divisor of the block's rows."""
    rows = min(tr, max(edge, _PIECE // width // edge * edge))
    while tr % rows:
        rows -= edge
    return rows


def _piece(i, rows, width, heads):
    """Where piece i of a block is, as (x's and dx's index, the other
    arrays' index, its first row in the block). Flat: rows [i rows,
    (i + 1) rows) of the group's columns in all of them. By head: piece
    (r, h) is head h of rows [r rows, (r + 1) rows), in x every
    `heads`-th row of [rows x heads, width] from row r rows heads + h (a
    strided load: a head's rows lie a sublane apart in the tiles of the
    producer's [.., heads, width]) and in the others the columns
    [h width, (h + 1) width) of those rows."""
    if not heads:
        at = (pl.ds(pl.multiple_of(i * rows, rows), rows), slice(None))
        return at, at, i * rows
    r, h = i // heads, i % heads
    return ((pl.ds(r * (rows * heads) + h, rows, stride=heads), slice(None)),
            (pl.ds(pl.multiple_of(r * rows, rows), rows),
             pl.ds(pl.multiple_of(h * width, width), width)), r * rows)


def _inv(v, eps):
    return lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)


def _fwd_kernel(x_ref, gate_ref, w_ref, y_ref, *, eps, norm_first, rows,
                heads, gate_act='silu'):
    w = w_ref[...]
    width = w.shape[1]
    act = jax.nn.sigmoid if gate_act == 'sigmoid' else jax.nn.silu

    def piece(i, _):
        head, at, _ = _piece(i, rows, width, heads)
        x = x_ref[head].astype(_F32)
        s = act(gate_ref[at].astype(_F32))
        if norm_first:
            y = x * _inv(x, eps) * w * s
        else:
            u = x * s
            y = u * _inv(u, eps) * w
        y_ref[at] = y.astype(y_ref.dtype)
        return 0

    lax.fori_loop(0, x_ref.shape[0] // rows, piece, 0)


def _fold(x):
    """[rows, width] -> [8, width]: the sublane tiles added up, vreg by
    vreg."""
    return functools.reduce(
        jnp.add, [x[r:r + _F32_ROWS] for r in range(0, x.shape[0],
                                                    _F32_ROWS)])


def _bwd_kernel(x_ref, gate_ref, w_ref, g_ref, dx_ref, dgate_ref, dw_ref,
                *, eps, norm_first, rows, heads, total, gate_act='silu'):
    w = w_ref[...]
    width = w.shape[1]
    tr = gate_ref.shape[0]
    # rows of this block inside the array: fewer than `tr` in a last,
    # partial block, whose other rows hold anything
    live = total - pl.program_id(1) * tr if total % tr else None

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def piece(i, sums):
        head, at, first = _piece(i, rows, width, heads)
        x = x_ref[head].astype(_F32)
        z = gate_ref[at].astype(_F32)
        gw = g_ref[at].astype(_F32)
        sig = jax.nn.sigmoid(z)
        if gate_act == 'sigmoid':
            s, ds = sig, sig * (1.0 - sig)                  # sigmoid'(gate)
        else:
            s = z * sig
            ds = sig * (1.0 + z * (1.0 - sig))              # silu'(gate)
        u = x if norm_first else x * s
        inv = _inv(u, eps)
        v = u * inv
        dwp = gw * v * s if norm_first else gw * v
        gw = gw * w
        dv = gw * s if norm_first else gw
        du = inv * (dv - v * jnp.mean(dv * v, axis=-1, keepdims=True))
        if norm_first:
            dx, dz = du, gw * v * ds
        else:
            dx, dz = du * s, du * x * ds
        dx_ref[head] = dx.astype(dx_ref.dtype)
        dgate_ref[at] = dz.astype(dgate_ref.dtype)
        if live is not None:
            row = first + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            dwp = jnp.where(row < live, dwp, 0.0)
        return sums + _fold(dwp)

    sums = lax.fori_loop(0, x_ref.shape[0] // rows, piece,
                         jnp.zeros((_F32_ROWS, width), _F32))
    dw_ref[...] += jnp.sum(sums, axis=0, keepdims=True)


class _Plan(collections.namedtuple('_Plan', (
        'heads', 'total', 'width', 'rows', 'x_view', 'view', 'x_block',
        'block'))):
    """How a call sees its arrays: the heads x is read by (0: flat), the
    rows, a group's width, the rows of a piece, x's and dx's view and the
    other arrays', a grid step's block of each."""

    @property
    def grid(self):
        """(blocks of rows, blocks of columns)"""
        return (pl.cdiv(self.total, self.block[0]),
                self.view[1] // self.block[1])


def _plan(x, gate, groups, tile):
    heads = x.shape[-2] if by_head(x.shape, groups, x.dtype) else 0
    cols = x.shape[-1] * max(heads, 1)        # of the gate's view
    width = x.shape[-1] // groups
    total = x.size // cols
    tr = min(tile, total) if tile else rows_of(total, cols if heads else width)
    rows = _piece_rows(tr, width, _edge(x.dtype, gate.dtype))
    if heads:
        return _Plan(heads, total, width, rows, (total * heads, width),
                     (total, cols), (tr * heads, width), (tr, cols))
    return _Plan(0, total, width, rows, (total, cols), (total, cols),
                 (tr, width), (tr, width))


def _act(gate_act):
    """The kernels' keyword for the gate's activation, where it is not the
    SiLU they were written with."""
    if gate_act not in ('silu', 'sigmoid'):
        raise ValueError('gated norm: gate_act %r' % (gate_act,))
    return {} if gate_act == 'silu' else {'gate_act': gate_act}


# Both calls are jitted functions of their own, as the convolution's: a
# model has several such ops, each traced for the primal, for its forward
# rule and in every check Program. jit keeps one trace a shape and emits
# one function a module, called under each place's scopes.
@functools.partial(jax.jit, static_argnames=(
    'eps', 'norm_first', 'groups', 'interpret', 'tile', 'gate_act'))
def gated_norm_fwd(x, gate, w, *, eps, norm_first, groups, interpret,
                   tile=None, gate_act='silu'):
    """x, gate [..., G x width], w [G x width] -> y of x's shape and
    dtype. `tile` overrides the rows of a block (the sweep's and the
    tests' door); `gate_act` `sigmoid` gates by sigmoid(gate) where the
    formulas above say silu."""
    p = _plan(x, gate, groups, tile)
    here = pl.BlockSpec(p.block, lambda i, j: (i, j))
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, norm_first=norm_first,
                          rows=p.rows, heads=p.heads, **_act(gate_act)),
        grid=p.grid,
        in_specs=[pl.BlockSpec(p.x_block, lambda i, j: (i, j)), here,
                  pl.BlockSpec((1, p.width), lambda i, j: (0, j))],
        out_specs=here,
        out_shape=jax.ShapeDtypeStruct(p.view, x.dtype),
        interpret=interpret, name='gated_norm_fwd',
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')))(
        x.reshape(p.x_view), gate.reshape(p.view),
        w.astype(_F32).reshape(1, -1))
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    'eps', 'norm_first', 'groups', 'interpret', 'tile', 'gate_act'))
def gated_norm_bwd(x, gate, w, g, *, eps, norm_first, groups, interpret,
                   tile=None, gate_act='silu'):
    """The cotangent g of y -> (dx in x's dtype, dgate in the gate's, dw
    in w's)."""
    p = _plan(x, gate, groups, tile)
    here = pl.BlockSpec(p.block, lambda j, i: (i, j))
    head = pl.BlockSpec(p.x_block, lambda j, i: (i, j))
    group = pl.BlockSpec((1, p.width), lambda j, i: (0, j))
    dx, dgate, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, norm_first=norm_first,
                          rows=p.rows, heads=p.heads, total=p.total,
                          **_act(gate_act)),
        grid=p.grid[::-1],
        in_specs=[head, here, group, here],
        out_specs=[head, here, group],
        out_shape=[jax.ShapeDtypeStruct(p.x_view, x.dtype),
                   jax.ShapeDtypeStruct(p.view, gate.dtype),
                   jax.ShapeDtypeStruct((1, w.size), _F32)],
        interpret=interpret, name='gated_norm_bwd',
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')))(
        x.reshape(p.x_view), gate.reshape(p.view),
        w.astype(_F32).reshape(1, -1), g.reshape(p.view))
    return (dx.reshape(x.shape), dgate.reshape(gate.shape),
            dw.reshape(w.shape).astype(w.dtype))
