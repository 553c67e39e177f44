"""The depthwise causal convolution as one Pallas kernel forward and one
backward: the K - 1 shifts along T happen in VMEM, on a tile that is
already there, and each pass moves its arrays once.

    y[b, t, c] = act(sum_j w[j, c] x[b, t - (K - 1) + j, c] + bias[c])
                                                              x = 0, t < 0

The bias is optional (None: the calls, their operands and their bodies are
what they were without it); with one, both bodies add it to the float32
sum before the activation, and the backward sums dpre into a [1, tC] block
beside dw's.

The arithmetic, its order and its precisions are those of
fluid/ops_impl/linear_attention_ops.py `_conv`, which stays as the
composition this is tested against and as every other platform's path: x
read in its dtype, K multiply-adds in float32 in the order of j, the
activation in float32, the result rounded to x's dtype. XLA's `_conv` pads
a float32 copy along T and sums K slices of it offset by 0 to K - 1 rows;
T is the sublane axis, every slice is off the tile, and the array moves
again for each.

Forward (`causal_conv1d_fwd`): a grid step owns a tile [tT, tC] of one
row of the batch. It reads the tile once and, as a second view of the same
array, the sublane tile that ends where the tile begins (the K - 1 rows
before it are its last; zeros at a row's start, so rows of a batch never
see each other), and walks the tile in pieces of sixteen float32 vregs (a
`lax.fori_loop`, so the body is traced and compiled once): a piece and the
eight float32 rows before it, which the piece before hands on, rolled down
by 1 to K - 1 rows (`pltpu.roll` on the sublane axis), are the K operands.

Backward (`causal_conv1d_bwd`): one grid step reads x with the tile edges
on both sides and the cotangent g with the eight rows after the tile,
computes the sum before the activation again, dpre = g act'(pre), and

    dx[t]    = sum_j w[j] dpre[t + (K - 1) - j]         rolled up
    dw[j, c] = sum_t dpre[t] x[t - (K - 1) + j]

walking the pieces from the tile's end so that the eight rows of dpre
after a piece are the ones the loop has just computed and carries. dw is
summed in float32, in vregs along a tile and then into a [K, tC] block
that stays where it is while the grid walks B and T.
No [B, T, C] float32 array leaves VMEM on either pass, so the backward has
nothing to keep from XLA behind an `optimization_barrier`.

`interpret` as every kernel here: True for the Pallas interpreter, False
for Mosaic. Not under the PADDLE_TPU_KERNELS knob: like the flash kernels,
the grouped matmul and stage `gdn_intra` it is what the op lowers to on
the TPU.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['causal_conv1d_fwd', 'causal_conv1d_bwd', 'usable', 'tile_of']

# (tT, tC) at 2-byte operands (tools/bench_causal_conv1d.py --sweep;
# docs/perf.md has the rows); 4-byte operands take half the rows
TILE = (1024, 512)
_PIECE = 16384       # elements a piece: sixteen float32 vregs an operand
_F32_ROWS = 8        # a float32 sublane tile: what a piece sees before it

_F32 = jnp.float32


def _silu_grad(pre):
    sig = jax.nn.sigmoid(pre)
    return sig * (1.0 + pre * (1.0 - sig))


# name -> (act, act'), both of the float32 sum: the names `_conv` takes
_ACTS = {'': (lambda pre: pre, None), 'silu': (jax.nn.silu, _silu_grad)}
_ACTS['swish'] = _ACTS['silu']


def _edge(dtype):
    """Rows of a sublane tile: 8 at four bytes, 16 at two."""
    return 32 // jnp.dtype(dtype).itemsize


def tile_of(t, c, dtype):
    """The tile [tT, tC] a grid step takes of [T, C]: the widest of 512,
    256, 128 lanes that divides C, TILE's rows (half at 4 bytes), or all
    of a shorter row."""
    tc = next((n for n in (TILE[1], 256, 128) if c % n == 0), c)
    return min(TILE[0] * 2 // jnp.dtype(dtype).itemsize, t), tc


def usable(t, c, taps, dtype):
    """Whole lane tiles of channels, whole tiles of tokens (and whole
    sublane tiles, where a row is shorter than one), a filter that looks
    back no further than a float32 sublane tile, bf16 or float32."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    return (c % 128 == 0 and t % _edge(dtype) == 0
            and t % tile_of(t, c, dtype)[0] == 0
            and 1 <= taps - 1 <= _F32_ROWS)


def _rows_of(tt, tc, edge):
    """Rows a piece: about `_PIECE` elements, whole sublane tiles, a
    divisor of the tile's rows."""
    rows = max(edge, _PIECE // tc // edge * edge)
    while tt % rows:
        rows -= edge
    return rows


def _taps(w_ref):
    return [w_ref[j:j + 1, :] for j in range(w_ref.shape[0])]


def _last(rows):
    """The last eight rows of a sublane tile (or a piece), float32."""
    return rows.astype(_F32)[rows.shape[0] - _F32_ROWS:]


def _windows(before, piece, taps):
    """x[t - (K - 1) + j] for j = 0..K - 1 over a piece's rows t: the
    piece under the eight rows before it, rolled down."""
    ext = jnp.concatenate([before, piece], axis=0)
    return [pltpu.roll(ext, taps - 1 - j, 0)[_F32_ROWS:]
            for j in range(taps - 1)] + [piece]


def _weighted(ws, xs):
    """sum_j w[j] x_j, in the order of j."""
    acc = ws[0] * xs[0]
    for w, x in zip(ws[1:], xs[1:]):
        acc = acc + w * x
    return acc


def _piece(i, rows):
    """Where piece i of a tile's block is: rows [i rows, (i + 1) rows)."""
    return 0, pl.ds(pl.multiple_of(i * rows, rows), rows)


# The pieces of a tile are a `lax.fori_loop`, not a Python loop: unrolled,
# a Program traced 64 pieces of some 200 operations for each dtype, which
# no compile cache keeps (setup_s; docs/perf.md has both forms' times).
def _pre(ws, xs, b_ref):
    """The float32 sum before the activation."""
    pre = _weighted(ws, xs)
    return pre if b_ref is None else pre + b_ref[...]


def _fwd_kernel(x_ref, prev_ref, w_ref, *rest, act, rows):
    b_ref, y_ref = rest if len(rest) == 2 else (None,) + rest
    ws = _taps(w_ref)
    # the eight rows before the tile: the neighbouring tile's last, zeros
    # before a row's first token
    first = jnp.where(pl.program_id(2) > 0, _last(prev_ref[0]), 0.0)

    def piece(i, before):
        x = x_ref[_piece(i, rows)].astype(_F32)
        y_ref[_piece(i, rows)] = _ACTS[act][0](
            _pre(ws, _windows(before, x, len(ws)), b_ref)
        ).astype(y_ref.dtype)
        return _last(x)

    lax.fori_loop(0, x_ref.shape[1] // rows, piece, first)


def _fold(x):
    """[rows, tC] -> [8, tC]: the sublane tiles added up, vreg by vreg."""
    return functools.reduce(
        jnp.add, [x[r:r + _F32_ROWS] for r in range(0, x.shape[0],
                                                    _F32_ROWS)])


def _bwd_kernel(x_ref, prev_ref, next_ref, g_ref, g_next_ref, w_ref,
                *rest, act, rows):
    b_ref, dx_ref, dw_ref, db_ref = rest if len(rest) == 4 \
        else (None,) + rest + (None,)
    ws = _taps(w_ref)
    taps, tt = len(ws), x_ref.shape[1]
    edge = prev_ref.shape[1]
    grad = _ACTS[act][1]

    def dpre_of(g, xs):
        g = g.astype(_F32)
        return g if grad is None else g * grad(_pre(ws, xs, b_ref))

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    first = jnp.where(pl.program_id(2) > 0, _last(prev_ref[0]), 0.0)
    # dpre of the eight rows after the tile: the next tile's first, whose
    # windows reach back into this one; nothing after a row's last token
    after = _windows(_last(x_ref[0, tt - edge:]),
                     next_ref[0].astype(_F32)[:_F32_ROWS], taps)
    head = jnp.where(pl.program_id(2) < pl.num_programs(2) - 1,
                     dpre_of(g_next_ref[0][:_F32_ROWS], after), 0.0)
    pieces = tt // rows

    # from the tile's end: the eight rows of dpre after a piece are the
    # first of the piece walked just before it
    def piece(k, carry):
        head, sums = carry
        i = pieces - 1 - k
        x = x_ref[_piece(i, rows)].astype(_F32)
        # the sublane tile that ends where the piece begins (piece 0's is
        # read and not used)
        lo = pl.multiple_of(jnp.maximum(i * rows - edge, 0), edge)
        before = jnp.where(i > 0, _last(x_ref[0, pl.ds(lo, edge)]), first)
        xs = _windows(before, x, taps)
        dpre = dpre_of(g_ref[_piece(i, rows)], xs)
        sums = tuple(s + _fold(dpre * xj) for s, xj in zip(sums, xs)) \
            + ((sums[taps] + _fold(dpre),) if db_ref is not None else ())
        # dpre[t + s] over the piece's rows t: the piece over the eight
        # rows after it, rolled up by s
        ext = jnp.concatenate([dpre, head], axis=0)
        ups = [pltpu.roll(ext, rows + _F32_ROWS - (taps - 1 - j), 0)[:rows]
               for j in range(taps - 1)] + [dpre]
        dx_ref[_piece(i, rows)] = _weighted(ws, ups).astype(dx_ref.dtype)
        return dpre[:_F32_ROWS], sums

    zero = jnp.zeros((_F32_ROWS, x_ref.shape[2]), _F32)
    _, sums = lax.fori_loop(
        0, pieces, piece,
        (head, (zero,) * (taps + (db_ref is not None))))
    for j in range(taps):
        dw_ref[j:j + 1, :] += jnp.sum(sums[j], axis=0, keepdims=True)
    if db_ref is not None:
        db_ref[...] += jnp.sum(sums[taps], axis=0, keepdims=True)


def _geometry(x, tile):
    """(tile, edge, tiles of T, edge blocks a tile, edge blocks in T)"""
    t, c = x.shape[1:]
    tt, tc = tile or tile_of(t, c, x.dtype)
    edge = _edge(x.dtype)
    return tt, tc, edge, t // tt, tt // edge, t // edge


# Both calls are jitted functions of their own, as the delta rule's chunk
# kernels: a model has several such ops, each traced for the primal, for
# its forward rule and in every check Program. jit keeps one trace a shape
# and emits one function a module, called under each place's scopes.
def _bias(bias, tc, index):
    """([the bias as a [1, C] float32 operand], [its block's spec]), or
    two empty lists where there is none."""
    if bias is None:
        return [], []
    return ([bias.astype(_F32).reshape(1, -1)],
            [pl.BlockSpec((1, tc), index)])


@functools.partial(jax.jit, static_argnames=('act', 'interpret', 'tile'))
def causal_conv1d_fwd(x, w, bias=None, *, act, interpret, tile=None):
    """x [B, T, C], w [K, C], bias [C] or None -> y [B, T, C] in x's
    dtype. `tile` overrides (tT, tC) (the sweep's and the tests' door)."""
    b, _, c = x.shape
    tt, tc, edge, n_t, per, _ = _geometry(x, tile)
    bias, bias_spec = _bias(bias, tc, lambda b, j, i: (0, j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act,
                          rows=_rows_of(tt, tc, edge)),
        grid=(b, c // tc, n_t),
        in_specs=[
            pl.BlockSpec((1, tt, tc), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, edge, tc), lambda b, j, i: (
                b, jnp.maximum(i * per - 1, 0), j)),
            pl.BlockSpec((w.shape[0], tc), lambda b, j, i: (0, j))]
        + bias_spec,
        out_specs=pl.BlockSpec((1, tt, tc), lambda b, j, i: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, name='causal_conv1d_fwd',
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel')))(
        x, x, w.astype(_F32), *bias)


@functools.partial(jax.jit, static_argnames=('act', 'interpret', 'tile'))
def causal_conv1d_bwd(x, w, g, bias=None, *, act, interpret, tile=None):
    """The cotangent g of y -> (dx in x's dtype, dw in w's) and, with a
    bias, its gradient in its dtype as a third."""
    b, _, c = x.shape
    tt, tc, edge, n_t, per, n_edge = _geometry(x, tile)
    dtype = None if bias is None else bias.dtype
    bias, bias_spec = _bias(bias, tc, lambda j, b, i: (0, j))

    def here(j, b, i):
        return b, i, j

    def before(j, b, i):
        return b, jnp.maximum(i * per - 1, 0), j

    def after(j, b, i):
        return b, jnp.minimum((i + 1) * per, n_edge - 1), j

    dx, dw, *db = pl.pallas_call(
        functools.partial(_bwd_kernel, act=act,
                          rows=_rows_of(tt, tc, edge)),
        grid=(c // tc, b, n_t),
        in_specs=[
            pl.BlockSpec((1, tt, tc), here),
            pl.BlockSpec((1, edge, tc), before),
            pl.BlockSpec((1, edge, tc), after),
            pl.BlockSpec((1, tt, tc), here),
            pl.BlockSpec((1, edge, tc), after),
            pl.BlockSpec((w.shape[0], tc), lambda j, b, i: (0, j))]
        + bias_spec,
        out_specs=[
            pl.BlockSpec((1, tt, tc), here),
            pl.BlockSpec((w.shape[0], tc), lambda j, b, i: (0, j))]
        + bias_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, _F32)]
        + [jax.ShapeDtypeStruct((1, c), _F32)] * len(bias),
        interpret=interpret, name='causal_conv1d_bwd',
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary')))(
        x, x, x, g, g, w.astype(_F32), *bias)
    return (dx, dw.astype(w.dtype)) + tuple(
        v.reshape(-1).astype(dtype) for v in db)
