"""Op lowering registry: Fluid op symbols -> JAX.

TPU-first replacement for the reference's per-op C++/CUDA kernel registry
(paddle/fluid/framework/op_registry.h + operators/*_op.cu). Instead of a
kernel per (op, Place, dtype), each op type has ONE pure-JAX rule. The
Executor symbolically evaluates a whole Program through these rules inside a
single jax.jit trace, so XLA sees the entire training step as one module and
fuses across op boundaries (the reference pays a kernel launch per op).

The same rules power build-time shape inference via jax.eval_shape
(framework.Block.append_op), so op semantics are defined exactly once.
"""
import collections
import contextlib
import functools
import re

import numpy as np

import jax
import jax.numpy as jnp

from .framework import DYN_DIM

_RULES = {}
_BLOCK_RULES = {}


class NoRuleError(KeyError):
    pass


class InferShapeError(ValueError):
    """A lowering rule failed to abstract-eval at program-build time under
    strict inference (framework.strict_infer_shape / PADDLE_TPU_STRICT_INFER)
    — the message names the op type and the user callsite that built it."""


def register(op_type):
    def deco(fn):
        _RULES[op_type] = fn
        return fn
    return deco


def register_block_op(op_type):
    """Register a structured-control-flow rule.

    Unlike plain rules (ins, attrs, ctx) -> outs, a block rule receives
    (op, env, ctx) and mutates env: it must execute its sub-block(s) itself
    (via run_block) under lax.while_loop / lax.scan / predicated select.
    This replaces the reference's C++ WhileOp/ConditionalBlockOp sub-scope
    interpreters (paddle/fluid/operators/while_op.cc,
    conditional_block_op.cc) with XLA-native structured control flow.
    """
    def deco(fn):
        _BLOCK_RULES[op_type] = fn
        return fn
    return deco


def get_rule(op_type):
    try:
        return _RULES[op_type]
    except KeyError:
        raise NoRuleError("no lowering rule for op %r" % op_type)


def has_rule(op_type):
    return op_type in _RULES


# One int32 the compiled step hands back for an op, beside its fetches:
# `source` names the variable it is reduced from (an output the op already
# has, so it crosses the backward pass and a recompute region as any
# output does), `reduce(value)` is traced inside the step, and
# `record(label, value, facts)` runs on the host once the step is done,
# only while observability is on: it counts into the registry and returns
# the op's entry of the step record's `fields['device']`. `facts` is what
# the op's rule told `Ctx.note` when it was traced.
DeviceCounter = collections.namedtuple('DeviceCounter',
                                       'source reduce record')
_DEVICE_COUNTERS = {}


def register_device_counter(op_type):
    """Register `declare(op)` for an op type: the DeviceCounter the step
    keeps for this op, or None where it counts nothing. What a step does
    that depends on its DATA (a rule's lax.cond, the load of a share)
    leaves the device this way; StepArtifact gathers the declared
    counters into the step's one packed vector (docs/observability.md)."""
    def deco(fn):
        _DEVICE_COUNTERS[op_type] = fn
        return fn
    return deco


def device_counter(op):
    declare = _DEVICE_COUNTERS.get(op.type)
    return declare(op) if declare is not None else None


class Ctx(object):
    """Per-op lowering context: PRNG key, run mode, target platform
    (the Executor's Place decides this — jax.default_backend() lies when a
    TPU plugin is present but the computation is placed on CPU), and the
    device mesh the step is compiled against (None = single device) so
    mesh-aware rules (moe_mlp) can shard_map over it. `manual_axes` names
    mesh axes the op is ALREADY manual over (inside a shard_map body, e.g.
    the pipeline region): rules that would otherwise open their own
    shard_map (sp attention) must instead use the per-shard collective
    bodies on those axes. `facts` is the step's dict of what rules say
    about their device counters (`note`); None where nothing gathers it
    (shape inference, sub-blocks, a pipeline stage). `bodies` is the
    step's dict of the rule bodies its ops share (`traced_once`); None in
    the same places. `scope` is the `fluid.name_scope` path the op was
    built under (`run_op` writes it)."""

    __slots__ = ('key', 'op_index', 'is_test', 'amp', 'platform', 'mesh',
                 'manual_axes', 'facts', 'bodies', 'scope')

    def __init__(self, key, op_index=0, is_test=False, amp=False,
                 platform='cpu', mesh=None, manual_axes=frozenset(),
                 facts=None, bodies=None):
        self.key = key
        self.op_index = op_index
        self.is_test = is_test
        self.amp = amp
        self.platform = platform
        self.mesh = mesh
        self.manual_axes = manual_axes
        self.facts = facts
        self.bodies = bodies
        self.scope = ''

    def rng(self):
        return jax.random.fold_in(self.key, self.op_index)

    def note(self, **facts):
        """What this op's rule fixed while it was traced and the host
        needs to read the op's device counter by (`DeviceCounter.record`):
        plain Python values, kept under the op's index. A rule traced
        again (a recompute region, the bundle's scan) says the same."""
        if self.facts is not None:
            self.facts[self.op_index] = facts

    @property
    def pallas_interpret(self):
        """The `interpret=` every pallas dispatch site passes: Mosaic where
        the step's arrays live on TPUs, the pallas interpreter elsewhere.
        Decided from the Executor's place (or mesh), never from the
        process's default backend."""
        return self.platform != 'tpu'


def traced_once(ctx, fn, **static):
    """`fn(ctx, *arrays, **static)` as ONE `jax.jit` function for every op
    of the step that asks with the same `fn` and `static` from under the
    same `fluid.name_scope`: a model's equal layers then run a rule's
    Python body once a shape, autodiff works on one jaxpr, and the module
    holds one function, called from each op under that op's scopes (XLA
    writes the caller's op_name before the body's own, so a profile still
    reads `<type>_<index>/jit(<fn>)/...`; `fn`'s name must not itself end
    in `_<digits>`). Not across name scopes: the inlined calls share ONE
    copy of the body's nested computations (a conditional's branches), and
    that copy carries one caller's op_name, its op index (any op of the
    type: what a reader by op type sums) and its name scope, which a
    reader by name scope (`mtp_ms`) would be handed. The function lives in
    the step's `bodies`, so another Program (a check beside the training
    step, a build after a test patched the rule's module) traces its own.
    The `ctx` the shared body sees says what the step's every op says
    (run mode, AMP, platform, mesh) and has no PRNG key: a body that
    draws from `rng()` is not one to share. Where no step gathers bodies
    (shape inference, a sub-block, a rule called with a stand-in for
    `ctx`) the plain function on the caller's `ctx`, traced where it is
    called."""
    bodies = getattr(ctx, 'bodies', None)
    if bodies is None:
        return functools.partial(fn, ctx, **static)
    key = (fn, ctx.scope, tuple(sorted(static.items())))
    if key not in bodies:
        body = functools.partial(
            fn, Ctx(None, is_test=ctx.is_test, amp=ctx.amp,
                    platform=ctx.platform, mesh=ctx.mesh,
                    manual_axes=ctx.manual_axes), **static)
        body.__name__ = fn.__name__        # the function's name in the module
        bodies[key] = jax.jit(body)
    return bodies[key]


def amp_cast(ctx, *xs):
    """Under AMP, cast fp32 matmul/conv operands to bf16 for the MXU."""
    if not ctx.amp:
        return xs if len(xs) > 1 else xs[0]
    out = tuple(x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
                for x in xs)
    return out if len(out) > 1 else out[0]


def use_kernel(ctx, name):
    """Trace-time pallas-kernel routing for lowering rules
    (docs/perf.md#kernel-layer): True iff kernel `name` is enabled via
    the ops.kernels knob (env PADDLE_TPU_KERNELS / kernels.configure).
    Records the decision on the kernels.dispatch/fallback counters, so
    every rule answers "which variant did this compile carry" in the
    obs report. Enablement is process-level, not a Ctx field — the
    Executor keys its compile cache on kernels.signature() so a knob
    flip can never be served a stale cached step. Rules keep their
    original jnp code as the False branch: that IS the fallback
    contract (knob off == byte-identical to the pre-kernel lowering).
    """
    from ..ops import kernels
    use = kernels.enabled(name)
    kernels.note_dispatch(name, use)
    return use


class SeqValue(object):
    """Runtime value of a lod_level>0 Variable: dense padded data + lengths.

    TPU-first replacement for LoDTensor's flattened [total_tokens, d] layout
    (reference paddle/fluid/framework/lod_tensor.h): static shapes
    [batch, max_len, ...] keep XLA happy; `lengths` int32[batch] carries the
    ragged structure of the INNERMOST LoD level; masked ops consult it.
    Nested LoD of arbitrary depth (the reference's recursive LoD table)
    keeps every level above the innermost in `outer_lengths`, a tuple of
    int32 vectors ordered outermost-first: level k's entries are lengths
    measured in units of level k+1's sequences, and the innermost level
    (`lengths`) is measured in tokens/rows. A bare array is accepted for
    the common 2-level case and normalised to a 1-tuple.

    `beam_cap` marks the CAPACITY form of the LoD beam-search decoder
    (ops_impl/lod_beam.py): data [B*K, ...] with each source's live rows
    compacted to the front of its K-row block. The flag is static pytree
    aux — it survives jit/while_loop round trips — and is set ONLY by
    normalize_capacity, the While capacity-widening pass, and the beam
    ops themselves, so ordinary 2-level LoD data whose shapes happen to
    look capacity-like (uniform group counts) can never be misrouted onto
    the beam path (round-5 ADVICE, lod_beam.is_beam_form).
    """

    __slots__ = ('data', 'lengths', 'outer_lengths', 'beam_cap')

    def __init__(self, data, lengths, outer_lengths=None, beam_cap=False):
        self.data = data
        self.lengths = lengths
        if outer_lengths is not None and not isinstance(outer_lengths, tuple):
            if isinstance(outer_lengths, list):
                outer_lengths = tuple(outer_lengths)
            else:
                outer_lengths = (outer_lengths,)
        self.outer_lengths = outer_lengths or None
        self.beam_cap = bool(beam_cap)

    @property
    def max_len(self):
        return self.data.shape[1]

    def mask(self, dtype=jnp.float32):
        """[batch, max_len] validity mask."""
        t = self.data.shape[1]
        return (jnp.arange(t)[None, :] < self.lengths[:, None]).astype(dtype)

    def tree_flatten(self):
        n_outer = len(self.outer_lengths) if self.outer_lengths else 0
        return (self.data, self.lengths) + (self.outer_lengths or ()), \
            (n_outer, self.beam_cap)

    @classmethod
    def tree_unflatten(cls, aux, children):
        n_outer, beam_cap = aux if isinstance(aux, tuple) else (aux, False)
        outer = tuple(children[2:2 + n_outer]) if n_outer else None
        return cls(children[0], children[1], outer, beam_cap=beam_cap)


jax.tree_util.register_pytree_node(
    SeqValue,
    lambda s: s.tree_flatten(),
    lambda aux, ch: SeqValue.tree_unflatten(aux, ch))


class SparseRows(object):
    """Sparse gradient of an embedding table: the rows actually touched.

    TPU-native analogue of the reference's SelectedRows
    (paddle/fluid/framework/selected_rows.h; lookup_table_op.cc emits one
    as the table grad when is_sparse=True). `ids` int32[N] are the looked-up
    row indices (duplicates allowed, in lookup order), `rows` [N, D] the
    corresponding per-occurrence gradients; the equivalent dense gradient
    is scatter-add(zeros(dense_shape), ids, rows). Optimizer rules
    (ops_impl/optim_ops.py) consume it with index-based row updates, so the
    vocab-sized dense @GRAD buffer never materializes in HBM. Static shapes
    throughout (N = batch positions, not unique count) keep XLA happy.

    Sharded case (docs/embedding.md): `dense_shape` is always the GLOBAL
    table shape — under a mesh with a row-sharded table the [N, D] rows
    stay batch-sized (merged replicated by _merge_sparse) while the
    optimizer's row scatter partitions per shard, so neither layout ever
    builds the dense buffer."""

    __slots__ = ('ids', 'rows', 'dense_shape')

    def __init__(self, ids, rows, dense_shape):
        self.ids = ids
        self.rows = rows
        self.dense_shape = tuple(dense_shape)

    @property
    def dtype(self):
        return self.rows.dtype

    def astype(self, dtype):
        return SparseRows(self.ids, self.rows.astype(dtype),
                          self.dense_shape)

    def to_dense(self):
        out = jnp.zeros(self.dense_shape, self.rows.dtype)
        return out.at[self.ids].add(self.rows)


jax.tree_util.register_pytree_node(
    SparseRows,
    lambda s: ((s.ids, s.rows), s.dense_shape),
    lambda shape, ch: SparseRows(ch[0], ch[1], shape))


def data_of(v):
    return v.data if isinstance(v, SeqValue) else v


def like(template, new_data):
    """Wrap new_data with template's sequence structure (if any)."""
    if isinstance(template, SeqValue):
        return SeqValue(new_data, template.lengths, template.outer_lengths,
                        beam_cap=template.beam_cap)
    return new_data


def first_seq(*vals):
    for v in vals:
        if isinstance(v, SeqValue):
            return v
    return None


def scope_label(name):
    """A `fluid.name_scope` prefix as it is written into op_name: what is
    not a letter, a digit or `_` becomes `_`, and a name that ends in
    `_<digits>` gets one more `_`, because `<type>_<index>` is how an op's
    own scope reads and whoever parses op_name takes the innermost such
    for the op (`layer_1` is written `layer_1_`)."""
    label = re.sub(r'[^A-Za-z0-9_]', '_', name)
    return label + '_' if re.search(r'_[0-9]+$', label) else label


def run_op(op, env, ctx):
    """Resolve an op's inputs from env, apply its rule, bind outputs.

    Each rule traces under jax.named_scope('<op.type>_<op_index>'), so the
    XLA module's per-instruction metadata op_name carries the Fluid op it
    came from: profiler traces and HLO dumps of the COMPILED fused step map
    back to program ops (the reference's per-op C++ event tracer,
    profiler.py:81-130, attributes the real run the same way — here the
    attribution survives fusion instead of requiring the eager path). The
    scopes the op was built in (`fluid.name_scope`, the attribute
    `name_scope`) are entered around it, outermost first, each as
    `scope_label` writes it: `.../mtp/latent_attention/mul_17/...`."""
    ctx.scope = op.attrs.get('name_scope', '')
    with contextlib.ExitStack() as scopes:
        for name in ctx.scope.split('/'):
            if name:
                scopes.enter_context(jax.named_scope(scope_label(name)))
        scopes.enter_context(
            jax.named_scope('%s_%d' % (op.type, ctx.op_index)))
        if op.type in _BLOCK_RULES:
            _BLOCK_RULES[op.type](op, env, ctx)
            return
        rule = get_rule(op.type)
        ins = {slot: [env[v.name] for v in vs]
               for slot, vs in op.inputs.items()}
        outs = rule(ins, op.attrs, ctx)
    _bind_outputs(op, outs, env)


def run_block(block, env, ctx):
    """Execute every op of a (sub-)block against env, in place.

    The PRNG stream stays distinct per (block, op) position so dropout etc.
    inside loop bodies doesn't collide with the outer ops' streams.
    """
    base = block.idx * 4096
    for i, op in enumerate(block.ops):
        run_op(op, env, Ctx(ctx.key, base + i, is_test=ctx.is_test,
                            amp=ctx.amp, platform=ctx.platform,
                            mesh=ctx.mesh, manual_axes=ctx.manual_axes))


# Default slot count for LoDTensorArray buffers (see ArrayValue). Layers
# read layers/control_flow.py:ARRAY_CAPACITY (initialized from this) at
# call time; this is the single fallback for ops lacking a capacity attr.
DEFAULT_ARRAY_CAPACITY = 128


class ArrayValue(object):
    """Runtime value of a LOD_TENSOR_ARRAY variable.

    The reference's LoDTensorArray is a C++ vector<LoDTensor> grown by
    array_write ops inside While loops (operators/array_write_op.cc). Under
    XLA everything must be statically shaped, so an array is a preallocated
    ring of `capacity` slots [capacity, *elem] plus a live-length scalar;
    writes are lax.dynamic_update_slice, reads dynamic_index_in_dim. This
    makes arrays legal lax.while_loop carries.

    Elements may be LoD-carrying SeqValues (the book's beam-search decoder
    stores 2-level selected_ids/scores in arrays): `buffer` is then a TUPLE
    of stacked leaf buffers (data, lengths, *outer_lengths) and `n_outer`
    (static) says how many trailing buffers are outer LoD levels; -1 marks
    a plain dense element. `beam` (static aux, like SeqValue.beam_cap)
    records that the stored elements are capacity-form beam values, so
    array_read rebuilds them with the flag intact."""

    __slots__ = ('buffer', 'length', 'n_outer', 'beam')

    def __init__(self, buffer, length, n_outer=-1, beam=False):
        self.buffer = buffer
        self.length = length
        self.n_outer = n_outer
        self.beam = bool(beam)

    @property
    def is_seq(self):
        return self.n_outer >= 0

    def read(self, i):
        """Element at slot i (rebuilds the SeqValue for seq-backed arrays)."""
        take = lambda b: jax.lax.dynamic_index_in_dim(b, i, axis=0,
                                                      keepdims=False)
        if not self.is_seq:
            return take(self.buffer)
        leaves = tuple(take(b) for b in self.buffer)
        outer = leaves[2:2 + self.n_outer] if self.n_outer else None
        return SeqValue(leaves[0], leaves[1], outer, beam_cap=self.beam)

    @staticmethod
    def _grow_rows(buf, rows_new, n_sources=None):
        """[cap, r_old, ...] -> [cap, rows_new, ...]: row i moves to
        i * stride (the LoD beam capacity convention — each source's rows
        must land at the START of its capacity block; see
        ops_impl/lod_beam.py). That placement is only correct when every
        source owns exactly ONE narrow row (r_old == number of sources);
        a multi-row-per-source init would be scattered at stride intervals
        INSIDE each block, silently breaking the rows-compacted-to-front
        invariant that rows_live/the live-mask assume — so when the caller
        knows the source count, widening anything else raises loudly
        (round-5 ADVICE)."""
        r_old = buf.shape[1]
        if rows_new == r_old:
            return buf
        if rows_new % r_old:
            raise ValueError(
                'array_write: element rows grew %d -> %d; capacity '
                'widening needs an integer stride' % (r_old, rows_new))
        if n_sources is not None and r_old != n_sources:
            raise ValueError(
                'array_write: cannot widen %d rows to capacity %d for %d '
                'sources — stride placement is only valid from one row '
                'per source (%d rows); compact the init to one row per '
                'source before the loop' % (r_old, rows_new, n_sources,
                                            n_sources))
        out = jnp.zeros((buf.shape[0], rows_new) + buf.shape[2:],
                        buf.dtype)
        return out.at[:, ::rows_new // r_old].set(buf)

    def _grown_to(self, x):
        """Widen/convert the buffers so a write of `x` fits (the book's
        decode idiom writes one row per source before the While, beam_size
        rows per source inside it). Widening follows the beam capacity
        convention, so the result is beam-flagged; the source count from
        x's outer LoD gates _grow_rows' one-row-per-source check."""
        if isinstance(x, SeqValue):
            n_outer = len(x.outer_lengths or ())
            n_src = (x.outer_lengths[0].shape[0]
                     if x.outer_lengths else None)
            if not self.is_seq:
                data = self._grow_rows(self.buffer, x.data.shape[0],
                                       n_sources=n_src)
                stride = x.data.shape[0] // self.buffer.shape[1]
                lens = jnp.zeros((data.shape[0], x.data.shape[0]),
                                 jnp.int32)
                lens = lens.at[:, ::stride].set(1)
                outer = tuple(
                    jnp.ones((data.shape[0],) + o.shape, o.dtype)
                    for o in (x.outer_lengths or ()))
                return ArrayValue((data, lens) + outer, self.length,
                                  n_outer, beam=True)
            d0 = self.buffer[0]
            if d0.ndim == x.data.ndim + 2 and d0.shape[2] == 1:
                # padded 2-level feed slots [B, max_len=1, ...] -> flat rows
                d0 = d0.reshape(d0.shape[:2] + d0.shape[3:])
            data = self._grow_rows(d0, x.data.shape[0], n_sources=n_src)
            lens = self._grow_rows(self.buffer[1], x.lengths.shape[0],
                                   n_sources=n_src)
            return ArrayValue((data, lens) + self.buffer[2:], self.length,
                              self.n_outer,
                              beam=self.beam or data is not d0)
        if not self.is_seq:
            return ArrayValue(self._grow_rows(self.buffer,
                                              data_of(x).shape[0]),
                              self.length, -1, beam=self.beam)
        return self

    def _elem_fits(self, x):
        if isinstance(x, SeqValue):
            return (self.is_seq
                    and self.n_outer == len(x.outer_lengths or ())
                    and self.buffer[0].shape[1:] == x.data.shape
                    and self.buffer[1].shape[1:] == x.lengths.shape)
        return (not self.is_seq
                and self.buffer.shape[1:] == data_of(x).shape)

    def write(self, i, x):
        """New ArrayValue with slot i <- x; the buffers grow (capacity
        convention) when x is wider than the current slots."""
        if not isinstance(x, SeqValue) and self.is_seq:
            # dense write into an LoD array (e.g. an encoder state fed to
            # the decode idiom's state array): adopt one full-length group
            # per row
            x = SeqValue(data_of(x),
                         jnp.ones((data_of(x).shape[0],), jnp.int32),
                         tuple(jnp.ones(b.shape[1:], b.dtype)
                               for b in self.buffer[2:2 + self.n_outer])
                         or None, beam_cap=self.beam)
        if isinstance(x, SeqValue) and not self._elem_fits(x):
            slot = self.buffer[0] if self.is_seq else self.buffer
            if (x.data.ndim == slot.ndim and x.data.shape[1] == 1
                    and slot.shape[1:] != x.data.shape):
                # [rows, max_len=1, ...] padded element vs flat-row slots
                # (the decode idiom's pre-loop feeds): drop the singleton
                # time dim before fitting/growing
                x = SeqValue(x.data[:, 0], x.lengths, x.outer_lengths,
                             beam_cap=x.beam_cap)
        if not self._elem_fits(x):
            grown = self._grown_to(x)
            if not grown._elem_fits(x):
                def shp(v):
                    if isinstance(v, SeqValue):
                        return ('seq', v.data.shape, v.lengths.shape,
                                tuple(o.shape
                                      for o in (v.outer_lengths or ())))
                    return getattr(v, 'shape', v)
                raise TypeError(
                    'array_write: element %r does not fit (and cannot '
                    'grow to fit) array slots %r'
                    % (shp(x), [b.shape for b in grown.buffer]
                       if grown.is_seq else grown.buffer.shape))
            return grown.write(i, x)
        put = lambda b, v: jax.lax.dynamic_update_index_in_dim(
            b, v.astype(b.dtype), i, axis=0)
        if isinstance(x, SeqValue):
            leaves = (x.data, x.lengths) + tuple(x.outer_lengths or ())
            assert len(leaves) == len(self.buffer)  # _elem_fits checked
            buf = tuple(put(b, v) for b, v in zip(self.buffer, leaves))
        else:
            buf = put(self.buffer, x)
        cap = (self.buffer[0] if self.is_seq else self.buffer).shape[0]
        length = jnp.minimum(jnp.maximum(self.length, i + 1), cap)
        return ArrayValue(buf, length, self.n_outer,
                          beam=self.beam or getattr(x, 'beam_cap', False))

    @classmethod
    def fresh(cls, x, capacity):
        """Empty array sized for elements shaped like x."""
        z = lambda v: jnp.zeros((capacity,) + tuple(v.shape), v.dtype)
        if isinstance(x, SeqValue):
            leaves = (x.data, x.lengths) + tuple(x.outer_lengths or ())
            return cls(tuple(z(v) for v in leaves),
                       jnp.asarray(0, jnp.int32),
                       len(x.outer_lengths or ()),
                       beam=x.beam_cap)
        return cls(z(x), jnp.asarray(0, jnp.int32), -1)


jax.tree_util.register_pytree_node(
    ArrayValue,
    lambda a: ((a.buffer, a.length), (a.n_outer, a.beam)),
    lambda aux, ch: ArrayValue(ch[0], ch[1], aux[0], beam=aux[1])
    if isinstance(aux, tuple) else ArrayValue(ch[0], ch[1], aux))


def _bind_outputs(op, outs, env):
    for slot, vs in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for var, val in zip(vs, vals):
            if val is not None:
                env[var.name] = val


def spec_of(var):
    """Build-time abstract value of a Variable: a jax.ShapeDtypeStruct (the
    dynamic batch dim stood in by DYN_DIM), a SeqValue of specs for
    lod_level>0 vars, or None when the shape is undeclared. Shared by
    append_op's inference and the fluid.analysis shape pass."""
    if var.shape is None:
        return None
    s = var._spec()
    if var.lod_level and var.lod_level > 0:
        # padded layout [batch, time, ...]; shape already carries both
        # dynamic dims (see layers/io.py:data)
        batch = s.shape[0]
        lens = jax.ShapeDtypeStruct((batch,), np.int32)
        if var.lod_level > 1:
            return SeqValue(s, lens, jax.ShapeDtypeStruct((batch,), np.int32))
        return SeqValue(s, lens)
    return s


def abstract_eval(op, in_specs):
    """Abstract-evaluate op's lowering rule over per-slot input specs
    ({slot: [spec | SeqValue-of-specs | None, ...]}) via jax.eval_shape.
    Returns the rule's output structure with ShapeDtypeStructs for arrays.
    Raises NoRuleError for unregistered ops and whatever the rule raises
    when the specs are inconsistent (the caller decides strictness)."""
    rule = get_rule(op.type)

    def f():
        key = jax.random.key(0)
        ctx = Ctx(key, op_index=0, is_test=bool(op.attrs.get('is_test', False)))
        concrete_ins = {
            slot: [jnp.zeros(s.data.shape, s.data.dtype) if isinstance(s, SeqValue)
                   else (jnp.zeros(s.shape, s.dtype) if s is not None else None)
                   for s in vs]
            for slot, vs in in_specs.items()}
        # re-wrap SeqValues
        for slot, vs in in_specs.items():
            for i, s in enumerate(vs):
                if isinstance(s, SeqValue):
                    concrete_ins[slot][i] = SeqValue(
                        concrete_ins[slot][i],
                        jnp.ones(s.lengths.shape, s.lengths.dtype))
        return rule(concrete_ins, op.attrs, ctx)

    return jax.eval_shape(f)


def shape_from_spec(spec):
    """Declared-shape view of an inferred ShapeDtypeStruct: DYN_DIM is
    prime, so any multiple of it can only have come from the dynamic batch
    dim (tiled/merged by expand/reshape) and maps back to -1."""
    return tuple(-1 if d % DYN_DIM == 0 and d > 0 else int(d)
                 for d in spec.shape)


def infer_op_shapes(op, strict=False):
    """Build-time shape/dtype inference by abstract-evaluating the rule.

    The dynamic batch dim (-1) is stood in by DYN_DIM and mapped back; this
    replaces the reference's per-op C++ InferShape functions. Best-effort
    by default (a failing rule leaves declared shapes alone); with
    strict=True a failure raises InferShapeError naming the op type and
    the callsite that built it (framework.strict_infer_shape)."""
    ins = {slot: [spec_of(v) for v in vs] for slot, vs in op.inputs.items()}

    try:
        outs = abstract_eval(op, ins)
    except NoRuleError:
        raise
    except Exception as e:
        if strict:
            site = getattr(op, 'callsite', None)
            raise InferShapeError(
                "shape inference failed for op %r%s: %s: %s (inputs: %s)"
                % (op.type,
                   ' built at %s' % site if site else '',
                   type(e).__name__, e,
                   {k: [getattr(s, 'shape', None) if not isinstance(s, SeqValue)
                        else ('seq', s.data.shape) for s in vs]
                    for k, vs in ins.items()}))
        return  # shape inference is best-effort at build time

    for slot, vs in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for var, val in zip(vs, vals):
            if val is None:
                continue
            spec = val.data if isinstance(val, SeqValue) else val
            var.shape = shape_from_spec(spec)
            from . import core
            var.dtype = core.convert_dtype(spec.dtype)
            if isinstance(val, SeqValue) and var.lod_level == 0:
                var.lod_level = 1
