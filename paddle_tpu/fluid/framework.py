"""Program / Block / Operator / Variable graph IR.

Parity: reference python/paddle/fluid/framework.py (Variable:142,
Operator:431, Block:855, Program:1339, Parameter:1874).

TPU-first redesign: the reference serializes ops into a protobuf ProgramDesc
interpreted op-by-op by a C++ Executor with per-Place CUDA/CPU kernels. Here
the Program is a lightweight Python-side op list that the Executor lowers in
one pass into a single jitted XLA computation (see executor.py) — ops are
*symbols*, resolved through the lowering registry (ops_impl/) at trace time.
Shape inference runs at graph-build time through jax.eval_shape over the same
lowering rules, so there is exactly one definition of every op's semantics.
"""
import collections
import contextlib
import copy
import itertools
import os
import sys

import numpy as np

from . import core
from . import unique_name

__all__ = [
    'Program', 'Operator', 'Parameter', 'Variable', 'Block',
    'default_startup_program', 'default_main_program', 'program_guard',
    'name_scope', 'recompute_guard', 'recompute_keep', 'RecomputeKeepError',
    'device_guard', 'get_var', 'grad_var_name',
    'strict_infer_shape', 'normalize_sharding',
]

GRAD_VAR_SUFFIX = "@GRAD"
# Mirrors the reference's OpRole attr used to prune backward/optimize ops in
# Program.clone(for_test=True) (framework.py op_role machinery).
ROLE_FORWARD = 0
ROLE_BACKWARD = 1
ROLE_OPTIMIZE = 2
ROLE_LRSCHED = 16
ROLE_METRIC = 32

# A distinctive stand-in for the dynamic batch dim (-1) during build-time
# abstract evaluation; mapped back to -1 in inferred output shapes. A large
# prime so (a) multiples of it can only have come from the stand-in itself
# and (b) no plausible user tensor dim collides with it; Variable.__init__
# rejects the collision outright rather than silently mapping the dim to -1.
DYN_DIM = 999983


def normalize_sharding(spec):
    """Normalize a sharding annotation into the canonical per-dim tuple.

    A spec names, per tensor dimension, the mesh axis (or axes) that
    dimension is partitioned over: each entry is an axis name, None
    (replicated dim), or a tuple of axis names (partitioned over the
    axes' product). Trailing dims may be omitted (replicated). Examples:
    ``('model', None)``, ``('dp',)``, ``(('tp', 'dp'), None)``. A bare
    string means dim 0 over that axis. Returns None for None, else a
    tuple ready for jax.sharding.PartitionSpec(*spec) — framework.py
    itself never imports jax; the Executor builds the NamedSharding."""
    if spec is None:
        return None
    if isinstance(spec, str):
        spec = (spec,)
    if not isinstance(spec, (list, tuple)):
        raise ValueError(
            'sharding must be a tuple of mesh-axis names / None / '
            'axis-name tuples, got %r' % (spec,))
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, str):
            out.append(e)
        elif (isinstance(e, (list, tuple)) and e
              and all(isinstance(a, str) for a in e)):
            out.append(tuple(e))
        else:
            raise ValueError(
                'bad sharding entry %r in %r: each dim is an axis name, '
                'None, or a non-empty tuple of axis names' % (e, spec))
    return tuple(out)


def _sharding_to_jsonable(spec):
    return [list(e) if isinstance(e, tuple) else e for e in spec]


def grad_var_name(name):
    return name + GRAD_VAR_SUFFIX


# -- op provenance (docs/analysis.md) ---------------------------------------
# Every Operator records the user-code callsite that built it (the first
# stack frame OUTSIDE paddle_tpu/fluid), so analyzer findings and strict
# shape-inference errors can say "the op you built at train.py:42" instead
# of naming an anonymous temp var. The sys._getframe walk costs ~1us per op
# at BUILD time only (never on the run path); PADDLE_TPU_PROVENANCE=0
# disables it for build-latency-critical embedders.
ENV_PROVENANCE = 'PADDLE_TPU_PROVENANCE'
_FLUID_DIR = os.path.dirname(os.path.abspath(__file__)) + os.sep


def provenance_enabled():
    return os.environ.get(ENV_PROVENANCE, '1').lower() not in (
        '0', 'off', 'false', 'no')


def _capture_callsite():
    """file:line of the nearest stack frame outside paddle_tpu/fluid (the
    layer call that created the op), or None when disabled/not found."""
    if not provenance_enabled():
        return None
    try:
        f = sys._getframe(2)
    except ValueError:
        return None
    while f is not None:
        fn = f.f_code.co_filename
        if not os.path.abspath(fn).startswith(_FLUID_DIR):
            return '%s:%d' % (fn, f.f_lineno)
        f = f.f_back
    return None


# -- strict shape inference --------------------------------------------------
# Default: append_op's build-time inference is best-effort (a rule that
# cannot abstract-eval leaves the declared shapes alone). Under strict mode
# a FAILING rule raises lowering.InferShapeError naming the op type and its
# build callsite — the loud contract layers opt into and tests drill.
ENV_STRICT_INFER = 'PADDLE_TPU_STRICT_INFER'
_strict_infer_override = []   # stack of bools from strict_infer_shape()


def strict_infer_enabled():
    if _strict_infer_override:
        return _strict_infer_override[-1]
    return os.environ.get(ENV_STRICT_INFER, '').lower() in (
        '1', 'on', 'true', 'yes')


@contextlib.contextmanager
def strict_infer_shape(enable=True):
    """Within this context, append_op(infer_shape=True) failures raise
    lowering.InferShapeError (op type + provenance) instead of silently
    leaving shapes undeclared."""
    _strict_infer_override.append(bool(enable))
    try:
        yield
    finally:
        _strict_infer_override.pop()


class Variable(object):
    """A named tensor in a Block. Reference framework.py:142.

    Holds static metadata only (shape may contain -1 for the batch dim);
    values live in a Scope as jax arrays at run time.
    """

    def __init__(self,
                 block,
                 name=None,
                 shape=None,
                 dtype='float32',
                 lod_level=0,
                 persistable=False,
                 stop_gradient=False,
                 is_data=False,
                 type=None,
                 initializer=None,
                 sharding=None,
                 tiered=False,
                 **kwargs):
        self.block = block
        if name is None:
            name = unique_name.generate('_generated_var')
        self.name = name
        # GSPMD sharding annotation (docs/parallel.md): per-dim mesh-axis
        # names interpreted against the Program's mesh spec (set_mesh).
        # Static metadata like shape/dtype — the Executor turns it into a
        # NamedSharding at lowering time; fluid.analysis.sharding checks
        # consistency ahead of that. Annotated vars capture the layer
        # call that declared the spec (params have no producer op in the
        # main program, so op provenance can't name it).
        self.sharding = normalize_sharding(sharding)
        self._annot_callsite = (_capture_callsite()
                                if self.sharding is not None else None)
        # backed by a host-RAM tier store (embedding.TieredVocabTable
        # stamps this): spills gather WHOLE rows, so the static sharding
        # pass refuses an embedding-dim sharding on a tiered table
        # (DimSharding) the way tiers.validate_program would at runtime
        self.tiered = bool(tiered)
        self.shape = tuple(int(d) for d in shape) if shape is not None else None
        if self.shape is not None and DYN_DIM in self.shape:
            raise ValueError(
                "dim %d collides with the build-time dynamic-batch sentinel "
                "(framework.DYN_DIM); use a different size" % DYN_DIM)
        self.dtype = core.convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.type = type or 'LOD_TENSOR'
        self.op = None  # producer op (set by append_op)
        if name not in block.vars:
            block.vars[name] = self

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s, lod=%d)" % (
            self.name, self.shape, self.dtype, self.lod_level)

    __str__ = __repr__

    def to_string(self, throw_on_error=False, with_details=False):
        return repr(self)

    @property
    def ndim(self):
        return len(self.shape)

    def astype(self, dtype):
        from .layers import tensor
        return tensor.cast(self, dtype)

    def _spec(self, batch=DYN_DIM):
        """jax.ShapeDtypeStruct view with -1 dims replaced by `batch`."""
        import jax
        shape = tuple(batch if d == -1 else d for d in self.shape)
        dt = self.dtype
        return jax.ShapeDtypeStruct(shape, np.dtype(dt) if dt != 'bfloat16' else 'bfloat16')

    def _to_dict(self):
        d = dict(name=self.name,
                 shape=list(self.shape) if self.shape is not None else None,
                 dtype=self.dtype, lod_level=self.lod_level,
                 persistable=self.persistable, stop_gradient=self.stop_gradient,
                 is_data=self.is_data, type=self.type,
                 cls=type(self).__name__)
        if self.sharding is not None:
            # only when annotated: un-annotated programs serialize
            # byte-identically to pre-sharding artifacts
            d['sharding'] = _sharding_to_jsonable(self.sharding)
        if self.tiered:
            # same only-when-set policy: the tier mark survives clone()
            # and the artifact round-trip so program_lint --mesh can
            # refuse a dim-sharded tiered table statically
            d['tiered'] = True
        return d


class Parameter(Variable):
    """A persistable, trainable Variable. Reference framework.py:1874."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs['persistable'] = True
        self.trainable = kwargs.pop('trainable', True)
        self.optimize_attr = kwargs.pop('optimize_attr', {'learning_rate': 1.0})
        self.regularizer = kwargs.pop('regularizer', None)
        self.gradient_clip_attr = kwargs.pop('gradient_clip_attr', None)
        self.do_model_average = kwargs.pop('do_model_average', None)
        super(Parameter, self).__init__(block, shape=shape, dtype=dtype, **kwargs)

    def _to_dict(self):
        d = super(Parameter, self)._to_dict()
        d['trainable'] = self.trainable
        d['optimize_attr'] = self.optimize_attr
        return d


class Operator(object):
    """One op in a Block. Reference framework.py:431.

    inputs/outputs map slot name -> list of Variable. attrs are plain
    JSON-able python values. The op's semantics are defined solely by the
    lowering rule registered for `type` in ops_impl/.
    """

    # default sentinel: capture the callsite. Callers that already KNOW the
    # op's provenance (clone, _from_dict) pass the preserved value instead
    # — a thousand-op artifact load must not pay a thousand stack walks
    # for values it would immediately overwrite.
    _CAPTURE = object()

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None,
                 callsite=_CAPTURE):
        self.block = block
        self.type = type
        self.inputs = {}
        self.outputs = {}
        # user-code file:line that built this op (None when provenance is
        # disabled); clone()/prune()/_from_dict carry the original through
        # the callsite kwarg, so findings keep pointing at the layer call
        self.callsite = (_capture_callsite()
                         if callsite is Operator._CAPTURE else callsite)
        self.attrs = dict(attrs or {})
        self.attrs.setdefault('op_role', ROLE_FORWARD)
        if _device_guard_stack and _device_guard_stack[-1] is not None:
            self.attrs.setdefault('op_device', _device_guard_stack[-1])
        scope = '/'.join(s for s in _name_scope_stack if s)
        if scope:
            self.attrs.setdefault('name_scope', scope)
        if _recompute_stack:
            self.attrs.setdefault('recompute', _recompute_stack[-1])
        if inputs:
            for slot, vs in inputs.items():
                if vs is None:
                    continue
                if not isinstance(vs, (list, tuple)):
                    vs = [vs]
                self.inputs[slot] = list(vs)
        if outputs:
            for slot, vs in outputs.items():
                if vs is None:
                    continue
                if not isinstance(vs, (list, tuple)):
                    vs = [vs]
                self.outputs[slot] = list(vs)
                for v in vs:
                    if isinstance(v, Variable):
                        v.op = self

    def input(self, slot):
        return [v.name for v in self.inputs.get(slot, [])]

    def output(self, slot):
        return [v.name for v in self.outputs.get(slot, [])]

    @property
    def input_arg_names(self):
        return [v.name for vs in self.inputs.values() for v in vs]

    @property
    def output_arg_names(self):
        return [v.name for vs in self.outputs.values() for v in vs]

    def attr(self, name):
        return self.attrs[name]

    def has_attr(self, name):
        return name in self.attrs

    def _set_attr(self, name, val):
        self.attrs[name] = val
        self.block.program._bump_version()

    set_attr = _set_attr

    def all_attrs(self):
        return dict(self.attrs)

    def __repr__(self):
        ins = {k: [v.name for v in vs] for k, vs in self.inputs.items()}
        outs = {k: [v.name for v in vs] for k, vs in self.outputs.items()}
        return "{%s: %s -> %s %s}" % (self.type, ins, outs,
                                      {k: v for k, v in self.attrs.items()
                                       if k not in ('op_role',)})

    def _to_dict(self):
        d = dict(
            type=self.type,
            inputs={k: [v.name for v in vs] for k, vs in self.inputs.items()},
            outputs={k: [v.name for v in vs] for k, vs in self.outputs.items()},
            attrs={k: v for k, v in self.attrs.items()},
        )
        if self.callsite:
            # provenance survives save/load so program_lint findings on a
            # saved artifact still name the original layer call — but as
            # basename:line, not the absolute build-machine path: an
            # artifact must not leak local filesystem layout, and two
            # checkouts of the same tree must serialize byte-identically
            path, _, line = self.callsite.rpartition(':')
            d['callsite'] = '%s:%s' % (os.path.basename(path), line)
        return d


class Block(object):
    """An ordered op list + var table. Reference framework.py:855."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = collections.OrderedDict()
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    def var(self, name):
        v = self.vars.get(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def _var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        raise ValueError("Variable %r not found (recursive)" % name)

    def has_var(self, name):
        return name in self.vars

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def create_var(self, *args, **kwargs):
        return Variable(self, *args, **kwargs)

    def create_variable(self, *args, **kwargs):
        return Variable(self, *args, **kwargs)

    def create_parameter(self, *args, **kwargs):
        return Parameter(self, *args, **kwargs)

    def append_op(self, type=None, inputs=None, outputs=None, attrs=None,
                  infer_shape=True, callsite=Operator._CAPTURE):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs,
                      attrs=attrs, callsite=callsite)
        self.ops.append(op)
        self.program._bump_version()
        if infer_shape:
            try:
                from . import lowering
                lowering.infer_op_shapes(op, strict=strict_infer_enabled())
            except lowering.NoRuleError:
                pass
        return op

    def _insert_op(self, index, **kwargs):
        op = Operator(self, **kwargs)
        self.ops.insert(index, op)
        self.program._bump_version()
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def _to_dict(self):
        return dict(idx=self.idx, parent_idx=self.parent_idx,
                    vars=[v._to_dict() for v in self.vars.values()],
                    ops=[op._to_dict() for op in self.ops])


class Program(object):
    """A list of Blocks; the unit the Executor lowers and jits.

    Reference framework.py:1339. `_version` is a mutation counter used as the
    jit-cache fingerprint (any append/mutation invalidates compiled code).
    """

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        self._seed_counter = 0
        # GSPMD mesh spec (docs/parallel.md): ((axis, size), ...) in mesh
        # layout order + the axis feeds shard their batch dim over. Set by
        # set_mesh(); consumed by the Executor's annotated-sharding path
        # and by fluid.analysis.sharding.
        self._mesh_axes = None
        self._mesh_data_axis = None
        # id(program) can be recycled after GC, colliding in the Executor's
        # jit cache; a monotonically unique uid cannot.
        self._uid = Program._next_uid
        Program._next_uid += 1

    _next_uid = 0

    def set_mesh(self, axes, data_axis=None):
        """Declare the device mesh this Program's sharding annotations
        refer to — the program-level half of the annotation surface
        (docs/parallel.md; the per-tensor half is
        ``ParamAttr(sharding=...)`` / ``Variable(sharding=...)``).

        axes: {'dp': 8} / {'dp': 2, 'model': 4}-style dict (insertion
        order = mesh layout, row-major over the visible devices) or an
        ``((name, size), ...)`` sequence. ``set_mesh(None)`` clears the
        spec. data_axis: the mesh axis feed batches shard their leading
        dim over; defaults to ``'dp'`` (then ``'data'``) when present,
        else feeds replicate. ``data_axis=False`` forces feeds to
        REPLICATE even when a 'dp'/'data' axis exists — the sharded
        SERVING posture (docs/serving.md#pod): request batches are
        bucket-sized, not divisible-by-mesh-sized, while the params
        (e.g. a row-sharded table) stay sharded over the axis.

        The Executor lowers an annotated Program through ONE jitted step
        with explicit in/out shardings and a donation vector over the
        sharded persistables — no strategy wrapper involved; plain
        ``run``/``run_bundle``/``Trainer`` all take this path."""
        # any spec change invalidates the Executor's cached Mesh build
        for a in ('_dist_mesh', '_annot_axes'):
            if hasattr(self, a):
                delattr(self, a)
        if axes is None:
            self._mesh_axes = None
            self._mesh_data_axis = None
            self._bump_version()
            return self
        items = tuple(axes.items()) if isinstance(axes, dict) \
            else tuple((str(n), int(s)) for n, s in axes)
        if not items:
            raise ValueError('set_mesh needs at least one (axis, size)')
        seen = set()
        for name, size in items:
            if not isinstance(name, str) or not name:
                raise ValueError('mesh axis name must be a non-empty '
                                 'string, got %r' % (name,))
            if name in seen:
                raise ValueError('duplicate mesh axis %r' % name)
            seen.add(name)
            if int(size) < 1:
                raise ValueError('mesh axis %r has size %r' % (name, size))
        items = tuple((n, int(s)) for n, s in items)
        if data_axis is False:
            # forced replicate (serving posture): kept as False — NOT
            # collapsed to None — so the choice survives clone() and
            # the _to_dict/_from_dict round-trip (None would re-derive
            # 'dp' on reload and silently re-shard request batches)
            pass
        elif data_axis is None:
            for cand in ('dp', 'data'):
                if cand in seen:
                    data_axis = cand
                    break
        elif data_axis not in seen:
            raise ValueError('data_axis %r is not a mesh axis (have %r)'
                             % (data_axis, sorted(seen)))
        self._mesh_axes = items
        self._mesh_data_axis = data_axis
        self._bump_version()
        return self

    @property
    def mesh_axes(self):
        """The declared mesh spec as an ordered dict, or None."""
        if self._mesh_axes is None:
            return None
        return collections.OrderedDict(self._mesh_axes)

    def _bump_version(self):
        self._version += 1

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def block(self, idx):
        return self.blocks[idx]

    def create_block(self, parent_idx=None):
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.blocks.append(Block(self, new_idx, parent))
        self.current_block_idx = new_idx
        return self.current_block()

    def rollback(self):
        self.current_block_idx = self.blocks[self.current_block_idx].parent_idx

    @property
    def num_blocks(self):
        return len(self.blocks)

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    def all_parameters(self):
        return self.global_block().all_parameters()

    def clone(self, for_test=False):
        """Deep-copy the program. With for_test=True, prune backward/optimize
        ops and flip is_test on dropout/batch_norm etc. (reference
        Program.clone + inference_optimize)."""
        p = Program()
        p.random_seed = self.random_seed
        # execution flags travel with the program: amp mode, the
        # Float16Transpiler fetch contract, rematerialisation
        for flag in ('_amp', '_fetch_f32', '_use_remat',
                     '_quant', '_quant_ir', '_quant_ops'):
            if hasattr(self, flag):
                setattr(p, flag, getattr(self, flag))
        # the mesh spec travels with the program exactly like _dist_config:
        # a clone of an annotated program stays annotated (per-var specs
        # ride through Variable._to_dict below)
        p._mesh_axes = self._mesh_axes
        p._mesh_data_axis = self._mesh_data_axis
        if getattr(self, '_dist_config', None) is not None:
            # mesh annotations travel with the program (the scope's arrays
            # are already mesh-placed; a meshless clone would mix devices)
            p._dist_config = dict(self._dist_config)
        p.blocks = []
        var_maps = []
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            p.blocks.append(nb)
            vmap = {}
            for v in blk.vars.values():
                d = v._to_dict()
                cls = d.pop('cls')
                d.pop('name')
                if cls == 'Parameter':
                    d.pop('trainable', None)
                    d.pop('optimize_attr', None)
                    nv = Parameter(nb, name=v.name,
                                   trainable=getattr(v, 'trainable', True),
                                   optimize_attr=dict(v.optimize_attr),
                                   regularizer=v.regularizer,
                                   gradient_clip_attr=v.gradient_clip_attr,
                                   do_model_average=v.do_model_average, **d)
                else:
                    nv = Variable(nb, name=v.name, **d)
                vmap[v.name] = nv
            var_maps.append(vmap)
        for bi, blk in enumerate(self.blocks):
            nb = p.blocks[bi]
            vmap = var_maps[bi]

            def lookup(name, bidx=bi):
                b = p.blocks[bidx]
                while b is not None:
                    if name in b.vars:
                        return b.vars[name]
                    b = b.parent_block
                return var_maps[bi][name]

            for op in blk.ops:
                role = op.attrs.get('op_role', ROLE_FORWARD)
                if for_test and role in (ROLE_BACKWARD, ROLE_OPTIMIZE, ROLE_LRSCHED):
                    continue
                ins = {k: [lookup(v.name) for v in vs] for k, vs in op.inputs.items()}
                outs = {k: [lookup(v.name) for v in vs] for k, vs in op.outputs.items()}
                attrs = copy.deepcopy(op.attrs)
                if for_test and 'is_test' in attrs:
                    attrs['is_test'] = True
                nb.append_op(type=op.type, inputs=ins, outputs=outs,
                             attrs=attrs, infer_shape=False,
                             callsite=op.callsite)
        p.current_block_idx = 0
        self._retranspile_pipeline(p)
        p._bump_version()
        return p

    def _retranspile_pipeline(self, p):
        """Re-derive `_pipeline_config` on a clone/prune result: op indices
        shift when ops are dropped, so the config is re-computed from the
        (copied) device_guard stamps. If the surgery broke the stage
        structure, the stamps stay inert and the region runs sequentially
        (same semantics) on the mesh the _dist_config still describes."""
        cfg = getattr(self, '_pipeline_config', None)
        if cfg is None:
            return
        from .transpiler.pipeline_transpiler import PipelineTranspiler
        try:
            PipelineTranspiler(n_micro=cfg['n_micro'],
                               axis=cfg['axis'],
                               n_virtual=cfg.get('n_virtual', 1)
                               ).transpile(p)
        except ValueError:
            p._pipeline_config = None

    def inference_optimize(self):
        return self.clone(for_test=True)

    def verify(self, level='error', startup=None, feeds=None, fetches=None,
               concurrent=False):
        """Static analysis of this program BEFORE lowering (docs/analysis.md):
        dataflow/def-use, shape/dtype inference, donation safety and
        scope-race checks over every block. Returns the list of
        analysis.Finding objects.

        level: 'error' raises analysis.ProgramVerifyError when any
        error-severity finding exists (warnings are warned); 'warn' warns
        for every finding; 'off' skips analysis and returns [].
        startup/feeds/fetches/concurrent refine the context exactly as
        fluid.analysis.analyze does."""
        if level not in ('off', 'warn', 'error'):
            raise ValueError(
                "verify level must be 'off', 'warn' or 'error', got %r"
                % (level,))
        if level == 'off':
            return []
        from . import analysis
        findings = analysis.analyze(self, startup=startup, feeds=feeds,
                                    fetches=fetches, concurrent=concurrent)
        analysis.report_findings(findings, mode=level,
                                 where='Program.verify')
        return findings

    def optimize(self, level='default', feeds=None, fetches=None):
        """Ahead-of-lowering optimization (docs/passes.md): returns a NEW
        Program rewritten by the fluid.passes pipeline — AMP cast
        insertion, constant folding, CSE, and (when `fetches` is given)
        dead-op elimination. This program is never mutated. The
        PassReport lands on the result as `_opt_report`.

        The Executor applies the same pipeline automatically behind
        PADDLE_TPU_OPT={off,default,aggressive}, once per compiled-step
        cache key; this method is the manual/offline surface (e.g.
        optimizing before save_inference_model)."""
        from . import passes
        p, report = passes.optimize(self, feeds=feeds, fetches=fetches,
                                    level=level)
        if p is self:
            # passes.optimize returns the input itself when nothing can
            # run (level='off', pipeline-transpiled) — the executor wants
            # that aliasing, but THIS method promises a program the
            # caller owns and may mutate
            p = self.clone(for_test=False)
            p._opt_report = report
        return p

    def prune(self, targets):
        """Backward-slice the program to the ops needed to compute
        `targets` (reference Program.prune / C++ framework/prune.cc)."""
        if not isinstance(targets, (list, tuple)):
            targets = [targets]
        needed = {t.name if isinstance(t, Variable) else str(t)
                  for t in targets}
        p = self.clone(for_test=False)
        blk = p.global_block()
        keep = []
        for op in reversed(blk.ops):
            out_names = set(op.output_arg_names)
            if out_names & needed:
                keep.append(op)
                needed |= set(op.input_arg_names)
        keep.reverse()
        blk.ops = keep
        p._pipeline_config = None
        self._retranspile_pipeline(p)
        p._bump_version()
        return p

    def to_string(self, throw_on_error=False, with_details=False):
        lines = []
        for blk in self.blocks:
            lines.append("-- block %d (parent %d) --" % (blk.idx, blk.parent_idx))
            for v in blk.vars.values():
                lines.append("    " + repr(v))
            for op in blk.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)

    __str__ = to_string
    __repr__ = to_string

    # -- serialization (reference: ProgramDesc protobuf round-trip) --
    def _to_dict(self):
        d = dict(random_seed=self.random_seed,
                 blocks=[b._to_dict() for b in self.blocks])
        if self._mesh_axes is not None:
            # mesh spec survives save/load so program_lint --mesh and a
            # re-loaded artifact see the same annotation context
            d['mesh'] = {'axes': [[n, s] for n, s in self._mesh_axes],
                         'data_axis': self._mesh_data_axis}
        return d

    @staticmethod
    def _from_dict(d):
        p = Program()
        p.random_seed = d.get('random_seed', 0)
        mesh = d.get('mesh')
        if mesh:
            p.set_mesh([(n, s) for n, s in mesh['axes']],
                       data_axis=mesh.get('data_axis'))
        p.blocks = []
        for bd in d['blocks']:
            blk = Block(p, bd['idx'], bd['parent_idx'])
            p.blocks.append(blk)
            for vd in bd['vars']:
                vd = dict(vd)
                cls = vd.pop('cls', 'Variable')
                name = vd.pop('name')
                if cls == 'Parameter':
                    vd.pop('optimize_attr', None)
                    Parameter(blk, name=name, **vd)
                else:
                    Variable(blk, name=name, **vd)
        for bd in d['blocks']:
            blk = p.blocks[bd['idx']]
            for od in bd['ops']:
                ins = {k: [blk._var_recursive(n) for n in vs]
                       for k, vs in od['inputs'].items()}
                outs = {k: [blk._var_recursive(n) for n in vs]
                        for k, vs in od['outputs'].items()}
                # the serialized build site (or None) — never the
                # deserialization frame, which would mislabel every finding
                blk.append_op(type=od['type'], inputs=ins, outputs=outs,
                              attrs=od['attrs'], infer_shape=False,
                              callsite=od.get('callsite'))
        p._bump_version()
        return p


_main_program_ = Program()
_startup_program_ = Program()


def default_startup_program():
    return _startup_program_


def default_main_program():
    return _main_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_start = None
    if startup_program is not None:
        prev_start = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_start is not None:
            switch_startup_program(prev_start)


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    """Ops appended inside carry the path of the scopes they were built in
    as the attribute `name_scope` ('mtp/latent_attention'), and
    lowering.run_op enters it around the op's own `<type>_<index>` scope:
    an HLO instruction's op_name then reads
    `.../mtp/latent_attention/mul_17/...`, so a trace can tell which `mul`
    belongs to which part of the model. Any prefix is taken, as the
    reference takes it (`layer_1`, `block.0`); what the trace shows of it
    is lowering.scope_label's to make safe."""
    prefix = prefix or ''
    _name_scope_stack.append(prefix)
    try:
        yield
    finally:
        _name_scope_stack.pop()


_recompute_stack = []
_recompute_serial = itertools.count(1)


@contextlib.contextmanager
def recompute_guard():
    """The forward ops appended inside form ONE region that the training
    step recomputes in its backward pass: the step keeps the region's
    inputs (and the attention kernels' outputs and statistics, and what
    the model marks with `recompute_keep`) and not what else the region
    computes on the way, one `jax.checkpoint` a region
    (step_artifact._run_ops). A model marks each decoder layer. A region
    is the guard's ops in a row; a guard inside another belongs to the
    outer one. The default main program is marked `_use_remat`, which
    `fluid.memory_optimize` sets for a Program with no region of its own
    (the whole forward is then the one region)."""
    _recompute_stack.append(_recompute_stack[-1] if _recompute_stack
                            else next(_recompute_serial))
    default_main_program()._use_remat = True
    try:
        yield
    finally:
        _recompute_stack.pop()


class RecomputeKeepError(ValueError):
    """`fluid.recompute_keep` was handed something no recompute region
    built: a mark there would keep nothing, silently."""


def recompute_keep(var):
    """Marks `var`, built inside a `fluid.recompute_guard()` region, as
    KEPT: the step saves its value beside the region's inputs and the
    backward pass does not compute it again (nor what only it needed: a
    kept projection's matmul leaves the region's second forward). A kept
    value costs its bytes for the whole step and saves the FLOPs that made
    it: for the output of a matmul over K columns, 2 x K FLOPs for an
    element of 4 bytes. The mark is the attribute `recompute_keep` of the
    op that produced `var` (its output names), so the Program's
    fingerprint carries it. Returns `var`."""
    op = getattr(var, 'op', None)
    if op is None or op.attrs.get('recompute') is None:
        raise RecomputeKeepError(
            'recompute_keep: %r was not built inside a '
            'fluid.recompute_guard() region'
            % (getattr(var, 'name', var),))
    kept = op.attrs.get('recompute_keep', [])
    if var.name not in kept:
        op._set_attr('recompute_keep', sorted(kept + [var.name]))
    return var


_device_guard_stack = []


@contextlib.contextmanager
def device_guard(device=None):
    """Op placement annotation (later-Paddle `fluid.device_guard`; the
    closest v0.14 notion is per-op Place dispatch). On TPU, XLA owns chip
    placement, so the only consumed form is 'pipe:K': ops appended inside
    are stamped with pipeline stage K, which PipelineTranspiler turns into
    a GPipe schedule over the `pp` mesh axis (parallel/pipeline.py). Other
    device strings are recorded on the op but ignored."""
    _device_guard_stack.append(device)
    try:
        yield
    finally:
        _device_guard_stack.pop()


def get_var(name, program=None):
    if program is None:
        program = default_main_program()
    return program.global_block()._var_recursive(name)
