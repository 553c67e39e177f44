"""Inference deployment runtime.

Parity: reference paddle/fluid/inference + paddle/capi (load a saved
inference model and execute it without the training framework). TPU-first
there are two artifacts:

1. A program bundle (fluid.io.save_inference_model: JSON ProgramDesc +
   persistables) loaded by `Predictor` — the fluid-level path, runs through
   the normal Executor lowering with the jit cache.
2. A compiler-level artifact: `export_compiled` lowers the pruned program
   to a serialized StableHLO module via jax.export — load with
   `load_compiled` and call with no framework at all (the reference's
   C-API / inference-library equivalent; the artifact is
   compiler-portable across hosts with the same jax version).
"""
import os

import numpy as np

__all__ = ['Predictor', 'export_compiled', 'load_compiled']

_ARTIFACT = '__model__.stablehlo'
_META = '__model__.meta.json'


class Predictor(object):
    """Load + run a saved inference model (reference: NativePaddlePredictor,
    inference/api/api_impl.cc).

    Thread-safe: the model's variables live in a PRIVATE scope that is
    passed explicitly through `Executor.run(scope=...)` — never via the
    process-global `scope_guard`, which two predictors (or two threads
    on one predictor) would race on. The serving engine
    (paddle_tpu.serving) relies on this.

    `kernels`: the predictor-config surface of the pallas kernel knob
    (docs/perf.md#kernel-layer) — same grammar as the PADDLE_TPU_KERNELS
    env ('all', 'paged_attention', 'all,-sparse_adam', an iterable, a
    bool). Routes to `ops.kernels.configure()`; the enablement is
    process-level (the compile cache keys on it), and None leaves the
    env in charge."""

    def __init__(self, dirname, place=None, kernels=None):
        from ..fluid import core, io
        from ..fluid.executor import Executor, Scope
        if kernels is not None:
            from ..ops import kernels as kernels_mod
            kernels_mod.configure(kernels)
        self._scope = Scope()
        self._place = place or core.default_place()
        self._exe = Executor(self._place)
        prog, feeds, fetches = io.load_inference_model(dirname, self._exe,
                                                       scope=self._scope)
        # Ahead-of-lowering verification (PADDLE_TPU_VERIFY, docs/
        # analysis.md): a Predictor's program runs CONCURRENTLY against one
        # scope (multi-threaded run(), the serving engine), so a saved
        # artifact that still writes persistables is a scope race — reject
        # it at load time, not as corrupted params under load.
        from ..fluid import analysis
        analysis.maybe_verify(
            prog, where='predictor', feeds=list(feeds),
            fetches=[v.name for v in fetches], concurrent=True)
        self._program = prog
        self.feed_names = feeds
        self._fetch_vars = fetches

    @property
    def fetch_names(self):
        return [v.name for v in self._fetch_vars]

    @property
    def input_spec(self):
        """{feed name: (shape, dtype str)} from the loaded program; the
        leading batch dim is -1 (any). The serving engine's warmup uses
        this to build per-bucket feeds without an example."""
        blk = self._program.global_block()
        spec = {}
        for n in self.feed_names:
            v = blk.vars.get(n)
            if v is not None:
                spec[n] = (tuple(int(d) for d in v.shape), str(v.dtype))
        return spec

    def run(self, feed):
        """feed: dict name -> ndarray/LoDTensor. Returns list of ndarrays."""
        return self._exe.run(self._program, feed=feed,
                             fetch_list=self._fetch_vars, scope=self._scope)


def export_compiled(dirname, feed_example, target_vars, executor,
                    main_program=None):
    """Lower the pruned inference graph to ONE serialized StableHLO module.

    feed_example: dict name -> example ndarray fixing shapes/dtypes (pass
    DENSE arrays; sequence (lod) inputs are exported with every row
    treated full-length — pad at inference time).
    Writes `__model__.stablehlo` (jax.export serialization, params baked
    in as constants) + a meta file; returns the artifact path.
    """
    import json

    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    from ..fluid import framework
    from ..fluid.executor import global_scope
    from ..fluid.lowering import SeqValue

    if main_program is None:
        main_program = framework.default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    fetch_names = [v.name if isinstance(v, framework.Variable) else str(v)
                   for v in target_vars]
    infer = main_program.clone(for_test=True).prune(target_vars)

    # run once through the executor to build+cache the pure step fn
    executor.run(infer, feed=dict(feed_example), fetch_list=fetch_names)
    compiled = None
    for k, c in executor._cache.items():
        pid, fetches = k[0], k[3]  # (uid, version, feed_sig, fetches, ...)
        if pid == infer._uid and tuple(fetches) == tuple(fetch_names):
            compiled = c
    assert compiled is not None
    scope = global_scope()
    persist = {n: scope.vars[n] for n in compiled.persist_in}
    feed_names = sorted(feed_example)

    # reproduce Executor.run's feed wrapping: lod-level vars were traced as
    # SeqValue(data, lengths) (dense feed = every row full-length)
    blk = infer.global_block()
    lod_feed = {n for n in feed_names
                if blk.vars.get(n) is not None and blk.vars[n].lod_level > 0}

    def fn(*arrays):
        feed = {}
        for n, a in zip(feed_names, arrays):
            var = blk.vars.get(n)
            if var is not None and var.dtype not in (str(a.dtype), 'bfloat16'):
                a = a.astype(np.dtype(var.dtype))
            if n in lod_feed:
                lens = jnp.full((a.shape[0],), a.shape[1], jnp.int32)
                feed[n] = SeqValue(a, lens)
            else:
                feed[n] = a
        fetches = compiled._step(*compiled.plan.split(persist), feed,
                                 jax.random.key(0)).fetches
        return [f.data if isinstance(f, SeqValue) else f for f in fetches]

    args = [jnp.asarray(feed_example[n]) for n in feed_names]
    exported = jax_export.export(jax.jit(fn))(*args)
    os.makedirs(dirname, exist_ok=True)
    path = os.path.join(dirname, _ARTIFACT)
    with open(path, 'wb') as f:
        f.write(exported.serialize())
    # per-input shapes/dtypes AS EXPORTED (post jnp.asarray, so an int64
    # example records the int32 the x64-disabled module actually takes):
    # load_compiled validates feeds against these instead of letting jax
    # fail deep inside exported.call
    inputs = {n: {'shape': list(a.shape), 'dtype': str(a.dtype)}
              for n, a in zip(feed_names, args)}
    with open(os.path.join(dirname, _META), 'w') as f:
        json.dump({'feed_names': feed_names, 'fetch_names': fetch_names,
                   'inputs': inputs,
                   'stablehlo': exported.mlir_module()[:10000]}, f)
    return path


def load_compiled(dirname):
    """Load an export_compiled artifact -> callable(feed dict) -> [np].

    Feeds are validated against the per-input shapes/dtypes recorded in
    `__model__.meta.json` at export time: a missing/unknown name, a
    wrong shape (the exported module is FIXED-shape, batch dim
    included), or an unsafely-cast dtype raises a ValueError naming the
    offending input instead of failing deep inside `exported.call`.
    Artifacts exported before the meta carried `inputs` skip the
    shape/dtype checks."""
    import json

    import jax.numpy as jnp
    from jax import export as jax_export

    with open(os.path.join(dirname, _ARTIFACT), 'rb') as f:
        exported = jax_export.deserialize(f.read())
    with open(os.path.join(dirname, _META)) as f:
        meta = json.load(f)
    feed_names = meta['feed_names']
    inputs = meta.get('inputs') or {}

    def _validated(name, val):
        a = np.asarray(val)
        spec = inputs.get(name)
        if spec is None:
            return jnp.asarray(a)
        want_shape = tuple(spec['shape'])
        want_dtype = np.dtype(spec['dtype'])
        if a.dtype != want_dtype:
            # accept safe casts plus WITHIN-kind narrowing (int64->int32,
            # float64->float32: what jnp.asarray already applied silently
            # under disabled x64); reject kind-crossing unsafe casts
            # (int32 fed to a float32 input is a client bug worth naming)
            if np.can_cast(a.dtype, want_dtype, 'safe') or (
                    a.dtype.kind == want_dtype.kind
                    and np.can_cast(a.dtype, want_dtype, 'same_kind')):
                a = a.astype(want_dtype)
            else:
                raise ValueError(
                    'input %r: dtype %s cannot safely cast to the '
                    'exported dtype %s' % (name, a.dtype, want_dtype))
        if tuple(a.shape) != want_shape:
            raise ValueError(
                'input %r: shape %r does not match the exported shape %r '
                '(the compiled artifact is fixed-shape; pad/bucket the '
                'feed, e.g. via paddle_tpu.serving)'
                % (name, tuple(a.shape), want_shape))
        return jnp.asarray(a)

    def run(feed):
        missing = [n for n in feed_names if n not in feed]
        if missing:
            raise ValueError(
                'missing input(s) %r; the artifact expects exactly %r'
                % (missing, feed_names))
        extra = sorted(set(feed) - set(feed_names))
        if extra:
            raise ValueError(
                'unknown input(s) %r; the artifact expects exactly %r'
                % (extra, feed_names))
        args = [_validated(n, feed[n]) for n in feed_names]
        out = exported.call(*args)
        return [np.asarray(o) for o in out]

    run.feed_names = feed_names
    run.fetch_names = meta['fetch_names']
    run.input_spec = {n: (tuple(s['shape']), s['dtype'])
                      for n, s in inputs.items()}
    return run
