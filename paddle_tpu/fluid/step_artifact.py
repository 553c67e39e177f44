"""The compiled-step artifact: ONE first-class object per (program,
feed-signature, fetch-set) owning everything the four step drivers need
(the SNIPPETS.md pjit exemplar: one donation_vector/in_shardings/
out_shardings computation reused by every caller). A `StepArtifact` owns

  * the optimized program + lowered op walk (the jittable step body);
  * the memory/donation plan (fluid.passes.memplan) — which persistables
    donate, which ride read-only, which re-emerge as outputs;
  * the NamedSharding trees (GSPMD annotation path) pinned as the step's
    in/out layout fixed point;
  * the RNG-stream policy (op_seq-stamped per-op streams; bundled scans
    re-derive per-step keys from scanned uint32 seeds);
  * its identity: `feed_names`/`fetch_names`, the feed-signature tuples,
    the cache key's id and the source program, all constructor arguments
    (cache keys and AOT manifests are built from them);
  * the `state_dict` seam (the placement-true view of `persist_in` that
    sharded checkpointing consumes);
  * every jitted entry point compiled from it: the unbundled step and
    one K-scan per bundle length (`signatures()` enumerates them), each
    behind a call seam that hands back a `StepResult`.

The four drivers stay thin: `Executor.run` dispatches one step,
`run_bundle` scans K steps over the SAME body, `StepHandle` pins a
donation view for hot loops, and the serving engines drive warmed
signatures through the same cache. All of them build through
`Executor._prepare`, which resolves one artifact per cache key — the
driver-equivalence drill in tests/test_step_artifact.py asserts the
shared entry and bit-identical fetches.

`pin_state` is the donate-exactly-once contract: persistable state is
committed to its device placement BEFORE the first jitted call, so the
first call's argument signature (committed device arrays) is identical
to every later call's (donated outputs come back committed) and each
entry point compiles exactly once.
"""
import os
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import obs
from . import lowering
from .lowering import SeqValue, Ctx

__all__ = ['StepArtifact', 'StepResult', 'program_fingerprint',
           'stable_signature', 'aot_manifest', 'write_aot', 'read_aot',
           'aot_check', 'AOT_MANIFEST', 'AOT_CACHE_DIR']


# What a recompute region keeps besides its inputs (`_run_region`), three
# kinds: the flash calls' outputs and row statistics; what the model marked
# with `fluid.recompute_keep`, every mark of every region under the ONE
# name REGION_KEEP; and, under the same name, what an expert layer's route
# stage DECIDED (ops_impl/moe_ops.py `_routed`, parallel/moe.py: the
# router's choice, the auxiliary loss's `f`, the counts, the compact path's
# index and plan): integers and one [E] vector that take no gradient and
# that the second forward would make again bit for bit, a megabyte a layer,
# named by the rule itself, with no barrier and no count among the marks
# (`moe.route_kept`). ONE object for every region: jax caches the split of a
# jitted body into what is known and what is run again on the policy's
# identity, so regions that call one shared body (lowering.traced_once,
# the kernels' own jits) share its halves too; a policy made anew a region
# gave every layer its own.
REGION_KEEP = 'region_keep'
_REGION_KEEPS = jax.checkpoint_policies.save_only_these_names(
    'flash_out', 'flash_lse', REGION_KEEP)


def _is_annotated(program):
    """True for a Program on the first-class GSPMD annotation path:
    a `set_mesh()` spec and no legacy transpiler `_dist_config` (the
    transpilers keep their own mesh build until fully retired)."""
    return (getattr(program, '_mesh_axes', None) is not None
            and getattr(program, '_dist_config', None) is None)


def _feed_signature(name, val):
    if isinstance(val, SeqValue):
        return (name, 'seq', tuple(val.data.shape), str(val.data.dtype))
    arr = np.asarray(val) if not hasattr(val, 'shape') else val
    return (name, tuple(arr.shape), str(arr.dtype))


class StepResult(NamedTuple):
    """What one execution of a compiled step hands back, whichever driver
    made it (the jitted step, `debug_step`, a `StepHandle`, a first call).
    A bundle's K-scan returns the same record with `fetches`, `health`
    and `counters` stacked on a leading K axis and `new_persist` as the
    state after the last step."""
    fetches: Any       # [value], in fetch_names order
    new_persist: Any   # {name: value} of the persistables the step wrote
    health: Any        # the anomaly guard's vector, None when unarmed
    counters: Any      # the device counters' vector, None without any


class StepArtifact(object):
    """One lowered+jitted (program, feed-sig, fetch) combination."""

    def __init__(self, program, block, feed_names, fetch_names, persist_in,
                 feed_sig, key_id, source_program, input_of,
                 amp=False, platform='cpu', persist_shardings=None,
                 mesh=None, guard=False, jit_shardings=None):
        self.program = program
        self.amp = amp
        self.platform = platform
        self.mesh = mesh
        # in-graph anomaly guard (see anomaly_guard()): only meaningful on
        # training steps — without an autodiff op there are no gradients
        # to check and no optimizer update to skip
        self.guard = bool(guard)
        # GPipe region from PipelineTranspiler: only active when a mesh
        # with the pp axis exists; otherwise the stamped ops run
        # sequentially (identical semantics, which tests compare against)
        pipe = getattr(program, '_pipeline_config', None)
        self.pipe = (pipe if pipe is not None and mesh is not None
                     and pipe['axis'] in getattr(mesh, 'shape', {})
                     else None)
        if self.pipe is not None and 'sp' in getattr(mesh, 'shape', {}):
            # backstop for programs whose configs were hand-assembled or
            # clone-carried past the transpilers' own validation: stage
            # bodies run sequence-local under sp (see pipeline_transpiler)
            from .transpiler.pipeline_transpiler import (
                validate_sp_sequence_local)
            lo0, hi0 = self.pipe['stage0']
            validate_sp_sequence_local(block.ops[lo0:hi0])
        if self.pipe is not None:
            lo_r, hi_r = self.pipe['region']
            internal = set()
            for op in block.ops[lo_r:hi_r]:
                internal.update(op.output_arg_names)
            internal.discard(self.pipe['output_var'])
            bad = internal & set(fetch_names)
            if bad:
                raise ValueError(
                    'cannot fetch %r: produced inside the pipeline region, '
                    'which runs as one GPipe call — fetch the stage output '
                    '%r or run the program untranspiled'
                    % (sorted(bad), self.pipe['output_var']))
        self.use_remat = bool(getattr(program, '_use_remat', False))
        # name -> NamedSharding: enforced on the step's outputs so
        # mesh-placed state (ZeRO accumulators, tp weights) STAYS sharded
        # inside the compiled module instead of relying on propagation
        self.persist_shardings = dict(persist_shardings or {})
        ops = list(block.ops)
        self.ops = ops
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.persist_in = list(persist_in)
        # identity: the placed-feed signature tuples this artifact was
        # keyed on, the short cache-key id it reports under, and the SOURCE
        # program (self.program may be the optimized clone) — the inputs
        # of stable_signature()
        self._feed_sig = feed_sig
        self._key_id = key_id
        self._source_program = source_program
        self._stable_sig = None
        # which entry points a driver has first-called (the compile-vs-
        # deserialize probe runs once each; the AOT manifest exports them)
        self._obs_compiled = False
        self._obs_bundles = set()
        ad_idxs = [i for i, op in enumerate(ops) if op.type == 'autodiff']
        assert len(ad_idxs) <= 1, "at most one append_backward per program"
        self.ad_idx = ad_idxs[0] if ad_idxs else None
        # the step's device counters (lowering.register_device_counter),
        # in op order: (label, op index, DeviceCounter). The label is the
        # op's own scope in the module's op_name, `<type>_<index>`.
        # `counter_facts` is filled when the step is traced ({op index:
        # what the op's rule told Ctx.note}). Ops of a pipeline region
        # bind nothing in the step's env, so a pipelined step keeps none.
        self.counters = []
        self.counter_facts = {}
        # the rule bodies this step's ops share (lowering.traced_once)
        self.bodies = {}
        for i, op in enumerate(ops if self.pipe is None else ()):
            found = lowering.device_counter(op)
            if found is not None:
                seq = op.attrs.get('op_seq', i)
                self.counters.append(
                    ('%s_%d' % (op.type, seq), seq, found))
        self.regions = self._recompute_regions(program)
        for op in (o for blk in program.blocks for o in blk.ops):
            # loud inertness check (docs/embedding.md): a TRAINING step
            # whose lookup was built for the distributed wire (annotated
            # table, is_distributed) compiling WITHOUT a mesh that
            # declares its axis silently degrades to a replicated dense
            # gather — the pserver-era failure mode this subsystem
            # exists to replace. Once per compiled key, like every other
            # _prepare-time diagnostic. Inference programs are exempt:
            # the documented export seam (gather_table + set_mesh(None),
            # docs/serving.md) runs the for_test clone dense-after-
            # gather on purpose.
            if (self.ad_idx is not None and op.type == 'lookup_table'
                    and op.attrs.get('is_distributed')
                    and op.attrs.get('dist_axis') is not None
                    and (mesh is None or op.attrs['dist_axis']
                         not in getattr(mesh, 'shape', {}))):
                import warnings
                warnings.warn(
                    "embedding(is_distributed=True) on table %r is "
                    "annotated for mesh axis %r but the step compiles "
                    "against %s — the lookup runs as a replicated dense "
                    "gather. Declare Program.set_mesh({%r: N, ...}) to "
                    "shard it (docs/embedding.md)."
                    % (op.inputs['W'][0].name, op.attrs['dist_axis'],
                       'no mesh' if mesh is None
                       else 'mesh axes %r' % sorted(mesh.shape),
                       op.attrs['dist_axis']), UserWarning)
        self.sparse_plan = self._sparse_embedding_plan(program)
        # the plan's tables that a trace found their ids for (_grad_setup)
        self._sparse_active = {}
        # sparse-embedding accounting (docs/embedding.md): the rows-
        # touched-per-step bound is static given the step's inputs, so it
        # is resolved here, once a compiled key — a driver's hot loop only
        # bumps a counter by `_embed_rows_step`
        self._embed_rows = self._rows_per_step(input_of)
        self._embed_rows_step = sum(self._embed_rows.values())
        # Donation/memory plan (fluid.passes.memplan): which persistables
        # the ops actually WRITE decides donation. A mutating step
        # (training: optimizer updates, BN stats, LR counters) donates
        # EXACTLY its written buffers — in-place HBM updates, re-exposed
        # as outputs — while read-only persistable inputs (frozen
        # weights, inference BN stats) are neither donated nor carried
        # through the module's output list: their scope buffers stay
        # valid, and XLA stops paying a passthrough copy per step. A
        # fully read-only step (inference) donates nothing at all:
        # donation there would invalidate the param buffers under
        # concurrent runs (the serving engine / multi-threaded
        # Predictors). The plan derives from the SAME write-set
        # fluid.analysis verifies, so the static donation-safety pass
        # cross-checks THIS decision, not a copy of it; run_bundle and
        # the serving warmup consume the same plan object.
        from .passes import memory_plan
        self.plan = memory_plan(program)
        self.mutates_persist = self.plan.donates
        self.donate_names = self.plan.donate_names(self.persist_in)
        self.readonly_names = self.plan.readonly_names(self.persist_in)
        self.persist_out = self.plan.persist_out()
        # GSPMD annotation path (docs/parallel.md): explicit jit in/out
        # sharding trees derived by the memory plan from the ACTUAL
        # placed shardings — donated inputs and persistable outputs
        # share one NamedSharding object per name, so the compiled
        # step's state layout is a fixed point (no inter-step
        # resharding, no involuntary rematerialization at scan/carry
        # boundaries). jit_shardings: {'persist': name->sharding|None,
        # 'feed': name->sharding|None, 'specs': name->annotation}.
        self._annot_sh = None
        if jit_shardings is not None and mesh is not None:
            from jax.sharding import NamedSharding as _NS, \
                PartitionSpec as _PS
            repl = _NS(mesh, _PS())
            don_sh, ro_sh, out_sh = self.plan.sharding_plan(
                self.persist_in, jit_shardings['persist'])
            for n in out_sh:
                if out_sh[n] is None and n not in jit_shardings['persist']:
                    # persistable the step CREATES (startup programs):
                    # its annotation decides the birth layout
                    spec = jit_shardings['specs'].get(n)
                    out_sh[n] = _NS(mesh, _PS(*spec)) if spec else repl
            self._annot_sh = (don_sh, ro_sh,
                              dict(jit_shardings['feed']), out_sh)

        run_range = self._run_ops

        def step_body(donated, readonly, feed, key):
            env = dict(readonly)
            env.update(donated)
            env.update(feed)
            health = None
            if self.ad_idx is None:
                run_range(env, 0, len(ops), key)
            else:
                ad = ops[self.ad_idx]
                pnames, gnames, trainable, base, taps = \
                    self._grad_setup(env, ad)
                fwd = self._make_fwd(base, ad, key, taps=taps)
                grads, env = jax.grad(fwd, has_aux=True)(trainable)
                self._apply_grads(grads, env, ad, pnames, gnames)
                if self.guard:
                    health = self._step_health(env, ad, pnames, gnames)
                run_range(env, self.ad_idx + 1, len(ops), key)
            fetches = [env[n] for n in self.fetch_names]
            new_persist = {n: env[n] for n in self.persist_out if n in env}
            if health is not None:
                self._select_healthy(health['healthy'], new_persist,
                                     donated)
            for n, sh in self.persist_shardings.items():
                if n in new_persist and not isinstance(new_persist[n], SeqValue):
                    new_persist[n] = jax.lax.with_sharding_constraint(
                        new_persist[n], sh)
            return StepResult(fetches, new_persist, health,
                              self._device_counters(env))

        def step(donated, readonly, feed, key):
            # a plain tuple across the jit boundary: jax writes each
            # result's path in the output tree into the lowered module
            # (`jax.result_info`), so a named record here would change
            # every module's text and, with it, its key in the persistent
            # compile cache. `__call__` puts the names back on the host.
            return tuple(step_body(donated, readonly, feed, key))

        # the pure, un-jitted body over (donated, readonly, feed, key): what
        # a bundle scans, and what a caller that traces the step into a
        # function of its own calls (export_compiled, __graft_entry__)
        self._step = step_body
        # the donation vector comes from the memory plan for BOTH paths
        # (one definition: donate exactly the written-persistables arg)
        donate = self.plan.donate_argnums(self.persist_in)
        if self._annot_sh is not None:
            don_sh, ro_sh, feed_sh, out_sh = self._annot_sh
            self._jitted = jax.jit(
                step,
                in_shardings=(don_sh, ro_sh, feed_sh, None),
                out_shardings=(None, out_sh, None, None),
                donate_argnums=donate)
        else:
            self._jitted = jax.jit(step, donate_argnums=donate)
        # K -> jitted K-step lax.scan over the SAME step body (run_bundle)
        self._bundles = {}

    def bundle(self, K):
        """The K-step bundled executable: ONE jitted lax.scan whose body is
        the exact `step` the unbundled path jits — one device dispatch and
        one host round-trip per K steps instead of per step. Carry is the
        persist dict (donated, so persistables stay in-place in HBM across
        ALL K inner steps); xs are the stacked feeds plus per-step uint32
        seeds — the RNG key is created INSIDE the body from the same seed
        integer run() would pass to jax.random.key on the host, so
        per-step randomness is bit-identical to K unbundled runs. ys are
        the per-step fetches (stacked on a leading K axis) and, when the
        anomaly guard is armed, the per-step health vectors (rollback
        already applied in-graph by `step`, per inner step) and, where the
        step keeps device counters, theirs ([K, counters]). Returns the
        call seam over it: `(donated, readonly, feeds, seeds)` ->
        StepResult, named on the host as `__call__` names the step's."""
        K = int(K)
        fn = self._bundles.get(K)
        if fn is None:
            step = self._step

            def bundled(donated, readonly, feeds, seeds):
                # carry = the plan's donated (written) set only; the
                # read-only persistables ride along as a plain argument,
                # invariant across the scan
                def body(carry, xs):
                    feed, seed = xs
                    res = step(carry, readonly, feed, jax.random.key(seed))
                    nxt = {n: res.new_persist.get(n, carry[n])
                           for n in carry}
                    return nxt, (res.fetches, res.health, res.counters)

                return jax.lax.scan(body, donated, (feeds, seeds))

            donate = self.plan.donate_argnums(self.persist_in)
            if self._annot_sh is not None:
                # same sharding fixed point as the unbundled jit: the
                # scan carry's in- and out-shardings are the SAME
                # objects, feeds gain a leading (scanned) K dim
                from jax.sharding import NamedSharding as _NS, \
                    PartitionSpec as _PS
                don_sh, ro_sh, feed_sh, _out = self._annot_sh
                stacked_sh = {
                    n: (_NS(sh.mesh, _PS(None, *sh.spec))
                        if isinstance(sh, _NS) else None)
                    for n, sh in feed_sh.items()}
                fn = jax.jit(
                    bundled,
                    in_shardings=(don_sh, ro_sh, stacked_sh, None),
                    out_shardings=(don_sh, None),
                    donate_argnums=donate)
            else:
                fn = jax.jit(bundled, donate_argnums=donate)
            self._bundles[K] = fn

        def call(donated, readonly, feeds, seeds):
            new_persist, stacked = fn(donated, readonly, feeds, seeds)
            return StepResult(stacked[0], new_persist, *stacked[1:])

        return call

    # optimizer ops with a SparseRows (SelectedRows-analogue) grad branch
    # in ops_impl/optim_ops.py
    _SPARSE_OPTS = frozenset(['sgd', 'adagrad', 'adam'])

    def _sparse_embedding_plan(self, program):
        """Which embedding tables can take the sparse gradient path.

        Reference: lookup_table_op.cc emits a SelectedRows grad when
        is_sparse=True and sgd/adagrad/adam update only the touched rows.
        Here jax.grad would produce a DENSE vocab-sized @GRAD buffer; for a
        table W we instead differentiate w.r.t. a zero "tap" added to each
        lookup's gathered rows, and hand the optimizer a
        lowering.SparseRows(ids, rows) — the vocab-sized buffer never
        exists (VERDICT r4 item 4). Eligibility (else silent dense
        fallback, bit-identical for SGD since scatter-add is how XLA
        derives the dense grad anyway):
          - every reader of W (except its optimizer op) is a lookup_table
            with is_sparse=True;
          - W@GRAD is consumed by exactly one sgd/adagrad/adam op and
            produced only by autodiff (no clip/regularizer rewriting it),
            is not persistable and not fetched;
          - the step is unsharded (self.mesh is None), OR — the sharded-
            embedding subsystem (docs/embedding.md) — the program is on
            the first-class annotation path and W is row-sharded over a
            mesh axis with every lookup stamped for the distributed wire
            (is_sparse=True + is_distributed=True): the SparseRows grad
            then stays touched-rows-only and the optimizer's row scatter
            partitions per shard, so the dense [vocab, dim] gradient
            never exists on any device. Legacy transpiler meshes keep
            the dense fallback: there the dense grad IS the right thing
            — XLA all-reduces it — and SelectedRows never distributed in
            the reference either.
        Returns {w_name: {'lookups': [(op_idx, ids_name, padding_idx)],
                          'gname': str}}."""
        if self.ad_idx is None:
            return {}
        if self.mesh is not None and not _is_annotated(program):
            return {}
        ad = self.ops[self.ad_idx]
        gnames = dict(zip(ad.attrs['param_names'], ad.attrs['grad_names']))
        persistable = {v.name for v in program.list_vars() if v.persistable}
        readers = {}   # var name -> [op index]
        writers = {}
        for i, op in enumerate(self.ops):
            if i == self.ad_idx:
                continue
            for n in op.input_arg_names:
                readers.setdefault(n, []).append(i)
            for n in op.output_arg_names:
                writers.setdefault(n, []).append(i)
        plan = {}
        for w, gname in gnames.items():
            if self.mesh is not None:
                var = program.global_block().vars.get(w)
                spec = getattr(var, 'sharding', None)
                row = spec[0] if spec else None
                if (row is None or isinstance(row, tuple)
                        or row not in getattr(self.mesh, 'shape', {})):
                    # mesh without a row-sharded annotation: the dense
                    # grad all-reduces; only the sharded-sparse
                    # combination takes the SparseRows path here
                    continue
            lookups = []
            opt_idx = None
            ok = gname not in self.fetch_names and gname not in persistable
            for i in set(readers.get(w, [])):
                op = self.ops[i]
                if (op.type == 'lookup_table' and op.attrs.get('is_sparse')
                        and op.inputs['W'][0].name == w
                        and (self.mesh is None
                             or op.attrs.get('dist_axis') is not None)):
                    lookups.append(
                        (i, op.inputs['Ids'][0].name,
                         op.attrs.get('padding_idx', -1)))
                elif (op.type in self._SPARSE_OPTS and opt_idx is None
                      and any(v.name == gname
                              for v in op.inputs.get('Grad', []))):
                    opt_idx = i
                else:
                    ok = False
            grad_readers = set(readers.get(gname, []))
            grad_writers = set(writers.get(gname, []))
            if (ok and lookups and opt_idx is not None
                    and grad_readers <= {opt_idx} and not grad_writers):
                plan[w] = {'lookups': sorted(lookups), 'gname': gname}
        return plan

    def _rows_per_step(self, input_of):
        """Static per-step bound on table rows the sparse-embedding plan
        touches: the total id count of the plan's lookups, from the shapes
        of the step's inputs (`input_of`: name -> the fed or scope-held
        value, or None). On-device merge collapses duplicates, so the true
        unique count is <= this; the dense path would touch the full vocab
        instead. Mirrors _grad_setup's ALL-OR-NOTHING activation per
        table: a table with ANY unresolvable ids tensor falls back to the
        dense path there, so it must contribute zero here, or the counter
        would claim touched-rows updates while the [vocab, dim] dense grad
        materializes. Returns {table: rows}, 0 for fallen-back tables."""
        per_table = {}
        for w, plan in self.sparse_plan.items():
            table_rows = 0
            for _, ids_name, _ in plan['lookups']:
                v = input_of(ids_name)
                if v is None:
                    table_rows = 0
                    break   # dense fallback for this whole table
                shp = tuple(getattr(lowering.data_of(v), 'shape', ()))
                if shp and shp[-1] == 1:
                    shp = shp[:-1]
                table_rows += int(np.prod(shp)) if shp else 1
            per_table[w] = table_rows
        return per_table

    @staticmethod
    def _tap_name(w, op_idx):
        return '%s@SPTAP%d' % (w, op_idx)

    def _grad_setup(self, env, ad):
        """Split env into trainable params vs everything else for jax.grad.

        Sparse-embedding params (self.sparse_plan) are NOT differentiated
        directly: a zero tap per lookup joins `trainable` instead, whose
        gradient is the per-occurrence row gradient (see
        _sparse_embedding_plan). Returns (pnames, gnames, trainable, base,
        taps) where taps maps lookup op index -> (tap name, out var name)
        for _run_ops to inject."""
        pnames = [n for n in ad.attrs['param_names'] if n in env]
        gnames = dict(zip(ad.attrs['param_names'], ad.attrs['grad_names']))
        taps = {}
        sparse_active = {}
        for w, plan in self.sparse_plan.items():
            if w not in env:
                continue
            # ids must be resolvable BEFORE the forward runs to size the
            # zero taps: feed/persist vars only (intermediate id tensors
            # fall back to the dense path)
            if not all(ids in env for _, ids, _ in plan['lookups']):
                continue
            sparse_active[w] = plan
        trainable = {n: env[n] for n in pnames if n not in sparse_active}
        for w, plan in sparse_active.items():
            d = env[w].shape[-1]
            for op_idx, ids_name, _pad in plan['lookups']:
                ids = lowering.data_of(env[ids_name])
                shp = ids.shape[:-1] if (ids.ndim and ids.shape[-1] == 1) \
                    else ids.shape
                op = self.ops[op_idx]
                taps[op_idx] = (self._tap_name(w, op_idx),
                                op.outputs['Out'][0].name)
                trainable[self._tap_name(w, op_idx)] = jnp.zeros(
                    tuple(shp) + (d,), env[w].dtype)
        self._sparse_active = sparse_active
        pnames = [n for n in pnames if n not in sparse_active]
        base = {k: v for k, v in env.items() if k not in trainable}
        return pnames, gnames, trainable, base, taps

    def _recompute_regions(self, program):
        """{first op: (one past the last, names read, names handed on)} of
        the forward's recompute regions. A region is a run of ops that
        one `fluid.recompute_guard()` built; a Program flagged by
        `fluid.memory_optimize` that marks none has the whole forward as
        its one region (the reference traded buffer reuse there; here
        the trade is FLOPs for HBM). The backward pass recomputes a
        region from the values it read, which is all of it the step
        keeps (_run_region)."""
        if not self.use_remat or self.ad_idx is None:
            return {}
        n = self.ad_idx
        marks = [op.attrs.get('recompute') for op in self.ops[:n]]
        spans, i = [], 0
        while i < n:
            j = i + 1
            if marks[i] is not None:
                while j < n and marks[j] == marks[i]:
                    j += 1
                spans.append((i, j))
            i = j
        spans = spans or [(0, n)]

        def reads(op):
            names = set(op.input_arg_names)
            subs = list(op.attrs.get('sub_blocks') or [])
            if op.attrs.get('sub_block') is not None:
                subs.append(op.attrs['sub_block'])
            for b in subs:
                for inner in program.block(b).ops:
                    names |= reads(inner)
            return names

        read_at = [reads(op) for op in self.ops]
        always = set(self.fetch_names) | {
            v.name for v in program.list_vars() if v.persistable} | {
            found.source for _, _, found in self.counters}
        regions = {}
        for lo, hi in spans:
            inside = set().union(*read_at[lo:hi])
            outside = always.union(*(read_at[:lo] + read_at[hi:]))
            written = {name for op in self.ops[lo:hi]
                       for name in op.output_arg_names}
            regions[lo] = (hi, sorted(inside), sorted(written & outside))
        return regions

    def _run_region(self, env, lo, key, taps):
        """Ops [lo, hi) of one recompute region as ONE jax.checkpoint: the
        backward pass is handed the region's inputs and runs its ops
        again. Kept besides: what ops/flash_attention.py names
        `flash_out` and `flash_lse`, an attention call's output and row
        statistics (a sixth of its forward's bytes, and its forward is a
        third of its work), and the outputs the model marked
        (`fluid.recompute_keep`; `_keep_marked`)."""
        hi, read, handed_on = self.regions[lo]
        tap_names = [taps[i][0] for i in range(lo, hi) if taps and i in taps]
        inputs = {n: env[n] for n in read + tap_names if n in env}

        def region(inputs):
            e = dict(inputs)
            self._run_ops(e, lo, hi, key, grad_mode=True, taps=taps,
                          in_region=True)
            return {n: e[n] for n in handed_on if n in e}

        env.update(jax.checkpoint(region, policy=_REGION_KEEPS)(inputs))
        return hi

    @staticmethod
    def _keep_marked(op, env):
        """Names the outputs of `op` that carry `fluid.recompute_keep`'s
        mark REGION_KEEP, which `_REGION_KEEPS` saves: the region's
        second forward reads them and does not run what made them. Each
        goes on behind an `optimization_barrier`, an array in memory that
        its readers read: a kept value is written once whatever happens,
        and without the barrier XLA builds it again inside each reader
        from what made it (two operands and a `reduce_precision` where
        the parent's fusion read one array), and a matmul that takes such
        a reader in gets a tiling three times as slow
        (`granite4hmicro_s8192`'s W_out; chip, PR 54). Counted at trace
        time: `recompute.kept_values`, and `recompute.kept_bytes` from
        their shapes and dtypes."""
        for name in op.attrs.get('recompute_keep', ()):
            v = env[name]
            data = jax.lax.optimization_barrier(
                checkpoint_name(lowering.data_of(v), REGION_KEEP))
            env[name] = lowering.like(v, data)
            obs.counter('recompute.kept_values').inc()
            obs.counter('recompute.kept_bytes').inc(
                data.size * data.dtype.itemsize)

    def _make_fwd(self, base, ad, key, taps=None):
        """The differentiable forward closure: trainable -> (loss, env)."""
        def fwd(tr):
            e = dict(base)
            e.update(tr)
            self._run_ops(e, 0, self.ad_idx, key, grad_mode=True,
                          taps=taps)
            loss = e[ad.attrs['loss_name']]
            return jnp.sum(loss.astype(jnp.float32)), e
        return fwd

    def _apply_grads(self, grads, env, ad, pnames, gnames,
                     check_nan_inf=False):
        """Scale/cast gradients into env under their @GRAD names. Shared by
        the jitted step and debug_step so both paths compute identically.
        Sparse-embedding params bind a lowering.SparseRows under their
        @GRAD name instead of a dense vocab-sized buffer."""
        scale = ad.attrs.get('loss_scale', 1.0)
        for n in pnames:
            g = grads[n]
            if scale != 1.0:
                g = g * scale
            g = g.astype(env[n].dtype)
            if check_nan_inf and not bool(jnp.isfinite(g).all()):
                raise FloatingPointError(
                    "NaN/Inf in gradient %r (of parameter %r)"
                    % (gnames[n], n))
            env[gnames[n]] = g
        for w, plan in self._sparse_active.items():
            d = env[w].shape[-1]
            ids_parts, row_parts = [], []
            for op_idx, ids_name, pad in plan['lookups']:
                ids = lowering.data_of(env[ids_name]).astype(
                    jnp.int32).reshape((-1,))
                rows = grads[self._tap_name(w, op_idx)].reshape((-1, d))
                if pad is not None and pad >= 0:
                    # the dense grad's padding_idx row is zeroed by the
                    # lookup rule's w.at[pad].set(0); mirror that here
                    rows = jnp.where((ids == pad)[:, None], 0.0, rows)
                ids_parts.append(ids)
                row_parts.append(rows)
            rows = jnp.concatenate(row_parts, axis=0)
            if scale != 1.0:
                rows = rows * scale
            rows = rows.astype(env[w].dtype)
            if check_nan_inf and not bool(jnp.isfinite(rows).all()):
                raise FloatingPointError(
                    "NaN/Inf in gradient %r (of parameter %r)"
                    % (gnames[w], w))
            env[gnames[w]] = lowering.SparseRows(
                jnp.concatenate(ids_parts, axis=0), rows, env[w].shape)

    def _step_health(self, env, ad, pnames, gnames):
        """Per-step health vector, computed INSIDE the compiled module on
        values the backward pass already produced: finiteness of the loss
        and of every gradient (dense and sparse-row), and the global
        grad-norm. A few fused reductions — no extra launch, no eager
        fallback (contrast debugger.check_nan_inf, the op-by-op eager
        attribution mode)."""
        loss = lowering.data_of(env[ad.attrs['loss_name']])
        loss_finite = jnp.isfinite(loss.astype(jnp.float32)).all()
        grads_finite = jnp.asarray(True)
        sq = jnp.asarray(0.0, jnp.float32)
        names = list(pnames) + list(self._sparse_active)
        for n in names:
            g = env.get(gnames[n])
            if g is None:
                continue
            gl = g.rows if isinstance(g, lowering.SparseRows) \
                else lowering.data_of(g)
            gf = gl.astype(jnp.float32)
            grads_finite = grads_finite & jnp.isfinite(gf).all()
            sq = sq + jnp.sum(gf * gf)
        grad_norm = jnp.sqrt(sq)
        return {'healthy': loss_finite & grads_finite,
                'loss_finite': loss_finite,
                'grads_finite': grads_finite,
                'grad_norm': grad_norm}

    def _device_counters(self, env):
        """The step's device counters as ONE packed int32 vector, an entry
        a declared op in op order, reduced INSIDE the compiled module
        from variables the step already has (`_step_health` is the
        precedent); None, and a module that returns nothing more, where
        no op declares one. The module is the same whether anyone reads
        the vector: observability is no part of it (`device_record`)."""
        if not self.counters:
            return None
        return jnp.stack([
            found.reduce(lowering.data_of(env[found.source]))
            for _, _, found in self.counters]).astype(jnp.int32)

    def device_record(self, values):
        """One step's counters, read to the host (`values`, [counters]),
        as the step record's `fields['device']` holds them: an entry an
        op in op order, made by the op's own `DeviceCounter.record` from
        the integer and from what its rule noted when it was traced. The
        caller reads only while observability is on
        (Executor._read_device)."""
        return [found.record(label, int(v), self.counter_facts[seq])
                for (label, seq, found), v in zip(self.counters, values)]

    def _select_healthy(self, healthy, new_persist, persist):
        """Step-skip policy (the AMP loss-scaling skip, generalized): when
        the step is unhealthy, every persistable output rolls back to its
        pre-step value via a predicated select, so params / optimizer
        state / BN stats are bit-identical to before the step. Runs inside
        the jitted module; with donation the select aliases in place."""
        for n in list(new_persist):
            old = persist.get(n)
            new = new_persist[n]
            if old is None:
                continue
            if jax.tree_util.tree_structure(old) != \
                    jax.tree_util.tree_structure(new):
                continue  # layout changed this step; nothing to roll back to
            new_persist[n] = jax.tree_util.tree_map(
                lambda a, b: a if getattr(a, 'shape', None) != getattr(
                    b, 'shape', None) else jnp.where(healthy, a, b),
                new, old)

    def _ctx(self, key, seq):
        return Ctx(key, seq, amp=self.amp, platform=self.platform,
                   mesh=self.mesh, facts=self.counter_facts,
                   bodies=self.bodies)

    def _run_ops(self, env, lo, hi, key, grad_mode=False, on_op=None,
                 taps=None, in_region=False):
        """Execute ops [lo, hi); on_op(i, op, seconds, env) — when set, each
        op is synchronized and timed (debug/profiling path, eager only).
        taps: {op_index: (tap_name, out_var_name)} — after the op at
        op_index runs, the zero tap joins its output so jax.grad yields the
        per-row gradient there (sparse embedding path). Under grad_mode a
        recompute region (self.regions) runs as one jax.checkpoint
        (_run_region, which comes back here with `in_region`)."""
        pipe = self.pipe
        done = lo
        for i in range(lo, hi):
            if i < done:
                continue            # ran inside a recompute region
            if grad_mode and on_op is None and not in_region \
                    and i in self.regions:
                done = self._run_region(env, i, key, taps)
                continue
            if pipe is not None and on_op is None \
                    and pipe['region'][0] <= i < pipe['region'][1]:
                if i == pipe['region'][0]:
                    self._run_pipeline_region(env, key, grad_mode=grad_mode)
                continue  # region ops execute inside pipeline_apply
            op = self.ops[i]
            if op.type == 'autodiff':
                continue
            # RNG stream id: the op's ORIGINAL build index when the
            # optimizer stamped one (passes.OP_SEQ_ATTR) — op removal
            # must never shift another op's dropout mask — else the
            # list position (unoptimized programs, bit-for-bit the old
            # behavior)
            seq = op.attrs.get('op_seq', i)
            if on_op is None:
                lowering.run_op(op, env, self._ctx(key, seq))
            else:
                import time
                t0 = time.perf_counter()
                lowering.run_op(op, env, self._ctx(key, seq))
                outs = [env[v.name] for vs in op.outputs.values()
                        for v in vs if env.get(v.name) is not None]
                jax.block_until_ready(outs)
                on_op(i, op, time.perf_counter() - t0, env)
            if taps is not None and i in taps:
                tname, oname = taps[i]
                v = env[oname]
                env[oname] = lowering.like(
                    v, lowering.data_of(v) + env[tname])
            if grad_mode:
                for vs in op.outputs.values():
                    for v in vs:
                        if v.stop_gradient and v.name in env and env[v.name] is not None:
                            env[v.name] = jax.tree_util.tree_map(
                                jax.lax.stop_gradient, env[v.name])
            if in_region:
                self._keep_marked(op, env)

    def _run_pipeline_region(self, env, key, grad_mode=False):
        with jax.named_scope('pipeline_region_%d' % self.pipe['region'][0]):
            return self._run_pipeline_region_impl(env, key,
                                                  grad_mode=grad_mode)

    def _run_pipeline_region_impl(self, env, key, grad_mode=False):
        """Execute the PipelineTranspiler region as ONE GPipe call.

        Per-stage parameters are stacked [S, ...] on the fly (grad of
        stack = unstack, so jax.grad routes each stage's gradient back to
        its own parameter, and the program's optimizer ops update them
        unchanged); pipeline_apply shards the stack over the pp mesh axis
        and streams n_micro microbatches around the ppermute ring. NOTE:
        the stage RNG key is shared across stages/microbatches, so
        in-stage dropout masks are correlated — acceptable for GPipe
        (dropout is per-activation); tests compare with dropout off.
        """
        cfg = self.pipe
        from .. import parallel
        S, M = cfg['n_stages'], cfg['n_micro']
        x = env[cfg['input_var']]
        if x.shape[0] % M:
            raise ValueError(
                'pipeline n_micro=%d does not divide batch size %d'
                % (M, x.shape[0]))
        extras = tuple(env[n] for n in cfg['extra_names'])
        mb = x.shape[0] // M
        streamed = []
        for n in cfg['extra_stream_names']:
            e = env[n]
            if e.shape[0] != x.shape[0]:
                raise ValueError(
                    'batch-aligned pipeline extra %r has leading dim %d, '
                    'expected the batch size %d' % (n, e.shape[0],
                                                    x.shape[0]))
            streamed.append(e.reshape((M, mb) + e.shape[1:]))
        # Stack each stage's weights [S, ...] and PIN the stack's sharding:
        # dim 0 over the pp axis, trailing dims keeping the per-stage
        # weight's own (tp) spec. Without the constraint GSPMD has to
        # transition from the stacked per-stage shardings to the
        # shard_map's pp layout on its own and falls back to
        # replicate-then-repartition ("Involuntary full rematerialization",
        # MULTICHIP_r04 tail) — a full weight-stack all-gather every step.
        from jax.sharding import NamedSharding, PartitionSpec as _PS
        stacked, stacked_specs = {}, {}
        for j, n0 in enumerate(cfg['param_names'][0]):
            leaves = [env[cfg['param_names'][k][j]] for k in range(S)]
            if self.mesh is not None:
                # pin each element to an explicit replicated layout before
                # stacking: without this GSPMD back-propagates shardings
                # from inside the pipeline shard_map onto the stack and
                # falls back to replicate-then-repartition per step
                # ("Involuntary full rematerialization", MULTICHIP_r04)
                rep = NamedSharding(self.mesh, _PS())
                leaves = [jax.lax.with_sharding_constraint(x, rep)
                          for x in leaves]
            stacked[n0] = jnp.stack(leaves)
            base_sh = self.persist_shardings.get(n0)
            stacked_specs[n0] = (tuple(base_sh.spec)
                                 if base_sh is not None else ())
        mbs = x.reshape((M, mb) + x.shape[1:])
        lo0, hi0 = cfg['stage0']
        stage_ops = self.ops[lo0:hi0]
        extra_names = cfg['extra_stream_names'] + cfg['extra_names']
        input_name, boundary0 = cfg['input_var'], cfg['boundary0']

        # the region body is manual over dp/pp (and sp when composed);
        # mesh-aware lowerings (sp attention) must use per-shard
        # collective bodies on these axes instead of opening a shard_map
        manual = (parallel.pipeline_manual_axes(self.mesh, cfg['axis'])
                  if self.mesh is not None else frozenset())

        def stage_fn(p, xx, *ex):
            sub = dict(zip(extra_names, ex))
            sub.update(p)
            sub[input_name] = xx
            for t, op in enumerate(stage_ops):
                lowering.run_op(op, sub, Ctx(key, lo0 + t, amp=self.amp,
                                             platform=self.platform,
                                             mesh=self.mesh,
                                             manual_axes=manual))
                if grad_mode:
                    # same stop_gradient contract as the sequential path
                    # (_run_ops): frozen vars stay frozen when pipelined
                    for vs in op.outputs.values():
                        for v in vs:
                            if (v.stop_gradient and v.name in sub
                                    and sub[v.name] is not None):
                                sub[v.name] = jax.tree_util.tree_map(
                                    jax.lax.stop_gradient, sub[v.name])
            return sub[boundary0]

        out = parallel.pipeline_apply(stage_fn, stacked, mbs, self.mesh,
                                      axis=cfg['axis'], extras=extras,
                                      extras_streamed=tuple(streamed),
                                      n_virtual=cfg.get('n_virtual', 1),
                                      param_specs=stacked_specs)
        res = out.reshape((-1,) + out.shape[2:])
        if self.mesh is not None:
            # Pin the region boundary to the batch-sharded layout the
            # surrounding (dp/sp-partitioned) ops use. The constraint
            # transposes to ITSELF, so the backward cotangent entering
            # the region carries the same explicit sharding — without it
            # GSPMD has to invent the transition from the downstream
            # layout to the region's microbatched one and falls back to
            # replicate-then-repartition ("Involuntary full
            # rematerialization", MULTICHIP_r05 tail).
            from jax.sharding import NamedSharding as _NS, \
                PartitionSpec as _PS
            entries = [None] * res.ndim
            if 'dp' in self.mesh.shape:
                entries[0] = 'dp'
            if 'sp' in self.mesh.shape and res.ndim >= 2:
                entries[1] = 'sp'
            if any(entries):
                res = jax.lax.with_sharding_constraint(
                    res, _NS(self.mesh, _PS(*entries)))
        env[cfg['output_var']] = res

    def debug_step(self, persist, feed, key, check_nan_inf=False, on_op=None):
        """Eager op-by-op execution: per-op NaN/Inf checks (reference C++
        check_nan_inf, operators/isfinite_op) and per-op wall times for the
        profiler table. Slower than the jitted step by design."""
        hooks = []
        if on_op is not None:
            hooks.append(on_op)
        if check_nan_inf:
            hooks.append(_nan_inf_hook)

        def hook(i, op, dt, env):
            for h in hooks:
                h(i, op, dt, env)

        ops = self.ops
        env = dict(persist)
        env.update(feed)
        health = None
        if self.ad_idx is None:
            self._run_ops(env, 0, len(ops), key, on_op=hook)
        else:
            ad = ops[self.ad_idx]
            pnames, gnames, trainable, base, taps = \
                self._grad_setup(env, ad)
            # eager, hooked forward pass (this is the per-op signal)
            self._run_ops(env, 0, self.ad_idx, key, on_op=hook)
            grads, _ = jax.grad(self._make_fwd(base, ad, key, taps=taps),
                                has_aux=True)(trainable)
            self._apply_grads(grads, env, ad, pnames, gnames,
                              check_nan_inf=check_nan_inf)
            if self.guard:
                # the guard stays armed on the eager path too (profiler
                # hook / debugger active): same health vector, same
                # skip-with-rollback — the jnp ops just run un-jitted
                health = self._step_health(env, ad, pnames, gnames)
            self._run_ops(env, self.ad_idx + 1, len(ops), key, on_op=hook)
        fetches = [env[n] for n in self.fetch_names]
        new_persist = {n: env[n] for n in self.persist_out if n in env}
        if health is not None:
            self._select_healthy(health['healthy'], new_persist, persist)
        return StepResult(fetches, new_persist, health,
                          self._device_counters(env))

    def __call__(self, donated, readonly, feed, key):
        """THE call seam of the jitted step: every driver's dispatch (run,
        a StepHandle, a first call) goes through here, over the persist
        dict as `plan.split` divides it."""
        return StepResult(*self._jitted(donated, readonly, feed, key))

    # -- first-class artifact surface ----------------------------------

    def signatures(self):
        """Every jitted entry point this artifact has built: the
        unbundled step plus one ('bundle', K) scan per bundle length.
        Each compiles (or persistent/AOT-deserializes) exactly once —
        the signature set an AOT export warms."""
        return [('step',)] + [('bundle', K) for K in sorted(self._bundles)]

    def pin_state(self, persist, device):
        """Commit the step's DONATED persistables to their device
        placement BEFORE the first jitted call, so the entry's argument
        signature is stable for the artifact's whole life: donated
        outputs come back COMMITTED device arrays, and a first call made
        with uncommitted arrays (fresh startup outputs, host ndarrays
        io.load wrote into the scope) would specialize the executable
        once more on call two — the PR 4 "warm twice" run_bundle wart.
        One donation layout, one compile per signature; steady state is
        a per-name attribute check.

        Only the donation set is touched: read-only persistables are
        never re-emitted by the step, so their committedness can never
        flip between calls — and re-placing them would needlessly break
        buffer identity for frozen weights callers still hold.

        Mutates `persist` in place; returns the names re-placed (the
        caller syncs those back into the scope so the pinned arrays ARE
        the scope arrays). `device=None` (mesh-placed programs, executors
        without a place) is a no-op — those paths own their placement."""
        if device is None:
            return []
        from jax.sharding import NamedSharding
        pinned = []
        for n in self.donate_names:
            v = persist.get(n)
            if v is None or isinstance(v, SeqValue):
                continue
            if isinstance(v, jax.Array):
                if (getattr(v, 'committed', True)
                        or isinstance(v.sharding, NamedSharding)
                        or len(v.sharding.device_set) > 1):
                    continue
                persist[n] = jax.device_put(v, device)
            else:
                persist[n] = jax.device_put(np.asarray(v), device)
            pinned.append(n)
        return pinned

    def touched_rows(self, feed):
        """HOST-side touched-row derivation for one fed batch: which
        rows of each sparse-plan table will the step's sparse update
        actually write? The answer is already in the feed — every
        eligible table's lookup ids are feed/persist vars
        (_sparse_embedding_plan resolves them before the forward runs),
        so the streaming delta publisher (paddle_tpu.streaming) reads
        the touched set without fetching anything from the device or
        changing the compiled step.

        Returns {table name: sorted unique int64 row ids} for tables on
        the sparse path whose ids are present in `feed` (padding_idx
        rows excluded — the lookup rule zeroes their gradient). Tables
        training DENSE (no sparse plan) are absent: their update writes
        every row, and a row-delta push would under-report; the
        publisher warns on that case."""
        out = {}
        for w, plan in self.sparse_plan.items():
            parts = []
            ok = True
            for _op_idx, ids_name, pad in plan['lookups']:
                v = feed.get(ids_name)
                if v is None:
                    ok = False
                    break
                ids = np.asarray(lowering.data_of(v)).reshape(-1)
                if pad is not None and pad >= 0:
                    ids = ids[ids != pad]
                parts.append(ids.astype(np.int64))
            if ok and parts:
                out[w] = np.unique(np.concatenate(parts))
        return out

    def state_dict(self, scope):
        """Placement-true {name: jax.Array} view of THIS step's
        persistable state, read live from `scope` — the state_dict seam
        owned by the artifact rather than bolted onto the executor: a
        mesh-placed array keeps its NamedSharding (save_sharded then
        writes only addressable shards). LoD (SeqValue) state is skipped,
        matching Executor.state_dict."""
        out = {}
        for n in self.persist_in:
            v = scope._chain_get(n)
            if v is None or isinstance(v, SeqValue):
                continue
            out[n] = v if isinstance(v, jax.Array) else jnp.asarray(v)
        return out


# ---------------------------------------------------------------------------
# AOT warm signatures (docs/perf.md#aot): serialize the compiled-signature
# set of a WARMED executor so a cold replica / elastic restart reaches its
# first step (first token) with ZERO online compiles. The executable bytes
# are the persistent XLA compilation cache's (utils/compile_cache.py) —
# this packages them WITH a typed manifest of every warm signature (feed
# names/shapes/dtypes, fetches, donation plan, program fingerprint), so the
# blob travels across machines and `tools/program_lint.py --aot` can detect
# a stale blob statically instead of a silent online recompile.
# ---------------------------------------------------------------------------

AOT_MANIFEST = 'aot_manifest.json'
AOT_CACHE_DIR = 'xla_cache'
AOT_FORMAT = 'paddle_tpu-aot-v1'


def program_fingerprint(program):
    """Process-independent structural identity of a Program: sha256 over
    its canonical dict serialization (the save_inference_model shape, so
    a saved artifact round-trips to the same fingerprint)."""
    import hashlib
    import json
    doc = json.dumps(program._to_dict(), sort_keys=True, default=str)
    return hashlib.sha256(doc.encode('utf-8')).hexdigest()[:16]


def _mesh_axes(art):
    if art.mesh is None:
        return None
    return sorted([str(a), int(s)] for a, s in art.mesh.shape.items())


def stable_signature(art):
    """Process-independent identity of one compiled step signature —
    unlike the Executor's in-process cache key (which embeds the
    program's per-process _uid), this survives restarts and travels with
    an AOT export: program fingerprint + feed signature + fetch set +
    persistable set + the mode flags that change the lowering. Cached on
    the artifact."""
    if art._stable_sig is not None:
        return art._stable_sig
    import hashlib
    import json
    payload = json.dumps({
        'program': program_fingerprint(art._source_program),
        'feed_sig': [[str(x) for x in sig] for sig in art._feed_sig],
        'fetches': list(art.fetch_names),
        'persist_in': list(art.persist_in),
        'donates': sorted(art.donate_names),
        'amp': bool(art.amp),
        'guard': bool(art.guard),
        'remat': bool(art.use_remat),
        'mesh': _mesh_axes(art),
    }, sort_keys=True)
    art._stable_sig = hashlib.sha256(
        payload.encode('utf-8')).hexdigest()[:16]
    return art._stable_sig


def _feed_entries(art):
    """Manifest feed records from the artifact's placed-feed signature:
    [{'name', 'shape', 'dtype', 'seq'}...] (seq inputs record their dense
    data plane's shape)."""
    out = []
    for sig in art._feed_sig:
        if len(sig) == 4 and sig[1] == 'seq':
            name, _, shape, dtype = sig
            seq = True
        else:
            name, shape, dtype = sig
            seq = False
        out.append({'name': name, 'shape': [int(d) for d in shape],
                    'dtype': str(dtype), 'seq': seq})
    return out


def aot_manifest(executor):
    """The typed signature-set manifest of a warmed executor's compiled
    artifacts (one entry per cache entry): what write_aot serializes and
    program_lint --aot checks against."""
    sigs = []
    for art in executor._cache.values():
        sigs.append({
            'sig': stable_signature(art),
            'key': art._key_id,
            'program': program_fingerprint(art._source_program),
            'feeds': _feed_entries(art),
            'fetches': list(art.fetch_names),
            'donates': sorted(art.donate_names),
            'readonly': sorted(art.readonly_names),
            'bundles': sorted(art._bundles),
            # which entry points were actually first-called here — a
            # replica warmed only through run_bundle never serialized
            # the plain step, and the importer's stale detection must
            # know that (Executor._aot_warmed)
            'warmed_step': art._obs_compiled,
            'guard': bool(art.guard),
            'amp': bool(art.amp),
            'mesh': _mesh_axes(art),
        })
    try:
        platform = jax.devices()[0].platform
    except Exception:
        platform = None
    return {'format': AOT_FORMAT, 'jax': jax.__version__,
            'platform': platform, 'signatures': sigs}


def write_aot(dirname, executor):
    """Export the executor's warm signature set: the manifest plus the
    persistent-compile-cache entries (the serialized XLA executables)
    under `dirname/xla_cache/`. Requires the executor to have been
    constructed with the compilation cache wired — the on-disk
    executable IS the AOT payload; without it there is nothing
    transportable to export. Returns (manifest_path, manifest)."""
    import json
    import shutil
    src = executor._compile_cache_dir
    if not src or not os.path.isdir(src):
        raise RuntimeError(
            'export_warm_signatures needs the persistent compilation '
            'cache: construct the Executor with JAX_COMPILATION_CACHE_DIR='
            '<dir> set (or after utils.compile_cache.enable()), warm the '
            'signature set, then export — the cached XLA executables are '
            'the AOT payload (docs/perf.md#aot)')
    man = aot_manifest(executor)
    if not man['signatures']:
        raise RuntimeError(
            'export_warm_signatures: this executor has compiled nothing '
            'yet — warm the signature set (run / run_bundle / serving '
            'warmup) before exporting')
    os.makedirs(dirname, exist_ok=True)
    cache_dst = os.path.join(dirname, AOT_CACHE_DIR)
    os.makedirs(cache_dst, exist_ok=True)
    # ship only the entries THIS executor's first calls wrote when that
    # tracked set is authoritative (every first call cold-compiled here:
    # no persistent hits served entries the tracker never saw). A warm
    # process exporting a shared long-lived cache dir falls back to the
    # whole dir — over-shipping beats a blob whose signatures miss.
    tracked = getattr(executor, '_warm_entries', None) or set()
    use_tracked = bool(tracked) and executor._persistent_hits == 0
    scope = 'tracked' if use_tracked else 'full_dir'
    copied = []
    with os.scandir(src) as it:
        for e in it:
            if not e.is_file() or e.name.endswith('-atime'):
                continue
            if use_tracked and e.name not in tracked:
                continue
            shutil.copy2(e.path, os.path.join(cache_dst, e.name))
            copied.append(e.name)
    man['cache_entries'] = sorted(copied)
    man['cache_scope'] = scope
    path = os.path.join(dirname, AOT_MANIFEST)
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(man, f, indent=1)
    os.replace(tmp, path)
    return path, man


def read_aot(dirname):
    """Load (and format-check) an AOT manifest from an export dir (or a
    manifest file path). Raises RuntimeError on a missing/alien blob."""
    import json
    path = dirname
    if os.path.isdir(path):
        path = os.path.join(path, AOT_MANIFEST)
    if not os.path.exists(path):
        raise RuntimeError('no AOT manifest at %r (expected %s)'
                           % (dirname, AOT_MANIFEST))
    with open(path) as f:
        man = json.load(f)
    if man.get('format') != AOT_FORMAT:
        raise RuntimeError('AOT manifest %r has format %r, expected %r'
                           % (path, man.get('format'), AOT_FORMAT))
    return man


def aot_check(src, program):
    """Static staleness check of an exported AOT blob against a program
    artifact (tools/program_lint.py --aot): does any exported signature
    actually match THIS program, do the recorded feed shapes/dtypes still
    exist on it, and does the recorded donation plan agree with the
    program's memory plan? Returns a list of human-readable problems —
    empty means a replica loading this blob warms without online
    compiles; any problem means a stale blob whose first calls would
    silently recompile (the exact failure this check types)."""
    manifest = src if isinstance(src, dict) else read_aot(src)
    problems = []
    fp = program_fingerprint(program)
    sigs = manifest.get('signatures', [])
    if not sigs:
        return ['AOT manifest records no signatures — nothing is warmed']
    if jax.__version__ != manifest.get('jax'):
        problems.append(
            'AOT blob was exported under jax %s but this process runs '
            '%s — serialized executables will not deserialize; every '
            'first call compiles online'
            % (manifest.get('jax'), jax.__version__))
    matching = [s for s in sigs if s.get('program') == fp]
    if not matching:
        problems.append(
            'no exported signature matches this program (fingerprint %s; '
            'exported: %s) — the blob was built from a different/older '
            'program and every first call would compile online'
            % (fp, sorted({str(s.get('program')) for s in sigs})))
    blk = program.global_block()
    from .passes import memory_plan
    plan = memory_plan(program)
    persistable = {v.name for v in program.list_vars() if v.persistable}
    for s in matching or sigs:
        tag = 'signature %s' % s.get('sig', '?')
        for f in s.get('feeds', []):
            var = blk.vars.get(f.get('name'))
            if var is None:
                problems.append(
                    '%s: feed %r is not a variable of this program'
                    % (tag, f.get('name')))
                continue
            want = str(var.dtype)
            got = str(f.get('dtype'))
            # int64-declared vars run int32 on device (x64 disabled), and
            # bf16 feeds arrive as the var's compute dtype — compare the
            # placed dtype only when the var's declared one maps to it
            if want == 'int64':
                want = 'int32'
            if got != want and want != 'bfloat16':
                problems.append(
                    '%s: feed %r recorded dtype %s but the program '
                    'declares %s' % (tag, f['name'], got, want))
            vshape = tuple(int(d) for d in var.shape)
            rec = tuple(int(d) for d in f.get('shape', ()))
            # the leading (batch) dim is -1/any in program metadata; the
            # trailing dims must agree where the program declares them
            if len(rec) == len(vshape):
                for rd, vd in zip(rec[1:], vshape[1:]):
                    if vd > 0 and rd != vd:
                        problems.append(
                            '%s: feed %r recorded shape %r but the '
                            'program declares %r'
                            % (tag, f['name'], list(rec), list(vshape)))
                        break
        for name in s.get('fetches', []):
            if name not in blk.vars and not any(
                    name in b.vars for b in program.blocks):
                problems.append(
                    '%s: fetch %r is not produced by this program'
                    % (tag, name))
        stale_don = sorted(set(s.get('donates', [])) - plan.write_set)
        if stale_don:
            problems.append(
                '%s: recorded donation of %r but this program\'s memory '
                'plan does not write them — the donation vector changed '
                'since export' % (tag, stale_don))
        missing_don = sorted(
            (plan.write_set & persistable) - set(s.get('donates', []))
            - set(s.get('readonly', [])))
        if missing_don:
            problems.append(
                '%s: the program now writes persistable(s) %r that the '
                'exported plan never donated — the compiled layout is '
                'stale' % (tag, missing_don))
    return problems


def _nan_inf_hook(i, op, dt, env):
    for slot, vs in op.outputs.items():
        for v in vs:
            val = env.get(v.name)
            if val is None:
                continue
            for leaf in jax.tree_util.tree_leaves(val):
                if (hasattr(leaf, 'dtype')
                        and jnp.issubdtype(leaf.dtype, jnp.floating)
                        and not bool(jnp.isfinite(leaf).all())):
                    raise FloatingPointError(
                        "NaN/Inf in output %r of op #%d %r" %
                        (v.name, i, op.type))
