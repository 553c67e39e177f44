"""fluid.passes — ahead-of-lowering Program->Program optimization.

PR 5's `fluid.analysis` proved facts about the Program IR (def-use,
shape/dtype propagation, donation safety); this package aims the same
facts at SPEED. Every execution path — train `run`, `run_bundle`'s scan,
the serving engine, `export_compiled` — shares one lowering, so a
pipeline of Program->Program transforms applied just before that lowering
makes all of them faster at once, the way classic graph-compiler stacks
(and the reference's own memory_optimization_transpiler /
inference_transpiler) pre-digest the graph before codegen.

Passes (docs/passes.md has the catalog and the A/B guarantees):

  fold  — constant folding: ops whose inputs are all compile-time
          constants (`fill_constant`/`assign_value` chains) are evaluated
          through THEIR OWN lowering rules (one definition of op
          semantics) and replaced by `assign_value`.
  cse   — common subexpression elimination: ops hashed by
          (type, attrs, canonicalized input values) within the top-level
          block, def-use-safe, pure ops only.
  dce   — dead-op elimination: `analysis.live_mask` (the DeadOp finding's
          own liveness) promoted to a pruning transform that respects
          fetch and persistable liveness.

Equivalence contract: every pass is BIT-EXACT against the unoptimized
lowering, AMP or not (per-op RNG streams survive op removal via the
`op_seq` stamp the executor consults; the quant rewrite keeps its own
documented tolerance). bf16 is not a pass: an AMP program keeps `_amp`
and its optimized clone lowers through `ctx.amp` (`lowering.amp_cast`)
like the original, so the rules that call `amp_cast` are never folded.
`tests/test_passes.py` drills the claim over the program-fuzz corpus and
the book models.

Wiring: `PADDLE_TPU_OPT={off,default,aggressive}` gates the Executor
(once per compiled-step cache key, like PADDLE_TPU_VERIFY);
`Program.optimize()` is the manual surface; `tools/program_lint.py
--optimize` reports what the passes would do to a saved artifact.
Telemetry: every pass runs under a `passes.<name>` span and bumps
`passes.<name>.ops_removed` / `.ops_inserted` counters, and the whole
pipeline records `passes.optimize` with the total op-count delta, so
`obs_report` can attribute wins to passes.
"""
import functools
import inspect
import os

from ... import obs
from .. import lowering
from ..analysis.dataflow import sub_block_indices

from .memplan import MemoryPlan, memory_plan  # noqa: F401  (re-export)

__all__ = ['optimize', 'opt_mode', 'is_pure', 'is_foldable',
           'MemoryPlan', 'memory_plan', 'ENV_OPT', 'LEVELS', 'OP_SEQ_ATTR']

# PADDLE_TPU_OPT wires optimize() into Executor._prepare, once per
# compiled-step cache key:
#   off        (default) — lower the program exactly as built;
#   default    — constant folding, CSE, DCE (bit-exact transforms only);
#   aggressive — same passes with a larger constant-folding budget.
ENV_OPT = 'PADDLE_TPU_OPT'
LEVELS = ('off', 'default', 'aggressive')

# Original top-level op index, stamped on every op of the optimized clone
# BEFORE any structural change. The executor derives each op's RNG stream
# from this attr (falling back to the list position), so removing or
# merging ops never shifts another op's dropout mask — the keystone of
# the bit-exactness guarantee.
OP_SEQ_ATTR = 'op_seq'

_C_PROGRAMS = obs.counter('passes.programs_optimized')
_C_REMOVED = obs.counter('passes.ops_removed')


def opt_mode():
    v = os.environ.get(ENV_OPT, 'off').strip().lower()
    if v in ('', '0', 'off', 'false', 'no', 'none'):
        return 'off'
    if v in ('default', '1', 'on', 'true'):
        return 'default'
    if v == 'aggressive':
        return 'aggressive'
    raise ValueError(
        '%s must be one of off|default|aggressive, got %r' % (ENV_OPT, v))


# -- purity ------------------------------------------------------------------
# A pass may only touch ops it can PROVE are pure functions of their
# inputs. Rather than a hand-curated list that silently rots as ops are
# added, the proof is mechanical: the op must have a plain lowering rule
# (no block rule, no sub-blocks) whose SOURCE never touches the PRNG
# stream — a rule that mentions ctx.rng is impure on every code path,
# conservatively. Folding is stricter still: the rule must not branch on
# the compilation context (platform/mesh), because folding evaluates it
# OUTSIDE the compiled module — nor call `amp_cast`, because folding
# evaluates it with amp off where the step may run it in bf16.

_EFFECTFUL = frozenset(['print', 'autodiff', 'py_func'])


@functools.lru_cache(maxsize=None)
def _rule_source(op_type):
    try:
        return inspect.getsource(lowering.get_rule(op_type))
    except Exception:
        return None


@functools.lru_cache(maxsize=None)
def _rule_uses_rng(op_type):
    src = _rule_source(op_type)
    return src is None or 'rng(' in src


@functools.lru_cache(maxsize=None)
def _rule_uses_context(op_type):
    src = _rule_source(op_type)
    return src is None or any(m in src for m in (
        'ctx.platform', 'ctx.mesh', 'manual_axes', 'ctx.is_test',
        'amp_cast'))


def is_pure(op):
    """True when the op is a deterministic pure function of its inputs:
    safe to deduplicate (CSE) and to drop when dead (DCE still keeps
    effectful ops explicitly)."""
    if op.type in _EFFECTFUL or op.type in lowering._BLOCK_RULES:
        return False
    if not lowering.has_rule(op.type):
        return False
    if sub_block_indices(op):
        return False
    return not _rule_uses_rng(op.type)


def is_foldable(op):
    """Pure AND context-free: the rule can be evaluated eagerly at
    optimization time with the same result the compiled module would
    produce (no platform/mesh/is_test/amp branching)."""
    return is_pure(op) and not _rule_uses_context(op.type)


def written_names(program, op, cache=None):
    """Every name `op` writes at its position in a top-level walk: the
    declared outputs PLUS every name its sub-blocks write — while/ifelse
    bodies legally update outer names (persistables included) without
    listing them as the parent op's outputs. Any pass keeping a
    name->version map over the walk must bump with THIS set, or two
    reads straddling an undeclared sub-block write would look like the
    same value. `cache` memoizes the sub-block walk (dataflow's
    _block_writes memo, block idx -> names)."""
    from ..analysis.dataflow import _block_writes
    names = set(op.output_arg_names)
    for bi in sub_block_indices(op, program):
        names |= _block_writes(program, program.block(bi), cache=cache)
    return names


def write_counts(program):
    """name -> number of writes program-wide (all blocks), counting the
    names `autodiff` defines via attrs (grad_names) as writes. The
    written-exactly-once test both fold and cse build their SSA-ness
    guarantees on — one definition, so the passes can never disagree."""
    counts = {}
    for blk in program.blocks:
        for op in blk.ops:
            for n in op.output_arg_names:
                counts[n] = counts.get(n, 0) + 1
            if op.type == 'autodiff':
                for n in op.attrs.get('grad_names', ()):
                    counts[n] = counts.get(n, 0) + 1
    return counts


# -- report ------------------------------------------------------------------

# the one number per pass the passes.optimize span (and obs_report's
# attribution line) carries: actual WORK DONE, never a grab-bag sum that
# would count quant's skipped ops as rewrites
_PRIMARY_STAT = {'dce': 'ops_removed', 'fold': 'ops_folded',
                 'cse': 'ops_merged', 'quant': 'ops_rewritten'}

class PassReport(object):
    """What one optimize() run did: per-pass numbers + the total top-level
    op-count delta. Rendered by program_lint --optimize; attached to the
    optimized program as `_opt_report`."""

    def __init__(self, level):
        self.level = level
        self.passes = {}       # name -> {stat: int}
        self.ops_before = 0
        self.ops_after = 0
        self.skipped = None    # reason string when nothing ran

    def note(self, name, **stats):
        d = self.passes.setdefault(name, {})
        for k, v in stats.items():
            d[k] = d.get(k, 0) + int(v)

    def to_dict(self):
        return {'level': self.level, 'ops_before': self.ops_before,
                'ops_after': self.ops_after, 'skipped': self.skipped,
                'passes': {k: dict(v) for k, v in self.passes.items()}}

    def __repr__(self):
        if self.skipped:
            return 'PassReport(skipped=%r)' % self.skipped
        per = ', '.join('%s=%s' % (k, v)
                        for k, v in sorted(self.passes.items()))
        return 'PassReport(level=%s, ops %d -> %d%s)' % (
            self.level, self.ops_before, self.ops_after,
            '; ' + per if per else '')


# -- the pipeline ------------------------------------------------------------

def _clone_for_opt(program):
    """A deep copy the passes may mutate freely, carrying every execution
    flag run() consults (clone() already moves _amp/_fetch_f32/_use_remat/
    _dist_config; the anomaly guard travels here) and stamped with each
    op's original index for RNG-stream stability."""
    p = program.clone(for_test=False)
    for flag in ('_anomaly_guard', '_anomaly_guard_max_skips'):
        if hasattr(program, flag):
            setattr(p, flag, getattr(program, flag))
    for i, op in enumerate(p.global_block().ops):
        op.attrs.setdefault(OP_SEQ_ATTR, i)
    return p


def optimize(program, feeds=None, fetches=None, level='default',
             where=None):
    """Run the pass pipeline over `program`; returns (optimized_program,
    PassReport). The input program is NEVER mutated — the result is an
    optimized clone (possibly the input itself when nothing can run).

    feeds/fetches: the execution context, exactly as analysis.analyze
    takes them. fetches gates DCE (one run's fetch subset IS dead-code
    evidence here, because the optimized clone is cached per fetch set —
    unlike the verifier, which must stay quiet about it).
    """
    if level not in LEVELS:
        raise ValueError('optimize level must be one of %s, got %r'
                         % ('|'.join(LEVELS), level))
    report = PassReport(level)
    if level == 'off':
        report.skipped = 'level=off'
        return program, report
    if getattr(program, '_pipeline_config', None) is not None:
        # the GPipe region depends on contiguous op ranges derived from
        # device_guard stamps; structural surgery would silently demote
        # the region to sequential execution — leave pipelined programs
        # to the lowering they were transpiled for
        report.skipped = 'pipeline-transpiled program'
        return program, report

    from . import cse, dce, fold, quant_pass

    with obs.span('passes.optimize', level=level,
                  where=where or 'api') as sp:
        p = _clone_for_opt(program)
        report.ops_before = len(p.global_block().ops)
        if quant_pass.is_quant(program):
            with obs.span('passes.quant'):
                quant_pass.run(p, report)
        with obs.span('passes.fold'):
            fold.run(p, report, level=level)
        if fetches is not None:
            # CSE and DCE both ELIMINATE output names; without knowing
            # the fetch set, any terminal output may be fetched later —
            # only the quant/fold rewrites (which preserve every name) are
            # safe to run blind
            with obs.span('passes.cse'):
                cse.run(p, report, feeds=feeds, fetches=fetches)
            with obs.span('passes.dce'):
                dce.run(p, report, fetches=fetches)
        # Self-check: a pass bug must surface HERE — where the executor's
        # fallback catches it and lowers the unoptimized program — not as
        # a raw KeyError at trace time. One cheap def-use walk over the
        # result (no shape propagation, no DeadOp noise).
        from ..analysis import dataflow as _dataflow
        from ..analysis.findings import SEV_ERROR
        errs = [f for f in _dataflow.run_pass(p, feeds=feeds,
                                              fetches=fetches,
                                              dead_ops=False)
                if f.severity == SEV_ERROR]
        if errs:
            raise RuntimeError(
                'optimizer produced an invalid program (%d error '
                'finding(s)):\n%s'
                % (len(errs), '\n'.join('  %s' % f for f in errs)))
        report.ops_after = len(p.global_block().ops)
        sp.fields.update(ops_before=report.ops_before,
                         ops_after=report.ops_after,
                         **{k: v.get(_PRIMARY_STAT.get(k),
                                     sum(v.values()))
                            for k, v in report.passes.items()})
    _C_PROGRAMS.inc()
    if report.ops_before > report.ops_after:
        _C_REMOVED.inc(report.ops_before - report.ops_after)
    p._opt_report = report
    p._bump_version()
    return p, report
