"""Pass 3 — donation / aliasing safety.

The executor donates every persistable input buffer to the jitted step
when (and only when) the program's TOP-LEVEL ops write at least one
persistable (StepArtifact): a mutating step updates params in place in
HBM and re-exposes every donated input as an output; a read-only
step donates nothing, because donation would invalidate the param buffers
under concurrent runs over a shared scope (the PR-3 serving bug).

This pass recomputes the persistable write-set INDEPENDENTLY — including
sub-block writes the executor's top-level scan cannot see — and verifies
it against the executor's donation decision:

  * DonationUnsafe (donates but write-set empty): a read-only step whose
    buffers would be invalidated — exactly the PR-3 class;
  * DonationUnsafe (writes but no donation/write-back): persistable
    updates the executor would silently drop;
  * DonationUnsafe (sub-block-only writes): a persistable written ONLY
    inside a sub-block — the executor's decision scan reads top-level
    outputs, so the step is treated read-only and the update is lost.
"""
from .dataflow import sub_block_indices
from .findings import Finding, SEV_ERROR, DONATION_UNSAFE

__all__ = ['run_pass', 'persistable_write_set', 'executor_write_set',
           'executor_donates']


def executor_write_set(program):
    """Persistable names the TOP-LEVEL block writes — byte-for-byte the
    scan StepArtifact bases its donation decision on (defined here so
    the executor and the analyzer can never drift apart)."""
    persistable = {v.name for v in program.list_vars() if v.persistable}
    produced = set()
    for op in program.global_block().ops:
        for vs in op.outputs.values():
            for v in vs:
                if v.name in persistable:
                    produced.add(v.name)
    return produced


def executor_donates(program):
    """The executor's donation decision for this program (True = every
    persistable input buffer is donated to the jitted step)."""
    return bool(executor_write_set(program))


def _reachable_sub_blocks(program):
    """Sub-block indices actually executed by some (transitively
    reachable) block op. Orphaned blocks — prune()/clone(for_test) drop
    ops but keep every Block, so a pruned inference program can carry a
    dead While body — must not contribute writes: they never run."""
    reachable = set()
    frontier = [program.global_block().idx]
    seen = {program.global_block().idx}
    while frontier:
        bi = frontier.pop()
        for op in program.block(bi).ops:
            for nbi in sub_block_indices(op, program):
                if nbi not in seen:
                    seen.add(nbi)
                    reachable.add(nbi)
                    frontier.append(nbi)
    return reachable


def persistable_write_set(program, recursive=True):
    """Persistable names written anywhere in the REACHABLE program; with
    recursive=True this includes executed sub-block bodies (which the
    executor's top-level scan does NOT see — that gap is finding
    material), but never orphaned blocks left behind by prune(). The
    top-level scan is executor_write_set itself — one definition, no
    drift."""
    writes = set(executor_write_set(program))
    if recursive:
        for bi in sorted(_reachable_sub_blocks(program)):
            for op in program.block(bi).ops:
                for vs in op.outputs.values():
                    for v in vs:
                        if getattr(v, 'persistable', False):
                            writes.add(v.name)
    return writes


def _sub_block_only_writers(program):
    """(op, name) pairs for persistable writes that happen ONLY inside a
    sub-block, attributed to the sub-block op that performs them."""
    top = executor_write_set(program)
    hits = []
    for bi in sorted(_reachable_sub_blocks(program)):
        for op in program.block(bi).ops:
            for vs in op.outputs.values():
                for v in vs:
                    if getattr(v, 'persistable', False) and v.name not in top:
                        hits.append((op, v.name))
    return hits


def run_pass(program, donates=None):
    """donates: the executor's actual donation decision for the step about
    to run (compiled.mutates_persist). None = standalone analysis; the
    decision is re-derived from the executor's own rule, so only the
    sub-block gap can fire."""
    findings = []
    top_writes = executor_write_set(program)
    if donates is None:
        donates = bool(top_writes)

    if donates and not top_writes:
        findings.append(Finding(
            DONATION_UNSAFE, SEV_ERROR,
            'the step donates its persistable input buffers but no op '
            'writes any persistable — donation would invalidate parameter '
            'buffers under concurrent runs over a shared scope (read-only '
            'inference steps must not donate)', var_names=()))
    if not donates and top_writes:
        findings.append(Finding(
            DONATION_UNSAFE, SEV_ERROR,
            'ops write persistable(s) %r but the step neither donates nor '
            'writes back persistables — the updates would be dropped'
            % sorted(top_writes), var_names=sorted(top_writes)))

    for op, name in _sub_block_only_writers(program):
        findings.append(Finding.for_op(
            DONATION_UNSAFE, SEV_ERROR,
            'persistable %r is written only inside a sub-block; the '
            'executor\'s donation/write-back decision scans top-level '
            'outputs, so this update never reaches the scope — stage the '
            'write through a loop carry and assign it at the top level'
            % name, op, var_names=(name,)))
    return findings
