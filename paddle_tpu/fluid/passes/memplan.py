"""Per-program donation/memory plan.

The executor used to make its buffer-donation decision inline
(`StepArtifact.__init__` scanned `analysis.executor_write_set` and, when
mutating, donated EVERY persistable input and re-exposed every one as an
output). This module turns that ad-hoc decision into a first-class plan
object computed from the same analysis facts:

  * `write_set`   — persistable names the top-level block writes (the
                    shared `analysis.executor_write_set`, so the static
                    donation-safety pass cross-checks THIS plan, not a
                    copy of it);
  * `donates`     — whether the step donates at all (a read-only step
                    donates nothing: donation would invalidate parameter
                    buffers under concurrent runs — the PR-3 serving
                    class);
  * donated vs read-only SPLIT — only the buffers the step actually
    writes are donated and re-exposed as outputs. Read-only persistables
    (frozen weights, inference-time BN statistics, embedding tables on a
    scoring step) keep their scope buffers valid and leave the module's
    output list — XLA no longer carries a passthrough copy per step, and
    the donated set is exactly the set XLA can alias in place, which is
    what keeps the update fusible with the compute that produced it.

Consumers: `StepArtifact` (jit donation + write-back),
`Executor.run_bundle` (the scan-carry gap check names the plan's
uninitialized writes), and the serving engine's `warmup()` (records the
plan in its spans and rejects donating models behind a concurrent
engine).
"""

__all__ = ['MemoryPlan', 'memory_plan']


class MemoryPlan(object):
    """Donation/write-back plan for one Program (see module docstring)."""

    __slots__ = ('write_set', 'donates')

    def __init__(self, write_set):
        self.write_set = frozenset(write_set)
        self.donates = bool(self.write_set)

    def donate_names(self, persist_in):
        """Persistable inputs the step donates (and re-exposes as
        outputs): exactly the initialized ones it writes."""
        return sorted(n for n in persist_in if n in self.write_set)

    def readonly_names(self, persist_in):
        """Persistable inputs the step only reads: not donated, not
        re-exposed — their scope buffers stay valid across the call."""
        return sorted(n for n in persist_in if n not in self.write_set)

    def split(self, persist):
        """(donated, readonly) dicts from a full persist dict."""
        donated = {n: v for n, v in persist.items() if n in self.write_set}
        readonly = {n: v for n, v in persist.items()
                    if n not in self.write_set}
        return donated, readonly

    def uninitialized(self, persist_in):
        """Writes with no scope value yet — the run_bundle scan-carry gap
        (and the startup-program case: outputs created by the step)."""
        return sorted(self.write_set - set(persist_in))

    def persist_out(self):
        """Names the compiled step writes back to the scope."""
        return sorted(self.write_set)

    def donation_vector(self, persist_in):
        """pjit-style donation vector over the compiled step's
        (donated, readonly, feed, rng_key) argument list: exactly the
        written-persistables argument is donated, and only when the step
        writes at all (the pjit `donation_vector`/`rebase_donate_argnums`
        idiom, collapsed onto the executor's fixed 4-arg signature)."""
        return (bool(self.donate_names(persist_in)), False, False, False)

    def donate_argnums(self, persist_in):
        """The donate_argnums tuple jax.jit takes, derived from
        donation_vector — one definition of the donation decision for
        both the plain and the GSPMD-annotated jit paths."""
        return tuple(i for i, d in enumerate(self.donation_vector(persist_in))
                     if d)

    def sharding_plan(self, persist_in, shardings, default=None):
        """(donated_in, readonly_in, persist_out) NamedSharding trees for
        the GSPMD executor path (docs/parallel.md): the donated argument's
        in-shardings and the persistable outputs' out-shardings are THE
        SAME objects, so the compiled step's state keeps one stable layout
        across steps/scan carries — XLA never inserts a resharding (or a
        full rematerialization) between a step's output and the next
        step's input.

        shardings: name -> NamedSharding (or None = unconstrained) for
        values present in the scope; `default` fills persistable outputs
        the step CREATES (startup programs). Entries missing from both
        stay None (jit leaves them unconstrained)."""
        donated = {n: shardings.get(n, default)
                   for n in self.donate_names(persist_in)}
        readonly = {n: shardings.get(n, default)
                    for n in self.readonly_names(persist_in)}
        out = {n: shardings.get(n, default) for n in self.persist_out()}
        return donated, readonly, out

    def to_dict(self):
        return {'donates': self.donates,
                'write_set': sorted(self.write_set)}

    def __repr__(self):
        return 'MemoryPlan(donates=%s, writes=%d)' % (
            self.donates, len(self.write_set))


def memory_plan(program):
    """The donation/memory plan for `program`, derived from the SAME
    write-set the static donation-safety pass verifies."""
    from ..analysis import executor_write_set
    return MemoryPlan(executor_write_set(program))
