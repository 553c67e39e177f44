"""Pallas kernel registry + enablement knob (docs/perf.md#kernel-layer).

`ops/` stopped being "one flash-attention file" here: every hand-tiled
kernel registers under a NAME, ships alongside the pure-XLA lowering it
replaces (the fallback contract — with the kernel disabled the op's
lowering is byte-identical to the pre-kernel code path, because the
dispatch sites keep the original jnp code as the `else` branch). Each
kernel takes a required `interpret=`: the dispatch sites pass
`ctx.pallas_interpret` (Mosaic where the step's arrays live on TPUs,
the pallas interpreter elsewhere, so tier-1 drills the real kernel
bodies on `JAX_PLATFORMS=cpu`); nothing reads the process's default
backend.

Enablement is per-kernel, resolved at TRACE time (the decision is baked
into the compiled module; the Executor keys its step cache on
`signature()` so flipping the knob recompiles instead of serving the
other variant's cached step):

  * env `PADDLE_TPU_KERNELS` — `0`/`off`/unset: all kernels disabled
    (the default; nothing changes for existing programs); `1`/`on`/
    `all`: every registered kernel; a comma list enables by name, and
    a `-name` entry subtracts (`all,-paged_attention`).
  * `configure(spec)` — the programmatic surface (the predictor-config
    path: `inference.Predictor(..., kernels=...)` routes here). Takes
    the same grammar (str), an iterable of names, a bool, or None to
    fall back to the env. Overrides the env while set.

Dispatch sites call `enabled(name)` (via `lowering.use_kernel`) and bump
the per-kernel dispatch/fallback counters — `kernels.dispatch` /
`kernels.fallback` totals plus `kernels.<name>.dispatch` — at trace
time, so the counters count COMPILED modules carrying the kernel, not
steady-state steps (which re-trace nothing). Each dispatch also writes
a `kernels.dispatch` event (once per trace, for the obs_report
`-- kernels --` section).

Not under the knob, and not in the registry: the kernels that ARE an op's
lowering on the TPU, chosen by the op's rule from what it can see
(`ctx.platform == 'tpu'`, the module's `usable(...)` of the shapes and
the dtype), with the composition they are tested against as every other
platform's and shape's path:

  * `grouped_matmul` — the expert matmuls of `moe_mlp`'s dropless paths;
  * `gated_delta_intra` — stage `gdn_intra` of `gated_delta_rule`, forward
    and backward (else `linear_attention_ops._intra`);
  * `causal_conv1d` — the op `causal_conv1d`, one kernel forward and one
    backward that shift the K taps in VMEM (else
    `linear_attention_ops._conv`); `conv1d.way{way=kernel|composed}`
    counts the choice at trace time;
  * `ssd_scan` — the op `ssd_scan`, one kernel forward and one backward
    with the state in VMEM (else the composition's three stages);
    `ssd.way{way=kernel|composed}`;
  * `gated_norm` — the op `gated_rms_norm`, one kernel forward and one
    backward whose block holds a group's columns, a float32 x
    [.., heads, 128] read by head (else
    `linear_attention_ops._gated_norm`);
    `gated_rms_norm.way{way=kernel|composed}`;
  * `row_add` — the add of a held share's laid-out rows to their tokens
    (`moe_ops._add_up`: forward under `moe_combine`, and as the row
    gather's transpose in the backward pass), one kernel that walks the
    live rows only (else the sorted scatter-add);
    `moe.add{way=kernel|scatter}` counts the choice at trace time, once
    for each of a layout's two adds.
"""
import os

from ... import obs

__all__ = ['register_kernel', 'available', 'enabled', 'configure',
           'signature', 'note_dispatch',
           'ENV_KERNELS',
           'paged_attention', 'paged_attention_reference',
           'fused_sparse_adagrad', 'fused_sparse_adam',
           'sparse_adagrad_reference', 'sparse_adam_reference']

ENV_KERNELS = 'PADDLE_TPU_KERNELS'

_REGISTRY = {}        # name -> short description (the catalog)
_CONFIG = None        # configure() override; None = consult the env

_C_DISPATCH = obs.counter('kernels.dispatch')
_C_FALLBACK = obs.counter('kernels.fallback')


def register_kernel(name, description=''):
    """Add `name` to the kernel catalog (module import time). Returns the
    name so kernel modules can do `NAME = register_kernel('x', ...)`."""
    _REGISTRY[name] = description
    return name


def available():
    """Registered kernel names, sorted (the catalog docs/perf.md lists)."""
    return tuple(sorted(_REGISTRY))


def _parse(spec):
    """Normalize an enablement spec to a frozenset of enabled names.
    Accepts bool, None/'' (nothing), 'all'/'1'/'on', comma grammar with
    `-name` subtraction, or an iterable of names."""
    if spec is None:
        return frozenset()
    if isinstance(spec, bool):
        return frozenset(_REGISTRY) if spec else frozenset()
    if isinstance(spec, (list, tuple, set, frozenset)):
        return frozenset(str(s) for s in spec)
    s = str(spec).strip().lower()
    if s in ('', '0', 'off', 'false', 'no', 'none'):
        return frozenset()
    on, off = set(), set()
    for tok in s.split(','):
        tok = tok.strip()
        if not tok:
            continue
        if tok in ('1', 'on', 'true', 'all'):
            on |= set(_REGISTRY)
        elif tok.startswith('-'):
            off.add(tok[1:])
        else:
            on.add(tok)
    return frozenset(on - off)


def configure(spec):
    """Set (or with None, clear) the programmatic enablement override.
    Returns the previous override so callers can restore it."""
    global _CONFIG
    prev = _CONFIG
    _CONFIG = spec
    return prev


def _enabled_set():
    if _CONFIG is not None:
        return _parse(_CONFIG)
    return _parse(os.environ.get(ENV_KERNELS))


def enabled(name):
    """Is kernel `name` enabled right now? (Trace-time decision; the
    executor's cache key carries signature() so this never flips a
    cached module.)"""
    return name in _enabled_set()


def signature():
    """Hashable summary of the current enablement, for compile-cache
    keys: the enabled subset of the registered names."""
    return tuple(sorted(_enabled_set() & set(_REGISTRY)))


def note_dispatch(name, used):
    """Record one trace-time routing decision: `used`=True means the
    pallas kernel was emitted, False means the XLA fallback. Called by
    `lowering.use_kernel` — dispatch sites don't bump counters
    themselves."""
    if used:
        _C_DISPATCH.inc()
        obs.counter('kernels.%s.dispatch' % name).inc()
    else:
        _C_FALLBACK.inc()
        obs.counter('kernels.%s.fallback' % name).inc()
    obs.event('kernels.dispatch', kernel=name,
              mode='kernel' if used else 'fallback')


from .paged_attention import paged_attention, \
    paged_attention_reference  # noqa: E402
from .sparse_optim import fused_sparse_adagrad, fused_sparse_adam, \
    sparse_adagrad_reference, sparse_adam_reference  # noqa: E402
