"""The reduction from a profiler trace to numbers.

read_xplane() turns an `.xplane.pb` (jax.profiler.ProfileData, nothing but
jax) into plain lists; reduce() turns those into the quantities the
per-layer readers use. All times inside are nanoseconds on the trace's own
clock, which host threads and device queues share; results are seconds.

What is what in a TPU trace (looked at by hand, PR 22; jax 0.9.0, libtpu
0.0.34): one plane `/device:TPU:<n>` per chip. Its line `XLA Ops` holds one
event per executed HLO instruction, named by the instruction's whole text
(`%fusion.12 = bf16[...] fusion(...), kind=kLoop, calls=...`; a Mosaic
kernel's text carries `custom_call_target="tpu_custom_call"`), with no
op_name statistic, so the Fluid scope comes from joining the instruction's
name with the compiled module's metadata (harness/scopes.py). Its line
`Async XLA Ops` holds one span per asynchronous pair, from the `-start`
to the `-done` (copies, and collectives under a mesh), while `XLA Ops`
holds the two short ends. `XLA Modules` has one event per executed module
and `Steps` the profiler's own markers. Plane `/host:CPU` holds a line per
host thread; the line `python` carries the TraceAnnotation spans of the
program (`executor.*`, sent there when its observability directory is
set) and of the benchmark (`chipbench.*`).
"""
import collections
import glob
import os

from chipbench.harness import intervals as iv
from chipbench.harness import scopes

OP_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'
SPAN_PREFIXES = ('executor.', 'chipbench.')
WINDOW_SPAN = 'chipbench.traced_steps'
KERNEL_MARK = 'tpu_custom_call'


def instruction_name(event_name):
    """`%fusion.12 = bf16[8,4]{1,0} fusion(...)` -> `fusion.12`."""
    return event_name.split(' = ', 1)[0].strip().lstrip('%')


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return found[-1]


def read_xplane(path):
    """{'devices': {plane: [(start, end, instruction, is_kernel)]},
        'async': {plane: [(start, end, instruction)]},
        'spans': [(start, end, name)]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, asyncs, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:TPU:'):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [
                        (int(ev.start_ns),
                         int(ev.start_ns) + int(ev.duration_ns),
                         instruction_name(ev.name), KERNEL_MARK in ev.name)
                        for ev in line.events]
                elif line.name == ASYNC_LINE:
                    asyncs[plane.name] = [
                        (int(ev.start_ns),
                         int(ev.start_ns) + int(ev.duration_ns),
                         instruction_name(ev.name)) for ev in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        start = int(ev.start_ns)
                        spans.append((start, start + int(ev.duration_ns),
                                      ev.name))
    return {'devices': devices, 'async': asyncs, 'spans': spans}


def _covering_span(spans, lo, hi):
    """Name of the innermost recorded span that covers most of [lo, hi]."""
    best, best_key = None, None
    for s, e, name in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, hi) - max(s, lo)
        if overlap <= 0:
            continue
        key = (overlap >= 0.5 * (hi - lo), -(e - s))   # covers it; shortest
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best or 'no_span'


def reduce(raw, instr_scopes, steps):
    """Quantities of one traced window of `steps` steps, or None when no
    operation ran on a device in it."""
    devices = {k: v for k, v in raw['devices'].items() if v}
    if not devices:
        return None
    window = [s for s in raw['spans'] if s[2] == WINDOW_SPAN]
    if window:
        lo, hi = window[-1][0], window[-1][1]
    else:
        lo = min(e[0] for evs in devices.values() for e in evs)
        hi = max(e[1] for evs in devices.values() for e in evs)
    first = sorted(devices)[0]
    busy = {}
    for name, events in devices.items():
        busy[name] = iv.clip(iv.union((s, e) for s, e, _, _ in events),
                             lo, hi)
    events0 = [e for e in devices[first] if e[1] > lo and e[0] < hi]
    async0 = [e for e in raw.get('async', {}).get(first, [])
              if e[1] > lo and e[0] < hi]

    by_op = collections.Counter()         # Fluid op type -> ns
    by_scope = collections.Counter()      # '<op type>_<index>' -> ns
    # Fluid op type -> {callee (scopes.callee_of) -> Mosaic ns}
    kernel_by_callee = collections.defaultdict(collections.Counter)
    for name, self_ns in iv.self_times([(s, e, n) for s, e, n, _ in events0]):
        scope = scopes.scope_of(instr_scopes.get(name, ''))
        by_op[scope[0] if scope else 'unattributed'] += self_ns
        if scope:
            by_scope['%s_%d' % scope] += self_ns
    kernels = [(s, e, n) for s, e, n, k in events0 if k]
    kernel_ns = iv.total(iv.union((s, e) for s, e, _ in kernels))
    # the Mosaic events alone, by the Fluid op type whose scope they lie
    # in: self times among themselves, so the parts add up to kernel_ns
    for name, self_ns in iv.self_times(kernels):
        op_name = instr_scopes.get(name, '')
        scope = scopes.scope_of(op_name)
        kernel_by_callee[scope[0] if scope else 'unattributed'][
            scopes.callee_of(op_name)] += self_ns

    # a collective is busy from its start to its done (the async span);
    # what runs meanwhile on the op queue, other than its own two ends,
    # hides it
    coll = iv.union([(s, e) for s, e, n, _ in events0
                     if scopes.is_collective(n)]
                    + [(s, e) for s, e, n in async0
                       if scopes.is_collective(n)])
    compute = iv.union((s, e) for s, e, n, _ in events0
                       if not scopes.is_collective(n))
    exposed = iv.total(iv.clip(iv.subtract(coll, compute), lo, hi))

    idle = iv.gaps(busy[first], lo, hi)
    gap_ns = collections.Counter()
    for s, e in idle:
        gap_ns[_covering_span(raw['spans'], s, e)] += e - s
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:5]

    ns = 1e-9
    busy_s = {k: iv.total(v) * ns for k, v in busy.items()}
    return {
        'steps': steps,
        'window_s': (hi - lo) * ns,
        'busy_s': sum(busy_s.values()) / len(busy_s),
        'busy_s_by_device': busy_s,
        'busy0_s': busy_s[first],
        'fluid_op_s': {k: v * ns for k, v in by_op.items()},
        'fluid_scope_s': {k: v * ns for k, v in by_scope.items()},
        'kernel_s': kernel_ns * ns,
        'kernel_by_op_s': {op: sum(by.values()) * ns
                           for op, by in kernel_by_callee.items()},
        'kernel_by_callee_s': {op: {c: v * ns for c, v in by.items()}
                               for op, by in kernel_by_callee.items()},
        'collective_s': iv.total(iv.clip(coll, lo, hi)) * ns,
        'collective_exposed_s': exposed * ns,
        'idle_by_span_s': {k: v * ns for k, v in gap_ns.items()},
        'longest_gaps': [(_covering_span(raw['spans'], s, e), (e - s) * ns)
                         for s, e in longest],
    }


def breakdown(red, top=10):
    """The contract's `breakdown`: device time by Fluid op type, and idle
    time by what the host was doing."""
    ops = sorted(red['fluid_op_s'].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red['idle_by_span_s'].items(), key=lambda kv: -kv[1])[:top]
    return {'device_ops': [[k, v] for k, v in ops],
            'idle_gaps': [[k, v] for k, v in idle]}
