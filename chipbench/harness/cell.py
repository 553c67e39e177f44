"""Runs one cell: set-up, the measured window, and in a traced run the
trace and its reduction. run.py owns the process (arguments, the demand
for a TPU, the last line); this module takes the place to run on, so the
CPU tests drive the same code at a toy width.
"""
import json
import os
import shutil
import statistics
import time

import numpy as np

from chipbench.harness import catalog, check, peaks, scopes, trace

WARMUP_STEPS = 3
TRACED_STEPS = 5
# the share of the window's steps left out at each end of the sorted step
# times before the mean that the rate is taken from
TRIM = 0.1
OBS_ENV = 'PADDLE_TPU_OBS_DIR'
_WATCHED = ('online_compiles', 'misses', 'persistent_hits')


def _registry_snapshot():
    """The program's in-memory registry, as the readers see it."""
    from paddle_tpu import obs

    def hist(name):
        h = obs.REGISTRY.histogram(name)
        snap = h.snapshot()
        return {'count': snap.get('count', 0), 'sum': snap.get('sum', 0.0)}

    return {
        'executor.step': hist('executor.step.seconds'),
        'executor.fetch': hist('executor.fetch.seconds'),
        'executor.feed.bytes': obs.counter('executor.feed.bytes').value,
    }


def _pass_span(since):
    """Fields of the last `passes.optimize` span the program's run log
    holds from monotonic time `since` on (ops_before, ops_after and each
    pass's own count), or None when no pass pipeline ran (its default,
    PADDLE_TPU_OPT=off: the Executor then lowers the Program it was
    handed). Where the pipeline runs, inside the first step, the Executor
    lowers the optimised clone, whose size only the program's own span
    tells. The log is written while the observability directory is set,
    which a traced run does."""
    from paddle_tpu import obs
    path = obs.run_log_path()
    if not path or not os.path.exists(path):
        return None
    found = None
    with open(path) as f:
        for text in f:
            if '"passes.optimize"' not in text:
                continue
            rec = json.loads(text)
            if rec.get('kind') == 'span' and rec['ts'] >= since:
                found = rec['fields']
    return found


def steady_step_s(step_s):
    """The step time the rate is taken from: the mean of the window's
    completed steps without the fastest and the slowest tenth.

    A run is one process on a host whose cores are shared. In 5 of 24
    runs of `tfm_s256` and `resnet50_b256` one step of some 65 took 40 to
    115 ms longer than its 145 or 162 ms (chip, PR 22): 0.4 to 1.1% of a
    10 s window in that run and nothing in the next, more than the runs
    spread without it. The trimmed mean leaves such a step out whichever
    run it falls into, and follows one for one whatever slows every step.
    So does the median, but ResNet-50's steps wait on a transfer and
    spread over 2%, and the median of sixty jumps between their clusters.
    What is left out stays on the summary line: `rate_total` (completed
    work over elapsed time) and `outside_steady_pct`."""
    x = np.sort(np.asarray(step_s, dtype=float))
    if not len(x):
        return None
    k = int(len(x) * TRIM)
    return float(x[k:len(x) - k].mean())


def _delta(after, before):
    out = {}
    for k, v in after.items():
        out[k] = ({f: v[f] - before[k][f] for f in v} if isinstance(v, dict)
                  else v - before[k])
    return out


def _memory_peak(devices):
    """(peak bytes on the fullest chip, on device 0, device 0's counters).

    The runtime keeps two disjoint pools (libtpu 0.0.34, looked at on the
    chip, PR 22): `bytes_in_use` are the live arrays, `bytes_reserved` is
    what the loaded programs hold for their scratch. A 16 x 1024
    Transformer step read 1.98 GB of peak `in_use` and 9.11 GB reserved,
    against the 9.19 GB of temporaries XLA states for its module. The
    peak is the sum of the two pools' peaks."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get('peak_bytes_in_use', 0) + s.get('peak_bytes_reserved', 0)
             for s in stats]
    return max(peaks), peaks[0], stats[0]


def run_cell(cell, seed, seconds, traced, place, t_start, work_dir,
             devices=None, spec=None):
    """Returns the result: the contract's last-line object under 'line' and
    the run's other facts under 'summary'."""
    import jax
    import paddle_tpu.fluid as fluid

    config, traffic, spec_cell = cell['config'], cell['traffic'], cell['cell']
    mesh = spec_cell.get('mesh')
    chips = spec_cell['chips']
    devices = devices if devices is not None else jax.devices()[:chips]
    if traced:
        # the program forwards its spans to the profiler only when its
        # observability directory is set (obs/__init__.py)
        os.environ[OBS_ENV] = os.path.join(work_dir, 'obs')

    marks = {'process_to_cell_s': time.perf_counter() - t_start}
    t0 = time.perf_counter()
    pool, units = cell['generator'].make_pool(traffic, config, seed)
    marks['traffic_s'] = time.perf_counter() - t0

    t0 = time.perf_counter()
    built = cell['builder'].build(config, traffic, train=True)
    if mesh:
        built['main'].set_mesh(dict(mesh))
    exe = fluid.Executor(place)
    scope = fluid.global_scope()
    exe.run(built['startup'])
    marks['build_startup_s'] = time.perf_counter() - t0

    t0 = time.perf_counter()
    checked = check.run_checks(cell, exe, scope, seed, mesh=mesh)
    marks['reference_check_s'] = time.perf_counter() - t0

    t0, since = time.perf_counter(), time.monotonic()
    first_loss = cell['loop'].step(exe, built, pool[0])
    marks['first_step_s'] = time.perf_counter() - t0
    pass_span = _pass_span(since) if traced else None
    stats_first = dict(exe.cache_stats)
    t0 = time.perf_counter()
    warm = cell['loop'].run(exe, built, pool, units, steps=WARMUP_STEPS)
    marks['warmup_s'] = time.perf_counter() - t0
    stats_warm = dict(exe.cache_stats)
    reg_warm = _registry_snapshot()
    setup_s = time.perf_counter() - t_start

    window = cell['loop'].run(exe, built, pool, units, seconds=seconds)
    stats_end = dict(exe.cache_stats)
    registry = _delta(_registry_snapshot(), reg_warm)
    compiles = sum(stats_end[k] - stats_warm[k] for k in _WATCHED)
    # units of work a completed step over the steady step time; the plain
    # total, completed work over elapsed time, goes on the summary line
    steady_s = steady_step_s(window['step_s'])
    rate = window['units'] / len(window['step_s']) / steady_s / chips \
        if steady_s else 0.0
    rate_total = window['units'] / window['elapsed_s'] / chips \
        if window['elapsed_s'] > 0 else 0.0
    losses = [first_loss] + warm['losses'] + window['losses']
    peak_max, peak0, mem0 = _memory_peak(devices)

    d0 = devices[0]
    # the device as jax reports it; the memory peak over the chips used
    device = {'platform': d0.platform, 'kind': d0.device_kind,
              'count': jax.device_count(), 'memory_peak_bytes': peak_max}
    ok = (window['failed'] == 0 and warm['failed'] == 0
          and bool(losses) and bool(np.all(np.isfinite(losses)))
          and window['attempted'] > 0 and compiles == 0
          and all(c['passed'] for c in checked.values()))
    # the rate is named by the generator's unit of work: tokens_per_s
    rate_metric = cell['generator'].UNIT + '_per_s'
    peak = peaks.PEAKS.get(d0.device_kind)
    step_flops = cell['flops'].train_step_flops(config, traffic)
    done = window['attempted'] - window['failed']
    summary = {
        'cell': cell['name'], 'seed': seed, 'seconds': seconds,
        'traced': bool(traced), 'setup_s': setup_s, 'setup_parts': marks,
        'steps': window['attempted'], 'units': window['units'],
        'unit': cell['generator'].UNIT, 'elapsed_s': window['elapsed_s'],
        rate_metric: rate, 'rate_total': rate_total,
        'step_steady_s': steady_s,
        # the share of the window that the steady step times the steps
        # does not account for: stalls of single steps
        'outside_steady_pct': 100.0 * (
            1.0 - steady_s * len(window['step_s']) / window['elapsed_s'])
        if steady_s else None,
        'step_median_s': statistics.median(window['step_s'])
        if window['step_s'] else None,
        'step_s': [round(t, 6) for t in window['step_s']],
        'loss_first': losses[0], 'loss_last': losses[-1],
        # the conventional MFU, over the wall clock: it follows the rate
        # one for one (the per-layer `mfu_pct` is over the device's busy
        # time, from the trace)
        'mfu_wall_pct': 100.0 * step_flops * done / window['elapsed_s']
        / (chips * peak['bf16_flops_per_s'])
        if peak and window['elapsed_s'] else None,
        'reference_check': checked, 'compiles_in_window': compiles,
        'cache_after_first_step': {k: stats_first[k] for k in _WATCHED},
        'cache_dir': stats_end.get('compile_cache_dir'),
        'pool': len(pool), 'units_per_pool_batch': units,
        'memory_stats_device0': mem0,
    }
    spec = spec or catalog.benchmark_json(cell['root'])
    line = {'correct': ok, 'attempted': window['attempted'],
            'failed': window['failed'], 'metrics': {}, 'device': device}
    if not traced:
        values = {rate_metric: rate, 'setup_s': setup_s}
        for m in catalog.metrics_of(cell['name'], 'end_to_end',
                                    cell['root'], spec):
            if m['name'] in values:
                line['metrics'][m['name']] = {'value': values[m['name']],
                                              'unit': m['unit']}
        exe.close()
        return {'line': line, 'summary': summary}

    try:
        traced_run, hlo, red = _trace_steps(
            cell, exe, built, pool, units, os.path.join(work_dir, 'trace'))
    finally:
        del os.environ[OBS_ENV]
    kernel_cost = cell['flops'].kernel_cost(config, traffic, chips)
    reading = {
        'cell': cell, 'chips': chips, 'trace': red, 'hlo': hlo,
        'registry': registry, 'window': window,
        'marks': marks, 'compiles_in_window': compiles,
        'pass_span': pass_span,
        'program_ops_handed': len(built['main'].global_block().ops),
        'peak_bytes_device0': peak0, 'peaks': peak,
        'step_flops': step_flops,
        'kernel_cost': kernel_cost,
    }
    for m in catalog.metrics_of(cell['name'], 'per_layer', cell['root'],
                                spec):
        value = catalog.load_reader(m['name'], cell['root'])(reading)
        if value is not None:
            line['metrics'][m['name']] = {'value': value, 'unit': m['unit']}
    # a chip that ran the steps and left no device operation in the trace
    # is a measurement that failed, not a run without per-layer metrics
    line['correct'] = (ok and traced_run['failed'] == 0
                       and (red is not None or d0.platform != 'tpu'))
    summary['traced_steps_rate'] = (
        traced_run['units'] / traced_run['elapsed_s'] / chips)
    summary['pass_span'] = pass_span
    if red is not None:
        device['busy_s'] = red['busy_s']
        device['window_s'] = red['window_s']
        line['breakdown'] = trace.breakdown(red)
        summary['longest_gaps'] = red['longest_gaps']
        # the traced window's own idle share, as the last line's busy_s
        # and window_s give it; `device_idle_pct` is over the measured
        # window's step time (layers/device_idle_pct.py says why)
        summary['traced_idle_pct'] = 100.0 * (
            1.0 - red['busy0_s'] / red['window_s'])
        summary['kernel_s'] = red['kernel_s']
        summary['kernel_by_op_s'] = red['kernel_by_op_s']
        summary['kernel_by_callee_s'] = red['kernel_by_callee_s']
    if kernel_cost and peak:
        summary['kernel_bound'] = {op: peaks.roofline(cost, peak)[1]
                                   for op, cost in kernel_cost.items()}
    exe.close()
    return {'line': line, 'summary': summary}


def _trace_steps(cell, exe, built, pool, units, trace_dir):
    """Five consecutive steps under the profiler. Returns the loop's
    result, the step's optimized HLO text and the trace's reduction (None
    when no device operation was recorded, as on a host)."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    # the reduction reads the device's lines and the TraceMe spans; the
    # tracer of Python's own calls is nine tenths of a trace, read by
    # nothing, and slowed the traced Transformer steps by 2% (chip, PR 22)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            traced_run = cell['loop'].run(exe, built, pool, units,
                                          steps=TRACED_STEPS, traced=True)
    finally:
        jax.profiler.stop_trace()
    hlo = exe.lowered_hlo(built['main'], pool[0], [built['loss']],
                          optimized=True)
    try:
        raw = trace.read_xplane(trace.find_xplane(trace_dir))
        red = trace.reduce(raw, scopes.instruction_scopes(hlo), TRACED_STEPS)
    except FileNotFoundError:
        red = None
    shutil.rmtree(trace_dir, ignore_errors=True)      # tens of MB
    return traced_run, hlo, red
