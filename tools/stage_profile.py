"""Where a cell's device time goes INSIDE its Fluid ops: the stages that
rules name with `jax.named_scope` (`gdn_intra`, `gdn_scan`, `moe_route`,
`moe_experts`, `moe_combine`), which the benchmark's reduction folds into
their op. On the chip only.

    python tools/stage_profile.py --workload qwen3next_s8192 [--seed N]
        [--steps 3] [--top 12] [--op gated_rms_norm]

Builds the cell's training step as chipbench/run.py does (no reference
check), warms it up, traces `--steps` steps and reduces the trace with
the benchmark's own reader (chipbench/harness/trace.py, scopes.py): each
device event's self time goes to `<op type>/<stage>`, the stage being the
last path element of its HLO op_name that one of STAGES names (or `-`).
Prints one JSON line: ms a step by op type and stage, forward apart from
backward (`transpose(` in the op_name), and the largest unattributed
instructions by name; with `--op TYPE`, that op type's instructions by
name besides (which fusions and copies an op's time is).
"""
import argparse
import collections
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STAGES = ('gdn_intra', 'gdn_scan', 'moe_route', 'moe_experts',
          'moe_combine')


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, default=1)
    p.add_argument('--steps', type=int, default=3)
    p.add_argument('--top', type=int, default=12)
    p.add_argument('--op', help='list this op type\'s instructions by name')
    args = p.parse_args(argv)

    import shutil
    import tempfile
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.utils import compile_cache
    from chipbench.harness import catalog, intervals, scopes, trace
    if jax.devices()[0].platform != 'tpu':
        raise SystemExit('stage_profile: no TPU; a device time is taken '
                         'on the chip only')
    compile_cache.enable()
    cell = catalog.load_cell(args.workload)
    pool, units = cell['generator'].make_pool(cell['traffic'],
                                              cell['config'], args.seed)
    built = cell['builder'].build(cell['config'], cell['traffic'])
    if cell['cell'].get('mesh'):
        built['main'].set_mesh(dict(cell['cell']['mesh']))
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(built['startup'])
    cell['loop'].run(exe, built, pool, units, steps=3)
    out = tempfile.mkdtemp(dir=os.path.join(REPO, '.chipbench')
                           if os.path.isdir(os.path.join(REPO, '.chipbench'))
                           else None)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        cell['loop'].run(exe, built, pool, units, steps=args.steps)
    finally:
        jax.profiler.stop_trace()
    hlo = exe.lowered_hlo(built['main'], pool[0], [built['loss']],
                          optimized=True)
    names = scopes.instruction_scopes(hlo)
    raw = trace.read_xplane(trace.find_xplane(out))
    shutil.rmtree(out, ignore_errors=True)
    events = raw['devices'][sorted(raw['devices'])[0]]
    by_stage = collections.Counter()
    loose, inside = collections.Counter(), collections.Counter()
    for name, self_ns in intervals.self_times(
            [(s, e, n) for s, e, n, _ in events]):
        op_name = names.get(name, '')
        scope = scopes.scope_of(op_name)
        stage = ([s for s in op_name.split('/') if s in STAGES] or ['-'])[-1]
        way = 'bwd' if 'transpose(' in op_name else 'fwd'
        if scope is None:
            loose[name.rstrip('0123456789.')] += self_ns
        elif scope[0] == args.op:
            inside['%s/%s' % (name, way)] += self_ns
        by_stage['%s/%s/%s' % (scope[0] if scope else 'unattributed',
                               stage, way)] += self_ns
    per_step = 1e-6 / args.steps
    print(json.dumps({
        'workload': args.workload, 'steps': args.steps,
        'ms_per_step': {k: round(v * per_step, 3)
                        for k, v in by_stage.most_common() if
                        v * per_step >= 0.05},
        'unattributed_ms_per_step': {
            k: round(v * per_step, 3)
            for k, v in loose.most_common(args.top)},
        **({'instructions_ms_per_step': {
            k: round(v * per_step, 3) for k, v in inside.most_common()}}
           if args.op else {})}))
    exe.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
