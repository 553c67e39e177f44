"""The share of a step's expert assignments that lands on the experts THIS
chip holds, as the program's own device counters have it, beside the
expected one.

    python tools/held_share.py --workload qwen3next_s8192 --seed 7 [--steps 8]

A cell whose configuration holds a share of its experts
(chipbench/configs/qwen3_next_80b_a3b.json: 16 of 512) does the held
experts' work on however many of the tokens x top_k assignments the router
sends to them: data, not a shape. chipbench's rooflines count the EXPECTED
share, held / routed (flops/qwen3_next.py `held_rows`); this prints what
the seeded traffic really sent, layer by layer, over a few training steps
(Adam moves the router, so the share drifts from its first value). The
LAYER is the unit that chooses the path (ops_impl/moe_ops.py `_held_moe`):
a layer whose held rows fit its layout (`_HELD_SLACK` times the expected
number, at most half the layer's rows: `_held_layout`) lays out those
rows alone, by index (the compact path); beyond that it keeps every row.
So the line also gives each layer-step's held rows over the expected
number (`layer_over_expected_*`), how many layer-steps went over their
layout (`layers_over_slack`) and the share that stayed on the compact path
(`compact_share`). All of it is read from `fields['device']` of the
program's `executor.step` records (docs/observability.md): the rows the
step counted on the device and the `expected`, `cap` and `way` its rule
fixed, so "over the slack" has one definition, the rule's. Runs wherever
jax runs: on the chip the cell's own step, on the host the same Program
on CPUPlace (slow at published widths; `--toy` takes the widths of
tests/test_chipbench/toy/).
"""
import argparse
import json
import os
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def summary(steps, count, routed):
    """What the line says of `steps`, a list a step of the `device`
    entries of the held ops in op order."""
    def of(key):                                       # [steps, layers]
        return np.asarray([[e[key] for e in entries] for entries in steps])

    over = of('rows') / of('expected').astype(float)
    shares = over * count / routed
    compact = of('way') == 'compact'
    static = np.asarray([[e['cap'] is None for e in entries]
                         for entries in steps])
    return {
        'expected_share': count / routed,
        'measured_share_mean': float(shares.mean()),
        'measured_share_min': float(shares.min()),
        'measured_share_max': float(shares.max()),
        'first_step_by_layer': shares[0].tolist(),
        'last_step_by_layer': shares[-1].tolist(),
        'layer_over_expected_max_by_layer': over.max(axis=0).tolist(),
        'layer_over_expected_p50': float(np.median(over)),
        'layer_over_expected_max_by_step': [round(float(b), 2)
                                            for b in over.max(axis=1)],
        # a layer under one tile of rows has no layout (`cap` None): it
        # keeps every row whatever the router does, and is not over
        'layers_over_slack': int((~compact & ~static).sum()),
        'compact_share': float(compact.mean()),
        'layer_steps': int(over.size)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--steps', type=int, default=8)
    p.add_argument('--toy', action='store_true')
    args = p.parse_args(argv)

    import paddle_tpu.fluid as fluid
    from chipbench.harness import catalog
    overrides = None
    if args.toy:
        sys.path.insert(0, os.path.join(REPO, 'tests', 'test_chipbench'))
        import chipbench_toy
        name = catalog._json(catalog.ROOT, 'workloads',
                             args.workload + '.json')['config']
        overrides = chipbench_toy.toy_overrides(name)
    cell = catalog.load_cell(args.workload, overrides=overrides)
    config, traffic = cell['config'], cell['traffic']
    # builders/afmoe.py names it `held_share` (its docstring says why)
    share = getattr(cell['builder'], 'experts', None) \
        or cell['builder'].held_share
    routed, held = share(config)
    if held is None:
        raise SystemExit('%s holds every expert: nothing to measure'
                         % args.workload)
    first, count = held
    pool, _ = cell['generator'].make_pool(traffic, config, args.seed)
    built = cell['builder'].build(config, traffic, train=True)
    exe = fluid.Executor()
    exe.run(built['startup'])
    from paddle_tpu import obs
    from paddle_tpu.fluid.ops_impl import moe_ops
    # the counters are recorded while observability is on
    obs.enable(tempfile.mkdtemp(prefix='held_share_obs_'))
    for i in range(args.steps):
        exe.run(built['main'], feed=pool[i % len(pool)],
                fetch_list=[built['loss']])
    steps = [r['fields']['device'] for r in obs.completed_spans()
             if r['name'] == 'executor.step' and r['fields'].get('device')]
    steps = steps[-args.steps:]

    print(json.dumps({
        'workload': args.workload, 'seed': args.seed, 'steps': args.steps,
        'held': [first, count], 'routed': routed,
        **summary(steps, count, routed), 'slack': moe_ops._HELD_SLACK}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
