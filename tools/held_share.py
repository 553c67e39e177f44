"""The share of a step's expert assignments that lands on the experts THIS
chip holds, measured from `ExpertCount`, beside the expected one.

    python tools/held_share.py --workload qwen3next_s8192 --seed 7 [--steps 8]

A cell whose configuration holds a share of its experts
(chipbench/configs/qwen3_next_80b_a3b.json: 16 of 512) does the held
experts' work on however many of the tokens x top_k assignments the router
sends to them: data, not a shape. chipbench's rooflines count the EXPECTED
share, held / routed (flops/qwen3_next.py `held_rows`); this prints what
the seeded traffic really sent, layer by layer, over a few training steps
(Adam moves the router, so the share drifts from its first value). Runs
wherever jax runs: on the chip the cell's own step, on the host the same
Program on CPUPlace (slow at published widths; `--toy` takes the widths
of tests/test_chipbench/toy/).
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--steps', type=int, default=8)
    p.add_argument('--toy', action='store_true')
    args = p.parse_args(argv)

    import numpy as np
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
    routed, held = cell['builder'].experts(config)
    if held is None:
        raise SystemExit('%s holds every expert: nothing to measure'
                         % args.workload)
    first, count = held
    pool, _ = cell['generator'].make_pool(traffic, config, args.seed)
    built = cell['builder'].build(config, traffic, train=True)
    moes = [op for op in built['main'].global_block().ops
            if op.type == 'moe_mlp']
    fetch = [built['loss']] + [op.output('ExpertCount')[0] for op in moes] \
        + [op.input('X')[0] for op in moes]
    exe = fluid.Executor()
    exe.run(built['startup'])
    scope = fluid.global_scope()
    from paddle_tpu.fluid.ops_impl import moe_ops
    top_k = moes[0].attrs['top_k']
    shares, blocks = [], []
    for i in range(args.steps):
        out = exe.run(built['main'], feed=pool[i % len(pool)],
                      fetch_list=fetch)
        counts, inputs = out[1:1 + len(moes)], out[1 + len(moes):]
        shares.append([float(np.asarray(c)[first:first + count].sum())
                       / float(np.asarray(c).sum()) for c in counts])
        # the same routing block by block, on the host: which of a block's
        # assignments are held decides the path the block takes
        # (ops_impl/moe_ops.py `_held_moe`)
        per_layer = []
        for op, x in zip(moes, inputs):
            x = np.asarray(x, np.float32)
            x = x.reshape(-1, x.shape[-1])
            w = np.asarray(scope.find_var(op.input('GateW')[0]).get_tensor())
            top = np.argsort(-(x @ w), axis=-1)[:, :top_k]
            held_rows = ((top >= first) & (top < first + count)).sum(-1)
            size = moe_ops._HELD_BLOCK if len(x) % moe_ops._HELD_BLOCK == 0 \
                else len(x)
            per_layer.append((held_rows.reshape(-1, size).sum(-1)
                              / (size * top_k * count / routed)).tolist())
        blocks.append(per_layer)
    shares, blocks = np.asarray(shares), np.asarray(blocks)
    print(json.dumps({
        'workload': args.workload, 'seed': args.seed, 'steps': args.steps,
        'held': [first, count], 'routed': routed,
        'expected_share': count / routed,
        'measured_share_mean': float(shares.mean()),
        'measured_share_min': float(shares.min()),
        'measured_share_max': float(shares.max()),
        'first_step_by_layer': shares[0].tolist(),
        'last_step_by_layer': shares[-1].tolist(),
        # a block's held rows over the expected number: above
        # moe_ops._HELD_SLACK the block keeps all its rows
        'block_over_expected_max_by_layer': blocks.max(axis=(0, 2)).tolist(),
        'block_over_expected_p50': float(np.median(blocks)),
        'blocks_over_slack': int((blocks > moe_ops._HELD_SLACK).sum()),
        'block_over_expected_max_by_step': [round(float(b), 2) for b in
                                            blocks.max(axis=(1, 2))],
        'blocks': int(blocks.size)}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
