"""A configuration's checks shown FAILING: the cell's check Programs with
one rule moved, against the plain reference, at the published widths.

    python tools/check_controls.py --workload ling3flash_s8192 --seed 7
        [--control NAME ...] [--toy]

A tolerance says something only if a wrong program reads above it. This
builds the cell's scope as a run does, runs each entry of `checks` as it
stands (which must pass, and leaves the reference's gradients memoised:
the reference walks its tokens one by one, once), then builds the check
Program again with ONE rule of the program moved and compares it with the
same reference; a control that still passes is the finding. Prints one
JSON line a check and a control. The controls, by the entry they run
under:

  float32  bf16_arithmetic  the entry's own gradients in the cell's bf16
                          AMP: the nearest precision below, which the
                          entry's limit has to refuse
           decay_a_head   the delta rule's decay averaged over a head's
                          channels (the per-head rule on a per-channel g)
           no_groups      the router's choice over all experts, its groups
                          left out
           key_scale_dv   attention's scores scaled by the VALUES' width
                          where keys are wider (128^-0.5 for 192^-0.5)
  amp      eight_bit      every AMP operand of the forward pass rounded
                          to 3 mantissa bits before its cast to bf16
                          (8-bit arithmetic; cotangents pass as they do)

A control that does not apply to a configuration (no such op) reads what
the unmoved Program reads. Runs wherever jax runs: on the chip the cell's
widths, on the host with `--toy` those of tests/test_chipbench/toy/.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _decay_a_head(stack):
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops_impl import linear_attention_ops as la
    rule = la.gated_delta_rule

    def averaged(q, k, v, g, beta, **kw):
        if g.ndim == 4:
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        return rule(q, k, v, g, beta, **kw)
    stack.append((la, 'gated_delta_rule', rule))
    la.gated_delta_rule = averaged


def _no_groups(stack):
    from paddle_tpu.parallel import moe
    router = moe.router_topk

    def free(logits, top_k, norm_topk_prob=True, scoring='softmax',
             bias=None, gate_scale=1.0, norm_eps=None, n_group=1,
             topk_group=1):
        return router(logits, top_k, norm_topk_prob, scoring, bias,
                      gate_scale, norm_eps)
    stack.append((moe, 'router_topk', router))
    moe.router_topk = free


def _key_scale_dv(stack):
    from paddle_tpu import ops

    def moved(attention):
        def scaled(q, k, v, *args, sm_scale=None, **kw):
            if q.shape[-1] != v.shape[-1]:
                sm_scale = v.shape[-1] ** -0.5
            return attention(q, k, v, *args, sm_scale=sm_scale, **kw)
        return scaled

    # the kernels on the TPU, the XLA chain on the host
    for name in ('flash_attention', 'reference_attention'):
        stack.append((ops, name, getattr(ops, name)))
        setattr(ops, name, moved(getattr(ops, name)))


def _eight_bit(stack):
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.fluid import lowering

    def rounded(ctx, *xs):
        """lowering.amp_cast with the operand rounded to 3 mantissa bits
        first."""
        if not ctx.amp:
            return xs if len(xs) > 1 else xs[0]

        def cut(x):
            if x.dtype != jnp.float32:
                return x
            m, e = jnp.frexp(x)
            coarse = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
            # the rounding is the forward pass's: the cotangent passes it
            # as it passes the cast (a `round` alone would zero it, and
            # every gradient would read 1.0 for no rounding's sake)
            return (x + lax.stop_gradient(coarse - x)).astype(jnp.bfloat16)
        out = tuple(cut(x) for x in xs)
        return out if len(out) > 1 else out[0]

    real = lowering.amp_cast
    for name, module in list(sys.modules.items()):
        if name.startswith('paddle_tpu.fluid') and getattr(
                module, 'amp_cast', None) is real:
            stack.append((module, 'amp_cast', real))
            module.amp_cast = rounded


def _bf16_arithmetic(stack):
    """No rule moved: the entry itself in the cell's bf16 AMP (`main`
    reads `ENTRY_MOVES`)."""


# what a control changes of the ENTRY it runs under, beside the program
ENTRY_MOVES = {'bf16_arithmetic': {'amp': 'bf16', 'matmul_precision': None}}

CONTROLS = {'bf16_arithmetic': ('float32', _bf16_arithmetic),
            'decay_a_head': ('float32', _decay_a_head),
            'no_groups': ('float32', _no_groups),
            'key_scale_dv': ('float32', _key_scale_dv),
            'eight_bit': ('amp', _eight_bit)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, default=7)
    p.add_argument('--control', action='append', choices=sorted(CONTROLS))
    p.add_argument('--toy', action='store_true')
    args = p.parse_args(argv)

    import paddle_tpu.fluid as fluid
    from chipbench.harness import catalog, check
    if args.toy:
        sys.path.insert(0, os.path.join(REPO, 'tests', 'test_chipbench'))
        import chipbench_toy
        cell = chipbench_toy.load_toy_cell(args.workload)
    else:
        cell = catalog.load_cell(args.workload)
    checks = cell['config']['checks']
    built = cell['builder'].build(cell['config'], cell['traffic'],
                                  train=True)
    exe = fluid.Executor()
    exe.run(built['startup'])
    scope = fluid.global_scope()

    def line(entry, control, got):
        print(json.dumps({
            'workload': args.workload, 'seed': args.seed, 'check': entry,
            'control': control, 'passed': got['passed'],
            'loss_rel': got['loss_rel'], 'grad_rel': got['grad_rel'],
            'tolerance': got['tolerance'],
            'seconds': got['seconds']}), flush=True)

    failed = 0
    names = args.control or sorted(CONTROLS)
    for entry in sorted({CONTROLS[n][0] for n in names} & set(checks)):
        got = check.run_check(cell, exe, scope, args.seed, checks[entry])
        line(entry, None, got)
        failed += not got['passed']
        for name in names:
            if CONTROLS[name][0] != entry:
                continue
            stack = []
            try:
                CONTROLS[name][1](stack)
                got = check.run_check(
                    cell, exe, scope, args.seed,
                    dict(checks[entry], **ENTRY_MOVES.get(name, {})))
            finally:
                for module, attr, value in stack:
                    setattr(module, attr, value)
            line(entry, name, got)
            failed += bool(got['passed'])       # a control must FAIL
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
