"""Compiles a benchmark cell's training step for a described TPU v5e, with
no chip, and prints what the compiler says of it: PERF.md's batch rule
(section 4) reads `memory_analysis()` here.

    JAX_PLATFORMS=cpu python tools/aot_cell.py --workload olmoe_s4096
        [--batch N] [--seq T] [--hlo FILE] [--check NAME]

Builds the cell's Program at its published widths, runs the start-up
program on the host (the step's arguments need shapes, not values), lowers
the Executor's own jitted step with the TPU's lowering rules (flash and
grouped-matmul kernels, not their host fallbacks) for device 0 of a
described `v5e:2x2`, and compiles. With `--check NAME` it compiles that
entry of the configuration's `checks` instead: the check Program in its
own arithmetic and the plain reference on the check's sample, the two
programs that share the chip with the scope before the window (a
reference that walks its layers with the weights on the host, one that
has `pieces`, as the pieces of its walk). The step's line also says how
many values its recompute regions keep by the model's marks and their
bytes (`recompute.kept_values`, `recompute.kept_bytes`). One chip
only: a mesh cell builds its mesh from jax.devices(). A compile that
passes is not a chip run.
"""
import argparse
import json
import os
import sys

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--batch', type=int)
    p.add_argument('--seq', type=int,
                   help="the traffic's row length in place of the cell's")
    p.add_argument('--hlo', help='write the optimized HLO text here')
    p.add_argument('--check', help="compile this entry of `checks` instead")
    args = p.parse_args(argv)

    import contextlib
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import paddle_tpu.fluid as fluid
    from paddle_tpu import obs
    from chipbench.harness import catalog

    cell = catalog.load_cell(args.workload)
    config, traffic = cell['config'], dict(cell['traffic'])
    if args.batch:
        traffic['batch'] = args.batch
    if args.seq:
        traffic['seq'] = args.seq
    precision = None
    if args.check:
        # as harness/check.py run_check builds it
        entry = config['checks'][args.check]
        config = dict(config, check=entry,
                      amp=entry.get('amp', config['amp']))
        traffic['batch'] = args.batch or entry['sample']
        precision = entry.get('matmul_precision')
    pool, _ = cell['generator'].make_pool(dict(traffic, pool=1), config, 1)
    built = cell['builder'].build(config, traffic, train=not args.check)
    exe = fluid.Executor(fluid.CPUPlace())
    # the start-up program of the TRAINING Program: the check runs in a
    # scope that holds the optimizer's state too
    exe.run(cell['builder'].build(cell['config'], traffic)['startup'])

    # the step as the TPU's place would lower it
    exe._lowering_platform = lambda mesh: 'tpu'
    scope = fluid.global_scope()
    compiled = exe.step_artifact(
        built['main'], pool[0],
        [built['loss']] + [built['grads'][n] for n in sorted(built['grads'])],
        scope)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    # shapes only: the state as the scope holds it, the feed as jax
    # holds a batch of the pool (an int64 id is an int32 there)
    donated, readonly = compiled.plan.split(compiled.state_dict(scope))
    feed = {n: jax.ShapeDtypeStruct(
        v.shape, jax.dtypes.canonicalize_dtype(v.dtype))
        for n, v in pool[0].items()}
    shapes = jax.tree_util.tree_map(spec, (donated, readonly, feed))
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        done = compiled._jitted.lower(
            *shapes, spec(jax.random.key(0))).compile()
    # what the step's recompute regions keep by the model's marks
    # (fluid.recompute_keep): this process's one trace of a step counted
    kept = {n: int(obs.counter(n).value) for n in (
        'recompute.kept_values', 'recompute.kept_bytes')}
    text = done.as_text()
    if args.hlo:
        with open(args.hlo, 'w') as f:
            f.write(text)

    def report(what, done, **more):
        mem = done.memory_analysis()
        sizes = {k: getattr(mem, k) for k in (
            'argument_size_in_bytes', 'output_size_in_bytes',
            'alias_size_in_bytes', 'temp_size_in_bytes',
            'generated_code_size_in_bytes')}
        total = (sizes['argument_size_in_bytes']
                 + sizes['output_size_in_bytes']
                 - sizes['alias_size_in_bytes']
                 + sizes['temp_size_in_bytes'])
        print(json.dumps({
            'workload': args.workload, 'program': what,
            'batch': traffic['batch'], 'seq': traffic.get('seq'), **sizes,
            'step_bytes': total, 'step_gib': total / 2.0 ** 30,
            'compiled_for': str(topo.devices[0].device_kind), **more}),
            flush=True)

    report(args.check or 'train_step', done,
           mosaic_calls=text.count('tpu_custom_call'), **kept)
    if not args.check:
        return 0

    # the plain reference on the same sample, its parameters as arguments
    # (a reference with forward_loss(params, model, *feeds), as the causal
    # language models' have)
    from chipbench.harness import check as check_mod
    params, tree = cell['builder'].reference_params(
        config, built['main'],
        lambda name: np.asarray(scope.find_var(name).get_tensor()))
    paths = check_mod.grad_paths(tree, set(built['grads']))
    wanted = sorted({path for path, _ in paths.values()})
    reference = cell['reference']

    def like(a, dtype=np.float32):
        return jax.ShapeDtypeStruct(np.shape(a), dtype, sharding=chip)

    if hasattr(reference, 'pieces'):
        # a reference that walks its layers with the weights on the host
        # (references/granitemoehybrid.py): each piece of the walk alone,
        # a layer's parameters and its input the arguments
        model = config['model']
        fn = reference.pieces(model)
        ids, labels = (like(pool[0][k], np.int32) for k in built['feeds'])
        x = like(np.empty(ids.shape + (model['hidden_size'],)))
        # the head's matrix: the tied table, or an untied head of its own
        table = like(params.get('head', params['tok_emb']))
        with jax.default_matmul_precision('highest'):
            report('reference.head', fn['head'].lower(
                x, like(params['norm_final']), table, labels).compile())
            kinds = reference.kinds_of(model)
            for kind in sorted(set(kinds)):
                w = jax.tree_util.tree_map(like, reference.sub(
                    params, 'layer%d.' % kinds.index(kind)))
                report('reference.%s.forward' % kind,
                       fn['forward'][kind].lower(w, x).compile())
                report('reference.%s.backward' % kind,
                       fn['backward'][kind].lower(w, x, x).compile())
        return 0
    with jax.default_matmul_precision('highest'):
        ref = jax.jit(lambda p, ids, labels: jax.value_and_grad(
            lambda w: reference.forward_loss({**p, **w}, config['model'],
                                             ids, labels))(
            {k: p[k] for k in wanted})).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, np.float32,
                                               sharding=chip), params),
            *(jax.ShapeDtypeStruct(pool[0][k].shape, np.int32,
                                   sharding=chip)
              for k in built['feeds'])).compile()
    report('reference', ref)
    return 0


if __name__ == '__main__':
    sys.exit(main())
