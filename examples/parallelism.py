"""Program-level parallelism tour: pp / sp / pp+sp / local-SGD on a mesh.

TPU-first capabilities beyond the reference book chapters (the reference's
distributed story is pserver scripts; see docs/distributed.md): one small
Fluid Transformer is trained four ways on an 8-device mesh —

  1. pipeline parallelism: decoder stages stamped with
     fluid.device_guard('pipe:K'), transpiled by fluid.PipelineTranspiler,
     executed as a GPipe schedule inside the jitted step;
  2. sequence parallelism: fluid.SequenceParallelTranspiler routes every
     fused_attention through the ring (flash blocks on TPU) — the
     long-context path;
  3. pp + sp composed: pipeline stage bodies run sequence-local, the
     attention ring turning inside the pipeline's shard_map;
  4. local SGD (parallel.LocalSGD): the async-training analogue — dp
     replicas take collective-free local steps and periodically average.

Run:  python examples/parallelism.py [--steps 4]
(claims an 8-device virtual CPU mesh BEFORE backend init when run
standalone, same as the test suite's conftest).
"""
from common import claim_devices, example_args, fresh_session


def main():
    args = example_args(epochs=1)
    if args.device == 'CPU':
        claim_devices(8)

    import numpy as np
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel
    from paddle_tpu.models import transformer as T

    steps = args.steps or 4
    vocab, seq, batch = 64, 16, 8
    rng = np.random.RandomState(0)
    feed = {n: rng.randint(1, vocab, size=(batch, seq)).astype('int64')
            for n in ('src_word', 'trg_word', 'lbl_word')}
    losses = {}

    def train(tag, transpile, pp_decoder=False):
        fresh_session()
        avg_cost, _, _ = T.transformer(
            vocab, vocab, seq, n_layer=4, d_model=32, n_head=4,
            d_inner=64, dropout_rate=0.0, pp_decoder=pp_decoder)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        transpile(fluid.default_main_program())
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        out = [float(exe.run(feed=feed, fetch_list=[avg_cost])[0])
               for _ in range(steps)]
        losses[tag] = out
        print('%-10s loss %.4f -> %.4f' % (tag, out[0], out[-1]))
        return out

    def pp_and_sp(p):
        # the composed stack: pipelined decoder stages run sequence-local,
        # attention rides the sp ring inside the pipeline's shard_map
        fluid.PipelineTranspiler(n_micro=2).transpile(p)
        fluid.SequenceParallelTranspiler(sp=2).transpile(p)

    train('baseline', lambda p: None)
    train('pipeline', lambda p: fluid.PipelineTranspiler(
        n_micro=2).transpile(p), pp_decoder=True)
    train('seq-par', lambda p: fluid.SequenceParallelTranspiler(
        sp=8).transpile(p))
    train('pp+sp', pp_and_sp, pp_decoder=True)

    # identical math, different schedules
    for tag in ('pipeline', 'seq-par', 'pp+sp'):
        np.testing.assert_allclose(losses[tag], losses['baseline'],
                                   rtol=2e-4)

    # local SGD: the async-training analogue (docs/distributed.md)
    import jax.numpy as jnp
    mesh = parallel.make_mesh({'dp': 8})
    w0 = rng.rand(16).astype('float32')

    def step_fn(params, batch_xy):
        x, y = batch_xy
        g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(params['w'])
        return {'w': params['w'] - 0.1 * g}, jnp.mean(
            (x @ params['w'] - y) ** 2)

    ls = parallel.LocalSGD(step_fn, mesh, sync_steps=2)
    params = ls.replicate({'w': w0})
    for i in range(steps):
        b = (rng.rand(32, 16).astype('float32'),
             rng.rand(32).astype('float32'))
        params, aux = ls.step(params, ls.shard_batch(b))
        if (i + 1) % ls.sync_steps == 0:
            params = ls.sync(params)
    final = ls.collapse(params)['w']
    print('local-SGD  final |w| %.4f (replicas mixed every %d steps)'
          % (float(np.linalg.norm(final)), ls.sync_steps))
    return losses['baseline'][-1]


if __name__ == '__main__':
    main()
