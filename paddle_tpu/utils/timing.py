"""On-chip timing helper shared by bench.py and tools/tune_flash.py.

`block_until_ready` is honest on the chip, but timing one kernel per
dispatch still measures the host: each call pays a dispatch and a sync
whose fixed cost is of the order of a small kernel's run time, and it
drowns the per-candidate deltas a tile sweep looks for. So kernel timing
chains the steps ON DEVICE inside one jit (each step's input depends on
the previous step's gradient) and round-trips ONE scalar whose value
depends on the final result: one dispatch, one sync, `iters` kernels.
"""
import time

__all__ = ['time_fwd_bwd_chained']


def time_fwd_bwd_chained(loss_fn, q, k, v, iters, warmup=1):
    """Seconds per fwd+bwd step of loss_fn(q, k, v) -> scalar, measured as
    `iters` chained steps inside one jit with a single scalar pulled to
    the host at the end. ALL THREE inputs advance by their gradients —
    dq and (dk, dv) come from separate pallas calls in the flash backward,
    so a chain that consumed only dq would let XLA dead-code-eliminate
    the dk/dv kernel and time half a backward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    grad = jax.grad(loss_fn, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        def body(_, qkv):
            qq, kk, vv = qkv
            dq, dk, dv = grad(qq, kk, vv)
            return (qq + 1e-6 * dq, kk + 1e-6 * dk, vv + 1e-6 * dv)
        qn, kn, vn = jax.lax.fori_loop(0, iters, body, (q, k, v))
        return jnp.sum((qn[0, 0, 0, :8] + kn[0, 0, 0, :8]
                        + vn[0, 0, 0, :8]).astype(jnp.float32))

    for _ in range(warmup):
        s = float(run(q, k, v))     # compile + warm; host sync
        assert np.isfinite(s), s
    t0 = time.time()
    s = float(run(q, k, v))         # host round-trip = completion
    assert np.isfinite(s), s
    return (time.time() - t0) / iters
