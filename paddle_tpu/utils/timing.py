"""On-chip timing helper of tools/tune_flash.py.

`block_until_ready` is honest on the chip, but timing one kernel per
dispatch still measures the host: each call pays a dispatch and a sync
whose fixed cost is of the order of a small kernel's run time, and it
drowns the per-candidate deltas a tile sweep looks for. So kernel timing
chains the steps ON DEVICE inside one jit (each step's input depends on
the previous step's gradient) and round-trips ONE scalar whose value
depends on the final result: one dispatch, one sync, `iters` kernels.
"""
import time

__all__ = ['time_chained', 'time_fwd_bwd_chained']


def time_chained(step, x, iters, warmup=1, consts=None):
    """Seconds per call of step(x, **consts) -> x' (x a tuple of
    [B, H, T, D] arrays), measured as `iters` calls chained inside one
    jit with a single scalar, which depends on every final array, pulled
    to the host at the end. Arrays a step only reads go in the dict
    `consts`: one it closes over is compiled into the program, a quarter
    of a gigabyte of it at 16384 positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    consts = consts or {}

    @jax.jit
    def run(x, consts):
        x = jax.lax.fori_loop(0, iters, lambda _, x: step(x, **consts), x)
        return sum(jnp.sum(a[0, 0, 0, :8].astype(jnp.float32)) for a in x)

    for _ in range(warmup):
        s = float(run(x, consts))   # compile + warm; host sync
        assert np.isfinite(s), s
    t0 = time.time()
    s = float(run(x, consts))       # host round-trip = completion
    assert np.isfinite(s), s
    return (time.time() - t0) / iters


def time_fwd_bwd_chained(loss_fn, q, k, v, iters, warmup=1):
    """Seconds per fwd+bwd step of loss_fn(q, k, v) -> scalar, chained as
    time_chained does. ALL THREE inputs advance by their gradients —
    dq and (dk, dv) come from separate pallas calls in the flash backward,
    so a chain that consumed only dq would let XLA dead-code-eliminate
    the dk/dv kernel and time half a backward."""
    import jax
    grad = jax.grad(loss_fn, argnums=(0, 1, 2))

    def step(qkv):
        return tuple(x + 1e-6 * dx for x, dx in zip(qkv, grad(*qkv)))

    return time_chained(step, (q, k, v), iters, warmup)
