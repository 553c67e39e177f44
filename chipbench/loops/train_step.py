"""Loop kind `train_step`: the blocking training loop of the reference's
benchmark/fluid/fluid_benchmark.py.

    exe.run(main, feed=<next host batch>, fetch_list=[loss])

with the default blocking fetch, so every step ends in a device-to-host
read of the loss and every step time is a completed step. The loop cycles
a pool of host batches made before the window; the program receives only
the arrays. A loop of another kind (a Trainer, a decode engine) is another
file beside this one, named by the cell's `loop`.
"""
import contextlib
import math
import time

import numpy as np


def _annotate(name, traced):
    """A span in the profiler's trace, on the clock the device events use."""
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def step(exe, built, feed):
    """One completed step; returns its loss as a float."""
    out, = exe.run(built['main'], feed=feed, fetch_list=[built['loss']])
    return float(np.asarray(out).reshape(-1)[0])


def run(exe, built, pool, units, seconds=None, steps=None, traced=False):
    """Steps until `seconds` have passed (then stops after the step in
    flight) or for exactly `steps` steps. Returns what happened: the
    losses, each step's seconds, the units of work completed, and the
    time from the first step's start to the last step's end."""
    losses, step_s = [], []
    attempted = failed = done_units = 0
    t_first = t_last = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if seconds is not None and t0 - t_first >= seconds:
            break
        if steps is not None and attempted >= steps:
            break
        i = attempted % len(pool)
        attempted += 1
        try:
            with _annotate('chipbench.step', traced):
                loss = step(exe, built, pool[i])
        except Exception as e:                    # noqa: BLE001
            # a step that raises is a failed operation of the run, which
            # the last line reports; the run itself goes on
            failed += 1
            print('step %d raised %s: %s' % (attempted, type(e).__name__, e),
                  flush=True)
            continue
        t_last = time.perf_counter()
        losses.append(loss)
        if math.isfinite(loss):
            step_s.append(t_last - t0)
            done_units += units[i]
        else:
            failed += 1
    return {'attempted': attempted, 'failed': failed, 'losses': losses,
            'step_s': step_s, 'units': done_units,
            'elapsed_s': t_last - t_first}
