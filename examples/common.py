"""Shared plumbing for the runnable book examples.

Each example mirrors a reference Fluid book chapter
(python/paddle/fluid/tests/book/) as a standalone user script: build the
model through the public API, train, save/reload an inference model, infer.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))


def example_args(epochs, batch_size=None, argv=None, extra=None):
    p = argparse.ArgumentParser()
    if extra is not None:
        extra(p)  # script-specific flags, e.g. --net
    p.add_argument('--epochs', type=int, default=epochs)
    p.add_argument('--steps', type=int, default=None,
                   help='cap on train steps per epoch (0 = full epoch; '
                        'unset = per-script default)')
    if batch_size is not None:
        p.add_argument('--batch_size', type=int, default=batch_size)
    p.add_argument('--device', type=str, default='CPU',
                   choices=['CPU', 'TPU'])
    p.add_argument('--save_dir', type=str,
                   default=os.path.join(tempfile.gettempdir(),
                                        'paddle_tpu_example'))
    return p.parse_args(argv)


def force_platform(args):
    """A --device CPU run pins jax to the host before any backend
    initializes, so it never takes the chip from a process that needs it
    (JAX_PLATFORMS=cpu in the environment does the same)."""
    if args.device == 'CPU':
        import jax
        jax.config.update('jax_platforms', 'cpu')


def claim_devices(n=8):
    """Provision n virtual CPU devices for a mesh example. The device
    count can only be set before the backend initializes; once one is up
    (the test harness pre-provisions its own 8-device mesh) jax refuses
    the change and the example runs on what is there."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    try:
        jax.config.update('jax_num_cpu_devices', n)
    except RuntimeError:
        pass


def fresh_session():
    """Reset the process-global default programs, scope, and name counters
    so several examples can run in one interpreter (each script is its own
    program; standalone runs are unaffected)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.executor import Scope, _switch_scope
    framework.switch_main_program(fluid.Program())
    framework.switch_startup_program(fluid.Program())
    unique_name.switch()
    _switch_scope(Scope())


def capped(reader, steps):
    """Limit a batch reader to `steps` batches (0 = no cap)."""
    def _r():
        for i, b in enumerate(reader()):
            if steps and i >= steps:
                break
            yield b
    return _r
