"""Multi-host evidence: parallel.init_multihost really joins two processes
into one jax.distributed cluster over loopback (the DCN path of
docs/distributed.md), using the reference launcher's environment variables
(PADDLE_TRAINER_ENDPOINTS / PADDLE_TRAINERS / PADDLE_TRAINER_ID —
reference transpiler/distribute_transpiler.py launcher contract).

Each child claims 2 virtual CPU devices, so the cluster's global view is
4 devices across 2 processes; a jitted global-mesh reduction proves the
processes actually compute together rather than merely handshaking.
"""
import os
import socket
import subprocess
import sys

import pytest

_CHILD = r"""
import os
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 2)
import numpy as np
from paddle_tpu import parallel

assert parallel.init_multihost() is True
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()
assert len(jax.local_devices()) == 2

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = parallel.make_mesh({'dp': 4})
src = np.arange(8, dtype=np.float32)
x = jax.make_array_from_callback(
    (8,), NamedSharding(mesh, P('dp')), lambda idx: src[idx])
s = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(x)
total = float(np.asarray(s.addressable_data(0)))
assert total == src.sum(), total
print('MULTIHOST OK', jax.process_index(), total)
"""


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_loopback_cluster(tmp_path):
    port = _free_port()
    procs = []
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rank in (0, 1):
        env = dict(os.environ,
                   PADDLE_TRAINER_ENDPOINTS='127.0.0.1:%d' % port,
                   PADDLE_TRAINERS='2',
                   PADDLE_TRAINER_ID=str(rank),
                   PYTHONPATH=here)
        env.pop('JAX_PLATFORMS', None)
        env.pop('XLA_FLAGS', None)
        procs.append(subprocess.Popen(
            [sys.executable, '-c', _CHILD], env=env, cwd=here,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=210)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, 'child failed rc=%d\nstdout:%s\nstderr:%s' % (
            rc, out, err[-2000:])
        assert 'MULTIHOST OK' in out
