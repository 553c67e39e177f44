"""Nothing hides the device (ISSUE 21): the refusals chip_smoke.py and the
on-chip path rely on, checked where there is no chip.

  - an explicit TPUPlace with no TPU (or a device id past the last one)
    raises core.DeviceUnavailableError naming what jax.devices() returned,
    at Executor construction; the place=None default still picks the host;
  - the compile-cache resolver returns JAX_COMPILATION_CACHE_DIR when set
    and <checkout>/.jax_cache when not, and utils/compile_cache.py is the
    only file that points jax at a cache directory;
  - `python chip_smoke.py` on a CPU host exits non-zero naming `cpu`, and
    alone in a directory it cannot even import the program;
  - the training leg itself, called at a toy width on CPUPlace, runs and
    its loss falls;
  - the last line main() prints is the driver's verdict object and nothing
    more (the first on-chip check was refused for a fuller last line).
"""
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_explicit_tpu_place_without_a_tpu_raises():
    for place in (fluid.TPUPlace(0), fluid.CUDAPlace(0), fluid.TPUPlace(3)):
        with pytest.raises(core.DeviceUnavailableError) as e:
            fluid.Executor(place)
        assert 'CpuDevice' in str(e.value) and repr(place) in str(e.value)
    # the default looks at what is present; CPUPlace is unchanged
    assert fluid.Executor().place == fluid.CPUPlace()
    assert fluid.Executor(fluid.CPUPlace())._device() \
        == jax.devices('cpu')[0]


def test_cache_resolver(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.resolve() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.resolve() == os.path.join(REPO, '.jax_cache')
    # not in the environment and no entry point enabled it: Executors
    # leave jax's cache alone
    assert compile_cache.wired() is None
    assert fluid.Executor(fluid.CPUPlace()).cache_stats[
        'compile_cache_dir'] is None
    assert jax.config.jax_compilation_cache_dir is None


_CACHE_CHILD = r"""
import sys
sys.path.insert(0, %r)
import jax
import paddle_tpu.fluid as fluid
exe = fluid.Executor(fluid.CPUPlace())
print('DIR=%%s|%%s' %% (exe.cache_stats['compile_cache_dir'],
                      jax.config.jax_compilation_cache_dir))
""" % REPO


def test_executor_wires_only_the_environments_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cc'))
    r = subprocess.run([sys.executable, '-c', _CACHE_CHILD], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'DIR=%s|%s' % (tmp_path / 'cc', tmp_path / 'cc') in r.stdout


def test_one_file_points_jax_at_a_cache_directory():
    """No path made from tempfile, a pid or the time can reach jax's
    cache config if only the resolver ever sets it."""
    setters = []
    for root in ('paddle_tpu', 'tools', 'examples', 'benchmark'):
        for d, _, files in os.walk(os.path.join(REPO, root)):
            setters += [os.path.join(d, f) for f in files
                        if f.endswith(('.py', '.sh'))]
    setters += [os.path.join(REPO, f)
                for f in ('chip_smoke.py', '__graft_entry__.py')]
    hits = [os.path.relpath(p, REPO) for p in setters
            if re.search(r'jax_compilation_cache_dir|set_cache_dir',
                         open(p).read())]
    assert hits == ['paddle_tpu/utils/compile_cache.py']


def test_chip_smoke_refuses_a_cpu_host(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py')],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
    # alone in a directory: nothing of the program to import
    shutil.copy(os.path.join(REPO, 'chip_smoke.py'), str(tmp_path))
    r = subprocess.run([sys.executable, 'chip_smoke.py'], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_training_leg_runs_at_toy_width_on_the_host():
    import chip_smoke
    out = chip_smoke.train_leg(fluid.CPUPlace(), cfg=chip_smoke.TOY,
                               steps=6, expect_kernel=False)
    assert out['last_loss'] < out['first_loss']
    assert out['tpu_custom_calls'] == 0       # the host takes the XLA chain
    assert out['steps'] == 6


def test_last_line_is_the_verdict_object_and_nothing_more(monkeypatch, capsys):
    import chip_smoke
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    monkeypatch.setattr(chip_smoke, 'device_report', lambda: device)
    monkeypatch.setattr(chip_smoke, 'train_leg',
                        lambda place: {'first_loss': 10.0, 'last_loss': 9.0})
    monkeypatch.setattr(chip_smoke, 'reference_leg', lambda: {})
    monkeypatch.setattr(chip_smoke, 'kernel_leg', lambda: {'k': 0.0})
    monkeypatch.setattr(compile_cache, 'enable', lambda: '/nowhere')
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')      # main() only setdefaults
    assert not chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {'ok': True, 'device': device}
    assert lines[-2].startswith('summary {')
    assert json.loads(lines[-2][len('summary '):])['train']['last_loss'] == 9.0
