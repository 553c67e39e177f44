"""One command runs one cell (the form BENCHMARK.json's `command` gives):

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chips. It exits non-zero and prints no result
when jax finds no TPU, fewer chips than the cell asks for, or a device
whose published peaks are not in harness/peaks.py: a device metric is
never taken anywhere else. The run's facts go on a `summary {...}` line;
the LAST line of standard output is one JSON object with exactly the keys
`correct`, `attempted`, `failed`, `metrics`, `device` and, in a traced run,
`breakdown`.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

# everything the benchmark itself writes (obs run log, trace) goes here
WORK_DIR = os.path.join(CHECKOUT, '.chipbench')


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, required=True)
    p.add_argument('--seconds', type=float, required=True)
    p.add_argument('--trace', type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def emit(result):
    print('summary ' + json.dumps(result['summary'], default=float),
          flush=True)
    print(json.dumps(result['line']), flush=True)


def main(argv=None):
    args = parse(argv)
    from chipbench.harness import catalog, cell as cell_runner, peaks
    cell = catalog.load_cell(args.workload)
    chips = cell['cell']['chips']

    # ask for the TPU by name before any backend exists, so that a failed
    # libtpu start-up raises instead of handing back the host
    os.environ.setdefault('JAX_PLATFORMS', 'tpu,cpu')
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != 'tpu':
        raise SystemExit('chipbench: no TPU: jax.devices()[0].platform is %r '
                         '(%r); a device rate is measured on the chip only'
                         % (d0.platform, devices))
    if len(devices) < chips:
        raise SystemExit('chipbench: cell %s needs %d chips, jax found %d'
                         % (args.workload, chips, len(devices)))
    peaks.peaks_for(d0.device_kind)

    import paddle_tpu.fluid as fluid
    from paddle_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    print('chipbench: %s on %d x %s, compile cache %s'
          % (args.workload, chips, d0.device_kind, cache_dir), flush=True)
    os.makedirs(WORK_DIR, exist_ok=True)
    result = cell_runner.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), fluid.TPUPlace(0),
        T_START, WORK_DIR, devices=devices[:chips])
    emit(result)
    return 0


if __name__ == '__main__':
    sys.exit(main())
