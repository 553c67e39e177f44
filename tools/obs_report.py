#!/usr/bin/env python
"""obs_report: summarize (or validate) a paddle_tpu run log.

    python tools/obs_report.py                    # latest run in $PADDLE_TPU_OBS_DIR
    python tools/obs_report.py RUN.jsonl          # one run file
    python tools/obs_report.py OBS_DIR --merge    # every run file in a dir
    python tools/obs_report.py RUN.jsonl --check  # validate; rc=2 on bad records
    python tools/obs_report.py --emit NAME k=v... # append one event record

Prints p50/p95/max step time, the compile-vs-step split per cache key, the
compile-cache hit ratio, anomaly-guard skips, retry/reader-degrade events,
and the checkpoint timeline — a run is diagnosable from its JSONL alone,
no TensorBoard needed.

The obs package is loaded STANDALONE (stdlib importlib, never `import
paddle_tpu`), so this CLI starts in milliseconds and works on machines
without jax.
"""
import argparse
import importlib.util
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_obs():
    """Load paddle_tpu/obs as a standalone top-level package — no
    paddle_tpu import, hence no jax import (the package is stdlib-only
    by contract; tests/test_obs.py enforces it)."""
    if 'paddle_tpu' in sys.modules:       # already paid for: reuse it
        from paddle_tpu import obs
        return obs
    pkg_dir = os.path.join(_REPO, 'paddle_tpu', 'obs')
    name = '_paddle_tpu_obs_standalone'
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, '__init__.py'),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _parse_field(kv):
    if '=' not in kv:
        raise SystemExit('--emit fields must be key=value, got %r' % kv)
    k, v = kv.split('=', 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            continue
    return k, v


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='obs_report', description=__doc__.splitlines()[0])
    ap.add_argument('path', nargs='?', default=None,
                    help='run .jsonl file or obs dir '
                         '(default: $PADDLE_TPU_OBS_DIR, latest run)')
    ap.add_argument('--check', action='store_true',
                    help='validate records; exit 2 if any are malformed')
    ap.add_argument('--merge', action='store_true',
                    help='when path is a dir, merge ALL run files instead '
                         'of only the newest')
    ap.add_argument('--emit', metavar='NAME', default=None,
                    help='append one event record named NAME (fields from '
                         'remaining key=value args) to the current run log')
    ap.add_argument('fields', nargs='*', metavar='key=value',
                    help='fields for --emit')
    args = ap.parse_args(argv)

    obs = load_obs()

    if args.emit:
        if not obs.enabled():
            print('obs_report --emit: PADDLE_TPU_OBS_DIR not set; '
                  'nothing recorded', file=sys.stderr)
            return 0
        # argparse slots the first key=value into `path`; reclaim it
        kvs = ([args.path] if args.path else []) + args.fields
        obs.event(args.emit, **dict(_parse_field(kv) for kv in kvs))
        return 0
    if args.fields:
        ap.error('positional key=value fields are only valid with --emit')

    path = args.path
    if path is None:
        path = os.environ.get(obs.ENV_DIR)
        if not path:
            print('obs_report: no path given and PADDLE_TPU_OBS_DIR is '
                  'not set', file=sys.stderr)
            return 1
    if not os.path.exists(path):
        print('obs_report: %r does not exist' % path, file=sys.stderr)
        return 1
    if os.path.isdir(path) and obs.report.latest_run(path) is None:
        print('obs_report: no run-*.jsonl files under %r' % path,
              file=sys.stderr)
        return 1

    events, errors, files = obs.report.collect_events(path,
                                                      merge_dir=args.merge)
    for where, why, raw in errors:
        print('MALFORMED %s: %s   %s' % (where, why, raw), file=sys.stderr)
    if args.check:
        if errors:
            print('obs_report --check: %d malformed record(s) in %s'
                  % (len(errors), ', '.join(os.path.basename(f)
                                            for f in files)),
                  file=sys.stderr)
            return 2
        print('obs_report --check: %d record(s) OK in %s'
              % (len(events), ', '.join(os.path.basename(f)
                                        for f in files)))
        return 0

    print(obs.report.summarize(events))
    return 0


if __name__ == '__main__':
    sys.exit(main())
