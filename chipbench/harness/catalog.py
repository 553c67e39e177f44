"""Finds everything by name. A cell is `workloads/<cell>.json`; it names a
configuration (`configs/<config>.json`), a traffic mix
(`traffic/<traffic>.json`, whose `kind` names the generator
`traffic/<kind>.py`) and a loop (`loops/<loop>.py`); the configuration
names its builder, reference and FLOP function. A per-layer metric is
its reader `layers/<metric>.py` (its layer, unit and cells stand in
BENCHMARK.json). Modules are
loaded from their files under `root`, so a directory that adds files to a
copy of this one is a complete benchmark.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root, kind, name):
    path = os.path.join(root, kind, name + '.py')
    spec = importlib.util.spec_from_file_location(
        'chipbench_%s_%s' % (kind, name), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _merge(base, override):
    """`base` with `override` laid over it, dict by dict; a null takes the
    key away."""
    out = dict(base)
    for k, v in (override or {}).items():
        if v is None:
            out.pop(k, None)
        else:
            out[k] = _merge(out[k], v) if isinstance(v, dict) \
                and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, root=ROOT, overrides=None):
    """The cell with everything it names resolved. `overrides`
    ({'config': {...}, 'traffic': {...}}) is how the CPU tests shrink a
    cell to a toy width; the command line has no such door."""
    overrides = overrides or {}
    cell = _json(root, 'workloads', name + '.json')
    config = _merge(_json(root, 'configs', cell['config'] + '.json'),
                    overrides.get('config'))
    traffic = _merge(_json(root, 'traffic', cell['traffic'] + '.json'),
                     overrides.get('traffic'))
    return {
        'name': name, 'root': root, 'cell': cell, 'config': config,
        'traffic': traffic,
        'builder': load_module(root, 'builders', config['builder']),
        'reference': load_module(root, 'references', config['reference']),
        'flops': load_module(root, 'flops', config['flops']),
        'generator': load_module(root, 'traffic', traffic['kind']),
        'loop': load_module(root, 'loops', cell['loop']),
    }


def benchmark_json(root=ROOT):
    return _json(os.path.dirname(root), 'BENCHMARK.json')


def metrics_of(cell_name, section, root=ROOT, spec=None):
    """The metrics of BENCHMARK.json's `section` that this cell reports."""
    spec = spec or benchmark_json(root)
    return [m for m in spec[section]
            if 'workloads' not in m or cell_name in m['workloads']]


def load_reader(metric, root=ROOT):
    """The reader of a per-layer metric. A metric named `<reader>.<tag>` is
    read by layers/<reader>.py: one reader serves the same quantity where
    it moves another end-to-end metric (`mfu_pct` moves `tokens_per_s`,
    `mfu_pct.img` moves `images_per_s`; an entry has one `moves`)."""
    return load_module(root, 'layers', metric.split('.')[0]).read
