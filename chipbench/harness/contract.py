"""What BENCHMARK.json and the files it names are held to, as a function.

check(spec, repo_root, bench_root) raises ContractError, with the offending
entry in its message, where `spec` (BENCHMARK.json, parsed) or a file it
names under `repo_root` breaks the driver's contract or this benchmark's
own rules. tier-1 runs it on the repository's BENCHMARK.json, and on a
copy to which a configuration was added as files (the dry additions of
tests/test_chipbench/test_chipbench_cells.py): a PR that adds a cell or a
configuration passes it without editing a file that is here.

The list of cells is whatever `workloads` names. What is held about
particular cells is that the ones accepted so far are still there
(HELD_CELLS, HELD_CONFIGS): only a `benchmark` PR that retires one touches
those lists, an addition never does.
"""
import json
import os
import re

from chipbench.harness import catalog

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
FILE = re.compile(r'^[A-Za-z0-9_.\-]+$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
KEYS = {'command', 'paths', 'run_seconds', 'configs', 'workloads',
        'end_to_end', 'per_layer'}
TOY_DIR = os.path.join('tests', 'test_chipbench', 'toy')

# accepted cells and configurations (PR 22): {cell: (config, traffic, chips)}
HELD_CELLS = {
    'tfm_s1024': ('transformer_base', 'seq2seq_b16_s1024', 1),
    'tfm_s256': ('transformer_base', 'seq2seq_b64_s256', 1),
    'resnet50_b256': ('resnet50', 'host_images_b256', 1),
    'tfm_s1024_dp4': ('transformer_base', 'seq2seq_b64_s1024_dp4', 4),
}
HELD_CONFIGS = ('transformer_base', 'resnet50')

# The model-configs guide, section 4: "Cells therefore use the published
# widths: hidden size, sizes of heads, feed-forward and expert widths,
# experts per token, window and state sizes. No width is ever cut." The
# driver refuses the same: a hidden, intermediate, latent, state or
# projection size, a key that ends in `_dim` or `_rank`, a head size, an
# expansion factor, the number of experts per token. Depth, and how many
# heads, experts or vocabulary rows are held here, may be cut.
WIDTH_STEMS = ('hidden_size', 'intermediate', 'latent', 'state', 'proj',
               'head_dim', 'head_size', 'expand', 'expansion', 'per_tok',
               'top_k', 'window', 'width', 'd_model', 'd_inner', 'd_ff',
               'd_key', 'd_value')
WIDTH_ENDINGS = ('_dim', '_rank')


class ContractError(ValueError):
    pass


def _hold(cond, what, *entry):
    if not cond:
        raise ContractError(what + ''.join(': %r' % (e,) for e in entry))


def names_a_width(key):
    k = key.lower()
    return k.endswith(WIDTH_ENDINGS) or any(s in k for s in WIDTH_STEMS)


def _one_line(text, most=200):
    return isinstance(text, str) and 1 <= len(text) <= most \
        and '\n' not in text and '\t' not in text


def check_reduced(entry, held):
    """`reduced` of a configuration's BENCHMARK.json `entry` against the
    configuration's file `held`. An entry of `reduced` is a key (the driver
    takes it as a name, so it holds no space); the file's `reduced_from`
    states the source's value of each, and the value here, in `model` (or
    absent from it), differs from it."""
    reduced = entry['reduced']
    _hold(isinstance(reduced, list) and len(reduced) <= 16
          and len(set(map(str, reduced))) == len(reduced),
          'reduced is a list of at most 16 distinct keys', entry)
    _hold(held.get('reduced') == reduced,
          "the file's reduced is not BENCHMARK.json's", entry['name'],
          held.get('reduced'), reduced)
    for key in reduced:
        _hold(isinstance(key, str) and NAME.match(key),
              'an entry of reduced is a key of the model, a name', key)
        _hold(not names_a_width(key),
              'reduced may not name a width (model-configs guide, '
              'section 4: no width is ever cut)', entry['name'], key)
    if not reduced:
        return
    source = held.get('reduced_from')
    _hold(isinstance(source, dict) and set(source) == set(reduced),
          "the file's reduced_from gives the source's value of each key of "
          'reduced and of no other', entry['name'], source)
    for key in reduced:
        _hold(held['model'].get(key) != source[key],
              "a reduced key's value here equals the source's",
              entry['name'], key, source[key])
    _hold(isinstance(held.get('deployment'), str)
          and held['deployment'].strip(),
          'a cut configuration states the deployment it stands for',
          entry['name'])


def check_spec(spec):
    """BENCHMARK.json alone: keys, names, limits, the four-chip share, the
    time a full check of 24 cells takes, which metric is where."""
    _hold(set(spec) == KEYS, 'top-level keys', sorted(spec))
    _hold(len(json.dumps(spec, indent=1)) < 65536, 'over 64 KiB')
    _hold(spec['paths'] == ['chipbench', 'tests/test_chipbench'], 'paths',
          spec['paths'])
    _hold(spec['command'][:2] == ['python3', 'chipbench/run.py'], 'command',
          spec['command'])
    n = len(spec['workloads'])
    _hold(2 <= n <= 24, 'cells', n)
    seconds = spec['run_seconds']
    _hold(isinstance(seconds, int) and 1 <= seconds <= 51, 'run_seconds',
          seconds)
    # the full check, with all 24 cells a later PR may add, fits
    full = (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200
    _hold(full <= 43200, 'a full check of 24 cells takes', full)

    configs = {c['name']: c for c in spec['configs']}
    _hold(len(configs) == len(spec['configs']) <= 24, 'configuration names')
    files = [c['file'] for c in spec['configs']]
    _hold(len(set(files)) == len(files), 'one file a configuration', files)
    for c in spec['configs']:
        _hold(set(c) == {'name', 'source', 'file', 'reduced', 'why'},
              'keys of a configuration', c)
        _hold(NAME.match(c['name']) and c['file'].startswith('chipbench/'),
              'name and file of a configuration', c)
        _hold(_one_line(c['source']) and _one_line(c['why']),
              'source and why have 1 to 200 characters on one line', c)

    names, pairs = set(), set()
    for w in spec['workloads']:
        _hold(set(w) == {'name', 'config', 'traffic', 'chips', 'why'},
              'keys of a cell', w)
        _hold(NAME.match(w['name']) and NAME.match(w['traffic']),
              'names of a cell', w)
        _hold(w['config'] in configs and w['chips'] in (1, 4),
              'configuration and chips of a cell', w)
        _hold(_one_line(w['why']), 'why of a cell', w)
        pairs.add((w['config'], w['traffic']))
        names.add(w['name'])
    _hold(len(pairs) == n and len(names) == n,
          'a cell name and a pair of configuration and traffic appear once')
    _hold({w['config'] for w in spec['workloads']} == set(configs),
          'every configuration is used by some cell')
    four = sum(w['chips'] == 4 for w in spec['workloads'])
    _hold(four <= max(1, n // 4), 'cells on four chips', four, n)

    e2e = {m['name']: m for m in spec['end_to_end']}
    _hold('setup_s' in e2e and e2e['setup_s']['bound'] <= 0.1, 'setup_s')
    metrics = spec['end_to_end'] + spec['per_layer']
    _hold(len({m['name'] for m in metrics}) == len(metrics),
          'metric names are distinct')
    for m in spec['end_to_end']:
        _hold(set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                         'source'}, 'keys of a metric', m)
        _hold(0.01 <= m['bound'] <= 0.1, 'bound', m)
        _hold(m['source'] in ('host_clock', 'device_trace'), 'source', m)
    for m in spec['per_layer']:
        _hold(set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                         'layer', 'moves'},
              'keys of a metric', m)
        _hold(m['moves'] in e2e and m['source'] in SOURCES,
              'moves and source', m)
        _hold(_one_line(m['layer']), 'layer', m)
        # reported only where the metric it moves is
        where = set(m.get('workloads', names))
        _hold(where <= set(e2e[m['moves']].get('workloads', names)),
              'a per-layer metric is reported where the metric it moves '
              'is not', m)
    for m in metrics:
        _hold(NAME.match(m['name']) and UNIT.match(m['unit']),
              'name and unit of a metric', m)
        _hold(m['better'] in ('lower', 'higher'), 'better', m)
        _hold(set(m.get('workloads', [])) <= names,
              'a metric lists a cell that is not there', m)
    for w in names:
        has = [m['name'] for m in spec['end_to_end']
               if w in m.get('workloads', names)]
        _hold('setup_s' in has and len(has) >= 2,
              'a cell reports setup_s and another end-to-end metric', w, has)
        _hold(any(w in m.get('workloads', names) for m in spec['per_layer']),
              'a cell reports a per-layer metric', w)

    for name, (config, traffic, chips) in HELD_CELLS.items():
        got = [w for w in spec['workloads'] if w['name'] == name]
        _hold(got and (got[0]['config'], got[0]['traffic'], got[0]['chips'])
              == (config, traffic, chips), 'an accepted cell is gone or '
              'changed', name, got)
    _hold(set(HELD_CONFIGS) <= set(configs),
          'an accepted configuration is gone', HELD_CONFIGS)


def check_files(spec, repo_root, bench_root):
    """Everything BENCHMARK.json names is found by that name under
    `bench_root` (a chipbench directory) and `repo_root` (its checkout)."""
    for c in spec['configs']:
        path = os.path.join(repo_root, c['file'])
        _hold(os.path.exists(path), 'no file of the configuration', path)
        with open(path) as f:
            held = json.load(f)
        _hold(held['source'] == c['source'], "the file's source", c['name'])
        check_reduced(c, held)
        toy = os.path.join(repo_root, TOY_DIR, c['name'] + '.json')
        _hold(os.path.exists(toy), "no toy width for the configuration's "
              'CPU tests: missing file', toy)
    for w in spec['workloads']:
        cell = catalog.load_cell(w['name'], root=bench_root)
        for key in ('config', 'traffic', 'chips', 'why'):
            _hold(cell['cell'][key] == w[key],
                  "the cell's file and BENCHMARK.json differ", w['name'], key)
        # the rate is named by the generator's unit of work
        got = {m['name'] for m in catalog.metrics_of(
            w['name'], 'end_to_end', bench_root, spec)}
        want = {cell['generator'].UNIT + '_per_s', 'setup_s'}
        _hold(got == want, "a cell's end-to-end metrics", w['name'], got,
              want)
    for m in spec['per_layer']:
        _hold(callable(catalog.load_reader(m['name'], bench_root)),
              'no reader', m['name'])
    for path in spec['paths']:
        for d, _, files in os.walk(os.path.join(repo_root, path)):
            if '__pycache__' in d:
                continue
            for f in files:
                _hold(FILE.match(f), 'a file is named from the characters '
                      'of a name', os.path.join(d, f))


def check(spec, repo_root, bench_root):
    check_spec(spec)
    check_files(spec, repo_root, bench_root)
