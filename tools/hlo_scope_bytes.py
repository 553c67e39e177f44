"""Counts what XLA's own instructions move inside a scope of an optimized
HLO text, by bytes: the account ISSUE 56 made by hand of the
`gated_delta_rule_<n>` scopes of `ling3flash_s8192`.

    JAX_PLATFORMS=cpu python tools/aot_cell.py --workload ling3flash_s8192
        --hlo step.hlo
    python tools/hlo_scope_bytes.py step.hlo [--scope 'gated_delta_rule_\\d+']
        [--top 12]

Of the ENTRY computation, every instruction whose `op_name` holds the
scope and that is no Mosaic call, no bitcast, tuple or tuple element, no
parameter or constant and no half of an async pair: the bytes of its
operands plus those of its result, from the shapes (a fusion reads each
operand once and writes its result once; what it keeps in registers is
inside it). Prints one JSON line: the instructions, their bytes, the same
by opcode and by the last part of `op_name`, the `--top` largest, and how
many instructions of the scope (fused computations included) are a
`reduce-window` or hold an `rsqrt`. Bytes over the HBM's peak are a floor
of the time, never a time: a compile is not a chip run.
"""
import argparse
import collections
import json
import re
import sys

_SIZES = {'pred': 1, 's8': 1, 'u8': 1, 's16': 2, 'u16': 2, 'bf16': 2,
          'f16': 2, 's32': 4, 'u32': 4, 'f32': 4, 's64': 8, 'u64': 8,
          'f64': 8}
_SHAPE = re.compile(r'\b(%s)\[([\d,]*)\]' % '|'.join(_SIZES))
_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$')
_SKIPPED = {'bitcast', 'tuple', 'get-tuple-element', 'parameter', 'constant',
            'optimization-barrier'}


def shape_bytes(text):
    """The bytes of every array shape written in `text` (a tuple's are its
    parts')."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(','):
            n *= int(d) if d else 1
        total += n * _SIZES[dtype]
    return total


def entry_lines(text):
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith('ENTRY '))
    end = next(i for i in range(start, len(lines)) if lines[i] == '}')
    return lines[start:end]


def account(text, scope):
    """(rows, marks): a row an instruction of the scope that moves bytes,
    (name, opcode, the last part of its op_name, bytes); marks the lines of
    the scope anywhere in the module that are a reduce-window or an
    rsqrt."""
    scope = re.compile(r'op_name="[^"]*(?:%s)[^"]*"' % scope)
    sizes, rows = {}, []
    for line in entry_lines(text)[1:]:      # parameters are lines too
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        sizes[name] = shape_bytes(shape)
        if not scope.search(line) or opcode in _SKIPPED \
                or opcode.endswith(('-start', '-done')) \
                or 'tpu_custom_call' in line:
            continue
        operands = re.findall(r'%([\w.\-]+)', rest.split('), ')[0])
        what = re.search(r'op_name="([^"]*)"', line).group(1).split('/')[-1]
        rows.append((name, opcode, what,
                     sizes[name] + sum(sizes.get(o, 0) for o in operands)))
    marks = collections.Counter()
    for line in text.splitlines():
        if scope.search(line) and 'tpu_custom_call' not in line:
            marks['reduce_window'] += ' reduce-window(' in line
            marks['rsqrt'] += ' rsqrt(' in line
    return rows, dict(marks)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    p.add_argument('hlo')
    p.add_argument('--scope', default=r'gated_delta_rule_\d+')
    p.add_argument('--top', type=int, default=12)
    args = p.parse_args(argv)
    with open(args.hlo) as f:
        rows, marks = account(f.read(), args.scope)
    by_opcode, by_what = collections.Counter(), collections.Counter()
    for _, opcode, what, n in rows:
        by_opcode[opcode] += n
        by_what[what] += n
    print(json.dumps({
        'scope': args.scope, 'instructions': len(rows),
        'gb': sum(r[3] for r in rows) / 1e9,
        'gb_by_opcode': {k: v / 1e9 for k, v in by_opcode.most_common()},
        'gb_by_op_name': {k: v / 1e9 for k, v in by_what.most_common()},
        'largest': [(r[0], r[2], r[3] / 1e9) for r in
                    sorted(rows, key=lambda r: -r[3])[:args.top]],
        'in_scope': marks}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
