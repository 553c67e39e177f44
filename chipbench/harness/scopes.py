"""From a device event to the Fluid op it belongs to.

The Executor stamps every lowered Fluid op with a named scope
`<op_type>_<index>` (lowering.run_op), which XLA keeps in each HLO
instruction's `op_name` metadata, through fusion. A device event in the
profiler's trace carries the instruction's name; `instruction_scopes`
reads the optimized HLO text (`exe.lowered_hlo(optimized=True)`) into
{instruction name: op_name}, and `scope_of` takes the innermost scope out
of an op_name path. The parser is a copy of
paddle_tpu/fluid/profiler.py:_scope_of, kept here so that the yardstick
does not move with the program.
"""
import re

_SCOPE_RE = re.compile(r'(?:^|[/(])([A-Za-z][A-Za-z0-9_]*?)_(\d+)(?=[/)]|$)')
_JIT_RE = re.compile(r'jit\(([A-Za-z_][A-Za-z0-9_]*)\)')
_INSTR_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?op_name="([^"]*)"')

COLLECTIVES = ('all-gather', 'all-reduce', 'reduce-scatter', 'all-to-all',
               'collective-permute')


def scope_of(op_name):
    """Innermost `<fluid_op_type>_<index>` scope of an HLO op_name path:
    'jit(step)/jvp(mul_3)/dot_general' -> ('mul', 3); None if none."""
    best = None
    for m in _SCOPE_RE.finditer(op_name):
        best = (m.group(1), int(m.group(2)))
    return best


def callee_of(op_name):
    """The function a kernel's call was made in, where it is a jitted one
    of its own: the innermost `jit(<name>)` of the op_name path after the
    step's own (`jit(step)/moe_mlp_9/transpose(jvp(jit(tgmm)))/pallas_call`
    -> 'tgmm': jax's megablox pair is `gmm` and `tgmm`); '' for a call a
    rule makes itself (`jit(step)/jvp(flash_attention_4)/pallas_call`)."""
    found = _JIT_RE.findall(op_name.partition('/')[2])
    return found[-1] if found else ''


def instruction_scopes(hlo_text):
    """{HLO instruction name: op_name metadata} of an HLO module's text.
    Instructions of every computation are listed, so both a fusion and
    the instructions fused into it resolve."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def collective_counts(hlo_text):
    """{collective: instructions in the compiled module}; an async pair
    (`-start` / `-done`) counts once. As chip_smoke._custom_call_shapes
    counts them."""
    return {op: len(re.findall(r'\s%s(?:-start)?\(' % op, hlo_text))
            for op in COLLECTIVES}


def is_collective(name):
    base = name.lstrip('%')
    return any(base.startswith(c) for c in COLLECTIVES)
