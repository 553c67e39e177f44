"""Not a metric: what the readers of a model's name scopes share.

A program that stamps its ops with the scopes they were built in
(`fluid.name_scope`, PR 32) enters them around each op's own
`<type>_<index>` scope, so an HLO instruction's op_name reads
`.../mtp/latent_attention/mul_17/...`, each element wrapped by the
transforms it went through (`transpose(jvp(latent_attention))`). The
trace's reduction keys device time by the innermost op scope
(`fluid_scope_s`, harness/trace.py); this file finds, in the step's
optimized HLO (`reading['hlo']`), which op scopes lie under a name and
adds their time up. A reader loads it as

    catalog.load_module(reading['cell']['root'], 'layers',
                        'name_scope_window')

Where the program names no such scope (a program from before PR 32, or a
model without it) `seconds_per_step` returns None and the reader leaves
its metric out.
"""
import re

from chipbench.harness import scopes


def op_scopes_under(hlo_text, name):
    """{'<type>_<index>'} of the Fluid op scopes whose instructions' op_name
    has `name` as an element of its path."""
    element = re.compile(r'(?:^|[/(])%s(?=[/)]|$)' % re.escape(name))
    found = set()
    for op_name in scopes.instruction_scopes(hlo_text).values():
        if element.search(op_name):
            scope = scopes.scope_of(op_name)
            if scope:
                found.add('%s_%d' % scope)
    return found


def seconds_per_step(reading, name):
    """Device seconds a step of every op scope under `name`, forward and
    backward, or None where there is no trace or no such scope."""
    red = reading['trace']
    if red is None or not reading.get('hlo'):
        return None
    under = op_scopes_under(reading['hlo'], name)
    total = sum(s for scope, s in red['fluid_scope_s'].items()
                if scope in under)
    return total / red['steps'] if total else None
