"""Not a metric: what the readers of the program's completed spans share.

With its observability directory set (a traced run sets it before
anything runs) the program keeps every completed span in memory, with its
start and end on one clock, its parent and its fields, and hands them out
through `paddle_tpu.obs.completed_spans()`. The readers take them from
there, never from the run log's file. A reader loads this file as

    catalog.load_module(reading['cell']['root'], 'layers', 'span_window')

Which records are the window's. Only the loop calls `exe.run` on the
training Program once the first step is done: the first step, the
warm-up, the window, then the traced steps (`lowered_hlo` opens no
`executor.step`). So of the `executor.step` records that carry the key of
the last one, the last TRACED_STEPS are the traced steps and the
`reading['window']['attempted']` before them are the window. The
registry's `executor.step` histogram saw the same spans over the same
window (`reading['registry']`): a selection that is off by one step does
not add up to its sum. Where the program keeps no spans (a program from
before PR 23), has dropped some, or the selection does not fit, select()
returns None and the reader leaves its metric out: a missing number, never
a wrong one.
"""
import collections

from chipbench.harness.cell import TRACED_STEPS

STEP = 'executor.step'


def completed_spans():
    """The program's span records, oldest first; None where it keeps none
    or its bounded buffer has pushed some out (it then leads with a
    `spans.dropped` record)."""
    from paddle_tpu import obs
    accessor = getattr(obs, 'completed_spans', None)
    if accessor is None:
        return None
    records = accessor()
    if records and records[0].get('name') == 'spans.dropped':
        return None
    return [r for r in records if r.get('kind') == 'span']


def select(reading):
    """{'key': the training step's cache key, 'steps': the window's
    `executor.step` records, 'spans': every record, 'below': {span id:
    records whose parent it is}}, or None."""
    spans = completed_spans()
    steps = [r for r in spans or () if r['name'] == STEP]
    n = reading['window']['attempted']
    if not steps or n <= 0:
        return None
    key = steps[-1]['fields'].get('key')
    mine = [r for r in steps if r['fields'].get('key') == key]
    if key is None or len(mine) < n + TRACED_STEPS:
        return None
    window = mine[-(n + TRACED_STEPS):-TRACED_STEPS]
    seen = reading['registry'][STEP]
    total = sum(r['dur_s'] for r in window)
    if seen['count'] != n or abs(total - seen['sum']) > 1e-6 * total:
        return None
    below = collections.defaultdict(list)
    for r in spans:
        below[r['parent']].append(r)
    return {'key': key, 'steps': window, 'spans': spans, 'below': below}


def _under(step, below):
    out, todo = [step], [step]
    while todo:
        for child in below.get(todo.pop()['span'], ()):
            out.append(child)
            todo.append(child)
    return out


def per_step_ms(reading, name, own=False):
    """Milliseconds a step of the window spent in spans called `name`, at
    any depth below its `executor.step` (or in that span itself): the
    mean over the window's steps. `own` takes out of each such span what
    its child spans cover, which leaves its self time. None where no such
    span was recorded in the window."""
    sel = select(reading)
    if sel is None:
        return None
    seconds, found = 0.0, 0
    for step in sel['steps']:
        for r in _under(step, sel['below']):
            if r['name'] != name:
                continue
            found += 1
            seconds += r['dur_s']
            if own:
                seconds -= sum(c['dur_s']
                               for c in sel['below'].get(r['span'], ()))
    return 1e3 * seconds / len(sel['steps']) if found else None


def first_call_s(reading, names):
    """Seconds of the records called one of `names` that carry the
    training step's key: the parts of its first call. None where the
    first call recorded no parts."""
    sel = select(reading)
    if sel is None:
        return None
    mine = [r for r in sel['spans'] if r['name'] in names
            and r['fields'].get('key') == sel['key']]
    if not any(r['name'].startswith('executor.first_call.') for r in mine):
        return None
    return sum(r['dur_s'] for r in mine)
