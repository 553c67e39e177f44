"""Not a metric: what the readers of a step's device counters share.

What a step does that depends on its DATA, the program counts inside the
compiled step and, while its observability directory is set (a traced
run sets it before anything runs), writes onto the step's own
`executor.step` span record as `fields['device']`: a list with one entry
for each op that keeps a counter, in op order. Its one client so far is
a `moe_mlp` op that holds a share of its experts (`experts_held`):

    {'op': 'moe_mlp_<index>',  the op's scope in the module's op_name
     'rows': the step's assignments that landed on the held experts,
     'expected': tokens x top_k x held / routed experts,
     'cap': the rows the layer's compact layout holds, or None where the
            layer keeps every row whatever the router does,
     'way': 'compact' | 'blocks', the path the layer took on the device
            this step, by the integers its `lax.cond` compared}

The record's `t0`/`t1` are on the clock the profiler's `executor.step`
annotation shares, so the last TRACED_STEPS records of the training
step's key are the traced steps and the `attempted` before them are the
window, as `span_window.select` has it; this file takes the window from
there, checked against the registry as it is there. A reader loads this
file as

    catalog.load_module(reading['cell']['root'], 'layers',
                        'step_counter_window')

Where the program keeps no such field (a program from before PR 35, or
one whose step holds no share), or the window's records cannot be shown
to be the window's, select() returns None and the reader leaves its
metric out: a missing number, never a wrong one.
"""
from chipbench.harness import catalog
from chipbench.harness.cell import TRACED_STEPS


def select(reading):
    """{'window': [a step's entries, ...], 'traced': [...]}: the `device`
    lists of the window's steps and of the traced steps, or None."""
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    sel = spans.select(reading)
    if sel is None:
        return None
    mine = [r for r in sel['spans'] if r['name'] == spans.STEP
            and r['fields'].get('key') == sel['key']]
    steps = {'window': sel['steps'], 'traced': mine[-TRACED_STEPS:]}
    out = {part: [r['fields'].get('device') for r in records]
           for part, records in steps.items()}
    if not all(entries for part in out.values() for entries in part):
        return None
    return out


def _ratios(steps):
    return [e['rows'] / e['expected'] for entries in steps for e in entries]


def rows_x(reading, part):
    """Held rows over expected rows, mean over the layer-steps of `part`
    ('window' | 'traced'); None where there is nothing to read."""
    sel = select(reading)
    if sel is None:
        return None
    x = _ratios(sel[part])
    return sum(x) / len(x)


def rows_x_peak(reading):
    """The largest held rows over expected rows of any layer-step of the
    window and the traced steps."""
    sel = select(reading)
    return None if sel is None else max(_ratios(sel['window'] + sel['traced']))


def blocks_layers(reading, part):
    """Layer-steps that kept every row (`way` `blocks`), a step of `part`."""
    sel = select(reading)
    if sel is None:
        return None
    return sum(e['way'] == 'blocks' for entries in sel[part]
               for e in entries) / len(sel[part])
