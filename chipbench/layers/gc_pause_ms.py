"""Milliseconds a step of the window lost to Python's full garbage
collections: the part of the program's `host.gc` spans (generation 2)
that falls inside the window's `executor.step` spans, over its steps. The
pause lies inside whatever span it interrupted, so this is a share of the
other step metrics and not a further term of their sum. 0 where no full
collection fell into the window."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    sel = spans.select(reading)
    if sel is None:
        return None
    pauses = [r for r in sel['spans'] if r['name'] == 'host.gc']
    inside = sum(max(0.0, min(g['t1'], s['t1']) - max(g['t0'], s['t0']))
                 for s in sel['steps'] for g in pauses)
    return 1e3 * inside / len(sel['steps'])
