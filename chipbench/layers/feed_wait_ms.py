"""Host time of the program's `executor.feed_wait` span, per step of the
window: the program's first act inside its blocking `executor.fetch` is
to wait for the arrays the step was fed, so this is what of the feed's
transfer the host's own dispatch did not hide, with no profiler running.
A program that overlaps the next batch's transfer with the step drives it
to zero; over the step time it is the most such a change can gain. None
where the program records no such span (a program from before PR 51).

Also what the readers of that span share: `waits()`.
"""
WAIT = 'executor.feed_wait'
FEED = 'executor.feed'


def load_spans(reading):
    from chipbench.harness import catalog
    return catalog.load_module(reading['cell']['root'], 'layers',
                               'span_window')


def waits(steps, below):
    """[(the step's `executor.feed` record or None, its `executor.feed_wait`
    record)] of the `executor.step` records `steps` that recorded the
    wait; `below` is span_window.select()'s {span id: children}."""
    out = []
    for step in steps:
        found, todo = {}, [step]
        while todo:
            for child in below.get(todo.pop()['span'], ()):
                found[child['name']] = child
                todo.append(child)
        if WAIT in found:
            out.append((found.get(FEED), found[WAIT]))
    return out


def read(reading):
    return load_spans(reading).per_step_ms(reading, WAIT)
