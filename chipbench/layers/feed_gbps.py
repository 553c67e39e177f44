"""The rate the host-to-device path gave the step's feed, in 1e9 bytes a
second: over the window's steps whose `executor.feed_wait` found the
arrays not yet landed (`ready` false), the span's `bytes` over the time
from the start of the same step's `executor.feed` (the transfer starts
inside it) to the end of the wait, the mean of the steps' rates. A lower
bound on the link: the conversion of the fed arrays on the host is in the
time. None where every step's feed had landed before the wait (a token
cell feeds kilobytes), or the program records no such span.
"""


def read(reading):
    from chipbench.harness import catalog
    wait = catalog.load_module(reading['cell']['root'], 'layers',
                               'feed_wait_ms')
    sel = wait.load_spans(reading).select(reading)
    if sel is None:
        return None
    rates = [w['fields']['bytes'] / (w['t1'] - feed['t0'])
             for feed, w in wait.waits(sel['steps'], sel['below'])
             if feed is not None and not w['fields']['ready']]
    return 1e-9 * sum(rates) / len(rates) if rates else None
