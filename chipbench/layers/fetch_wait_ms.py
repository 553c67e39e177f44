"""Self time of the program's `executor.fetch` span, per step of the
window, where its first child is `executor.feed_wait`: with the wait for
the step's input taken out, the blocking fetch is the wait for the
device's compute, the loss's copy to the host and, in a traced run, the
read of the step's device counters. So `host_dispatch_ms` +
`feed_wait_ms` + `fetch_wait_ms` is the step, and this stands beside the
trace's busy time a step. None where the program records no
`executor.feed_wait`: the fetch's self time is then both waits in one.
"""


def read(reading):
    from chipbench.harness import catalog
    wait = catalog.load_module(reading['cell']['root'], 'layers',
                               'feed_wait_ms')
    spans = wait.load_spans(reading)
    if spans.per_step_ms(reading, wait.WAIT) is None:
        return None
    return spans.per_step_ms(reading, 'executor.fetch', own=True)
