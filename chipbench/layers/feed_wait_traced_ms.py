"""Host time of the program's `executor.feed_wait` span, per step of the
TRACED steps: the last TRACED_STEPS `executor.step` records of the
training step's key, which ran under the profiler. Beside `feed_wait_ms`
it says what the profiler does to the feed's transfer, so that nobody
takes the traced steps' idle share or the `idle_gaps` of a cell with a
large feed for the window's. None where the program records no such span.
"""
from chipbench.harness.cell import TRACED_STEPS


def read(reading):
    from chipbench.harness import catalog
    wait = catalog.load_module(reading['cell']['root'], 'layers',
                               'feed_wait_ms')
    spans = wait.load_spans(reading)
    sel = spans.select(reading)
    if sel is None:
        return None
    traced = [r for r in sel['spans'] if r['name'] == spans.STEP
              and r['fields'].get('key') == sel['key']][-TRACED_STEPS:]
    found = wait.waits(traced, sel['below'])
    if not found:
        return None
    return 1e3 * sum(w['dur_s'] for _, w in found) / len(traced)
