"""The windowed mixers' share of their roofline: the least time the chip
needs for their FLOPs and bytes (flops/<config>.py
`window_attention_cost()`: the projections and the scores the window's
mask ADMITS, not the blocks a kernel visits), over the measured time of
the scopes under `window_attention` (`swa_ms`), in percent: the mechanism
against what the mathematics requires, whatever implements it and
whatever it runs twice."""
from chipbench.harness import peaks


def read(reading):
    from chipbench.harness import catalog
    cell = reading['cell']
    cost = getattr(cell['flops'], 'window_attention_cost', None)
    if cost is None or reading['peaks'] is None:
        return None
    window = catalog.load_module(cell['root'], 'layers', 'name_scope_window')
    s = window.seconds_per_step(reading, 'window_attention')
    if s is None:
        return None
    least_s, _ = peaks.roofline(
        cost(cell['config'], cell['traffic'], reading['chips']),
        reading['peaks'])
    return 100.0 * least_s / s
