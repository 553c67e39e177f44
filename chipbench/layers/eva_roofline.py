"""The EVA mixers' share of their roofline: the least time the chip needs
for the mixers' FLOPs and bytes (flops/<config>.py `eva_cost()`: the
projections, both sets' admitted pairs, the pooling's and the merge's
bytes), over the measured time of the scopes under `eva_mixer`
(`eva_ms`), in percent: the mechanism against what the mathematics
requires, whatever implements it and whatever it runs twice."""
from chipbench.harness import peaks


def read(reading):
    from chipbench.harness import catalog
    cell = reading['cell']
    cost = getattr(cell['flops'], 'eva_cost', None)
    if cost is None or reading['peaks'] is None:
        return None
    window = catalog.load_module(cell['root'], 'layers', 'name_scope_window')
    s = window.seconds_per_step(reading, 'eva_mixer')
    if s is None:
        return None
    least_s, _ = peaks.roofline(
        cost(cell['config'], cell['traffic'], reading['chips']),
        reading['peaks'])
    return 100.0 * least_s / s
