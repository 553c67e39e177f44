"""The state-space scan's share of its roofline: the least time the chip
needs for the recurrence's FLOPs and the bytes of its operands
(flops/<config>.py `ssd_cost()`), over the measured time of the `ssd_scan`
scopes (`ssd_ms`), in percent: the mechanism against what the mathematics
requires, whatever implements it."""
from chipbench.harness import peaks


def read(reading):
    red, cell = reading['trace'], reading['cell']
    cost = getattr(cell['flops'], 'ssd_cost', None)
    if red is None or cost is None or reading['peaks'] is None \
            or not red['fluid_op_s'].get('ssd_scan'):
        return None
    least_s, _ = peaks.roofline(
        cost(cell['config'], cell['traffic'], reading['chips']),
        reading['peaks'])
    return 100.0 * least_s / (red['fluid_op_s']['ssd_scan'] / red['steps'])
