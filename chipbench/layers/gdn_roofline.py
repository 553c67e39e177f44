"""The gated delta rule's share of its roofline: the least time the chip
needs for the recurrence's FLOPs and the bytes of its operands
(flops/<config>.py `delta_rule_cost()`), over the measured time of the
`gated_delta_rule` scopes (`gdn_ms`), in percent: the mechanism against
what the mathematics requires, whatever implements it."""
from chipbench.harness import peaks


def read(reading):
    red, cell = reading['trace'], reading['cell']
    cost = getattr(cell['flops'], 'delta_rule_cost', None)
    if red is None or cost is None or reading['peaks'] is None \
            or not red['fluid_op_s'].get('gated_delta_rule'):
        return None
    least_s, _ = peaks.roofline(
        cost(cell['config'], cell['traffic'], reading['chips']),
        reading['peaks'])
    return 100.0 * least_s / (red['fluid_op_s']['gated_delta_rule']
                              / red['steps'])
