"""The expert layer's share of its roofline: the least time the chip needs
for the ACTIVE experts' FLOPs and the bytes of their weights and rows
(flops/<config>.py `expert_cost()`), over the measured time of the
`moe_mlp` scopes (`moe_ms`), in percent: the whole mechanism against what
the mathematics requires, whatever implements it."""
from chipbench.harness import peaks


def read(reading):
    red, cell = reading['trace'], reading['cell']
    cost = getattr(cell['flops'], 'expert_cost', None)
    if red is None or cost is None or reading['peaks'] is None \
            or not red['fluid_op_s'].get('moe_mlp'):
        return None
    least_s, _ = peaks.roofline(
        cost(cell['config'], cell['traffic'], reading['chips']),
        reading['peaks'])
    return 100.0 * least_s / (red['fluid_op_s']['moe_mlp'] / red['steps'])
