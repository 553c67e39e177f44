"""The short-convolution mixers' share of their roofline: the least time
the chip needs for the two projections' FLOPs at its peak PLUS the
gate-and-convolution stage's bytes at its bandwidth (flops/<config>.py
`shortconv_cost()`: the stage lies between the projections and is no
matmul, so the two times add), over the measured time under the name scope
`short_conv_mixer` (`shortconv_ms`), in percent: the mechanism against
what the mathematics requires, whatever implements it."""


def read(reading):
    from chipbench.harness import catalog
    cell, peaks = reading['cell'], reading['peaks']
    cost = getattr(cell['flops'], 'shortconv_cost', None)
    if cost is None or peaks is None:
        return None
    window = catalog.load_module(cell['root'], 'layers', 'name_scope_window')
    s = window.seconds_per_step(reading, 'short_conv_mixer')
    if s is None:
        return None
    flops, nbytes = cost(cell['config'], cell['traffic'], reading['chips'])
    return 100.0 * (flops / peaks['bf16_flops_per_s']
                    + nbytes / peaks['hbm_bytes_per_s']) / s
