"""Model FLOP/s utilization of the device while it is busy: the forward
and backward FLOPs the static shapes require of one chip in a step (pads
included, flash recomputation not; flops/<config>.py) over the device's
busy time per step, from the trace (union of device operations, mean of
the chips), over the published bf16 peak. The host's share is left out
on purpose: `device_idle_pct` holds it, and the conventional MFU over
the wall clock, which only repeats the rate, is `mfu_wall_pct` on the
summary line."""


def read(reading):
    red, peaks = reading['trace'], reading['peaks']
    if red is None or not red['busy_s'] or peaks is None:
        return None
    per_chip = reading['step_flops'] / reading['chips']
    busy_per_step = red['busy_s'] / red['steps']
    return 100.0 * per_chip / busy_per_step / peaks['bf16_flops_per_s']
