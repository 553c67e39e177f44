"""The share of a step in which device 0 ran no operation: 1 minus the
trace's busy time per step (union of device-op intervals over the traced
steps) over the median step time of the measured window, which ran
without the profiler in the same process. The busy time of a step repeats
to four digits whatever the host does; the length of the traced steps
does not: under the profiler a 154 MB feed took 0.55 s and not 0.045 s
in every warm run (chip, PR 22), so the traced window's own idle share
(`busy_s` / `window_s` on the last line, `traced_idle_pct` on the summary
line) read 82% where the steps that were measured idle 24%."""
import statistics


def read(reading):
    red, step_s = reading['trace'], reading['window']['step_s']
    if red is None or not step_s:
        return None
    busy_per_step = red['busy0_s'] / red['steps']
    return 100.0 * (1.0 - busy_per_step / statistics.median(step_s))
