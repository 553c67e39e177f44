"""Device time per step in the windowed mixers: every event whose Fluid op
scope lies under the name scope `window_attention` (the four projections,
rotary, the transposes and the flash kernels on their banded grid),
forward and backward and what a region runs again, from the trace.
Nothing to read where the program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'window_attention')
    return None if s is None else 1e3 * s
