"""The largest held rows over expected rows of any single layer-step of
the window and the traced steps: how far the cell is from the slack at
which a layer leaves the compact path (`cap` over `expected`, 10)."""


def read(reading):
    from chipbench.harness import catalog
    counters = catalog.load_module(reading['cell']['root'], 'layers',
                                   'step_counter_window')
    return counters.rows_x_peak(reading)
