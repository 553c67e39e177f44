"""Held rows over expected rows of a held share's expert layers, mean over
the layers and the TRACED steps: the load under which `moe_ms`,
`grouped_matmul_ms`, `moe_roofline` and `mtp_ms` of the same line were
read (the program's device counter, `fields['device']` of its
`executor.step` records)."""


def read(reading):
    from chipbench.harness import catalog
    counters = catalog.load_module(reading['cell']['root'], 'layers',
                                   'step_counter_window')
    return counters.rows_x(reading, 'traced')
