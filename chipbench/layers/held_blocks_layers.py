"""Expert layers a step that kept every tokens x top_k row
(`_held_blocks`) in the TRACED steps, by the program's device counter.
`moe_ms` of a line compares with another line's only where this is
equal."""


def read(reading):
    from chipbench.harness import catalog
    counters = catalog.load_module(reading['cell']['root'], 'layers',
                                   'step_counter_window')
    return counters.blocks_layers(reading, 'traced')
