"""Expert layers a step that kept every tokens x top_k row
(`_held_blocks`) in the traced run's WINDOW: whether the window the rate
comes from saw the fallback at all."""


def read(reading):
    from chipbench.harness import catalog
    counters = catalog.load_module(reading['cell']['root'], 'layers',
                                   'step_counter_window')
    return counters.blocks_layers(reading, 'window')
