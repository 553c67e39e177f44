"""The backend's share of the training step's first call, in seconds:
the program's `executor.first_call.backend` record of the training step's
key, which is XLA's compile in a cold checkout and the read from the
persistent cache in a warm one (the record's `cached` says which)."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.first_call_s(reading, ('executor.first_call.backend',))
