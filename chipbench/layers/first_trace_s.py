"""Python's share of the training step's first call, in seconds: the
program's `executor.lowering` span (Program to jittable step) plus
`executor.first_call.trace` (jax's trace to a jaxpr and its lowering to
MLIR, from jax's own duration events) of the training step's key. Only
the program can shorten it; a warm compile cache does not."""


def read(reading):
    from chipbench.harness import catalog
    spans = catalog.load_module(reading['cell']['root'], 'layers',
                                'span_window')
    return spans.first_call_s(reading, ('executor.lowering',
                                        'executor.first_call.trace'))
