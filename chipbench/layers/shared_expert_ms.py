"""Device time per step in the shared experts: every event whose Fluid op
scope lies under the name scope `shared_expert` (the three projections
and the gate's product of the expert that every token passes, beside the
routed ones), forward and backward and what a region runs again, from the
trace. Nothing to read where the program names no such scope."""


def read(reading):
    from chipbench.harness import catalog
    window = catalog.load_module(reading['cell']['root'], 'layers',
                                 'name_scope_window')
    s = window.seconds_per_step(reading, 'shared_expert')
    return None if s is None else 1e3 * s
