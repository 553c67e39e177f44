"""The dense gated feed-forwards' share of the chip's bf16 peak: the FLOPs
they REQUIRE of one chip in a step (flops/<config>.py `dense_mlp_flops()`:
6 a weight a token) over the device time under the name scope `dense_mlp`
(`dense_mlp_ms`), over the published peak, in percent. The time holds what
a recompute region runs again (a forward in three passes' FLOPs) and the
FLOPs do not, so a feed-forward at the MXU's peak reads 75. Nothing to
read where the program names no such scope or the configuration counts no
such FLOPs."""


def read(reading):
    from chipbench.harness import catalog
    cell = reading['cell']
    required = getattr(cell['flops'], 'dense_mlp_flops', None)
    if required is None or reading['peaks'] is None:
        return None
    ms = catalog.load_reader('dense_mlp_ms', cell['root'])(reading)
    if ms is None:
        return None
    flops = required(cell['config'], cell['traffic'], reading['chips'])
    return 100.0 * flops / (1e-3 * ms) / reading['peaks']['bf16_flops_per_s']
