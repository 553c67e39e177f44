"""Collective instructions in the compiled step (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute; an async pair counts
once). Repeats exactly."""
from chipbench.harness import scopes


def read(reading):
    if reading['chips'] < 2:
        return None
    return sum(scopes.collective_counts(reading['hlo']).values())
