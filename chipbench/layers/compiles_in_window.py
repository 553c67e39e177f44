"""`exe.cache_stats` after the window minus after warm-up, summed over
`online_compiles`, `misses` and `persistent_hits`. Must be 0; `correct`
is false otherwise."""


def read(reading):
    return reading['compiles_in_window']
