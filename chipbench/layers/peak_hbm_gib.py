"""Peak device memory of device 0 after the window: the runtime's
`peak_bytes_in_use` (live arrays) plus `peak_bytes_reserved` (the loaded
programs' scratch), the two pools `memory_stats()` keeps apart."""


def read(reading):
    b = reading['peak_bytes_device0']
    return b / 2 ** 30 if b else None
