"""Bytes the program's `executor.feed.bytes` counter saw per step of the
window, in MB: a count."""


def read(reading):
    steps = reading['registry']['executor.step']['count']
    if not steps:
        return None
    return reading['registry']['executor.feed.bytes'] / steps / 1e6
