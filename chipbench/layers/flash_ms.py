"""Device time per step of the Mosaic (Pallas) kernel events."""


def read(reading):
    red = reading['trace']
    if red is None or not red['kernel_s']:
        return None
    return 1e3 * red['kernel_s'] / red['steps']
