"""Per step, the time device 0 spent in collective operations with no
compute operation running under them."""


def read(reading):
    red = reading['trace']
    if red is None or reading['chips'] < 2:
        return None
    return 1e3 * red['collective_exposed_s'] / red['steps']
