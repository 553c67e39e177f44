"""Host time of a step outside the blocking fetch: the program's
`executor.step` span minus its `executor.fetch` child, per step, over the
measured window (registry histograms, always armed)."""


def read(reading):
    step, fetch = (reading['registry'][k] for k in
                   ('executor.step', 'executor.fetch'))
    if not step['count']:
        return None
    return 1e3 * (step['sum'] - fetch['sum']) / step['count']
