"""Host time per window job in ``bsp.prepare``: the backend's layout, the
static tree and the initial state, uploaded to the device."""
from bench import program


def read(run):
    return program.per_call(run, lambda r: 1e3 * r.seconds("bsp.prepare"))
