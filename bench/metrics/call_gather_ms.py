"""Host time per window job in ``bsp.gather``: the answer's copy to the
host and its merge into the global vertex order."""
from bench import program


def read(run):
    return program.per_call(run, lambda r: 1e3 * r.seconds("bsp.gather"))
