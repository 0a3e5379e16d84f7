"""Device-to-host reads per window job (``bsp.host_syncs``): one per
superstep and one for the answer under the stepwise runner."""
from bench import program


def read(run):
    return program.per_call(run, program.counter("bsp.host_syncs"))
