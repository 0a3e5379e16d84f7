"""Megabytes (1e6 bytes) uploaded host to device per window job
(``bsp.h2d_bytes``)."""
from bench import program


def read(run):
    v = program.per_call(run, program.counter("bsp.h2d_bytes"))
    return None if v is None else v * 1e-6
