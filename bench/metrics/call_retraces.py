"""Traces of a superstep program per window job (``bsp.retraces``): 0
where a job reuses a traced program."""
from bench import program


def read(run):
    return program.per_call(run, program.counter("bsp.retraces"))
