"""Median host time of one ``bsp.superstep`` span over the window: the
dispatch through the read of its active counts."""
from bench import program


def read(run):
    return program.median_span_ms(run, "bsp.superstep")
