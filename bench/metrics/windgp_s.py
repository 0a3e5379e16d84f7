"""The program's own span around the WindGP partition
(``windgp.partition``)."""
from bench import program


def read(run):
    return program.total_seconds(run, "windgp.partition")
