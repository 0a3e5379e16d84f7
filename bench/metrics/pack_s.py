"""The program's own span around the pack of the partition into
per-machine arrays (``runtime.pack``)."""
from bench import program


def read(run):
    return program.total_seconds(run, "runtime.pack")
