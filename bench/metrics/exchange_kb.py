"""Kilobytes (1e3 bytes) of replica table that the exchange all-reduces
per chip in a superstep (``bsp.exchange_bytes``), as the mean over the
window's jobs.  Silent where the program never counted it: a program
that runs no superstep across chips, or predates the counter."""
from bench import program

COUNTER = "bsp.exchange_bytes"


def read(run):
    obs = program.recorder(run)
    if obs is None or COUNTER not in obs.totals()["counts"]:
        return None
    steps = int(getattr(run.workload, "supersteps", 0))
    per_job = program.per_call(run, program.counter(COUNTER))
    if per_job is None or not steps:
        return None
    return per_job / steps * 1e-3
