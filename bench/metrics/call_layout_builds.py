"""Host builds of an edge kernel's layout per window job
(``bsp.layout_builds``): 0 where every job reuses the layout its runtime
built once.  Silent where the program never counted a build (a program
that keeps no such layout)."""
from bench import program

COUNTER = "bsp.layout_builds"


def read(run):
    obs = program.recorder(run)
    if obs is None or COUNTER not in obs.totals()["counts"]:
        return None
    return program.per_call(run, program.counter(COUNTER))
