"""JAX trace, lowering and backend compile (a persistent-cache load
included) per window job, from the durations JAX reports to the program's
recorder; a trace nested inside another is counted once."""
from bench import program


def read(run):
    return program.per_call(
        run, lambda r: 1e3 * sum(r.counters.get(e, 0.0)
                                 for e in program.COMPILE_EVENTS))
