"""Device busy time in the traced window per superstep run in it."""


def read(run):
    if run.trace is None or not run.supersteps:
        return None
    return 1e3 * run.trace["busy_s"] / run.supersteps
