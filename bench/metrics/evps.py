"""Graphalytics EVPS: (V + E) of each job completed in the window, over
the window's length."""


def read(run):
    work = getattr(run.workload, "work_per_request", None)
    if work is None or run.window_s <= 0:
        return None
    return work * run.completed / run.window_s
