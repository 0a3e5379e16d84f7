"""PageRank's least time per superstep over its device busy time per
superstep, in percent.

The least time is the larger of the superstep's operations over the peak
rate and its least bytes over HBM bandwidth (``bench/work.py``), counted
from V and E alone.  Busy time is the union of device operations in the
traced window, over the supersteps run in it.
"""


def read(run):
    wl = run.workload
    if (run.trace is None or getattr(wl, "app", None) != "pagerank"
            or not run.supersteps or run.peaks is None):
        return None
    ops, nbytes = wl.spmv_work()
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    per_step = run.trace["busy_s"] / run.supersteps
    return 100.0 * least / per_step
