"""Whether each window job's edge kernel gathered its messages with the
VMEM kernel (``bsp.vmem_gathers``): 1 where ``segment``'s combine takes
``kernels/vmem_gather``, 0 where it keeps XLA's gather, as a mean over
the window's jobs.  Silent where the program never counted the name (a
program without the kernel)."""
from bench import program

COUNTER = "bsp.vmem_gathers"


def read(run):
    obs = program.recorder(run)
    if obs is None or COUNTER not in obs.totals()["counts"]:
        return None
    return program.per_call(run, program.counter(COUNTER))
