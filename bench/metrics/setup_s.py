"""Process start to the window's start: generation, partition, pack,
transfer and warm-up (with compilation where the cache has no program)."""


def read(run):
    return run.setup_s
