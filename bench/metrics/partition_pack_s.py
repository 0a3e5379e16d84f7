"""Host clock around ``PartitionRuntime.create``: the WindGP partition and
the pack into device arrays."""


def read(run):
    return run.spans.get("partition_pack")
