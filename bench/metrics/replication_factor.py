"""Vertex copies over all machines, per vertex with an edge (1.0 = no
vertex is replicated)."""


def read(run):
    c = run.workload.counters
    if "vertex_copies" not in c or not c.get("covered_vertices"):
        return None
    return c["vertex_copies"] / c["covered_vertices"]
