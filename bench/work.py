"""Least work of one BSP superstep, counted from the graph alone.

The count depends on V and E and on nothing the program chose (machines,
padding, ``vmax``/``emax``, a backend's layout), so one job reads the same
work whatever kernel runs it.  It is a lower bound: any implementation of
the superstep reads each directed edge's neighbour index once and each
vertex's value, degree and CSR offset once, and writes each result once,
all 4 bytes wide.  Values gathered along edges may stay on chip, so they
are not charged.
"""
from __future__ import annotations

#: bytes of one index or one float32 value
WORD = 4


def spmv_superstep(app: str, num_vertices: int,
                   num_edges: int) -> tuple[float, float]:
    """(operations, least bytes) of one superstep of ``app``.

    Every app makes one semiring product over the 2E directed edges:
    one multiply (or add, under (min, +)) and one combine per edge.
    PageRank adds the division by the degree and the damping update per
    vertex (3 operations); the monotone apps add one compare per vertex.
    """
    v, e2 = float(num_vertices), 2.0 * float(num_edges)
    per_vertex = 3.0 if app == "pagerank" else 1.0
    ops = 2.0 * e2 + per_vertex * v
    least_bytes = WORD * e2 + WORD * 4.0 * v
    return ops, least_bytes
