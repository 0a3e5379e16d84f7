"""Graph500 Kronecker graph, as LDBC Graphalytics ships it.

Configuration keys: ``scale``, ``edge_factor`` and the initiator's ``a``,
``b``, ``c``.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import canonical, drop_isolated


def kronecker(scale: int, edge_factor: int, a: float, b: float, c: float,
              rng: np.random.Generator) -> tuple[np.ndarray, int]:
    """``edge_factor * 2**scale`` edge draws over ``2**scale`` labels.

    Each edge descends ``scale`` levels of the 2x2 initiator
    ``[[a, b], [c, 1 - a - b - c]]``; labels are then permuted at random,
    as the Graph500 specification requires.  Duplicates and self loops go,
    and so do the labels no edge touches: Graphalytics' graph500 datasets
    list only vertices with an edge.
    """
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, dtype=np.int32)
    dst = np.zeros(m, dtype=np.int32)
    ab, abc = np.float32(a + b), np.float32(a + b + c)
    for _ in range(scale):
        r = rng.random(m, dtype=np.float32)
        src = (src << 1) | (r >= ab)
        dst = (dst << 1) | (((r >= a) & (r < ab)) | (r >= abc))
    perm = rng.permutation(n).astype(np.int32)
    return drop_isolated(canonical(perm[src], perm[dst], n), n)


def generate(cfg: dict, rng: np.random.Generator) -> tuple[np.ndarray, int]:
    return kronecker(cfg["scale"], cfg["edge_factor"], cfg["a"], cfg["b"],
                     cfg["c"], rng)
