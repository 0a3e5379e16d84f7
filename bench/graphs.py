"""What every graph generator shares: seeds, the canonical edge order,
edge weights, and finding a generator by name.

A generator is ``generators/<name>.py`` with ``generate(cfg, rng) ->
(edges, V)``; a configuration names it under ``generator``.  Edges are
canonical: ``u < v``, no self loops, no duplicates, sorted by ``u * V + v``.
That is the order the program's own ``Graph`` keeps, so per-edge weights
drawn here line up with its edge ids.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of ``--seed`` (graph, weights, ...).

    Any whole number is a valid seed, negative or past 64 bits included.
    """
    return np.random.default_rng([seed % (1 << 64), stream])


def canonical(u: np.ndarray, v: np.ndarray, n: int) -> np.ndarray:
    """(E, 2) int32 canonical edges of the undirected multigraph (u, v)."""
    lo = np.minimum(u, v).astype(np.int64)
    hi = np.maximum(u, v).astype(np.int64)
    keep = lo != hi
    key = np.unique(lo[keep] * n + hi[keep])
    return np.stack([key // n, key % n], axis=1).astype(np.int32)


def drop_isolated(edges: np.ndarray, n: int) -> tuple[np.ndarray, int]:
    """Drop the vertices without an edge and number the rest densely, in
    their old order (so canonical edges stay canonical)."""
    used = np.bincount(edges.ravel(), minlength=n) > 0
    new_id = (np.cumsum(used) - 1).astype(np.int32)
    return new_id[edges], int(used.sum())


def edge_weights(kind: str, num_edges: int, seed: int) -> np.ndarray | None:
    """Per-edge weights, or None for unit weights.

    ``uniform01`` is Graph500 kernel 3's: uniform in [0, 1), float32.
    """
    if kind == "unit":
        return None
    if kind == "uniform01":
        return rng_for(seed, 1).random(num_edges, dtype=np.float32)
    raise ValueError(f"unknown edge weights {kind!r}")
