"""Plain references, written from the semantics alone.

They take the benchmark's own edge list and weights, never anything the
program built, and compute in float64 on the host.

* PageRank: the program's documented power iteration.  Every vertex starts
  at 1/V; each superstep sends ``pr / weighted degree`` along every edge
  (both directions, scaled by the edge's weight) and sets
  ``pr = (1 - d) / V + d * received``.  Isolated vertices keep the
  teleport mass ``(1 - d) / V``.  There is no dangling-mass term.
* SSSP: Dijkstra (scipy) from the root over the undirected weighted graph.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


def adjacency(edges: np.ndarray, n: int,
              weights: np.ndarray | None = None) -> sp.csr_matrix:
    """Symmetric (V, V) CSR, float64; unit weights when ``weights`` is None."""
    w = (np.ones(len(edges)) if weights is None
         else np.asarray(weights, dtype=np.float64))
    u, v = edges[:, 0], edges[:, 1]
    a = sp.coo_matrix((np.concatenate([w, w]),
                       (np.concatenate([u, v]), np.concatenate([v, u]))),
                      shape=(n, n))
    return a.tocsr()


def pagerank(adj: sp.csr_matrix, num_iters: int, damping: float) -> np.ndarray:
    n = adj.shape[0]
    wdeg = np.asarray(adj.sum(axis=1)).ravel()
    inv = np.where(wdeg > 0, 1.0 / np.where(wdeg > 0, wdeg, 1.0), 0.0)
    pr = np.full(n, 1.0 / n)
    for _ in range(num_iters):
        pr = (1.0 - damping) / n + damping * (adj @ (pr * inv))
    return pr


def sssp(adj: sp.csr_matrix, root: int) -> np.ndarray:
    return csgraph.dijkstra(adj, directed=False, indices=root)


def rel_error(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| / |want| over entries finite in both; inf where
    exactly one side is infinite (reachability differs)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    fin_g, fin_w = np.isfinite(got), np.isfinite(want)
    if np.any(fin_g != fin_w):
        return float("inf")
    g, w = got[fin_w], want[fin_w]
    if not len(w):
        return 0.0
    den = np.where(w != 0, np.abs(w), 1.0)
    err = np.where(w != 0, np.abs(g - w) / den, np.where(g == 0, 0.0, np.inf))
    return float(err.max())
