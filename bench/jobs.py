"""The general closed loop of BSP analytics jobs on one WindGP partition.

A cell's traffic file (``bench/traffic/<name>.json``) names a ``kind``;
``bench/kinds/<kind>.py`` holds a ``Workload`` that subclasses
:class:`BspJobs` with the job's call, its request and its reference.  The
configuration (``bench/configs/<name>.json``) names the graph's generator
and the partition; the cell's own file (``bench/cells/<cell>.json``) gives
its ``params``, such as an SSSP job's superstep cap.

Set-up generates the graph from the seed, partitions and packs it; the
window then takes one request at a time.  Every answer is kept, and once
the window has closed up to ``max_checked`` of them, drawn from the seed,
are compared with :mod:`bench.reference`.
"""
from __future__ import annotations

import numpy as np

from bench import graphs, reference, work


def _program_graph(edges: np.ndarray, n: int):
    from repro.core import from_edge_list
    g = from_edge_list(edges, num_vertices=n)
    if not np.array_equal(g.edges, edges):
        raise RuntimeError("the program reordered the canonical edge list; "
                           "per-edge weights would not line up")
    return g


class BspJobs:
    """Closed loop of one kind of analytics job on a ``PartitionRuntime``."""

    #: the app as :func:`bench.work.spmv_superstep` counts its work
    app = ""
    #: the number the check compares
    compared = "max_rel_err"

    def __init__(self, cell, seed: int):
        self.cfg, self.traffic, self.params = (cell.config, cell.traffic,
                                               cell.params)
        self.generator = cell.generator
        self.seed = seed
        self.options: dict = {}
        self.kept: list = []
        self.counters: dict = {}
        self.failed = self.checked = 0

    def set_options(self, options: dict) -> None:
        """Engine options for every later call: the control's
        ``message_dtype``.  The benchmark's own runs set none."""
        self.options = dict(options)

    # -- what a kind defines --------------------------------------------
    def job_supersteps(self) -> int:
        """Supersteps one job runs."""
        raise NotImplementedError

    def next_request(self):
        """The next job's request, drawn outside the timed call."""
        raise NotImplementedError

    def call(self, request):
        """One job through the program; returns its global answer."""
        raise NotImplementedError

    def errors(self, adj, kept: list) -> list[float]:
        """The compared number of each kept (i, request, answer)."""
        raise NotImplementedError

    # -- set-up --------------------------------------------------------
    def build(self, clock) -> None:
        from repro.bsp import PartitionRuntime
        from repro.core import scaled_paper_cluster
        with clock.span("generate"):
            self.edges, self.n = self.generator.generate(
                self.cfg, graphs.rng_for(self.seed, 0))
            self.weights = graphs.edge_weights(self.traffic["weights"],
                                               len(self.edges), self.seed)
            g = _program_graph(self.edges, self.n)
        part = self.cfg["partition"]
        cl = scaled_paper_cluster(part["n_super"], part["n_normal"],
                                  g.num_edges)
        with clock.span("partition_pack"):
            self.rt = PartitionRuntime.create(
                source=g, method=part["method"], cluster=cl,
                edge_weights=self.weights)
        deg = np.bincount(self.edges.ravel(), minlength=self.n)
        self.roots_pool = np.flatnonzero(deg > 0)
        self.rng = graphs.rng_for(self.seed, 2)
        rt = self.rt
        self.counters = {
            "V": self.n, "E": len(self.edges),
            "vertex_copies": int(rt.verts_per_machine.sum()),
            "covered_vertices": int(len(self.roots_pool)),
            "replicated_vertices": int(rt.num_replicas),
            "p": rt.p, "vmax": rt.vmax, "emax": rt.emax,
        }
        self.supersteps = self.job_supersteps()

    def draw_root(self) -> int:
        """A root with an edge, from the seed's request stream."""
        return int(self.rng.choice(self.roots_pool))

    def keep(self, i: int, request, answer) -> None:
        self.kept.append((i, request, answer))

    # -- per request work (the work count EVPS charges) -----------------
    @property
    def work_per_request(self) -> float:
        return float(self.n + len(self.edges))

    def spmv_work(self) -> tuple[float, float]:
        """(operations, least bytes) of one superstep, from V and E alone."""
        return work.spmv_superstep(self.app, self.n, len(self.edges))

    # -- check -----------------------------------------------------------
    def check(self, limits: dict) -> dict:
        """Compare kept answers with the reference: {name: (value, limit)}."""
        adj = reference.adjacency(self.edges, self.n, self.weights)
        kept = self.kept
        max_checked = int(self.traffic.get("max_checked", len(kept)))
        if len(kept) > max_checked:
            pick = graphs.rng_for(self.seed, 3)
            idx = np.sort(pick.choice(len(kept), max_checked, replace=False))
            kept = [kept[i] for i in idx]
        errs = self.errors(adj, kept)
        limit = limits[self.compared]
        value = max(errs) if errs else float("inf")
        self.failed = sum(e > limit for e in errs) if errs else 1
        self.checked = len(errs)
        return {self.compared: (value, limit)}
