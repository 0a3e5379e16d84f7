"""Graph500 kernel-3 SSSP jobs: ``repro.bsp.sssp(rt, root, cap)`` from a
new root with an edge per job, each checked against float64 Dijkstra.

Traffic keys: ``weights``, ``max_checked``.  Cell params: ``supersteps``,
the cap, which the graph's deepest shortest-path tree sets.
"""
from bench import reference
from bench.jobs import BspJobs


class Workload(BspJobs):
    app = "sssp"

    def job_supersteps(self) -> int:
        return int(self.params["supersteps"])

    def next_request(self):
        return self.draw_root()

    def call(self, root):
        from repro import bsp
        out, _ = bsp.sssp(self.rt, root, self.supersteps, **self.options)
        return out

    def errors(self, adj, kept):
        return [reference.rel_error(a, reference.sssp(adj, r))
                for _, r, a in kept]
