"""PageRank jobs: ``repro.bsp.pagerank(rt, num_iters, damping)`` with no
engine options, each checked against the float64 power iteration.

Traffic keys: ``num_iters``, ``damping``, ``weights``, ``max_checked``.
"""
from bench import reference
from bench.jobs import BspJobs


class Workload(BspJobs):
    app = "pagerank"

    def job_supersteps(self) -> int:
        return int(self.traffic["num_iters"])

    def next_request(self):
        return None

    def call(self, request):
        from repro import bsp
        out, _ = bsp.pagerank(self.rt, self.supersteps,
                              float(self.traffic["damping"]), **self.options)
        return out

    def errors(self, adj, kept):
        want = reference.pagerank(adj, self.supersteps,
                                  float(self.traffic["damping"]))
        return [reference.rel_error(a, want) for _, _, a in kept]
