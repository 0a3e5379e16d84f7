"""PageRank jobs as ``kinds/pagerank.py`` runs them, over a ``machines``
mesh of the cell's chips, one machine per chip:
``repro.bsp.pagerank(rt, num_iters, damping, mesh=)`` with no engine
options, each checked against the float64 power iteration.

Traffic keys: those of ``kinds/pagerank.py``.
"""
from bench.kinds import pagerank


class Workload(pagerank.Workload):
    app = "pagerank_mesh"

    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        self.chips = cell.chips

    def build(self, clock) -> None:
        import jax
        super().build(clock)
        if self.rt.p != self.chips:
            raise ValueError(f"the partition has {self.rt.p} machines and "
                             f"the cell {self.chips} chips: the mesh holds "
                             f"one machine per chip")
        self.mesh = jax.make_mesh((self.chips,), ("machines",),
                                  devices=jax.devices()[:self.chips])

    def call(self, request):
        from repro import bsp
        out, _ = bsp.pagerank(self.rt, self.supersteps,
                              float(self.traffic["damping"]),
                              mesh=self.mesh, **self.options)
        return out
