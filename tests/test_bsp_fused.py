"""Fused on-device BSP loop vs the per-step oracle.

``run_bsp`` (one dispatch + host sync per superstep) is the bit-exact
reference; ``run_bsp_fused`` must reproduce it — bitwise for the min/max
semiring apps (SSSP/BFS/CC, whose exchange-epilogue rewrite is exact)
and to 1e-6 for PageRank's (+,×) — including the actives trajectory,
early exit mid-chunk, and the zero-step edge case.  The frontier-
sparsified scatter path and the low-precision message knob are pinned
here too, plus the dtype-safe integer exchange identities.
"""
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bsp import (PartitionRuntime, bfs, connected_components,
                       frontier_entries, pagerank, run_bsp, run_bsp_fused,
                       sssp)
from repro.bsp.apps import build_pagerank
from repro.bsp import build_app
from repro.bsp.engine import (MACHINES, exchange, make_fused_runner,
                              make_step)
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat

APPS = {
    "pagerank": (pagerank, dict(num_iters=15)),
    "sssp": (sssp, dict(source=0, num_iters=25)),
    "bfs": (bfs, dict(source=1, num_iters=25)),
    "cc": (connected_components, dict(num_iters=25)),
}


@pytest.fixture(scope="module")
def part():
    g = rmat(8, seed=2)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    r = windgp(g, cl, t0=2)
    rt = PartitionRuntime.build(g, r.assign, cl.p)
    return g, rt


class TestFusedEquivalence:
    @pytest.mark.parametrize("backend", ["scatter", "segment"])
    @pytest.mark.parametrize("app", list(APPS))
    def test_fused_matches_stepwise(self, part, app, backend):
        """Fused ≡ stepwise: results and the actives prefix, per app."""
        _, rt = part
        fn, kw = APPS[app]
        a, acts_a = fn(rt, backend=backend, **kw)
        b, acts_b = fn(rt, backend=backend, fused=True, chunk=4, **kw)
        if app == "pagerank":
            np.testing.assert_allclose(a, b, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)   # bitwise (min/max)
        n = len(acts_b)
        np.testing.assert_array_equal(acts_a[:n], acts_b)
        # anything the fused runner skipped, the oracle spent idling
        assert np.asarray(acts_a)[n:].sum() == 0

    @pytest.mark.parametrize("chunk", [1, 3, 8, 64])
    def test_chunk_size_is_cosmetic(self, part, chunk):
        """Any chunking (incl. chunk > budget) gives the same trajectory."""
        _, rt = part
        d0, acts0 = sssp(rt, source=0, num_iters=25)
        d1, acts1 = sssp(rt, source=0, num_iters=25, fused=True,
                         chunk=chunk)
        np.testing.assert_array_equal(d0, d1)
        # monotone app exits early mid-chunk regardless of the boundary
        assert 0 < len(acts1) < 25
        np.testing.assert_array_equal(np.asarray(acts0)[:len(acts1)],
                                      acts1)

    def test_pagerank_tol_early_exit(self, part):
        """The on-device residual gate stops well before the budget."""
        _, rt = part
        pr_t, acts_t = pagerank(rt, num_iters=50, tol=1e-7)
        pr_f, _ = pagerank(rt, num_iters=50)
        assert len(acts_t) < 50
        # drift from stopping early is bounded by ~tol·d/(1-d)
        assert np.abs(pr_t - pr_f).max() <= 1e-6

    def test_zero_steps_returns_0_by_p(self, part):
        """num_steps=0: (0, p) actives and an untouched state tree."""
        _, rt = part
        spec = build_pagerank(rt)
        for runner in (run_bsp, run_bsp_fused):
            out, acts = runner(spec.superstep, spec.state, spec.static, 0)
            assert acts.shape == (0, rt.p), runner.__name__
            for a, b in zip(jax.tree.leaves(spec.state),
                            jax.tree.leaves(out)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

    def test_runner_factory_reuse(self, part):
        """One compiled runner serves many calls and step budgets."""
        _, rt = part
        spec = build_pagerank(rt)
        run = make_fused_runner(spec.superstep, spec.static, chunk=4)
        _, acts5 = run(spec.state, 5)
        _, acts9 = run(spec.state, 9)
        assert acts5.shape == (5, rt.p) and acts9.shape == (9, rt.p)
        np.testing.assert_array_equal(acts9[:5], acts5)


class TestFrontier:
    def test_frontier_cap_bitwise_vs_dense(self, part):
        """A generous cap never drops a message: bitwise == dense."""
        _, rt = part
        for fn, kw in [(sssp, dict(source=0, num_iters=25)),
                       (bfs, dict(source=1, num_iters=25))]:
            dense, _ = fn(rt, backend="scatter", **kw)
            sparse, _ = fn(rt, backend="scatter",
                           frontier_cap=int(rt.vmax), **kw)
            np.testing.assert_array_equal(dense, sparse)

    def test_frontier_entries_counts_live_vertices(self, part):
        """Per-machine live-vertex counts, restricted to valid slots."""
        _, rt = part
        cnt = frontier_entries(rt, np.asarray(rt.vertex_valid))
        np.testing.assert_array_equal(
            cnt, np.asarray(rt.vertex_valid).sum(axis=1))
        assert frontier_entries(
            rt, np.zeros_like(np.asarray(rt.vertex_valid))).sum() == 0

    def test_frontier_cap_validation(self, part):
        _, rt = part
        with pytest.raises(ValueError, match="frontier_cap"):
            sssp(rt, source=0, num_iters=2, backend="scatter",
                 frontier_cap=0)


class TestMessageDtype:
    def test_float32_is_identity(self, part):
        """The default knob must be a bitwise no-op on every backend."""
        _, rt = part
        for backend in ("scatter", "segment"):
            a, _ = pagerank(rt, num_iters=12, backend=backend)
            b, _ = pagerank(rt, num_iters=12, backend=backend,
                            message_dtype="float32")
            np.testing.assert_array_equal(a, b)

    def test_bfloat16_close_and_finite(self, part):
        _, rt = part
        a, _ = pagerank(rt, num_iters=12)
        b, _ = pagerank(rt, num_iters=12, message_dtype="bfloat16")
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() < 1e-2

    def test_unknown_dtype_rejected(self, part):
        _, rt = part
        with pytest.raises(ValueError, match="message_dtype"):
            pagerank(rt, num_iters=2, message_dtype="float64")


class TestExchangeDtypes:
    """min/max identities must be representable in integer dtypes."""

    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_min_max_roundtrip(self, dtype):
        # two machines; vertex 0 replicated in slot 0, vertex 1 private
        rep_slot = jnp.asarray(np.array([[0, -1], [0, -1]], np.int32))
        slot_vertex = jnp.asarray(np.array([[0, -1], [0, -1]], np.int32))
        vals = jnp.asarray(np.array([[5, 7], [3, 9]], dtype))
        lo = jax.vmap(lambda v, s, t: exchange(v, (s, t), 1, "min"),
                      axis_name=MACHINES)(vals, rep_slot, slot_vertex)
        np.testing.assert_array_equal(np.asarray(lo), [[3, 7], [3, 9]])
        hi = jax.vmap(lambda v, s, t: exchange(v, (s, t), 1, "max"),
                      axis_name=MACHINES)(vals, rep_slot, slot_vertex)
        np.testing.assert_array_equal(np.asarray(hi), [[5, 7], [5, 9]])
        assert np.asarray(hi).dtype == dtype


def _largest_constant(hlo: str) -> int:
    """Element count of the largest ``stablehlo.constant`` in a module."""
    sizes = [0]
    for line in hlo.splitlines():
        if "stablehlo.constant" in line:
            for dims in re.findall(r"tensor<([0-9x]+)x[a-z]", line):
                sizes.append(math.prod(int(d) for d in dims.split("x")))
    return max(sizes)


class TestStaticIsAnArgument:
    """The partitioned graph enters the BSP program as an argument: baked
    in as constants it would grow each executable and its compile time
    with the graph, and key the persistent compile cache on the data."""

    @pytest.mark.parametrize("runner", ["step", "fused"])
    @pytest.mark.parametrize("backend,opts", [
        ("scatter", {}), ("segment", {}), ("pallas", {"block_size": 16})])
    def test_no_graph_sized_constant(self, part, runner, backend, opts):
        _, rt = part
        spec = build_app(rt, "pagerank", backend=backend, **opts)
        if runner == "step":
            lowered = make_step(spec.superstep, spec.static).lower(
                spec.state)
        else:
            lowered = make_fused_runner(spec.superstep, spec.static).lower(
                spec.state, 10)
        graph = min(a.size for a in jax.tree.leaves(spec.static))
        assert _largest_constant(lowered.as_text()) < graph


class TestDefaultSuperstepHasNoScatter:
    """The default edge kernel reduces by a segmented scan and the
    exchange fills its table by gathers: XLA:TPU runs a scatter as
    element-wise random access, so the lowered default superstep, alone
    or in the fused loop, holds none — under each semiring."""

    @pytest.mark.parametrize("runner", ["step", "fused"])
    @pytest.mark.parametrize("app", ["pagerank", "sssp", "bfs"])
    def test_lowered_default_superstep(self, part, runner, app):
        _, rt = part
        spec = build_app(rt, app)
        if runner == "step":
            lowered = make_step(spec.superstep, spec.static).lower(
                spec.state)
        else:
            lowered = make_fused_runner(spec.superstep, spec.static).lower(
                spec.state, 10)
        text = lowered.as_text()
        assert "eb_seg_in" in spec.static
        assert "scatter" not in text
        graph = min(a.size for a in jax.tree.leaves(spec.static))
        assert _largest_constant(text) < graph


MESH_FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.bsp import (PartitionRuntime, pagerank, sssp, bfs,
                       connected_components)
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat

g = rmat(9, seed=2)
cl = scaled_paper_cluster(2, 6, g.num_edges)   # p = 8 machines
r = windgp(g, cl, t0=2)
rt = PartitionRuntime.build(g, r.assign, cl.p)
mesh = jax.make_mesh((8,), ("machines",))

for name, fn, kw in [("pagerank", pagerank, dict(num_iters=10)),
                     ("sssp", sssp, dict(source=0, num_iters=20)),
                     ("bfs", bfs, dict(source=1, num_iters=20)),
                     ("cc", connected_components, dict(num_iters=20))]:
    a, acts_a = fn(rt, mesh=mesh, **kw)
    b, acts_b = fn(rt, mesh=mesh, fused=True, chunk=4, **kw)
    if name == "pagerank":
        np.testing.assert_allclose(a, b, atol=1e-6)
    else:
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(acts_a[:len(acts_b)], acts_b)

_, acts = pagerank(rt, num_iters=50, mesh=mesh, tol=1e-6)
assert len(acts) < 50

# the other backends, Pallas with shard_map's replication check off
for backend, opts in [("segment", {}), ("pallas", {"block_size": 16})]:
    a, _ = pagerank(rt, num_iters=10, backend=backend, **opts)
    b, _ = pagerank(rt, num_iters=10, mesh=mesh, fused=True, chunk=4,
                    backend=backend, **opts)
    np.testing.assert_allclose(a, b, atol=1e-6)
print("MESH_FUSED_OK")
"""


def test_fused_sharded_8_devices():
    """Fused while/scan loop under shard_map over a real 8-device mesh."""
    out = subprocess.run(
        [sys.executable, "-c", MESH_FUSED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"})
    assert "MESH_FUSED_OK" in out.stdout, out.stderr[-2000:]
