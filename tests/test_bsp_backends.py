"""Edge-kernel backends: cross-backend app equivalence + weighted PageRank.

The refactor's contract: all four BSP apps produce the same results
whichever backend combines their edge messages — bitwise for the
(min, +)/(or, and) apps (min/max reassociate exactly), within 1e-5 for
(+, ×) (each backend sums a vertex's messages in its own order) — under
vmap here and under a real 8-device shard_map mesh in the subprocess
test.  ``scatter`` is the oracle the others are compared with, named
explicitly; ``segment`` (the default) is pinned against it per semiring.
Weighted PageRank is pinned against a NetworkX-free dense oracle.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bsp import (PartitionRuntime, bfs, build_app, connected_components,
                       get_backend, pagerank, ref, sssp)
from repro.core import scaled_paper_cluster, windgp
from repro.data import graph500, rmat
from repro.bsp.backends import DEFAULT_BACKEND, default_backend
from repro.kernels.bsr_spmv import get_semiring

PALLAS_OPTS = {"block_size": 16}
OTHER = (("segment", {}), ("pallas", PALLAS_OPTS))


@pytest.fixture(scope="module")
def part():
    g = rmat(8, seed=2)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    r = windgp(g, cl, t0=2)
    return g, cl, PartitionRuntime.build(g, r.assign, cl.p)


@pytest.fixture(scope="module")
def weighted_part():
    g = rmat(8, seed=3)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    r = windgp(g, cl, t0=2)
    w = (np.random.default_rng(5).random(g.num_edges) + 0.1).astype(
        np.float32)
    rt = PartitionRuntime.build(g, r.assign, cl.p, edge_weights=w)
    return g, w, rt


def dense_weighted_pagerank(g, w, num_iters=20, damping=0.85):
    """NetworkX-free oracle: dense weighted adjacency, float64."""
    n = g.num_vertices
    A = np.zeros((n, n))
    np.add.at(A, (g.edges[:, 0], g.edges[:, 1]), w)
    np.add.at(A, (g.edges[:, 1], g.edges[:, 0]), w)
    wdeg = A.sum(axis=1)
    pr = np.full(n, 1.0 / n)
    for _ in range(num_iters):
        msg = np.where(wdeg > 0, pr / np.maximum(wdeg, 1e-300), 0.0)
        pr = (1 - damping) / n + damping * (A @ msg)
    return pr


class TestCrossBackend:
    def test_pagerank_close(self, part):
        _, _, rt = part
        base, _ = pagerank(rt, num_iters=15, backend="scatter")
        for be, opts in OTHER:
            got, _ = pagerank(rt, num_iters=15, backend=be, **opts)
            np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)

    def test_sssp_bitwise(self, part):
        _, _, rt = part
        base, _ = sssp(rt, source=0, num_iters=20, backend="scatter")
        for be, opts in OTHER:
            got, _ = sssp(rt, source=0, num_iters=20, backend=be, **opts)
            np.testing.assert_array_equal(got, base)

    def test_bfs_bitwise(self, part):
        g, _, rt = part
        base, actives = bfs(rt, source=1, num_iters=20, backend="scatter")
        # the (or, and) frontier rewrite still equals the min-plus oracle
        expect = ref.bfs(g, source=1, num_iters=20)
        m = ~np.isinf(expect)
        np.testing.assert_allclose(base[m], expect[m])
        assert actives.sum(axis=1)[-1] == 0
        for be, opts in OTHER:
            got, _ = bfs(rt, source=1, num_iters=20, backend=be, **opts)
            np.testing.assert_array_equal(got, base)

    def test_cc_bitwise(self, part):
        _, _, rt = part
        base, _ = connected_components(rt, num_iters=20, backend="scatter")
        for be, opts in OTHER:
            got, _ = connected_components(rt, num_iters=20, backend=be,
                                          **opts)
            np.testing.assert_array_equal(got, base)

    def test_unknown_backend_rejected(self, part):
        _, _, rt = part
        with pytest.raises(ValueError, match="unknown edge-kernel backend"):
            pagerank(rt, num_iters=1, backend="gpu_warp")

    def test_backend_declares_check_rep(self):
        assert get_backend("scatter").check_rep
        assert get_backend("segment").check_rep
        assert not get_backend("pallas").check_rep

    def test_cli_mirror_matches_registry(self):
        """launch.partition's static choices (kept jax-free) == BACKENDS."""
        from repro.bsp import BACKENDS, MESSAGE_DTYPES
        from repro.launch import partition as cli
        assert set(cli.EDGE_BACKENDS) == set(BACKENDS)
        assert set(cli.MESSAGE_DTYPES) == set(MESSAGE_DTYPES)

    def test_build_app_specs(self, part):
        _, _, rt = part
        for app in ("pagerank", "sssp", "bfs", "cc"):
            spec = build_app(rt, app, backend="segment")
            assert spec.name in (app, "sssp")
            assert "eb_seg_in" in spec.static and "edges" not in spec.static
        with pytest.raises(ValueError, match="unknown BSP app"):
            build_app(rt, "betweenness")


@pytest.mark.parametrize("backend", ["scatter", "segment"])
def test_pagerank_per_vertex_relative_error(backend):
    """Every vertex keeps its own digits under (+, ×).  graph500 scale 12
    is the smallest scale where one float32 running sum over a machine's
    whole incidence list, differenced at the row pointers, misses 1e-4
    (2.8e-4; 1.8e-2 at scale 18)."""
    g = graph500(12, seed=0)
    cl = scaled_paper_cluster(2, 6, g.num_edges)
    rt = PartitionRuntime.create(source=g, method="windgp", cluster=cl)
    got, _ = pagerank(rt, 20, backend=backend, fused=True)
    want = ref.pagerank(g, 20)
    assert np.max(np.abs(got - want) / want) <= 1e-4


# ---------------------------------------------------------------------------
# segment, the default: a segmented scan checked against the scatter oracle
# ---------------------------------------------------------------------------

#: semiring -> (the weight kind its app combines with, the app)
SEMIRINGS = {"plus_times": ("weight", "pagerank"),
             "min_plus": ("weight", "sssp"),
             "or_and": ("unit", "bfs")}
APP_CALLS = {"pagerank": (pagerank, dict(num_iters=15)),
             "sssp": (sssp, dict(source=0, num_iters=20)),
             "bfs": (bfs, dict(source=1, num_iters=20)),
             "cc": (connected_components, dict(num_iters=20))}


@pytest.fixture(scope="module")
def g500_12():
    g = graph500(12, seed=0)
    cl = scaled_paper_cluster(2, 6, g.num_edges)
    return PartitionRuntime.create(source=g, method="windgp", cluster=cl)


def _messages(semiring, shape, seed=0):
    """Vertex values with every kind of entry the semiring meets: for
    (min, +) half are +inf (no message), for (or, and) a 0/1 frontier."""
    rng = np.random.default_rng(seed)
    if semiring == "plus_times":
        return rng.random(shape).astype(np.float32)
    if semiring == "min_plus":
        return np.where(rng.random(shape) < 0.5, np.inf,
                        rng.random(shape) * 10).astype(np.float32)
    return (rng.random(shape) < 0.3).astype(np.float32)


def _combine(rt, backend, semiring, x):
    """One backend's (p, Vmax) combine of ``x``, before the exchange."""
    extras, combine = get_backend(backend).prepare(
        rt, semiring, SEMIRINGS[semiring][0])
    return np.asarray(jax.jit(jax.vmap(combine))(extras, jnp.asarray(x)))


def _local_messages(rt):
    """(p, Vmax) bool: the vertex has a valid incident edge on the
    machine, so it receives at least one message there."""
    has = np.zeros((rt.p, rt.vmax), dtype=bool)
    for i in range(rt.p):
        e = rt.local_edges[i][rt.edge_valid[i]]
        has[i, e[:, 0]] = has[i, e[:, 1]] = True
    return has


@pytest.mark.parametrize("app", list(APP_CALLS))
def test_the_default_backend_is_segment(part, app):
    """No backend named: ``segment``; a ``frontier_cap`` alone keeps
    ``scatter``, whose frontier path it asks for."""
    _, _, rt = part
    assert DEFAULT_BACKEND == "segment"
    assert get_backend().name == "segment"
    assert default_backend() == "segment"
    assert default_backend(frontier_cap=8) == "scatter"
    assert get_backend(frontier_cap=8).name == "scatter"
    spec = build_app(rt, app)
    assert "eb_seg_in" in spec.static and "edges" not in spec.static
    fn, kw = APP_CALLS[app]
    got, _ = fn(rt, **kw)
    want, _ = fn(rt, backend="segment", **kw)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("semiring", list(SEMIRINGS))
def test_segment_matches_scatter_per_vertex(g500_12, semiring):
    """Bitwise for min/max; for (+, ×) each vertex's sum within 1e-6
    relative of the oracle's (another summation order of the same
    float32 products, never another vertex's)."""
    rt = g500_12
    x = _messages(semiring, (rt.p, rt.vmax))
    want = _combine(rt, "scatter", semiring, x)
    got = _combine(rt, "segment", semiring, x)
    if semiring != "plus_times":
        np.testing.assert_array_equal(got, want)
        return
    assert not np.array_equal(got, 0)
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    assert rel.max() <= 1e-6


@pytest.mark.parametrize("semiring", list(SEMIRINGS))
def test_vertices_without_local_messages_get_the_zero(g500_12, semiring):
    rt = g500_12
    none = ~_local_messages(rt)
    assert none.any()                   # padding lanes at least
    x = _messages(semiring, (rt.p, rt.vmax), seed=1)
    got = _combine(rt, "segment", semiring, x)
    np.testing.assert_array_equal(got[none],
                                  get_semiring(semiring).zero)


@pytest.fixture(scope="module")
def idle_machine():
    """Five machines, the last of which gets no edge at all."""
    g = rmat(8, seed=4)
    assign = (np.arange(g.num_edges) % 4).astype(np.int64)
    return g, PartitionRuntime.build(g, assign, 5)


@pytest.mark.parametrize("semiring", list(SEMIRINGS))
def test_a_machine_with_no_valid_edge(idle_machine, semiring):
    g, rt = idle_machine
    assert not rt.edge_valid[-1].any()
    x = _messages(semiring, (rt.p, rt.vmax), seed=2)
    got = _combine(rt, "segment", semiring, x)
    np.testing.assert_array_equal(got[-1], get_semiring(semiring).zero)
    want = _combine(rt, "scatter", semiring, x)
    if semiring == "plus_times":
        np.testing.assert_allclose(got, want, rtol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)
    app = SEMIRINGS[semiring][1]
    fn, kw = APP_CALLS[app]
    seg, _ = fn(rt, **kw)
    sca, _ = fn(rt, backend="scatter", **kw)
    if app == "pagerank":
        np.testing.assert_allclose(seg, ref.pagerank(g, 15), rtol=2e-4)
        np.testing.assert_allclose(seg, sca, rtol=1e-5)
    else:
        np.testing.assert_array_equal(seg, sca)


@pytest.mark.parametrize("app", list(APP_CALLS))
def test_default_stepwise_and_fused_runners_agree(part, app):
    _, _, rt = part
    fn, kw = APP_CALLS[app]
    a, acts_a = fn(rt, **kw)
    b, acts_b = fn(rt, fused=True, chunk=4, **kw)
    if app == "pagerank":
        np.testing.assert_allclose(a, b, atol=1e-6)
    else:
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(acts_a[:len(acts_b)], acts_b)


class TestWeightedPageRank:
    def test_matches_dense_oracle(self, weighted_part):
        """The `edge_weight`-vs-`edge_valid` bug regression: weights must
        actually scale messages and the degree normalizer."""
        g, w, rt = weighted_part
        got, _ = pagerank(rt, num_iters=20)
        expect = dense_weighted_pagerank(g, w, num_iters=20)
        np.testing.assert_allclose(got, expect, rtol=2e-4, atol=1e-8)
        # weights genuinely change the answer (guards a silent all-ones)
        uniform = ref.pagerank(g, num_iters=20)
        assert np.abs(got - uniform).max() > 1e-4

    def test_weighted_across_backends(self, weighted_part):
        _, _, rt = weighted_part
        base, _ = pagerank(rt, num_iters=15, backend="scatter")
        for be, opts in OTHER:
            got, _ = pagerank(rt, num_iters=15, backend=be, **opts)
            np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)

    def test_default_unit_weights_unchanged(self, part):
        """No weights supplied == the classic uniform-split PageRank."""
        g, _, rt = part
        got, _ = pagerank(rt, num_iters=15)
        np.testing.assert_allclose(got, ref.pagerank(g, num_iters=15),
                                   rtol=2e-4)

    def test_weighted_degree_field(self, weighted_part):
        g, w, rt = weighted_part
        wdeg = np.zeros(g.num_vertices)
        np.add.at(wdeg, g.edges[:, 0], w)
        np.add.at(wdeg, g.edges[:, 1], w)
        for i in range(rt.p):
            m = rt.vertex_valid[i]
            np.testing.assert_allclose(
                rt.weighted_degree[i, m],
                wdeg[rt.local_vertex_gid[i, m]], rtol=1e-6)


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.bsp import (PartitionRuntime, pagerank, sssp, bfs,
                       connected_components)
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat

g = rmat(9, seed=2)
cl = scaled_paper_cluster(2, 6, g.num_edges)   # p = 8 machines = 8 devices
r = windgp(g, cl, t0=2)
rt = PartitionRuntime.build(g, r.assign, cl.p)
mesh = jax.make_mesh((8,), ("machines",))
pr0, _ = pagerank(rt, num_iters=8, backend="scatter")
d0, _ = sssp(rt, source=0, num_iters=12, backend="scatter")
b0, _ = bfs(rt, source=1, num_iters=12, backend="scatter")
c0, _ = connected_components(rt, num_iters=12, backend="scatter")
for be, kw in (("scatter", {}), ("segment", {}),
               ("pallas", {"block_size": 32})):
    pr, _ = pagerank(rt, num_iters=8, mesh=mesh, backend=be, **kw)
    np.testing.assert_allclose(pr, pr0, rtol=1e-5, atol=1e-5)
    d, _ = sssp(rt, source=0, num_iters=12, mesh=mesh, backend=be, **kw)
    np.testing.assert_array_equal(d, d0)
    b, _ = bfs(rt, source=1, num_iters=12, mesh=mesh, backend=be, **kw)
    np.testing.assert_array_equal(b, b0)
    c, _ = connected_components(rt, num_iters=12, mesh=mesh, backend=be,
                                **kw)
    np.testing.assert_array_equal(c, c0)
print("MULTIDEV_BACKENDS_OK")
"""


def test_backends_on_8_device_mesh():
    """Every backend under a real shard_map mesh == the vmap scatter
    reference — including Pallas through ``check_rep=False``."""
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "MULTIDEV_BACKENDS_OK" in out.stdout, out.stderr[-2000:]
