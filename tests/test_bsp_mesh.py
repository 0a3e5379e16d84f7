"""The apps' normal path on a 4-device ``machines`` mesh, one machine per
device: answers equal the NumPy oracles and the vmap run; a call uploads
each machine's rows straight to its own device and moves nothing between
devices afterwards but the exchange's all-reduce, whose bytes both
runners count; the lowered mesh superstep holds no scatter and no
graph-sized constant.  The mesh tests run in a subprocess with four CPU
devices; the vmap path is pinned in this process."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.bsp import PartitionRuntime, apps, pagerank
from repro.bsp.engine import make_step
from repro.core import scaled_paper_cluster, windgp
from repro.data import graph500

MESH_SCRIPT = r"""
import math, os, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components as components
from repro import obs
from repro.bsp import (PartitionRuntime, apps, bfs, build_app,
                       connected_components, pagerank, ref, sssp)
from repro.bsp.engine import make_fused_runner, make_step, placed_on
from repro.core import scaled_paper_cluster, windgp
from repro.data import graph500

g = graph500(10, seed=3)
cl = scaled_paper_cluster(1, 3, g.num_edges)   # p = 4 machines = 4 devices
rt = PartitionRuntime.build(g, windgp(g, cl, t0=2).assign, cl.p)
assert rt.p == 4
mesh = jax.make_mesh((4,), ("machines",), devices=jax.devices()[:4])
n, steps = g.num_vertices, 30
# roots with an edge: the program holds no isolated vertex
a, b = np.flatnonzero(g.degree() > 0)[[5, 7]]

# the oracles: float64 PageRank, unit-weight SSSP/BFS, min-label components
n_comp, comp = components(coo_matrix(
    (np.ones(g.num_edges), (g.edges[:, 0], g.edges[:, 1])), shape=(n, n)))
least = np.full(n_comp, np.inf)
np.minimum.at(least, comp, np.arange(n, dtype=np.float64))
cc_want = np.where(g.degree() > 0, least[comp], np.inf)
want = {"pagerank": ref.pagerank(g, num_iters=20),
        "sssp": ref.sssp(g, source=a, num_iters=steps),
        "bfs": ref.bfs(g, source=b, num_iters=steps),
        "cc": cc_want}
calls = {"pagerank": lambda **kw: pagerank(rt, 20, **kw),
         "sssp": lambda **kw: sssp(rt, a, steps, **kw),
         "bfs": lambda **kw: bfs(rt, b, steps, **kw),
         "cc": lambda **kw: connected_components(rt, steps, **kw)}

# every leaf the normal path hands its runner, kept for the shard check
specs = []
run = apps._run
apps._run = lambda spec, *args, **kw: (specs.append(spec),
                                       run(spec, *args, **kw))[1]

for name, call in calls.items():
    for fused in (False, True):
        obs.reset()
        with jax.transfer_guard_device_to_device("disallow"):
            got, acts = call(mesh=mesh, fused=fused)
        ran = len(acts)
        req = [r for r in obs.requests() if r.name == "bsp.call"][-1]
        assert req.root.attrs["devices"] == 4, req.root.attrs
        table = (max(1, rt.num_replicas) + 1) * 4
        assert req.counters["bsp.exchange_bytes"] == table * ran, (
            name, fused, req.counters.get("bsp.exchange_bytes"), table, ran)
        vmap, _ = call(fused=fused)
        assert "bsp.exchange_bytes" not in obs.requests()[-1].counters
        assert obs.requests()[-1].root.attrs["devices"] == 1
        if name == "pagerank":
            rel = np.abs(got - want[name]) / want[name]
            assert rel.max() <= 1e-6, rel.max()
            np.testing.assert_allclose(got, vmap, rtol=1e-6, atol=0)
        else:
            np.testing.assert_array_equal(got, want[name])
            np.testing.assert_array_equal(got, vmap)

# the mesh calls' state and static: each leaf one row on each device
mesh_specs = specs[0::2]
assert len(mesh_specs) == 8
for spec in mesh_specs:
    for leaf in jax.tree.leaves((spec.state, spec.static)):
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == 4, leaf.sharding
        assert all(s.data.shape[0] == 1 for s in shards), leaf.shape


def largest_constant(text):
    sizes = [1]
    for line in text.splitlines():
        if "constant" in line:
            for dims in re.findall(r"tensor<([0-9x]+)x[a-z]", line):
                sizes.append(math.prod(int(d) for d in dims.split("x")))
    return max(sizes)


# the mesh superstep, alone and fused: no scatter, the graph an argument
for app in ("pagerank", "sssp", "bfs"):
    with placed_on(mesh):
        spec = build_app(rt, app)
    graph = min(leaf.size for leaf in jax.tree.leaves(spec.static))
    for lowered in (
            make_step(spec.superstep, spec.static, mesh=mesh).lower(
                spec.state),
            make_fused_runner(spec.superstep, spec.static,
                              mesh=mesh).lower(spec.state, 10)):
        text = lowered.as_text()
        assert "scatter" not in text, app
        assert largest_constant(text) < graph, app
print("MESH_PATH_OK")
"""


def test_the_normal_path_on_a_4_device_mesh():
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"})
    assert "MESH_PATH_OK" in out.stdout, out.stderr[-3000:]


@pytest.fixture(scope="module")
def rt():
    g = graph500(8, seed=3)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    return PartitionRuntime.build(g, windgp(g, cl, t0=2).assign, cl.p)


def test_without_a_mesh_a_call_uploads_as_before(rt, monkeypatch):
    """Under vmap every leaf the call hands its runner sits on the default
    device, as a plain upload puts it, and the superstep lowers to the
    same program text as from plain uploads of the same arrays."""
    specs = []
    run = apps._run
    monkeypatch.setattr(apps, "_run", lambda spec, *a, **kw: (
        specs.append(spec), run(spec, *a, **kw))[1])
    pagerank(rt, num_iters=2)
    spec, = specs
    default = SingleDeviceSharding(jax.devices()[0])
    leaves = jax.tree.leaves((spec.state, spec.static))
    assert all(leaf.sharding == default for leaf in leaves)
    plain = lambda tree: jax.tree.map(
        lambda a: jnp.asarray(np.asarray(a)), tree)
    text = make_step(spec.superstep, spec.static).lower(
        spec.state).as_text()
    assert text == make_step(spec.superstep, plain(spec.static)).lower(
        plain(spec.state)).as_text()


def test_without_a_mesh_no_exchange_bytes_are_counted(rt):
    obs.reset()
    pagerank(rt, num_iters=3)
    pagerank(rt, num_iters=3, fused=True)
    assert "bsp.exchange_bytes" not in obs.totals()["counts"]
    assert [r.root.attrs["devices"] for r in obs.requests()] == [1, 1]
