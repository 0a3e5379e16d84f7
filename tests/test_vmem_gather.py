"""The VMEM gather kernel (``kernels/vmem_gather``) in interpret mode:
bitwise ``x[ids]``, alone, under ``vmap`` and under ``shard_map``; and
``segment`` with the kernel forced on gives bitwise the answers of
``segment`` with XLA's gather.  On the CPU the combine keeps XLA's
gather: the kernel is forced on here through the backend's predicate,
a test-only seam."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.bsp import PartitionRuntime, backends, bfs, pagerank, sssp
from repro.bsp.backends import SegmentLayout
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat
from repro.kernels.vmem_gather import TILE, table_bytes, vmem_gather


def _case(vmax, n, seed=0, batch=()):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(batch + (vmax,)).astype(np.float32)
    ids = rng.integers(0, vmax, batch + (n,)).astype(np.int32)
    ids[..., :3] = [0, vmax - 1, 0]           # both ends of the table
    return x, ids


@pytest.mark.parametrize("vmax,tiles", [(1, 1), (128, 2), (1000, 3),
                                        (3001, 20), (1024, 17)])
def test_it_gathers_bitwise(vmax, tiles):
    """``vmax`` on and off the 128-lane and 1,024-value grid, and a
    message count that leaves the last grid step part full."""
    x, ids = _case(vmax, tiles * TILE)
    got = np.asarray(vmem_gather(jnp.asarray(x), jnp.asarray(ids)))
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(got.view(np.uint32),
                                  x[ids].view(np.uint32))


def test_it_moves_bits_not_values():
    """NaNs, infinities, signed zeros and denormals come through as
    they went in."""
    special = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-45,
                        -1e-45, 3.4e38], np.float32)
    x = np.resize(special, 515)
    ids = np.random.default_rng(1).integers(0, 515, TILE).astype(np.int32)
    got = np.asarray(vmem_gather(jnp.asarray(x), jnp.asarray(ids)))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  x[ids].view(np.uint32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gathering_then_casting_is_casting_then_gathering(dtype):
    """The combine gathers the float32 state and casts the messages:
    bitwise the messages of casting the state first."""
    x, ids = _case(2000, 2 * TILE, seed=2)
    x, ids = jnp.asarray(x), jnp.asarray(ids)
    got = vmem_gather(x, ids).astype(dtype)
    want = x.astype(dtype)[ids]
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_eight_machines_under_vmap():
    x, ids = _case(777, 3 * TILE, seed=3, batch=(8,))
    got = jax.jit(jax.vmap(vmem_gather))(jnp.asarray(x), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.take_along_axis(x, ids, axis=1))


def test_four_devices_under_shard_map():
    """One machine a device, with shard_map's replication check on."""
    devices = jax.devices()[:4]
    assert len(devices) == 4
    mesh = jax.sharding.Mesh(np.array(devices), ("machines",))
    x, ids = _case(1500, 2 * TILE, seed=4, batch=(4,))

    def body(x, ids):
        return vmem_gather(x[0], ids[0])[None]

    run = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(P("machines"), P("machines")),
                                out_specs=P("machines"), check_vma=True))
    got = run(jnp.asarray(x), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.take_along_axis(x, ids, axis=1))


def test_the_table_is_padded_to_whole_tiles():
    assert table_bytes(1) == TILE * 4
    assert table_bytes(TILE) == TILE * 4
    assert table_bytes(TILE + 1) == 2 * TILE * 4
    assert table_bytes(246116) == 1928 * 128 * 4


# ---------------------------------------------------------------------------
# segment with the kernel forced on
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rt():
    g = rmat(8, seed=2)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    return PartitionRuntime.build(g, windgp(g, cl, t0=2).assign, cl.p)


def test_the_cpu_keeps_xlas_gather(rt):
    assert jax.default_backend() == "cpu"
    assert not backends.takes_vmem_gather(rt.vmax)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus", "or_and"])
def test_the_layouts_rows_gather_bitwise(rt, semiring):
    """The segment layout's own ids: real messages, the dump run's and
    the padding's (input id 0)."""
    lay = SegmentLayout.build(rt, semiring, "weight", "float32")
    assert lay.in_ids.shape[1] % TILE == 0
    x = np.random.default_rng(5).standard_normal(
        (rt.p, rt.vmax)).astype(np.float32)
    got = jax.vmap(vmem_gather)(jnp.asarray(x), jnp.asarray(lay.in_ids))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.take_along_axis(x, lay.in_ids, axis=1))


APPS = {"pagerank": (pagerank, dict(num_iters=6)),
        "sssp": (sssp, dict(source=0, num_iters=12)),
        "bfs": (bfs, dict(source=1, num_iters=12))}


@pytest.mark.parametrize("message_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("app", list(APPS))
def test_segment_with_the_kernel_answers_bitwise(rt, monkeypatch, app,
                                                 message_dtype):
    fn, kw = APPS[app]
    off, acts_off = fn(rt, message_dtype=message_dtype, **kw)
    monkeypatch.setattr(backends, "takes_vmem_gather", lambda vmax: True)
    on, acts_on = fn(rt, message_dtype=message_dtype, **kw)
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(acts_on, acts_off)


def test_each_call_counts_whether_it_took_the_kernel(rt, monkeypatch):
    obs.reset()
    pagerank(rt, num_iters=1)
    monkeypatch.setattr(backends, "takes_vmem_gather", lambda vmax: True)
    pagerank(rt, num_iters=1)
    calls = [r for r in obs.requests() if r.name == "bsp.call"]
    assert [r.counters["bsp.vmem_gathers"] for r in calls] == [0, 1]
    assert obs.totals()["counts"]["bsp.vmem_gathers"] == 1


@pytest.mark.parametrize("app", ["pagerank", "sssp"])
def test_segment_with_the_kernel_on_a_4_device_mesh(monkeypatch, app):
    """One machine a device, through the apps' ``shard_map`` step: the
    kernel forced on answers bitwise as XLA's gather does."""
    g = rmat(8, seed=3)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    rt4 = PartitionRuntime.build(g, windgp(g, cl, t0=2).assign, cl.p)
    assert rt4.p == 4
    mesh = jax.make_mesh((4,), ("machines",), devices=jax.devices()[:4])
    fn, kw = APPS[app]
    off, acts_off = fn(rt4, mesh=mesh, **kw)
    monkeypatch.setattr(backends, "takes_vmem_gather", lambda vmax: True)
    obs.reset()
    on, acts_on = fn(rt4, mesh=mesh, **kw)
    assert obs.totals()["counts"]["bsp.vmem_gathers"] == 1
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(acts_on, acts_off)
