"""Compile the device path for a described TPU v5e, without the chip.

XLA:TPU and Mosaic refuse what the CPU backend and the Pallas interpreter
accept: blocks off the (8, 128) tile, contractions Mosaic cannot lower.
These tests compile, for one chip of a ``v5e:2x2`` topology, the
Block-ELL kernel at ``block_size=128`` under every semiring, the VMEM
gather at graph500-19's per-machine shapes, the fused k-hop sampler,
and one fused PageRank superstep program per edge-kernel backend (and
``segment`` taking the VMEM gather, as it does on a TPU), with the
runtime's shapes as ``ShapeDtypeStruct``\\s.  Nothing runs.  The
topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.bsp import PartitionRuntime, backends, build_app, make_fused_runner
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat
from repro.kernels.bsr_spmv import spmv_pallas
from repro.kernels.vmem_gather import TILE, table_bytes, vmem_gather
from repro.sampling.service import _sample_khop_jit


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def shapes_on(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)


@pytest.mark.parametrize("semiring,dtype", [
    ("plus_times", jnp.float32), ("plus_times", jnp.bfloat16),
    ("min_plus", jnp.float32), ("or_and", jnp.float32)])
def test_block_ell_kernel_compiles(one_chip, semiring, dtype):
    R, K, bm = 64, 8, 128
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((R, K), jnp.int32), ((R, K, bm, bm), dtype), ((R * bm,), dtype))]
    kernel = jax.jit(lambda c, b, x: spmv_pallas(
        c, b, x, block_size=bm, interpret=False, semiring=semiring))
    compiled = kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vmem_gather_compiles_at_graph500_19(one_chip):
    """Eight machines under vmap, each a (246116,) vertex vector and the
    2,142,464 rows of its segment layout padded to whole tiles."""
    p, vmax = 8, 246116
    n = -(-2142464 // TILE) * TILE
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((p, vmax), jnp.float32), ((p, n), jnp.int32))]
    kernel = jax.jit(jax.vmap(functools.partial(vmem_gather,
                                                interpret=False)))
    compiled = kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("machines", [None, 2])
def test_the_largest_table_segment_admits_compiles(one_chip, machines):
    """``takes_vmem_gather`` admits a table up to a quarter of a core's
    VMEM (128 MiB on a v5e): one machine alone, as under ``shard_map``,
    and two under ``vmap``."""
    vmax = (128 << 20) // 4 // 4
    assert table_bytes(vmax) == (128 << 20) // 4
    batch = () if machines is None else (machines,)
    args = [jax.ShapeDtypeStruct(batch + (k,), d, sharding=one_chip)
            for k, d in ((vmax, jnp.float32), (64 * TILE, jnp.int32))]
    gather = functools.partial(vmem_gather, interpret=False)
    kernel = jax.jit(gather if machines is None else jax.vmap(gather))
    compiled = kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_khop_sampler_compiles(one_chip):
    V, rows, D, B = 1 << 14, 1 << 12, 8, 256
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((rows, D), jnp.int32), ((rows,), jnp.int32), ((V,), jnp.int32),
        ((V,), jnp.int32), ((B,), jnp.int32), ((3, 2), jnp.uint32),
        ((), jnp.int32))]
    _sample_khop_jit.lower(*args, fanouts=(15, 10, 5), replace=False,
                           has_home=True).compile()


@pytest.fixture(scope="module")
def runtime():
    g = rmat(9, seed=2)
    cl = scaled_paper_cluster(2, 6, g.num_edges)
    return PartitionRuntime.create(g, assign=windgp(g, cl, t0=2).assign,
                                   cluster=cl)


@pytest.mark.parametrize("backend,opts", [
    ("scatter", {}), ("segment", {}), ("pallas", {"interpret": False})])
def test_fused_pagerank_program_compiles(one_chip, runtime, backend, opts):
    spec = build_app(runtime, "pagerank", backend=backend, **opts)
    run = make_fused_runner(spec.superstep, shapes_on(one_chip, spec.static),
                            check_rep=spec.check_rep)
    compiled = run.lower(shapes_on(one_chip, spec.state), 20).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_fused_pagerank_with_the_vmem_gather_compiles(one_chip, runtime,
                                                      monkeypatch):
    """``segment`` as it runs on a TPU: the combine takes the kernel,
    compiled by Mosaic (the CPU would choose XLA's gather and the
    interpreter)."""
    monkeypatch.setattr(backends, "takes_vmem_gather", lambda vmax: True)
    monkeypatch.setattr(backends, "vmem_gather",
                        functools.partial(vmem_gather, interpret=False))
    spec = build_app(runtime, "pagerank")
    run = make_fused_runner(spec.superstep, shapes_on(one_chip, spec.static),
                            check_rep=spec.check_rep)
    compiled = run.lower(shapes_on(one_chip, spec.state), 20).compile()
    assert "tpu_custom_call" in compiled.as_text()
