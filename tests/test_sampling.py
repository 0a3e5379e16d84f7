"""Partitioned GNN minibatch sampling + the unified construction API.

Five contracts, each tested here:

* ``MachineCSC`` packs, per machine, the full global adjacency of its
  owned vertices (degree-sorted rows, ``-1`` pad) — every vertex with an
  edge gets exactly one owner, isolated vertices get ``-1``;
* the jax fanout sampler equals its NumPy oracle **bitwise** on the same
  PRNG key in both replacement modes, samples only true neighbors, and
  never repeats a neighbor without replacement;
* a minibatch is a pure function of ``(partition, seeds, key)`` —
  bitwise identical across repeated runs and across equal-content
  runtimes built through *different* ``create`` routes (in-memory
  assignment vs on-disk stream), with empty-frontier and
  isolated-vertex seeds handled as all-``-1`` lanes;
* ``PartitionRuntime.create`` routes by keywords, builds bit-identical
  runtimes to every legacy constructor, and rejects conflicting routes;
  an app's keyword knobs are checked once (tol on a monotone app, an
  unknown backend, frontier_cap off-scatter);
* the registry's knob errors name the offending partitioner and its
  valid knobs, and ``windgp``'s ``train_balance`` knob reduces
  train-vertex skew while the default stays bit-identical.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.bsp import (MONOTONE_APPS, PartitionRuntime, StreamAssignment,
                       get_backend, sssp)
from repro.core import from_edge_list, scaled_paper_cluster
from repro.core import partitioners as registry
from repro.core.partition_state import PartitionState, edge_incidence_counts
from repro.data import rmat
from repro.sampling import (MachineCSC, SamplingService, sample_fanout,
                            sample_fanout_np)


@pytest.fixture(scope="module")
def small():
    """Graph + cluster + hdrf assignment shared by the sampling tests."""
    g = rmat(8, edge_factor=8, seed=3)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    assign = registry.get("hdrf")(g, cl)
    return g, cl, assign


def _neighbors(g):
    nbrs = {v: [] for v in range(g.num_vertices)}
    for u, v in g.edges:
        nbrs[int(u)].append(int(v))
        nbrs[int(v)].append(int(u))
    return nbrs


class TestMachineCSC:
    def test_owner_and_rows_cover_global_adjacency(self, small):
        g, cl, assign = small
        csc = MachineCSC.build(PartitionRuntime.create(g, assign=assign,
                                                       cluster=cl))
        nbrs = _neighbors(g)
        deg = np.array([len(nbrs[v]) for v in range(g.num_vertices)])
        # one owner per non-isolated vertex, -1 for isolated
        assert np.array_equal(csc.owner >= 0, deg > 0)
        assert csc.owner.max() < cl.p
        rowmap = csc.flat_rowmap()
        flat_nbr = csc.nbr.reshape(-1, csc.max_degree)
        flat_deg = csc.deg.reshape(-1)
        for v in range(g.num_vertices):
            if deg[v] == 0:
                continue
            r = rowmap[v]
            assert flat_deg[r] == deg[v]
            row = flat_nbr[r]
            assert sorted(row[:deg[v]].tolist()) == sorted(nbrs[v])
            assert (row[deg[v]:] == -1).all()

    def test_rows_degree_sorted_per_machine(self, small):
        g, cl, assign = small
        csc = MachineCSC.build(PartitionRuntime.create(g, assign=assign,
                                                       cluster=cl))
        for i in range(csc.p):
            n = int(csc.owned_per[i])
            d = csc.deg[i, :n]
            assert (np.diff(d) <= 0).all(), "owned rows not degree-sorted"

    def test_isolated_vertex_owner_is_minus_one(self):
        g = from_edge_list(np.array([[0, 1], [1, 2]]), num_vertices=5)
        cl = scaled_paper_cluster(1, 1, g.num_edges)
        csc = MachineCSC.build(
            PartitionRuntime.create(g, assign=np.zeros(2, np.int32),
                                    cluster=cl))
        assert (csc.owner[3:] == -1).all()
        assert (csc.owner[:3] == 0).all()


class TestSamplerOracle:
    @pytest.mark.parametrize("replace", [False, True])
    def test_bitwise_equals_numpy_oracle(self, small, replace):
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        rows = svc.csc.flat_rowmap()[np.arange(g.num_vertices)]
        key = jax.random.PRNGKey(7)
        got = np.asarray(sample_fanout(svc._table, svc._deg, rows, key, 6,
                                       replace=replace))
        want = sample_fanout_np(np.asarray(svc._table),
                                np.asarray(svc._deg), rows, key, 6,
                                replace=replace)
        assert np.array_equal(got, want)

    def test_samples_are_true_neighbors_no_dups(self, small):
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        nbrs = _neighbors(g)
        key = jax.random.PRNGKey(1)
        seeds = svc.local_seeds(0, 32, key)
        mb = svc.sample(seeds, jax.random.fold_in(key, 1), home=0)
        hop0 = mb.hops[0].reshape(len(seeds), -1)
        for s, row in zip(seeds.tolist(), hop0):
            picked = row[row >= 0].tolist()
            assert set(picked) <= set(nbrs[s])
            assert len(picked) == len(set(picked)), \
                "without-replacement repeated a neighbor"
            assert len(picked) == min(len(nbrs[s]), svc.fanouts[0])


class TestDeterminism:
    def test_same_key_same_minibatch(self, small):
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        key = jax.random.PRNGKey(11)
        seeds = svc.local_seeds(1, 16, key)
        a = svc.sample(seeds, jax.random.fold_in(key, 5), home=1)
        b = svc.sample(seeds, jax.random.fold_in(key, 5), home=1)
        for ha, hb in zip(a.hops, b.hops):
            assert np.array_equal(ha, hb)
        assert a.hop_stats == b.hop_stats

    def test_bitwise_across_create_routes(self, small, tmp_path):
        """Same partition through the in-memory route and the on-disk
        stream route yields the bitwise-same minibatch."""
        g, cl, assign = small
        sa = StreamAssignment(tmp_path / "assign", cl.p, g.num_vertices)
        sa.sink(g.edges, assign)
        sa.finalize(edge_incidence_counts(g, assign, cl.p) > 0,
                    {"method": "hdrf"})
        key = jax.random.PRNGKey(2)
        batches = []
        for source_kw in (dict(source=g, assign=assign, cluster=cl),
                          dict(source=g, assign=assign, p=cl.p),
                          dict(source=sa)):
            svc = SamplingService.create(fanouts=(5, 3), **source_kw)
            seeds = svc.local_seeds(0, 16, key)
            batches.append(svc.sample(seeds, jax.random.fold_in(key, 3),
                                      home=0))
        for mb in batches[1:]:
            assert np.array_equal(mb.seeds, batches[0].seeds)
            for ha, hb in zip(mb.hops, batches[0].hops):
                assert np.array_equal(ha, hb)
            assert mb.hop_stats == batches[0].hop_stats

    def test_empty_frontier(self, small):
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        mb = svc.sample(np.empty(0, np.int32), jax.random.PRNGKey(0),
                        home=0)
        assert all(h.size == 0 for h in mb.hops)
        assert all(s.frontier == 0 and s.halo == 0 for s in mb.hop_stats)

    def test_isolated_seed_samples_all_pad(self):
        g = from_edge_list(np.array([[0, 1]]), num_vertices=4)
        cl = scaled_paper_cluster(1, 1, g.num_edges)
        svc = SamplingService(
            PartitionRuntime.create(g, assign=np.zeros(1, np.int32),
                                    cluster=cl), fanouts=(3, 2))
        mb = svc.sample(np.array([2, 3], np.int32), jax.random.PRNGKey(0),
                        home=0)
        assert all((h == -1).all() for h in mb.hops)
        assert mb.num_sampled() == 0

    def test_out_of_range_seed_raises(self, small):
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        with pytest.raises(ValueError, match="seed ids"):
            svc.sample(np.array([g.num_vertices], np.int32),
                       jax.random.PRNGKey(0))

    def test_seed_below_minus_one_raises(self, small):
        """Only -1 is the pad lane; -2 etc. would silently alias rows
        through the clip — must be rejected, not sampled."""
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        with pytest.raises(ValueError, match="pad lane"):
            svc.sample(np.array([0, -2], np.int32), jax.random.PRNGKey(0))

    def test_local_seeds_undersized_pool_returns_whole_pool(self, small):
        """When a machine owns fewer (masked) vertices than n, the whole
        pool comes back key-permuted — length min(n, pool), no padding."""
        g, cl, assign = small
        svc = SamplingService(
            PartitionRuntime.create(g, assign=assign, cluster=cl))
        pool = int(svc.csc.owned_per[0])
        seeds = svc.local_seeds(0, pool + 100, jax.random.PRNGKey(4))
        assert len(seeds) == pool
        want = svc.csc.owned_gid[0][:pool]
        assert np.array_equal(np.sort(seeds), np.sort(want))
        # masked variant: pool shrinks to the masked subset
        mask = np.zeros(g.num_vertices, bool)
        mask[want[:3]] = True
        masked = svc.local_seeds(0, 50, jax.random.PRNGKey(4), mask)
        assert len(masked) == 3 and set(masked) == set(want[:3].tolist())

    def test_bad_fanouts_raise(self, small):
        g, cl, assign = small
        rt = PartitionRuntime.create(g, assign=assign, cluster=cl)
        with pytest.raises(ValueError, match="fanouts"):
            SamplingService(rt, fanouts=(5, 0))


class TestCreateFacade:
    def test_assign_route_bit_identical_to_build(self, small):
        g, cl, assign = small
        a = PartitionRuntime.build(g, assign, cl.p)
        b = PartitionRuntime.create(g, assign=assign, p=cl.p)
        c = PartitionRuntime.create(g, assign=assign, cluster=cl)
        for f in dataclasses.fields(a):
            va = getattr(a, f.name)
            for other in (b, c):
                vo = getattr(other, f.name)
                if isinstance(va, np.ndarray):
                    assert np.array_equal(va, vo), f.name
                else:
                    assert va == vo, f.name

    def test_method_route_bit_identical_to_from_partitioner(self, small):
        g, cl, _ = small
        a = PartitionRuntime.from_partitioner(g, cl, "hdrf")
        b = PartitionRuntime.create(g, method="hdrf", cluster=cl)
        assert np.array_equal(a.local_edges, b.local_edges)
        assert np.array_equal(a.local_vertex_gid, b.local_vertex_gid)

    def test_stream_route_bit_identical_to_from_stream(self, small,
                                                       tmp_path):
        g, cl, assign = small
        sa = StreamAssignment(tmp_path / "a", cl.p, g.num_vertices)
        sa.sink(g.edges, assign)
        sa.finalize(edge_incidence_counts(g, assign, cl.p) > 0, {})
        a = PartitionRuntime.from_stream(sa)
        b = PartitionRuntime.create(sa)
        c = PartitionRuntime.create(str(tmp_path / "a"))
        for other in (b, c):
            assert np.array_equal(a.local_edges, other.local_edges)
            assert np.array_equal(a.local_vertex_gid,
                                  other.local_vertex_gid)

    def test_route_conflicts_raise(self, small, tmp_path):
        g, cl, assign = small
        sa = StreamAssignment(tmp_path / "a", cl.p, g.num_vertices)
        sa.sink(g.edges, assign)
        sa.finalize(edge_incidence_counts(g, assign, cl.p) > 0, {})
        with pytest.raises(ValueError, match="requires source="):
            PartitionRuntime.create()
        with pytest.raises(ValueError, match="takes only"):
            PartitionRuntime.create(sa, assign=assign)
        with pytest.raises(ValueError, match="drop assign"):
            PartitionRuntime.create(g, method="hdrf", cluster=cl,
                                    assign=assign)
        with pytest.raises(ValueError, match="requires cluster="):
            PartitionRuntime.create(g, method="hdrf")
        with pytest.raises(ValueError):
            PartitionRuntime.create(g, assign=assign)  # no p=/cluster=
        with pytest.raises(ValueError):
            PartitionRuntime.create(42)


class TestRunOptions:
    def test_tol_rejected_on_monotone_apps(self, small):
        g, cl, assign = small
        rt = PartitionRuntime.create(g, assign=assign, cluster=cl)
        assert "sssp" in MONOTONE_APPS
        with pytest.raises(ValueError, match="monotone"):
            sssp(rt, source=0, tol=1e-3)

    def test_frontier_cap_is_scatter_only(self):
        with pytest.raises(ValueError, match="scatter"):
            get_backend("segment", frontier_cap=8)

    def test_unknown_backend_named(self):
        with pytest.raises(ValueError, match="unknown edge-kernel backend"):
            get_backend("nope")


class TestRegistryKnobErrors:
    def test_error_names_partitioner_and_valid_knobs(self, small):
        g, cl, _ = small
        with pytest.raises(TypeError) as ei:
            registry.get("hdrf")(g, cl, bogus=1)
        msg = str(ei.value)
        assert "partitioner 'hdrf'" in msg
        assert "bogus" in msg
        assert "valid knobs for 'hdrf'" in msg

    def test_error_lists_training_knobs_for_windgp(self, small):
        g, cl, _ = small
        with pytest.raises(TypeError, match="train_balance"):
            registry.get("windgp")(g, cl, bogus=1)


class TestTrainBalance:
    def test_default_bitwise_identical_without_mask(self, small):
        g, cl, _ = small
        wind = registry.get("windgp")
        a = wind(g, cl, t0=6, alpha=0.1, beta=0.1)
        train = np.zeros(g.num_vertices, bool)
        b = wind(g, cl, t0=6, alpha=0.1, beta=0.1, train_balance=0.0)
        assert np.array_equal(a, b)

    def test_balance_knob_reduces_train_skew(self):
        g = rmat(10, edge_factor=7, seed=42)
        cl = scaled_paper_cluster(2, 4, g.num_edges)
        train = np.random.default_rng(0).random(g.num_vertices) < 0.1
        wind = registry.get("windgp")

        def skew(assign):
            member = edge_incidence_counts(g, assign, cl.p) > 0
            c = member[:, train].sum(axis=1).astype(np.float64)
            return float(c.max() / c.mean())

        s_def = skew(wind(g, cl, t0=6, alpha=0.1, beta=0.1))
        s_bal = skew(wind(g, cl, t0=6, alpha=0.1, beta=0.1,
                          train_mask=train, train_balance=1.0))
        assert s_bal < s_def

    def test_weighted_state_cost_and_counts(self, small):
        g, cl, assign = small
        train = np.zeros(g.num_vertices, bool)
        train[:16] = True
        st = PartitionState.build(g, assign, cl, train_mask=train,
                                  train_balance=0.5)
        member = edge_incidence_counts(g, assign, cl.p) > 0
        w = 1.0 + 0.5 * train
        want = cl.c_node() * (member.astype(np.float64) @ w) \
            + cl.c_edge() * st.edges_per
        assert np.allclose(st.t_cal, want)
        assert np.array_equal(st.train_counts(train),
                              member[:, train].sum(axis=1))

    def test_bad_train_mask_shape_raises(self, small):
        g, cl, assign = small
        with pytest.raises(ValueError, match="train_mask"):
            PartitionState.build(g, assign, cl,
                                 train_mask=np.zeros(3, bool),
                                 train_balance=1.0)
