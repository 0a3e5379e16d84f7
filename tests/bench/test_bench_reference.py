"""The benchmark's own references agree with the program's test oracles
at a tiny size; its generator keeps the source's shape."""
import numpy as np
import pytest

from bench import graphs, reference
from bench.generators.kronecker import kronecker


@pytest.fixture(scope="module")
def tiny_graph():
    from repro.core import from_edge_list
    e, n = kronecker(9, 16, .57, .19, .19, graphs.rng_for(3, 0))
    w = graphs.edge_weights("uniform01", len(e), 3)
    return from_edge_list(e, n), e, n, w


def test_kronecker_is_canonical_and_keeps_its_shape():
    e, n = kronecker(10, 16, .57, .19, .19, graphs.rng_for(1, 0))
    assert (e[:, 0] < e[:, 1]).all()
    key = e[:, 0].astype(np.int64) * n + e[:, 1]
    assert (np.diff(key) > 0).all()
    # duplicates and self loops go; most of the 16 * 1024 draws stay
    assert 0.6 * 16 * 1024 < len(e) < 16 * 1024
    again, _ = kronecker(10, 16, .57, .19, .19, graphs.rng_for(1, 0))
    assert np.array_equal(e, again)


def test_kronecker_lists_only_vertices_with_an_edge():
    """As Graphalytics' graph500 datasets: of 2**scale labels, those no
    edge touches are dropped and the rest numbered densely."""
    e, n = kronecker(10, 16, .57, .19, .19, graphs.rng_for(1, 0))
    assert 0.4 * 1024 < n < 1024
    assert (np.bincount(e.ravel(), minlength=n) > 0).all()
    assert e.max() == n - 1


def test_dropping_isolated_vertices_keeps_edges_canonical():
    e = np.array([[1, 4], [1, 6], [4, 6]], np.int32)
    got, n = graphs.drop_isolated(e, 8)
    assert n == 3
    assert got.tolist() == [[0, 1], [0, 2], [1, 2]]


def test_seeds_past_64_bits_and_negative_are_valid():
    for seed in (2**31 + 5, 2**70, -3):
        graphs.rng_for(seed, 0).random()


def test_pagerank_matches_program_oracle(tiny_graph):
    from repro.bsp import ref
    g, e, n, _ = tiny_graph
    got = reference.pagerank(reference.adjacency(e, n), 20, 0.85)
    np.testing.assert_allclose(got, ref.pagerank(g, 20, 0.85), rtol=1e-12)


def test_sssp_matches_program_oracle(tiny_graph):
    from repro.bsp import ref
    g, e, n, w = tiny_graph
    adj = reference.adjacency(e, n, w)
    root = int(e[0, 0])
    want = ref.sssp(g, root, w.astype(np.float64), num_iters=200)
    assert reference.rel_error(reference.sssp(adj, root), want) < 1e-12


def test_rel_error_flags_reachability_and_zero_distances():
    assert reference.rel_error([0.0, 1.0], [0.0, 1.0]) == 0.0
    assert reference.rel_error([0.0, np.inf], [0.0, 1.0]) == np.inf
    assert reference.rel_error([1e-3, 1.0], [0.0, 1.0]) == np.inf
    assert reference.rel_error([0.0, 1.1], [0.0, 1.0]) == pytest.approx(0.1)
