"""The SpMV work count depends on V and E alone."""
import pytest

from bench import work


@pytest.mark.parametrize("app", ["pagerank", "sssp"])
def test_work_is_linear_in_vertices_and_edges(app):
    ops, nbytes = work.spmv_superstep(app, 1000, 5000)
    ops2, nbytes2 = work.spmv_superstep(app, 2000, 10000)
    assert ops2 == pytest.approx(2 * ops)
    assert nbytes2 == pytest.approx(2 * nbytes)
    # 2E directed edge indices plus four words per vertex
    assert nbytes == 4 * 2 * 5000 + 16 * 1000


def test_work_of_one_graph_ignores_the_partition(tiny_root):
    """Two partitions of one graph (other p, vmax, emax) read the same
    work: a job counts the graph's V and E, not the runtime's layout."""
    from bench.harness import Clock, load_cell
    cell = load_cell(tiny_root, "graph500-19.pagerank")
    seen = []
    for n_super, n_normal in ((1, 1), (2, 6)):
        cell.config["partition"].update(n_super=n_super, n_normal=n_normal)
        wl = cell.kind.Workload(cell, seed=5)
        wl.build(Clock())
        seen.append(((wl.rt.p, wl.rt.vmax, wl.rt.emax), wl.spmv_work()))
    (layout_a, work_a), (layout_b, work_b) = seen
    assert layout_a != layout_b
    assert work_a == work_b
