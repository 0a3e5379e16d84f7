"""The program's recorder (``repro.obs``) and where the BSP call path and
WindGP set-up put their spans and counters.  Nothing here asserts a time."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.bsp import PartitionRuntime, pagerank, sssp
from repro.bsp.backends import SegmentLayout
from repro.core import scaled_paper_cluster, windgp
from repro.data import rmat


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_spans_nest_under_their_parent_and_share_the_request_id():
    with obs.request("job", kind="x") as req:
        with obs.span("outer") as outer:
            with obs.span("inner") as inner:
                pass
        with obs.span("second") as second:
            pass
    assert obs.requests() == [req]
    assert [s.name for s in req.spans] == ["job", "outer", "inner",
                                            "second"]
    assert req.root.parent is None
    assert outer.parent == second.parent == req.root.id
    assert inner.parent == outer.id
    assert {s.attrs["req"] for s in req.spans} == {req.id}
    assert req.root.attrs["kind"] == "x"
    assert req.children(req.root) == [outer, second]


def test_self_time_is_the_duration_less_the_children():
    with obs.request("job") as req:
        with obs.span("a"):
            with obs.span("a1"):
                pass
        with obs.span("b"):
            pass
    a = req.named("a")[0]
    kids = sum(s.dur_ns for s in req.children(req.root))
    assert req.self_seconds() == pytest.approx(
        (req.root.dur_ns - kids) * 1e-9)
    assert req.self_seconds(a) == pytest.approx(
        (a.dur_ns - req.named("a1")[0].dur_ns) * 1e-9)
    assert req.seconds("b") == req.named("b")[0].seconds


def test_counters_land_on_the_open_request_and_the_totals():
    obs.count("c", 2)
    with obs.request("job") as req:
        obs.count("c")
        obs.count("bytes", 10)
    obs.count("c")
    assert req.counters == {"c": 1, "bytes": 10}
    assert obs.totals()["counts"] == {"c": 4, "bytes": 10}


def test_spans_outside_a_request_reach_the_totals_only():
    with obs.span("setup") as a:
        pass
    with obs.span("setup") as b:
        pass
    assert obs.requests() == []
    assert obs.totals()["spans"]["setup"] == pytest.approx(
        a.seconds + b.seconds)


def test_completed_requests_are_bounded():
    for i in range(obs.KEEP + 5):
        with obs.request("job", i=i):
            obs.count("n")
    kept = obs.requests()
    assert len(kept) == obs.KEEP
    assert kept[0].root.attrs["i"] == 5 and kept[-1].root.attrs["i"] == \
        obs.KEEP + 4
    # the totals keep counting past the bound
    assert obs.totals()["counts"]["n"] == obs.KEEP + 5


def test_each_thread_keeps_its_own_span_stack():
    seen = {}

    def worker():
        with obs.span("elsewhere") as sp:
            obs.count("n")
        seen["span"] = sp

    with obs.request("job") as req:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen["span"].parent is None and "req" not in seen["span"].attrs
    assert req.spans == [req.root] and req.counters == {}
    assert obs.totals()["counts"] == {"n": 1}


def test_the_listener_puts_jax_compile_durations_on_the_request():
    with obs.request("job") as req:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    got = {k for k in req.counters if k in obs.JAX_EVENTS}
    assert {"/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration"} <= got
    assert all(req.counters[k] >= 0 for k in got)


def _union_seconds(spans):
    total, end = 0.0, None
    for s, e in sorted(spans):
        if end is not None and s < end:
            total += max(0.0, e - end)
            end = max(end, e)
        else:
            total += e - s
            end = e
    return total


def test_a_nested_trace_is_counted_once():
    trace = "/jax/core/compile/jaxpr_trace_duration"
    seen = []

    def spy(event, start, end, **_):
        if event == trace:
            seen.append((start, end))

    def inner(x):
        return x * 2

    @jax.jit
    def outer(x):
        jax.eval_shape(inner, x)        # traced apart, inside outer's trace
        return inner(x) + 1

    obs.listen()
    jax.monitoring.register_event_time_span_listener(spy)
    try:
        with obs.request("job") as req:
            outer(jnp.arange(5.0)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(spy)
    assert len(seen) >= 2
    assert req.counters[trace] == pytest.approx(_union_seconds(seen))
    assert req.counters[trace] < sum(e - s for s, e in seen)


def test_outside_a_request_the_totals_get_every_compile_duration():
    compile_ = "/jax/core/compile/backend_compile_duration"
    obs.listen()
    jax.jit(lambda x: x - 5)(jnp.arange(3.0)).block_until_ready()
    assert obs.requests() == []
    assert obs.totals()["counts"][compile_] > 0


# ---------------------------------------------------------------------------
# the BSP call path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rt():
    g = rmat(8, seed=3)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    return PartitionRuntime.create(g, method="windgp", cluster=cl, t0=2)


def _vertex_bytes(rt):
    return sum(a.nbytes for a in (
        rt.vertex_valid, rt.weighted_degree, rt.rep_slot, rt.slot_vertex))


def _static_bytes(rt, semiring):
    """What a default call uploads besides its state: the per-vertex
    arrays and the segment layout, no edge array."""
    lay = SegmentLayout.build(rt, semiring, "weight", "float32")
    return _vertex_bytes(rt) + sum(a.nbytes for a in (
        lay.in_ids, lay.w, lay.level, lay.run_end))


def _one_call():
    calls = [r for r in obs.requests() if r.name == "bsp.call"]
    assert len(calls) == 1
    return calls[0]


@pytest.mark.parametrize("app", ["pagerank", "sssp"])
def test_a_stepwise_call_is_prepare_supersteps_gather(rt, app):
    n = 6
    if app == "pagerank":
        pagerank(rt, num_iters=n)
        state_bytes = rt.vertex_valid.nbytes
        semiring = "plus_times"
    else:
        sssp(rt, source=1, num_iters=n)
        state_bytes = rt.p * rt.vmax * (4 + 1)       # dist, changed
        semiring = "min_plus"
    req = _one_call()
    assert req.root.attrs == {"app": app, "runner": "stepwise",
                              "backend": "segment", "supersteps": n,
                              "devices": 1, "req": req.id}
    top = [s.name for s in req.children(req.root)]
    assert top == ["bsp.prepare"] + ["bsp.superstep"] * n + ["bsp.gather"]
    steps = req.named("bsp.superstep")
    assert [s.attrs["step_num"] for s in steps] == list(range(n))
    # each superstep reads its active counts, the gather the answer
    for s in steps + req.named("bsp.gather"):
        assert [c.name for c in req.children(s)] == ["bsp.sync"]
    assert req.counters["bsp.retraces"] == 1
    assert req.counters["bsp.host_syncs"] == n + 1
    assert req.counters["bsp.h2d_bytes"] == \
        _static_bytes(rt, semiring) + state_bytes


def test_a_fused_call_dispatches_once_and_syncs_three_times(rt):
    pagerank(rt, num_iters=5, fused=True)
    req = _one_call()
    assert req.root.attrs["runner"] == "fused"
    top = [s.name for s in req.children(req.root)]
    assert top == ["bsp.prepare", "bsp.sync", "bsp.sync", "bsp.gather"]
    assert req.counters["bsp.retraces"] == 1
    assert req.counters["bsp.host_syncs"] == 3      # ts, bufs, the answer
    assert req.counters["bsp.h2d_bytes"] == \
        _static_bytes(rt, "plus_times") + rt.vertex_valid.nbytes


def test_the_layout_is_built_on_the_first_call_only():
    g = rmat(7, seed=6)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    rt = PartitionRuntime.create(g, method="windgp", cluster=cl, t0=2)
    for _ in range(2):
        pagerank(rt, num_iters=2)
    calls = obs.requests()
    assert [r.counters.get("bsp.layout_builds", 0) for r in calls] == [1, 0]
    assert obs.totals()["counts"]["bsp.layout_builds"] == 1


def test_a_default_call_uploads_no_edge_array(rt):
    """``edges``, ``edge_weight`` and ``edge_valid`` go up only for the
    backend that reads them."""
    edge_bytes = sum(a.nbytes for a in (
        rt.local_edges, rt.edge_valid, rt.edge_weight))
    state_bytes = rt.vertex_valid.nbytes
    pagerank(rt, num_iters=1)
    pagerank(rt, num_iters=1, backend="scatter")
    default, scatter = [r.counters["bsp.h2d_bytes"] for r in obs.requests()]
    assert default == _static_bytes(rt, "plus_times") + state_bytes
    assert scatter == _vertex_bytes(rt) + edge_bytes + state_bytes


def test_every_app_call_traces_its_superstep_once(rt):
    for _ in range(2):
        sssp(rt, source=2, num_iters=3)
    calls = obs.requests()
    assert [r.counters["bsp.retraces"] for r in calls] == [1, 1]
    assert obs.totals()["counts"]["bsp.retraces"] == 2


# ---------------------------------------------------------------------------
# WindGP set-up
# ---------------------------------------------------------------------------

def test_phase_seconds_are_the_windgp_spans():
    g = rmat(8, seed=4)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    mask = np.zeros(g.num_vertices, bool)
    mask[::5] = True
    res = windgp(g, cl, t0=2, train_mask=mask, train_balance=1.0)
    spans = obs.totals()["spans"]
    assert set(res.phase_seconds) == {"preprocess", "expand", "sls",
                                      "train_balance"}
    for phase, secs in res.phase_seconds.items():
        assert spans[f"windgp.{phase}"] == secs
    assert res.seconds == spans["windgp.partition"]


def test_create_times_the_pack_apart_from_the_partition():
    g = rmat(8, seed=5)
    cl = scaled_paper_cluster(1, 3, g.num_edges)
    with obs.request("setup") as req:
        PartitionRuntime.create(g, method="windgp", cluster=cl, t0=2)
    top = [s.name for s in req.children(req.root)]
    assert top == ["windgp.partition", "runtime.pack"]
