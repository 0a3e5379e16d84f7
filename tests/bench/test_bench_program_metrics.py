"""The per-layer metrics that read the program's own recorder
(``repro.obs``): each reports in a traced run, and each is silent without
a trace, without the recorder, and with fewer app calls recorded than the
window completed."""
import json
import pathlib
import sys
import time

import pytest

from bench import harness
from conftest import make_tiny_root
from repro import obs

REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
PROGRAM = ["call_prepare_ms", "call_compile_ms", "call_gather_ms",
           "call_h2d_mb", "call_retraces", "call_host_syncs",
           "superstep_host_ms", "windgp_s", "pack_s"]
#: what a device trace would give; the CPU has no device plane
STUB_TRACE = {"busy_s": 0.25, "window_s": 0.3, "device_ops": [],
              "idle_gaps": []}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run per cell on the CPU, with a stub device summary."""
    root = make_tiny_root(tmp_path_factory.mktemp("bench"))
    mp = pytest.MonkeyPatch()
    mp.setattr(harness.tracing, "summarize", lambda tr: dict(STUB_TRACE))
    outs = {}
    try:
        for name in CELLS:
            obs.reset()
            cell = harness.load_cell(root, name)
            outs[name] = harness.run_cell(
                cell, 2**31 + 7, 0.3, True, root=root,
                t_start=time.perf_counter(), require_tpu=False)
    finally:
        mp.undo()
    return outs


def test_every_program_metric_lists_both_cells():
    entries = {m["name"]: m for m in SPEC["per_layer"]}
    for name in PROGRAM:
        assert entries[name]["workloads"] == CELLS
        assert (REPO / "bench" / "metrics" / f"{name}.py").exists()


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("metric", PROGRAM)
def test_each_reader_reports_in_a_traced_run(traced, name, metric):
    out = traced[name]
    assert out["correct"]
    assert out["metrics"][metric]["value"] >= 0


@pytest.mark.parametrize("name", CELLS)
def test_the_counts_read_as_the_call_path_runs(traced, name):
    m = {k: v["value"] for k, v in traced[name]["metrics"].items()}
    cell = harness.load_cell(REPO, name)
    steps = (cell.traffic.get("num_iters")
             or cell.params.get("supersteps"))
    assert m["call_retraces"] == 1
    assert m["call_host_syncs"] == steps + 1
    assert m["call_h2d_mb"] > 0
    # both program spans lie inside the benchmark's own span around them
    assert m["windgp_s"] + m["pack_s"] <= m["partition_pack_s"]


def _run(trace, completed):
    return harness.Run(cell=None, workload=None, setup_s=0.0, spans={},
                       completed=completed, trace=trace)


def _record():
    """One app call and the set-up spans, as the program records them."""
    with obs.span("windgp.partition"), obs.span("runtime.pack"):
        pass
    with obs.request("bsp.call"):
        with obs.span("bsp.superstep"):
            pass


def _read(metric, trace, completed):
    return harness.read_metric(REPO, metric, _run(trace, completed))


@pytest.mark.parametrize("metric", PROGRAM)
def test_a_reader_is_silent_without_a_trace(metric):
    obs.reset()
    _record()
    assert _read(metric, dict(STUB_TRACE), 1) is not None
    assert _read(metric, None, 1) is None


@pytest.mark.parametrize("metric", PROGRAM)
def test_a_reader_is_silent_with_fewer_calls_than_jobs(metric):
    obs.reset()
    if metric in ("windgp_s", "pack_s"):
        # these read the process totals: nothing recorded, nothing read
        assert _read(metric, dict(STUB_TRACE), 1) is None
        return
    _record()
    assert _read(metric, dict(STUB_TRACE), 2) is None


@pytest.mark.parametrize("metric", PROGRAM)
def test_a_reader_is_silent_where_the_program_has_no_recorder(
        metric, monkeypatch):
    obs.reset()
    _record()
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read(metric, dict(STUB_TRACE), 1) is None
