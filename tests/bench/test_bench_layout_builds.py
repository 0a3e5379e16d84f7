"""``call_layout_builds`` reads ``bsp.layout_builds`` per window job: 0 in
a traced run, where set-up built the layout and every job reuses it; the
count where a job builds; silent without a trace and where the program
never counted a build."""
import json
import pathlib
import time

import pytest

from bench import harness
from conftest import make_tiny_root
from repro import obs

REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRIC = "call_layout_builds"
STUB_TRACE = {"busy_s": 0.25, "window_s": 0.3, "device_ops": [],
              "idle_gaps": []}


def test_the_entry_lists_both_cells():
    entry = {m["name"]: m for m in SPEC["per_layer"]}[METRIC]
    assert entry["workloads"] == CELLS
    assert entry["source"] == "program_counter"


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reads_no_build_in_the_window(tmp_path, monkeypatch,
                                                   name):
    root = make_tiny_root(tmp_path)
    monkeypatch.setattr(harness.tracing, "summarize",
                        lambda tr: dict(STUB_TRACE))
    obs.reset()
    out = harness.run_cell(harness.load_cell(root, name), 2**31 + 11, 0.3,
                           True, root=root, t_start=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"]
    assert out["metrics"][METRIC]["value"] == 0
    # the warm-up call built the layout the window reused
    assert obs.totals()["counts"]["bsp.layout_builds"] == 1


def _read(trace, completed):
    run = harness.Run(cell=None, workload=None, setup_s=0.0, spans={},
                      completed=completed, trace=trace)
    return harness.read_metric(REPO, METRIC, run)


def _call(builds):
    with obs.request("bsp.call"):
        if builds:
            obs.count("bsp.layout_builds", builds)


@pytest.mark.parametrize("builds,want", [(0, 0), (1, 1), (2, 2)])
def test_it_reads_the_builds_of_the_window_call(builds, want):
    obs.reset()
    _call(1)                         # set-up's warm-up call
    _call(builds)
    assert _read(dict(STUB_TRACE), 1) == want


def test_it_is_silent_without_a_trace_or_a_counted_build():
    obs.reset()
    _call(1)
    assert _read(None, 1) is None
    obs.reset()
    _call(0)                         # a program that keeps no layout
    assert _read(dict(STUB_TRACE), 1) is None
    _call(1)
    assert _read(dict(STUB_TRACE), 3) is None      # fewer calls than jobs
