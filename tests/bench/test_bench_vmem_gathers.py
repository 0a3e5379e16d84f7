"""``call_vmem_gathers`` reads ``bsp.vmem_gathers`` per window job: 1
where ``segment``'s combine gathers with the VMEM kernel (on a TPU), 0
where it keeps XLA's gather, as in a traced run on the CPU; silent
without a trace and where the program never counted the name."""
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
METRIC = "call_vmem_gathers"
STUB_TRACE = {"busy_s": 0.25, "window_s": 0.3, "device_ops": [],
              "idle_gaps": []}


def test_the_entry_lists_every_cell():
    entry = {m["name"]: m for m in SPEC["per_layer"]}[METRIC]
    assert entry["workloads"] == CELLS
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "superstep loop and edge kernel"
    assert entry["moves"] == "evps"


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_cpu_run_reads_xla_gathers(tmp_path, monkeypatch, name):
    root = make_tiny_root(tmp_path)
    monkeypatch.setattr(harness.tracing, "summarize",
                        lambda tr: dict(STUB_TRACE))
    obs.reset()
    out = harness.run_cell(harness.load_cell(root, name), 2**31 + 13, 0.3,
                           True, root=root, t_start=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"]
    assert out["metrics"][METRIC]["value"] == 0
    # every call counted the name, with 0: the CPU keeps XLA's gather
    assert obs.totals()["counts"]["bsp.vmem_gathers"] == 0


def _read(trace, completed):
    run = harness.Run(cell=None, workload=None, setup_s=0.0, spans={},
                      completed=completed, trace=trace)
    return harness.read_metric(REPO, METRIC, run)


def _call(kernel):
    with obs.request("bsp.call"):
        obs.count("bsp.vmem_gathers", kernel)


@pytest.mark.parametrize("calls,want", [((1, 1), 1), ((0, 0), 0),
                                        ((1, 0), 0.5)])
def test_it_reads_the_window_calls_count(calls, want):
    obs.reset()
    _call(1)                         # set-up's warm-up call
    for kernel in calls:
        _call(kernel)
    assert _read(dict(STUB_TRACE), len(calls)) == want


def test_it_is_silent_where_the_name_was_never_counted():
    obs.reset()
    _call(1)
    assert _read(None, 1) is None                  # no trace
    obs.reset()
    with obs.request("bsp.call"):
        pass                         # a program without the kernel
    assert _read(dict(STUB_TRACE), 1) is None
    _call(1)
    assert _read(dict(STUB_TRACE), 3) is None      # fewer calls than jobs
