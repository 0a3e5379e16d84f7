"""The 4-chip cell ``graph500-18-mesh4.pagerank``: its files resolve by
name; at test size on four CPU devices a run is correct and reads the
exchange's bytes per superstep; the bfloat16 control is not correct; the
kind refuses a partition with another number of machines than chips;
``exchange_kb`` is silent where the program never counted the exchange."""
import json
import pathlib
import time

import pytest

from bench import harness
from repro import obs

REPO = pathlib.Path(__file__).resolve().parents[2]
CELL = "graph500-18-mesh4.pagerank"
STUB_TRACE = {"busy_s": 0.25, "window_s": 0.3, "device_ops": [],
              "idle_gaps": []}


def _run(root, traced=False, cell=None, **kw):
    cell = cell or harness.load_cell(root, CELL)
    return harness.run_cell(cell, 2**31 + 23, 0.3, traced, root=root,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def test_the_cell_resolves_every_file_on_4_chips():
    cell = harness.load_cell(REPO, CELL)
    assert cell.chips == 4
    assert cell.config["partition"] == {"method": "windgp", "n_super": 1,
                                        "n_normal": 3}
    assert cell.traffic["kind"] == "pagerank_mesh"
    assert cell.control == {"message_dtype": "bfloat16"}
    assert "exchange_kb" in {m["name"] for m in cell.per_layer}
    assert "pagerank_roofline" not in {m["name"] for m in cell.per_layer}


def test_a_traced_run_is_correct_and_reads_the_exchange(tiny_root,
                                                        monkeypatch):
    cell = harness.load_cell(tiny_root, CELL)
    built = []
    build = cell.kind.Workload.build
    monkeypatch.setattr(cell.kind.Workload, "build", lambda self, clock: (
        build(self, clock), built.append(self))[0])
    monkeypatch.setattr(harness.tracing, "summarize",
                        lambda tr: dict(STUB_TRACE))
    obs.reset()
    out = _run(tiny_root, traced=True, cell=cell)
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["count"] == 4
    rt = built[0].rt
    assert rt.p == 4
    table = (max(1, rt.num_replicas) + 1) * 4
    assert out["metrics"]["exchange_kb"]["value"] == \
        pytest.approx(table * 1e-3)
    assert all(r.root.attrs["devices"] == 4 for r in obs.requests())


def test_the_control_is_not_correct(tiny_root):
    cell = harness.load_cell(tiny_root, CELL)
    out = _run(tiny_root, options=cell.control)
    assert out["correct"] is False
    assert out["failed"] >= 1


def test_the_kind_refuses_other_than_one_machine_per_chip(tiny_root):
    path = tiny_root / "bench" / "configs" / "graph500-18-mesh4.json"
    cfg = json.loads(path.read_text())
    cfg["partition"].update(n_super=1, n_normal=1)
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="one machine per chip"):
        _run(tiny_root)


def _read(counted):
    obs.reset()
    with obs.request("bsp.call"):
        if counted:
            obs.count("bsp.exchange_bytes", 20 * 4000)
    run = harness.Run(cell=None, workload=type("W", (), {"supersteps": 20}),
                      setup_s=0.0, spans={}, completed=1,
                      trace=dict(STUB_TRACE))
    return harness.read_metric(REPO, "exchange_kb", run)


def test_exchange_kb_is_silent_where_nothing_was_counted():
    assert _read(counted=True) == pytest.approx(4.0)
    assert _read(counted=False) is None
