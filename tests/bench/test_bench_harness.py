"""The harness finds every cell, configuration, generator, traffic mix,
kind of job, cell file and metric by name, refuses a missing one, takes a
new cell as new files, and never reports without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_with_its_files(name):
    cell = harness.load_cell(REPO, name)
    assert callable(cell.generator.generate)
    assert cell.kind.Workload.app == cell.traffic["kind"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert set(cell.limits) and cell.control


def test_spec_keeps_its_contract():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        assert m["layer"] in layers
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).exists()
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("victim", [
    "cell", "config", "generator", "traffic", "kind", "cell_file",
    "metric"])
def test_a_missing_piece_is_refused(tiny_root, victim):
    name = "graph500-19.pagerank"
    gone = {"config": "bench/configs/graph500-19.json",
            "generator": "bench/generators/kronecker.py",
            "traffic": "bench/traffic/pagerank.json",
            "kind": "bench/kinds/pagerank.py",
            "cell_file": f"bench/cells/{name}.json",
            "metric": "bench/metrics/evps.py"}
    if victim == "cell":
        name = "graph500-19.nosuchtraffic"
    else:
        (tiny_root / gone[victim]).unlink()
    with pytest.raises(harness.SpecError):
        harness.load_cell(tiny_root, name)


def _run(root, name, seconds=0.3, traced=False, seed=2**31 + 11, **kw):
    cell = harness.load_cell(root, name)
    return harness.run_cell(cell, seed, seconds, traced, root=root,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct_and_reports_its_metrics(tiny_root, name):
    out = _run(tiny_root, name)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    cell = harness.load_cell(tiny_root, name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_a_traced_run_reports_per_layer_metrics_only(tiny_root):
    out = _run(tiny_root, "graph500-19.pagerank", traced=True)
    # on the CPU there is no device plane: the trace metrics stay silent
    assert set(out["metrics"]) == {"partition_pack_s", "replication_factor"}
    assert out["metrics"]["replication_factor"]["value"] >= 1.0


#: a generator the benchmark does not have: G(n, m) with isolated
#: vertices dropped
RANDOM_GRAPH = """
from bench.graphs import canonical, drop_isolated


def generate(cfg, rng):
    n, m = cfg["vertices"], cfg["draws"]
    return drop_isolated(canonical(rng.integers(0, n, m),
                                   rng.integers(0, n, m), n), n)
"""

#: a kind of job the benchmark does not have: Graph500 kernel-2 BFS
BFS_KIND = """
import numpy as np
from scipy.sparse import csgraph

from bench.jobs import BspJobs


class Workload(BspJobs):
    app = "bfs"
    compared = "mismatches"

    def job_supersteps(self):
        return int(self.params["supersteps"])

    def next_request(self):
        return self.draw_root()

    def call(self, root):
        from repro import bsp
        out, _ = bsp.bfs(self.rt, root, self.supersteps, **self.options)
        return out

    def errors(self, adj, kept):
        def levels(r):
            return csgraph.shortest_path(adj, method="D", directed=False,
                                         unweighted=True, indices=r)
        return [float(np.sum(np.asarray(a, np.float64) != levels(r)))
                for _, r, a in kept]
"""


def test_a_new_cell_is_only_new_files(tiny_root):
    """BFS, a kind of job no file has, on a new configuration with a new
    generator, with a new per-layer metric: new files and new entries in
    BENCHMARK.json, no existing file edited."""
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    new = {
        "generators/random_graph.py": RANDOM_GRAPH,
        "kinds/bfs.py": BFS_KIND,
        "configs/random-600.json": json.dumps(
            {"generator": "random_graph", "vertices": 600, "draws": 2400,
             "partition": {"method": "windgp", "n_super": 1,
                           "n_normal": 3}}),
        "traffic/bfs.json": json.dumps(
            {"kind": "bfs", "weights": "unit", "max_checked": 3}),
        "cells/random-600.bfs.json": json.dumps(
            {"params": {"supersteps": {"value": 40}},
             "limits": {"mismatches": {"limit": 0}},
             "control": {"message_dtype": "bfloat16"}}),
        "metrics/jobs_completed.py":
            "def read(run):\n    return run.completed\n",
    }
    for rel, text in new.items():
        assert not (tiny_root / "bench" / rel).exists()
        (tiny_root / "bench" / rel).write_text(text)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "random-600", "source": "x",
                            "file": "bench/configs/random-600.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "random-600.bfs",
                              "config": "random-600", "traffic": "bfs",
                              "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("random-600.bfs")
    spec["per_layer"].append({"name": "jobs_completed", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "evps",
                              "workloads": ["random-600.bfs"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p: p.read_bytes() for p in before}
    assert after == before
    out = _run(tiny_root, "random-600.bfs")
    assert out["correct"] and "evps" in out["metrics"]
    assert out["compared"]["mismatches"] == {"value": 0.0, "limit": 0.0}
    out = _run(tiny_root, "random-600.bfs", traced=True)
    assert out["metrics"]["jobs_completed"]["value"] == out["attempted"]


def _cli(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "graph500-19.pagerank", "--seed", "1", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    r = _cli(REPO)
    assert r.returncode == 3, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_an_unknown_cell_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", "x.y",
                        "--seed", "1", "--seconds", "1"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 2 and r.stdout.strip() == ""


def test_the_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _cli(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
