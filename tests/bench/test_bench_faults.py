"""A run drives the timed path with a fault planted underneath and its
check comes out false; the control (the cell's lower-precision path)
fails its limit too.  Tiny sizes, on the CPU; the
harness's look for a chip is skipped and everything else is a run."""
import time

import numpy as np
import pytest

from bench import harness

ANALYTICS = ["graph500-19.pagerank", "graph500-19.sssp"]


def _run(root, name, **kw):
    cell = harness.load_cell(root, name)
    return harness.run_cell(cell, 2**31 + 101, 0.3, False, root=root,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def _state_unchanged(monkeypatch):
    """Every superstep returns the state it was given."""
    from repro.bsp import apps

    def run_bsp(superstep, state, static, num_steps, **_):
        return state, np.zeros((0, 1))
    monkeypatch.setattr(apps, "run_bsp", run_bsp)


def _no_exchange(monkeypatch):
    """The replica exchange between machines is left out."""
    from repro.bsp import backends
    monkeypatch.setattr(backends, "exchange",
                        lambda partial, rep_slot, r_pad, mode="sum": partial)


def _answer_altered(monkeypatch):
    """One vertex's value is altered where the answer is gathered."""
    from repro.bsp import PartitionRuntime
    gather = PartitionRuntime.gather_global

    def altered(self, local_values, fill=0.0):
        out = gather(self, local_values, fill)
        i = int(np.flatnonzero(np.isfinite(out) & (out > 0))[0])
        out[i] = out[i] * 1.01
        return out
    monkeypatch.setattr(PartitionRuntime, "gather_global", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _no_exchange,
                                   _answer_altered])
@pytest.mark.parametrize("name", ANALYTICS)
def test_analytics_fault_is_not_correct(tiny_root, monkeypatch, name, fault):
    fault(monkeypatch)
    out = _run(tiny_root, name)
    assert out["correct"] is False
    assert out["failed"] >= 1


@pytest.mark.parametrize("name", ANALYTICS)
def test_the_control_is_not_correct(tiny_root, name):
    cell = harness.load_cell(tiny_root, name)
    out = _run(tiny_root, name, options=cell.control)
    assert out["correct"] is False
    (reading,) = out["compared"].values()
    assert reading["value"] > reading["limit"]
