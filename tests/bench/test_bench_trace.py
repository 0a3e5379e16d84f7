"""The reduction from a trace to busy time, idle share and breakdown."""
import pytest

from bench import trace as tr
from bench.harness import Run, read_metric

REPO = __import__("pathlib").Path(__file__).resolve().parents[2]


def test_union_merges_overlaps_and_drops_empty():
    got = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert got == [(0, 3), (5, 9)]


def test_busy_and_gaps_clip_to_window():
    iv = [(0, 4), (2, 6), (8, 12), (20, 30)]
    assert tr.busy(iv, 1, 10) == pytest.approx(5 + 2)
    assert tr.gaps(iv, 1, 25) == [(6, 8), (12, 20)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def _trace(ops_per_device, host=(), window=(0, 100)):
    return tr.Trace(device_ops=ops_per_device, host_events=list(host),
                    window=window)


def test_summarize_averages_devices_and_names_gaps():
    t = _trace(
        {"/device:TPU:0": [("scatter", 0, 40), ("add", 30, 50)],
         "/device:TPU:1": [("scatter", 0, 20)]},
        host=[(tr.WINDOW_SPAN, 0, 100), ("bench.call", 0, 100),
              ("PjitFunction(step)", 55, 70)])
    s = tr.summarize(t)
    # device 0 busy 50 ns, device 1 busy 20 ns: mean 35 ns
    assert s["busy_s"] == pytest.approx(35e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    names = dict(s["device_ops"])
    assert names["scatter"] == pytest.approx((40 + 20) / 2 * 1e-9)
    # gaps are those of the least busy device, longest first
    (name, secs), = s["idle_gaps"][:1]
    assert secs == pytest.approx(80e-9)
    assert name == "bench.call:PjitFunction(step)"


def test_summarize_without_device_work_is_none():
    assert tr.summarize(_trace({})) is None
    assert tr.summarize(_trace({"/device:TPU:0": [("x", 200, 300)]})) is None
    assert tr.summarize(tr.Trace({"/device:TPU:0": [("x", 0, 1)]}, [],
                                 None)) is None


def test_device_planes_exclude_host_and_system():
    assert tr.is_device_plane("/device:TPU:0")
    assert not tr.is_device_plane("/device:TPU_SYSTEM:0")
    assert not tr.is_device_plane("/host:CPU")


class _Wl:
    app = "pagerank"
    supersteps = 20
    counters = {}

    def spmv_work(self):
        return 1e9, 1e9


@pytest.mark.parametrize("name,expect", [
    ("superstep_device_ms", 1e3 * 2.0 / 40),
    ("device_idle_share.analytics", 100 * (1 - 2.0 / 8.0)),
    ("pagerank_roofline", 100 * (1e9 / 819e9) / (2.0 / 40)),
])
def test_trace_metrics_divide_by_supersteps_and_window(name, expect):
    run = Run(cell=None, workload=_Wl(), setup_s=1.0,
              spans={}, window_s=8.0, completed=2,
              trace={"busy_s": 2.0, "window_s": 8.0},
              peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    assert read_metric(REPO, name, run) == pytest.approx(expect)


def test_trace_metrics_are_silent_without_a_trace():
    run = Run(cell=None, workload=_Wl(), setup_s=1.0,
              spans={}, window_s=8.0, completed=2)
    for name in ("superstep_device_ms", "device_idle_share.analytics",
                 "pagerank_roofline"):
        assert read_metric(REPO, name, run) is None
