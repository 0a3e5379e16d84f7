"""``bench/idle_by_span.py``: device idle time put down to the program's
spans, on hand-made traces, and the program's spans as a real trace
holds them."""
import jax
import jax.numpy as jnp
import pytest

from bench import idle_by_span
from bench import trace as tracing
from repro import obs


def _trace(device_ops, host, window=(0, 100)):
    return tracing.Trace(
        {d: [("op", s, e) for s, e in ops] for d, ops in device_ops.items()},
        [("bench.window", *window)] + host, window)


#: a job of two supersteps: the first ends in a read, the second in the
#: gather; a JAX event the program's spans do not count
JOB = [("bsp.call", 5, 95), ("bsp.superstep", 5, 50), ("bsp.sync", 38, 50),
       ("bsp.superstep", 50, 95), ("bsp.gather", 92, 95),
       ("Transpose", 45, 55)]


def test_each_idle_piece_goes_to_the_innermost_span():
    tr = _trace({"/device:TPU:0": [(10, 40), (62, 90)]}, JOB)
    got = idle_by_span.attribute(tr)
    # idle: [0, 10], [40, 62], [90, 100]
    assert got["idle_s"] == pytest.approx(42e-9)
    assert got["idle_by_span_s"] == pytest.approx({
        "bsp.superstep": 19e-9, "none": 10e-9, "bsp.sync": 10e-9,
        "bsp.gather": 3e-9})
    assert got["idle_in_call_share"] == pytest.approx(32 / 42)
    assert got["idle_in_call_child_share"] == pytest.approx(32 / 42)
    longest = got["gaps"][0]
    assert longest["s"] == pytest.approx(22e-9)
    assert (longest["span"], longest["step"]) == ("bsp.superstep", 1)
    # the harness's own name is the innermost host event, here JAX's
    assert longest["name"] == "bench.none:Transpose"


def test_idle_in_the_call_alone_is_its_self_time():
    tr = _trace({"/device:TPU:0": [(0, 40), (60, 100)]},
                [("bsp.call", 0, 100), ("bsp.superstep", 10, 45)])
    got = idle_by_span.attribute(tr)
    assert got["idle_by_span_s"] == pytest.approx(
        {"bsp.call": 15e-9, "bsp.superstep": 5e-9})
    assert got["idle_in_call_share"] == pytest.approx(1.0)
    assert got["idle_in_call_child_share"] == pytest.approx(0.25)
    assert got["gaps"][0]["step"] is None


def test_the_gaps_are_the_least_busy_devices():
    tr = _trace({"/device:TPU:0": [(0, 100)],
                 "/device:TPU:1": [(0, 50)]}, JOB)
    got = idle_by_span.attribute(tr)
    assert got["idle_s"] == pytest.approx(50e-9)
    assert got["idle_by_span_s"]["none"] == pytest.approx(5e-9)


@pytest.mark.parametrize("tr", [
    tracing.Trace({}, JOB, (0, 100)),
    tracing.Trace({"/device:TPU:0": [("op", 0, 10)]}, JOB, None),
], ids=["no device ops", "no window"])
def test_nothing_to_reduce_reads_none(tr):
    assert idle_by_span.attribute(tr) is None


def test_a_real_trace_holds_the_program_spans_by_name(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.request("bsp.call", app="x"):
            for i in range(2):
                with obs.span("bsp.superstep", step_num=i):
                    with obs.span("bsp.sync"):
                        jnp.arange(4.0).sum().block_until_ready()
        with obs.span("runtime.pack"):
            pass
    names = [e[0] for e in idle_by_span.program_spans(
        tracing.load(str(tmp_path)))]
    assert sorted(names) == ["bsp.call", "bsp.superstep", "bsp.superstep",
                             "bsp.sync", "bsp.sync", "runtime.pack"]
