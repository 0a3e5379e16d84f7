"""What the program records of itself (``repro.obs``), as the per-layer
readers of ``bench/metrics/`` see it.

Every reader is silent (returns None) where the trace metrics are: in a
run without a device trace.  It is silent too where the program has no
recorder (a program older than ``repro.obs``), and where the recorder
holds fewer app calls than the window completed.
"""
from __future__ import annotations

import statistics

#: the request each app call of ``repro.bsp`` opens
CALL = "bsp.call"
#: JAX's trace, lowering and backend-compile durations (seconds) that the
#: recorder adds to the open request, a nested trace counted once; a
#: persistent-cache load is timed inside the backend compile
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def recorder(run):
    """``repro.obs``, or None where the run has no trace or the program
    has no recorder."""
    if run.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def window_calls(run) -> list | None:
    """The window's app calls, oldest first: the last ``run.completed``
    requests (the warm-up call comes before them)."""
    obs = recorder(run)
    if obs is None or not run.completed:
        return None
    calls = [r for r in obs.requests() if r.name == CALL]
    if len(calls) < run.completed:
        return None
    return calls[-run.completed:]


def per_call(run, value) -> float | None:
    """Mean of ``value(request)`` over the window's app calls."""
    calls = window_calls(run)
    if calls is None:
        return None
    return sum(value(r) for r in calls) / len(calls)


def counter(name: str):
    return lambda r: r.counters.get(name, 0)


def median_span_ms(run, name: str) -> float | None:
    """Median duration of the spans called ``name`` in the window's app
    calls, in milliseconds."""
    calls = window_calls(run)
    if calls is None:
        return None
    durs = [s.dur_ns for r in calls for s in r.named(name)]
    return statistics.median(durs) * 1e-6 if durs else None


def total_seconds(run, name: str) -> float | None:
    """Process total of the spans called ``name`` (one process runs one
    cell)."""
    obs = recorder(run)
    if obs is None:
        return None
    return obs.totals()["spans"].get(name)
