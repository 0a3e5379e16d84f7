"""Reduction from a profiler trace to device metrics.

A trace gives, per device, the intervals in which an XLA operation ran, and
on the host the benchmark's own spans (``bench.*``) beside JAX's.  From them:

* busy time: the union of the device's operation intervals inside the
  window, averaged over the devices used;
* idle share: 1 - busy / window;
* the operations that took most device time;
* the longest idle gaps, each named by what the host was doing in it: the
  innermost benchmark span and the innermost host event covering the gap's
  midpoint.

Times are nanoseconds on the trace's clock until :func:`summarize` turns
them into seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
#: the device line that holds one event per XLA operation run
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10
#: characters kept of a breakdown name (an op's HLO text runs to kilobytes)
NAME_CHARS = 120


@dataclasses.dataclass
class Trace:
    """What the reduction needs from one trace."""

    device_ops: dict[str, list[tuple[str, float, float]]]  # device -> ops
    host_events: list[tuple[str, float, float]]    # (name, start, end)
    window: tuple[float, float] | None


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle stretches of ``[lo, hi]`` not covered by any interval."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(events, t: float, keep) -> str | None:
    best = None
    for name, s, e in events:
        if s <= t <= e and keep(name) and (best is None
                                          or e - s < best[1]):
            best = (name, e - s)
    return None if best is None else best[0]


def gap_name(host_events, t: float) -> str:
    """What the host was doing at ``t``: innermost bench span and event."""
    span = _innermost(host_events, t,
                      lambda n: n.startswith("bench.") and n != WINDOW_SPAN)
    event = _innermost(host_events, t, lambda n: not n.startswith("bench."))
    return f"{span or 'bench.none'}:{event or 'none'}"[:NAME_CHARS]


def summarize(tr: Trace) -> dict | None:
    """busy_s, window_s, device_ops and idle_gaps; None without a window
    or without any device operation inside it."""
    if tr.window is None or not tr.device_ops:
        return None
    lo, hi = tr.window
    per_dev = {d: [(s, e) for _, s, e in ops]
               for d, ops in tr.device_ops.items()}
    busies = [busy(iv, lo, hi) for iv in per_dev.values()]
    busy_ns = sum(busies) / len(busies)
    if busy_ns <= 0:
        return None
    totals: dict[str, float] = {}
    for ops in tr.device_ops.values():
        for name, s, e in ops:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                totals[name] = totals.get(name, 0.0) + c
    n_dev = len(tr.device_ops)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    # gaps of the device that was busy least (the one the window waits on)
    quiet = min(per_dev, key=lambda d: busy(per_dev[d], lo, hi))
    idle = sorted(gaps(per_dev[quiet], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n[:NAME_CHARS], t * 1e-9 / n_dev]
                       for n, t in top_ops],
        "idle_gaps": [[gap_name(tr.host_events, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:TOP]],
    }


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name \
        and "SYSTEM" not in name.upper()


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return Trace({}, [], None)
    data = ProfileData.from_file(paths[-1])
    device_ops: dict[str, list] = {}
    host: list = []
    window = None
    for plane in data.planes:
        if is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns, ev.end_ns))
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
    return Trace(device_ops, host, window)
