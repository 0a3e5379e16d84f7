"""The program's own spans and counters, on the profiler trace's clock.

One recorder for the whole program, always on:

* :func:`span` times a block of host code.  Under a running profiler it
  also lands on the host plane of the same trace as the device's
  ``XLA Ops`` (a ``jax.profiler.TraceAnnotation``, or a
  ``StepTraceAnnotation`` when given ``step_num``), so a device idle gap
  can be put down to the innermost span covering it.
* :func:`request` is a root span with a fresh id: one unit of work a
  caller asked for (one BSP app call).  Every span and counter inside it
  carries that id (``req=<id>`` in the annotation); completed requests
  are kept, the newest :data:`KEEP` of them, for :func:`requests`.
* :func:`count` adds to the open request and to the process totals.

JAX's own compile-path durations (trace, lowering, backend compile) are
added to the open request as counters of seconds under their
``jax.monitoring`` event names, once :func:`listen` has run (the first
:func:`request` calls it).  JAX reports a nested trace (``jax.eval_shape``
inside a jitted function) as a span of its own inside its parent's; a
request adds only the time of the outermost spans of each event, so
nothing is counted twice.  Outside a request the totals get every
duration as JAX reports it.  An event JAX fires on another thread than
the request's is not the request's.

Spans and the request stack are per thread.  Recording reads two host
clocks and never touches the device: no span syncs, blocks or copies.
Start times are ``time.time_ns()``, the epoch clock the trace uses;
durations are ``time.perf_counter_ns()``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import sys
import threading
import time

#: completed requests kept for :func:`requests`
KEEP = 1024
#: the ``jax.monitoring`` compile-path events added to the open request
JAX_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass
class Span:
    name: str
    id: int
    parent: int | None
    start_ns: int            # time.time_ns() at entry
    dur_ns: int = 0          # perf_counter_ns() difference, set at exit
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.dur_ns * 1e-9


@dataclasses.dataclass
class Request:
    """One request's spans (its root first, then in order of entry) and
    counters."""

    id: int
    root: Span | None
    spans: list
    counters: dict
    # event -> its outermost (start, end) spans so far, in seconds
    _jax: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def name(self) -> str:
        return self.root.name

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def seconds(self, name: str) -> float:
        """Total duration of the spans called ``name``."""
        return sum(s.dur_ns for s in self.named(name)) * 1e-9

    def children(self, span: Span) -> list:
        return [s for s in self.spans if s.parent == span.id]

    def self_seconds(self, span: Span | None = None) -> float:
        """A span's duration less what its child spans cover (the root's
        by default)."""
        span = span or self.root
        kids = sum(s.dur_ns for s in self.children(span))
        return max(0, span.dur_ns - kids) * 1e-9


_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_done: collections.deque = collections.deque(maxlen=KEEP)
_span_ns: dict = collections.defaultdict(int)
_counts: dict = collections.defaultdict(float)
_listening = False


def _state():
    st = _local.__dict__
    if "stack" not in st:
        st["stack"], st["req"] = [], None
    return st


def _annotation(name: str, step_num, attrs: dict):
    # no profiler can run before JAX is imported, so code that never
    # imports it (the partitioner) pays nothing for the annotation
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return contextlib.nullcontext()
    if step_num is None:
        return prof.TraceAnnotation(name, **attrs)
    return prof.StepTraceAnnotation(name, step_num=step_num, **attrs)


@contextlib.contextmanager
def span(name: str, *, step_num: int | None = None, **attrs):
    """Time the block; yields its :class:`Span`, whose ``dur_ns`` is set
    when the block exits."""
    st = _state()
    req = st["req"]
    stack = st["stack"]
    if req is not None:
        attrs["req"] = req.id
    sp = Span(name, next(_ids), stack[-1].id if stack else None,
              time.time_ns(), attrs=dict(attrs))
    if step_num is not None:
        sp.attrs["step_num"] = step_num
    if req is not None:
        req.spans.append(sp)
    stack.append(sp)
    t0 = time.perf_counter_ns()
    try:
        with _annotation(name, step_num, attrs):
            yield sp
    finally:
        sp.dur_ns = time.perf_counter_ns() - t0
        stack.pop()
        with _lock:
            _span_ns[name] += sp.dur_ns


@contextlib.contextmanager
def request(name: str, **attrs):
    """A root span with a fresh request id; yields the :class:`Request`,
    which :func:`requests` returns once the block exits."""
    listen()
    st = _state()
    outer = st["req"], st["stack"]
    req = Request(next(_ids), None, [], {})
    st["req"], st["stack"] = req, []
    try:
        with span(name, **attrs) as req.root:
            yield req
    finally:
        st["req"], st["stack"] = outer
        _done.append(req)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the open request and the totals."""
    req = _state()["req"]
    if req is not None:
        req.counters[name] = req.counters.get(name, 0) + n
    with _lock:
        _counts[name] += n


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event not in JAX_EVENTS:
        return
    req = _state()["req"]
    if req is None:
        count(event, end - start)
        return
    # a nested span ends first: the outer one, when it ends, replaces
    # the spans it covers and adds only the time they left out
    outer = req._jax.setdefault(event, [])
    inner = [iv for iv in outer if start <= iv[0] and iv[1] <= end]
    for iv in inner:
        outer.remove(iv)
    outer.append((start, end))
    count(event, (end - start) - sum(e - s for s, e in inner))


def listen() -> None:
    """Start adding JAX's compile-path durations (:data:`JAX_EVENTS`) to
    the open request and the totals; idempotent."""
    global _listening
    if _listening:
        return
    import jax.monitoring
    with _lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_span)
            _listening = True


def requests() -> list:
    """Completed requests, oldest first (at most :data:`KEEP`)."""
    return list(_done)


def totals() -> dict:
    """Process-wide sums since the last :func:`reset`: ``spans`` (name ->
    seconds) and ``counts``."""
    with _lock:
        return {"spans": {k: v * 1e-9 for k, v in _span_ns.items()},
                "counts": dict(_counts)}


def reset() -> None:
    """Forget completed requests and totals (open spans still close)."""
    with _lock:
        _done.clear()
        _span_ns.clear()
        _counts.clear()
