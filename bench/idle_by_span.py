"""Device idle time of a traced window, put down to the program's own spans.

    python3 bench/idle_by_span.py --workload <cell> --seed <n> --seconds <s>

runs the cell once with a trace, as ``bench/run.py --trace 1`` does, and
prints two JSON lines: the run's result line, then the reduction below
(also written to ``--out`` when given).

The rule.  The idle stretches are those of the device busy least in the
window (``bench/trace.py``'s).  The program's spans are the host events of
the trace named ``bsp.*``, ``runtime.*`` or ``windgp.*`` (``repro.obs``
puts them on the host plane, on the trace's clock).  Every idle stretch is
cut at the edges of the spans that overlap it; each piece goes to the
innermost (shortest) span covering it, or to ``none``.  ``bsp.call`` as the
innermost is its self time; a piece that ``bsp.call`` and another program
span both cover lies inside a child of ``bsp.call``.  The longest gaps are
listed with the innermost span at their midpoint, the superstep it falls
in (its order among the ``bsp.superstep`` spans of its ``bsp.call``) and
``bench/trace.gap_name``'s name.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import trace as tracing  # noqa: E402

PROGRAM = ("bsp.", "runtime.", "windgp.")
CALL = "bsp.call"
STEP = "bsp.superstep"
TOP = 10


def program_spans(tr: tracing.Trace) -> list:
    """The program's spans among the trace's host events, by start."""
    return sorted((e for e in tr.host_events if e[0].startswith(PROGRAM)),
                  key=lambda e: e[1])


def _covering(spans, starts, t0: float, t1: float) -> list:
    return [s for s in spans[:bisect.bisect_left(starts, t1)] if s[2] > t0]


def _innermost(spans):
    return min(spans, key=lambda s: s[2] - s[1]) if spans else None


def _step(spans, starts, span) -> int | None:
    """The order of a ``bsp.superstep`` span within its ``bsp.call``."""
    if span is None or span[0] != STEP:
        return None
    calls = [s for s in _covering(spans, starts, span[1], span[2])
             if s[0] == CALL and s[1] <= span[1] and span[2] <= s[2]]
    lo = calls[0][1] if calls else float("-inf")
    return sum(1 for s in spans
               if s[0] == STEP and lo <= s[1] < span[1])


def attribute(tr: tracing.Trace, top: int = TOP) -> dict | None:
    """Idle seconds by innermost program span, the share of idle time
    inside ``bsp.call`` and inside a child of it, and the ``top`` longest
    gaps; None without a window or device operations."""
    if tr.window is None or not tr.device_ops:
        return None
    lo, hi = tr.window
    per_dev = {d: [(s, e) for _, s, e in ops]
               for d, ops in tr.device_ops.items()}
    quiet = min(per_dev, key=lambda d: tracing.busy(per_dev[d], lo, hi))
    idle = tracing.gaps(per_dev[quiet], lo, hi)
    spans = program_spans(tr)
    starts = [s[1] for s in spans]
    by_span: dict[str, float] = {}
    in_call = in_child = 0.0
    rows = []
    for gs, ge in idle:
        cover = _covering(spans, starts, gs, ge)
        cuts = sorted({gs, ge} | {min(max(t, gs), ge)
                                  for s in cover for t in s[1:3]})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [s for s in cover if s[1] <= mid <= s[2]]
            inner = _innermost(inside)
            name = inner[0] if inner else "none"
            by_span[name] = by_span.get(name, 0.0) + (b - a)
            if any(s[0] == CALL for s in inside):
                in_call += b - a
                if any(s[0] != CALL for s in inside):
                    in_child += b - a
        mid = (gs + ge) / 2
        inner = _innermost([s for s in cover if s[1] <= mid <= s[2]])
        rows.append((ge - gs, mid, inner))
    total = sum(ge - gs for gs, ge in idle)
    rows.sort(key=lambda r: -r[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": total * 1e-9,
        "idle_by_span_s": {k: v * 1e-9 for k, v in
                           sorted(by_span.items(), key=lambda kv: -kv[1])},
        "idle_in_call_share": in_call / total if total else None,
        "idle_in_call_child_share": in_child / total if total else None,
        "gaps": [{"s": d * 1e-9, "span": inner[0] if inner else "none",
                  "step": _step(spans, starts, inner),
                  "name": tracing.gap_name(tr.host_events, mid)}
                 for d, mid, inner in rows[:top]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    kept = {}
    load = harness.tracing.load

    def load_and_keep(log_dir):
        kept["trace"] = load(log_dir)
        return kept["trace"]

    harness.tracing.load = load_and_keep
    try:
        out = harness.run_cell(cell, args.seed, args.seconds, True,
                               root=ROOT, t_start=T_START)
    except harness.NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    finally:
        harness.tracing.load = load
    reduced = {"workload": args.workload, "seed": args.seed,
               **(attribute(kept["trace"]) or {})}
    print(json.dumps(out), flush=True)
    print(json.dumps(reduced), flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(reduced, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
