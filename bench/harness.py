"""One run of one benchmark cell: set-up, a measured window, the check.

``BENCHMARK.json`` names every cell, configuration and metric; everything
that belongs to one of them is a file of its own, found by its name:

* ``configs/<config>.json``      -- the deployment (``file`` in the spec);
* ``generators/<generator>.py``  -- the graph the configuration names;
* ``traffic/<traffic>.json``     -- the mix's parameters;
* ``kinds/<kind>.py``            -- the job the mix names, a ``Workload``
  on :mod:`bench.jobs`' closed loop;
* ``cells/<cell>.json``          -- each compared number's limit, the
  control's options, and the cell's ``params``;
* ``metrics/<metric>.py``        -- ``read(run) -> float | None``.

The window is a closed loop: the next request is issued when the previous
one has returned, from the first request's start until the last request
begun before ``--seconds`` has completed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

from bench import trace as tracing

CACHE_SUBDIR = ".bench_cache/jax"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class SpecError(LookupError):
    """A cell, configuration, generator, traffic mix, kind, cell file or
    metric is missing."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    control: dict       # the options that switch on the control's path
    params: dict
    generator: object   # generators/<name>.py
    kind: object        # kinds/<name>.py
    end_to_end: list
    per_layer: list


def _read_json(path: pathlib.Path, what: str) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{what}: no file {path}") from None


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """Resolve a workload of ``root/BENCHMARK.json`` and all its files."""
    spec = _read_json(root / "BENCHMARK.json", "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r}: no config {w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"],
                        f"config {w['config']!r}")
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    own = _read_json(root / "bench" / "cells" / f"{name}.json",
                     f"cell file of {name!r}")
    limits = {k: float(v["limit"]) for k, v in own["limits"].items()}
    params = {k: v["value"] for k, v in own.get("params", {}).items()}
    generator = load_module(root, "generators", config["generator"])
    kind = load_module(root, "kinds", traffic["kind"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, reported)]
    for m in e2e + per_layer:
        path = module_file(root, "metrics", m["name"])
        if not path.exists():
            raise SpecError(f"metric {m['name']!r}: no reader {path}")
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, limits, own["control"], params, generator, kind,
                e2e, per_layer)


def _applies(metric: dict, cell: str, reported: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def module_file(root: pathlib.Path, subdir: str, name: str) -> pathlib.Path:
    return root / "bench" / subdir / f"{name}.py"


def load_module(root: pathlib.Path, subdir: str, name: str):
    """``bench/<subdir>/<name>.py`` of ``root``, loaded by its path."""
    path = module_file(root, subdir, name)
    if not path.exists():
        raise SpecError(f"{subdir} {name!r}: no file {path}")
    mod_name = f"bench_{subdir}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(root: pathlib.Path, name: str, run) -> float | None:
    value = load_module(root, "metrics", name).read(run)
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------

class Clock:
    """Host-clock spans of set-up, also written into a running trace."""

    def __init__(self):
        self.spans: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.spans[name] = self.spans.get(name, 0.0) + \
            time.perf_counter() - t


class CompileCounter:
    """Compiles and persistent-cache loads since the last reset.

    JAX times a cache load under the compile event too, so a compile is a
    compile event that no cache load accounts for.
    """

    def __init__(self):
        import jax
        self.events = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.events += 1
        elif event == CACHE_HIT_EVENT:
            self.cache_loads += 1

    def snapshot(self) -> dict:
        """Counts since the last snapshot."""
        out = {"compiles": self.events - self.cache_loads,
               "cache_loads": self.cache_loads}
        self.events = self.cache_loads = 0
        return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def peaks_for(root: pathlib.Path, kind: str,
              require: bool) -> dict | None:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        if require:
            raise NoDevice(f"no peaks for device kind {kind!r}")
        return None
    return table[kind]


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)


def enable_compile_cache(root: pathlib.Path) -> str:
    """The persistent compilation cache, at one fixed path in the checkout;
    every program is written to it, however short its compile."""
    import jax
    path = str(root / CACHE_SUBDIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    workload: object
    setup_s: float
    spans: dict
    window_s: float = 0.0
    latencies_s: list = dataclasses.field(default_factory=list)
    completed: int = 0
    trace: dict | None = None
    peaks: dict | None = None

    @property
    def supersteps(self) -> int:
        return self.completed * int(getattr(self.workload, "supersteps", 0))


def window(wl, seconds: float) -> tuple[list, float]:
    """The measured closed loop; returns (latency of each request, window
    seconds).  Requests are drawn outside the timed call, and every answer
    is kept for the check."""
    import jax
    latencies = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        i = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.request_draw"):
                req = wl.next_request()
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                ans = wl.call(req)
            done = time.perf_counter()
            latencies.append(done - t)
            wl.keep(i, req, ans)
            del ans
            i += 1
            if done - t0 >= seconds:
                break
    return latencies, time.perf_counter() - t0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             root: pathlib.Path, t_start: float, require_tpu: bool = True,
             options: dict | None = None,
             compiles: CompileCounter | None = None) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    ``options`` go to the program's call (the control's lower-precision
    path); the benchmark's own runs pass none.
    """
    import jax
    devs = devices_for(cell.chips, require_tpu)
    device_kind = devs[0].device_kind
    peaks = peaks_for(root, device_kind, require_tpu)
    clock = Clock()

    wl = cell.kind.Workload(cell, seed)
    wl.build(clock)
    if options:
        wl.set_options(options)
    with clock.span("warmup"):
        wl.call(wl.next_request())
    setup_warm = compiles and compiles.snapshot()

    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    latencies, window_s = window(wl, seconds)
    in_window = compiles and compiles.snapshot()

    summary = None
    if traced:
        jax.profiler.stop_trace()
        summary = tracing.summarize(tracing.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak(devs)

    run = Run(cell=cell, workload=wl,
              setup_s=setup_s, spans=dict(clock.spans), window_s=window_s,
              latencies_s=latencies, completed=len(latencies),
              trace=summary, peaks=peaks)

    log(f"set-up spans (s): {json.dumps(clock.spans)}")
    log(f"counters: {json.dumps(wl.counters)}")
    log(f"compiles/cache loads in set-up {setup_warm}, in window {in_window}")
    compared = wl.check(cell.limits)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = read_metric(root, m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(v <= lim for v, lim in compared.values())
    device = {"platform": devs[0].platform, "kind": device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(latencies),
           "failed": int(wl.failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    log(f"checked {wl.checked} of {len(latencies)} answers")
    for k, (v, lim) in compared.items():
        log(f"{k} {v!r} limit {lim!r}")
    return out
