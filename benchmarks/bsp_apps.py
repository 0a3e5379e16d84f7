"""BSP apps × edge-kernel backends: superstep throughput at matched partitions.

The paper's end metric is distributed graph-algorithm runtime on the
partition it produces; this table holds the partition fixed (one hdrf run
per dataset) and swaps the *compute* layer — the edge-kernel backend each
superstep combines messages through (``repro.bsp.backends``):

* ``scatter`` — the gather-scatter oracle (`at[].⊕` per direction);
* ``segment`` — segmented scan over the destination-sorted incidence;
* ``pallas``  — the blocked Block-ELL semiring SpMV (interpret-mode on
  CPU, compiled on TPU; its ELL fill stats are the utilization proxy).

Per (app × backend): median superstep seconds, edge throughput, speedup
over ``scatter``, and the cross-backend result gap (bitwise for the
min/max semirings, ~1e-7 float drift for (+, ×)).

``--smoke`` is the tier-2 CI gate: asserts backend equivalence on a tiny
proxy for all four apps, ``segment`` ≥ 2× ``scatter`` PageRank superstep
throughput on the LJ proxy, fused-runner ≡ stepwise equivalence plus the
convergence-gated fused speedup (≥ 2× the full-budget stepwise wall),
and reports the Pallas ELL fill stats and the bf16 message path's final
PageRank error; emits ``BENCH_smoke.json`` for ``benchmarks/check_trend.py``.

``--latency`` is the superstep-latency study the fused runner exists
for: per-superstep wall fused vs stepwise (supersteps/sec vs chunk
size), convergence-gated fused runs vs the full iteration budget, and
the BFS/SSSP frontier table — per-superstep cost of the ``scatter``
backend's ``frontier_cap`` compaction re-bucketed per step as the
frontier grows/drains, against the dense O(E_local) superstep.

``--bf16-study`` prints the PageRank error-vs-iteration table for
``message_dtype="bfloat16"`` against the float32 path.

Run:  PYTHONPATH=src python -m benchmarks.bsp_apps
          [--smoke] [--json out] [--latency] [--bf16-study]
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.bsp import PartitionRuntime, build_app, frontier_entries
from repro.bsp.engine import make_fused_runner, make_step, run_bsp
from repro.core import scaled_paper_cluster
from repro.core.partitioners import get as partitioner
from repro.data import rmat, road_mesh

from .common import (CSV, cluster_for, dataset, median_iqr, spread_str,
                     write_bench_json)

APPS = ("pagerank", "sssp", "bfs", "cc")
BACKENDS = ("scatter", "segment", "pallas")

#: CPU-fitting Pallas tile for the proxies (128 is the TPU/MXU default;
#: the interpreter does not need MXU alignment and the dense blocks of a
#: proxy-sized graph stay in memory at 32/64)
SMOKE_BLOCK = 32


def _app_opts(app: str, backend: str, block_size: int) -> dict:
    opts = {} if backend != "pallas" else {"block_size": block_size}
    if app in ("sssp", "bfs"):
        opts["source"] = 0
    return opts


def _superstep_seconds(rt, app: str, backend: str, *, iters: int = 8,
                       repeats: int = 3, block_size: int = SMOKE_BLOCK):
    """Median seconds per (jit-compiled, vmap) superstep, state evolving."""
    spec = build_app(rt, app, backend=backend,
                     **_app_opts(app, backend, block_size))
    step = make_step(spec.superstep, spec.static)
    state, _ = step(spec.state)                 # compile + warm
    jax.block_until_ready(state)
    times = []
    for _ in range(max(1, repeats)):
        state = spec.state
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _ = step(state)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) / iters)
    return times


def _run_app(rt, app: str, backend: str, iters: int,
             block_size: int = SMOKE_BLOCK):
    """Final global result array after ``iters`` supersteps."""
    spec = build_app(rt, app, backend=backend,
                     **_app_opts(app, backend, block_size))
    out, _ = run_bsp(spec.superstep, spec.state, spec.static, iters,
                     check_rep=spec.check_rep)
    return spec.finalize(rt, out)


def _wall(fn, repeats: int = 5) -> float:
    """Median wall seconds of ``fn()`` (first call warms/compiles)."""
    fn()
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _steploop(step, state, iters: int):
    """``run_bsp``'s steady state: per-step dispatch + host sync on act."""
    for _ in range(iters):
        state, act = step(state)
        np.asarray(act)
    return state


def _pow2_cap(n: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1, int(n))))))


def _partition(g, cl) -> PartitionRuntime:
    return PartitionRuntime.create(g, assign=partitioner("hdrf")(g, cl),
                                   cluster=cl)


def _equivalence(rt, iters: int = 10, block_size: int = SMOKE_BLOCK):
    """Max |scatter − backend| result gap per app over the other backends."""
    gaps = {}
    for app in APPS:
        ref = _run_app(rt, app, "scatter", iters)
        worst = 0.0
        for be in BACKENDS[1:]:
            got = _run_app(rt, app, be, iters, block_size)
            m = np.isfinite(ref)
            assert (np.isfinite(got) == m).all(), (app, be, "inf mismatch")
            if m.any():
                worst = max(worst, float(np.abs(got[m] - ref[m]).max()))
        gaps[app] = worst
    return gaps


def run(quick: bool = True, datasets=("LJ", "RN"), apps=APPS,
        backends=("scatter", "segment"), repeats: int = 3,
        iters: int = 8) -> dict:
    """Backend timing table at proxy scale.

    ``pallas`` is excluded from timing by default: off-TPU it runs the
    Pallas *interpreter* (a correctness path, orders of magnitude slower
    than compiled), so timing it on CPU proxies only measures the
    emulator.  Pass ``backends=BACKENDS`` on a TPU host (or
    ``--with-pallas``) to include it; its layout fill stats — the part
    that matters off-TPU — are always reported, and the smoke gate checks
    its results on the tiny proxy where the interpreter is affordable.
    """
    csv = CSV("bsp_apps")
    out = {}
    for ds in datasets:
        g = dataset(ds, quick)
        cl = cluster_for(ds, g)
        rt = _partition(g, cl)
        edges = int(rt.edge_valid.sum())
        res = {}
        for app in apps:
            base = None
            ref = None
            for be in backends:
                times = _superstep_seconds(rt, app, be, iters=iters,
                                           repeats=repeats)
                med, _ = median_iqr(times)
                if be == "scatter":
                    base = med
                speed = base / max(med, 1e-9)
                csv.row(f"{ds}/{app}/{be}", med,
                        f"{spread_str(times)} {edges/med/1e6:.2f}Medges/s "
                        f"{speed:.2f}x")
                res[f"{app}/{be}"] = {"seconds": med, "speedup": speed}
                got = _run_app(rt, app, be, max(4, iters // 2))
                if ref is None:
                    ref = got
                else:
                    m = np.isfinite(ref)
                    gp = float(np.abs(got[m] - ref[m]).max()) if m.any() \
                        else 0.0
                    csv.row(f"{ds}/{app}/{be}_gap", 0, f"{gp:.2e}")
                    res[f"{app}/{be}_gap"] = gp
        bsr = rt.local_bsr(block_size=SMOKE_BLOCK)
        csv.row(f"{ds}/pallas/fill", 0, str(bsr.aggregate_fill()))
        res["fill"] = bsr.aggregate_fill()
        out[ds] = res
    return out


def bf16_error_study(rt, iters: int = 20, backend: str = "segment",
                     csv: CSV | None = None) -> list[float]:
    """PageRank error-vs-iteration for the bf16 message path.

    Steps the float32 and ``message_dtype="bfloat16"`` specs in
    lockstep and reports the per-iteration L∞ gap — the number that
    tells you whether halving message bandwidth is free at your
    iteration budget (the gap saturates near bf16's ~3e-3 relative
    resolution of the stationary distribution instead of growing).
    Returns the per-iteration absolute L∞ errors.
    """
    spec32 = build_app(rt, "pagerank", backend=backend)
    spec16 = build_app(rt, "pagerank", backend=backend,
                       message_dtype="bfloat16")
    s32 = make_step(spec32.superstep, spec32.static)
    s16 = make_step(spec16.superstep, spec16.static)
    st32, st16 = spec32.state, spec16.state
    errs = []
    for t in range(iters):
        st32, _ = s32(st32)
        st16, _ = s16(st16)
        a32 = np.asarray(st32["pr"])
        err = float(np.abs(np.asarray(st16["pr"]) - a32).max())
        errs.append(err)
        if csv is not None:
            rel = err / max(float(np.abs(a32).max()), 1e-30)
            csv.row(f"bf16/iter{t + 1:02d}", 0,
                    f"Linf={err:.3e} rel={rel:.3e}")
    return errs


def _frontier_table(rt, app: str, csv: CSV, repeats: int = 5,
                    max_steps: int = 40):
    """Per-superstep wall of the frontier-compacted scatter step vs the
    dense one, along the app's actual frontier trajectory.

    Walks the dense stepwise run; at every state, counts the live
    directed entries (:func:`frontier_entries`), buckets ``frontier_cap``
    to the next power of two (one compile per bucket, cached), checks
    the compacted step reproduces the dense step bitwise, and times
    both on that state.
    """
    spec_d = build_app(rt, app, backend="scatter", source=0)
    step_d = make_step(spec_d.superstep, spec_d.static)
    state, _ = step_d(spec_d.state)
    jax.block_until_ready(state)
    state = spec_d.state
    cache = {}
    for t in range(max_steps):
        if app == "sssp":
            changed = np.asarray(state["changed"])
        else:   # bfs: the frontier is the layer discovered last step
            changed = (np.asarray(state["dist"])
                       == np.asarray(state["step"])[:, None])
        cnt = frontier_entries(rt, changed)
        cap = _pow2_cap(cnt.max())
        if cap not in cache:
            sp = build_app(rt, app, backend="scatter", source=0,
                           frontier_cap=cap)
            cache[cap] = make_step(sp.superstep, sp.static)
        step_f = cache[cap]
        ref, act = step_d(state)
        got, act_f = step_f(state)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        t_d = _wall(lambda: jax.block_until_ready(step_d(state)), repeats)
        t_f = _wall(lambda: jax.block_until_ready(step_f(state)), repeats)
        csv.row(f"{app}/step{t:02d}", t_f,
                f"frontier={int(cnt.sum())} cap={cap} "
                f"dense={t_d * 1e6:.0f}us {t_d / max(t_f, 1e-9):.2f}x")
        state = ref
        if int(np.asarray(act).sum()) == 0:
            break


def run_latency(repeats: int = 5) -> dict:
    """``--latency``: the superstep-latency study (see module docstring).

    Three tables: (A) matched-iteration per-superstep wall, fused vs
    stepwise, across chunk sizes — what removing the per-step dispatch
    + host sync is worth on its own; (B) convergence-gated fused runs
    against the full stepwise iteration budget — the early-exit win
    (bitwise for the monotone apps, ≤ tol·d/(1−d) for PageRank); (C)
    the BFS/SSSP frontier-compaction table on the mesh proxy, dense vs
    ``frontier_cap`` re-bucketed per superstep.
    """
    out = {}
    csv = CSV("bsp_latency")
    g = rmat(9, seed=2)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    rt = _partition(g, cl)

    # -- A: matched iterations, supersteps/sec vs chunk size ---------------
    iters = 20
    for backend in ("scatter", "segment"):
        spec = build_app(rt, "pagerank", backend=backend)
        step = make_step(spec.superstep, spec.static)
        t_s = _wall(lambda: _steploop(step, spec.state, iters), repeats)
        csv.row(f"pagerank/{backend}/stepwise", t_s / iters,
                f"{iters / t_s:.0f} steps/s")
        for chunk in (2, 4, 8, 16):
            runner = make_fused_runner(spec.superstep, spec.static,
                                       chunk=chunk)
            t_f = _wall(lambda: runner(spec.state, iters), repeats)
            csv.row(f"pagerank/{backend}/fused_c{chunk}", t_f / iters,
                    f"{iters / t_f:.0f} steps/s {t_s / t_f:.2f}x")
            out[f"pagerank/{backend}/fused_c{chunk}_speedup"] = t_s / t_f

    # -- B: convergence-gated fused vs full-budget stepwise ----------------
    budget = 60
    spec = build_app(rt, "pagerank", backend="segment")
    step = make_step(spec.superstep, spec.static)
    t_s = _wall(lambda: _steploop(step, spec.state, budget), repeats)
    runner = make_fused_runner(spec.superstep, spec.static, chunk=8,
                               tol=1e-7)
    t_f = _wall(lambda: runner(spec.state, budget), repeats)
    _, acts = runner(spec.state, budget)
    csv.row("pagerank/segment/fused_tol1e-7", t_f,
            f"{len(acts)}/{budget} steps, {t_s / t_f:.2f}x vs "
            f"stepwise budget")
    out["pagerank/tol_speedup"] = t_s / t_f
    for app in ("sssp", "bfs", "cc"):
        sp = build_app(rt, app, backend="segment",
                       **_app_opts(app, "segment", SMOKE_BLOCK))
        step = make_step(sp.superstep, sp.static)
        t_s = _wall(lambda: _steploop(step, sp.state, 30), repeats)
        runner = make_fused_runner(sp.superstep, sp.static, chunk=8)
        t_f = _wall(lambda: runner(sp.state, 30), repeats)
        _, acts = runner(sp.state, 30)
        csv.row(f"{app}/segment/fused_exit", t_f,
                f"{len(acts)}/30 steps, {t_s / t_f:.2f}x vs "
                f"stepwise budget")
        out[f"{app}/exit_speedup"] = t_s / t_f

    # -- C: frontier sparsification along the real trajectory --------------
    # mesh proxy: long drain (~55 supersteps), frontier peaks at a few
    # hundred directed entries against 2·Emax ≈ 1.5k dense ones — the
    # regime the compaction targets (caps stay ≪ the incidence size; on
    # a tiny power-law proxy the O(E) mask floor hides the win)
    g = road_mesh(48, rewire=0.02, seed=42)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    rt = _partition(g, cl)
    for app in ("sssp", "bfs"):
        _frontier_table(rt, app, csv, repeats, max_steps=60)
    return out


def run_smoke(json_path: str | None = None) -> dict:
    """Tier-2 CI gate, three parts:

    * backend equivalence on a tiny proxy, all four apps: (min, +) and
      (or, and) apps must match ``scatter`` bitwise, (+, ×) within 1e-5
      (the cross-backend contract the tests pin per superstep; drift is
      the segment path's reassociated float sum);
    * ``segment`` ≥ 2× ``scatter`` PageRank superstep throughput on the
      LJ proxy (the backend the refactor makes the CPU default
      candidate must actually pay for itself);
    * fused runner ≡ stepwise on the tiny proxy, all four apps ×
      matched iterations (bitwise for the min/max semirings, ≤ 1e-6 for
      (+, ×) — gated in the trend baseline), and the convergence-gated
      fused PageRank run ≥ 2× the full-budget stepwise wall with ≤ 1e-6
      result drift;
    * the bf16 message path's final PageRank L∞ error (tracked,
      ungated) and the Pallas layout's ELL fill stats on the LJ proxy.
    """
    metrics = {}
    csv = CSV("bsp_smoke")

    # -- equivalence on the tiny proxy (pallas included) -------------------
    g = rmat(9, seed=2)
    cl = scaled_paper_cluster(2, 4, g.num_edges)
    rt = _partition(g, cl)
    gaps = _equivalence(rt, iters=10)
    for app, gp in gaps.items():
        tol = 1e-5 if app == "pagerank" else 0.0
        assert gp <= tol, (f"{app}: cross-backend gap {gp:.2e} > {tol} "
                           f"(scatter vs segment/pallas)")
        csv.row(f"equiv/{app}", 0, f"gap={gp:.2e} (tol {tol})")
        metrics[f"bsp/equiv/{app}_gap"] = gp

    # -- fused runner ≡ stepwise (matched iterations) ----------------------
    for app in APPS:
        spec = build_app(rt, app, backend="segment",
                         **_app_opts(app, "segment", SMOKE_BLOCK))
        out_s, _ = run_bsp(spec.superstep, spec.state, spec.static, 10)
        runner = make_fused_runner(spec.superstep, spec.static, chunk=4)
        out_f, _ = runner(spec.state, 10)
        ref = spec.finalize(rt, out_s)
        got = spec.finalize(rt, out_f)
        m = np.isfinite(ref)
        assert (np.isfinite(got) == m).all(), (app, "fused inf mismatch")
        gp = float(np.abs(got[m] - ref[m]).max()) if m.any() else 0.0
        tol = 1e-6 if app == "pagerank" else 0.0
        assert gp <= tol, f"{app}: fused vs stepwise gap {gp:.2e} > {tol}"
        csv.row(f"fused/{app}", 0, f"gap={gp:.2e} (tol {tol})")
        metrics[f"bsp/fused/{app}_gap"] = gp

    # -- convergence-gated fused ≥ 2× the full-budget stepwise wall --------
    budget = 60
    spec = build_app(rt, "pagerank", backend="segment")
    step = make_step(spec.superstep, spec.static)
    t_s = _wall(lambda: _steploop(step, spec.state, budget))
    runner = make_fused_runner(spec.superstep, spec.static, chunk=8,
                               tol=1e-7)
    t_f = _wall(lambda: runner(spec.state, budget))
    out_f, acts = runner(spec.state, budget)
    out_s, _ = run_bsp(spec.superstep, spec.state, spec.static, budget)
    drift = float(np.abs(np.asarray(out_f["pr"])
                         - np.asarray(out_s["pr"])).max())
    speed = t_s / max(t_f, 1e-9)
    csv.row("fused/pagerank_tol", t_f,
            f"{len(acts)}/{budget} steps {speed:.2f}x drift={drift:.2e}")
    assert drift <= 1e-6, (
        f"tol-gated fused PageRank drifts {drift:.2e} > 1e-6 from the "
        f"full-budget stepwise run (tol=1e-7 residual gate)")
    assert speed >= 2.0, (
        f"fused PageRank (tol=1e-7, early exit after {len(acts)} of "
        f"{budget} supersteps) only {speed:.2f}x the stepwise budget "
        f"(gate: >= 2x)")
    metrics["bsp/fused/pagerank_speedup"] = speed

    # -- bf16 message path: error vs iteration -----------------------------
    errs = bf16_error_study(rt, iters=15, csv=csv)
    assert all(np.isfinite(errs)), "bf16 PageRank diverged"
    metrics["bsp/bf16/pagerank_final_err"] = errs[-1]

    # -- segment vs scatter PageRank throughput on the LJ proxy ------------
    g = dataset("LJ", True)
    cl = cluster_for("LJ", g)
    rt = _partition(g, cl)
    edges = int(rt.edge_valid.sum())
    t_sc, _ = median_iqr(_superstep_seconds(rt, "pagerank", "scatter"))
    t_sg, _ = median_iqr(_superstep_seconds(rt, "pagerank", "segment"))
    speed = t_sc / max(t_sg, 1e-9)
    csv.row("lj/pagerank/scatter", t_sc, f"{edges/t_sc/1e6:.2f}Medges/s")
    csv.row("lj/pagerank/segment", t_sg,
            f"{edges/t_sg/1e6:.2f}Medges/s {speed:.2f}x")
    assert speed >= 2.0, (
        f"segment backend PageRank superstep only {speed:.2f}x scatter "
        f"on the LJ proxy (gate: >= 2x)")
    metrics["bsp/pagerank/segment_speedup"] = speed

    # -- Pallas ELL fill stats on the LJ proxy -----------------------------
    fill = rt.local_bsr(block_size=SMOKE_BLOCK).aggregate_fill()
    csv.row("lj/pallas/fill", 0,
            f"block_fill={fill['block_fill']:.3f} "
            f"entry_fill={fill['entry_fill']:.4f} "
            f"ell_k_max={fill['ell_k_max']} bm={fill['block_size']}")
    metrics["bsp/pallas/block_fill"] = fill["block_fill"]
    metrics["bsp/pallas/entry_fill"] = fill["entry_fill"]
    metrics["bsp/pallas/ell_k_max"] = fill["ell_k_max"]

    if json_path:
        write_bench_json(json_path, metrics)
    return metrics


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tier-2 CI gate: backend equivalence + segment "
                         ">= 2x scatter PageRank throughput on the LJ "
                         "proxy + pallas ELL fill stats")
    ap.add_argument("--json", default=None,
                    help="write gateable metrics to this path "
                         "(BENCH_smoke.json for CI)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--with-pallas", action="store_true",
                    help="include pallas in the timing table (TPU hosts; "
                         "on CPU this times the interpreter)")
    ap.add_argument("--latency", action="store_true",
                    help="superstep-latency study: fused vs stepwise "
                         "wall (supersteps/sec vs chunk size), "
                         "convergence-gated early exit, and the BFS/SSSP "
                         "frontier-compaction table")
    ap.add_argument("--bf16-study", action="store_true",
                    help="PageRank error-vs-iteration table for the "
                         "bfloat16 message path")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("table/name,us_per_call,derived")
    if args.smoke:
        run_smoke(json_path=args.json)
    elif args.latency:
        run_latency(repeats=max(3, args.repeats))
    elif args.bf16_study:
        g = dataset("LJ", quick=not args.full)
        rt = _partition(g, cluster_for("LJ", g))
        bf16_error_study(rt, iters=30, csv=CSV("bsp_bf16"))
    else:
        run(quick=not args.full, repeats=args.repeats,
            backends=BACKENDS if args.with_pallas
            else ("scatter", "segment"))
