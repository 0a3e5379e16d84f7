"""Chip smoke: the graph system's device path, end to end, on a TPU.

    python chip_smoke.py              # one chip: the three phases below
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases, each printing one JSON line (sizes, device bytes, compile and run
seconds, error against the reference):

* ``graph500`` — LDBC Graphalytics ``graph500-22``, cut to scale 21 to
  fit the run's time limit (edge factor 16, Kronecker
  (.57, .19, .19, .05)), generated from ``--seed``,
  partitioned by WindGP on the paper's 2 super + 6 normal machine profile,
  packed by ``PartitionRuntime.create``, then PageRank (20 iterations),
  BFS and SSSP from a sampled non-isolated root, fused on the device,
  under the ``scatter`` and ``segment`` edge kernels.  BFS and SSSP must
  equal ``bsp/ref.py`` exactly; PageRank must agree per vertex within
  ``PR_RTOL``.
* ``pallas`` — the Block-ELL Pallas kernel compiled for the chip (its
  program must hold a ``tpu_custom_call``) under all three semirings, at
  the largest graph500 scale whose padded ``LocalBSR`` fits well inside
  16 GB, checked against the same references.
* ``sampling`` — one fused k-hop ``SamplingService.sample`` batch (fanouts
  15/10/5) on a road mesh of roadNet-CA size, bitwise equal to the NumPy
  oracle ``sample_fanout_np`` on the same keys.

``--chips 4`` runs the multi-device BSP path alone: ``shard_map`` over a
4-device ``machines`` mesh, one machine per chip, WindGP on 1 super +
3 normal machines, PageRank and BFS compared with the one-device run of
the same partition and with the references.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Any mismatch or exception exits non-zero, and so does a run where JAX
finds no TPU: there is no CPU fallback.  One process drives the chip and
nothing is forked; partitioning is the sequential in-memory WindGP.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import obs  # noqa: E402
from repro.bsp import (PartitionRuntime, bfs, build_app,  # noqa: E402
                       make_fused_runner, pagerank, ref, sssp)
from repro.core import scaled_paper_cluster  # noqa: E402
from repro.data import graph500, road_mesh  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.sampling import SamplingService, sample_fanout_np  # noqa: E402

#: LDBC Graphalytics graph500-22 cut to scale 21: at 22, set-up alone
#: (generate 146 s, WindGP + pack 471 s on the v5e host) left the whole
#: smoke at 1213 s, past its 1200 s limit
GRAPH500_SCALE = 21
#: largest graph500 scale whose padded LocalBSR blocks (2.3 GB f32 per
#: semiring at 14; 8.7 GB at 15) fit well inside a v5e's 16 GB
KERNEL_SCALE = 14
#: roadNet-CA size: 1404² ≈ 1.97M vertices and 4.0M edges
ROAD_SIDE = 1404
FANOUTS = (15, 10, 5)
BATCH = 1024
#: the --chips 4 deployment: graph500 scale and WindGP machine profile
MESH_SCALE = 18
MESH_PROFILE = (1, 3)
PR_ITERS = 20
#: BFS/SSSP superstep budget, ``ref.sssp``'s own cap; runs must converge
#: inside it
MAX_STEPS = 30
#: per-vertex relative PageRank error against the float64 reference,
#: stated before any chip run (see CHANGES.md for the derivation)
PR_RTOL = 1e-4
BACKENDS = ("scatter", "segment")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def compile_seconds() -> float:
    """Seconds JAX spent in backend compiles so far, as ``repro.obs``
    counts them."""
    return obs.totals()["counts"].get(COMPILE_EVENT, 0.0)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def peak_device_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def runtime_bytes(rt: PartitionRuntime) -> int:
    return int(sum(a.nbytes for a in (
        rt.local_edges, rt.edge_valid, rt.edge_weight, rt.vertex_valid,
        rt.global_degree, rt.weighted_degree, rt.rep_slot)))


def partitioned_graph500(scale: int, profile, seed: int):
    """Generate graph500 at ``scale``, WindGP-partition and pack it."""
    t = time.perf_counter()
    g = graph500(scale, seed=seed)
    gen_s = time.perf_counter() - t
    cl = scaled_paper_cluster(*profile, g.num_edges)
    t = time.perf_counter()
    rt = PartitionRuntime.create(source=g, method="windgp", cluster=cl)
    part_s = time.perf_counter() - t
    deg = g.degree()
    rng = np.random.default_rng(seed)
    root = int(rng.choice(np.flatnonzero(deg > 0)))
    info = {"scale": scale, "V": g.num_vertices, "E": g.num_edges,
            "max_degree": int(deg.max()), "p": rt.p, "vmax": rt.vmax,
            "emax": rt.emax, "replicas": rt.num_replicas, "root": root,
            "runtime_bytes": runtime_bytes(rt),
            "generate_s": gen_s, "partition_pack_s": part_s}
    return g, rt, root, info


def references(g, root: int):
    """float64 NumPy references; SSSP runs on unit weights, which is BFS."""
    t = time.perf_counter()
    pr = ref.pagerank(g, PR_ITERS)
    dist = ref.sssp(g, root, num_iters=MAX_STEPS)
    return pr, dist, time.perf_counter() - t


def pr_error(pr: np.ndarray, pr_ref: np.ndarray) -> float:
    return float(np.max(np.abs(pr.astype(np.float64) - pr_ref) / pr_ref))


def judge(app: str, out: np.ndarray, actives, pr_ref, dist_ref) -> dict:
    """Compare one app's result with its reference; raises on mismatch."""
    if app == "pagerank":
        err = pr_error(out, pr_ref)
        check(err <= PR_RTOL, f"pagerank rel err {err} > {PR_RTOL}")
        return {"max_rel_err": err}
    check(len(actives) < MAX_STEPS or not actives[-1].any(),
          f"{app} did not converge in {MAX_STEPS} supersteps")
    mism = int(np.sum(out.astype(np.float64) != dist_ref))
    check(mism == 0, f"{app}: {mism} vertices differ from ref")
    return {"mismatches": mism, "reached": int(np.isfinite(out).sum()),
            "depth": float(out[np.isfinite(out)].max())}


APP_ENTRY = {"pagerank": lambda rt, root, **kw: pagerank(rt, PR_ITERS, **kw),
             "bfs": lambda rt, root, **kw: bfs(rt, root, MAX_STEPS, **kw),
             "sssp": lambda rt, root, **kw: sssp(rt, root, MAX_STEPS, **kw)}


def app_kwargs(app: str, root: int) -> dict:
    return {} if app == "pagerank" else {"source": root}


def steps_of(app: str) -> int:
    return PR_ITERS if app == "pagerank" else MAX_STEPS


def phase_graph500(seed: int) -> dict:
    g, rt, root, info = partitioned_graph500(GRAPH500_SCALE, (2, 6), seed)
    pr_ref, dist_ref, ref_s = references(g, root)
    runs = []
    for backend in BACKENDS:
        for app, entry in APP_ENTRY.items():
            c0, t = compile_seconds(), time.perf_counter()
            out, actives = entry(rt, root, backend=backend, fused=True)
            call_s = time.perf_counter() - t
            compile_s = compile_seconds() - c0
            runs.append({"backend": backend, "app": app,
                         "supersteps": len(actives), "call_s": call_s,
                         "compile_s": compile_s,
                         "run_s": call_s - compile_s,
                         **judge(app, out, actives, pr_ref, dist_ref)})
    return {"phase": "graph500", **info, "ref_s": ref_s, "runs": runs,
            "peak_device_bytes": peak_device_bytes()}


def phase_pallas(seed: int) -> dict:
    g, rt, root, info = partitioned_graph500(KERNEL_SCALE, (2, 6), seed)
    pr_ref, dist_ref, ref_s = references(g, root)
    runs = []
    for app in ("pagerank", "sssp", "bfs"):
        t = time.perf_counter()
        spec = build_app(rt, app, backend="pallas", **app_kwargs(app, root))
        runner = make_fused_runner(spec.superstep, spec.static,
                                   check_rep=spec.check_rep)
        prep_s = time.perf_counter() - t
        t = time.perf_counter()
        compiled = runner.lower(spec.state, steps_of(app)).compile()
        compile_s = time.perf_counter() - t
        check("tpu_custom_call" in compiled.as_text(),
              f"pallas {app}: no tpu_custom_call in the compiled program")
        t = time.perf_counter()
        state, actives = runner(spec.state, steps_of(app))
        out = spec.finalize(rt, state)
        run_s = time.perf_counter() - t
        blocks = spec.static["eb_bsr_blocks"]
        runs.append({"app": app, "blocks_shape": list(blocks.shape),
                     "blocks_bytes": int(blocks.nbytes),
                     "supersteps": len(actives), "prep_s": prep_s,
                     "compile_s": compile_s, "run_s": run_s,
                     "tpu_custom_call": True,
                     **judge(app, out, actives, pr_ref, dist_ref)})
        del spec, runner, compiled, state
    return {"phase": "pallas", **info, "ref_s": ref_s, "runs": runs,
            "peak_device_bytes": peak_device_bytes()}


def khop_oracle(svc: SamplingService, seeds: np.ndarray, key) -> list:
    """The k-hop batch hop by hop through the NumPy sampler oracle, with
    the service's key rule (one ``split`` per hop)."""
    csc = svc.csc
    table = csc.nbr.reshape(csc.p * csc.omax, csc.max_degree)
    deg = csc.deg.reshape(-1)
    rowmap = csc.flat_rowmap()
    frontier, hops = seeds, []
    for fanout in FANOUTS:
        key, sub = jax.random.split(key)
        rows = np.where(frontier >= 0,
                        rowmap[np.clip(frontier, 0, len(rowmap) - 1)], -1)
        frontier = sample_fanout_np(table, deg, rows, sub,
                                    fanout).reshape(-1)
        hops.append(frontier)
    return hops


def phase_sampling(seed: int) -> dict:
    t = time.perf_counter()
    g = road_mesh(ROAD_SIDE, seed=seed)
    cl = scaled_paper_cluster(2, 6, g.num_edges)
    svc = SamplingService.create(source=g, method="windgp", cluster=cl,
                                 fanouts=FANOUTS)
    setup_s = time.perf_counter() - t
    k_seeds, k_batch = jax.random.split(jax.random.PRNGKey(seed))
    seeds = svc.local_seeds(0, BATCH, k_seeds)
    c0, t = compile_seconds(), time.perf_counter()
    batch = svc.sample(seeds, k_batch, home=0)
    first_s = time.perf_counter() - t
    compile_s = compile_seconds() - c0
    t = time.perf_counter()
    again = svc.sample(seeds, k_batch, home=0)
    run_s = time.perf_counter() - t
    t = time.perf_counter()
    want = khop_oracle(svc, seeds, k_batch)
    oracle_s = time.perf_counter() - t
    for h, (got, exp, rep) in enumerate(zip(batch.hops, want, again.hops)):
        check(np.array_equal(got, exp), f"sampling hop {h} != oracle")
        check(np.array_equal(got, rep), f"sampling hop {h} not repeatable")
    csc = svc.csc
    return {"phase": "sampling", "V": g.num_vertices, "E": g.num_edges,
            "max_degree": csc.max_degree, "p": csc.p, "omax": csc.omax,
            "table_bytes": int(csc.nbr.nbytes), "seeds": len(seeds),
            "fanouts": list(FANOUTS), "sampled": batch.num_sampled(),
            "halo_fracs": list(batch.halo_fracs()), "setup_s": setup_s,
            "first_call_s": first_s, "compile_s": compile_s,
            "run_s": run_s, "oracle_s": oracle_s, "bitwise_equal": True,
            "peak_device_bytes": peak_device_bytes()}


def phase_mesh(seed: int, chips: int) -> dict:
    g, rt, root, info = partitioned_graph500(MESH_SCALE, MESH_PROFILE, seed)
    check(rt.p == chips, f"profile gives p={rt.p}, need one per chip")
    pr_ref, dist_ref, ref_s = references(g, root)
    mesh = make_mesh((chips,), ("machines",))
    runs = []
    for app in ("pagerank", "bfs"):
        one, _ = APP_ENTRY[app](rt, root, fused=True)
        spec = build_app(rt, app, **app_kwargs(app, root))
        runner = make_fused_runner(spec.superstep, spec.static, mesh=mesh,
                                   check_rep=spec.check_rep)
        t = time.perf_counter()
        compiled = runner.lower(spec.state, steps_of(app)).compile()
        compile_s = time.perf_counter() - t
        t = time.perf_counter()
        state, actives = runner(spec.state, steps_of(app))
        out = spec.finalize(rt, state)
        run_s = time.perf_counter() - t
        for leaf in jax.tree.leaves(state):
            shards = leaf.addressable_shards
            devs = {s.device for s in shards}
            check(len(devs) == chips and all(s.data.shape[0] == 1
                                             for s in shards),
                  f"{app}: state shards on {sorted(map(str, devs))}")
        if app == "pagerank":
            vs_one = {"max_rel_diff_vs_one_device": pr_error(out, one)}
            check(vs_one["max_rel_diff_vs_one_device"] <= PR_RTOL,
                  "pagerank: mesh run disagrees with the one-device run")
        else:
            vs_one = {"mismatches_vs_one_device":
                      int(np.sum(out != one))}
            check(vs_one["mismatches_vs_one_device"] == 0,
                  "bfs: mesh run disagrees with the one-device run")
        runs.append({"app": app, "supersteps": len(actives),
                     "compile_s": compile_s, "run_s": run_s,
                     "shard_devices": chips,
                     "all_reduce": "all-reduce" in compiled.as_text(),
                     **vs_one,
                     **judge(app, out, actives, pr_ref, dist_ref)})
    return {"phase": "mesh", "chips": chips, **info, "ref_s": ref_s,
            "runs": runs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    obs.listen()
    emit({"phase": "setup", "compile_cache": cache,
          "jax": jax.__version__, "devices": len(devices)})
    if args.chips == 4:
        emit(phase_mesh(args.seed, args.chips))
    else:
        emit(phase_graph500(args.seed))
        emit(phase_pallas(args.seed))
        emit(phase_sampling(args.seed))
    d = jax.devices()[0]
    emit({"ok": True, "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
