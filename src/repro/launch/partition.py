"""Graph-partitioning CLI: the production entry for the paper's own task.

  PYTHONPATH=src python -m repro.launch.partition \
      --graph rmat:13 --super 3 --normal 6 --method windgp --out part.npz
  PYTHONPATH=src python -m repro.launch.partition --graph edges.txt ...
  PYTHONPATH=src python -m repro.launch.partition --graph edges.txt.gz \
      --method hdrf --stream --dedup two_pass --out-dir parts/

Methods resolve through the unified partitioner registry
(``repro.core.partitioners``); ``--block-size`` reaches every method with
the ``blocked`` capability (the block-stream scorers).  ``--stream`` runs
a ``streamable`` method graph-free over an edge-list file — the edge set
never materializes; ``--dedup two_pass`` adds the exact spill-to-disk
dedup, and ``--out-dir`` persists the on-disk ``StreamAssignment``
(per-machine shards + membership) that ``PartitionRuntime.from_stream``
packs into the BSP runtime.  ``--workers W`` runs the whole stream
through the multi-process pipeline (``repro.core.parallel``): sharded
dedup plus W-worker wave scoring against membership snapshots synced
every ``--sync-blocks`` engine blocks.  ``--compact DIR`` is a
standalone maintenance pass: fold accumulated tombstone debt into the
shards of a finalized assignment directory and republish its meta.

``--pagerank`` closes the loop: it packs the partition it just built into
the BSP runtime and runs distributed PageRank supersteps on it, through a
selectable **edge-kernel backend** (``--backend``, see
``repro.bsp.backends``): ``segment`` (the default) reduces the
destination-sorted incidence by a segmented scan, ``scatter`` is the
gather-scatter oracle, ``pallas`` the blocked Block-ELL semiring SpMV (compiled on TPU,
interpreter on CPU).  ``--fused`` runs the whole
iteration as one on-device dispatch (``run_bsp_fused``) instead of one
dispatch + host sync per superstep; ``--tol`` additionally stops once
``‖pr_{t+1} − pr_t‖∞ ≤ tol`` (implies ``--fused``); ``--message-dtype
bfloat16`` opts into the low-precision message path.  In ``--stream``
mode ``--pagerank`` requires ``--out-dir`` (the runtime packs from the
on-disk shards, one machine at a time).

Every partition this CLI emits is also a valid *seed* for the dynamic
layer (``repro.core.DynamicPartitioner``): live edge inserts/deletes,
drift-triggered bounded repair, and epoch deltas that update the
``--out-dir`` shards and the BSP runtime in place — see the
dynamic-replay benchmark (``python -m benchmarks.dynamic_replay``) for
the measured workflow.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from ..core import evaluate, evaluate_membership, scaled_paper_cluster, windgp
from ..core import partitioners as registry
from ..data import graph500, read_edge_list, rmat, road_mesh

#: static mirror of ``repro.bsp.backends.BACKENDS`` — the bsp package
#: (and jax) must not load on the plain numpy partition path; a test
#: pins the two in sync
EDGE_BACKENDS = ("scatter", "segment", "pallas")

#: static mirror of ``repro.bsp.backends.MESSAGE_DTYPES`` (same test)
MESSAGE_DTYPES = ("float32", "bfloat16", "float16")


def load_graph(spec: str):
    if spec.startswith("rmat:"):
        return rmat(int(spec.split(":")[1]), seed=42)
    if spec.startswith("graph500:"):
        return graph500(int(spec.split(":")[1]), seed=42)
    if spec.startswith("mesh:"):
        return road_mesh(int(spec.split(":")[1]), seed=42)
    return read_edge_list(spec)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Graph-partitioning CLI (see module docstring for "
                    "the full tour).",
        epilog="dynamic-replay usage: the emitted partition seeds "
               "repro.core.DynamicPartitioner for live insert/delete "
               "streams with drift-triggered bounded repair; replay a "
               "mutation timeline against it (assignment-latency "
               "percentiles, amortized repair cost, TC drift vs "
               "scratch) with: PYTHONPATH=src python -m "
               "benchmarks.dynamic_replay [--smoke]")
    ap.add_argument("--graph",
                    help="rmat:<scale> | graph500:<scale> | mesh:<side> | "
                         "path to an edge list (.gz ok)")
    ap.add_argument("--super", type=int, default=3)
    ap.add_argument("--normal", type=int, default=6)
    ap.add_argument("--slack", type=float, default=1.8)
    ap.add_argument("--method", default="windgp",
                    choices=registry.names(exclude={"oracle"}))
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--beta", type=float, default=0.3)
    ap.add_argument("--t0", type=int, default=8)
    ap.add_argument("--theta", type=float, default=0.01)
    ap.add_argument("--block-size", type=int, default=None,
                    help="stream-block size for 'blocked' methods")
    ap.add_argument("--stream", action="store_true",
                    help="out-of-core: partition an edge-list file "
                         "graph-free ('streamable' methods only)")
    ap.add_argument("--dedup", default="block",
                    choices=("block", "two_pass"),
                    help="--stream dedup discipline: per-block only, or "
                         "exact two-pass spill-to-disk")
    ap.add_argument("--out-dir", default=None,
                    help="--stream: persist the StreamAssignment "
                         "(per-machine shards + membership) here")
    ap.add_argument("--workers", type=int, default=1,
                    help="--stream: run the W-process pipeline (sharded "
                         "two-pass dedup + parallel wave scoring); 1 = "
                         "the sequential path bit for bit")
    ap.add_argument("--sync-blocks", type=int, default=None,
                    help="--workers > 1: engine blocks between membership "
                         "sync barriers (1 = bit-identical to sequential; "
                         "default trades a bounded staleness window for "
                         "scoring overlap)")
    ap.add_argument("--compact", default=None, metavar="DIR",
                    help="standalone maintenance: fold tombstone debt "
                         "into the shards of a finalized StreamAssignment "
                         "directory, then exit (no partitioning run)")
    ap.add_argument("--compact-tomb-frac", type=float, default=0.0,
                    help="--compact: only rewrite shards whose tombstone "
                         "fraction exceeds this (0.0 = fold everything)")
    ap.add_argument("--pagerank", action="store_true",
                    help="after partitioning, pack the BSP runtime and "
                         "run distributed PageRank on the partition")
    ap.add_argument("--pagerank-iters", type=int, default=20)
    ap.add_argument("--backend", default="segment",
                    choices=EDGE_BACKENDS,
                    help="edge-kernel backend for --pagerank: segment "
                         "(segmented scan, the default), scatter "
                         "(gather-scatter oracle), pallas (blocked "
                         "Block-ELL semiring SpMV)")
    ap.add_argument("--fused", action="store_true",
                    help="--pagerank: run the whole iteration as one "
                         "on-device dispatch (run_bsp_fused) instead of "
                         "one dispatch + host sync per superstep")
    ap.add_argument("--tol", type=float, default=None,
                    help="--pagerank: stop once the on-device residual "
                         "max|pr_{t+1}-pr_t| <= TOL (implies --fused)")
    ap.add_argument("--message-dtype", default="float32",
                    choices=MESSAGE_DTYPES,
                    help="--pagerank: edge-message precision; bfloat16 "
                         "is the low-precision message path (messages "
                         "cast down, accumulation stays float32 on "
                         "scatter/segment)")
    ap.add_argument("--out", default=None, help=".npz output path")
    args = ap.parse_args(argv)
    if args.pagerank:
        from .cache import enable_compile_cache
        enable_compile_cache()

    if args.compact:
        return _run_compact(args)
    if not args.graph:
        ap.error("--graph is required (except with --compact)")
    if args.stream:
        return _run_stream(ap, args)

    g = load_graph(args.graph)
    cl = scaled_paper_cluster(args.super, args.normal, g.num_edges,
                              slack=args.slack)
    print(f"graph: V={g.num_vertices} E={g.num_edges} "
          f"maxdeg={int(g.degree().max())}; cluster p={cl.p}", flush=True)
    t0 = time.perf_counter()
    if args.method == "windgp":
        res = windgp(g, cl, alpha=args.alpha, beta=args.beta,
                     t0=args.t0, theta=args.theta)
        assign, stats = res.assign, res.stats
    else:
        part = registry.get(args.method)
        kw = {}
        if args.block_size is not None:
            if not part.supports("blocked"):
                ap.error(f"--block-size: method {part.name!r} is not a "
                         f"block-stream method (capabilities: "
                         f"{sorted(part.capabilities)})")
            kw["block_size"] = args.block_size
        assign = part(g, cl, **kw)
        stats = evaluate(g, assign, cl)
    dt = time.perf_counter() - t0
    report = {
        "method": args.method, "seconds": round(dt, 2),
        "TC": stats.tc, "RF": round(stats.rf, 4),
        "feasible": stats.feasible,
        "edges_per_machine": stats.edges_per_part.astype(int).tolist(),
        "t_total_per_machine": np.round(stats.t_total, 1).tolist(),
    }
    print(json.dumps(report, indent=2))
    if args.out:
        np.savez(args.out, assign=assign,
                 machines=np.array([m.as_tuple() for m in cl.machines]))
        print(f"wrote {args.out}")
    if args.pagerank:
        from ..bsp import PartitionRuntime
        rt = PartitionRuntime.create(g, assign=assign, cluster=cl)
        _run_pagerank(rt, args)
    return 0


def _run_pagerank(rt, args) -> None:
    """Distributed PageRank on the fresh partition via --backend."""
    from ..bsp import pagerank
    t0 = time.perf_counter()
    pr, actives = pagerank(rt, num_iters=args.pagerank_iters,
                           backend=args.backend, fused=args.fused,
                           tol=args.tol, message_dtype=args.message_dtype)
    dt = time.perf_counter() - t0
    top = np.argsort(pr)[::-1][:5]
    steps = len(actives)
    mode = "fused" if (args.fused or args.tol is not None) else "stepwise"
    print(f"pagerank[{args.backend}/{mode}/{args.message_dtype}]: "
          f"{steps}/{args.pagerank_iters} supersteps on "
          f"p={rt.p} machines (R={rt.num_replicas} replicas) in {dt:.2f}s; "
          f"mass={pr.sum():.6f}")
    print("top-5:", {int(v): round(float(pr[v]), 6) for v in top})


def _run_compact(args) -> int:
    """Standalone tombstone-folding pass over a finalized assignment."""
    from ..bsp import StreamAssignment
    sa = StreamAssignment.open(args.compact)
    before = int(sa.tomb_rows.sum())
    t0 = time.perf_counter()
    meta = sa.compact(args.compact_tomb_frac)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "compacted": args.compact, "seconds": round(dt, 3),
        "tomb_rows_folded": before - int(sa.tomb_rows.sum()),
        "tomb_rows_left": int(sa.tomb_rows.sum()),
        "shard_rows": meta["shard_rows"],
        "num_edges": meta["num_edges"],
    }, indent=2))
    return 0


def _run_stream(ap, args) -> int:
    """Out-of-core path: graph-free streaming over an edge-list file."""
    import pathlib

    from ..data import count_edge_list
    part = registry.get(args.method)
    if not part.supports("streamable"):
        ap.error(f"--stream: method {part.name!r} is not streamable "
                 f"(capabilities: {sorted(part.capabilities)}); "
                 f"streamable: {registry.names(require={'streamable'})}")
    if args.graph.split(":")[0] in ("rmat", "graph500", "mesh"):
        ap.error("--stream partitions edge-list files; generator specs "
                 "would materialize the graph first")
    if args.pagerank and not args.out_dir:
        ap.error("--stream --pagerank needs --out-dir: the BSP runtime "
                 "packs from the persisted StreamAssignment shards")

    if args.dedup == "two_pass":
        if args.workers > 1:
            # shard the spill/dedup passes across the same worker count
            # the scoring stage will use
            from ..core.parallel import ShardedTwoPassDedup
            source = ShardedTwoPassDedup(args.graph, workers=args.workers)
            source.prepare()
        else:
            from ..data import two_pass_dedup
            source = two_pass_dedup(args.graph)
        num_v, num_e = source.num_vertices, source.num_edges
    else:
        # count at the same reader granularity the stream will use:
        # per-block dedup makes the edge count a function of the window
        from ..data.io import DEFAULT_BLOCK_LINES
        source = args.graph
        num_v, num_e = count_edge_list(
            args.graph, args.block_size or DEFAULT_BLOCK_LINES)
    cl = scaled_paper_cluster(args.super, args.normal, num_e,
                              slack=args.slack)
    print(f"stream: V={num_v} E={num_e} dedup={args.dedup} p={cl.p}",
          flush=True)

    sa = None
    kw = {"dedup": args.dedup}
    if args.block_size is not None:
        kw["block_size"] = args.block_size
    if args.workers > 1:
        kw["workers"] = args.workers
        kw["sync_blocks"] = args.sync_blocks
    if args.out_dir:
        from ..bsp import StreamAssignment
        sa = StreamAssignment(pathlib.Path(args.out_dir), cl.p, num_v)
        kw["sink"] = sa.sink
    t0 = time.perf_counter()
    try:
        state = part.stream(source, num_v, num_e, cl, **kw)
    except BaseException:
        if sa is not None:
            sa.close()      # abort: drop shard handles, publish nothing
        raise
    finally:
        if hasattr(source, "close"):
            source.close()
    dt = time.perf_counter() - t0

    stats = evaluate_membership(state.cnt > 0, state.edges_per, cl)
    report = {
        "method": args.method, "mode": f"stream/{args.dedup}",
        "seconds": round(dt, 2),
        "TC": stats.tc, "RF": round(stats.rf, 4),
        "feasible": stats.feasible,
        "edges_per_machine": stats.edges_per_part.astype(int).tolist(),
        "t_total_per_machine": np.round(stats.t_total, 1).tolist(),
    }
    if state.spill_stats is not None:
        report["spill"] = {
            "buckets": state.spill_stats.num_buckets,
            "duplicate_rows": state.spill_stats.duplicate_rows,
            "peak_resident_rows": state.spill_stats.peak_resident_rows,
        }
    print(json.dumps(report, indent=2))
    if sa is not None:
        meta = sa.finalize(state, {"method": args.method,
                                   "dedup": args.dedup})
        print(f"wrote StreamAssignment to {args.out_dir} "
              f"(E={meta['num_edges']}, rf={meta['replication_factor']})")
        if args.pagerank:
            from ..bsp import PartitionRuntime
            rt = PartitionRuntime.create(sa)
            _run_pagerank(rt, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
