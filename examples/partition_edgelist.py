"""Partition a SNAP edge list into per-machine shards — then run on them.

A dgl/graphstorm-style partitioning CLI over the unified registry and the
chunked edge-list reader:

    PYTHONPATH=src python examples/partition_edgelist.py edges.txt \
        --part-method hdrf --num-parts 8 --block-size 4096 --out-dir parts/

Block-stream methods (``blocked`` capability: greedy/hdrf/ebv) run fully
chunked — a counting pass for |V|/|E| (the stream partitioner needs both
for its memory caps), then one streaming pass that writes each machine's
edge file as placements finalize; the graph is never materialized as a
single array.  Every other registered method (``--part-method ne``,
``metis``, ``windgp``, ...) falls back to an in-memory graph build.

Output layout: ``<out-dir>/part<i>.edges`` (one ``u v`` line per edge),
``<out-dir>/assignment/`` (the binary ``StreamAssignment`` the BSP runtime
consumes — streaming methods only), plus ``<out-dir>/meta.json`` with
counts and the replication factor.  ``meta.json`` is written last, via
tmp + atomic rename, and only after every shard verified its flushed byte
length — a crash can never leave a directory that parses as complete.

Out-of-core workflow
--------------------
The full partition→compute pipeline on a list that never materializes:

    PYTHONPATH=src python examples/partition_edgelist.py edges.txt.gz \
        --part-method hdrf --num-parts 8 --two-pass --pagerank \
        --out-dir parts/

``--two-pass`` replaces the single-pass per-block dedup with the exact
spill-to-disk dedup (``repro.data.TwoPassDedup``): pass one hashes
canonicalized edges into bounded disk buckets, pass two streams each
bucket back globally deduplicated in first-occurrence order, so the
partitioner sees every edge exactly once while peak edge residency stays
bounded by the spill-bucket accounting (reported in meta.json under
``spill``).  ``--pagerank`` then packs the streamed shards into the BSP
runtime (``PartitionRuntime.from_stream`` — reads one machine's shard at
a time, never the raw list) and runs distributed PageRank supersteps on
the partition it just built — the paper's end-to-end claim, out of core.

Multi-worker workflow
---------------------
``--workers W`` runs the same out-of-core pipeline through the
W-process orchestrator (``repro.core.parallel``) when the wall clock,
not memory, is the constraint::

    PYTHONPATH=src python examples/partition_edgelist.py edges.txt \
        --part-method hdrf --num-parts 8 --two-pass --workers 4 \
        --pagerank --out-dir parts/

Count and spill/dedup shard first: the raw list splits into W byte
ranges (line-aligned), each worker hash-shuffles its range into the
shared spill buckets, and pass-2 dedup runs per worker over disjoint
bucket sets — the merged stream is *identical* block for block to the
sequential dedup.  The parallel stream then scores engine blocks on W
workers against membership snapshots synced every ``sync_blocks``
blocks (results depend only on that period, never on W; at
``sync_blocks=1`` they are bit-identical to ``--workers 1``).
Placements replay through the sink on the coordinator in a
deterministic order, so this script still writes ONE
``StreamAssignment`` (and one set of ``part<i>.edges``) regardless of
W — ``--pagerank`` packs and runs on it exactly as in the sequential
workflow.  ``benchmarks/parallel_scale.py`` is the measured version
(dedup+scoring wall at W∈{1,2,4}, TC/RF gap vs sequential) and runs in
CI as the tier-2 ``parallel`` job.

Choosing an edge-kernel backend
-------------------------------
``--backend`` selects how each PageRank superstep combines messages over
the machine's local edges (``repro.bsp.backends``); results agree to
1e-5, only speed and hardware shape differ:

* ``segment`` (default) — each machine's incidence sorted by output
  vertex once, reduced by a segmented scan that restarts at every
  vertex's run.  No scatter at all.
* ``scatter`` — the gather-scatter oracle (``at[].add`` per
  direction), the reference every other backend is tested against;
  pick it when validating a new partition pipeline.
* ``pallas`` — the blocked Block-ELL semiring SpMV
  (``repro.kernels.bsr_spmv``) over the degree-sorted per-machine
  adjacency (``PartitionRuntime.local_bsr``).  MXU-shaped 128x128
  blocks on TPU; on CPU it runs the Pallas interpreter, so treat it as
  a validation/portability path, not a CPU speedup.

The same flag exists on ``repro.launch.partition`` (with ``--stream``)
and the backend registry is shared by all four BSP apps — SSSP/BFS/
components run the same kernels under (min, +)/(or, and) semirings.

Three more knobs tune how the supersteps *run*, independent of which
backend combines the edges:

* ``--fused`` — run the whole PageRank iteration as ONE on-device
  dispatch (``repro.bsp.engine.run_bsp_fused``: ``lax.scan`` over
  chunks of supersteps, host sync only at the end) instead of one
  jitted dispatch + device→host sync per superstep.  On small/medium
  shards the per-step runner is dispatch-bound, so this is the main
  superstep-latency lever — same results, bitwise at the default
  message dtype.
* ``--tol T`` — convergence gate for the fused runner (implies
  ``--fused``): stop as soon as the on-device residual
  ``max|pr_{t+1} − pr_t| <= T`` instead of always running
  ``--pagerank-iters`` supersteps.  The monotone apps (SSSP/BFS/CC)
  need no tolerance — their fused runs already early-exit when the
  active count hits zero.
* ``--message-dtype bfloat16`` — the low-precision message path:
  per-edge ⊗ operands are cast to bfloat16 while scatter/segment
  ⊕-accumulation stays float32.  Halves message bandwidth at ~1e-3
  relative PageRank error; ``benchmarks/bsp_apps.py --bf16-study``
  prints the error-vs-iteration table to judge the trade.  The default
  ``float32`` is bit-identical to not having the knob.

Dynamic workflow
----------------
The partition this script writes is a *seed*, not a terminal product:
when the graph keeps evolving, wrap it in the dynamic layer instead of
re-running the pipeline per snapshot::

    from repro.core import DynamicPartitioner
    from repro.bsp import PartitionRuntime, StreamAssignment, pagerank

    dp = DynamicPartitioner(g, cl, assign)     # live state over the seed
    sa = StreamAssignment.open(out_dir / "assignment")
    rt = PartitionRuntime.create(sa)
    snap = dp.snapshot()
    dp.insert(new_edges)                       # wave-scored vs live (p,V)
    dp.delete(stale_edges)                     # exact Eq.3/4 rollback
    # drift monitor fires bounded SLS repair automatically when balance
    # skew or RF crosses its leash; per-epoch, hand the diff downstream:
    delta = dp.delta_since(snap)
    sa.apply_delta(delta, dp.membership())     # shard append + tombstones
    rt = rt.apply_delta(sa, delta)             # repack touched machines
    pr, _ = pagerank(rt, init=pr_prev)         # warm-start from last run

Inserts are scored by the same block-stream engine this script uses for
the cold pass, so a quiet timeline converges to the static partition.
``benchmarks/dynamic_replay.py`` is the measured version of this loop
(assignment-latency percentiles, amortized repair cost, TC drift vs
scratch) and runs in CI as the tier-2 ``dynamic`` job.

Sampling workflow
-----------------
The shards this script writes also feed GNN minibatch training: wrap
the partition in the sampling service (``repro.sampling``) and draw
fixed-fanout k-hop neighborhoods per machine::

    from repro.sampling import (FeatureStore, HaloCache,
                                PrefetchPipeline, SamplingService)
    import jax

    svc = SamplingService.create(out_dir / "assignment",
                                 fanouts=(10, 5))
    key = jax.random.PRNGKey(0)
    seeds = svc.local_seeds(home=0, n=1024, key=key)   # machine 0's shard
    mb = svc.sample(seeds, jax.random.fold_in(key, 1), home=0)
    mb.halo_fracs()    # per-hop fraction of frontier owned elsewhere

    # feature path: owner-sharded store + per-trainer halo cache
    store = FeatureStore.build(svc, features)      # features: (V, F)
    cache = HaloCache.for_home(store, home=0, capacity=4096)
    rows, st = store.gather(mb.all_ids(), home=0, cache=cache)
    st.hit_rate    # deduplicated remote rows served without a fetch

    # steady-state training loop: prefetch overlaps batch i+1's fused
    # k-hop sampling with batch i's feature resolve; any depth (incl.
    # 0 = fully synchronous) yields the bitwise-same stream
    with PrefetchPipeline(svc, home=0, batch_size=1024, num_batches=100,
                          key=key, depth=2, store=store,
                          cache=cache) as pipe:
        for mb, feats in pipe:
            ...                                    # train on the batch

``SamplingService.create`` accepts every ``PartitionRuntime.create``
source — the assignment directory above, or ``(graph, method=,
cluster=)`` to partition in-process, or ``(graph, assign=, p=)`` for a
precomputed assignment.  Each machine holds a degree-sorted CSC of its
*owned* vertices; the whole k-hop expansion runs as one fused jitted
dispatch (a hop-at-a-time reference path survives behind
``sample(..., fused=False)``, pinned bitwise).  Per hop, sampled
vertices owned elsewhere are counted as one deduplicated batched halo
fetch — the traffic a better partition shrinks, which is how partition
quality becomes observable on the training workload; the feature
store's ``gather`` pays exactly that traffic, minus what the
``HaloCache`` (static degree-ranked hub tier + LRU tail) absorbs.  The
sampler is key-deterministic (same ``(partition, seeds, key)`` →
bitwise-same minibatch, pinned against a NumPy oracle) and
``local_seeds(..., train_mask=m)`` restricts seeds to labeled vertices.
For training-aware partitions, pass ``train_mask=`` /
``train_balance=`` to the windgp partitioner — Eq. 3 then weighs
hosted train vertices extra, balancing the labeled set across machines.
``benchmarks/sampling_service.py`` is the measured version (samples/sec,
fused-vs-loop speedup, halo fraction and cache hit rate windgp vs hdrf
vs hash, prefetch depth sweep, train-skew reduction) and runs in CI as
the tier-2 ``sampling`` job.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

from repro.bsp import (PartitionRuntime, StreamAssignment,
                       write_json_atomic)
from repro.core import evaluate, evaluate_membership, scaled_paper_cluster
from repro.core import partitioners as registry
from repro.data import TwoPassDedup, count_edge_list, read_edge_list
from repro.launch.partition import (EDGE_BACKENDS, MESSAGE_DTYPES,
                                    _run_pagerank)


def _partition_streaming(args, part, out: pathlib.Path):
    """Graph-free path: count → (optional two-pass dedup) → stream.

    Returns (num_v, num_e, stats, StreamAssignment, spill_stats).
    """
    source: object
    if args.two_pass:
        print(f"spilling+deduplicating {args.edge_list} "
              f"(workers={args.workers}) ...", flush=True)
        if args.workers > 1:
            from repro.core.parallel import ShardedTwoPassDedup
            source = ShardedTwoPassDedup(
                args.edge_list, block_size=args.block_size,
                bucket_rows=args.bucket_rows, workers=args.workers)
        else:
            source = TwoPassDedup(args.edge_list,
                                  block_size=args.block_size,
                                  bucket_rows=args.bucket_rows)
        num_v, num_e = source.prepare()
    else:
        print(f"counting {args.edge_list} ...", flush=True)
        # same block size as the partitioning pass, so both passes see the
        # identical canonicalized stream (dedup is per-block)
        num_v, num_e = count_edge_list(args.edge_list, args.block_size)
        source = args.edge_list
    n_super = args.n_super or max(1, args.num_parts // 3)
    cl = scaled_paper_cluster(n_super, args.num_parts - n_super, num_e,
                              slack=args.slack)
    print(f"V={num_v} E={num_e} p={cl.p} method={part.name} "
          f"(kind={part.kind}, caps={sorted(part.capabilities)})")

    sa = StreamAssignment(out / "assignment", cl.p, num_v)
    files = [open(out / f"part{i}.edges", "w") for i in range(cl.p)]
    try:
        def sink(edges, ms):
            sa.sink(edges, ms)
            for i in np.unique(ms):
                np.savetxt(files[int(i)], edges[ms == i], fmt="%d")

        kw = {}
        if args.workers > 1:
            kw = {"workers": args.workers, "sync_blocks": args.sync_blocks}
        state = part.stream(
            source, num_v, num_e, cl,
            dedup="two_pass" if args.two_pass else "block",
            block_size=args.block_size, sink=sink, **kw)
    except BaseException:
        sa.close()          # abort: drop shard handles, publish nothing
        raise
    finally:
        for f in files:
            f.close()
        if args.two_pass:
            source.close()
    stats = evaluate_membership(state.cnt > 0, state.edges_per, cl)
    sa.finalize(state, {"method": part.name,
                        "dedup": "two_pass" if args.two_pass else "block"})
    return num_v, num_e, stats, sa, state.spill_stats


def _partition_in_memory(args, part, out: pathlib.Path):
    """Fallback for non-streamable methods: materialize the graph."""
    g = read_edge_list(args.edge_list)
    num_v, num_e = g.num_vertices, g.num_edges
    n_super = args.n_super or max(1, args.num_parts // 3)
    cl = scaled_paper_cluster(n_super, args.num_parts - n_super, num_e,
                              slack=args.slack)
    print(f"V={num_v} E={num_e} p={cl.p} method={part.name} "
          f"(kind={part.kind}, caps={sorted(part.capabilities)})")
    assign = part(g, cl)
    stats = evaluate(g, assign, cl)
    sa = StreamAssignment(out / "assignment", cl.p, num_v)
    files = [open(out / f"part{i}.edges", "w") for i in range(cl.p)]
    try:
        sa.sink(g.edges.astype(np.int64), assign.astype(np.int64))
        for i in range(cl.p):
            np.savetxt(files[i], g.edges[assign == i], fmt="%d")
    except BaseException:
        sa.close()
        raise
    finally:
        for f in files:
            f.close()
    from repro.core.machines import vertex_partition_sets
    sa.finalize(vertex_partition_sets(g, assign, cl.p),
                {"method": part.name, "dedup": "in_memory"})
    return num_v, num_e, stats, sa, None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("edge_list", help="whitespace u-v edge list (.gz ok)")
    ap.add_argument("--part-method", default="hdrf",
                    choices=registry.names(exclude={"oracle"}))
    ap.add_argument("--num-parts", type=int, default=8)
    ap.add_argument("--super", type=int, default=0, dest="n_super",
                    help="how many of the parts are 'super' machines "
                         "(0 = one in three, the paper's default mix)")
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--slack", type=float, default=1.8)
    ap.add_argument("--two-pass", action="store_true",
                    help="exact out-of-core dedup via spill buckets "
                         "(streaming methods; default is per-block dedup)")
    ap.add_argument("--bucket-rows", type=int, default=1 << 16,
                    help="--two-pass spill-bucket row target (bounds peak "
                         "edge residency)")
    ap.add_argument("--workers", type=int, default=1,
                    help="W-process pipeline: sharded dedup + parallel "
                         "wave scoring (see 'Multi-worker workflow'); "
                         "1 = sequential bit for bit")
    ap.add_argument("--sync-blocks", type=int, default=None,
                    help="--workers > 1: engine blocks between membership "
                         "sync barriers (1 = bit-identical to sequential)")
    ap.add_argument("--pagerank", action="store_true",
                    help="after partitioning, pack the BSP runtime from "
                         "the shards and run distributed PageRank")
    ap.add_argument("--pagerank-iters", type=int, default=30)
    ap.add_argument("--backend", default="segment",
                    choices=EDGE_BACKENDS,
                    help="edge-kernel backend for --pagerank (see "
                         "module docstring)")
    ap.add_argument("--fused", action="store_true",
                    help="--pagerank: whole iteration as one on-device "
                         "dispatch (see module docstring)")
    ap.add_argument("--tol", type=float, default=None,
                    help="--pagerank: on-device convergence tolerance "
                         "(implies --fused)")
    ap.add_argument("--message-dtype", default="float32",
                    choices=MESSAGE_DTYPES,
                    help="--pagerank: edge-message precision (bfloat16 "
                         "= low-precision message path)")
    ap.add_argument("--out-dir", default="parts")
    args = ap.parse_args(argv)

    part = registry.get(args.part_method)
    if args.two_pass and not part.supports("streamable"):
        ap.error(f"--two-pass: {part.name!r} is not streamable "
                 f"(in-memory methods dedup exactly already)")
    out = pathlib.Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)

    t0 = time.perf_counter()
    if part.supports("streamable"):
        num_v, num_e, stats, sa, spill = _partition_streaming(args, part, out)
    else:
        num_v, num_e, stats, sa, spill = _partition_in_memory(args, part, out)
    dt = time.perf_counter() - t0

    counts = sa.edges_per
    meta = {
        "method": part.name, "num_parts": sa.p, "num_vertices": num_v,
        "num_edges": int(counts.sum()), "block_size": args.block_size,
        "dedup": sa.meta["dedup"],
        "seconds": round(dt, 3),
        "TC": stats.tc,
        "replication_factor": round(float(stats.rf), 4),
        "edges_per_part": counts.tolist(),
        "files": [f"part{i}.edges" for i in range(sa.p)],
        "assignment_dir": "assignment",
    }
    if spill is not None:
        meta["spill"] = {
            "num_buckets": spill.num_buckets,
            "bucket_rows": spill.bucket_rows,
            "spilled_rows": spill.spilled_rows,
            "duplicate_rows": spill.duplicate_rows,
            "max_bucket_rows": spill.max_bucket_rows,
            "peak_resident_rows": spill.peak_resident_rows,
        }
    # every edge exactly once: the written total must equal the
    # *independently counted* stream size (exact dedup count in two-pass
    # mode, the same-window per-block count otherwise)
    assert int(counts.sum()) == num_e, \
        f"wrote {int(counts.sum())} edges, counted {num_e}"
    # shards were verified in sa.finalize(); only now publish the manifest,
    # atomically — readers either see no meta.json or a complete one
    write_json_atomic(out / "meta.json", meta)
    print(json.dumps(meta, indent=2))

    if args.pagerank:
        # same report as the launch CLI (shared helper): pack the runtime
        # from the on-disk shards, run supersteps through --backend
        _run_pagerank(PartitionRuntime.create(sa), args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
