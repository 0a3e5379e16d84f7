"""Distributed graph algorithms on the BSP engine.

The paper evaluates dense (PageRank, TriangleCount) and sparse (SSSP, BFS)
algorithms over its edge partitions; these are the same four, written as
per-machine superstep bodies + the replica exchange.

Every superstep's edge work is one semiring SpMV against the machine's
local adjacency, expressed through a pluggable **edge-kernel backend**
(``bsp/backends.py``): PageRank combines under (+, ×) with edge weights,
SSSP under (min, +), BFS expands its frontier under (or, and), and
connected components propagates labels under (min, +) with zero weights.
``"segment"`` (the default) reduces a destination-sorted incidence by a
segmented scan, with no scatter; ``"scatter"`` is the historical
gather-scatter loop, the oracle the others are tested against, and the
frontier path (a ``frontier_cap`` with no backend named runs on it);
``"pallas"`` runs the blocked Block-ELL kernel (``kernels/bsr_spmv``) over
``rt.local_bsr()``.  Results agree across backends — bitwise for the
min/max semirings, to ~1e-7 for (+, ×) — and under both vmap and a real
``shard_map`` mesh (tests pin both).

The replica exchange is fused into the backend combine's epilogue
(``EdgeBackend.prepare_exchanged``): each superstep body makes a single
``combine`` call that already returns post-exchange values instead of
materializing a separate pre-exchange ``(Vmax,)`` partial.  For the
(min, +) apps this rewrites ``exchange(min(dist, cand))`` as
``min(dist, exchange(cand))`` — bitwise equal, because replicas of a
vertex always agree on ``dist`` (it is itself a post-exchange value)
and min is exact.

The monotone apps (SSSP/CC) carry a **changed-vertex mask** in their
state: only vertices whose value improved last superstep send messages;
everyone else feeds the semiring's no-message value (+inf under
(min, +)), whose ⊕ contribution is the identity.  This is exact — a
vertex's value was already folded into its neighbors the superstep
after it last changed, and (min, +) states only improve — and it is the
mask the ``scatter`` backend's ``frontier_cap`` compaction keys on to
make supersteps O(frontier) instead of O(E_local).  BFS's frontier
(``dist == step``) already is that mask.

Every app wrapper takes its knobs as keywords, those of
:class:`RunOptions` and the backend's own: ``pagerank(rt, 20,
backend="segment", fused=True, message_dtype="bfloat16")``.  It runs on
either engine runner: the per-step oracle (``run_bsp``, default) or the
fused on-device loop (``fused=True`` / ``tol=`` → ``run_bsp_fused``).
Each call is one ``bsp.call`` request of ``repro.obs``: ``bsp.prepare``, the
supersteps, ``bsp.gather``.  With ``mesh=`` the prepare uploads each
machine's rows straight to its own device, so the supersteps move no
array between devices but the exchange's all-reduce.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .backends import get_backend
from .engine import placed_on, run_bsp, run_bsp_fused, to_device, to_host
from .partition_runtime import PartitionRuntime

#: apps whose state is monotone under the semiring: they already early-exit
#: on an empty changed-set, so PageRank's ``tol`` residual gate does not
#: apply to them (RunOptions.validate rejects the combination).
MONOTONE_APPS = ("bfs", "cc", "sssp")


@dataclasses.dataclass(frozen=True)
class RunOptions:
    """The knobs an app call takes as keywords, checked once per call.

    * ``backend`` — edge-kernel backend (``bsp/backends.py``); None picks
      :func:`~.backends.default_backend`.
    * ``fused`` — run the whole iteration as one on-device dispatch.
    * ``tol`` — PageRank residual early-exit (implies ``fused``); the
      monotone apps (:data:`MONOTONE_APPS`) reject it.
    * ``chunk`` — fused-runner scan chunk (steps per convergence check).
    * ``message_dtype`` — message precision (see ``MESSAGE_DTYPES``).
    * ``frontier_cap`` — scatter-only frontier compaction width.

    :meth:`validate` checks the runner's rules; the backend's are
    :func:`~.backends.get_backend`'s alone.
    """

    backend: str | None = None
    fused: bool = False
    tol: float | None = None
    chunk: int = 8
    message_dtype: str = "float32"
    frontier_cap: int | None = None

    def validate(self, app: str | None = None) -> "RunOptions":
        """Raise ``ValueError`` on bad runner knobs; returns self."""
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.tol is not None and app in MONOTONE_APPS:
            raise ValueError(
                f"tol= is the PageRank residual gate; {app!r} is monotone "
                f"and already exits on an empty changed-set — valid "
                f"choices: tol=None here, or tol with app='pagerank' "
                f"(use fused=True for the one-dispatch runner)")
        return self


def _static_tree(rt: PartitionRuntime):
    """The per-vertex arrays the superstep bodies and the exchange read;
    each backend uploads the edge arrays it reads in its ``extras``."""
    return {
        "vertex_valid": to_device(rt.vertex_valid),
        "weighted_degree": to_device(rt.weighted_degree),
        "rep_slot": to_device(rt.rep_slot),
        "slot_vertex": to_device(rt.slot_vertex),
    }


@dataclasses.dataclass(frozen=True)
class AppSpec:
    """One app instance, ready for ``run_bsp`` (or a dryrun compile).

    ``superstep(state, static) -> (state, active)`` over rank-reduced
    per-machine arrays; ``static`` already carries the backend's prepared
    arrays; ``check_rep`` is the backend's shard_map replication-check
    flag the engine must honor.
    """

    name: str
    superstep: Callable
    state: dict
    static: dict
    check_rep: bool
    finalize: Callable        # (rt, out_state) -> global result array


def _resolve(rt, backend, semiring: str, weights: str, exchange_mode: str,
             **opts):
    """Backend + static tree + exchange-fused combine for one app.

    The returned ``combine(sa, x)`` yields *post-exchange* neighborhood
    values (``EdgeBackend.prepare_exchanged``) — the superstep bodies
    below never call :func:`~.engine.exchange` themselves.
    """
    r_pad = max(1, rt.num_replicas)
    eb = get_backend(backend, **opts)
    extras, combine = eb.prepare_exchanged(rt, semiring, weights,
                                           exchange_mode, r_pad)
    return eb, {**_static_tree(rt), **extras}, combine


def _run(spec: "AppSpec", num_steps: int, *, mesh=None, fused=False,
         tol=None, chunk=8):
    """Dispatch an :class:`AppSpec` to the stepwise or fused runner."""
    if fused:
        return run_bsp_fused(spec.superstep, spec.state, spec.static,
                             num_steps, mesh=mesh, check_rep=spec.check_rep,
                             chunk=chunk, tol=tol)
    return run_bsp(spec.superstep, spec.state, spec.static, num_steps,
                   mesh=mesh, check_rep=spec.check_rep)


def _call(app: str, rt, num_steps: int, mesh, options: dict,
          build: Callable[..., "AppSpec"], *args, **kw):
    """One app call as a ``bsp.call`` request: prepare (``build(*args,
    **kw)`` with the call's backend: its layout, the static tree and the
    initial state, uploaded to the mesh's devices if one is given), run,
    gather.  ``options`` are the call's knobs: the :class:`RunOptions`
    fields, and what is left goes to the backend (the pallas
    ``block_size``/``interpret``).  Returns ``(global result, actives)``.
    """
    names = [f.name for f in dataclasses.fields(RunOptions)]
    opts = RunOptions(**{k: options.pop(k) for k in names if k in options})
    opts.validate(app)
    eb = get_backend(opts.backend, message_dtype=opts.message_dtype,
                     frontier_cap=opts.frontier_cap, **options)
    fused = opts.fused or opts.tol is not None
    with obs.request("bsp.call", app=app,
                     runner="fused" if fused else "stepwise",
                     backend=eb.name, supersteps=num_steps,
                     devices=1 if mesh is None else mesh.size):
        with obs.span("bsp.prepare"), placed_on(mesh):
            spec = build(*args, backend=eb, **kw)
        out, actives = _run(spec, num_steps, mesh=mesh, fused=fused,
                            tol=opts.tol, chunk=opts.chunk)
        with obs.span("bsp.gather"):
            return spec.finalize(rt, out), actives


# ---------------------------------------------------------------------------
# PageRank (dense: every vertex/edge active every superstep; (+, ×))
# ---------------------------------------------------------------------------

def build_pagerank(rt: PartitionRuntime, damping: float = 0.85, *,
                   backend=None, init: np.ndarray | None = None,
                   **backend_opts) -> AppSpec:
    """``init`` warm-starts from a previous run's (V,) global PageRank —
    the dynamic-epoch hand-off after ``PartitionRuntime.apply_delta``.
    Power iteration converges to the stationary distribution from *any*
    non-degenerate start, so a stale vector is a valid (and, for small
    deltas, nearby) initial point; vertices new to this runtime fall back
    to the uniform mass.  CC/SSSP get no such hook: their states are
    monotone under the semiring, so stale labels are invalid the moment a
    deletion can lengthen a path."""
    n = rt.num_vertices
    eb, static, combine = _resolve(rt, backend, "plus_times", "weight",
                                   "sum", **backend_opts)

    def superstep(state, sa):
        pr = state["pr"]
        # weighted PageRank: messages normalize by the *weighted* degree
        # and edges scale by their weight (all-ones weights reduce to the
        # classic uniform split)
        msg = jnp.where(sa["vertex_valid"],
                        pr / sa["weighted_degree"], 0.0)
        total = combine(sa, msg)              # post-exchange ("sum")
        new_pr = jnp.where(sa["vertex_valid"],
                           (1.0 - damping) / n + damping * total, 0.0)
        active = sa["vertex_valid"].sum()
        return {"pr": new_pr}, active

    if init is None:
        pr0 = jnp.where(to_device(rt.vertex_valid),
                        1.0 / n, 0.0).astype(jnp.float32)
    else:
        pr0 = to_device(
            rt.scatter_global(np.asarray(init, dtype=np.float32),
                              fill=1.0 / n))
        pr0 = jnp.where(to_device(rt.vertex_valid), pr0, 0.0)
    state = {"pr": pr0}
    # isolated vertices (no incident edge, hence in no partition) hold the
    # teleport mass only:
    fin = lambda rt, out: rt.gather_global(to_host(out["pr"]),
                                           fill=(1.0 - damping) / n)
    return AppSpec("pagerank", superstep, state, static, eb.check_rep, fin)


def pagerank(rt: PartitionRuntime, num_iters: int = 20,
             damping: float = 0.85, *, mesh=None,
             init: np.ndarray | None = None, **options):
    """Returns (V,) global PageRank after ``num_iters`` supersteps.

    ``init`` warm-starts from a previous (V,) result (see
    :func:`build_pagerank`).  ``options`` are the knobs of
    :class:`RunOptions` and the backend's own: ``fused=True`` runs the
    whole iteration as one on-device dispatch (``run_bsp_fused``);
    ``tol`` additionally stops early once ``‖pr_{t+1} − pr_t‖∞ ≤ tol``
    (and implies fused)."""
    return _call("pagerank", rt, num_iters, mesh, options, build_pagerank,
                 rt, damping, init=init)


# ---------------------------------------------------------------------------
# SSSP (sparse: active set shrinks per superstep; (min, +))
# ---------------------------------------------------------------------------

def build_relax(rt: PartitionRuntime, source: int, weighted: bool, *,
                backend=None, name: str = "sssp",
                **backend_opts) -> AppSpec:
    inf = jnp.float32(jnp.inf)
    eb, static, combine = _resolve(rt, backend, "min_plus",
                                   "weight" if weighted else "unit",
                                   "min", **backend_opts)

    def superstep(state, sa):
        dist, changed = state["dist"], state["changed"]
        # only vertices that improved last superstep send; +inf is the
        # (min, +) no-message value, so the masked entries fold to the
        # ⊕ identity — exact, because an unchanged vertex's distance was
        # already folded into its neighbors when it last changed
        msg = jnp.where(changed, dist, inf)
        cand = combine(sa, msg)               # post-exchange ("min")
        new_dist = jnp.minimum(dist, cand)
        new_dist = jnp.where(sa["vertex_valid"], new_dist, inf)
        new_changed = new_dist < dist         # vertices updated this step
        return {"dist": new_dist, "changed": new_changed}, new_changed.sum()

    dist0 = np.full((rt.p, rt.vmax), np.inf, dtype=np.float32)
    holders = np.nonzero(rt.local_vertex_gid == source)
    dist0[holders] = 0.0
    state = {"dist": to_device(dist0),
             "changed": to_device(np.isfinite(dist0))}
    fin = lambda rt, out: rt.gather_global(to_host(out["dist"]),
                                           fill=np.inf)
    return AppSpec(name, superstep, state, static, eb.check_rep, fin)


def sssp(rt: PartitionRuntime, source: int = 0, num_iters: int = 30,
         *, mesh=None, **options):
    return _call("sssp", rt, num_iters, mesh, options, build_relax,
                 rt, source, weighted=True)


# ---------------------------------------------------------------------------
# BFS (sparse: frontier grows/shrinks; (or, and))
# ---------------------------------------------------------------------------

def build_bfs(rt: PartitionRuntime, source: int, *, backend=None,
              **backend_opts) -> AppSpec:
    """Layer-synchronous BFS: the frontier (vertices discovered last
    superstep) expands through one (or, and) product per step.  Distances
    equal the (min, +) relaxation with unit weights — the semiring view
    of the same traversal — which the backend-equivalence tests exploit.
    """
    eb, static, combine = _resolve(rt, backend, "or_and", "unit", "max",
                                   **backend_opts)

    def superstep(state, sa):
        dist, step = state["dist"], state["step"]
        frontier = jnp.where(sa["vertex_valid"] & (dist == step),
                             1.0, 0.0).astype(jnp.float32)
        reached = combine(sa, frontier)       # post-exchange ("max")
        newly = sa["vertex_valid"] & (reached > 0) & jnp.isinf(dist)
        new_dist = jnp.where(newly, step + 1.0, dist)
        return {"dist": new_dist, "step": step + 1.0}, newly.sum()

    dist0 = np.full((rt.p, rt.vmax), np.inf, dtype=np.float32)
    holders = np.nonzero(rt.local_vertex_gid == source)
    dist0[holders] = 0.0
    state = {"dist": to_device(dist0),
             "step": to_device(np.zeros(rt.p, dtype=np.float32))}
    fin = lambda rt, out: rt.gather_global(to_host(out["dist"]),
                                           fill=np.inf)
    return AppSpec("bfs", superstep, state, static, eb.check_rep, fin)


def bfs(rt: PartitionRuntime, source: int = 0, num_iters: int = 30,
        *, mesh=None, **options):
    return _call("bfs", rt, num_iters, mesh, options, build_bfs, rt, source)


# ---------------------------------------------------------------------------
# Weakly-connected components (label propagation: (min, +), zero weights)
# ---------------------------------------------------------------------------

def build_components(rt: PartitionRuntime, *, backend=None,
                     **backend_opts) -> AppSpec:
    inf = jnp.float32(jnp.inf)
    eb, static, combine = _resolve(rt, backend, "min_plus", "zero", "min",
                                   **backend_opts)

    def superstep(state, sa):
        lab, changed = state["lab"], state["changed"]
        msg = jnp.where(changed, lab, inf)    # changed-mask, as in SSSP
        cand = combine(sa, msg)               # post-exchange min label
        new = jnp.minimum(lab, cand)
        new = jnp.where(sa["vertex_valid"], new, inf)
        new_changed = new < lab
        return {"lab": new, "changed": new_changed}, new_changed.sum()

    lab0 = jnp.where(to_device(rt.vertex_valid),
                     to_device(rt.local_vertex_gid.astype(np.float32)),
                     jnp.inf)
    # every valid vertex broadcasts its own label once, on superstep 1
    state = {"lab": lab0, "changed": to_device(rt.vertex_valid)}
    fin = lambda rt, out: rt.gather_global(to_host(out["lab"]),
                                           fill=np.inf)
    return AppSpec("cc", superstep, state, static, eb.check_rep, fin)


def connected_components(rt: PartitionRuntime, num_iters: int = 30,
                         *, mesh=None, **options):
    """Min-label propagation; returns (V,) component id per vertex."""
    return _call("cc", rt, num_iters, mesh, options, build_components, rt)


#: app name -> AppSpec builder (benchmarks/dryrun iterate this)
APP_BUILDERS = {
    "pagerank": build_pagerank,
    "sssp": lambda rt, **kw: build_relax(rt, kw.pop("source", 0), True,
                                         **kw),
    "bfs": lambda rt, **kw: build_bfs(rt, kw.pop("source", 0), **kw),
    "cc": build_components,
}


def build_app(rt: PartitionRuntime, app: str, *, backend=None,
              **kw) -> AppSpec:
    """Build any registered app's :class:`AppSpec` by name."""
    try:
        builder = APP_BUILDERS[app]
    except KeyError:
        raise ValueError(f"unknown BSP app {app!r} "
                         f"(choices: {sorted(APP_BUILDERS)})") from None
    return builder(rt, backend=backend, **kw)


# ---------------------------------------------------------------------------
# Triangle counting (dense): edge-parallel |N(u) ∩ N(v)| with the global
# CSR replicated to every machine (HTC-style shared adjacency); each machine
# scans only its own edges.  Exact — every triangle is seen by exactly 3
# edges, hence the /3 (each edge of the triangle counts it once).
# ---------------------------------------------------------------------------

def triangle_count(rt: PartitionRuntime, g, *, max_degree: int = 64,
                   chunk: int = 4096, mesh=None) -> int:
    """Exact triangle count over the partitioned edge sets.

    Adjacency intersections run against a degree-bounded global neighbor
    table (ELL layout, TPU/MXU-friendly equality contraction); edges whose
    endpoint exceeds the bound take a numpy sorted-intersection fallback
    (hubs are few; each edge is still counted exactly once).
    """
    deg = g.degree()
    cap = int(max_degree)
    V = g.num_vertices
    ell = np.full((V, cap), -1, dtype=np.int32)
    over = np.flatnonzero(deg > cap)
    for v in np.flatnonzero((deg > 0) & (deg <= cap)):
        nb = g.neighbors(v)
        ell[v, :len(nb)] = np.sort(nb)
    ell_j = jnp.asarray(ell)

    @jax.jit
    def count_chunk(edges_gid, valid):
        a = ell_j[edges_gid[:, 0]]            # (chunk, cap)
        b = ell_j[edges_gid[:, 1]]
        hit = (a[:, :, None] == b[:, None, :]) & (a[:, :, None] >= 0)
        return jnp.where(valid, hit.sum(axis=(1, 2)), 0).sum()

    count = 0
    for i in range(rt.p):
        m = rt.edge_valid[i]
        gids = rt.local_vertex_gid[i][rt.local_edges[i]]
        both_ok = m & ~np.isin(gids[:, 0], over) & ~np.isin(gids[:, 1], over)
        idx = np.flatnonzero(both_ok)
        for s in range(0, len(idx), chunk):
            sel = idx[s:s + chunk]
            pad = chunk - len(sel)
            eg = np.pad(gids[sel], ((0, pad), (0, 0)))
            va = np.pad(np.ones(len(sel), bool), (0, pad))
            count += int(count_chunk(jnp.asarray(eg), jnp.asarray(va)))
        # numpy fallback for hub endpoints
        for e in np.flatnonzero(m & ~both_ok):
            u, v = gids[e]
            count += len(np.intersect1d(g.neighbors(u), g.neighbors(v),
                                        assume_unique=True))
    return count // 3
