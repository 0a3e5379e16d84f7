"""Build fixed-shape per-machine arrays from an edge partition.

Every machine gets the same padded shapes (shard_map/vmap require it):

* ``local_vertex_gid``: (p, Vmax) global id of each local vertex (pad: -1)
* ``local_edges``:      (p, Emax, 2) endpoints in *local* indices (pad: 0)
* ``edge_valid``:       (p, Emax) bool
* ``edge_weight``:      (p, Emax) float32
* ``vertex_valid``:     (p, Vmax) bool
* ``global_degree``:    (p, Vmax) degree of the vertex in G (pad: 1)
* ``weighted_degree``:  (p, Vmax) sum of incident edge weights (pad: 1)
* ``rep_slot``:         (p, Vmax) slot into the replica exchange table,
                        -1 if the vertex lives on a single machine
                        (``slot_vertex`` is its inverse).

The replica table has one slot per vertex present on ≥2 machines; the BSP
exchange is a psum/pmin over a (R+1,) buffer (the last slot is never a
vertex's: non-replicated lanes read it and discard what they read).

:meth:`PartitionRuntime.local_bsr` additionally exposes each machine's
edge set as a blocked local adjacency (:class:`LocalBSR`) — the layout the
Pallas edge-kernel backend consumes (``repro.bsp.backends``).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .. import obs
from ..core.graph import Graph


@dataclasses.dataclass(frozen=True)
class LocalBSR:
    """Per-machine blocked local adjacency, stacked over machines.

    Each machine's ``local_edges`` become one Block-ELL matrix
    (``repro.kernels.bsr_spmv``) over its padded local vertex space, after
    a *degree-sorted local relabeling*: local vertices are reordered by
    descending local degree, so hub rows/columns cluster into the leading
    blocks and the ELL fill concentrates there instead of smearing one
    nonzero block per hub edge across the whole matrix.  All machines
    share (R, K, bm) — K is padded to the machine-wise max with absent
    blocks — so the stack vmaps / shard_maps like every other runtime
    array.

    ``gather`` maps each padded BSR position to the local vertex whose
    value it reads (pad positions read slot 0; their matrix entries are
    all-absent so the contribution is the ⊕ identity); ``rank`` maps each
    local vertex to its BSR position — together they carry values into
    and out of the blocked index space inside a superstep.
    """

    cols: np.ndarray        # (p, R, K) int32 block-column ids
    blocks: np.ndarray      # (p, R, K, bm, bm) in ``dtype`` (absent-padded)
    gather: np.ndarray      # (p, R*bm) int32: BSR position -> local index
    rank: np.ndarray        # (p, Vmax) int32: local index -> BSR position
    block_size: int
    semiring: str
    fill_stats: tuple       # per-machine dicts (see BsrMatrix.fill_stats)
    dtype: str = "float32"  # stored block dtype (message precision)

    @property
    def p(self) -> int:
        return self.cols.shape[0]

    @property
    def padded(self) -> int:
        return self.gather.shape[1]

    def aggregate_fill(self) -> dict:
        """ELL fill/padding over all machines (the smoke-report numbers)."""
        tot = lambda k: sum(s[k] for s in self.fill_stats)
        slots = sum(s["rows"] * s["ell_k"] for s in self.fill_stats)
        cells = sum(s["nnz_blocks"] * s["block_size"] ** 2
                    for s in self.fill_stats)
        return {
            "machines": len(self.fill_stats),
            "block_size": self.block_size,
            "ell_k_max": max(s["ell_k"] for s in self.fill_stats),
            "nnz": tot("nnz"),
            "nnz_blocks": tot("nnz_blocks"),
            "block_fill": tot("nnz_blocks") / max(1, slots),
            "entry_fill": tot("nnz") / max(1, cells),
        }

    @classmethod
    def build(cls, rt: "PartitionRuntime", *, block_size: int = 128,
              semiring: str = "plus_times", weights: str = "weight",
              dtype: str = "float32") -> "LocalBSR":
        """Blocked adjacency from ``rt.local_edges``, one machine at a time.

        ``weights`` picks the stored ⊗ operand per edge: ``"weight"``
        (``rt.edge_weight``), ``"unit"`` (1, presence), or ``"zero"``
        (0 — (min,+) label propagation).  ``dtype`` is the stored block
        precision (``"bfloat16"`` for the low-precision message path;
        blocks are built in float32 and cast once).
        """
        from ..kernels.bsr_spmv import bsr_from_edges, get_semiring
        p, vmax = rt.p, rt.vmax
        bm = int(block_size)
        mats, orders, ranks = [], [], []
        for i in range(p):
            ev = rt.edge_valid[i]
            e = rt.local_edges[i][ev]
            # local degree over the valid prefix; invalid slots sort last
            deg = np.zeros(vmax, dtype=np.int64)
            if len(e):
                np.add.at(deg, e[:, 0], 1)
                np.add.at(deg, e[:, 1], 1)
            order = np.argsort(-deg, kind="stable").astype(np.int32)
            rank = rank_of(order, vmax)
            if weights == "weight":
                w = rt.edge_weight[i][ev]
            elif weights == "unit":
                w = np.ones(len(e), dtype=np.float32)
            elif weights == "zero":
                w = np.zeros(len(e), dtype=np.float32)
            else:
                raise ValueError(f"weights must be 'weight'|'unit'|'zero', "
                                 f"got {weights!r}")
            mats.append(bsr_from_edges(rank[e] if len(e) else e, vmax,
                                       values=w, block_size=bm,
                                       semiring=semiring))
            orders.append(order)
            ranks.append(rank)
        absent = get_semiring(semiring).absent
        R = mats[0].cols.shape[0]
        K = max(m.cols.shape[1] for m in mats)
        cols = np.zeros((p, R, K), dtype=np.int32)
        blocks = np.full((p, R, K, bm, bm), absent, dtype=np.float32)
        for i, m in enumerate(mats):
            k = m.cols.shape[1]
            cols[i, :, :k] = m.cols
            blocks[i, :, :k] = m.blocks
        gather = np.zeros((p, R * bm), dtype=np.int32)
        for i in range(p):
            gather[i, :vmax] = orders[i]
        if dtype != "float32":
            blocks = blocks.astype(_np_dtype(dtype))
        return cls(cols=cols, blocks=blocks, gather=gather,
                   rank=np.stack(ranks),
                   block_size=bm, semiring=get_semiring(semiring).name,
                   fill_stats=tuple(m.fill_stats() for m in mats),
                   dtype=str(dtype))


def _np_dtype(name: str):
    """numpy dtype by name, reaching into ml_dtypes (a jax dependency)
    for the narrow float types numpy itself does not register."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def rank_of(order: np.ndarray, n: int) -> np.ndarray:
    """Inverse permutation: position of each of ``n`` items in ``order``."""
    rank = np.empty(n, dtype=np.int32)
    rank[order] = np.arange(n, dtype=np.int32)
    return rank


@dataclasses.dataclass(frozen=True)
class PartitionRuntime:
    p: int
    num_vertices: int
    num_replicas: int                  # R
    local_vertex_gid: np.ndarray       # (p, Vmax) int32
    vertex_valid: np.ndarray           # (p, Vmax) bool
    local_edges: np.ndarray            # (p, Emax, 2) int32 (local indices)
    edge_valid: np.ndarray             # (p, Emax) bool
    edge_weight: np.ndarray            # (p, Emax) float32
    global_degree: np.ndarray          # (p, Vmax) int32
    weighted_degree: np.ndarray        # (p, Vmax) float32 (pad: 1)
    rep_slot: np.ndarray               # (p, Vmax) int32
    verts_per_machine: np.ndarray      # (p,)
    edges_per_machine: np.ndarray      # (p,)

    @property
    def vmax(self) -> int:
        return self.local_vertex_gid.shape[1]

    @property
    def emax(self) -> int:
        return self.local_edges.shape[1]

    @functools.cached_property
    def slot_vertex(self) -> np.ndarray:
        """(p, R + 1) int32, ``rep_slot`` inverted: the local vertex in
        each replica slot, -1 where the machine holds none (R =
        ``max(1, num_replicas)``; the last slot never has one)."""
        out = np.full((self.p, max(1, self.num_replicas) + 1), -1, np.int32)
        m, v = np.nonzero(self.rep_slot >= 0)
        out[m, self.rep_slot[m, v]] = v
        return out

    @functools.cached_property
    def _layouts(self) -> dict:
        return {}

    def layout(self, key: tuple, build):
        """``build()``, once per ``key`` on this runtime.

        The edge-kernel backends keep their host layouts here (the
        partition is static, so a layout is built once per runtime, not
        per app call); each build counts one ``bsp.layout_builds``.
        """
        if key not in self._layouts:
            obs.count("bsp.layout_builds")
            self._layouts[key] = build()
        return self._layouts[key]

    def local_bsr(self, *, block_size: int = 128,
                  semiring: str = "plus_times", weights: str = "weight",
                  dtype: str = "float32") -> LocalBSR:
        """The blocked per-machine adjacency (:class:`LocalBSR`).

        Built once from ``local_edges`` per (block_size, semiring,
        weights, dtype) combination and cached on the runtime — the
        Pallas edge-kernel backend's layout, with padding/ELL-fill stats
        on the returned object.  ``dtype`` is the stored block precision
        (the ``message_dtype`` knob: a bfloat16 operand cache entry
        halves the blocks' footprint and feeds the low-precision message
        path without touching the float32 entry).
        """
        key = ("bsr", int(block_size), str(semiring), str(weights),
               str(dtype))
        return self.layout(key, lambda: LocalBSR.build(
            self, block_size=block_size, semiring=semiring,
            weights=weights, dtype=dtype))

    @classmethod
    def create(cls, source=None, *, assign=None, p=None, cluster=None,
               method=None, edge_weights=None, **knobs) -> "PartitionRuntime":
        """One keyword-routed constructor for every runtime source.

        Routes on what ``source`` is and which keywords accompany it:

        * ``create(source=graph, assign=assign, p=p)`` — pack a runtime
          from an in-memory edge assignment (the old :meth:`build`);
          ``cluster=`` may replace ``p=`` (``cluster.p`` is used).
        * ``create(source=graph, method="windgp", cluster=cl, **knobs)``
          — partition first through the registry, then pack (the old
          :meth:`from_partitioner`); ``knobs`` are validated by the
          registry entry.
        * ``create(source=assignment_or_path)`` — pack out-of-core from
          an on-disk :class:`StreamAssignment` (the old
          :meth:`from_stream`); ``edge_weights`` may be a callable
          ``(edges_i, i) -> (k_i,)``.

        ``edge_weights`` is accepted on every route.  Conflicting or
        missing keywords raise ``ValueError`` naming the valid routes.
        The legacy constructors remain as thin aliases of this facade,
        so both spellings build bit-identical runtimes.
        """
        from .stream_assignment import StreamAssignment
        if source is None:
            raise ValueError(
                "PartitionRuntime.create requires source=: a Graph (with "
                "assign=+p=/cluster= or method=+cluster=), or a "
                "StreamAssignment / its directory path")
        import os
        if isinstance(source, (StreamAssignment, str, os.PathLike)):
            bad = {"assign": assign, "p": p, "cluster": cluster,
                   "method": method}
            bad = sorted(k for k, v in bad.items() if v is not None)
            if bad or knobs:
                raise ValueError(
                    f"create(source=<stream assignment>) takes only "
                    f"edge_weights=; got {bad + sorted(knobs)}")
            with obs.span("runtime.pack"):
                return cls._pack_from_stream(source,
                                             edge_weights=edge_weights)
        if not (hasattr(source, "edges") and hasattr(source, "num_vertices")):
            raise ValueError(
                f"create: source must be a Graph or a StreamAssignment "
                f"(or its path), got {type(source).__name__}")
        if method is not None:
            if assign is not None or p is not None:
                raise ValueError(
                    "create(source=graph, method=...) partitions the graph "
                    "itself — drop assign=/p= (or drop method= to pack a "
                    "precomputed assignment)")
            if cluster is None:
                raise ValueError(
                    "create(source=graph, method=...) requires cluster= "
                    "(the heterogeneous machine spec the partitioner "
                    "targets)")
            from ..core.partitioners import get
            assign = get(method)(source, cluster, **knobs)
            with obs.span("runtime.pack"):
                return cls._pack_from_assignment(source, assign, cluster.p,
                                                 edge_weights=edge_weights)
        if assign is None:
            raise ValueError(
                "create(source=graph) needs either assign= (+ p= or "
                "cluster=) for a precomputed assignment, or method= "
                "(+ cluster=) to partition via the registry")
        if knobs:
            raise ValueError(
                f"create(source=graph, assign=...) got partitioner knobs "
                f"{sorted(knobs)} — knobs only apply with method=")
        if p is None:
            if cluster is None:
                raise ValueError(
                    "create(source=graph, assign=...) requires p= or "
                    "cluster= for the machine count")
            p = cluster.p
        with obs.span("runtime.pack"):
            return cls._pack_from_assignment(source, assign, p,
                                             edge_weights=edge_weights)

    @classmethod
    def build(cls, g: Graph, assign: np.ndarray, p: int,
              edge_weights: np.ndarray | None = None) -> "PartitionRuntime":
        """Thin alias of :meth:`create` (``source=g, assign=, p=``)."""
        return cls.create(g, assign=assign, p=p, edge_weights=edge_weights)

    @classmethod
    def _pack_from_assignment(cls, g: Graph, assign: np.ndarray, p: int,
                              edge_weights: np.ndarray | None = None,
                              ) -> "PartitionRuntime":
        assert (assign >= 0).all() and assign.max() < p
        deg = g.degree().astype(np.int32)
        if edge_weights is None:
            edge_weights = np.ones(g.num_edges, dtype=np.float32)

        # Vertex membership / replica sets from the shared incidence counts
        # (same accounting the partitioner's incremental layer maintains).
        from ..core.partition_state import edge_incidence_counts
        member = edge_incidence_counts(g, assign, p) > 0     # (p, V)

        locals_, edges_, weights_ = [], [], []
        lut = np.full(g.num_vertices, -1, dtype=np.int64)
        for i in range(p):
            eids = np.flatnonzero(assign == i)
            verts = np.flatnonzero(member[i])   # sorted endpoints of E_i
            lut[verts] = np.arange(len(verts))
            locals_.append(verts)
            edges_.append(lut[g.edges[eids]])
            weights_.append(edge_weights[eids])

        vmax = max(1, max(len(v) for v in locals_))
        emax = max(1, max(len(e) for e in edges_))
        member_count = member.sum(axis=0).astype(np.int32)
        rep_vertices = np.flatnonzero(member_count >= 2)
        rep_index = np.full(g.num_vertices, -1, dtype=np.int32)
        rep_index[rep_vertices] = np.arange(len(rep_vertices), dtype=np.int32)

        # global weighted degree: sum of incident edge weights (the
        # (+,×) message normalizer; equals ``deg`` for unit weights)
        wdeg = np.zeros(g.num_vertices, dtype=np.float64)
        np.add.at(wdeg, g.edges[:, 0], edge_weights)
        np.add.at(wdeg, g.edges[:, 1], edge_weights)

        lv = np.full((p, vmax), -1, dtype=np.int32)
        vv = np.zeros((p, vmax), dtype=bool)
        le = np.zeros((p, emax, 2), dtype=np.int32)
        ev = np.zeros((p, emax), dtype=bool)
        ew = np.zeros((p, emax), dtype=np.float32)
        gd = np.ones((p, vmax), dtype=np.int32)
        wd = np.ones((p, vmax), dtype=np.float32)
        rs = np.full((p, vmax), -1, dtype=np.int32)
        for i in range(p):
            nv, ne = len(locals_[i]), len(edges_[i])
            lv[i, :nv] = locals_[i]
            vv[i, :nv] = True
            gd[i, :nv] = deg[locals_[i]]
            wd[i, :nv] = wdeg[locals_[i]]
            rs[i, :nv] = rep_index[locals_[i]]
            if ne:
                le[i, :ne] = edges_[i]
                ev[i, :ne] = True
                ew[i, :ne] = weights_[i]
        return cls(
            p=p, num_vertices=g.num_vertices,
            num_replicas=len(rep_vertices),
            local_vertex_gid=lv, vertex_valid=vv, local_edges=le,
            edge_valid=ev, edge_weight=ew, global_degree=gd,
            weighted_degree=wd, rep_slot=rs,
            verts_per_machine=np.array([len(v) for v in locals_]),
            edges_per_machine=np.array([len(e) for e in edges_]))

    @classmethod
    def from_stream(cls, assignment,
                    edge_weights=None) -> "PartitionRuntime":
        """Thin alias of :meth:`create` (``source=assignment``)."""
        return cls.create(assignment, edge_weights=edge_weights)

    @classmethod
    def _pack_from_stream(cls, assignment,
                          edge_weights=None) -> "PartitionRuntime":
        """Pack the BSP runtime from an on-disk :class:`StreamAssignment`.

        The out-of-core counterpart of :meth:`build`: no ``Graph`` and no
        global edge array — vertex membership, global degrees, and the
        replica table come from the assignment's streamed state, and each
        machine's shard is read one at a time, so peak residency during
        packing is one machine's edge set plus the fixed-shape output.
        ``edge_weights`` may be a callable ``(edges_i, i) -> (k_i,)`` (the
        global edge-id order of :meth:`build` does not exist here).
        """
        from .stream_assignment import StreamAssignment
        if not isinstance(assignment, StreamAssignment):
            assignment = StreamAssignment.open(assignment)
        p, V = assignment.p, assignment.num_vertices
        member = assignment.membership()
        deg = assignment.degree.astype(np.int32)

        member_count = member.sum(axis=0).astype(np.int32)
        rep_vertices = np.flatnonzero(member_count >= 2)
        rep_index = np.full(V, -1, dtype=np.int32)
        rep_index[rep_vertices] = np.arange(len(rep_vertices), dtype=np.int32)

        verts_per = member.sum(axis=1).astype(np.int64)
        edges_per = assignment.edges_per.astype(np.int64)
        vmax = max(1, int(verts_per.max(initial=0)))
        emax = max(1, int(edges_per.max(initial=0)))

        lv = np.full((p, vmax), -1, dtype=np.int32)
        vv = np.zeros((p, vmax), dtype=bool)
        le = np.zeros((p, emax, 2), dtype=np.int32)
        ev = np.zeros((p, emax), dtype=bool)
        ew = np.zeros((p, emax), dtype=np.float32)
        gd = np.ones((p, vmax), dtype=np.int32)
        wd = np.ones((p, vmax), dtype=np.float32)
        rs = np.full((p, vmax), -1, dtype=np.int32)
        lut = np.full(V, -1, dtype=np.int64)
        # every edge lives on exactly one machine, so the global weighted
        # degree accumulates across the one-shard-at-a-time loop
        wdeg = np.zeros(V, dtype=np.float64)
        for i in range(p):
            verts = np.flatnonzero(member[i])
            lut[verts] = np.arange(len(verts))
            edges_i = assignment.machine_edges(i)     # one shard at a time
            nv, ne = len(verts), len(edges_i)
            assert ne == edges_per[i], (i, ne, edges_per[i])
            lv[i, :nv] = verts
            vv[i, :nv] = True
            gd[i, :nv] = deg[verts]
            rs[i, :nv] = rep_index[verts]
            if ne:
                le[i, :ne] = lut[edges_i]
                ev[i, :ne] = True
                w_i = (np.ones(ne, dtype=np.float32)
                       if edge_weights is None
                       else np.asarray(edge_weights(edges_i, i),
                                       dtype=np.float32))
                ew[i, :ne] = w_i
                np.add.at(wdeg, edges_i[:, 0], w_i)
                np.add.at(wdeg, edges_i[:, 1], w_i)
        for i in range(p):
            wd[i, vv[i]] = wdeg[lv[i, vv[i]]]
        return cls(
            p=p, num_vertices=V, num_replicas=len(rep_vertices),
            local_vertex_gid=lv, vertex_valid=vv, local_edges=le,
            edge_valid=ev, edge_weight=ew, global_degree=gd,
            weighted_degree=wd, rep_slot=rs,
            verts_per_machine=verts_per, edges_per_machine=edges_per)

    def apply_delta(self, assignment, delta) -> "PartitionRuntime":
        """Repack after a dynamic epoch, reusing every untouched machine.

        ``assignment`` is the :class:`StreamAssignment` (or its path)
        *after* ``apply_delta(delta, ...)`` ran on it; ``delta`` is that
        same :class:`~repro.core.dynamic.AssignmentDelta`.  Machines whose
        edge set did not change this epoch keep their packed local-vertex
        and local-edge rows verbatim (membership derives from a machine's
        own edges, so an untouched machine's vertex set is untouched too);
        only changed machines re-read their shard and relabel.  The
        cross-machine quantities are always rebuilt — the replica table
        and global degrees shift whenever *any* machine changes, and they
        are cheap (no disk, no relabeling).

        Only valid for unit edge weights (the runtimes the dynamic layer
        produces); weighted runtimes must repack via :meth:`from_stream`
        with their weight callable.
        """
        from .stream_assignment import StreamAssignment
        if not isinstance(assignment, StreamAssignment):
            assignment = StreamAssignment.open(assignment)
        p, V = assignment.p, assignment.num_vertices
        if p != self.p:
            raise ValueError(f"delta runtime repack across machine counts "
                             f"({self.p} -> {p})")
        if not bool(np.all(self.edge_weight[self.edge_valid] == 1.0)):
            raise ValueError("apply_delta supports unit edge weights only "
                             "— repack weighted runtimes via from_stream")
        touched = delta.machines_touched(p)
        member = assignment.membership()
        deg = assignment.degree.astype(np.int32)
        member_count = member.sum(axis=0).astype(np.int32)
        rep_vertices = np.flatnonzero(member_count >= 2)
        rep_index = np.full(V, -1, dtype=np.int32)
        rep_index[rep_vertices] = np.arange(len(rep_vertices),
                                            dtype=np.int32)
        verts_per = member.sum(axis=1).astype(np.int64)
        edges_per = assignment.edges_per.astype(np.int64)
        vmax = max(1, int(verts_per.max(initial=0)))
        emax = max(1, int(edges_per.max(initial=0)))

        lv = np.full((p, vmax), -1, dtype=np.int32)
        vv = np.zeros((p, vmax), dtype=bool)
        le = np.zeros((p, emax, 2), dtype=np.int32)
        ev = np.zeros((p, emax), dtype=bool)
        ew = np.zeros((p, emax), dtype=np.float32)
        gd = np.ones((p, vmax), dtype=np.int32)
        wd = np.ones((p, vmax), dtype=np.float32)
        rs = np.full((p, vmax), -1, dtype=np.int32)
        lut = np.full(V, -1, dtype=np.int64)
        for i in range(p):
            nv, ne = int(verts_per[i]), int(edges_per[i])
            if not touched[i]:
                # unchanged machine: row content beyond (nv, ne) is pad
                lv[i, :nv] = self.local_vertex_gid[i, :nv]
                le[i, :ne] = self.local_edges[i, :ne]
            else:
                verts = np.flatnonzero(member[i])
                lut[verts] = np.arange(len(verts))
                edges_i = assignment.machine_edges(i)
                if len(verts) != nv or len(edges_i) != ne:
                    raise ValueError(f"machine {i}: shard/membership "
                                     f"disagree with the meta counts")
                lv[i, :nv] = verts
                if ne:
                    le[i, :ne] = lut[edges_i]
            vv[i, :nv] = True
            ev[i, :ne] = True
            ew[i, :ne] = 1.0
            gids = lv[i, :nv]
            gd[i, :nv] = deg[gids]
            wd[i, :nv] = deg[gids]       # unit weights: wdeg == degree
            rs[i, :nv] = rep_index[gids]
        return type(self)(
            p=p, num_vertices=V, num_replicas=len(rep_vertices),
            local_vertex_gid=lv, vertex_valid=vv, local_edges=le,
            edge_valid=ev, edge_weight=ew, global_degree=gd,
            weighted_degree=wd, rep_slot=rs,
            verts_per_machine=verts_per, edges_per_machine=edges_per)

    @classmethod
    def from_partitioner(cls, g: Graph, cluster, method: str = "windgp",
                         edge_weights: np.ndarray | None = None,
                         **knobs) -> "PartitionRuntime":
        """Thin alias of :meth:`create` (``source=g, method=, cluster=``)."""
        return cls.create(g, method=method, cluster=cluster,
                          edge_weights=edge_weights, **knobs)

    def gather_global(self, local_values: np.ndarray,
                      fill: float = 0.0) -> np.ndarray:
        """Merge per-machine local vertex values into a (V,) global array.

        Replicated vertices must agree across machines (post-exchange)."""
        out = np.full(self.num_vertices, fill, dtype=np.asarray(local_values).dtype)
        for i in range(self.p):
            m = self.vertex_valid[i]
            out[self.local_vertex_gid[i, m]] = local_values[i, m]
        return out

    def scatter_global(self, global_values: np.ndarray,
                       fill: float = 0.0) -> np.ndarray:
        """Spread a (V,) global array onto (p, Vmax) local vertex values —
        the inverse of :meth:`gather_global`, used to warm-start a BSP app
        from a previous runtime's converged result after
        :meth:`apply_delta` (replicas all receive the same value; pad
        slots get ``fill``)."""
        g = np.asarray(global_values)
        if len(g) < self.num_vertices:
            # runtime grew past the old result: new vertices get fill
            g = np.concatenate(
                [g, np.full(self.num_vertices - len(g), fill,
                            dtype=g.dtype)])
        out = np.full((self.p, self.vmax), fill, dtype=g.dtype)
        for i in range(self.p):
            m = self.vertex_valid[i]
            out[i, m] = g[self.local_vertex_gid[i, m]]
        return out
