"""Edge-kernel backends: how a BSP superstep combines messages over edges.

Every edge-centric superstep in ``bsp/apps.py`` is one semiring SpMV
against the machine's local adjacency (``y_i = ⊕_j A_ij ⊗ x_j``, symmetric
A):

* PageRank   — (+, ×) with edge weights;
* SSSP       — (min, +) with edge weights;
* BFS        — (or, and) frontier expansion (presence only);
* components — (min, +) with zero weights (min-label propagation).

A backend supplies that product.  ``prepare(rt, semiring, weights)``
returns ``(extras, combine)``: ``extras`` is a dict of ``(p, ...)`` arrays
merged into the superstep's static tree (vmap/shard_map stack them like
every other runtime array): the edge arrays it reads, and only those, so
a call uploads no edge array its backend does not read.
``combine(sa, x)`` maps this machine's ``(Vmax,)`` vertex values to
their ⊕-combined neighborhood values inside the (rank-reduced) superstep
body.

Backends:

``segment`` (the default, :func:`default_backend`)
    Segmented scan, no scatter.  The doubled incidence is sorted by
    output vertex once per runtime (:class:`SegmentLayout`, cached by
    ``rt.layout``), so each vertex's messages form one contiguous run.
    The combine gathers ``x`` once per message, ⊕-scans the messages in
    log-depth shifted passes that restart at every run
    (:func:`segmented_scan`), and reads each run's last row.  XLA lowers
    a sorted ``segment_sum`` to a scatter on the TPU; this lowers to
    dense vector work and two gathers.  On a TPU the message gather is
    the Pallas kernel ``kernels/vmem_gather``, which holds the machine's
    vertex vector in VMEM, wherever that vector fits a quarter of it
    (:func:`takes_vmem_gather`); elsewhere it is XLA's ``x[ids]``.  Both
    give bitwise the same messages.
``scatter``
    The historical gather-scatter loop (``at[dst].⊕(x[src] ⊗ w)``, one
    scatter per direction).  Kept as the oracle every other backend is
    tested against — float-identical to the pre-backend apps — and as
    the ``frontier_cap`` path.
``pallas``
    The blocked Block-ELL SpMV (``kernels/bsr_spmv``) over
    ``rt.local_bsr()``'s degree-sorted per-machine layout —
    compiled on an accelerator, interpret-mode on CPU.  Needs
    ``check_rep=False`` under shard_map (no replication rule for
    ``pallas_call``); the engine threads that through automatically.

Numerics: (+, ×) sums the same float32 products in a backend-specific
order, so ``segment`` and ``pallas`` drift O(eps) per superstep from
``scatter`` — within the 1e-5 cross-backend contract the tests pin.  A
sum never spans more than one output vertex's own messages: one float32
running sum over the whole incidence list, differenced per row, would
lose each low-degree row's digits to the accumulated total.  (min, +)
and (or, and) are exact (min/max are associative), so sparse apps agree
bitwise across all three backends.

Two cross-cutting knobs every backend understands:

``message_dtype`` (default ``"float32"``)
    The ⊗ operand precision: messages and edge weights are cast to this
    dtype before the per-edge product.  ``scatter``/``segment`` cast the
    *products* back to float32 before ⊕-accumulating (low-precision
    messages, full-precision accumulation — the classic bf16 message
    path); ``pallas`` stores its blocks in the dtype
    (``rt.local_bsr(dtype=...)``) and accumulates in float32.  With
    ``"float32"`` every cast is a no-op, so the default path is
    bit-identical to the pre-knob backends.

``frontier_cap`` (``scatter`` only, default ``None``)
    Active-frontier sparsification, two-level: the combine first
    compacts the *vertices carrying a live message* (``x`` differs from
    the semiring's no-message value — +inf for (min, +), 0 for
    (or, and)/(+, ×)) into a ``(frontier_cap,)`` id buffer via
    ``jnp.nonzero(..., size=cap)`` — an O(Vmax) scan, not O(E) — then
    gathers those vertices' rows of a per-vertex ELL incidence
    ``(Vmax, dmax)`` built at prepare time and ⊕-scatters the
    ``cap × dmax`` expanded entries.  Superstep edge work drops from
    O(E_local) to O(frontier · dmax + Vmax).  The layout spends
    O(Vmax · dmax) memory, so this path fits bounded-degree graphs
    (road networks, meshes — exactly where BFS/SSSP frontiers stay
    narrow); on power-law graphs the hub degree makes ``dmax`` —
    and the padding — explode.  The caller must pick ``frontier_cap ≥``
    the per-machine live-vertex count (vertices beyond the cap are
    dropped) — :func:`frontier_entries` computes the exact count
    host-side, and the ``--latency`` benchmark re-buckets the cap per
    superstep as the BFS/SSSP frontier drains.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from ..kernels.bsr_spmv import get_semiring
from ..kernels.bsr_spmv.kernel import spmv_pallas
from ..kernels.vmem_gather import TILE, table_bytes, vmem_gather
from .engine import exchange, to_device

#: weight kinds an app may ask for: the stored ⊗ operand per edge
WEIGHT_KINDS = ("weight", "unit", "zero")

#: message dtypes the low-precision path accepts
MESSAGE_DTYPES = ("float32", "bfloat16", "float16")


def _message_dtype(name: str):
    if str(name) not in MESSAGE_DTYPES:
        raise ValueError(f"message_dtype must be one of {MESSAGE_DTYPES}, "
                         f"got {name!r}")
    return jnp.dtype(str(name))


def _no_message(sr) -> float:
    """The x value meaning "this vertex sends nothing": its ⊗ product is
    the ⊕ identity for every edge weight ((min,+): +inf; else 0)."""
    return np.inf if sr.name == "min_plus" else 0.0


def _edge_operand(rt, weights: str) -> np.ndarray:
    """(p, Emax) raw ⊗ operand per edge for a weight kind."""
    if weights == "weight":
        return rt.edge_weight
    if weights == "unit":
        return np.ones_like(rt.edge_weight)
    if weights == "zero":
        return np.zeros_like(rt.edge_weight)
    raise ValueError(f"weights must be one of {WEIGHT_KINDS}, "
                     f"got {weights!r}")


@dataclasses.dataclass(frozen=True)
class EdgeBackend:
    """A named edge-combine strategy (see module docstring)."""

    name: str
    description: str
    prepare: Callable          # (rt, semiring, weights) -> (extras, combine)
    #: False when the backend's ops have no shard_map replication rule
    #: (Pallas) — the engine then passes ``check_vma=False``
    check_rep: bool = True

    def prepare_exchanged(self, rt, semiring: str, weights: str,
                          mode: str, r_pad: int):
        """``prepare`` with the replica :func:`~.engine.exchange` fused
        into the combine epilogue.

        The returned ``combine(sa, x)`` yields the *post-exchange*
        neighborhood values directly — the superstep never materializes
        the pre-exchange ``(Vmax,)`` partial as a separate value, and
        every app's cross-machine sync lives in one place instead of
        being re-spelled per superstep body.
        """
        extras, combine = self.prepare(rt, semiring, weights)

        def combine_exchanged(sa, x):
            with jax.named_scope("bsp.edge_kernel"):
                y = combine(sa, x)
            return exchange(y, (sa["rep_slot"], sa["slot_vertex"]), r_pad,
                            mode)

        return extras, combine_exchanged


#: the apps' edge kernel when the caller names none
DEFAULT_BACKEND = "segment"


def default_backend(frontier_cap: int | None = None) -> str:
    """The backend an app runs when none is named: :data:`DEFAULT_BACKEND`,
    or ``scatter`` when a ``frontier_cap`` asks for its frontier path."""
    return DEFAULT_BACKEND if frontier_cap is None else "scatter"


def frontier_entries(rt, changed: np.ndarray) -> np.ndarray:
    """(p,) live (message-carrying) vertices per machine for a changed
    mask — the exact lower bound for the ``scatter`` backend's
    ``frontier_cap``.

    ``changed``: (p, Vmax) bool, True where the vertex carries a message
    this superstep (the ``"changed"`` state leaf of the monotone apps;
    ``dist == step`` for BFS).
    """
    changed = np.asarray(changed, dtype=bool)
    return (changed & rt.vertex_valid).sum(axis=1).astype(np.int64)


# ---------------------------------------------------------------------------
# scatter: the oracle (gather + at[].⊕ per direction)
# ---------------------------------------------------------------------------

def _scatter_prepare_factory(message_dtype: str = "float32",
                             frontier_cap: int | None = None):
    mdt = _message_dtype(message_dtype)
    if frontier_cap is not None and frontier_cap < 1:
        raise ValueError(f"frontier_cap must be >= 1, got {frontier_cap}")

    def prepare(rt, semiring: str, weights: str):
        sr = get_semiring(semiring)
        wkind = weights

        if frontier_cap is None:
            extras = {"edges": to_device(rt.local_edges),
                      "edge_valid": to_device(rt.edge_valid)}
            if wkind == "weight":
                extras["edge_weight"] = to_device(rt.edge_weight)

            def combine(sa, x):
                src, dst = sa["edges"][:, 0], sa["edges"][:, 1]
                if wkind == "weight":
                    w_raw = sa["edge_weight"]
                else:
                    w_raw = jnp.full(sa["edge_valid"].shape,
                                     1.0 if wkind == "unit" else 0.0,
                                     jnp.float32)
                w = sr.weights(w_raw, sa["edge_valid"])
                xm = x.astype(mdt)
                out = jnp.full(x.shape, sr.zero, dtype=x.dtype)
                out = sr.scatter_accum(
                    out, dst,
                    sr.times(w.astype(mdt), xm[src]).astype(x.dtype))
                out = sr.scatter_accum(
                    out, src,
                    sr.times(w.astype(mdt), xm[dst]).astype(x.dtype))
                return out

            return extras, combine

        # frontier mode: per-vertex ELL of the directed incidence —
        # row v holds v's outgoing (dst, w) entries, padded to the
        # machine-max degree with the dump row / the ⊗ annihilator.
        # The combine compacts the live *vertices* (an O(Vmax) scan)
        # and expands only their rows: O(frontier · dmax) edge work.
        cap = int(frontier_cap)
        w_raw = _edge_operand(rt, weights)
        p, vmax = rt.p, rt.vmax
        src2 = np.concatenate([rt.local_edges[:, :, 0],
                               rt.local_edges[:, :, 1]], axis=1)
        dst2 = np.concatenate([rt.local_edges[:, :, 1],
                               rt.local_edges[:, :, 0]], axis=1)
        valid2 = np.concatenate([rt.edge_valid, rt.edge_valid], axis=1)
        w2 = np.concatenate([w_raw, w_raw], axis=1).astype(np.float32)
        deg = np.zeros((p, vmax), dtype=np.int64)
        for i in range(p):
            np.add.at(deg[i], src2[i][valid2[i]], 1)
        dmax = max(1, int(deg.max()))
        ell_dst = np.full((p, vmax, dmax), vmax, dtype=np.int32)
        ell_w = np.full((p, vmax, dmax), np.float32(sr.absent),
                        dtype=np.float32)
        for i in range(p):
            s = src2[i][valid2[i]]
            order = np.argsort(s, kind="stable")
            s = s[order]
            slot = np.arange(len(s)) - np.searchsorted(s, s)
            ell_dst[i][s, slot] = dst2[i][valid2[i]][order]
            ell_w[i][s, slot] = w2[i][valid2[i]][order]
        extras = {"eb_fr_dst": to_device(ell_dst),
                  "eb_fr_w": to_device(ell_w)}
        none = _no_message(sr)

        def combine(sa, x):
            live = sa["vertex_valid"] & (x != none)
            ids = jnp.nonzero(live, size=cap, fill_value=0)[0]
            ok = jnp.arange(cap) < live.sum()        # (cap,) real rows
            rows_d = sa["eb_fr_dst"][ids]            # (cap, dmax)
            rows_w = sa["eb_fr_w"][ids].astype(mdt)
            vals = sr.times(rows_w,
                            x.astype(mdt)[ids][:, None]).astype(x.dtype)
            vals = jnp.where(ok[:, None], vals,
                             jnp.asarray(sr.zero, x.dtype))
            d = jnp.where(ok[:, None], rows_d, vmax)  # pad -> dump row
            out = jnp.full((vmax + 1,), sr.zero, dtype=x.dtype)
            return sr.scatter_accum(out, d.reshape(-1),
                                    vals.reshape(-1))[:vmax]

        return extras, combine

    return prepare


# ---------------------------------------------------------------------------
# segment: segmented scan over the destination-sorted incidence (no scatter)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SegmentLayout:
    """Each machine's doubled incidence sorted by output vertex, so that
    every output vertex's messages are one contiguous run.

    Row ``j`` carries ``x[in_ids[j]] ⊗ w[j]`` into its run.  Invalid rows
    and the tail padding form a trailing dump run whose ⊗ operand is the
    semiring's annihilator; it ends at the last row, where every vertex
    without a message on the machine reads its ⊕ identity.
    """

    in_ids: np.ndarray      # (p, n) int32: the message's input vertex
    w: np.ndarray           # (p, n) ⊗ operand, in the message dtype
    level: np.ndarray       # (p, n) uint8: bit length of the row's rank
    #                         in its run, i.e. how many scan passes add to it
    run_end: np.ndarray     # (p, Vmax) int32: last row of each vertex's run
    passes: int             # scan passes that cover the longest real run

    @classmethod
    def build(cls, rt, semiring: str, weights: str,
              message_dtype: str) -> "SegmentLayout":
        sr = get_semiring(semiring)
        p, vmax, e = rt.p, rt.vmax, rt.local_edges
        out = np.concatenate([e[:, :, 1], e[:, :, 0]], axis=1)
        inc = np.concatenate([e[:, :, 0], e[:, :, 1]], axis=1)
        valid = np.concatenate([rt.edge_valid, rt.edge_valid], axis=1)
        w_raw = _edge_operand(rt, weights)
        w = np.concatenate([w_raw, w_raw], axis=1).astype(np.float32)
        # the dump run holds at least one row, and rows fill whole
        # (8, 128) tiles, as the VMEM gather reads them
        n = -(-(out.shape[1] + 1) // TILE) * TILE
        pad = ((0, 0), (0, n - out.shape[1]))
        out = np.pad(np.where(valid, out, vmax), pad, constant_values=vmax)
        inc = np.pad(np.where(valid, inc, 0), pad)
        w = np.pad(np.where(valid, w, np.float32(sr.absent)), pad,
                   constant_values=np.float32(sr.absent))
        order = np.argsort(out, axis=1, kind="stable")
        out = np.take_along_axis(out, order, 1)
        rows = np.arange(n)
        start = np.ones(out.shape, dtype=bool)
        start[:, 1:] = out[:, 1:] != out[:, :-1]
        rank = rows - np.maximum.accumulate(np.where(start, rows, 0), axis=1)
        level = np.frexp(rank.astype(np.float64))[1].astype(np.uint8)
        # every vertex reads the dump's last row unless a run of its own
        # ends earlier (the dump's own end lands in column vmax, dropped)
        end = np.ones(out.shape, dtype=bool)
        end[:, :-1] = start[:, 1:]
        m, j = np.nonzero(end)
        run_end = np.full((p, vmax + 1), n - 1, dtype=np.int32)
        run_end[m, out[m, j]] = j
        return cls(
            in_ids=np.take_along_axis(inc, order, 1).astype(np.int32),
            w=np.take_along_axis(w, order, 1).astype(
                _message_dtype(message_dtype)),
            level=level, run_end=run_end[:, :vmax],
            passes=int(level[out < vmax].max(initial=0)))


def segmented_scan(vals, level, passes: int, plus):
    """Inclusive ⊕-scan of ``vals`` that restarts at every run.

    Pass ``k`` adds the row ``2**k`` back to each row at least that far
    from its run's start (``level > k``), so after it every row holds
    the ⊕ of up to ``2**(k+1)`` rows of its own run ending there; after
    ``passes`` passes each run's last row holds the whole run.  Dense
    shifted elementwise work on the vector unit, log-depth, no scatter.
    """
    for k in range(passes):
        d = 1 << k
        prev = jnp.pad(vals[:-d], (d, 0))
        vals = jnp.where(level > k, plus(prev, vals), vals)
    return vals


def takes_vmem_gather(vmax: int) -> bool:
    """Whether ``segment`` gathers its messages with the VMEM kernel:
    on a TPU, where one machine's padded ``(vmax,)`` vector fits a
    quarter of a core's VMEM."""
    if jax.default_backend() != "tpu":
        return False
    return table_bytes(vmax) <= pltpu.get_tpu_info().vmem_capacity_bytes // 4


def _segment_prepare_factory(message_dtype: str = "float32"):
    mdt = _message_dtype(message_dtype)

    def prepare(rt, semiring: str, weights: str):
        sr = get_semiring(semiring)
        lay = rt.layout(("segment", sr.name, weights, str(message_dtype)),
                        lambda: SegmentLayout.build(rt, sr.name, weights,
                                                    message_dtype))
        extras = {"eb_seg_in": to_device(lay.in_ids),
                  "eb_seg_w": to_device(lay.w),
                  "eb_seg_level": to_device(lay.level),
                  "eb_seg_end": to_device(lay.run_end)}
        kernel = takes_vmem_gather(rt.vmax)
        obs.count("bsp.vmem_gathers", int(kernel))

        def combine(sa, x):
            # the state is gathered and then cast: bitwise the messages
            # of casting first.  Low-precision messages, full-precision
            # ⊕: ⊗ in message_dtype, products back to the state dtype
            # before the reduction
            ids = sa["eb_seg_in"]
            msgs = (vmem_gather(x, ids) if kernel else x[ids]).astype(mdt)
            vals = sr.times(sa["eb_seg_w"], msgs).astype(x.dtype)
            scanned = segmented_scan(vals, sa["eb_seg_level"], lay.passes,
                                     sr.plus)
            return scanned[sa["eb_seg_end"]]

        return extras, combine

    return prepare


# ---------------------------------------------------------------------------
# pallas: blocked Block-ELL SpMV over the degree-sorted local adjacency
# ---------------------------------------------------------------------------

def _pallas_prepare_factory(block_size: int = 128,
                            interpret: bool | None = None,
                            message_dtype: str = "float32"):
    mdt = _message_dtype(message_dtype)

    def prepare(rt, semiring: str, weights: str):
        sr = get_semiring(semiring)
        bsr = rt.local_bsr(block_size=block_size, semiring=sr.name,
                           weights=weights, dtype=str(message_dtype))
        extras = {"eb_bsr_cols": to_device(bsr.cols),
                  "eb_bsr_blocks": to_device(bsr.blocks),
                  "eb_bsr_gather": to_device(bsr.gather),
                  "eb_bsr_rank": to_device(bsr.rank)}

        def combine(sa, x):
            # blocks are stored in message_dtype (LocalBSR dtype cache
            # key) and x joins them; the kernel ⊕-accumulates in float32
            xb = x[sa["eb_bsr_gather"]].astype(mdt)
            y = spmv_pallas(sa["eb_bsr_cols"], sa["eb_bsr_blocks"], xb,
                            block_size=block_size, interpret=interpret,
                            semiring=sr.name)
            return y[sa["eb_bsr_rank"]].astype(x.dtype)

        return extras, combine

    return prepare


#: name -> (description, prepare factory, shard_map replication check)
_REGISTRY = {
    "scatter": ("gather-scatter oracle (at[].⊕ per direction)",
                _scatter_prepare_factory, True),
    "segment": ("segmented scan over the destination-sorted incidence",
                _segment_prepare_factory, True),
    "pallas": ("blocked Block-ELL semiring SpMV (kernels/bsr_spmv)",
               _pallas_prepare_factory, False),
}

BACKENDS = tuple(_REGISTRY)


def get_backend(name=None, *, frontier_cap: int | None = None,
                **opts) -> EdgeBackend:
    """Resolve a backend by name (``EdgeBackend`` passes through, ``None``
    is :func:`default_backend`), checking its knobs: the one place that
    raises ``ValueError`` on an unknown backend, message dtype or
    misplaced ``frontier_cap``.

    ``opts`` are backend-specific: every backend takes ``message_dtype``
    (default ``"float32"``; ``"bfloat16"`` is the low-precision message
    path); ``scatter`` adds ``frontier_cap`` (active-frontier
    sparsification — see module docstring); ``pallas`` adds
    ``block_size`` (default 128, the MXU tile) and ``interpret``
    (None = auto: interpreter on CPU only).
    """
    if isinstance(name, EdgeBackend):
        return name
    if name is None:
        name = default_backend(frontier_cap)
    try:
        description, factory, check_rep = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown edge-kernel backend {name!r} "
                         f"(choices: {sorted(_REGISTRY)})") from None
    if frontier_cap is not None:
        if name != "scatter":
            raise ValueError(
                f"frontier_cap is a 'scatter'-backend knob (frontier "
                f"compaction); backend {name!r} does not take it — valid "
                f"choices: backend='scatter', or frontier_cap=None")
        opts["frontier_cap"] = frontier_cap
    return EdgeBackend(name, description, factory(**opts), check_rep)
