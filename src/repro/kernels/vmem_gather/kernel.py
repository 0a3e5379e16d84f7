"""Gather from a VMEM-resident vector: ``out = x[ids]``, a Pallas TPU
kernel.

XLA:TPU lowers ``x[ids]`` to a loop that spends about 14 cycles on each
gathered element, whatever the table's size.  One BSP machine's vertex
vector is small (a few MB at most), so this kernel holds all of it in
VMEM, as a ``(rows, 128)`` table whose block ``index_map`` is constant
along the message axis: it is fetched once per machine.  The indices
stream past it, ``TILES`` output tiles of ``(8, 128)`` a grid step, and
each tile is gathered in two steps:

* **row load**: message ``(s, l)`` of the tile reads its table row
  ``ids >> 7`` from SMEM and loads that row broadcast over the 8
  sublanes (a stride-0 load); a select keeps it in sublane ``s``;
* **lane pick**: once the 8 rows of one lane ``l`` are in place, one
  ``take_along_axis`` over the lanes (``tpu.dynamic_gather``) with
  every message's ``ids & 127`` picks each sublane's value, and a
  select keeps lane ``l`` of it.

Both steps only move bits, so the output is bitwise ``x[ids]`` for any
32-bit dtype.  The row ids go to SMEM, where the scalar unit reads them
to address the loads; the ids themselves go to VMEM, where the vector
unit reads their lanes.  XLA makes the row ids with one shift.

The scalar unit bounds the kernel: an address add, an SMEM load and a
VMEM address a message, on two scalar slots a bundle.  So the row ids
come as a flat 1-D SMEM block, where a row id's address is one add from
its tile's, and a tile's lanes go ``UNROLL`` at a time through a loop:
an unrolled tile would save a tenth of the bundles, but every app call
lowers its superstep afresh, and lowering an unrolled tile takes about
half a second of host time.

A flat block is refused where ``vmap`` batches a ``pallas_call``, so the
kernel takes the machines as a grid axis of its own (``custom_vmap``):
under ``vmap`` one call gathers every machine's messages from its own
table; alone, as under ``shard_map``, it is one machine.

Layouts:
  x:   (vmax,)  32-bit, padded inside to (rows, 128), rows a multiple of 8
  ids: (n,)     int32 in [0, vmax), n a multiple of 1024
  out: (n,)     x's dtype
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes and sublanes of a 32-bit vreg: one output tile is 8 × 128
LANES, SUBLANES = 128, 8
TILE = LANES * SUBLANES
#: output tiles per grid step
TILES = 16
#: lanes of a tile gathered per loop step, unrolled
UNROLL = 8


def table_rows(vmax: int) -> int:
    """Rows of the padded ``(rows, 128)`` table that holds ``vmax``
    values."""
    return -(-vmax // TILE) * SUBLANES


def table_bytes(vmax: int) -> int:
    """VMEM bytes of the padded 32-bit table."""
    return table_rows(vmax) * LANES * 4


def _kernel(nt, rows_ref, ids_ref, tab_ref, out_ref):
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    # the machine's last block may hold fewer tiles than TILES
    todo = jnp.minimum(TILES, nt - pl.program_id(1) * TILES)

    def tile(t, carry):
        picks = ids_ref[0, t] & (LANES - 1)

        def lanes(k, acc):
            for u in range(UNROLL):
                l = k * UNROLL + u
                rows = jnp.zeros((SUBLANES, LANES), tab_ref.dtype)
                for s in range(SUBLANES):
                    r = rows_ref[t * TILE + s * LANES + l]
                    rows = jnp.where(
                        sub == s,
                        jnp.broadcast_to(tab_ref[0, pl.ds(r, 1), :],
                                         rows.shape), rows)
                acc = jnp.where(lane == l,
                                jnp.take_along_axis(rows, picks, axis=1), acc)
            return acc

        out_ref[0, t] = jax.lax.fori_loop(
            0, LANES // UNROLL, lanes,
            jnp.zeros((SUBLANES, LANES), tab_ref.dtype))
        return carry

    jax.lax.fori_loop(0, todo, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather(x, ids, interpret: bool):
    """``x[m][ids[m]]`` for every machine ``m``: ``x`` (p, vmax),
    ``ids`` (p, n)."""
    (p, vmax), (_, n) = x.shape, ids.shape
    assert x.dtype.itemsize == 4, x.dtype
    assert n % TILE == 0, n
    rows = table_rows(vmax)
    tab = jnp.pad(x, ((0, 0), (0, rows * LANES - vmax))).reshape(
        p, rows, LANES)
    nt = n // TILE
    nb = pl.cdiv(nt, TILES)
    # the row ids, one flat SMEM block of TILES tiles per grid step (a
    # 1-D block, so that a row id's address is one add from the
    # tile's), each machine's padded to whole blocks
    flat = jnp.pad(ids >> 7, ((0, 0), (0, nb * TILES * TILE - n)))
    block = (1, TILES, SUBLANES, LANES)
    stream = lambda m, j: (m, j, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, nt),
        # varying over the mesh axes its operands vary over, so that
        # shard_map's replication check accepts it
        out_shape=jax.ShapeDtypeStruct(
            (p, nt, SUBLANES, LANES), x.dtype,
            vma=jax.typeof(x).vma | jax.typeof(ids).vma),
        grid=(p, nb),
        in_specs=[
            pl.BlockSpec((TILES * TILE,), lambda m, j: (m * nb + j,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(block, stream),
            pl.BlockSpec((1, rows, LANES), lambda m, j: (m, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec(block, stream),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=table_bytes(vmax) + (8 << 20)),
        interpret=interpret,
    )(flat.reshape(-1), ids.reshape(p, nt, SUBLANES, LANES), tab)
    return out.reshape(p, n)


def _batched(interpret: bool):
    """The gather of one machine; under ``vmap`` one call for all of
    them, the machines an axis of the grid."""
    @jax.custom_batching.custom_vmap
    def gather(x, ids):
        return _gather(x[None], ids[None], interpret)[0]

    @gather.def_vmap
    def _(axis_size, in_batched, x, ids):
        x, ids = (a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                  for a, b in zip((x, ids), in_batched))
        return _gather(x, ids, interpret), True

    return gather


_GATHERS = {False: _batched(False), True: _batched(True)}


def vmem_gather(x: jnp.ndarray, ids: jnp.ndarray, *,
                interpret: bool | None = None) -> jnp.ndarray:
    """``x[ids]`` for a 1-D 32-bit ``x`` and int32 ``ids`` in range,
    ``len(ids)`` a multiple of :data:`TILE`.

    ``interpret=None`` runs the Pallas interpreter only when JAX's
    default backend is the CPU; on a TPU the kernel is compiled.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _GATHERS[bool(interpret)](x, ids)
