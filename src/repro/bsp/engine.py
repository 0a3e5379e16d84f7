"""BSP superstep engine.

One *machine* = one lane of the ``machines`` axis.  :func:`_on_machines`
maps a per-machine function over the machine-stacked arrays, the one
place that decides how machines meet devices: with no mesh the machines
are the lanes of ``jax.vmap(..., axis_name="machines")`` on one device;
on a ``machines`` mesh each device runs its own machine under
``jax.shard_map``, and the collectives become ICI traffic.

The replica exchange is the only cross-machine communication: a psum (or
pmin/pmax) over an (R+1,)-sized buffer — fixed shape, proportional to the
partition's replication, which is exactly the quantity the paper's TC comm
term charges for.  Each machine fills and reads the buffer by gathers
through its replica table (``rep_slot`` and ``slot_vertex``).

Superstep contract: ``superstep(state, static) -> (state, active_count)``
with per-machine (rank-reduced) arrays, using ``exchange`` for sync.

Two runners iterate that contract:

* :func:`run_bsp` — one jitted dispatch *per superstep* with a host sync
  in between (``np.asarray`` on the active counts).  Bit-exact oracle.
* :func:`run_bsp_fused` — the whole iteration fused on device:
  ``lax.scan`` over chunks of supersteps, each chunk an inner
  ``lax.while_loop`` gated on convergence (global active count == 0, or
  an on-device residual ``‖x_{t+1}−x_t‖∞ ≤ tol``), actives accumulated
  on device.  One dispatch and one host sync for the entire run — on
  dispatch-bound shards (small per-machine edge sets) this is where the
  superstep wall clock actually goes.

On the host every stepwise superstep is a ``bsp.superstep`` span
(``repro.obs``); every device→host read goes through :func:`to_host` and
every upload of the app path through :func:`to_device`, so the recorder
counts them.  On a mesh an upload goes from the host straight to the
devices (:func:`placed_on`), and both runners count the replica table
each superstep all-reduces (``bsp.exchange_bytes``).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs


MACHINES = "machines"


#: where :func:`to_device` puts an upload: JAX's default device (None),
#: or a machine-stacked sharding while a call on a mesh prepares
_placement = contextvars.ContextVar("bsp_placement", default=None)
#: while a mesh runner traces its superstep: the list each
#: :func:`exchange` adds its table's bytes per machine to
_exchanged = contextvars.ContextVar("bsp_exchanged", default=None)


def _extreme(dtype, sign: int):
    """Dtype-safe stand-in for ±∞: the most extreme representable value.

    Floats keep the true infinities; integer dtypes get ``iinfo`` max/min
    (``jnp.full(..., jnp.inf, dtype=int32)`` silently wraps — the replica
    exchange must stay correct for integer-valued states).
    """
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return dt.type(info.max if sign > 0 else info.min)
    return dt.type(jnp.inf if sign > 0 else -jnp.inf)


def exchange(partial: jnp.ndarray, replicas, r_pad: int,
             mode: str = "sum") -> jnp.ndarray:
    """Synchronize replicated-vertex values across machines.

    partial: (Vmax,) this machine's local value per local vertex.
    replicas: ``(rep_slot, slot_vertex)``: each local vertex's slot in
    the (r_pad + 1,) replica table, -1 if the vertex lives on this
    machine alone, and each slot's local vertex, -1 where this machine
    holds none.  The table is read by gathers both ways, so the
    exchange lowers to no scatter.
    Returns (Vmax,) with replicated entries replaced by the cross-machine
    combination (sum / min / max); non-replicated entries pass through.
    """
    rep_slot, slot_vertex = replicas
    reduce, identity = {
        "sum": (jax.lax.psum, partial.dtype.type(0)),
        "min": (jax.lax.pmin, _extreme(partial.dtype, +1)),
        "max": (jax.lax.pmax, _extreme(partial.dtype, -1)),
    }.get(mode, (None, None))
    if reduce is None:
        raise ValueError(mode)
    with jax.named_scope("bsp.exchange"):
        buf = jnp.where(slot_vertex >= 0,
                        partial[jnp.maximum(slot_vertex, 0)], identity)
        if (tally := _exchanged.get()) is not None:
            tally.append(buf.size * buf.dtype.itemsize)
        tot = reduce(buf, MACHINES)
        slot = jnp.where(rep_slot >= 0, rep_slot, r_pad)
        return jnp.where(rep_slot >= 0, tot[slot], partial)


@contextlib.contextmanager
def placed_on(mesh: Mesh | None):
    """Inside the block, :func:`to_device` puts row ``i`` of each
    machine-stacked array on device ``i`` of ``mesh``, straight from the
    host; with no mesh it uploads to JAX's default device."""
    token = _placement.set(
        None if mesh is None else NamedSharding(mesh, P(MACHINES)))
    try:
        yield
    finally:
        _placement.reset(token)


def to_device(x) -> jax.Array:
    """Host→device upload of one array, counted in ``bsp.h2d_bytes``."""
    x = np.asarray(x)
    obs.count("bsp.h2d_bytes", x.nbytes)
    sharding = _placement.get()
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


def to_host(x) -> np.ndarray:
    """Device→host read: waits for the device, so it is a ``bsp.sync``
    span and one ``bsp.host_syncs``."""
    with obs.span("bsp.sync"):
        obs.count("bsp.host_syncs")
        return np.asarray(x)


def _count_trace() -> None:
    # Python in a jitted function runs only while JAX traces it
    obs.count("bsp.retraces")


def _bind_static(jitted, static, make_args=lambda *a: a):
    """``run(*args) = jitted(*make_args(*args), static)``, with a matching
    ``run.lower``.

    ``static`` (the partitioned graph) always enters the program as an
    argument: closed over, it would be baked into the executable as
    constants — compile time growing with the graph, hundreds of MB per
    executable, and a persistent-cache key that changes with the data.
    """
    def run(*args):
        return jitted(*make_args(*args), static)

    run.lower = lambda *args: jitted.lower(*make_args(*args), static)
    return run


class _Exchanging:
    """A superstep that notes, each time JAX traces it, the bytes its
    exchanges all-reduce per machine (``nbytes``): what one superstep
    sends across chips on a mesh."""

    def __init__(self, superstep: Callable):
        self.superstep, self.nbytes = superstep, 0

    def __call__(self, state, static):
        tally = []
        token = _exchanged.set(tally)
        try:
            out = self.superstep(state, static)
        finally:
            _exchanged.reset(token)
        self.nbytes = sum(tally)
        return out


def _on_machines(superstep: Callable, mesh: Mesh | None, check_rep: bool,
                 body: Callable = lambda step, st, sa: step(st, sa)):
    """Map ``body(superstep, state, static, *shared)``, written for one
    machine, over machine-stacked ``state`` and ``static``; ``shared``
    reaches every machine whole, and every output comes back stacked.

    With no mesh the machines are the lanes of ``jax.vmap``; on a mesh
    each device runs its own machine under ``jax.shard_map``
    (``check_rep=False`` drops its replication check, which ops with no
    replication rule, such as ``pallas_call``, need).  Returns
    ``(mapped, count_exchanges)``: on a mesh ``count_exchanges(steps)``
    counts the bytes ``steps`` supersteps' exchanges all-reduce per
    machine (``bsp.exchange_bytes``); under vmap nothing crosses a chip
    and it counts nothing.
    """
    if mesh is None:
        def mapped(state, static, *shared):
            return jax.vmap(lambda st, sa: body(superstep, st, sa, *shared),
                            axis_name=MACHINES)(state, static)

        return mapped, lambda steps: None

    superstep = _Exchanging(superstep)

    def per_device(state, static, *shared):
        st, sa = jax.tree.map(lambda a: a[0], (state, static))
        out = body(superstep, st, sa, *shared)
        return jax.tree.map(lambda a: jnp.asarray(a)[None], out)

    def mapped(state, static, *shared):
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(MACHINES), P(MACHINES)) + (P(),) * len(shared),
            out_specs=P(MACHINES), check_vma=check_rep)(
                state, static, *shared)

    def count_exchanges(steps: int) -> None:
        obs.count("bsp.exchange_bytes", steps * superstep.nbytes)

    return mapped, count_exchanges


def make_step(superstep: Callable, static, *, mesh: Mesh | None = None,
              check_rep: bool = True):
    """Compile one BSP superstep: state -> (state, (p,) active counts).

    The returned callable also offers ``.lower(state)``.  ``check_rep``
    goes to :func:`_on_machines`; on a mesh each call counts the
    superstep's ``bsp.exchange_bytes``.
    """
    mapped, count_exchanges = _on_machines(superstep, mesh, check_rep)

    def step(state, static):
        _count_trace()
        return mapped(state, static)

    # under vmap the module stays jit_vstep (on a mesh, jit_step): the
    # program's text, and so a persistent compile cache's key, stay put
    step.__name__ = "step" if mesh is not None else "vstep"
    dispatch = _bind_static(jax.jit(step), static)

    def run(state):
        out = dispatch(state)
        count_exchanges(1)
        return out

    run.lower = dispatch.lower
    return run


def _num_machines(state) -> int:
    """p from any state tree: every leaf is machine-stacked on axis 0."""
    return len(jax.tree.leaves(state)[0])


def run_bsp(superstep: Callable, state, static, num_steps: int,
            *, mesh: Mesh | None = None, check_rep: bool = True):
    """Iterate the superstep; returns (final_state, (steps, p) actives).

    One jitted dispatch and one device→host sync per superstep — the
    bit-exact oracle :func:`run_bsp_fused` is pinned against.
    """
    step = make_step(superstep, static, mesh=mesh, check_rep=check_rep)
    actives = []
    for i in range(num_steps):
        with obs.span("bsp.superstep", step_num=i):
            state, act = step(state)
            actives.append(to_host(act))
    if not actives:
        # zero steps still contract to a (0, p) actives array
        return state, np.zeros((0, _num_machines(state)))
    return state, np.stack(actives)


def _state_residual(old, new) -> jnp.ndarray:
    """``‖new − old‖∞`` over every state leaf (cast to float32).

    The on-device convergence measure for contraction-map apps
    (PageRank): counter/mask leaves would keep it ≥ 1, which is why the
    monotone apps gate on the active count instead.
    """
    diffs = jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(b.astype(jnp.float32)
                                     - a.astype(jnp.float32))), old, new)
    return functools.reduce(jnp.maximum, jax.tree.leaves(diffs))


def make_fused_runner(superstep: Callable, static, *,
                      mesh: Mesh | None = None, check_rep: bool = True,
                      chunk: int = 8, tol: float | None = None):
    """Build a reusable fused runner: ``run(state, num_steps)``.

    The returned callable executes the whole BSP iteration as ONE jitted
    dispatch — ``lax.scan`` over ``ceil(num_steps / chunk)`` chunks of
    supersteps, each chunk an inner ``lax.while_loop`` that steps until
    the chunk is exhausted *or* the run has converged:

    * ``tol is None`` — converged when the global active count hits 0
      (the monotone apps: BFS/SSSP/CC activity is exactly the changed
      set, and 0 is absorbing);
    * ``tol`` set — converged when the on-device residual
      ``‖state_{t+1} − state_t‖∞ ≤ tol`` (PageRank power iteration).

    The loop is written once, per machine, and mapped by
    :func:`_on_machines`.  Its gate reduces over the machines with a
    collective, so every machine takes the same branch; under vmap the
    collective's result is unbatched, and the while loop stays one
    loop.  After convergence every remaining chunk's while_loop exits on
    its first predicate check, so converged tail steps cost one
    condition evaluation instead of a superstep.  Each machine's active
    counts accumulate into an on-device ``(chunk,)`` buffer per chunk;
    the single host sync at the end trims them to the steps actually
    run.

    The compiled computation is cached on the returned callable (one
    trace per distinct chunk count), so repeat runs — benchmark loops,
    dynamic-epoch hand-offs — pay dispatch, not retracing; it also
    offers ``.lower(state, num_steps)``.  The convenience wrapper
    :func:`run_bsp_fused` rebuilds it per call, like :func:`run_bsp`
    rebuilds its step.
    """
    chunk = max(1, int(chunk))

    def converged(old, new, act):
        if tol is not None:
            return jax.lax.pmax(_state_residual(old, new), MACHINES) <= tol
        total = jax.lax.psum(jnp.asarray(act), MACHINES)
        return total == jnp.zeros((), total.dtype)

    def loop(step, state, static, limits):
        act_dt = jax.eval_shape(lambda s: step(s, static)[1], state).dtype

        def chunk_body(carry, limit):
            """One scan step: while_loop over ≤ chunk supersteps, gated."""
            st0, done0 = carry
            # each machine's actives differ, so the buffer's initial value
            # carries the 'varying over machines' type the loop gives it
            buf0 = jax.lax.pcast(jnp.zeros((chunk,), act_dt), (MACHINES,),
                                 to="varying")

            def cond(c):
                _, _, t, done = c
                return (t < limit) & jnp.logical_not(done)

            def step_once(c):
                st, buf, t, _ = c
                new_st, act = step(st, static)
                # a select, not a dynamic_update_slice: vmap batches
                # that into a scatter
                buf = jnp.where(jnp.arange(chunk) == t, act, buf)
                return new_st, buf, t + 1, converged(st, new_st, act)

            st, buf, t, done = jax.lax.while_loop(
                cond, step_once, (st0, buf0, jnp.int32(0), done0))
            return (st, done), (buf, t)

        (state, _), (bufs, ts) = jax.lax.scan(
            chunk_body, (state, jnp.zeros((), bool)), limits)
        return state, bufs, ts

    mapped, count_exchanges = _on_machines(superstep, mesh, check_rep, loop)

    @jax.jit
    def fused(state, limits, static):
        _count_trace()
        return mapped(state, static, limits)

    def chunk_limits(state, num_steps):
        num_chunks = -(-num_steps // chunk)
        limits = np.full(num_chunks, chunk, dtype=np.int32)
        limits[-1] = num_steps - chunk * (num_chunks - 1)
        # from the host: on a mesh it goes to every device from there
        return state, limits

    dispatch = _bind_static(fused, static, chunk_limits)

    def run(state, num_steps: int):
        p = _num_machines(state)
        if num_steps <= 0:
            return state, np.zeros((0, p))
        state, bufs, ts = dispatch(state, num_steps)
        steps = int(to_host(ts)[0].sum())
        count_exchanges(steps)
        # bufs: (p, num_chunks, chunk) -> (num_chunks*chunk, p), trimmed
        actives = to_host(bufs).transpose(1, 2, 0).reshape(-1, p)[:steps]
        return state, actives

    run.lower = dispatch.lower
    return run


def run_bsp_fused(superstep: Callable, state, static, num_steps: int,
                  *, mesh: Mesh | None = None, check_rep: bool = True,
                  chunk: int = 8, tol: float | None = None):
    """One fused on-device BSP run (see :func:`make_fused_runner`).

    Returns ``(final_state, (steps_run, p) actives)``.  With ``tol=None``
    the final state is bit-identical to :func:`run_bsp` after
    ``num_steps`` supersteps for min/max-semiring apps (converged
    supersteps are state fixpoints) and the actives are the stepwise
    prefix (the stepwise tail is all zeros).
    """
    runner = make_fused_runner(superstep, static, mesh=mesh,
                               check_rep=check_rep, chunk=chunk, tol=tol)
    return runner(state, num_steps)
