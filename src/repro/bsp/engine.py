"""BSP superstep engine.

One *machine* = one lane of the ``machines`` axis.  The same superstep body
runs under

* ``jax.vmap(..., axis_name="machines")`` — single-device simulation (CPU
  tests, benchmarking), or
* ``jax.shard_map`` over a ``machines`` mesh axis — real multi-device runs
  (the multi-pod path; collectives become ICI traffic).

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


def make_step(superstep: Callable, static, *, mesh: Mesh | None = None,
              check_rep: bool = True):
    """Compile one BSP superstep: state -> (state, (p,) active counts).

    The returned callable also offers ``.lower(state)``.
    ``check_rep=False`` disables shard_map's replication check — required
    when the superstep body contains ops without a replication rule
    (``pallas_call``; the edge-kernel backends declare this).  On a mesh
    each call counts the superstep's ``bsp.exchange_bytes``.
    """
    if mesh is None:
        def vstep(state, static):
            _count_trace()
            return jax.vmap(superstep, axis_name=MACHINES)(state, static)

        return _bind_static(jax.jit(vstep), static)

    state_spec_of = lambda tree: jax.tree.map(lambda _: P(MACHINES), tree)
    superstep = _Exchanging(superstep)

    def step(state, static):
        _count_trace()

        def inner(st, sa):
            st = jax.tree.map(lambda a: a[0], st)
            sa = jax.tree.map(lambda a: a[0], sa)
            new_state, active = superstep(st, sa)
            return (jax.tree.map(lambda a: jnp.asarray(a)[None], new_state),
                    jnp.asarray(active)[None])
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(state_spec_of(state), state_spec_of(static)),
            out_specs=(state_spec_of(state), P(MACHINES)),
            check_vma=check_rep)(state, static)

    dispatch = _bind_static(jax.jit(step), static)

    def run(state):
        out = dispatch(state)
        obs.count("bsp.exchange_bytes", superstep.nbytes)
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
    """Global ``‖new − old‖∞`` over every state leaf (cast to float32).

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

    After convergence every remaining chunk's while_loop exits on its
    first predicate check, so converged tail steps cost one condition
    evaluation instead of a superstep.  Active counts accumulate into an
    on-device ``(chunk, p)`` buffer per chunk; the single host sync at
    the end trims them to the steps actually run.

    The compiled computation is cached on the returned callable (one
    trace per distinct chunk count), so repeat runs — benchmark loops,
    dynamic-epoch hand-offs — pay dispatch, not retracing; it also
    offers ``.lower(state, num_steps)``.  The convenience wrapper
    :func:`run_bsp_fused` rebuilds it per call, like :func:`run_bsp`
    rebuilds its step.
    """
    chunk = max(1, int(chunk))
    body = jax.vmap(superstep, axis_name=MACHINES)

    def chunk_limits(state, num_steps):
        num_chunks = -(-num_steps // chunk)
        limits = np.full(num_chunks, chunk, dtype=np.int32)
        limits[-1] = num_steps - chunk * (num_chunks - 1)
        # from the host: on a mesh it goes to every device from there
        return state, limits

    def chunk_body(step_fn, done_of, new_buf, carry, limit):
        """One scan step: while_loop over ≤ chunk supersteps, gated."""
        st0, done0 = carry
        buf0 = new_buf()

        def cond(c):
            _, _, t, dn = c
            return (t < limit) & jnp.logical_not(dn)

        def step_once(c):
            st, buf, t, _ = c
            new_st, act = step_fn(st)
            buf = jax.lax.dynamic_update_index_in_dim(buf, act, t, 0)
            return (new_st, buf, t + 1,
                    done_of(st, new_st, act))

        st, buf, t, done = jax.lax.while_loop(
            cond, step_once, (st0, buf0, jnp.int32(0), done0))
        return (st, done), (buf, t)

    if mesh is None:
        def done_of(st, new_st, act):
            if tol is not None:
                return _state_residual(st, new_st) <= tol
            return act.sum() == jnp.zeros((), act.dtype)

        @jax.jit
        def fused(state, limits, static):
            _count_trace()
            p = _num_machines(state)
            act_dt = jax.eval_shape(body, state, static)[1].dtype
            run = functools.partial(
                chunk_body, lambda st: body(st, static), done_of,
                lambda: jnp.zeros((chunk, p), act_dt))
            (st, _), (bufs, ts) = jax.lax.scan(
                run, (state, jnp.zeros((), bool)), limits)
            return st, bufs, ts

        dispatch = _bind_static(fused, static, chunk_limits)

        def run(state, num_steps: int):
            p = _num_machines(state)
            if num_steps <= 0:
                return state, np.zeros((0, p))
            state, bufs, ts = dispatch(state, num_steps)
            steps = int(to_host(ts).sum())
            actives = to_host(bufs).reshape(-1, p)[:steps]
            return state, actives

        run.lower = dispatch.lower
        return run

    # shard_map: the fused loop runs rank-reduced per device; the gate
    # reduces with a collective so every device agrees on the predicate
    state_spec_of = lambda tree: jax.tree.map(lambda _: P(MACHINES), tree)
    superstep = _Exchanging(superstep)

    def sharded(state_b, limits, static_b):
        st = jax.tree.map(lambda a: a[0], state_b)
        sa = jax.tree.map(lambda a: a[0], static_b)
        act_dt = jax.eval_shape(lambda s: superstep(s, sa)[1], st).dtype

        def done_of(old, new, act):
            if tol is not None:
                res = jax.lax.pmax(_state_residual(old, new), MACHINES)
                return res <= tol
            tot = jax.lax.psum(jnp.asarray(act), MACHINES)
            return tot == jnp.zeros((), tot.dtype)

        def step_fn(st):
            new_st, act = superstep(st, sa)
            return new_st, jnp.asarray(act)

        # each device's actives buffer differs, so its initial value
        # must carry the 'varying over machines' type the loop body gives it
        new_buf = lambda: jax.lax.pcast(jnp.zeros((chunk,), act_dt),
                                        (MACHINES,), to="varying")
        run = functools.partial(chunk_body, step_fn, done_of, new_buf)
        (st, _), (bufs, ts) = jax.lax.scan(
            run, (st, jnp.zeros((), bool)), limits)
        return (jax.tree.map(lambda a: jnp.asarray(a)[None], st),
                jnp.asarray(bufs)[None], jnp.asarray(ts)[None])

    @jax.jit
    def fused(state, limits, static):
        _count_trace()
        return jax.shard_map(
            sharded, mesh=mesh,
            in_specs=(state_spec_of(state), P(), state_spec_of(static)),
            out_specs=(state_spec_of(state), P(MACHINES), P(MACHINES)),
            check_vma=check_rep)(state, limits, static)

    dispatch = _bind_static(fused, static, chunk_limits)

    def run(state, num_steps: int):
        p = _num_machines(state)
        if num_steps <= 0:
            return state, np.zeros((0, p))
        state, bufs, ts = dispatch(state, num_steps)
        steps = int(to_host(ts)[0].sum())
        obs.count("bsp.exchange_bytes", steps * superstep.nbytes)
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
