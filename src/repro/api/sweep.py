"""Batched sweep runner: many independent runs, ONE compiled call.

Bench grids sweep delay models, seeds, server step sizes (gamma) and
sparsity levels over the *same spec shape* -- identical dataset, protocol,
round budget.  Running them as separate sessions pays one compile + one
dispatch chain per cell.  :func:`run_sweep` batches every scan-capable run
(the lockstep protocols ``sync`` / ``cocoa`` / ``cocoa_plus`` AND ``lag``)
into a single compiled computation built on the traced run bodies of
:mod:`repro.core.executor` (:func:`~repro.core.executor.lockstep_run_traced`
/ :func:`~repro.core.executor.lag_run_traced`):

* ``batch="vmap"`` (default) -- variants are vmapped: one XLA computation
  whose inner ops are batched across the sweep axis.  Fastest, but batched
  reductions reorder floats, so trajectories are NOT bit-identical to
  single-run executions (they are still deterministic for a fixed sweep).
* ``batch="map"``  -- variants run through ``lax.map``: still one compile
  and one dispatch for the whole sweep, but each variant keeps the
  unbatched op shapes -- bit-identical to ``Session(executor="scan")`` (and
  therefore to the event engine), pinned by tests/test_sweep.py.

The *delay axis rides along for free*: lockstep timing is host-side
accounting (gamma and the delay model never move the compiled computation),
and the lag executor's in-graph event queue consumes pre-sampled duration
streams and link factors as traced operands -- so a whole
delay x seed x gamma grid of one protocol is ONE compiled call.  Different
grid shapes reuse one compile: the cell axis AND the static eval-boundary
axis are padded to power-of-two buckets (trailing duplicates, the
``engine._eval_bucketed`` trick), so repeated calls with different
(n_delays, n_seeds, n_gammas) grids or eval cadences retrace at most
log-many times per axis.

Sharding (``shard=``): the batched axes can be partitioned over the local
device mesh (:func:`repro.launch.mesh.make_mesh` + ``shard_map``):

* ``"auto"`` (default) -- shard the cell axis over all local devices when
  more than one exists; degrade to the single-device path otherwise (the
  1-device behavior is bit-identical to ``shard="none"``).
* ``"none"``  -- force the unsharded vmap/map path.
* ``"cells"`` -- partition the sweep-cell axis: cells are independent, so
  there is no cross-shard communication at all and per-cell results are
  bit-identical to the unsharded path (each shard runs the same per-cell
  ops on its block).
* ``"workers"`` -- lockstep only: partition the worker axis of the
  per-round inner computation (each shard solves its local subproblems,
  one ``psum`` per round reduces the aggregate; see
  :func:`repro.core.executor.lockstep_run_traced_sharded`).  For large-K
  cells; deterministic but NOT bit-identical (the reduction re-associates,
  like ``batch="vmap"``).

Timing/byte accounting stays host-side for lockstep
(:func:`repro.core.executor.lockstep_accounts` -- per (delay, seed), since
gamma does not move the simulated clock) and is replayed on the host from
each lag cell's per-round pop order and byte counts
(:func:`repro.core.executor.lag_accounts`); the deferred gap certificates
of ALL variants evaluate in one bucketed ``lax.map`` dispatch.

The group-family protocols (data-dependent arrival control flow) cannot
batch this way; sweep them with one :class:`repro.api.Session` per cell.
:func:`run_lockstep_sweep` remains as the lockstep-only compat wrapper.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import compress as compress_lib
from repro.core import engine, executor, objectives, tracing
from repro.core.acpd import MethodConfig, RunRecord, RunResult
from repro.core.simulate import ClusterModel
from repro.launch import mesh as mesh_lib

SHARD_MODES = ("auto", "none", "cells", "workers")


@dataclasses.dataclass(frozen=True)
class SweepVariant:
    """One cell of the sweep: the varied parameters plus its RunResult.

    ``rounds`` carries the cell's full per-round host accounting
    (:class:`repro.core.executor.RoundAccount` tuples) so a consumer can
    replay the cell's complete Session event stream -- the serve layer's
    stream demultiplexer (:mod:`repro.serve.streams`) depends on it.  It is
    set by :func:`run_sweep_cells` (and the lag path generally); the
    lockstep cross-product sweep leaves it ``None`` -- that path dedups
    trajectories across the delay axis and only needs eval-boundary
    records.
    """

    seed: int
    gamma: float
    result: RunResult
    delay: str = "constant"  # the cell's delay-model registry entry
    rounds: tuple | None = None  # per-round RoundAccounts (cell sweeps)


@dataclasses.dataclass(frozen=True)
class SweepCellSpec:
    """One EXPLICIT sweep cell: its full per-cell parameterization.

    :func:`run_sweep` generates the cross product of its axes internally;
    :func:`run_sweep_cells` instead takes a flat list of these -- the serve
    layer's coalescer (:mod:`repro.serve.coalesce`) builds one per tenant
    request, so heterogeneous tenant grids batch into one compiled call
    with no cross-product waste.  ``gamma=None`` keeps the method's own
    gamma; ``sigma_prime=None`` resolves the protocol default for the
    cell's gamma (exactly what a solo run would do).  The ``cluster`` is
    fully per-cell: lockstep timing is host-side accounting, and the lag
    executor consumes pre-sampled per-cell delay streams as traced
    operands, so cells of different delay models / latencies / bandwidths
    share one computation.
    """

    cluster: ClusterModel
    seed: int
    gamma: float | None = None
    sigma_prime: float | None = None


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """A resolved ``shard=`` request: which axis, over how many devices."""

    mode: str  # "none" | "cells" | "workers"
    n_shards: int  # 1 iff mode == "none"


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def resolve_shard(shard: str, *, protocol: str, num_workers: int,
                  n_devices: int | None = None) -> ShardPlan:
    """Resolve a ``shard=`` request against this host's devices.

    ``auto`` picks ``cells`` whenever more than one device exists (cells are
    embarrassingly parallel and stay bit-identical) and degrades to ``none``
    on a single device.  ``cells`` degrades to ``none`` on one device too.
    ``workers`` needs a lockstep protocol (the lag event queue is
    sequential in arrivals and cannot split its worker axis) and a worker
    count divisible by the shard count; it degrades to ``none`` when no
    usable split exists.  Mesh sizes are the largest power of two that fits
    so cell-axis pow2 padding always divides evenly.
    """
    if shard not in SHARD_MODES:
        raise ValueError(f"unknown shard mode {shard!r}; expected one of "
                         f"{SHARD_MODES}")
    if n_devices is None:
        n_devices = len(jax.devices())
    pow2 = _pow2_floor(n_devices)
    if shard == "workers":
        if protocol not in executor.LOCKSTEP_PROTOCOLS:
            raise ValueError(
                f"shard='workers' partitions the lockstep worker axis; "
                f"protocol {protocol!r} cannot (lag's in-graph event queue "
                f"is sequential in arrival order). Use shard='cells'.")
        s = pow2
        while s > 1 and num_workers % s:
            s //= 2
        return ShardPlan("workers", s) if s > 1 else ShardPlan("none", 1)
    if shard == "none" or pow2 == 1:
        return ShardPlan("none", 1)
    return ShardPlan("cells", pow2)  # "auto" and "cells"


def sweep_supported(method: MethodConfig,
                    cluster: ClusterModel) -> tuple[bool, str]:
    """Can (method, cluster) batch into :func:`run_sweep`?  (ok, why-not).

    Strictly narrower than ``executor.scan_supported``: ``partial_work``
    scans solo (per-chunk carries are per-run state) but does not batch
    into shared sweep cells."""
    if method.protocol not in executor.SWEEP_PROTOCOLS:
        return False, (
            f"protocol {method.protocol!r} does not batch into shared sweep "
            f"cells (sweep-batchable: {executor.SWEEP_PROTOCOLS}); run it "
            f"one Session per cell")
    return executor.scan_supported(method, cluster)


# ---------------------------------------------------------------------------
# The compiled sweep computations.
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("loss", "num_steps", "solver", "length",
                          "batch", "n_shards"))
def _sweep_scan(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, eval_idx, *,
                loss, num_steps, solver, length, batch, n_shards):
    """All lockstep sweep variants in one compiled computation.

    ``eval_idx`` (a traced int32 vector, pow2-padded so eval cadences share
    compiles) gathers the eval-boundary snapshots in-graph, so only
    O(cells x boundaries) state leaves the device instead of the full
    O(cells x rounds) trail.  ``n_shards > 1`` partitions the cell axis over
    the local mesh via ``shard_map`` -- cells are independent, so each shard
    runs the identical per-cell ops on its block (no collectives; per-cell
    results are bit-identical to the unsharded path) with donated carries
    inside its scan.
    """
    executor.STATS["sweep_traces"] += 1  # trace-time side effect
    run = partial(executor.lockstep_run_traced, loss=loss,
                  num_steps=num_steps, solver=solver, length=length)

    def one(key, X, y, norms_sq, lam, n, sp, g, idx):
        w, alpha, ws, alphas = run(key, X, y, norms_sq, lam, n, sp, g)
        return w, alpha, ws[idx], alphas[idx]

    def block(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, idx):
        if batch == "vmap":
            return jax.vmap(
                lambda key, sp, g: one(key, X, y, norms_sq, lam, n, sp, g,
                                       idx)
            )(keys, sigma_ps, gammas)
        return jax.lax.map(
            lambda a: one(a[0], X, y, norms_sq, lam, n, a[1], a[2], idx),
            (keys, sigma_ps, gammas))

    if n_shards == 1:
        return block(keys, X, y, norms_sq, lam, n, sigma_ps, gammas,
                     eval_idx)
    mesh = mesh_lib.make_sweep_mesh(n_shards, "cells")
    fn = jax.shard_map(block, mesh=mesh,
                   in_specs=(P("cells"), P(), P(), P(), P(), P(),
                             P("cells"), P("cells"), P()),
                   out_specs=(P("cells"),) * 4, check_vma=False)
    return fn(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, eval_idx)


@partial(jax.jit,
         static_argnames=("loss", "num_steps", "solver", "length",
                          "batch", "n_shards", "num_workers"))
def _sweep_scan_workers(keys, X, y, norms_sq, lam, n, sigma_ps, gammas,
                        eval_idx, *, loss, num_steps, solver, length, batch,
                        n_shards, num_workers):
    """Lockstep sweep with the WORKER axis sharded over the mesh.

    Every device sees every cell but only its block of the K workers; each
    round's aggregate is one cross-shard ``psum``
    (:func:`repro.core.executor.lockstep_run_traced_sharded`).  A perf mode
    for large-K cells -- deterministic, not bit-identical (the reduction
    re-associates).
    """
    executor.STATS["sweep_traces"] += 1  # trace-time side effect
    mesh = mesh_lib.make_sweep_mesh(n_shards, "workers")

    def block(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, idx):
        run = partial(executor.lockstep_run_traced_sharded, loss=loss,
                      num_steps=num_steps, solver=solver, length=length,
                      axis="workers", num_workers=num_workers)

        def one(key, sp, g):
            w, alpha, ws, alphas = run(key, X, y, norms_sq, lam, n, sp, g)
            return w, alpha, ws[idx], alphas[idx]

        if batch == "vmap":
            return jax.vmap(one)(keys, sigma_ps, gammas)
        return jax.lax.map(lambda a: one(*a), (keys, sigma_ps, gammas))

    fn = jax.shard_map(block, mesh=mesh,
                   in_specs=(P(), P("workers"), P("workers"), P("workers"),
                             P(), P(), P(), P(), P()),
                   out_specs=(P(), P(None, "workers"), P(),
                              P(None, None, "workers")),
                   check_vma=False)
    return fn(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, eval_idx)


@partial(jax.jit,
         static_argnames=("loss", "num_steps", "comp", "length", "lag_window",
                          "dense_reply_bytes", "batch", "n_shards"))
def _lag_sweep_scan(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, xi,
                    durations, needs, up_bytes, heartbeat_bytes, latencies,
                    bandwidths, link_factors, eval_idx, *, loss, num_steps,
                    comp, length, lag_window, dense_reply_bytes, batch,
                    n_shards):
    """All LAG sweep variants in one compiled computation.

    The per-cell operands carry the whole delay axis: pre-sampled duration
    streams (f64, one per (delay, seed)), per-worker link factors and
    latency/bandwidth scalars -- so cells of DIFFERENT delay models batch
    into the same computation.  Must be called under ``enable_x64`` (the
    in-graph event-queue timing is f64, like the single-run path).
    """
    executor.STATS["sweep_lag_traces"] += 1  # trace-time side effect

    def one(shared, cells, key, sp, g, dur, lat, bw, lf):
        (X, y, norms_sq, lam, n, xi, needs, up_bytes, heartbeat_bytes,
         idx) = shared
        state, ys = executor.lag_run_traced(
            key, X, y, norms_sq, lam, n, sp, g, xi, dur, needs, up_bytes,
            heartbeat_bytes, lat, bw, lf, loss=loss, num_steps=num_steps,
            comp=comp, length=length, lag_window=lag_window,
            dense_reply_bytes=dense_reply_bytes, cells=cells)
        ws, app_rows, order, reply_bytes, launch_bytes = ys
        return (state["w_server"], state["alpha"], state["alpha_applied"],
                ws[idx], app_rows[idx], state["init_bytes"], order,
                reply_bytes, launch_bytes)

    def block(keys, X, y, norms_sq, lam, n, sigma_ps, gammas, xi, durations,
              needs, up_bytes, heartbeat_bytes, latencies, bandwidths,
              link_factors, idx):
        shared = (X, y, norms_sq, lam, n, xi, needs, up_bytes,
                  heartbeat_bytes, idx)
        if batch == "vmap":  # the cells of this block, one vectorised run
            return jax.vmap(partial(one, shared, keys.shape[0]))(
                keys, sigma_ps, gammas, durations, latencies, bandwidths,
                link_factors)
        return jax.lax.map(lambda a: one(shared, 1, *a),
                           (keys, sigma_ps, gammas, durations, latencies,
                            bandwidths, link_factors))

    args = (keys, X, y, norms_sq, lam, n, sigma_ps, gammas, xi, durations,
            needs, up_bytes, heartbeat_bytes, latencies, bandwidths,
            link_factors, eval_idx)
    if n_shards == 1:
        return block(*args)
    mesh = mesh_lib.make_sweep_mesh(n_shards, "cells")
    cell = P("cells")
    fn = jax.shard_map(block, mesh=mesh,
                   in_specs=(cell, P(), P(), P(), P(), P(), cell, cell, P(),
                             cell, P(), P(), P(), cell, cell, cell, P()),
                   out_specs=(cell,) * 9, check_vma=False)
    return fn(*args)


# ---------------------------------------------------------------------------
# The sweep drivers.
# ---------------------------------------------------------------------------


def _delay_variants(cluster: ClusterModel, delays):
    """Normalize the delay axis to [(name, ClusterModel), ...].

    ``delays=None`` keeps the spec's own cluster (a pure seed/gamma sweep);
    entries may be registry names (default parameters) or ``(name, params)``
    pairs.
    """
    if delays is None:
        return [(cluster.delay_model, cluster)]
    out = []
    for entry in delays:
        if isinstance(entry, str):
            name, params = entry, None
        else:
            name, params = entry
        if params is None:
            params = (dict(cluster.delay_params)
                      if name == cluster.delay_model else {})
        out.append((name, dataclasses.replace(
            cluster, delay_model=name, delay_params=tuple(params.items()))))
    return out


def _padded_cells(cells, n_shards):
    """Pad the cell list to the pow2 bucket (>= shard count) by repeating
    the last cell; padded rows compute real (discarded) work, so grids of
    different shapes share one compile without poisoning any live cell."""
    V = len(cells)
    V_pad = max(engine._bucket_size(V), n_shards)
    return cells + [cells[-1]] * (V_pad - V)


def _padded_eval_idx(evals) -> tuple:
    """The static eval-boundary tuple, padded to its pow2 bucket (last
    index repeated) so sweeps differing only in eval cadence share compiles
    the same way the cell axis does; callers slice the duplicate snapshot
    rows off before evaluation."""
    if not evals:
        return ()
    pad = engine._bucket_size(len(evals)) - len(evals)
    return tuple(evals) + (evals[-1],) * pad


def run_sweep(
    problem: objectives.Problem,
    method: MethodConfig,
    cluster: ClusterModel,
    *,
    num_outer: int,
    seeds=(0,),
    gammas=None,
    delays=None,
    eval_every: int = 1,
    batch: str = "vmap",
    shard: str = "auto",
) -> list[SweepVariant]:
    """Run the cross product ``delays x seeds x gammas`` of a scan-capable
    method as one compiled computation; returns one :class:`SweepVariant`
    per cell (delay-major, then seed, then gamma).

    ``gammas=None`` keeps the method's own gamma; when a gamma variant is
    swept and ``method.sigma_prime`` is unset, each variant gets its
    protocol's safe default sigma' for THAT gamma (the same resolution a
    single run would do).  ``delays=None`` keeps the cluster's own delay
    model; otherwise entries are delay-registry names or ``(name, params)``
    pairs.  ``shard`` partitions the batched axes over the local device mesh
    (see the module docstring; ``"auto"`` degrades gracefully to the
    unsharded path on one device).

    Contract: under ``batch="map"`` with an unsharded or cells-sharded
    plan, every cell is bit-identical to the corresponding
    ``Session(executor="scan")`` run -- and therefore to the event engine
    (pinned by tests/test_sweep.py).
    """
    variants, cells, plan = _sweep_grid(
        problem, method, cluster, num_outer=num_outer, seeds=seeds,
        gammas=gammas, delays=delays, batch=batch, shard=shard)
    if method.protocol == "lag":
        return _lag_cells(problem, method, cells, num_outer=num_outer,
                          eval_every=eval_every, batch=batch, plan=plan)
    return _run_lockstep_sweep(problem, method, variants, cells,
                               num_outer=num_outer, eval_every=eval_every,
                               batch=batch, plan=plan)


def lower_sweep(problem, method, cluster, *, num_outer: int, seeds=(0,),
                gammas=None, delays=None, eval_every: int = 1,
                batch: str = "vmap", shard: str = "auto") -> jax.stages.Lowered:
    """The computation :func:`run_sweep` would dispatch for these arguments,
    lowered and not run: ``.compile().memory_analysis()`` sizes a grid for a
    device before running it."""
    variants, cells, plan = _sweep_grid(
        problem, method, cluster, num_outer=num_outer, seeds=seeds,
        gammas=gammas, delays=delays, batch=batch, shard=shard)
    if method.protocol == "lag":
        with jax.enable_x64(True):
            fn, args, kw, _, _ = _lag_call(problem, method, cells,
                                        num_outer=num_outer,
                                        eval_every=eval_every, batch=batch,
                                        plan=plan)
            return fn.lower(*args, **kw)
    block = cells[:len(cells) // len(variants)]  # see _run_lockstep_sweep
    fn, args, kw, _ = _lockstep_call(problem, method, block,
                                     num_outer=num_outer,
                                     eval_every=eval_every, batch=batch,
                                     plan=plan)
    return fn.lower(*args, **kw)


def _sweep_grid(problem, method, cluster, *, num_outer, seeds, gammas,
                delays, batch, shard):
    """Validate a :func:`run_sweep` request; returns ``(delay variants,
    cells delay-major then seed then gamma, shard plan)``."""
    if method.protocol not in executor.SWEEP_PROTOCOLS:
        raise ValueError(
            f"sweep batching needs a sweep-batchable (shared-cell "
            f"scan-capable) protocol {executor.SWEEP_PROTOCOLS}, got "
            f"{method.protocol!r}; run other protocols one Session per "
            f"cell")
    if batch not in ("vmap", "map"):
        raise ValueError(f"unknown batch mode {batch!r}; 'vmap' or 'map'")
    if num_outer <= 0:
        raise ValueError(f"num_outer must be >= 1, got {num_outer}")
    gammas = [method.gamma] if gammas is None else list(gammas)
    seeds = list(seeds)
    if not seeds or not gammas:
        raise ValueError(
            f"the sweep grid is empty: got {len(seeds)} seeds x "
            f"{len(gammas)} gammas (each axis needs at least one value)")
    variants = _delay_variants(cluster, delays)
    if not variants:
        raise ValueError("delays=() declares an empty delay axis; pass "
                         "None to keep the cluster's own delay model")
    plan = resolve_shard(shard, protocol=method.protocol,
                         num_workers=problem.X.shape[0])
    # The cell-level cores key duration streams by the (hashable)
    # ClusterModel itself, NOT the delay name: two entries of the same model
    # with different params must not share a stream.
    cells = [SweepCellSpec(cl, s, g, method.sigma_prime)
             for _, cl in variants for s in seeds for g in gammas]
    return variants, cells, plan


def _variant_records(rounds, evals, gap, gap_srv, p, dv, v):
    return [
        RunRecord(iteration=r + 1, sim_time=rounds[r].sim_time,
                  gap=float(gap[v, i]), gap_server=float(gap_srv[v, i]),
                  primal=float(p[v, i]), dual=float(dv[v, i]),
                  bytes_up=rounds[r].bytes_up,
                  bytes_down=rounds[r].bytes_down,
                  compute_time=rounds[r].compute_time,
                  comm_time=rounds[r].comm_time)
        for i, r in enumerate(evals)
    ]


def _eval_grid(ws_eval, alphas_eval, problem, V, S):
    """Every variant's certificates in one bucketed lax.map dispatch: rows
    stay unbatched, so per-variant values match single-run evaluation.

    Snapshots are gathered to host first: a cells-sharded sweep leaves them
    distributed, and evaluating through the sharded layout would let GSPMD
    re-partition the certificate reductions (breaking the bit-identity of
    the certificates, though not of the trajectories).
    """
    K, n_k, d = problem.X.shape
    p, dv, gap, gap_srv = engine._eval_bucketed(
        np.asarray(ws_eval).reshape(V * S, d),
        np.asarray(alphas_eval).reshape(V * S, K, n_k),
        problem.X, problem.y, problem.lam, loss=problem.loss)
    return tuple(np.asarray(a, np.float64).reshape(V, S)
                 for a in (p, dv, gap, gap_srv))


def _run_lockstep_sweep(problem, method, variants, cells, *, num_outer,
                        eval_every, batch, plan):
    d = problem.X.shape[2]
    # Trajectories depend only on (seed, gamma): the delay axis is pure
    # host-side accounting for lockstep runs, so the first delay block's
    # cells run once and every delay variant reuses them.
    block = cells[:len(cells) // len(variants)]
    with tracing.span("repro.sweep.prepare"):
        fn, args, kw, evals = _lockstep_call(problem, method, block,
                                             num_outer=num_outer,
                                             eval_every=eval_every,
                                             batch=batch, plan=plan)
    executor.STATS["sweep_calls"] += 1
    with tracing.span("repro.sweep.dispatch"):
        w, alpha, ws_eval, alphas_eval = fn(*args, **kw)
    V, S = len(block), len(evals)
    with tracing.span("repro.sweep.fetch"):
        p, dv, gap, gap_srv = _eval_grid(ws_eval[:V, :S],
                                         alphas_eval[:V, :S], problem, V, S)
    # Gamma does not move the simulated clock: accounting is per
    # (delay variant, seed).
    out = []
    with tracing.span("repro.sweep.records"):
        for name, cl in variants:
            accounts = {c.seed: executor.lockstep_accounts(
                method, cl, d, num_rounds=num_outer, seed=c.seed)
                for c in block}
            for v, c in enumerate(block):
                records = _variant_records(accounts[c.seed], evals, gap,
                                           gap_srv, p, dv, v)
                out.append(SweepVariant(c.seed, c.gamma, RunResult(
                    dataclasses.replace(method, gamma=c.gamma), records,
                    np.asarray(w[v]), np.asarray(alpha[v])), delay=name))
    return out


def _lockstep_call(problem, method, cells, *, num_outer, eval_every, batch,
                   plan):
    """The one lockstep sweep dispatch for ``cells``: ``(jitted fn, args,
    static kwargs, eval-boundary rounds)``."""
    K, n_k, d = problem.X.shape
    padded = _padded_cells(list(cells), plan.n_shards)
    sigma_ps = np.asarray([dataclasses.replace(
        method, gamma=c.gamma,
        sigma_prime=c.sigma_prime).resolved_sigma_prime(K) for c in padded])
    keys = jax.vmap(jax.random.key)(jnp.asarray([c.seed for c in padded]))
    norms_sq = jnp.sum(problem.X * problem.X, axis=-1)
    evals = executor._eval_indices(num_outer, eval_every)
    fn = _sweep_scan if plan.mode != "workers" else partial(
        _sweep_scan_workers, num_workers=K)
    args = (keys, problem.X, problem.y, norms_sq, problem.lam, K * n_k,
            jnp.asarray(sigma_ps, problem.X.dtype),
            jnp.asarray([c.gamma for c in padded], problem.X.dtype),
            jnp.asarray(_padded_eval_idx(evals), jnp.int32))
    kw = dict(loss=problem.loss, num_steps=method.H,
              solver=executor.lockstep_solver(method), length=num_outer,
              batch=batch,
              n_shards=plan.n_shards if plan.mode != "none" else 1)
    return fn, args, kw, evals


def _lag_call(problem, method, cells, *, num_outer, eval_every, batch,
              plan):
    """The one LAG sweep dispatch for ``cells``: ``(jitted fn, args, static
    kwargs, eval-boundary rounds, host timing per cell)``.  Build it under
    ``enable_x64``: the timing operands are float64."""
    K, n_k, d = problem.X.shape
    R = num_outer * method.T
    comp = compress_lib.for_method(method, d)
    dense = isinstance(comp, compress_lib.Dense)
    for c in cells:
        ok, why = executor.scan_supported(method, c.cluster)
        if not ok:
            raise ValueError(
                f"delay model {c.cluster.delay_model!r} cannot batch into a "
                f"lag sweep: {why}; run it per-cell via "
                f"Session(executor='event')")

    # Durations are per (cluster, seed) -- the same host-RNG stream a single
    # run would consume -- so gamma variants of one (cluster, seed) share.
    padded = _padded_cells(list(cells), plan.n_shards)
    dur_cache: dict = {}
    link_cache: dict = {}
    for c in padded:
        if (c.cluster, c.seed) not in dur_cache:
            durations, delay = executor.lag_durations(
                method, c.cluster, num_rounds=R, seed=c.seed)
            dur_cache[(c.cluster, c.seed)] = durations
            link_cache[c.cluster] = delay.link_factors()
    durations = np.stack([dur_cache[(c.cluster, c.seed)] for c in padded])
    link_factors = np.stack([link_cache[c.cluster] for c in padded])
    lats = np.asarray([c.cluster.latency for c in padded])
    bws = np.asarray([c.cluster.bandwidth for c in padded])
    sigma_ps = np.asarray([dataclasses.replace(
        method, gamma=c.gamma,
        sigma_prime=c.sigma_prime).resolved_sigma_prime(K) for c in padded])
    keys = jax.vmap(jax.random.key)(
        jnp.asarray([c.seed for c in padded]))
    norms_sq = jnp.sum(problem.X * problem.X, axis=-1)
    evals = executor._eval_indices(R, eval_every)
    args = (keys, problem.X, problem.y, norms_sq, jnp.float32(problem.lam),
            jnp.int32(K * n_k), jnp.asarray(sigma_ps, jnp.float32),
            jnp.asarray([c.gamma for c in padded], jnp.float32),
            jnp.float32(method.lag_xi),
            jnp.asarray(durations, jnp.float64),
            jnp.asarray(executor.lag_needs(method, K, R), jnp.int64),
            jnp.asarray(comp.wire_bytes(d), jnp.int64),
            jnp.asarray(engine.LagProtocol.HEARTBEAT_BYTES, jnp.int64),
            jnp.asarray(lats, jnp.float64),
            jnp.asarray(bws, jnp.float64),
            jnp.asarray(link_factors, jnp.float64),
            jnp.asarray(_padded_eval_idx(evals), jnp.int32))
    kw = dict(loss=problem.loss, num_steps=method.H, comp=comp, length=R,
              lag_window=method.lag_window,
              dense_reply_bytes=d * 4 if dense else 0, batch=batch,
              n_shards=plan.n_shards if plan.mode == "cells" else 1)
    timing = (durations, link_factors, lats, bws)  # per padded cell, host
    return _lag_sweep_scan, args, kw, evals, timing


def _lag_cells(problem, method, cells, *, num_outer, eval_every, batch,
               plan):
    K = problem.X.shape[0]
    T = method.T
    needs = executor.lag_needs(method, K, num_outer * T)
    mcfgs = [dataclasses.replace(method, gamma=c.gamma,
                                 sigma_prime=c.sigma_prime) for c in cells]
    with jax.enable_x64(True):
        with tracing.span("repro.sweep.prepare"):
            fn, args, kw, evals, timing = _lag_call(
                problem, method, cells, num_outer=num_outer,
                eval_every=eval_every, batch=batch, plan=plan)
        executor.STATS["sweep_lag_calls"] += 1
        with tracing.span("repro.sweep.dispatch"):
            (w, alpha, alpha_applied, ws_eval, app_eval, init_bytes, order,
             reply_bytes, launch_bytes) = fn(*args, **kw)

    V, S = len(cells), len(evals)
    with tracing.span("repro.sweep.fetch"):
        p, dv, gap, gap_srv = _eval_grid(ws_eval[:V, :S], app_eval[:V, :S],
                                         problem, V, S)
        init_bytes, order, reply_bytes, launch_bytes = (
            np.asarray(a) for a in (init_bytes, order, reply_bytes,
                                    launch_bytes))
    durations, link_factors, lats, bws = timing
    out = []
    with tracing.span("repro.sweep.records"):
        for v, c in enumerate(cells):
            rounds = executor.lag_accounts(
                needs, T, durations[v], link_factors[v], float(lats[v]),
                float(bws[v]), init_bytes[v], order[v], reply_bytes[v],
                launch_bytes[v])
            records = _variant_records(rounds, evals, gap, gap_srv, p, dv,
                                       v)
            out.append(SweepVariant(c.seed, c.gamma, RunResult(
                mcfgs[v], records, np.asarray(w[v]), np.asarray(alpha[v]),
                alpha_applied=np.asarray(alpha_applied[v])),
                delay=c.cluster.delay_model, rounds=tuple(rounds)))
    return out


def _lockstep_cells(problem, method, cells, *, num_outer, eval_every, batch,
                    plan):
    d = problem.X.shape[2]
    mcfgs = [dataclasses.replace(method, gamma=c.gamma,
                                 sigma_prime=c.sigma_prime) for c in cells]
    with tracing.span("repro.sweep.prepare"):
        fn, args, kw, evals = _lockstep_call(problem, method, cells,
                                             num_outer=num_outer,
                                             eval_every=eval_every,
                                             batch=batch, plan=plan)
    executor.STATS["sweep_calls"] += 1
    with tracing.span("repro.sweep.dispatch"):
        w, alpha, ws_eval, alphas_eval = fn(*args, **kw)

    V, S = len(cells), len(evals)
    with tracing.span("repro.sweep.fetch"):
        p, dv, gap, gap_srv = _eval_grid(ws_eval[:V, :S],
                                         alphas_eval[:V, :S], problem, V, S)
    out = []
    with tracing.span("repro.sweep.records"):
        for v, c in enumerate(cells):
            rounds = executor.lockstep_accounts(mcfgs[v], c.cluster, d,
                                                num_rounds=num_outer,
                                                seed=c.seed)
            records = _variant_records(rounds, evals, gap, gap_srv, p, dv,
                                       v)
            out.append(SweepVariant(c.seed, c.gamma, RunResult(
                mcfgs[v], records, np.asarray(w[v]), np.asarray(alpha[v])),
                delay=c.cluster.delay_model, rounds=tuple(rounds)))
    return out


def run_sweep_cells(
    problem: objectives.Problem,
    method: MethodConfig,
    cells,
    *,
    num_outer: int,
    eval_every: int = 1,
    batch: str = "vmap",
    shard: str = "auto",
) -> list[SweepVariant]:
    """Run an EXPLICIT list of sweep cells as one compiled computation.

    Where :func:`run_sweep` runs the full ``delays x seeds x gammas`` cross
    product, this takes a flat list of :class:`SweepCellSpec` (or
    ``(cluster, seed, gamma)`` tuples) and runs exactly those cells -- the
    entry point the multi-tenant serve layer (:mod:`repro.serve`) batches
    coalesced requests through, since different tenants rarely ask for a
    rectangular grid.  ``method`` is the shared template: everything that
    is static to the compiled computation (protocol, H, T, B, rho,
    compressor, solver, lag window) comes from it, while each cell's
    ``gamma`` / ``sigma_prime`` / ``cluster`` / ``seed`` override per cell.

    Same compiled callables, same pow2 cell/eval bucketing, and same
    bit-identity contract as :func:`run_sweep`: under ``batch="map"`` with
    an unsharded or cells-sharded plan every cell is bit-identical to the
    corresponding solo ``Session(executor="scan")`` run (pinned by
    tests/test_serve.py).  Every returned variant carries its full
    per-round accounting (``SweepVariant.rounds``) so callers can replay
    the cell's complete Round/Sync/Eval/Stop event stream.
    """
    if method.protocol not in executor.SWEEP_PROTOCOLS:
        raise ValueError(
            f"sweep batching needs a sweep-batchable (shared-cell "
            f"scan-capable) protocol {executor.SWEEP_PROTOCOLS}, got "
            f"{method.protocol!r}; run other protocols one Session per "
            f"cell")
    if batch not in ("vmap", "map"):
        raise ValueError(f"unknown batch mode {batch!r}; 'vmap' or 'map'")
    if num_outer <= 0:
        raise ValueError(f"num_outer must be >= 1, got {num_outer}")
    cells = [c if isinstance(c, SweepCellSpec) else SweepCellSpec(*c)
             for c in cells]
    if not cells:
        raise ValueError("cells is empty: pass at least one SweepCellSpec")
    cells = [dataclasses.replace(c, gamma=method.gamma)
             if c.gamma is None else c for c in cells]
    K = problem.X.shape[0]
    for c in cells:
        if c.cluster.num_workers != K:
            raise ValueError(
                f"cell cluster has num_workers={c.cluster.num_workers} but "
                f"the problem is partitioned over K={K} workers")
    plan = resolve_shard(shard, protocol=method.protocol, num_workers=K)
    core = _lag_cells if method.protocol == "lag" else _lockstep_cells
    return core(problem, method, cells, num_outer=num_outer,
                eval_every=eval_every, batch=batch, plan=plan)


# ---------------------------------------------------------------------------
# Compat + spec-level entry points.
# ---------------------------------------------------------------------------


def run_lockstep_sweep(
    problem: objectives.Problem,
    method: MethodConfig,
    cluster: ClusterModel,
    *,
    num_outer: int,
    seeds=(0,),
    gammas=None,
    eval_every: int = 1,
    batch: str = "vmap",
    shard: str = "none",
) -> list[SweepVariant]:
    """Lockstep-only compat wrapper over :func:`run_sweep` (PR-4 surface;
    unsharded by default).  New code should call :func:`run_sweep`."""
    if method.protocol not in executor.LOCKSTEP_PROTOCOLS:
        raise ValueError(
            f"sweep batching needs a lockstep protocol "
            f"{executor.LOCKSTEP_PROTOCOLS}, got {method.protocol!r}; use "
            f"run_sweep for lag, or one Session per cell for the group "
            f"family")
    return run_sweep(problem, method, cluster, num_outer=num_outer,
                     seeds=seeds, gammas=gammas, eval_every=eval_every,
                     batch=batch, shard=shard)


def sweep_spec(spec, method_name: str, *, seeds=None, gammas=None,
               delays=None, batch: str = "vmap",
               shard: str | None = None) -> list[SweepVariant]:
    """Spec-level convenience: sweep one method entry of an
    :class:`repro.api.ExperimentSpec` (its eval cadence, its problem, its
    seed -- ``seeds`` defaults to ``(spec.seed,)`` so the no-axes call
    reproduces exactly the run the spec declares).  ``shard`` defaults to
    the spec's own ``shard`` field."""
    if spec.target_gap is not None or spec.time_budget is not None:
        raise ValueError(
            "sweep batching compiles whole runs and cannot early-stop; "
            "this spec sets target_gap/time_budget -- run it per-cell via "
            "Experiment/Session instead")
    entry = spec.method_named(method_name)
    problem = spec.problem.build()
    return run_sweep(problem, entry.config, spec.cluster,
                     num_outer=entry.num_outer,
                     seeds=(spec.seed,) if seeds is None else seeds,
                     gammas=gammas, delays=delays,
                     eval_every=spec.eval_every, batch=batch,
                     shard=spec.shard if shard is None else shard)
