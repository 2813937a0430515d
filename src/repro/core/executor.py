"""Scan-fused lockstep executor: whole runs as ONE compiled computation.

The event engine (:mod:`repro.core.engine` driven by
:class:`repro.api.session.Session`) pays one jitted dispatch per worker group
and per server round.  That is already ~100x fewer host<->device round trips
than the reference loops, but for protocols with *no data-dependent host
control flow* even the per-round dispatch is overhead: the entire run can be
a single ``lax.scan`` over rounds.  This module is that second execution
backend -- selected via ``Session(executor="scan")`` or automatically under
``executor="auto"`` (the default).

Three scan paths:

* **Lockstep** (``sync`` / ``cocoa`` / ``cocoa_plus``): every round is a
  K-barrier with static byte accounting, so timing is fully host-computable.
  Compute-time streams are pre-sampled by
  :meth:`repro.core.delays.DelayModel.sample_stream` (same host-RNG order as
  the event loop, so trajectories are bit-identical), the model state
  ``(w, alpha)`` evolves in one donated scan dispatch, and deferred gap
  certificates reuse the engine's bucketed ``lax.map`` evaluation.

* **LAG** (``lag``): B-of-K arrivals couple timing to device values (reply
  ``nnz`` -> reply bytes -> link time -> arrival order), so the *event queue
  itself* moves in-graph: per-worker arrival times and sequence numbers live
  in the scan carry, the B earliest messages are selected with a
  lexicographic ``lax.sort`` over float64 arrival times (traced under
  ``jax.enable_x64(True)``; model math stays explicitly float32, and
  ``sdca`` pins its PRNG dtypes, so the f32 trajectory is bit-identical to
  the event executor's).  The clocks and byte totals are replayed on the
  host from the device's pop order and byte counts
  (:func:`lag_accounts`), because a TPU's float64 is emulated without IEEE
  rounding.  Eligible whenever
  the delay model can pre-sample ``(round, worker)`` compute times without
  changing the event executor's RNG stream (``sample_stream`` contract);
  ``markov`` and jittered ``constant`` cannot, and ``executor="auto"`` falls
  back to the event queue for them.

* **partial_work** (``partial_work``): the lag machinery generalized to
  per-CHUNK carries -- every in-flight chunk's payload/arrival/seq lives in
  the scan state, the round deadline is the B-th *full* arrival (a lex sort
  over final-chunk keys), and harvested chunks fold in via a flattened
  ``K x n_chunks`` arrival-order sort.  Eligible when the delay model can
  pre-sample a (round, chunk, worker) stream
  (:meth:`repro.core.delays.DelayModel.sample_chunk_stream`), there is no
  elastic membership schedule, and no ``pw_quantum`` harvest tick (both are
  host-adaptive and keep the event queue).

Protocols with genuinely host-adaptive control flow (``group``'s
interleaved accounting pins, ``async``, ``adaptive_b``'s observed-latency
feedback, ``hierarchical_b``'s rack-dependent pop counts) keep the event
queue -- they still benefit from the engine's fused multi-arrival server
apply and one-dispatch group relaunches.

``target_gap`` early stop is scan-capable for lockstep runs: the duality-gap
certificate moves in-graph and a ``done`` flag in the carry freezes the
state once the target is reached (:func:`lockstep_run_gap_traced`,
compute-and-mask with post-hoc truncation).  The traced run bodies
(:func:`lockstep_run_traced`, :func:`lag_run_traced`, and the
worker-sharded :func:`lockstep_run_traced_sharded`) are also the building
blocks of :func:`repro.api.sweep.run_sweep`, which maps/vmaps them across
whole protocol x delay x seed x gamma grids and can shard the batched axes
over a device mesh.

Bit-for-bit contract: for every supported (protocol, delay) cell the scan
executor reproduces the event executor's ``RunResult`` exactly --
trajectories, byte/time accounting, and gap certificates (pinned by
tests/test_executor.py across the zoo grid).  ``STATS`` counts compiled-call
and retrace events so tests can assert the one-dispatch-per-run contract.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress as compress_lib
from repro.core import engine
from repro.core import objectives
from repro.core import tracing
from repro.core.acpd import MethodConfig, RunResult
from repro.core.simulate import ClusterModel

LOCKSTEP_PROTOCOLS = ("sync", "cocoa", "cocoa_plus")
# Protocols whose traced run bodies batch into shared sweep cells
# (repro.api.sweep / the serve coalescer): one computation, many variants.
SWEEP_PROTOCOLS = LOCKSTEP_PROTOCOLS + ("lag",)
# Protocols with a single-run scan backend.  partial_work scans solo (its
# per-chunk carries are per-run state) but does NOT batch into sweep cells.
SCAN_PROTOCOLS = SWEEP_PROTOCOLS + ("partial_work",)

# target_gap runs on the scan backend compute-and-mask: every budgeted round
# executes even after the target is hit, so for huge budgets the masked tail
# can dwarf the dispatch overhead the scan saves.  ``executor="auto"`` only
# picks the gap scan up to this round budget and keeps the event loop (which
# stops at the hit) beyond it; forcing ``executor="scan"`` overrides.
GAP_SCAN_AUTO_MAX_ROUNDS = 4096

# Dispatch accounting for the 1-dispatch-per-run contract: "*_calls" counts
# compiled executions (one per run), "*_traces" counts retraces (flat across
# same-shape runs).  tests/test_executor.py + tests/test_sweep.py assert on
# these.  The counters live in the leaf module repro.core.tracing (the event
# engine counts its rounds there too, and it cannot import this module);
# this is the same dict, so one reset covers every entry point.
STATS = tracing.STATS
reset_stats = tracing.reset_stats


# ---------------------------------------------------------------------------
# Eligibility.
# ---------------------------------------------------------------------------


def scan_supported(method: MethodConfig, cluster: ClusterModel, *,
                   eval_mode: str = "batched",
                   target_gap: float | None = None,
                   time_budget: float | None = None) -> tuple[bool, str]:
    """Can this run compile to one scan?  Returns (ok, reason-if-not).

    ``target_gap`` early stop is scan-capable for the lockstep protocols:
    the duality-gap certificate moves in-graph and a ``done`` flag in the
    scan carry freezes the state once the target is reached
    (compute-and-mask; see :func:`lockstep_run_gap_traced`).  ``lag`` and
    the group family keep the event loop for early stop, as does
    ``time_budget`` (its stop point depends on interleaved host accounting).
    """
    if method.exact_dual_feedback:
        return False, ("exact_dual_feedback needs a host lstsq per round "
                       "(reference path only)")
    if time_budget is not None:
        return False, "time_budget early stop needs the per-round event loop"
    if target_gap is not None:
        if method.protocol not in LOCKSTEP_PROTOCOLS:
            return False, (
                f"target_gap early stop compiles in-graph only for lockstep "
                f"protocols {LOCKSTEP_PROTOCOLS}; {method.protocol!r} needs "
                f"the per-round event loop")
    elif eval_mode == "stream":
        return False, ("streamed certificates without a gap target need "
                       "the per-round event loop")
    if method.protocol in LOCKSTEP_PROTOCOLS:
        return True, ""
    if method.protocol == "lag":
        model = cluster.make_delay()
        if model.vector_sampled or model.deterministic:
            return True, ""
        return False, (
            f"delay model {cluster.delay_model!r} draws per-launch host "
            f"randomness in arrival order, which cannot be pre-sampled "
            f"into a (round, worker) stream")
    if method.protocol == "partial_work":
        if cluster.membership:
            return False, ("elastic membership drop/rejoin schedules are "
                           "host-adaptive control flow (event loop only)")
        if method.pw_quantum is not None:
            return False, ("pw_quantum harvest ticks pop clock-dependent "
                           "arrival counts (event loop only)")
        model = cluster.make_delay()
        if model.vector_sampled or model.deterministic:
            return True, ""
        return False, (
            f"delay model {cluster.delay_model!r} draws per-launch host "
            f"randomness in arrival order, which cannot be pre-sampled "
            f"into a (round, chunk, worker) stream")
    return False, (
        f"protocol {method.protocol!r} has host-adaptive control flow "
        f"(scan-capable protocols: {SCAN_PROTOCOLS})")


def coalesce_supported(method: MethodConfig, cluster: ClusterModel, *,
                       target_gap: float | None = None,
                       time_budget: float | None = None) -> tuple[bool, str]:
    """Can this (method, cluster) join a SHARED sweep batch?  (ok, why-not).

    The serve-layer admission check (:mod:`repro.serve`): a coalesced batch
    compiles whole fixed-length runs for many tenants at once, so it is
    strictly narrower than :func:`scan_supported` -- early-stopped runs
    never coalesce (their round count is data-dependent; a stopping tenant
    would either truncate or pad every cohort cell), even though a solo
    lockstep ``target_gap`` run can scan.  Ineligible requests are still
    servable, one :class:`repro.api.Session` per request (the solo lane).

    Per-protocol eligibility is the registry's
    :meth:`repro.core.engine.Protocol.coalesce_supported` hook (the
    ``registry-hooks`` analyzer rule requires it on new entries), so a new
    protocol states its own batching story instead of inheriting a silent
    default here -- ``partial_work`` scans solo but declines coalescing (its
    per-chunk carries are per-run state, not shared sweep cells).
    """
    if target_gap is not None:
        return False, ("target_gap early stop makes the round count "
                       "data-dependent; batches compile fixed-length runs "
                       "-- served per-request instead")
    if time_budget is not None:
        return False, ("time_budget early stop needs the per-round event "
                       "loop -- served per-request instead")
    return engine.get_protocol(method.protocol).coalesce_supported(
        method, cluster)


# ---------------------------------------------------------------------------
# Run container handed back to the Session.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RoundAccount:
    """Host-side accounting of one server round (cumulative totals)."""

    arrivals: int
    is_sync: bool
    sim_time: float
    bytes_up: int
    bytes_down: int
    compute_time: float
    comm_time: float


@dataclasses.dataclass
class ScanRun:
    """Everything a Session needs to emit the run's event stream.

    ``eval_ws``/``eval_alphas`` hold the eval-boundary snapshots as ONE
    stacked array each (gathered from the scan outputs in a single op --
    per-snapshot slicing would reintroduce an O(rounds) dispatch tail).
    """

    method: MethodConfig
    rounds: list[RoundAccount]
    eval_rounds: list[int]  # 0-based round index per eval boundary
    eval_ws: jax.Array | None
    eval_alphas: jax.Array | None
    w: jax.Array
    alpha: jax.Array
    alpha_applied: jax.Array | None = None
    # target_gap runs: why/when the run stopped, plus the records already
    # materialized from the in-graph certificates (nothing left to defer).
    stop_reason: str = "completed"
    stream_records: list | None = None

    def materialize_records(self, problem, eval_mode: str):
        """The run's RunRecords; same certificate ops as the event path
        (``batched``: one bucketed ``lax.map``; ``replay``: eager oracle).
        target_gap runs computed their certificates in-graph and carry the
        finished records (``stream_records``)."""
        from repro.core.acpd import RunRecord

        if self.stream_records is not None:
            return self.stream_records
        if not self.eval_rounds:
            return []
        if eval_mode == "replay":
            rows = []
            for i in range(len(self.eval_rounds)):
                cert = objectives.gap_certificate(
                    problem, self.eval_alphas[i], w=self.eval_ws[i])
                rows.append((cert["primal"], cert["dual"], cert["gap"],
                             cert["gap_server"]))
        elif eval_mode == "batched":
            p, dv, gap, gap_srv = engine._eval_bucketed(
                self.eval_ws, self.eval_alphas, problem.X, problem.y,
                problem.lam, loss=problem.loss)
            rows = list(zip(np.asarray(p, np.float64),
                            np.asarray(dv, np.float64),
                            np.asarray(gap, np.float64),
                            np.asarray(gap_srv, np.float64)))
        else:
            raise ValueError(f"unknown eval_mode {eval_mode!r}")
        records = []
        for r, (p_, dv_, gap_, gs_) in zip(self.eval_rounds, rows):
            a = self.rounds[r]
            records.append(RunRecord(
                iteration=r + 1, sim_time=a.sim_time, gap=float(gap_),
                gap_server=float(gs_), primal=float(p_), dual=float(dv_),
                bytes_up=a.bytes_up, bytes_down=a.bytes_down,
                compute_time=a.compute_time, comm_time=a.comm_time))
        return records

    def finalize(self, records) -> RunResult:
        return RunResult(
            self.method, records, np.asarray(self.w), np.asarray(self.alpha),
            alpha_applied=(None if self.alpha_applied is None
                           else np.asarray(self.alpha_applied)))


def run_scan(problem: objectives.Problem, method: MethodConfig,
             cluster: ClusterModel, *, num_outer: int, seed: int,
             eval_every: int, norms_sq=None,
             target_gap: float | None = None) -> ScanRun:
    """Execute one run on the scan backend (caller checked eligibility).

    ``norms_sq``: optional precomputed per-row squared norms (the Session's
    protocol instance already holds them; passing them avoids a second full
    pass over ``X``).  ``target_gap``: gap early stop, lockstep only (the
    certificate moves in-graph; see :func:`lockstep_run_gap_traced`).
    """
    if norms_sq is None:
        norms_sq = jnp.sum(problem.X * problem.X, axis=-1)
    if method.protocol in LOCKSTEP_PROTOCOLS:
        return _run_lockstep(problem, method, cluster, num_outer=num_outer,
                             seed=seed, eval_every=eval_every,
                             norms_sq=norms_sq, target_gap=target_gap)
    if target_gap is not None:
        raise ValueError(
            f"target_gap early stop on the scan backend is lockstep-only; "
            f"{method.protocol!r} runs it through the event loop")
    if method.protocol == "lag":
        return _run_lag(problem, method, cluster, num_outer=num_outer,
                        seed=seed, eval_every=eval_every, norms_sq=norms_sq)
    if method.protocol == "partial_work":
        return _run_partial(problem, method, cluster, num_outer=num_outer,
                            seed=seed, eval_every=eval_every,
                            norms_sq=norms_sq)
    raise ValueError(f"protocol {method.protocol!r} is not scan-capable "
                     f"(supported: {SCAN_PROTOCOLS})")


def _eval_indices(num_rounds: int, eval_every: int) -> list[int]:
    """0-based round indices of eval boundaries (iteration % eval_every == 0)."""
    return [it - 1 for it in range(1, num_rounds + 1) if it % eval_every == 0]


# ---------------------------------------------------------------------------
# Lockstep path: sync / cocoa / cocoa_plus.
# ---------------------------------------------------------------------------


def lockstep_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma, *, loss,
                        num_steps, solver, length):
    """The whole lockstep run as a traced computation (scan over rounds,
    workers vmapped inside each round).

    The round body IS the event engine's (``engine._lockstep_round``, the
    single definition both backends inline -- scalars stay traced operands;
    constant-folding them changes XLA's simplifications and breaks
    bit-equality).  Shared by the single-run jit below and the batched sweep
    runner (:mod:`repro.api.sweep`), which maps/vmaps it over run variants.
    """
    K, n_k, d = X.shape
    w0 = jnp.zeros((d,), X.dtype)
    alpha0 = jnp.zeros((K, n_k), X.dtype)

    def step(carry, _):
        key, w, alpha = carry
        key, w, alpha = engine._lockstep_round(
            key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma, loss=loss,
            num_steps=num_steps, solver=solver)
        return (key, w, alpha), (w, alpha)

    (key, w, alpha), (ws, alphas) = jax.lax.scan(
        step, (key, w0, alpha0), None, length=length)
    return w, alpha, ws, alphas


def lockstep_run_traced_sharded(key, X, y, norms_sq, lam, n, sigma_p, gamma,
                                *, loss, num_steps, solver, length, axis,
                                num_workers):
    """:func:`lockstep_run_traced` on ONE worker shard of a device mesh.

    Runs inside ``shard_map`` with the worker axis partitioned over mesh
    axis ``axis``: ``X``/``y``/``norms_sq`` are the local ``(K_loc, n_k, d)``
    blocks, ``w`` stays replicated, and each round does exactly one
    cross-shard reduction (the ``psum`` of the shard-local ``sum_k v_k``).
    The PRNG split chain is the global one -- every shard splits the full
    ``num_workers`` keys and slices its block by ``axis_index`` -- so each
    worker sees the same key as the unsharded run.  Per-shard ops keep
    unbatched per-worker shapes inside the local vmap, so kernel-backed
    solvers (e.g. the Pallas SDCA inner loop in
    :mod:`repro.kernels.sdca_inner`) drop in per shard unchanged.

    The partial-sum + psum association differs from the unsharded
    ``sum(v, axis=0)``, so results are deterministic for a fixed mesh but
    NOT bit-identical to ``shard="none"`` -- a perf mode, like
    ``batch="vmap"`` (tests pin allclose agreement instead).
    """
    K_loc, n_k, d = X.shape
    w0 = jnp.zeros((d,), X.dtype)
    alpha0 = jnp.zeros((K_loc, n_k), X.dtype)
    shard = jax.lax.axis_index(axis)

    def step(carry, _):
        key, w, alpha = carry
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, num_workers)
        local_keys = jax.lax.dynamic_slice_in_dim(keys, shard * K_loc, K_loc)
        dalpha, v = engine._lockstep_local_solves(
            w, alpha, X, y, norms_sq, lam, n, sigma_p, local_keys, loss=loss,
            num_steps=num_steps, solver=solver)
        with jax.named_scope(tracing.SOLVE):
            alpha = alpha + gamma * dalpha
        with jax.named_scope(tracing.AGGREGATE):
            w = w + gamma * jax.lax.psum(jnp.sum(v, axis=0), axis)
        return (key, w, alpha), (w, alpha)

    (key, w, alpha), (ws, alphas) = jax.lax.scan(
        step, (key, w0, alpha0), None, length=length)
    return w, alpha, ws, alphas


@partial(jax.jit, static_argnames=("loss", "num_steps", "solver", "length"))
def _lockstep_scan(key, X, y, norms_sq, lam, n, sigma_p, gamma, *, loss,
                   num_steps, solver, length):
    STATS["lockstep_traces"] += 1  # trace-time side effect, not per call
    return lockstep_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma,
                               loss=loss, num_steps=num_steps, solver=solver,
                               length=length)


def gap_floor_f32(target_gap: float) -> np.float32:
    """The largest float32 ``t`` with ``float(t) <= target_gap``.

    The event loop's early stop compares ``float(gap_f32) <= target_gap`` in
    float64; the in-graph test compares float32 against float32.  Flooring
    the target to the f32 grid makes the two predicates decide identically
    for every representable gap value, so the executors stop on the same
    round bit-for-bit.
    """
    t = np.float32(target_gap)
    if float(t) > target_gap:
        t = np.nextafter(t, np.float32(-np.inf), dtype=np.float32)
    return t


def lockstep_run_gap_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma,
                            gap_target, eval_mask, *, loss, num_steps, solver,
                            length):
    """Lockstep run with in-graph duality-gap early stop, as one scan.

    The round body is the shared :func:`engine._lockstep_round`; at eval
    boundaries (``eval_mask``, a static-per-round bool stream) the duality
    gap certificate is computed in-graph via the shared
    :func:`engine._certificate_ops`, and a ``done`` flag in the
    carry freezes ``(w, alpha)`` once the gap reaches ``gap_target``
    (compute-and-mask: later rounds still execute but write nothing).  The
    caller truncates the per-round outputs at the stop boundary post hoc --
    trajectories and certificates up to the stop are bit-identical to the
    event loop's streamed path (pinned by tests/test_executor.py).

    ``gap_target`` must be pre-floored to the f32 grid
    (:func:`gap_floor_f32`) so the f32 comparison decides like the host's
    f64 one.
    """
    K, n_k, d = X.shape
    w0 = jnp.zeros((d,), X.dtype)
    alpha0 = jnp.zeros((K, n_k), X.dtype)

    def certify(args):
        w, alpha = args
        return engine._certificate_ops(w, alpha, X, y, lam, loss=loss)

    def no_cert(args):
        z = jnp.zeros((), args[0].dtype)
        return z, z, z, z

    def step(carry, is_eval):
        key, w, alpha, done = carry
        key, w_new, alpha_new = engine._lockstep_round(
            key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma, loss=loss,
            num_steps=num_steps, solver=solver)
        w = jnp.where(done, w, w_new)
        alpha = jnp.where(done, alpha, alpha_new)
        do_cert = is_eval & ~done
        p, dv, gap, gap_srv = jax.lax.cond(do_cert, certify, no_cert,
                                           (w, alpha))
        done = done | (do_cert & (gap <= gap_target))
        return (key, w, alpha, done), (p, dv, gap, gap_srv, done)

    (key, w, alpha, done), ys = jax.lax.scan(
        step, (key, w0, alpha0, jnp.zeros((), bool)), eval_mask,
        length=length)
    return w, alpha, ys


@partial(jax.jit, static_argnames=("loss", "num_steps", "solver", "length"))
def _lockstep_gap_scan(key, X, y, norms_sq, lam, n, sigma_p, gamma,
                       gap_target, eval_mask, *, loss, num_steps, solver,
                       length):
    STATS["lockstep_gap_traces"] += 1  # trace-time side effect, not per call
    return lockstep_run_gap_traced(key, X, y, norms_sq, lam, n, sigma_p,
                                   gamma, gap_target, eval_mask, loss=loss,
                                   num_steps=num_steps, solver=solver,
                                   length=length)


def lockstep_solver(method: MethodConfig):
    """The local solver a lockstep protocol runs: the CoCoA lineage swaps it
    via ``MethodConfig.local_solver``; the hard-wired ``sync`` entry is the
    registry's ``sdca`` (the same vmapped computation)."""
    from repro.core import solvers as solvers_lib

    return solvers_lib.get_solver(
        method.local_solver if method.protocol != "sync" else "sdca")


def lockstep_accounts(method: MethodConfig, cluster: ClusterModel, d: int,
                      *, num_rounds: int, seed: int) -> list[RoundAccount]:
    """Host-side timing/byte accounting of a lockstep run.

    Fully independent of device values: compute streams are pre-sampled
    (same host-RNG order as the event loop's one-K-vector-per-round draws,
    so the floats are bit-identical), allreduce time and ring bytes are
    static per round.
    """
    K = cluster.num_workers
    delay = cluster.make_delay()
    rng = np.random.default_rng(seed)
    durations = delay.sample_stream(num_rounds, method.H, rng, lockstep=True)
    step_comm = delay.allreduce_time(d)
    phase = (K - 1) * d * 4  # ring reduce-scatter == all-gather
    sim = comp_t = comm_t = 0.0
    bu = bd = 0
    rounds: list[RoundAccount] = []
    for r in range(num_rounds):
        step_compute = float(np.max(durations[r]))
        sim += step_compute + step_comm
        comp_t += step_compute
        comm_t += step_comm
        bu += phase
        bd += phase
        rounds.append(RoundAccount(K, True, sim, bu, bd, comp_t, comm_t))
    return rounds


def _run_lockstep(problem, method, cluster, *, num_outer, seed, eval_every,
                  norms_sq, target_gap=None):
    K, n_k, d = problem.X.shape
    R = num_outer
    if R == 0:
        dt = problem.X.dtype
        return ScanRun(method, [], [], None, None, jnp.zeros((d,), dt),
                       jnp.zeros((K, n_k), dt))
    rounds = lockstep_accounts(method, cluster, d, num_rounds=R, seed=seed)
    sigma_p = method.resolved_sigma_prime(K)
    if target_gap is not None:
        return _run_lockstep_gap(problem, method, rounds, sigma_p,
                                 num_outer=R, seed=seed,
                                 eval_every=eval_every, norms_sq=norms_sq,
                                 target_gap=target_gap)
    STATS["lockstep_calls"] += 1
    w, alpha, ws, alphas = _lockstep_scan(
        jax.random.key(seed), problem.X, problem.y, norms_sq, problem.lam,
        K * n_k, sigma_p, method.gamma, loss=problem.loss,
        num_steps=method.H, solver=lockstep_solver(method), length=R)

    evals = _eval_indices(R, eval_every)
    idx = jnp.asarray(evals, jnp.int32)
    return ScanRun(method, rounds, evals, ws[idx], alphas[idx], w, alpha)


def _run_lockstep_gap(problem, method, rounds, sigma_p, *, num_outer, seed,
                      eval_every, norms_sq, target_gap):
    """Lockstep + target_gap: one gap-scan dispatch, records truncated at the
    stop boundary from the in-graph certificates."""
    from repro.core.acpd import RunRecord

    R = num_outer
    eval_mask = np.asarray([(r + 1) % eval_every == 0 for r in range(R)])
    STATS["lockstep_gap_calls"] += 1
    w, alpha, ys = _lockstep_gap_scan(
        jax.random.key(seed), problem.X, problem.y, norms_sq, problem.lam,
        problem.n, sigma_p, method.gamma, gap_floor_f32(target_gap),
        jnp.asarray(eval_mask), loss=problem.loss, num_steps=method.H,
        solver=lockstep_solver(method), length=R)
    p, dv, gap, gap_srv = (np.asarray(a, np.float64) for a in ys[:4])
    done = np.asarray(ys[4])
    hit = bool(done.any())
    stop = int(np.argmax(done)) if hit else R - 1
    records = []
    for r in range(stop + 1):
        if not eval_mask[r]:
            continue
        a = rounds[r]
        records.append(RunRecord(
            iteration=r + 1, sim_time=a.sim_time, gap=float(gap[r]),
            gap_server=float(gap_srv[r]), primal=float(p[r]),
            dual=float(dv[r]), bytes_up=a.bytes_up, bytes_down=a.bytes_down,
            compute_time=a.compute_time, comm_time=a.comm_time))
    return ScanRun(method, rounds[:stop + 1], [], None, None, w, alpha,
                   stop_reason="target_gap" if hit else "completed",
                   stream_records=records)


# ---------------------------------------------------------------------------
# LAG path: the B-of-K event queue in-graph.
# ---------------------------------------------------------------------------


def lag_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma, xi, durations,
                   needs, up_bytes, heartbeat_bytes, latency,
                   bandwidth, link_factors, *, loss, num_steps, comp, length,
                   lag_window, dense_reply_bytes, cells=1):
    """The whole LAG run as a traced computation: in-graph B-of-K event queue.

    Carries per-worker in-flight message state (payload, arrival time f64,
    sequence number) alongside the model state; each round sorts arrivals
    lexicographically by ``(arrival, seq)`` -- exactly the host heap's pop
    order -- applies the group with the event engine's op sequence, then
    relaunches the arrived workers under a ``lax.cond``-guarded rank scan
    that splits the global PRNG key only for launched workers (the event
    path's sequential split chain).  Must be traced under ``enable_x64`` so
    the arrival times are float64 like the host's; all model math is pinned
    float32.  ``dense_reply_bytes`` is 0 for sparse compressors (replies
    billed on in-graph nnz) or the static dense byte count.

    The device times arrivals only to order them.  A TPU emulates float64
    without IEEE rounding, so the clocks and byte totals are not summed
    here: the run returns its decisions -- per round the pop order, each
    rank's reply bytes and each launch's upload bytes (``state
    ["init_bytes"]`` for the t=0 wave) -- and :func:`lag_accounts` replays
    the accounting on the host.

    Shared by the single-run jit below and the batched sweep runner
    (:mod:`repro.api.sweep`), which maps/vmaps it over delay x seed x gamma
    cells -- durations, link factors and latency/bandwidth are traced
    operands, so a whole delay-model axis batches into one computation.
    """
    K, n_k, d = X.shape
    dt = X.dtype
    f64 = jnp.float64
    i64 = jnp.int64
    iota = jnp.arange(K, dtype=i64)

    def launch(args, *, initial):
        """Rank-scan relaunching the first ``need`` ranks of ``order``;
        returns the carried state plus each rank's upload bytes (0 for a
        rank that does not launch)."""
        (key, alpha, residual, payload, applied, arrival, seq, seq_ctr,
         ref_buf, ref_len, w_local, need, order, starts, dur_row) = args

        def do_launch(carry, xs):
            key, alpha, residual, payload, applied, arrival, seq = carry
            j, k, start = xs
            ref_k = engine._lag_reference(ref_buf[k], ref_len[k], xi)
            key, alpha_k, res_k, dw, sent = engine._local_round(
                key, w_local, alpha[k], residual[k], X, y[k], norms_sq[k],
                k, lam, n, sigma_p, gamma, loss=loss, num_steps=num_steps,
                comp=comp, cells=cells)
            send_sq = jnp.vdot(sent, sent, precision=objectives.HIGHEST)
            skip = send_sq < ref_k
            sent = jnp.where(skip, jnp.zeros_like(sent), sent)
            res_k = jnp.where(skip, dw, res_k)
            nbytes = jnp.where(skip, heartbeat_bytes, up_bytes)
            up_t = latency + nbytes * link_factors[k] / bandwidth
            alpha = alpha.at[k].set(alpha_k)
            residual = residual.at[k].set(res_k)
            payload = payload.at[k].set(sent)
            applied = applied.at[k].set(~skip)
            arrival = arrival.at[k].set(start + dur_row[k] + up_t)
            seq = seq.at[k].set(seq_ctr + j + 1)
            return (key, alpha, residual, payload, applied, arrival,
                    seq), nbytes

        def no_op(carry, xs):
            return carry, jnp.zeros((), i64)

        def rank_body(carry, xs):
            return jax.lax.cond(xs[0] < need, do_launch, no_op, carry, xs)

        init = (key, alpha, residual, payload, applied, arrival, seq)
        # No ambiguity on the first launch: every worker, worker order.
        out, nbytes = jax.lax.scan(do_launch if initial else rank_body, init,
                                   (iota, order, starts))
        return out + (seq_ctr + need,), nbytes

    # --- initial state + the t=0 launch wave ------------------------------
    state = dict(
        key=key,
        w_server=jnp.zeros((d,), dt),
        dw_tilde=jnp.zeros((K, d), dt),
        w_local=jnp.zeros((K, d), dt),
        alpha=jnp.zeros((K, n_k), dt),
        alpha_applied=jnp.zeros((K, n_k), dt),
        residual=jnp.zeros((K, d), dt),
        payload=jnp.zeros((K, d), dt),
        applied=jnp.ones((K,), bool),
        ref_buf=jnp.zeros((K, lag_window), dt),
        ref_len=jnp.zeros((K,), jnp.int32),
        arrival=jnp.zeros((K,), f64),
        seq=jnp.zeros((K,), i64),
        seq_ctr=jnp.zeros((), i64),
    )
    (state["key"], state["alpha"], state["residual"], state["payload"],
     state["applied"], state["arrival"], state["seq"],
     state["seq_ctr"]), init_bytes = launch(
        (state["key"], state["alpha"], state["residual"], state["payload"],
         state["applied"], state["arrival"], state["seq"], state["seq_ctr"],
         state["ref_buf"], state["ref_len"], state["w_local"],
         jnp.asarray(K, i64), iota, jnp.zeros((K,), f64), durations[0]),
        initial=True)

    # --- the round loop ---------------------------------------------------

    def round_step(carry, xs):
        s = dict(carry)
        need, dur_row = xs
        need = need.astype(i64)
        with jax.named_scope(tracing.SERVER_APPLY):
            # Pop order: lexicographic (arrival, seq) -- the host heap's order.
            _, _, perm = jax.lax.sort((s["arrival"], s["seq"], iota),
                                      num_keys=2)
            server_time = s["arrival"][perm][need - 1]
            sel = iota < need

            # Aggregation, summed in arrival order over exactly `need`
            # payloads.
            def agg(j, tot):
                return tot + s["payload"][perm[j]]

            total = jax.lax.fori_loop(0, need, agg, jnp.zeros((d,), dt))
            w_server = s["w_server"] + gamma * total
            dw_tilde = s["dw_tilde"] + gamma * total[None, :]

            # Each arrived upload's dual snapshot (its worker's ``alpha``
            # row) becomes server-visible.  Selected per worker: XLA:TPU
            # returned the operand unchanged for the rank-space form
            # ``a.at[perm].set(where(m, b[perm], a[perm]))``.
            _, rank = jax.lax.sort((perm, iota), num_keys=1)
            mask = ((rank < need) & s["applied"])[:, None]
            alpha_applied = jnp.where(mask, s["alpha"], s["alpha_applied"])
            replies = dw_tilde[perm]
            reply_nnz = jnp.sum(replies != 0, axis=1)
            reply_sq = jnp.sum(replies * replies, axis=1)
            w_rows = s["w_local"][perm]
            w_local = s["w_local"].at[perm].set(
                jnp.where(sel[:, None], w_rows + replies, w_rows))
            dw_tilde = dw_tilde.at[perm].set(
                jnp.where(sel[:, None], jnp.zeros_like(replies),
                          dw_tilde[perm]))

            # Reply-energy windows (the op sequence of _lag_window_append,
            # masked to the arrived workers).
            rows = s["ref_buf"][perm]
            lens = s["ref_len"][perm]
            full = (lens >= lag_window)[:, None]
            shifted = jnp.where(full, jnp.roll(rows, -1, axis=1), rows)
            pos = jnp.minimum(lens, lag_window - 1)
            new_rows = shifted.at[jnp.arange(K), pos].set(reply_sq)
            ref_buf = s["ref_buf"].at[perm].set(
                jnp.where(sel[:, None], new_rows, rows))
            ref_len = s["ref_len"].at[perm].set(
                jnp.where(sel, jnp.minimum(lens + 1, lag_window), lens))

            # Reply billing per rank (same arithmetic as DelayModel.p2p_time).
            if dense_reply_bytes:
                reply_bytes = jnp.full((K,), dense_reply_bytes, i64)
            else:
                reply_bytes = (reply_nnz * 8).astype(i64)
            down_times = latency + reply_bytes * link_factors[perm] / bandwidth
            starts = server_time + down_times

        (key, alpha, residual, payload, applied, arrival, seq,
         seq_ctr), launch_bytes = launch(
            (s["key"], s["alpha"], s["residual"], s["payload"], s["applied"],
             s["arrival"], s["seq"], s["seq_ctr"], ref_buf, ref_len, w_local,
             need, perm, starts, dur_row),
            initial=False)

        s.update(key=key, w_server=w_server, dw_tilde=dw_tilde,
                 w_local=w_local, alpha=alpha, alpha_applied=alpha_applied,
                 residual=residual, payload=payload, applied=applied,
                 ref_buf=ref_buf, ref_len=ref_len, arrival=arrival, seq=seq,
                 seq_ctr=seq_ctr)
        ys = (w_server, alpha_applied, perm, reply_bytes, launch_bytes)
        return s, ys

    state, ys = jax.lax.scan(round_step, state,
                             (needs, durations[1:]), length=length)
    state["init_bytes"] = init_bytes
    return state, ys


@partial(jax.jit,
         static_argnames=("loss", "num_steps", "comp", "length", "lag_window",
                          "dense_reply_bytes"))
def _lag_scan(key, X, y, norms_sq, lam, n, sigma_p, gamma, xi, durations,
              needs, up_bytes, heartbeat_bytes, latency,
              bandwidth, link_factors, *, loss, num_steps, comp, length,
              lag_window, dense_reply_bytes):
    """One LAG run = one dispatch (jit over :func:`lag_run_traced`)."""
    STATS["lag_traces"] += 1  # trace-time side effect, not per call
    return lag_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma, xi,
                          durations, needs, up_bytes, heartbeat_bytes,
                          latency, bandwidth, link_factors, loss=loss,
                          num_steps=num_steps, comp=comp, length=length,
                          lag_window=lag_window,
                          dense_reply_bytes=dense_reply_bytes)


def lag_needs(method: MethodConfig, K: int, num_rounds: int) -> np.ndarray:
    """Per-round arrival counts of a LAG run (B-of-K + T-periodic barrier)."""
    T = method.T
    return np.asarray([K if r % T == T - 1 else min(method.B, K)
                       for r in range(num_rounds)], np.int64)


def lag_durations(method: MethodConfig, cluster: ClusterModel, *,
                  num_rounds: int, seed: int):
    """Pre-sample a LAG run's compute stream; returns (durations, delay).

    Row 0 feeds the t=0 launch wave, row 1+r feeds round r -- exactly the
    event executor's one-sample_round-per-_launch_workers consumption.
    Raises when the delay model cannot pre-sample a (round, worker) stream
    (callers normally check :func:`scan_supported` first).
    """
    delay = cluster.make_delay()
    rng = np.random.default_rng(seed)
    durations = delay.sample_stream(num_rounds + 1, method.H, rng,
                                    lockstep=False)
    if durations is None:
        raise ValueError(
            f"delay model {cluster.delay_model!r} cannot pre-sample a "
            f"(round, worker) stream; use executor='event'")
    return durations, delay


def lag_accounts(needs, T: int, durations, link_factors, latency: float,
                 bandwidth: float, init_bytes, order, reply_bytes,
                 launch_bytes) -> list[RoundAccount]:
    """One lag run's RoundAccounts, replayed on the host from the device's
    decisions (:func:`lag_run_traced`): per round the pop ``order``, each
    rank's ``reply_bytes`` and each launch's upload bytes.

    The replay is the event executor's float64 arithmetic in its order
    (reply billing, compute, upload, per arrival), so the clocks match it
    bit for bit on any backend.  It re-derives the pop order from its own
    IEEE arrival times and raises if the device ever popped differently --
    a TPU's emulated float64 could only reorder a near tie.
    """
    K = len(link_factors)
    lf = np.asarray(link_factors, np.float64)
    durations = np.asarray(durations, np.float64)
    order, reply_bytes, launch_bytes = (
        np.asarray(a) for a in (order, reply_bytes, launch_bytes))
    init_bytes = np.asarray(init_bytes)
    arrival = np.zeros(K)
    seq = np.zeros(K, np.int64)
    bu = bd = 0
    ct = cm = 0.0
    for k in range(K):  # the t=0 wave: every worker, worker order
        nb = int(init_bytes[k])
        up_t = latency + nb * lf[k] / bandwidth
        ct += durations[0, k]
        cm += up_t
        bu += nb
        arrival[k] = 0.0 + durations[0, k] + up_t
        seq[k] = k + 1
    seq_ctr = K
    rounds = []
    for r, need in enumerate(int(x) for x in needs):
        popped = np.lexsort((seq, arrival))[:need]
        if not np.array_equal(popped, order[r, :need]):
            raise RuntimeError(
                f"round {r}: the device popped workers "
                f"{order[r, :need].tolist()} but the float64 arrival times "
                f"order them {popped.tolist()}; run it with "
                f"executor='event'")
        server_time = arrival[popped[-1]]
        for j, k in enumerate(popped):
            rb = int(reply_bytes[r, j])
            down_t = latency + rb * lf[k] / bandwidth
            bd += rb
            cm += down_t
            ct += durations[r + 1, k]
            nb = int(launch_bytes[r, j])
            up_t = latency + nb * lf[k] / bandwidth
            cm += up_t
            bu += nb
            arrival[k] = server_time + down_t + durations[r + 1, k] + up_t
            seq[k] = seq_ctr + j + 1
        seq_ctr += need
        rounds.append(RoundAccount(need, r % T == T - 1, float(server_time),
                                   bu, bd, float(ct), float(cm)))
    return rounds


def _run_lag(problem, method, cluster, *, num_outer, seed, eval_every,
             norms_sq):
    K, n_k, d = problem.X.shape
    T = method.T
    R = num_outer * T
    durations, delay = lag_durations(method, cluster, num_rounds=R, seed=seed)
    needs = lag_needs(method, K, R)
    comp = compress_lib.for_method(method, d)
    dense = isinstance(comp, compress_lib.Dense)
    up_bytes = comp.wire_bytes(d)
    sigma_p = method.resolved_sigma_prime(K)
    if R == 0:
        dt = problem.X.dtype
        return ScanRun(method, [], [], None, None, jnp.zeros((d,), dt),
                       jnp.zeros((K, n_k), dt),
                       alpha_applied=jnp.zeros((K, n_k), dt))

    STATS["lag_calls"] += 1
    with jax.enable_x64(True):
        state, ys = _lag_scan(
            jax.random.key(seed), problem.X, problem.y, norms_sq,
            jnp.float32(problem.lam), jnp.int32(K * n_k),
            jnp.float32(sigma_p), jnp.float32(method.gamma),
            jnp.float32(method.lag_xi),
            jnp.asarray(durations, jnp.float64),
            jnp.asarray(needs, jnp.int64),
            jnp.asarray(up_bytes, jnp.int64),
            jnp.asarray(engine.LagProtocol.HEARTBEAT_BYTES, jnp.int64),
            jnp.asarray(cluster.latency, jnp.float64),
            jnp.asarray(cluster.bandwidth, jnp.float64),
            jnp.asarray(delay.link_factors(), jnp.float64),
            loss=problem.loss, num_steps=method.H, comp=comp, length=R,
            lag_window=method.lag_window,
            dense_reply_bytes=d * 4 if dense else 0)

    ws, alpha_applied_rows, order, reply_bytes, launch_bytes = ys
    rounds = lag_accounts(needs, T, durations, delay.link_factors(),
                          cluster.latency, cluster.bandwidth,
                          state["init_bytes"], order, reply_bytes,
                          launch_bytes)
    evals = _eval_indices(R, eval_every)
    idx = jnp.asarray(evals, jnp.int32)
    return ScanRun(method, rounds, evals, ws[idx], alpha_applied_rows[idx],
                   state["w_server"], state["alpha"],
                   alpha_applied=state["alpha_applied"])


# ---------------------------------------------------------------------------
# partial_work path: the per-CHUNK B-of-K event queue in-graph.
# ---------------------------------------------------------------------------


def partial_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma, durations,
                       needs, up_bytes, latency, bandwidth, link_factors, *,
                       loss, chunk_steps, comp, length, dense_reply_bytes):
    """The whole partial_work run as a traced computation.

    The lag scan's per-worker arrival/seq carries generalize to per-CHUNK
    ``(K, C)`` state: every in-flight chunk's payload, dual snapshot, arrival
    time and sequence number live in the carry, alongside a ``harvested``
    mask marking chunks the server already folded in.  Each round:

    * the round deadline is the ``need``-th FULL arrival -- a lexicographic
      ``lax.sort`` over the final chunks' ``(arrival, seq)`` keys (without an
      elastic membership schedule every worker always has its final chunk in
      flight, so the per-round pop counts are the host-computable
      ``lag_needs`` stream and scan eligibility holds);
    * every un-harvested chunk whose key is lex-<= the deadline key is
      aggregated, in global arrival order (a flattened ``K*C`` lex sort
      driving a where-masked ``fori_loop``, so the float summation order is
      exactly the event heap's pop order -- masked-out entries select the old
      accumulator rather than adding zeros, keeping the op stream identical);
    * only the ``need`` COMPLETED workers get catch-up replies and relaunch
      (the event path's ``_server_apply_partial`` + ``_launch_chunks`` op
      sequence: per rank, reply billing then per-chunk compute/up billing,
      one PRNG split per chunk, j-major chunk-minor).

    Must be traced under ``enable_x64`` like the lag path; model math stays
    float32, so the trajectory is bit-identical to the event executor's
    (pinned by tests/test_partial_work.py).
    """
    K, n_k, d = X.shape
    dt = X.dtype
    f64 = jnp.float64
    i64 = jnp.int64
    C = len(chunk_steps)
    KC = K * C
    iota = jnp.arange(K, dtype=i64)
    kiota = jnp.arange(KC, dtype=i64)

    def launch(args, *, initial):
        """Rank-scan relaunching whole chunked passes for the first ``need``
        ranks of ``order`` (the completed workers, final-arrival order)."""
        (key, alpha, residual, payload, snaps, arrival, seq, harvested,
         seq_ctr, bytes_up, bytes_down, compute_t, comm_t, w_local, need,
         order, starts, reply_bytes, down_times, dur_wave) = args

        def do_launch(carry, xs):
            (key, alpha, residual, payload, snaps, arrival, seq, harvested,
             compute_t, comm_t, bytes_up, bytes_down) = carry
            j, k, start, rbytes, down_t = xs
            # Host accounting replica: reply billing first, then per chunk
            # compute/up billing (the event loop's float accumulation order).
            bytes_down = bytes_down + rbytes
            comm_t = comm_t + down_t
            up_t = latency + up_bytes * link_factors[k] / bandwidth
            alpha_k, res_k = alpha[k], residual[k]
            t = start
            pays, snps, arrs, seqs = [], [], [], []
            for c, h in enumerate(chunk_steps):
                key, alpha_k, res_k, _, sent = engine._local_round(
                    key, w_local, alpha_k, res_k, X, y[k], norms_sq[k],
                    k, lam, n, sigma_p, gamma, loss=loss, num_steps=h,
                    comp=comp)
                dur = dur_wave[c, k]
                compute_t = compute_t + dur
                comm_t = comm_t + up_t
                bytes_up = bytes_up + up_bytes
                t = t + dur
                pays.append(sent)
                snps.append(alpha_k)
                arrs.append(t + up_t)
                seqs.append(seq_ctr + j * C + c + 1)
            alpha = alpha.at[k].set(alpha_k)
            residual = residual.at[k].set(res_k)
            payload = payload.at[k].set(jnp.stack(pays))
            snaps = snaps.at[k].set(jnp.stack(snps))
            arrival = arrival.at[k].set(jnp.stack(arrs))
            seq = seq.at[k].set(jnp.stack(seqs))
            harvested = harvested.at[k].set(jnp.zeros((C,), bool))
            return (key, alpha, residual, payload, snaps, arrival, seq,
                    harvested, compute_t, comm_t, bytes_up, bytes_down), None

        def no_op(carry, xs):
            return carry, None

        def rank_body(carry, xs):
            return jax.lax.cond(xs[0] < need, do_launch, no_op, carry, xs)

        init = (key, alpha, residual, payload, snaps, arrival, seq,
                harvested, compute_t, comm_t, bytes_up, bytes_down)
        if initial:
            # No ambiguity on the first launch: every worker, worker order.
            out, _ = jax.lax.scan(do_launch, init,
                                  (iota, order, starts, reply_bytes,
                                   down_times))
        else:
            out, _ = jax.lax.scan(rank_body, init,
                                  (iota, order, starts, reply_bytes,
                                   down_times))
        (key, alpha, residual, payload, snaps, arrival, seq, harvested,
         compute_t, comm_t, bytes_up, bytes_down) = out
        return (key, alpha, residual, payload, snaps, arrival, seq,
                harvested, seq_ctr + need * C, bytes_up, bytes_down,
                compute_t, comm_t)

    # --- initial state + the t=0 launch wave ------------------------------
    zero64 = jnp.zeros((), f64)
    state = dict(
        key=key,
        w_server=jnp.zeros((d,), dt),
        dw_tilde=jnp.zeros((K, d), dt),
        w_local=jnp.zeros((K, d), dt),
        alpha=jnp.zeros((K, n_k), dt),
        alpha_applied=jnp.zeros((K, n_k), dt),
        residual=jnp.zeros((K, d), dt),
        payload=jnp.zeros((K, C, d), dt),
        snaps=jnp.zeros((K, C, n_k), dt),
        arrival=jnp.zeros((K, C), f64),
        seq=jnp.zeros((K, C), i64),
        harvested=jnp.zeros((K, C), bool),
        seq_ctr=jnp.zeros((), i64),
        bytes_up=jnp.zeros((), i64),
        bytes_down=jnp.zeros((), i64),
        compute_t=zero64,
        comm_t=zero64,
        sim_time=zero64,
    )
    (state["key"], state["alpha"], state["residual"], state["payload"],
     state["snaps"], state["arrival"], state["seq"], state["harvested"],
     state["seq_ctr"], state["bytes_up"], state["bytes_down"],
     state["compute_t"], state["comm_t"]) = launch(
        (state["key"], state["alpha"], state["residual"], state["payload"],
         state["snaps"], state["arrival"], state["seq"], state["harvested"],
         state["seq_ctr"], state["bytes_up"], state["bytes_down"],
         state["compute_t"], state["comm_t"], state["w_local"],
         jnp.asarray(K, i64), iota, jnp.zeros((K,), f64),
         jnp.zeros((K,), i64), jnp.zeros((K,), f64), durations[0]),
        initial=True)

    # --- the round loop ---------------------------------------------------

    def round_step(carry, xs):
        s = dict(carry)
        need, dur_wave = xs
        need = need.astype(i64)
        with jax.named_scope(tracing.SERVER_APPLY):
            # Deadline: the need-th FULL arrival, lex (arrival, seq) -- the
            # host heap's order over final chunks (always in flight, see
            # above).
            arr_fin = s["arrival"][:, C - 1]
            seq_fin = s["seq"][:, C - 1]
            _, _, perm = jax.lax.sort((arr_fin, seq_fin, iota), num_keys=2)
            sorted_arr = arr_fin[perm]
            sorted_seq = seq_fin[perm]
            server_time = sorted_arr[need - 1]
            cut_s = sorted_seq[need - 1]
            # Harvest: every pending chunk at or before the deadline key.
            take = ~s["harvested"] & (
                (s["arrival"] < server_time)
                | ((s["arrival"] == server_time) & (s["seq"] <= cut_s)))

            # Aggregation in global arrival order over the harvested chunks:
            # flattened lex sort, where-masked accumulation (event pop order).
            _, _, fperm = jax.lax.sort(
                (s["arrival"].reshape(KC), s["seq"].reshape(KC), kiota),
                num_keys=2)
            take_f = take.reshape(KC)
            pay_f = s["payload"].reshape(KC, d)

            def agg(j, tot):
                p = fperm[j]
                return jnp.where(take_f[p], tot + pay_f[p], tot)

            total = jax.lax.fori_loop(0, KC, agg, jnp.zeros((d,), dt))
            w_server = s["w_server"] + gamma * total
            dw_tilde = s["dw_tilde"] + gamma * total[None, :]

            # alpha_applied: each harvesting worker's LAST harvested chunk.
            any_k = jnp.any(take, axis=1)
            last = (C - 1) - jnp.argmax(take[:, ::-1], axis=1)
            snap_last = s["snaps"][jnp.arange(K), last]
            alpha_applied = jnp.where(any_k[:, None], snap_last,
                                      s["alpha_applied"])

            # Catch-up replies to the `need` COMPLETED workers only (the event
            # path's _server_apply_partial op order: replies read dw_tilde
            # AFTER this round's harvest landed).
            sel = iota < need
            replies = dw_tilde[perm]
            reply_nnz = jnp.sum(replies != 0, axis=1)
            w_rows = s["w_local"][perm]
            w_local = s["w_local"].at[perm].set(
                jnp.where(sel[:, None], w_rows + replies, w_rows))
            dw_tilde = dw_tilde.at[perm].set(
                jnp.where(sel[:, None], jnp.zeros_like(replies),
                          dw_tilde[perm]))

            # Reply billing per rank (same arithmetic as DelayModel.p2p_time).
            if dense_reply_bytes:
                reply_bytes = jnp.full((K,), dense_reply_bytes, i64)
            else:
                reply_bytes = (reply_nnz * 8).astype(i64)
            factors = link_factors[perm]
            down_times = latency + reply_bytes * factors / bandwidth
            starts = server_time + down_times

        harvested = s["harvested"] | take
        (key, alpha, residual, payload, snaps, arrival, seq, harvested,
         seq_ctr, bytes_up, bytes_down, compute_t, comm_t) = launch(
            (s["key"], s["alpha"], s["residual"], s["payload"], s["snaps"],
             s["arrival"], s["seq"], harvested, s["seq_ctr"], s["bytes_up"],
             s["bytes_down"], s["compute_t"], s["comm_t"], w_local, need,
             perm, starts, reply_bytes, down_times, dur_wave),
            initial=False)

        s.update(key=key, w_server=w_server, dw_tilde=dw_tilde,
                 w_local=w_local, alpha=alpha, alpha_applied=alpha_applied,
                 residual=residual, payload=payload, snaps=snaps,
                 arrival=arrival, seq=seq, harvested=harvested,
                 seq_ctr=seq_ctr, bytes_up=bytes_up, bytes_down=bytes_down,
                 compute_t=compute_t, comm_t=comm_t, sim_time=server_time)
        ys = (w_server, alpha_applied, server_time, bytes_up, bytes_down,
              compute_t, comm_t, jnp.sum(take).astype(i64))
        return s, ys

    state, ys = jax.lax.scan(round_step, state,
                             (needs, durations[1:]), length=length)
    return state, ys


@partial(jax.jit,
         static_argnames=("loss", "chunk_steps", "comp", "length",
                          "dense_reply_bytes"))
def _partial_scan(key, X, y, norms_sq, lam, n, sigma_p, gamma, durations,
                  needs, up_bytes, latency, bandwidth, link_factors, *, loss,
                  chunk_steps, comp, length, dense_reply_bytes):
    """One partial_work run = one dispatch (jit over
    :func:`partial_run_traced`)."""
    STATS["partial_traces"] += 1  # trace-time side effect, not per call
    return partial_run_traced(key, X, y, norms_sq, lam, n, sigma_p, gamma,
                              durations, needs, up_bytes, latency, bandwidth,
                              link_factors, loss=loss,
                              chunk_steps=chunk_steps, comp=comp,
                              length=length,
                              dense_reply_bytes=dense_reply_bytes)


def partial_durations(method: MethodConfig, cluster: ClusterModel, *,
                      num_rounds: int, seed: int):
    """Pre-sample a partial_work run's per-chunk compute stream; returns
    ``(durations (num_rounds+1, C, K), delay)``.

    Row 0 feeds the t=0 launch wave, row 1+r feeds round r -- exactly the
    event executor's one-``sample_chunks``-per-``_launch_chunks``
    consumption (without a membership schedule every round launches, so the
    wave count is static).  Raises when the delay model cannot pre-sample
    (callers normally check :func:`scan_supported` first).
    """
    steps = engine.chunk_steps(method.H, method.n_chunks)
    delay = cluster.make_delay()
    rng = np.random.default_rng(seed)
    durations = delay.sample_chunk_stream(num_rounds + 1, steps, rng)
    if durations is None:
        raise ValueError(
            f"delay model {cluster.delay_model!r} cannot pre-sample a "
            f"(round, chunk, worker) stream; use executor='event'")
    return durations, delay


def _run_partial(problem, method, cluster, *, num_outer, seed, eval_every,
                 norms_sq):
    K, n_k, d = problem.X.shape
    T = method.T
    R = num_outer * T
    if R == 0:
        dt = problem.X.dtype
        return ScanRun(method, [], [], None, None, jnp.zeros((d,), dt),
                       jnp.zeros((K, n_k), dt),
                       alpha_applied=jnp.zeros((K, n_k), dt))
    durations, delay = partial_durations(method, cluster, num_rounds=R,
                                         seed=seed)
    # Relaunch counts are the lag stream: the round deadline is the B-th
    # full arrival (K on the T-periodic barrier) and, membership-free, the
    # completed-worker count IS the deadline rank.
    needs = lag_needs(method, K, R)
    comp = compress_lib.for_method(method, d)
    dense = isinstance(comp, compress_lib.Dense)
    up_bytes = comp.wire_bytes(d)
    sigma_p = method.resolved_sigma_prime(K)

    STATS["partial_calls"] += 1
    with jax.enable_x64(True):
        state, ys = _partial_scan(
            jax.random.key(seed), problem.X, problem.y, norms_sq,
            jnp.float32(problem.lam), jnp.int32(K * n_k),
            jnp.float32(sigma_p), jnp.float32(method.gamma),
            jnp.asarray(durations, jnp.float64),
            jnp.asarray(needs, jnp.int64),
            jnp.asarray(up_bytes, jnp.int64),
            jnp.asarray(cluster.latency, jnp.float64),
            jnp.asarray(cluster.bandwidth, jnp.float64),
            jnp.asarray(delay.link_factors(), jnp.float64),
            loss=problem.loss,
            chunk_steps=engine.chunk_steps(method.H, method.n_chunks),
            comp=comp, length=R, dense_reply_bytes=d * 4 if dense else 0)

    ws, alpha_applied_rows, sim, bu, bd, ct, cm, harv = ys
    sim, ct, cm = np.asarray(sim), np.asarray(ct), np.asarray(cm)
    bu, bd, harv = np.asarray(bu), np.asarray(bd), np.asarray(harv)
    rounds = [RoundAccount(int(harv[r]), r % T == T - 1, float(sim[r]),
                           int(bu[r]), int(bd[r]), float(ct[r]),
                           float(cm[r]))
              for r in range(R)]
    evals = _eval_indices(R, eval_every)
    idx = jnp.asarray(evals, jnp.int32)
    return ScanRun(method, rounds, evals, ws[idx], alpha_applied_rows[idx],
                   state["w_server"], state["alpha"],
                   alpha_applied=state["alpha_applied"])


# ---------------------------------------------------------------------------
# Divergence certificates + checkpointed lockstep runs (PR 9).
# ---------------------------------------------------------------------------


@jax.jit
def _finite_cells(ws, alphas):
    """Per-cell finiteness over stacked final iterates: (C, ...) -> (C,)."""
    fw = jnp.isfinite(ws).reshape(ws.shape[0], -1).all(axis=1)
    fa = jnp.isfinite(alphas).reshape(alphas.shape[0], -1).all(axis=1)
    return fw & fa


def finite_certificates(variants) -> np.ndarray:
    """Per-cell finite certificates over sweep results.

    ONE jitted reduction over the stacked per-cell final ``(w, alpha)``
    (the compute-and-mask idiom of :func:`lockstep_run_gap_traced`, applied
    across the cell axis): a NaN-poisoned cell only corrupts its own vmap
    lane, so the batch itself completes -- this certificate tells the serve
    layer which cells to mask out of delivery and report per-cell
    (``CellDivergenceError``) instead of failing the whole cohort.

    A deliberately SEPARATE tiny jit: folding the certificate into the
    sweep computation would change the batched jit signatures that
    :func:`repro.serve.cache.sweep_cache_key` mirrors and every trace
    counter pin in tests/test_sweep.py.
    """
    ws = jnp.stack([jnp.asarray(v.result.w) for v in variants])
    alphas = jnp.stack([jnp.asarray(v.result.alpha) for v in variants])
    return np.asarray(_finite_cells(ws, alphas))


def checkpoint_supported(method: MethodConfig, cluster: ClusterModel, *,
                         target_gap: float | None = None,
                         time_budget: float | None = None) -> tuple[bool, str]:
    """Can this run checkpoint/resume bit-identically?  (ok, why-not).

    Checkpointed runs execute as fixed-length scan SEGMENTS
    (:func:`run_lockstep_checkpointed`), so they need the lockstep scan
    path with a static round count: early stop makes the segment boundary
    data-dependent, and the non-lockstep scan protocols thread pre-sampled
    whole-run operand streams (lag durations, partial_work chunk grids)
    whose mid-run state is not a small carry.
    """
    if method.exact_dual_feedback:
        return False, ("exact_dual_feedback needs a host lstsq per round "
                       "(reference path only)")
    if target_gap is not None or time_budget is not None:
        return False, ("early stop (target_gap/time_budget) makes the "
                       "checkpoint boundary data-dependent; run without a "
                       "stop target to checkpoint")
    if method.protocol not in LOCKSTEP_PROTOCOLS:
        return False, (
            f"checkpoint segments scan from a (key, w, alpha) carry, which "
            f"only the lockstep protocols {LOCKSTEP_PROTOCOLS} expose; "
            f"{method.protocol!r} threads whole-run operand streams")
    return True, ""


def lockstep_segment_traced(key, w, alpha, X, y, norms_sq, lam, n, sigma_p,
                            gamma, *, loss, num_steps, solver, length):
    """``length`` lockstep rounds scanned FROM a given ``(key, w, alpha)``
    carry (vs :func:`lockstep_run_traced`'s zero init): the resumable unit
    of a checkpointed run.  The round body is the same shared
    ``engine._lockstep_round``, and ``lax.scan`` is sequential in the
    carry, so chaining segments is bit-identical to one whole scan."""

    def step(carry, _):
        key, w, alpha = carry
        key, w, alpha = engine._lockstep_round(
            key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma, loss=loss,
            num_steps=num_steps, solver=solver)
        return (key, w, alpha), (w, alpha)

    (key, w, alpha), (ws, alphas) = jax.lax.scan(
        step, (key, w, alpha), None, length=length)
    return key, w, alpha, ws, alphas


@partial(jax.jit, static_argnames=("loss", "num_steps", "solver", "length"))
def _lockstep_segment_scan(key, w, alpha, X, y, norms_sq, lam, n, sigma_p,
                           gamma, *, loss, num_steps, solver, length):
    STATS["lockstep_segment_traces"] += 1  # trace-time side effect
    return lockstep_segment_traced(key, w, alpha, X, y, norms_sq, lam, n,
                                   sigma_p, gamma, loss=loss,
                                   num_steps=num_steps, solver=solver,
                                   length=length)


def checkpoint_run_id(problem, method: MethodConfig, cluster: ClusterModel,
                      *, seed: int, num_outer: int, eval_every: int) -> str:
    """Stable per-run subdirectory name: a digest of everything that shapes
    the run's trajectory.  Resuming under a different configuration would
    silently splice two different runs; the id check makes that loud."""
    sig = (dataclasses.asdict(method), dataclasses.asdict(cluster),
           tuple(problem.X.shape), str(problem.X.dtype), problem.loss,
           float(problem.lam), int(seed), int(num_outer), int(eval_every))
    return f"run_{zlib.crc32(repr(sig).encode()):08x}"


def checkpoint_manifest(checkpoint_dir, run_id: str) -> dict | None:
    """The latest durable snapshot manifest of run ``run_id``, or ``None``.

    The cluster takeover path (:mod:`repro.serve.cluster`): a surviving
    replica inspecting a dead peer's progress must learn the resume point
    WITHOUT deserializing the array payload -- it only needs to know whether
    re-running :func:`run_lockstep_checkpointed` with the same arguments
    will resume rather than restart.  Reads only the json sidecar, which
    :func:`repro.checkpoint.checkpoint.save_checkpoint` makes durable
    *before* the ``.npz`` becomes visible, so any round this returns is
    loadable.  Returns ``{"run", "round", "seed", "num_outer",
    "eval_every", "sim_time", "path"}``; ``None`` when no snapshot exists
    (takeover then restarts the run from round 0 -- still bit-identical,
    just slower)."""
    from repro.checkpoint import checkpoint as ckpt_lib

    cdir = pathlib.Path(checkpoint_dir) / run_id
    latest = ckpt_lib.latest_step(cdir)
    if latest is None:
        return None
    try:
        manifest = json.loads((cdir / f"ckpt_{latest:08d}.json").read_text())
    except (OSError, ValueError):
        return None
    extra = dict(manifest.get("extra", {}))
    extra.setdefault("run", run_id)
    extra.setdefault("round", int(manifest.get("step", latest)))
    extra["path"] = str(cdir)
    return extra


def run_lockstep_checkpointed(problem, method: MethodConfig,
                              cluster: ClusterModel, *, num_outer: int,
                              seed: int, eval_every: int, checkpoint_dir,
                              checkpoint_every: int, norms_sq=None,
                              segment_hook=None) -> ScanRun:
    """A lockstep run executed in resumable segments of ``checkpoint_every``
    rounds, serializing the scan carry after every segment.

    After each segment the carry (RNG key data, ``w``, ``alpha``) plus the
    eval-boundary snapshots gathered so far land in
    ``checkpoint_dir/<run id>/ckpt_<round>.npz``
    (:mod:`repro.checkpoint`); a killed process re-invoked with the same
    arguments resumes from the latest snapshot and executes ONLY the
    remaining segments.  Bit-identity with the unsegmented
    :func:`_run_lockstep` run holds by construction: segments chain the
    sequential scan carry exactly, host accounting is recomputed
    deterministically from ``seed``, and ALL certificate evaluation stays
    deferred to one bucketed call over the identical stacked snapshots at
    ``materialize_records`` time.

    ``segment_hook(start_round)`` is called before each segment executes --
    the serve layer wires fault injection (``kind="segment"``) through it,
    and a hook that raises kills the run AFTER the previous segment's
    checkpoint was durably written.
    """
    if checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}")
    ok, why = checkpoint_supported(method, cluster)
    if not ok:
        raise ValueError(f"run cannot checkpoint: {why}")
    from repro.checkpoint import checkpoint as ckpt_lib

    if norms_sq is None:
        norms_sq = jnp.sum(problem.X * problem.X, axis=-1)
    K, n_k, d = problem.X.shape
    dt = problem.X.dtype
    R = num_outer
    if R == 0:
        return ScanRun(method, [], [], None, None, jnp.zeros((d,), dt),
                       jnp.zeros((K, n_k), dt))
    run_id = checkpoint_run_id(problem, method, cluster, seed=seed,
                               num_outer=R, eval_every=eval_every)
    cdir = pathlib.Path(checkpoint_dir) / run_id
    evals = _eval_indices(R, eval_every)
    rounds = lockstep_accounts(method, cluster, d, num_rounds=R, seed=seed)
    sigma_p = method.resolved_sigma_prime(K)
    solver = lockstep_solver(method)

    key = jax.random.key(seed)
    key_dt = jax.random.key_data(key).dtype
    key_shape = jax.random.key_data(key).shape
    w = jnp.zeros((d,), dt)
    alpha = jnp.zeros((K, n_k), dt)
    snap_ws: list = []  # eval-boundary snapshots gathered so far
    snap_alphas: list = []
    start = 0

    latest = ckpt_lib.latest_step(cdir)
    if latest is not None:
        if not 0 < latest <= R:
            raise ValueError(
                f"checkpoint at round {latest} is outside this run's "
                f"budget of {R} rounds ({cdir})")
        n_done = sum(1 for e in evals if e < latest)
        reference = {
            "key": np.zeros(key_shape, key_dt),
            "w": np.zeros((d,), dt),
            "alpha": np.zeros((K, n_k), dt),
            "eval_ws": np.zeros((n_done, d), dt),
            "eval_alphas": np.zeros((n_done, K, n_k), dt),
        }
        tree, extra = ckpt_lib.load_checkpoint(cdir, reference, latest)
        if extra.get("run") != run_id or extra.get("round") != latest:
            raise ValueError(
                f"checkpoint manifest under {cdir} does not match this run "
                f"(expected run={run_id!r} round={latest}, got "
                f"run={extra.get('run')!r} round={extra.get('round')!r})")
        key = jax.random.wrap_key_data(jnp.asarray(tree["key"]))
        w = jnp.asarray(tree["w"])
        alpha = jnp.asarray(tree["alpha"])
        if n_done:
            snap_ws.append(jnp.asarray(tree["eval_ws"]))
            snap_alphas.append(jnp.asarray(tree["eval_alphas"]))
        start = latest

    def stacked():
        if not snap_ws:
            return (jnp.zeros((0, d), dt), jnp.zeros((0, K, n_k), dt))
        if len(snap_ws) == 1:
            return snap_ws[0], snap_alphas[0]
        return jnp.concatenate(snap_ws), jnp.concatenate(snap_alphas)

    while start < R:
        if segment_hook is not None:
            segment_hook(start)
        length = min(checkpoint_every, R - start)
        STATS["lockstep_segment_calls"] += 1
        key, w, alpha, ws, alphas = _lockstep_segment_scan(
            key, w, alpha, problem.X, problem.y, norms_sq, problem.lam,
            K * n_k, sigma_p, method.gamma, loss=problem.loss,
            num_steps=method.H, solver=solver, length=length)
        seg_evals = [e - start for e in evals if start <= e < start + length]
        if seg_evals:
            idx = jnp.asarray(seg_evals, jnp.int32)
            snap_ws.append(ws[idx])
            snap_alphas.append(alphas[idx])
        start += length
        eval_ws, eval_alphas = stacked()
        ckpt_lib.save_checkpoint(
            cdir, start,
            {"key": jax.random.key_data(key), "w": w, "alpha": alpha,
             "eval_ws": eval_ws, "eval_alphas": eval_alphas},
            extra={"run": run_id, "round": start, "seed": int(seed),
                   "num_outer": int(R), "eval_every": int(eval_every),
                   "sim_time": rounds[start - 1].sim_time})

    eval_ws, eval_alphas = stacked()
    if not evals:
        eval_ws = eval_alphas = None
    return ScanRun(method, rounds, evals, eval_ws, eval_alphas, w, alpha)
