"""Pluggable event-driven protocol engine for distributed primal-dual methods.

One priority-queue server loop, parameterized by a :class:`Protocol` that
supplies the three rules the paper's Algorithm 1 fixes ad hoc:

* **arrival rule**   -- how many worker messages the server waits for
  (``B`` of ``K`` for the group protocol, all ``K`` for synchronous methods,
  1 for fully-asynchronous operation);
* **aggregation rule** -- how arrived payloads enter the server state
  (catch-up buffers ``dw_tilde`` for the group family, plain allreduce-style
  summation for the CoCoA lineage);
* **reply rule**     -- what goes back to each worker and how it is timed
  and billed (p2p catch-up replies vs one ring all-reduce).

Protocols are registry entries (:func:`register_protocol`), so new server
disciplines -- e.g. LAG-style lazy aggregation (Chen et al., arXiv:1805.09965)
-- are ~50-line configs instead of forks of the loop.  Shipped entries:
``group``/``sync`` (the paper's disciplines, bit-for-bit pinned), ``async``,
``lag`` (D-window lazy uploads), ``cocoa``/``cocoa_plus`` (CoCoA lineage,
arXiv:1409.1458, pluggable :mod:`repro.core.solvers` local solver) and
``adaptive_b`` (group size learned from arrival quantiles).  Worker timing is
itself pluggable: protocols draw compute/message delays from the
:mod:`repro.core.delays` registry via ``ClusterModel.delay_model``, so every
protocol x delay x compressor scenario is one declarative spec.  The
extension walkthrough lives in ``docs/extending-protocols.md``; the contract
every subclass implements is documented on :class:`Protocol`.

Performance contract vs the reference loops in :mod:`repro.core.acpd`:

* a whole GROUP of worker rounds is ONE donated, jitted dispatch
  (:func:`_worker_rounds_fused` scans the arrived workers with the same
  unbatched per-worker ops and sequential PRNG split chain, so a B-message
  relaunch costs one dispatch instead of B);
* each server round is ONE jitted dispatch (aggregation + catch-up replies +
  reply ``nnz`` computed in-graph) followed by a single scalar pull for the
  byte accounting -- the reference does a blocking ``int(nnz(...))`` per
  message;
* host-side delay sampling is vectorized: delay models flagged
  ``vector_sampled`` draw ONE size-K numpy vector per round
  (:meth:`repro.core.delays.DelayModel.sample_round`) instead of per-message
  scalars.  The pinned trajectories (``constant`` delay, the only model the
  reference oracle covers) are unmoved; group-family trajectories under the
  stochastic vectorized models moved with the consumption change (see the
  :mod:`repro.core.delays` docstring) -- both executors stay bit-identical
  to each other;
* duality-gap evaluation is deferred: snapshots of ``(w, alpha)`` device
  arrays are collected during simulation and evaluated afterwards (one
  ``lax.map`` dispatch, padded to power-of-two snapshot buckets so sweeps
  with different round budgets reuse one compile -- NOT vmap, which would
  break bit-exactness; see ``_eval_batched``/``_eval_bucketed`` -- or
  op-for-op identical to the reference with ``eval_mode="replay"``).

This module is the per-round EVENT backend.  Runs without host-adaptive
control flow can skip per-round dispatch entirely: the scan-fused executor
(:mod:`repro.core.executor`, ``Session(executor="scan"|"auto")``) compiles
an entire run into one ``lax.scan`` and reproduces this engine bit-for-bit
(docs/performance.md).  ``benchmarks/bench_engine.py`` measures the
dispatch/wall-clock reductions of both layers; ``tests/test_engine.py`` pins
bit-for-bit equality of the ``group``/``sync`` trajectories against the
reference implementation and ``tests/test_executor.py`` pins the executors
against each other across the zoo grid.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress as compress_lib
from repro.core import filter as msg_filter
from repro.core import objectives
from repro.core import tracing
from repro.core.acpd import MethodConfig, RunRecord, RunResult
from repro.core.sdca import solve_subproblem
from repro.core.simulate import ClusterModel

# ---------------------------------------------------------------------------
# Protocol registry.
# ---------------------------------------------------------------------------

_PROTOCOLS: dict[str, type["Protocol"]] = {}


def register_protocol(name: str):
    """Class decorator: make a Protocol constructible via ``MethodConfig.protocol``."""

    def deco(cls: type["Protocol"]) -> type["Protocol"]:
        cls.protocol_name = name
        _PROTOCOLS[name] = cls
        return cls

    return deco


def available_protocols() -> tuple[str, ...]:
    return tuple(sorted(_PROTOCOLS))


def get_protocol(name: str) -> type["Protocol"]:
    try:
        return _PROTOCOLS[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r}; available: {available_protocols()}"
        ) from None


# ---------------------------------------------------------------------------
# Messages and deferred evaluation records.
# ---------------------------------------------------------------------------


class Message:
    """An in-flight worker->server message (payload stays on device)."""

    __slots__ = ("arrival", "worker", "payload", "alpha_snapshot", "nbytes",
                 "seq", "applied", "chunk", "final")

    def __init__(self, arrival: float, worker: int, payload, alpha_snapshot,
                 nbytes: int, seq: int, applied: bool = True,
                 chunk: int = 0, final: bool = True):
        self.arrival = arrival
        self.worker = worker
        self.payload = payload
        self.alpha_snapshot = alpha_snapshot
        self.nbytes = nbytes
        self.seq = seq
        self.applied = applied  # False for LAG heartbeats (skipped uploads)
        self.chunk = chunk  # chunk index within the sender's local pass
        self.final = final  # last chunk of the pass (non-chunked: always)

    def __lt__(self, other: "Message") -> bool:
        return (self.arrival, self.seq) < (other.arrival, other.seq)


def _host_array(x) -> np.ndarray:
    """A round's one blocking device->host read: counted in
    ``tracing.STATS["host_syncs"]`` and spanned as ``repro.engine.sync``."""
    with tracing.span("repro.engine.sync"):
        tracing.STATS["host_syncs"] += 1
        return np.asarray(x)


@dataclasses.dataclass
class _Snapshot:
    """Host-side accounting + device state captured at an eval boundary."""

    iteration: int
    sim_time: float
    bytes_up: int
    bytes_down: int
    compute_time: float
    comm_time: float
    w: jax.Array
    alpha: jax.Array  # (K, n_k) server-visible (group) / canonical (sync)


# ---------------------------------------------------------------------------
# Fused jitted rounds.
# ---------------------------------------------------------------------------


def _local_round(key, w_local, alpha_k, residual_k, X_k, y_k, norms_k, k, lam,
                 n, sigma_p, gamma, *, loss, num_steps, comp):
    """Shared Alg. 2 body: solve + dual update + filter. Traced, not jitted --
    both fused worker rounds inline it so the op sequence (and therefore the
    bit-exact trajectory) is defined in exactly one place. ``comp`` is a
    frozen :mod:`repro.core.compress` registry object (static under jit)."""
    with jax.named_scope(tracing.SOLVE):
        key, sub = jax.random.split(key)
        w_eff = w_local[k] + gamma * residual_k
        dalpha, v = solve_subproblem(
            w_eff, alpha_k, X_k, y_k, norms_k, lam, n, sigma_p, sub,
            loss=loss, num_steps=num_steps)
        alpha_new = alpha_k + gamma * dalpha  # Alg. 2 line 5
        dw = residual_k + v  # line 6
    with jax.named_scope(tracing.FILTER):
        sent, new_residual = comp.compress(dw)
    return key, alpha_new, new_residual, dw, sent


@partial(jax.jit, static_argnames=("loss", "num_steps", "comp"),
         donate_argnums=(0, 2, 3))
def _worker_rounds_fused(key, w_local, alpha, residual, X, y, norms_sq, idxs,
                         lam, n, sigma_p, gamma, *, loss, num_steps, comp):
    """A whole group of local rounds (Alg. 2) as ONE donated dispatch.

    ``idxs`` holds the relaunched workers in arrival order.  The body scans
    over them with the same unbatched per-worker ops (and the same
    sequential global-key split chain) the former one-dispatch-per-worker
    path used, so trajectories stay bit-identical while a B-message relaunch
    costs one dispatch instead of B.  ``alpha``/``residual`` are the stacked
    (K, n_k)/(K, d) worker states; returns them updated plus the per-message
    dual snapshots and compressed payloads, stacked in arrival order.
    """

    @jax.named_scope(tracing.WORKER_STATE)
    def body(carry, k):
        key, alpha, residual = carry
        key, alpha_k, res_k, _, sent = _local_round(
            key, w_local, alpha[k], residual[k], X[k], y[k], norms_sq[k], k,
            lam, n, sigma_p, gamma, loss=loss, num_steps=num_steps, comp=comp)
        carry = (key, alpha.at[k].set(alpha_k), residual.at[k].set(res_k))
        return carry, (alpha_k, sent)

    (key, alpha, residual), (alpha_rows, sents) = jax.lax.scan(
        body, (key, alpha, residual), idxs)
    return key, alpha, residual, alpha_rows, sents


@partial(jax.jit, static_argnames=("loss", "chunk_steps", "comp"),
         donate_argnums=(0, 2, 3))
def _worker_chunk_rounds_fused(key, w_local, alpha, residual, X, y, norms_sq,
                               idxs, lam, n, sigma_p, gamma, *, loss,
                               chunk_steps, comp):
    """A group of CHUNKED local passes (partial_work) as ONE donated dispatch.

    Each launched worker runs ``len(chunk_steps)`` sequential sub-rounds of
    the shared Alg. 2 body against its fixed ``w_local`` row (the model does
    not change mid-pass -- the server only replies at relaunch), carrying its
    dual/residual state from chunk to chunk and compressing EVERY chunk's
    delta independently (residual feedback chains through, so un-harvested
    chunk mass is never lost).  With ``chunk_steps == (H,)`` the op sequence
    -- including the one key split per worker -- degenerates to exactly
    :func:`_worker_rounds_fused`, which the n_chunks=1 bit-identity tests
    pin.  Returns per-worker per-chunk dual snapshots, payloads, and
    post-chunk residuals (``(G, C, n_k)`` / ``(G, C, d)``, arrival order).
    """

    @jax.named_scope(tracing.WORKER_STATE)
    def body(carry, k):
        key, alpha, residual = carry
        alpha_k, res_k = alpha[k], residual[k]
        snaps, sents, resids = [], [], []
        for h in chunk_steps:
            key, alpha_k, res_k, _, sent = _local_round(
                key, w_local, alpha_k, res_k, X[k], y[k], norms_sq[k], k,
                lam, n, sigma_p, gamma, loss=loss, num_steps=h, comp=comp)
            snaps.append(alpha_k)
            sents.append(sent)
            resids.append(res_k)
        carry = (key, alpha.at[k].set(alpha_k), residual.at[k].set(res_k))
        return carry, (jnp.stack(snaps), jnp.stack(sents), jnp.stack(resids))

    (key, alpha, residual), (alpha_rows, sents, resids) = jax.lax.scan(
        body, (key, alpha, residual), idxs)
    return key, alpha, residual, alpha_rows, sents, resids


# Only dw_tilde/w_local are donated: w_server and alpha_applied may be held
# by deferred eval snapshots, which donation would invalidate.
@partial(jax.jit, donate_argnums=(1, 2))
def _server_apply_partial(w_server, dw_tilde, w_local, alpha_applied,
                          snap_idxs, snapshots, payloads, reply_idxs, gamma):
    """Partial-work server round: harvest whatever chunks arrived, reply only
    to the workers being relaunched.

    ``payloads`` is every harvested chunk in arrival order (the summation
    order matters bit-for-bit); ``snap_idxs``/``snapshots`` carry ONE dual
    snapshot per harvested worker -- the host pre-selects each worker's LAST
    harvested chunk so the scatter has unique indices.  ``reply_idxs`` are
    the workers receiving a catch-up reply this round (completed workers in
    final-arrival order, then rejoining members): unlike the group fused
    apply, mid-pass stragglers get NO reply -- their ``dw_tilde`` rows keep
    accruing until their own pass completes.  With one chunk per pass the
    returned values equal :func:`_server_apply_fused` on the same arrivals.
    """
    with jax.named_scope(tracing.SERVER_APPLY):
        total = jnp.zeros_like(w_server)
        for p in payloads:
            total = total + p
        w_server = w_server + gamma * total
        dw_tilde = dw_tilde + gamma * total[None, :]
        if snapshots:
            alpha_applied = alpha_applied.at[snap_idxs].set(
                jnp.stack(list(snapshots)))
        replies = dw_tilde[reply_idxs]
        reply_nnz = jnp.sum(replies != 0, axis=1)
        reply_sq = jnp.sum(replies * replies, axis=1)
        w_local = w_local.at[reply_idxs].add(replies)
        dw_tilde = dw_tilde.at[reply_idxs].set(0.0)
    return w_server, dw_tilde, w_local, alpha_applied, reply_nnz, reply_sq


def _lag_reference(ref_buf_k, ref_len_k, xi):
    """LAG's laziness reference for one worker: the windowed mean of its
    recent catch-up-reply energies, scaled by xi.  Zero-padded fixed-width
    buffer (index < len masks the live entries) so the event and scan
    executors evaluate the identical expression."""
    W = ref_buf_k.shape[0]
    live = jnp.arange(W) < ref_len_k
    total = jnp.sum(jnp.where(live, ref_buf_k, 0.0))
    return xi * total / jnp.maximum(ref_len_k, 1)


@partial(jax.jit, donate_argnums=(0, 1))
def _lag_window_append(ref_buf, ref_len, idxs, reply_sq):
    """Slide this round's reply energies into the arrived workers' windows.

    Fixed-width (K, lag_window) rolling buffers: append at ``len`` while
    filling, shift-left-and-append once full (the deque-with-maxlen
    semantics, expressed as ops both executors share).
    """
    W = ref_buf.shape[1]
    rows = ref_buf[idxs]
    lens = ref_len[idxs]
    full = (lens >= W)[:, None]
    shifted = jnp.where(full, jnp.roll(rows, -1, axis=1), rows)
    pos = jnp.minimum(lens, W - 1)
    new_rows = shifted.at[jnp.arange(idxs.shape[0]), pos].set(reply_sq)
    ref_buf = ref_buf.at[idxs].set(new_rows)
    ref_len = ref_len.at[idxs].set(jnp.minimum(lens + 1, W))
    return ref_buf, ref_len


@partial(jax.jit, static_argnames=("loss", "num_steps", "comp"),
         donate_argnums=(0, 2, 3))
def _worker_rounds_lag_fused(key, w_local, alpha, residual, ref_buf, ref_len,
                             X, y, norms_sq, idxs, lam, n, sigma_p, gamma, xi,
                             *, loss, num_steps, comp):
    """LAG-style lazy group relaunch: one dispatch for the whole group.

    Per worker, the upload is skipped when ``||F(dw)||^2 < xi * ref`` where
    ``ref`` is the windowed mean of the worker's recent catch-up-reply
    energies -- its freshest view of how much the global model is already
    moving without it (the primal-dual analogue of LAG's
    gradient-change-vs-model-movement test). Skipped mass stays in the
    residual: error feedback makes laziness lossless, only late, and since
    replies shrink as the system converges the test stays calibrated
    (all-quiet -> replies ~ 0 -> uploads resume, no starvation).
    """

    @jax.named_scope(tracing.WORKER_STATE)
    def body(carry, k):
        key, alpha, residual = carry
        ref_k = _lag_reference(ref_buf[k], ref_len[k], xi)
        key, alpha_k, res_k, dw, sent = _local_round(
            key, w_local, alpha[k], residual[k], X[k], y[k], norms_sq[k], k,
            lam, n, sigma_p, gamma, loss=loss, num_steps=num_steps, comp=comp)
        send_sq = jnp.vdot(sent, sent, precision=objectives.HIGHEST)
        skip = send_sq < ref_k
        sent = jnp.where(skip, jnp.zeros_like(sent), sent)
        res_k = jnp.where(skip, dw, res_k)
        carry = (key, alpha.at[k].set(alpha_k), residual.at[k].set(res_k))
        return carry, (alpha_k, sent, skip)

    (key, alpha, residual), (alpha_rows, sents, skips) = jax.lax.scan(
        body, (key, alpha, residual), idxs)
    return key, alpha, residual, alpha_rows, sents, skips


# Only dw_tilde/w_local are donated: w_server and alpha_applied may be held
# by deferred eval snapshots, which donation would invalidate.
@partial(jax.jit, donate_argnums=(1, 2))
def _server_apply_fused(w_server, dw_tilde, w_local, alpha_applied, idxs,
                        payloads, snapshots, apply_mask, gamma):
    """Alg. 1 lines 8-11 for one group of arrivals, as a single dispatch.

    ``payloads``/``snapshots`` are tuples ordered by arrival (the summation
    order matters bit-for-bit); ``apply_mask`` marks real uploads (False for
    LAG heartbeats, whose zero payloads leave the sum unchanged but whose dual
    snapshots must NOT become server-visible). Reply ``nnz`` is computed
    in-graph and returned as one small vector -- the only device->host value
    the event loop needs.
    """
    with jax.named_scope(tracing.SERVER_APPLY):
        total = jnp.zeros_like(w_server)
        for p in payloads:
            total = total + p
        w_server = w_server + gamma * total
        dw_tilde = dw_tilde + gamma * total[None, :]
        snap = jnp.stack(list(snapshots))
        mask = apply_mask[:, None]
        alpha_applied = alpha_applied.at[idxs].set(
            jnp.where(mask, snap, alpha_applied[idxs]))
        replies = dw_tilde[idxs]
        reply_nnz = jnp.sum(replies != 0, axis=1)
        reply_sq = jnp.sum(replies * replies, axis=1)  # LAG's reference
        w_local = w_local.at[idxs].add(replies)
        dw_tilde = dw_tilde.at[idxs].set(0.0)
    return w_server, dw_tilde, w_local, alpha_applied, reply_nnz, reply_sq


def _lockstep_local_solves(w, alpha, X, y, norms_sq, lam, n, sigma_p, keys, *,
                           loss, num_steps, solver):
    """The vmapped per-worker subproblem solves of one lockstep round.

    Shared by :func:`_lockstep_round` (full worker axis) and the
    worker-sharded executor variant
    (:func:`repro.core.executor.lockstep_run_traced_sharded`, which maps it
    over a local worker block with its slice of the key split) so the solve
    op sequence is defined in exactly one place; only the aggregation
    (plain ``sum`` vs ``sum`` + ``psum``) differs between the two callers.
    """
    K = X.shape[0]
    fn = partial(solver, loss=loss, num_steps=num_steps)
    with jax.named_scope(tracing.SOLVE):
        w_all = jnp.broadcast_to(w, (K, w.shape[0]))
        return jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None, None, None, 0))(
            w_all, alpha, X, y, norms_sq, lam, n, sigma_p, keys)


def _lockstep_round(key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma, *,
                    loss, num_steps, solver):
    """Shared lockstep round body: all K subproblems vmapped + aggregation.

    Traced, not jitted -- the per-round fused dispatches below AND the
    scan-fused whole-run executor (:mod:`repro.core.executor`) inline it, so
    the op sequence (and therefore the bit-exact trajectory) is defined in
    exactly one place.  ``solver`` is a :mod:`repro.core.solvers` entry
    (``solve_subproblem`` for the hard-wired ``sync`` discipline).
    """
    K = X.shape[0]
    key, sub = jax.random.split(key)
    keys = jax.random.split(sub, K)
    dalpha, v = _lockstep_local_solves(w, alpha, X, y, norms_sq, lam, n,
                                       sigma_p, keys, loss=loss,
                                       num_steps=num_steps, solver=solver)
    with jax.named_scope(tracing.SOLVE):
        alpha = alpha + gamma * dalpha
    with jax.named_scope(tracing.AGGREGATE):
        w = w + gamma * jnp.sum(v, axis=0)
    return key, w, alpha


# Only the key is donated: w/alpha may be held by deferred eval snapshots.
@partial(jax.jit, static_argnames=("loss", "num_steps"), donate_argnums=(0,))
def _sync_round_fused(key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma, *,
                      loss, num_steps):
    """One lockstep CoCoA-family round (all K subproblems + aggregation)."""
    return _lockstep_round(key, w, alpha, X, y, norms_sq, lam, n, sigma_p,
                           gamma, loss=loss, num_steps=num_steps,
                           solver=solve_subproblem)


# Like _sync_round_fused but with the local solver as a static argument: the
# CoCoA lineage runs any repro.core.solvers registry entry, vmapped over the
# worker axis, in one donated dispatch.
@partial(jax.jit, static_argnames=("loss", "num_steps", "solver"),
         donate_argnums=(0,))
def _cocoa_round_fused(key, w, alpha, X, y, norms_sq, lam, n, sigma_p, gamma,
                       *, loss, num_steps, solver):
    return _lockstep_round(key, w, alpha, X, y, norms_sq, lam, n, sigma_p,
                           gamma, loss=loss, num_steps=num_steps,
                           solver=solver)


def _certificate_ops(w, alpha, X, y, lam, *, loss):
    """ONE snapshot's gap certificate: (primal, dual, gap, gap_server).

    The single definition of the certificate op sequence -- shared by the
    deferred batch evaluation below and the scan executor's in-graph
    ``target_gap`` test (:func:`repro.core.executor.lockstep_run_gap_traced`)
    so the two can never silently desynchronize; the ops mirror the
    reference's eager ``objectives.gap_certificate`` exactly (the bit-exact
    equivalence contract).
    """
    with jax.named_scope(tracing.CERTIFICATE):
        w_alpha = objectives.primal_from_dual(alpha, X, lam)
        p = objectives.primal_objective(w_alpha, X, y, lam, loss=loss)
        dv = objectives.dual_objective(alpha, X, y, lam, loss=loss)
        p_srv = objectives.primal_objective(w, X, y, lam, loss=loss)
        return p, dv, p - dv, p_srv - dv


@partial(jax.jit, static_argnames=("loss",))
def _eval_batched(ws, alphas, X, y, lam, *, loss):
    """All deferred gap certificates in one dispatch.

    ``lax.map`` (not vmap): the per-snapshot computation stays unbatched, so
    each reduction sees the exact operand shapes of the reference's eager
    ``gap_certificate`` calls -- batched dot_generals reduce in a different
    order on CPU and break the last-bit equivalence contract.
    """

    def one(args):
        w, alpha = args
        return _certificate_ops(w, alpha, X, y, lam, loss=loss)

    return jax.lax.map(one, (ws, alphas))


def _bucket_size(count: int) -> int:
    """Next power of two >= count: the static snapshot-batch sizes
    ``_eval_batched`` compiles for."""
    return 1 << max(0, count - 1).bit_length()


def _eval_bucketed(ws, alphas, X, y, lam, *, loss):
    """``_eval_batched`` padded to power-of-two snapshot counts.

    Deferred-gap evaluation used to retrace whenever the snapshot count
    changed across runs (every distinct ``num_outer`` x ``eval_every``
    combination in a sweep paid a fresh compile).  Padding the batch with
    copies of the last snapshot pins the traced shape to log-many buckets;
    ``lax.map`` evaluates rows independently, so the first ``count`` rows
    are bit-identical to the unpadded call (pinned by tests).
    """
    count = ws.shape[0]
    if count == 0:
        empty = jnp.zeros((0,), ws.dtype)
        return empty, empty, empty, empty
    pad = _bucket_size(count) - count
    if pad:
        ws = jnp.concatenate([ws, jnp.broadcast_to(ws[-1], (pad,) + ws.shape[1:])])
        alphas = jnp.concatenate(
            [alphas, jnp.broadcast_to(alphas[-1], (pad,) + alphas.shape[1:])])
    p, dv, gap, gap_srv = _eval_batched(ws, alphas, X, y, lam, loss=loss)
    return p[:count], dv[:count], gap[:count], gap_srv[:count]


# ---------------------------------------------------------------------------
# Protocols.
# ---------------------------------------------------------------------------


class Protocol:
    """Arrival + aggregation + reply rules driving the engine's event loop.

    A *protocol* is one server discipline: it decides how many worker
    messages a round waits for, how arrived payloads enter the server state,
    and what (and when) each worker hears back.  Subclass, decorate with
    :func:`register_protocol`, and the entry becomes constructible from any
    ``MethodConfig.protocol`` string -- inheriting engine fusion, deferred
    gap evaluation, the streaming :class:`repro.api.session.Session` loop,
    and the bit-for-bit regression harness (tests/test_engine.py) for free.
    ``docs/extending-protocols.md`` is the worked walkthrough.

    **Classmethod contract** (consulted before an instance exists):

    ``default_sigma_prime(method, K)``
        The subproblem safety parameter sigma' used when
        ``MethodConfig.sigma_prime`` is ``None``.  sigma' scales the
        quadratic penalty of the local subproblem (Eq. 7-8) and must upper
        bound the aggregation overlap: gamma * B for B-of-K group
        aggregation (the paper's rule), gamma * K for "adding" CoCoA+
        aggregation, 1 for "averaging" CoCoA aggregation.  Protocol-owned so
        registry entries supply a *correct* default instead of growing
        string checks in the config dataclass -- an unsafe sigma' diverges,
        an over-conservative one merely converges slowly.

    **Instance hooks, in the order the Session loop calls them:**

    ``num_rounds(num_outer)``
        Total server rounds for a ``num_outer`` budget (``num_outer * T``
        for the T-periodic group family, ``num_outer`` for lockstep rounds).

    ``initial_messages()``
        Launch every worker's first local round; returns the Messages that
        seed the arrival queue.  Each Message's ``arrival`` is the simulated
        time the server would receive it.

    ``arrivals_needed(round_index)``
        How many queued messages round ``round_index`` waits for -- the
        *arrival rule* (B, K, 1, or anything state-dependent; it is re-read
        every round, so adaptive disciplines just return fresh state).

    ``is_sync_round(round_index)``
        True when the round is a full-K barrier; the Session emits a
        :class:`repro.api.session.SyncEvent` after processing it.

    ``process_round(round_index, arrived)``
        The *aggregation + reply* rules: fold the arrived payloads into
        server state, bill reply bytes/time, advance ``self.sim_time``, and
        return the next wave of in-flight Messages (usually one relaunch per
        arrived worker).  Accounting invariant: ``bytes_up``/``bytes_down``/
        ``compute_time``/``comm_time`` are cumulative totals and
        ``sim_time`` is monotone.

    ``snapshot(iteration)``
        Capture (device arrays allowed, no host sync required) whatever a
        deferred duality-gap evaluation needs -- called at eval boundaries.

    ``finalize(records)``
        Fold the finished run into a :class:`RunResult`.

    Timing comes from ``self.delay`` -- a fresh
    :class:`repro.core.delays.DelayModel` per run (so stateful models like
    ``markov`` never leak across runs), resolved from
    ``ClusterModel.delay_model``.  Host randomness comes from ``self.rng``
    and device randomness from ``self.key``; both are seeded from the run's
    single ``seed`` so a (spec, seed) pair reproduces the trajectory.
    """

    protocol_name = "abstract"
    # True for protocols that honor ClusterModel.membership (elastic worker
    # dropout/rejoin schedules).  Protocols that do not understand
    # membership reject a non-empty schedule at construction rather than
    # silently simulating a full-strength cluster.
    supports_membership = False

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        """sigma' when ``MethodConfig.sigma_prime`` is unset.

        The paper's rule for the group family: gamma * B (safe for B-of-K
        aggregation). Protocol-owned so new registry entries supply their own
        value instead of growing string checks in the config dataclass.
        """
        return method.gamma * method.B

    @classmethod
    def coalesce_supported(cls, method: MethodConfig,
                           cluster: ClusterModel) -> tuple[bool, str]:
        """May runs of this protocol join a coalesced sweep batch
        (:mod:`repro.serve`)?  Returns ``(ok, reason)``.

        The base rule delegates to the executor's scan eligibility -- a run
        the scan executor can express IS expressible as one sweep cell.
        Protocols whose scan path is not the shared lockstep/lag cell
        machinery (e.g. ``partial_work``'s per-chunk carries) override this
        with an explicit refusal so the serve layer routes them to the solo
        lane instead of silently mis-batching.
        """
        from repro.core import executor  # late import: executor imports us

        return executor.scan_supported(method, cluster)

    def __init__(self, problem: objectives.Problem, method: MethodConfig,
                 cluster: ClusterModel, *, seed: int):
        if cluster.membership and not self.supports_membership:
            raise ValueError(
                f"protocol {self.protocol_name!r} does not support elastic "
                f"membership; ClusterModel.membership is non-empty. Use a "
                f"protocol declaring supports_membership (e.g. "
                f"'partial_work') or clear the membership schedule.")
        self.problem = problem
        self.method = method
        self.cluster = cluster
        self.delay = cluster.make_delay()  # fresh per run; may be stateful
        self.K, self.n_k, self.d = problem.X.shape
        self.n = self.K * self.n_k
        self.sigma_p = method.resolved_sigma_prime(self.K)
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.key(seed)
        self.bytes_up = 0
        self.bytes_down = 0
        self.compute_time = 0.0
        self.comm_time = 0.0
        self.sim_time = 0.0
        self.seq = 0

    # --- hooks the engine loop calls (contract in the class docstring) ----

    def num_rounds(self, num_outer: int) -> int:
        raise NotImplementedError

    def initial_messages(self) -> Iterable[Message]:
        raise NotImplementedError

    def arrivals_needed(self, round_index: int) -> int:
        raise NotImplementedError

    def is_sync_round(self, round_index: int) -> bool:
        """True when round ``round_index`` is a full-K barrier (SyncEvent)."""
        return False

    def process_round(self, round_index: int, arrived: list[Message]) -> list[Message]:
        raise NotImplementedError

    def snapshot(self, iteration: int) -> _Snapshot:
        raise NotImplementedError

    def finalize(self, records: list[RunRecord]) -> RunResult:
        raise NotImplementedError


@register_protocol("group")
class GroupProtocol(Protocol):
    """Algorithms 1+2: straggler-agnostic B-of-K server with catch-up buffers."""

    full_sync_period: bool = True  # every T-th round is a K-barrier

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        # The paper's rule: sigma' covers the B updates a round aggregates.
        return method.gamma * method.B

    @classmethod
    def coalesce_supported(cls, method: MethodConfig,
                           cluster: ClusterModel) -> tuple[bool, str]:
        # Group runs coalesce exactly when the scan executor can express
        # them as shared sweep cells (the base delegation, stated here so
        # the registry-hooks rule records the decision per family).
        return super().coalesce_supported(method, cluster)

    def __init__(self, problem, method, cluster, *, seed):
        super().__init__(problem, method, cluster, seed=seed)
        dt = problem.X.dtype
        self.comp = compress_lib.for_method(method, self.d)
        self.dense = isinstance(self.comp, compress_lib.Dense)
        self.up_bytes = self.comp.wire_bytes(self.d)
        self.w_server = jnp.zeros((self.d,), dt)
        self.dw_tilde = jnp.zeros((self.K, self.d), dt)
        self.w_local = jnp.zeros((self.K, self.d), dt)
        self.alpha_applied = jnp.zeros((self.K, self.n_k), dt)
        # Stacked worker state: the fused group relaunch updates rows
        # in-graph (the former per-worker array lists forced one dispatch
        # per relaunched worker).
        self.alpha = jnp.zeros((self.K, self.n_k), dt)
        self.residual = jnp.zeros((self.K, self.d), dt)
        self.norms_sq = jnp.sum(problem.X * problem.X, axis=-1)

    def num_rounds(self, num_outer: int) -> int:
        return num_outer * self.method.T

    def initial_messages(self):
        return self._launch_workers([(k, 0.0) for k in range(self.K)])

    def arrivals_needed(self, round_index: int) -> int:
        T = self.method.T
        if self.full_sync_period and round_index % T == T - 1:
            return self.K
        return min(self.method.B, self.K)

    def is_sync_round(self, round_index: int) -> bool:
        T = self.method.T
        return self.full_sync_period and round_index % T == T - 1

    # -- the fused group relaunch -----------------------------------------

    def _round_payloads(self, idxs):
        """Run the group's local rounds; returns stacked (alpha_rows, sents,
        skip flags or None).  Subclasses (LAG) override to add laziness."""
        (self.key, self.alpha, self.residual, alpha_rows,
         sents) = _worker_rounds_fused(
            self.key, self.w_local, self.alpha, self.residual,
            self.problem.X, self.problem.y, self.norms_sq, idxs,
            self.problem.lam, self.n, self.sigma_p, self.method.gamma,
            loss=self.problem.loss, num_steps=self.method.H, comp=self.comp)
        return alpha_rows, sents, None

    def _message_bytes(self, skipped: bool) -> int:
        return self.up_bytes

    def _launch_workers(self, starts, pre_account=None):
        """Launch local rounds for ``starts = [(worker, start_time), ...]``
        (arrival order) as ONE fused dispatch, then do the host-side
        accounting per worker.

        ``pre_account``: optional per-worker ``(rbytes, down_time)`` reply
        billing, applied immediately before each worker's own launch
        accounting -- this keeps the float accumulation order of the
        reference loops exactly (down_0, up_0, down_1, up_1, ...), which the
        bit-for-bit pins depend on.
        """
        if not starts:
            return []
        m = self.method
        # Satellite of the vectorized-delay work: per-round vector draws
        # (ONE size-K numpy draw) for models that support them, per-message
        # scalar draws (the legacy, reference-pinned order: worker by
        # worker in launch order) otherwise.
        with tracing.span("repro.engine.delay_sample"):
            if self.delay.vector_sampled:
                drawn = self.delay.sample_round(m.H, self.rng)
                durations = [drawn[k] for k, _ in starts]
            else:
                durations = [self.delay.compute_time(k, m.H, self.rng)
                             for k, _ in starts]
        with tracing.span("repro.engine.worker_dispatch"):
            idxs = jnp.asarray([k for k, _ in starts], jnp.int32)
            alpha_rows, sents, skips = self._round_payloads(idxs)
        out = []
        with tracing.span("repro.engine.split"):
            for j, (k, start) in enumerate(starts):
                if pre_account is not None:
                    rbytes, down_time = pre_account[j]
                    self.bytes_down += rbytes
                    self.comm_time += down_time
                skipped = bool(skips[j]) if skips is not None else False
                nbytes = self._message_bytes(skipped)
                duration = durations[j]
                up_time = self.delay.p2p_time(nbytes, k)
                self.compute_time += duration
                self.comm_time += up_time
                self.bytes_up += nbytes
                self.seq += 1
                msg = Message(start + duration + up_time, k, sents[j],
                              alpha_rows[j], nbytes, self.seq,
                              applied=not skipped)
                self._observe_launch(k, start, msg.arrival)
                out.append(msg)
        return out

    def _observe_launch(self, k: int, start: float, arrival: float) -> None:
        """Per-launch hook (adaptive disciplines observe round latencies)."""

    def _apply_server(self, arrived):
        """Fused aggregation + replies; returns (server_time, reply nnz)."""
        server_time = max(m.arrival for m in arrived)
        with tracing.span("repro.engine.server_dispatch"):
            idxs = jnp.asarray([m.worker for m in arrived], jnp.int32)
            mask = jnp.asarray([m.applied for m in arrived], bool)
            (self.w_server, self.dw_tilde, self.w_local, self.alpha_applied,
             reply_nnz, reply_sq) = _server_apply_fused(
                self.w_server, self.dw_tilde, self.w_local,
                self.alpha_applied, idxs, tuple(m.payload for m in arrived),
                tuple(m.alpha_snapshot for m in arrived), mask,
                self.method.gamma)
        self._last_reply_sq = reply_sq  # stays on device; LAG reads slices
        # The ONE host<->device sync of the round (skipped when replies are
        # dense, whose byte count is static).
        nnz_host = None if self.dense else _host_array(reply_nnz)
        return server_time, nnz_host

    def _reply_billing(self, j, worker, nnz_host) -> tuple[int, float]:
        """(bytes, link time) of arrival ``j``'s catch-up reply."""
        rbytes = (msg_filter.dense_bytes(self.d) if self.dense
                  else msg_filter.message_bytes(int(nnz_host[j])))
        return rbytes, self.delay.p2p_time(rbytes, worker)

    def process_round(self, round_index, arrived):
        server_time, nnz_host = self._apply_server(arrived)
        # Reply billing is computed up front but ACCOUNTED inside the launch
        # loop (via pre_account), interleaved per worker exactly like the
        # reference's float accumulation order (down, up, down, up).
        starts, billing = [], []
        for j, m in enumerate(arrived):
            rbytes, down_time = self._reply_billing(j, m.worker, nnz_host)
            starts.append((m.worker, server_time + down_time))
            billing.append((rbytes, down_time))
        self.sim_time = server_time
        return self._launch_workers(starts, pre_account=billing)

    def snapshot(self, iteration):
        return _Snapshot(iteration, self.sim_time, self.bytes_up,
                         self.bytes_down, self.compute_time, self.comm_time,
                         self.w_server, self.alpha_applied)

    def finalize(self, records):
        return RunResult(self.method, records, np.asarray(self.w_server),
                         np.asarray(self.alpha),
                         alpha_applied=np.asarray(self.alpha_applied))


@register_protocol("async")
class AsyncProtocol(GroupProtocol):
    """Fully-asynchronous ablation: B=1, per-worker apply, no sync barrier.

    Every arrival is applied immediately; staleness is unbounded (Assumption 3
    is intentionally violated -- this is the protocol the paper's T-periodic
    barrier exists to tame, now expressible as a config).
    """

    full_sync_period = False

    def __init__(self, problem, method, cluster, *, seed):
        if method.B != 1:
            raise ValueError(
                f"protocol 'async' is defined by B=1 (per-arrival apply); "
                f"got B={method.B}. Use protocol='group' for B-of-K "
                f"aggregation, or baselines.acpd_async() for a valid config.")
        super().__init__(problem, method, cluster, seed=seed)


@register_protocol("lag")
class LagProtocol(GroupProtocol):
    """Group protocol + LAG-style lazy uploads (arXiv:1805.09965 adapted).

    LAG's worker-side rule (LAG-WK) reuses the previous gradient -- i.e.
    uploads nothing -- when the new gradient differs from the last
    communicated one by less than a windowed average of recent global model
    movement: ``||grad change||^2 <= (xi / D) * sum_{d'=1..D}
    ||theta_{t+1-d'} - theta_{t-d'}||^2``.  Two translations to this
    delta-coded primal-dual setting:

    * the upload *is already a delta* (``F(dw)``: the change since the
      worker's last applied contribution), so "gradient unchanged -> reuse"
      becomes "delta negligible -> send nothing"; the skipped mass stays in
      the error-feedback residual, making laziness lossless, only late;
    * the worker's freshest view of global model movement is its stream of
      catch-up replies (``dw_tilde``: exactly the model change it missed),
      so the RHS window averages the squared norms of its last
      ``lag_window`` replies -- the paper's D-round window (D=10 in their
      experiments), replacing the cruder single-last-reply test this
      protocol used previously (``lag_window=1`` restores it).

    A skipping worker sends an 8-byte heartbeat instead of the payload.  The
    server treats heartbeats as arrivals (the worker is alive and gets its
    catch-up reply) but applies nothing for them.  Since replies shrink as
    the system converges, the test stays calibrated: all-quiet -> replies
    ~ 0 -> uploads resume, no starvation.

    The reply-energy window lives in a fixed-width device buffer
    ``(K, lag_window)`` plus per-worker fill counts (see
    :func:`_lag_window_append`), summed afresh each round over the live
    entries -- an incremental running sum in f32 would accumulate
    catastrophic cancellation once reply norms decay orders of magnitude
    below the evicted early entries.  The scan executor
    (:mod:`repro.core.executor`) carries the identical buffers, so both
    executors evaluate the same laziness expression bit-for-bit.
    """

    HEARTBEAT_BYTES = 8

    def __init__(self, problem, method, cluster, *, seed):
        if method.lag_window < 1:
            raise ValueError(
                f"lag_window must be >= 1, got {method.lag_window}")
        super().__init__(problem, method, cluster, seed=seed)
        # Empty windows => ref 0 => the first rounds always upload.
        self._ref_buf = jnp.zeros((self.K, method.lag_window),
                                  problem.X.dtype)
        self._ref_len = jnp.zeros((self.K,), jnp.int32)

    def _round_payloads(self, idxs):
        (self.key, self.alpha, self.residual, alpha_rows, sents,
         skips) = _worker_rounds_lag_fused(
            self.key, self.w_local, self.alpha, self.residual, self._ref_buf,
            self._ref_len, self.problem.X, self.problem.y, self.norms_sq,
            idxs, self.problem.lam, self.n, self.sigma_p, self.method.gamma,
            self.method.lag_xi, loss=self.problem.loss,
            num_steps=self.method.H, comp=self.comp)
        return alpha_rows, sents, _host_array(skips)  # one pull per group

    def _message_bytes(self, skipped):
        return self.HEARTBEAT_BYTES if skipped else self.up_bytes

    def process_round(self, round_index, arrived):
        server_time, nnz_host = self._apply_server(arrived)
        # Slide this round's reply energies into the arrived workers'
        # windows (one fused dispatch, no host sync).
        with tracing.span("repro.engine.server_dispatch"):
            idxs = jnp.asarray([m.worker for m in arrived], jnp.int32)
            self._ref_buf, self._ref_len = _lag_window_append(
                self._ref_buf, self._ref_len, idxs, self._last_reply_sq)
        starts, billing = [], []
        for j, m in enumerate(arrived):
            rbytes, down_time = self._reply_billing(j, m.worker, nnz_host)
            starts.append((m.worker, server_time + down_time))
            billing.append((rbytes, down_time))
        self.sim_time = server_time
        return self._launch_workers(starts, pre_account=billing)


@register_protocol("sync")
class SyncProtocol(Protocol):
    """CoCoA / CoCoA+ / DisDCA: lockstep rounds timed as MPI allreduce.

    The queue degenerates to K tokens popped per round; timing follows the
    reference implementation exactly (max worker compute + ring allreduce,
    bytes split evenly between the reduce-scatter and all-gather phases).
    """

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        # "Adding" aggregation over all K partitions (Ma et al. 2015).
        return method.gamma * K

    @classmethod
    def coalesce_supported(cls, method: MethodConfig,
                           cluster: ClusterModel) -> tuple[bool, str]:
        # Lockstep rounds are the sweep machinery's native shape; defer to
        # the executor's scan eligibility for the delay-model fine print.
        return super().coalesce_supported(method, cluster)

    def __init__(self, problem, method, cluster, *, seed):
        super().__init__(problem, method, cluster, seed=seed)
        dt = problem.X.dtype
        self.w = jnp.zeros((self.d,), dt)
        self.alpha = jnp.zeros((self.K, self.n_k), dt)
        self.norms_sq = jnp.sum(problem.X * problem.X, axis=-1)

    def num_rounds(self, num_outer: int) -> int:
        return num_outer

    def is_sync_round(self, round_index: int) -> bool:
        return True  # every lockstep round is a K-barrier

    def _tokens(self):
        out = []
        for k in range(self.K):
            self.seq += 1
            out.append(Message(self.sim_time, k, None, None, 0, self.seq))
        return out

    def initial_messages(self):
        return self._tokens()

    def arrivals_needed(self, round_index: int) -> int:
        return self.K

    def _round_update(self):
        """One fused lockstep update; CoCoA-lineage subclasses override to
        swap the local solver while inheriting timing/byte accounting."""
        m = self.method
        self.key, self.w, self.alpha = _sync_round_fused(
            self.key, self.w, self.alpha, self.problem.X, self.problem.y,
            self.norms_sq, self.problem.lam, self.n, self.sigma_p, m.gamma,
            loss=self.problem.loss, num_steps=m.H)

    def process_round(self, round_index, arrived):
        m = self.method
        with tracing.span("repro.engine.worker_dispatch"):
            self._round_update()
        # One per-round vector draw (same host-RNG stream as K scalar calls
        # in worker order -- the order the pinned trajectories consumed).
        with tracing.span("repro.engine.delay_sample"):
            step_compute = float(np.max(self.delay.sample_round(m.H,
                                                                self.rng)))
        step_comm = self.delay.allreduce_time(self.d)
        self.sim_time += step_compute + step_comm
        self.compute_time += step_compute
        self.comm_time += step_comm
        phase = (self.K - 1) * self.d * 4  # ring reduce-scatter == all-gather
        self.bytes_up += phase
        self.bytes_down += phase
        return self._tokens()

    def snapshot(self, iteration):
        return _Snapshot(iteration, self.sim_time, self.bytes_up,
                         self.bytes_down, self.compute_time, self.comm_time,
                         self.w, self.alpha)

    def finalize(self, records):
        return RunResult(self.method, records, np.asarray(self.w),
                         np.asarray(self.alpha))


@register_protocol("cocoa")
class CocoaProtocol(SyncProtocol):
    """CoCoA v1 (Jaggi et al., arXiv:1409.1458): synchronous rounds,
    "averaging" aggregation, pluggable local solver.

    The CoCoA framework's point is that ANY local subproblem solver reaching
    a Theta-approximate solution plugs into the same aggregation; here the
    solver comes from the :mod:`repro.core.solvers` registry via
    ``MethodConfig.local_solver`` (``sdca`` | ``importance`` |
    ``accelerated``) instead of being hard-wired SDCA.  ``gamma`` is the
    aggregation parameter: CoCoA's averaging uses ``gamma = 1/K`` (the
    :func:`repro.core.baselines.cocoa_v1` preset), for which ``sigma' = 1``
    is the safe subproblem scaling.  Timing/byte accounting is inherited
    from the lockstep ``sync`` discipline (MPI-style ring allreduce).
    """

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        # "Averaging" aggregation (Jaggi et al. 2014): safe for gamma <= 1/K.
        return 1.0

    def __init__(self, problem, method, cluster, *, seed):
        # Averaging is only safe for gamma <= 1/K (sigma'=1 does not damp a
        # larger aggregate; it visibly diverges).  Only the "cocoa" entry
        # enforces this -- CocoaPlusProtocol inherits with its own sigma'.
        # An explicit MethodConfig.sigma_prime overrides at the user's risk.
        K = problem.X.shape[0]
        if (self.protocol_name == "cocoa" and method.sigma_prime is None
                and method.gamma > 1.0 / K + 1e-9):
            raise ValueError(
                f"protocol 'cocoa' uses averaging aggregation (sigma'=1), "
                f"which is only safe for gamma <= 1/K; got gamma="
                f"{method.gamma} with K={K}. Use baselines.cocoa_v1, "
                f"protocol='cocoa_plus' for adding aggregation, or set "
                f"sigma_prime explicitly.")
        super().__init__(problem, method, cluster, seed=seed)
        from repro.core import solvers as solvers_lib

        self.solver = solvers_lib.get_solver(method.local_solver)

    def _round_update(self):
        m = self.method
        self.key, self.w, self.alpha = _cocoa_round_fused(
            self.key, self.w, self.alpha, self.problem.X, self.problem.y,
            self.norms_sq, self.problem.lam, self.n, self.sigma_p, m.gamma,
            loss=self.problem.loss, num_steps=m.H, solver=self.solver)


@register_protocol("cocoa_plus")
class CocoaPlusProtocol(CocoaProtocol):
    """CoCoA+ (Ma et al. 2015): "adding" aggregation, pluggable local solver.

    Same lockstep round as :class:`CocoaProtocol` but with the adding
    aggregation's safe subproblem scaling ``sigma' = gamma * K`` (gamma = 1
    recovers the paper's CoCoA+ baseline, which the hard-wired ``sync``
    protocol pins bit-for-bit; this entry exists for the pluggable-solver
    axis).
    """

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        return method.gamma * K


@register_protocol("adaptive_b")
class AdaptiveBProtocol(GroupProtocol):
    """Group protocol with the group size B adapted to observed arrivals.

    The paper fixes B ahead of time, but the right B depends on delay
    behavior the operator rarely knows (how many workers are persistently
    late?).  This discipline learns it online: it keeps an EWMA of each
    worker's round latency (launch -> arrival, exactly what a real server
    observes) and waits each round for the workers in the fast
    ``adaptive_quantile`` of that latency distribution::

        B_t = clip(#{k : ewma_k <= quantile_q(ewma)}, b_min, ceil(q * K))

    The upper clip matters: ``ceil(q * K)`` is the aggregation size
    ``default_sigma_prime`` covers, and under tied latencies (a homogeneous
    cluster) the raw count alone reaches K and out-runs sigma' -- which
    diverges, not errors.  Heavy-tailed or bursty delay models (``pareto``,
    ``markov``) shrink B_t automatically while the tail is hot and relax it
    when stragglers recover; under homogeneous delays it settles at
    ``ceil(q * K)``.  The
    T-periodic full barrier is kept, so the staleness bound (Assumption 3)
    still holds.  ``MethodConfig.B`` only seeds the first rounds, before one
    latency sample per worker exists.

    This class is also the worked example of ``docs/extending-protocols.md``.
    """

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        # sigma' must cover the aggregation size the discipline targets:
        # about quantile * K arrivals per round (the paper's gamma * B rule
        # with the adapted B's expected value).
        target_b = max(method.b_min, math.ceil(method.adaptive_quantile * K))
        return method.gamma * target_b

    def __init__(self, problem, method, cluster, *, seed):
        if not 0.0 < method.adaptive_quantile <= 1.0:
            raise ValueError(
                f"adaptive_quantile must be in (0, 1], got "
                f"{method.adaptive_quantile}")
        if not 0.0 < method.adaptive_ewma <= 1.0:
            raise ValueError(
                f"adaptive_ewma must be in (0, 1], got {method.adaptive_ewma}")
        super().__init__(problem, method, cluster, seed=seed)
        self._latency = np.full(self.K, np.nan)  # EWMA round latency
        # The adapted B lives in [b_min, ceil(q*K)]: the upper end is the
        # aggregation size the default sigma' covers (see classmethod above).
        self._b_lo = max(1, method.b_min)
        self._b_hi = min(self.K, max(self._b_lo,
                                     math.ceil(method.adaptive_quantile
                                               * self.K)))
        self._B = int(np.clip(method.B, self._b_lo, self._b_hi))

    @property
    def current_b(self) -> int:
        """The group size the next non-barrier round will wait for."""
        return self._B

    def arrivals_needed(self, round_index: int) -> int:
        T = self.method.T
        if round_index % T == T - 1:
            return self.K  # the staleness-bounding full barrier stays
        return self._B

    def _observe_launch(self, k, start, arrival):
        latency = arrival - start
        beta = self.method.adaptive_ewma
        if np.isnan(self._latency[k]):
            self._latency[k] = latency
        else:
            self._latency[k] = (1.0 - beta) * self._latency[k] + beta * latency
        if not np.isnan(self._latency).any():
            cut = np.quantile(self._latency, self.method.adaptive_quantile)
            self._B = int(np.clip(int(np.sum(self._latency <= cut)),
                                  self._b_lo, self._b_hi))


@register_protocol("partial_work")
class PartialWorkProtocol(GroupProtocol):
    """Straggler-UTILIZING group rounds: harvest chunk-level partial work.

    The paper's B-of-K server discards whatever stragglers computed after
    the B-th arrival; Ozfatura et al. (arXiv:2004.04948, arXiv:1808.02240)
    show that streaming chunk-level PARTIAL updates dominates discard-based
    schemes exactly in high-delay-variance regimes.  Here each local pass of
    ``H`` SDCA steps is split into ``MethodConfig.n_chunks`` chunks; the
    worker compresses and uploads EVERY chunk as it finishes (each chunk
    billed through the one compressor formula, ``wire_bytes``), and the
    server's round deadline is the ``B``-th FULL arrival (a worker's last
    chunk) -- or a fixed ``pw_quantum`` of simulated seconds when set.  The
    server folds every chunk that arrived by the deadline into the catch-up
    buffers, so a straggler at chunk 3 of 4 has contributed 3/4 of its round
    instead of nothing.  Only COMPLETED workers are replied to and
    relaunched; stragglers keep computing undisturbed (their ``dw_tilde``
    rows accrue until their own pass completes).  With ``n_chunks=1`` the
    discipline degrades bit-for-bit to ``group`` (pinned by tests).

    Elasticity: this is the protocol family honoring
    ``ClusterModel.membership`` (worker drop/rejoin schedules).  A dropping
    worker's unsent chunks are rolled back to its last sent chunk (error
    feedback keeps the mass accounted), its bytes stop accruing, and the
    B-of-K deadline shrinks with the live membership (``b_eff = min(B,
    pending full passes)``) so dropouts can never hang the barrier.  A
    rejoining worker receives a dense catch-up reply and re-enters the
    launch RNG stream at its rejoin round, deterministically.
    """

    supports_membership = True

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        # The group family's gamma * B, by mass conservation: a round's
        # deadline is the B-th FULL arrival, and a completing worker's
        # earlier chunks were already harvested in PRIOR rounds, so the
        # round folds B pass-equivalents of update mass in steady state --
        # straggler chunks SUBSTITUTE for the completers' already-applied
        # mass rather than adding to it.  Chunking redistributes when mass
        # lands, not how much lands per apply.  min(B, K) is what the
        # elastic ``_live_sigma`` rescaling needs: with L < B live workers
        # the deadline shrinks to the L-th full arrival.
        return method.gamma * min(method.B, K)

    @classmethod
    def coalesce_supported(cls, method: MethodConfig,
                           cluster: ClusterModel) -> tuple[bool, str]:
        return (False, "protocol 'partial_work' streams per-chunk arrivals "
                       "(per-chunk scan carries); its runs are not "
                       "expressible as shared lockstep/lag sweep cells")

    def __init__(self, problem, method, cluster, *, seed):
        if method.n_chunks < 1:
            raise ValueError(f"n_chunks must be >= 1, got {method.n_chunks}")
        if method.n_chunks > method.H:
            raise ValueError(
                f"n_chunks={method.n_chunks} exceeds H={method.H}: every "
                f"chunk needs at least one local step")
        if method.pw_quantum is not None and method.pw_quantum <= 0:
            raise ValueError(
                f"pw_quantum must be > 0 (simulated seconds per harvest "
                f"tick), got {method.pw_quantum}")
        super().__init__(problem, method, cluster, seed=seed)
        self._chunk_steps = chunk_steps(method.H, method.n_chunks)
        # Host mirror of the in-flight queue: seq -> (arrival, worker,
        # final).  arrivals_needed computes pop counts from it, so the
        # session's generic "pop N" loop never needs protocol-specific
        # peeking.
        self._pending: dict[int, tuple[float, int, bool]] = {}
        # Rejoin schedule, time-ascending; popped as the clock passes each.
        self._rejoins = sorted(
            (r, k) for k, _, r in cluster.membership if r is not None)

    # -- arrival rule ------------------------------------------------------

    def initial_messages(self):
        return self._launch_chunks(
            [(k, 0.0) for k in range(self.K)
             if self.cluster.live_at(k, 0.0)])

    def arrivals_needed(self, round_index: int) -> int:
        T = self.method.T
        if self.full_sync_period and round_index % T == T - 1:
            return len(self._pending)  # barrier: drain every in-flight chunk
        if not self._pending:
            return 0  # starved (all live workers dropped): see process_round
        if self.method.pw_quantum is not None:
            deadline = self.sim_time + self.method.pw_quantum
            return sum(1 for a, _, _ in self._pending.values()
                       if a <= deadline)
        fulls = sorted((a, s) for s, (a, _, f) in self._pending.items() if f)
        if not fulls:
            return len(self._pending)  # only orphan chunks left: drain them
        b_eff = min(self.method.B, len(fulls))  # deadline shrinks with
        cut = fulls[b_eff - 1]                  # the live membership
        return sum(1 for s, (a, _, _) in self._pending.items()
                   if (a, s) <= cut)

    # -- aggregation + reply rules -----------------------------------------

    def process_round(self, round_index, arrived):
        m = self.method
        T = m.T
        barrier = self.full_sync_period and round_index % T == T - 1
        quantum = m.pw_quantum is not None and not barrier
        for msg in arrived:
            del self._pending[msg.seq]
        if quantum:
            server_time = self.sim_time + m.pw_quantum  # fixed harvest tick
        elif arrived:
            server_time = max(msg.arrival for msg in arrived)
        elif self._rejoins:
            # Starved: every live worker dropped mid-pass. Jump the clock to
            # the next rejoin so elasticity can never hang the round loop.
            server_time = max(self.sim_time, self._rejoins[0][0])
        else:
            return []  # permanently starved; remaining rounds are no-ops
        completed = [msg.worker for msg in arrived if msg.final
                     and self.cluster.live_at(msg.worker, server_time)]
        rejoiners = [k for k in self._collect_rejoiners(server_time)
                     if self.cluster.live_at(k, server_time)
                     and k not in completed]
        reply_to = completed + rejoiners
        nnz_host = None
        if arrived or reply_to:
            last = {}  # worker -> LAST harvested chunk's dual snapshot
            for msg in arrived:
                last[msg.worker] = msg.alpha_snapshot
            with tracing.span("repro.engine.server_dispatch"):
                (self.w_server, self.dw_tilde, self.w_local,
                 self.alpha_applied, reply_nnz,
                 reply_sq) = _server_apply_partial(
                    self.w_server, self.dw_tilde, self.w_local,
                    self.alpha_applied,
                    jnp.asarray(list(last.keys()), jnp.int32),
                    tuple(last.values()),
                    tuple(msg.payload for msg in arrived),
                    jnp.asarray(reply_to, jnp.int32), m.gamma)
            self._last_reply_sq = reply_sq
            if not self.dense and reply_to:
                nnz_host = _host_array(reply_nnz)
        starts, billing = [], []
        for j, k in enumerate(reply_to):
            rbytes, down_time = self._reply_billing(j, k, nnz_host)
            starts.append((k, server_time + down_time))
            billing.append((rbytes, down_time))
        self.sim_time = server_time
        return self._launch_chunks(starts, pre_account=billing)

    def _collect_rejoiners(self, upto: float) -> list[int]:
        out = []
        while self._rejoins and self._rejoins[0][0] <= upto:
            out.append(self._rejoins.pop(0)[1])
        return out

    def _live_sigma(self) -> float:
        """sigma' for the next launch wave: membership-scaled when elastic
        (the default formula evaluated at the LIVE worker count), the run's
        resolved sigma' otherwise."""
        if self.method.sigma_prime is not None or not self.cluster.membership:
            return self.sigma_p
        live = max(1, sum(self.cluster.live_at(k, self.sim_time)
                          for k in range(self.K)))
        return self.default_sigma_prime(self.method, live)

    # -- the fused chunked launch ------------------------------------------

    def _launch_chunks(self, starts, pre_account=None):
        """Launch chunked local passes for ``starts = [(worker, start), ...]``
        as ONE fused dispatch, then account each SENT chunk host-side.

        Per-chunk durations come from ``DelayModel.sample_chunks`` (one
        chunk-major draw per wave) for ``vector_sampled`` models and from
        per-(worker, chunk) scalar draws otherwise; with one chunk both
        reduce to the group family's per-wave draw, bit-for-bit.  A chunk is
        sent only if its compute finishes strictly before the worker's next
        scheduled drop; a truncated pass rolls the worker's dual/residual
        back to its last sent chunk (durable state), so dropped bytes stop
        accruing and no update mass is silently lost.
        """
        if not starts:
            return []
        m = self.method
        C = len(self._chunk_steps)
        with tracing.span("repro.engine.delay_sample"):
            if self.delay.vector_sampled:
                sampled = self.delay.sample_chunks(self._chunk_steps,
                                                   self.rng)
                durations = [[sampled[c][k] for c in range(C)]
                             for k, _ in starts]
            else:
                durations = [[self.delay.compute_time(k, h, self.rng)
                              for h in self._chunk_steps] for k, _ in starts]
        finishes, n_sent = [], []
        for j, (k, start) in enumerate(starts):
            drop = self.cluster.next_drop_after(k, start)
            fin, t = [], start
            for c in range(C):
                t = t + durations[j][c]
                fin.append(t)
            finishes.append(fin)
            n_sent.append(sum(1 for t in fin if t < drop))
        # Pre-capture rows for passes that will be FULLY truncated: the
        # fused call donates alpha/residual, so their pre-launch values must
        # be materialized first (rare -- only drop-before-first-chunk).
        saved = {j: (self.alpha[k], self.residual[k])
                 for j, (k, _) in enumerate(starts) if n_sent[j] == 0}
        with tracing.span("repro.engine.worker_dispatch"):
            idxs = jnp.asarray([k for k, _ in starts], jnp.int32)
            (self.key, self.alpha, self.residual, alpha_rows, sents,
             resids) = _worker_chunk_rounds_fused(
                self.key, self.w_local, self.alpha, self.residual,
                self.problem.X, self.problem.y, self.norms_sq, idxs,
                self.problem.lam, self.n, self._live_sigma(), m.gamma,
                loss=self.problem.loss, chunk_steps=self._chunk_steps,
                comp=self.comp)
        out = []
        with tracing.span("repro.engine.split"):
            for j, (k, start) in enumerate(starts):
                if pre_account is not None:
                    rbytes, down_time = pre_account[j]
                    self.bytes_down += rbytes
                    self.comm_time += down_time
                for c in range(n_sent[j]):
                    nbytes = self.up_bytes  # the one compressor formula
                    up_time = self.delay.p2p_time(nbytes, k)
                    self.compute_time += durations[j][c]
                    self.comm_time += up_time
                    self.bytes_up += nbytes
                    self.seq += 1
                    msg = Message(finishes[j][c] + up_time, k, sents[j, c],
                                  alpha_rows[j, c], nbytes, self.seq,
                                  chunk=c, final=(c == C - 1))
                    self._pending[self.seq] = (msg.arrival, k, msg.final)
                    out.append(msg)
                if n_sent[j] < C:
                    if n_sent[j] == 0:
                        row_a, row_r = saved[j]
                    else:
                        row_a = alpha_rows[j, n_sent[j] - 1]
                        row_r = resids[j, n_sent[j] - 1]
                    self.alpha = self.alpha.at[k].set(row_a)
                    self.residual = self.residual.at[k].set(row_r)
        return out


def chunk_steps(H: int, n_chunks: int) -> tuple[int, ...]:
    """Split ``H`` local steps into ``n_chunks`` near-equal chunk sizes
    (earlier chunks take the remainder; sums to exactly ``H``)."""
    base, rem = divmod(H, n_chunks)
    return tuple(base + (1 if i < rem else 0) for i in range(n_chunks))


@register_protocol("hierarchical_b")
class HierarchicalBProtocol(GroupProtocol):
    """Two-level rack-aware aggregation: per-rack B-of-k, then cross-rack.

    Workers are split into ``MethodConfig.n_racks`` contiguous racks (worker
    ``k`` belongs to rack ``k * n_racks // K``).  A round's deadline is the
    first simulated instant at which EVERY rack has at least ``rack_b``
    arrivals in flight past its top-of-rack link -- per-rack B-of-k on
    per-rack links, then one cross-rack merge (the inherited arrival-order
    catch-up aggregation; the merge is associative so the two levels fold
    into one fused apply).  Pair with the ``bandwidth_coupled`` delay model
    (``ClusterModel.straggler_workers`` = the slow rack's members) to model
    a rack behind an oversubscribed uplink: the discipline then waits for
    ``rack_b`` arrivals from the slow rack instead of letting the fast racks
    outvote it -- per-rack representation at B-of-K cost.

    The T-periodic full barrier is kept (Assumption 3's staleness bound is
    rack-agnostic).  sigma' covers ``n_racks * rack_b`` aggregated passes.
    """

    @classmethod
    def default_sigma_prime(cls, method: MethodConfig, K: int) -> float:
        return method.gamma * max(1, method.n_racks * method.rack_b)

    @classmethod
    def coalesce_supported(cls, method: MethodConfig,
                           cluster: ClusterModel) -> tuple[bool, str]:
        return (False, "protocol 'hierarchical_b' pops rack-dependent "
                       "arrival counts (host-adaptive control flow); its "
                       "runs are not expressible as shared sweep cells")

    def __init__(self, problem, method, cluster, *, seed):
        K = problem.X.shape[0]
        if not 1 <= method.n_racks <= K:
            raise ValueError(
                f"n_racks must be in [1, K={K}], got {method.n_racks}")
        self._rack_of = [k * method.n_racks // K for k in range(K)]
        rack_sizes = [self._rack_of.count(r) for r in range(method.n_racks)]
        if not 1 <= method.rack_b <= min(rack_sizes):
            raise ValueError(
                f"rack_b must be in [1, min rack size={min(rack_sizes)}] "
                f"(racks of {rack_sizes}), got {method.rack_b}")
        super().__init__(problem, method, cluster, seed=seed)
        # One in-flight message per worker at all times (the group-family
        # relaunch invariant); recorded at launch so the arrival rule can
        # count the per-rack prefix without peeking at the session's heap.
        self._pending: dict[int, tuple[float, int, int]] = {}

    def _observe_launch(self, k, start, arrival):
        self._pending[self.seq] = (arrival, self.seq, k)

    def arrivals_needed(self, round_index: int) -> int:
        T = self.method.T
        if self.full_sync_period and round_index % T == T - 1:
            return self.K
        need = [self.method.rack_b] * self.method.n_racks
        outstanding = sum(need)
        for count, (_, _, k) in enumerate(
                sorted(self._pending.values()), start=1):
            r = self._rack_of[k]
            if need[r] > 0:
                need[r] -= 1
                outstanding -= 1
                if outstanding == 0:
                    return count
        return len(self._pending)  # unreachable under the launch invariant

    def process_round(self, round_index, arrived):
        for msg in arrived:
            del self._pending[msg.seq]
        return super().process_round(round_index, arrived)


def _materialize_records(snaps: list[_Snapshot], problem: objectives.Problem,
                         eval_mode: str) -> list[RunRecord]:
    """Turn deferred snapshots into RunRecords.

    ``batched``: one ``lax.map`` dispatch covering every gap certificate.
    ``replay``: op-for-op the reference's per-round ``gap_certificate`` calls
    (bit-identical floats by construction; used as a debugging oracle --
    ``batched`` is equally bit-exact, which tests/test_engine.py pins).
    """
    if not snaps:
        return []
    if eval_mode == "replay":
        rows = []
        for s in snaps:
            cert = objectives.gap_certificate(problem, s.alpha, w=s.w)
            rows.append((cert["primal"], cert["dual"], cert["gap"],
                         cert["gap_server"]))
    elif eval_mode == "batched":
        ws = jnp.stack([s.w for s in snaps])
        alphas = jnp.stack([s.alpha for s in snaps])
        p, dv, gap, gap_srv = _eval_bucketed(ws, alphas, problem.X, problem.y,
                                             problem.lam, loss=problem.loss)
        rows = list(zip(np.asarray(p, np.float64), np.asarray(dv, np.float64),
                        np.asarray(gap, np.float64),
                        np.asarray(gap_srv, np.float64)))
    else:
        raise ValueError(f"unknown eval_mode {eval_mode!r}")
    return [
        RunRecord(iteration=s.iteration, sim_time=s.sim_time,
                  gap=float(gap), gap_server=float(gap_srv), primal=float(p),
                  dual=float(dv), bytes_up=int(s.bytes_up),
                  bytes_down=int(s.bytes_down), compute_time=s.compute_time,
                  comm_time=s.comm_time)
        for s, (p, dv, gap, gap_srv) in zip(snaps, rows)
    ]


def run_method(
    problem: objectives.Problem,
    method: MethodConfig,
    cluster: ClusterModel,
    *,
    num_outer: int,
    seed: int = 0,
    eval_every: int = 1,
    eval_mode: str = "batched",
) -> RunResult:
    """Run ``method`` through the pluggable engine. Same contract as
    :func:`repro.core.acpd.run_method` (which now delegates here).

    Thin compat wrapper: the round loop lives in
    :class:`repro.api.session.Session`; this drains its event stream and
    folds it back into a :class:`RunResult` (the tests/test_engine.py
    bit-for-bit pins hold through this path).
    """
    from repro.api.session import Session  # late import: api imports engine

    session = Session(problem, method, cluster, num_outer=num_outer,
                      seed=seed, eval_every=eval_every, eval_mode=eval_mode)
    return session.run()
