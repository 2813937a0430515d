"""The engine's instrumentation: counters, host spans and device scopes.

Everything here lands in one place a profiler can read, on one clock:

* ``STATS`` -- process-wide counters.  The scan family's compiled-call and
  retrace counts (``*_calls`` / ``*_traces``, the one-dispatch-per-run
  contract tests/test_executor.py and tests/test_sweep.py assert on), and
  the event loop's boundaries: ``event_rounds`` (server rounds applied),
  ``event_arrivals`` (messages popped off the arrival queue) and
  ``host_syncs`` (blocking device->host reads inside rounds and streamed
  certificates: a round's reply ``nnz``, LAG's skip flags, each ``float``
  of a gap certificate).  ``repro.core.executor.STATS`` is this dict.
* :func:`span` -- a host span in the JAX profiler's trace
  (``jax.profiler.TraceAnnotation``), named ``repro.<layer>.<what>``.  With
  no profiler running it costs about a microsecond and records nothing;
  under ``jax.profiler.trace`` the profiler keeps it beside the device's
  ops, on the same clock.  A span measures host time, so it belongs in host
  code only: inside ``jit``/``scan``/``shard_map`` it would run once at
  trace time (the ``traced-span`` lint rule flags that).
* Device scopes -- ``jax.named_scope`` names at the layer boundaries inside
  the compiled programs (:data:`SOLVE`, :data:`WORKER_STATE`,
  :data:`FILTER`, :data:`SERVER_APPLY`, :data:`AGGREGATE`,
  :data:`CERTIFICATE`).  They change only the ops' metadata (each op's
  ``op_name``, the ``tf_op`` of its events in a device trace), never the
  ops.

docs/performance.md ("Profiling a run") lists what each name covers.
"""

from __future__ import annotations

import jax

# Device scopes (jax.named_scope), one per layer of a round.
SOLVE = "acpd.solve"  # the local SDCA pass and the dual update
# A fused worker program's per-worker state and data in and out: the
# slices of X, y, the duals and the residual, the carry's updates.
WORKER_STATE = "acpd.worker_state"
FILTER = "acpd.filter"  # the message compressor (top-k or other)
SERVER_APPLY = "acpd.server_apply"  # aggregation of arrivals + replies
AGGREGATE = "acpd.aggregate"  # the lockstep sum over workers
CERTIFICATE = "acpd.certificate"  # primal, dual and gap of a snapshot

STATS = {"lockstep_calls": 0, "lockstep_traces": 0,
         "lockstep_gap_calls": 0, "lockstep_gap_traces": 0,
         "lockstep_segment_calls": 0, "lockstep_segment_traces": 0,
         "lag_calls": 0, "lag_traces": 0,
         "partial_calls": 0, "partial_traces": 0,
         "sweep_calls": 0, "sweep_traces": 0,
         "sweep_lag_calls": 0, "sweep_lag_traces": 0,
         "event_rounds": 0, "event_arrivals": 0, "host_syncs": 0}


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


def span(name: str, **args):
    """A host span ``name`` (with ``args`` as its trace arguments) in the
    JAX profiler's trace; use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **args)


def host_read(x):
    """``float(x)``: one blocking device->host read, counted."""
    STATS["host_syncs"] += 1
    return float(x)
