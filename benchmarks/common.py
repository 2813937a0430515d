"""Shared benchmark plumbing: the RCV1-like problem, timing, CSV emission.

Every benchmark prints ``name,us_per_call,derived`` rows (one per measured
configuration) so ``python -m benchmarks.run`` output is machine-readable;
``derived`` carries the benchmark's headline metric (speedup, bytes ratio,
rounds-to-gap, ...). Figures' raw curves are also dumped as JSON under
experiments/bench/ for EXPERIMENTS.md; every payload is stamped with
provenance (the ExperimentSpec JSON that produced it, the seed, and
``jax.__version__``) so bench trajectories are reproducible from the file
alone (``python -m repro run`` accepts the embedded spec).

Failure policy: a raising grid cell must not silently truncate the dump.
Benchmarks wrap per-cell work in :func:`run_cell`, which records the failing
cell + exception into the payload's ``errors`` list (written by
:func:`dump`) and keeps the rest of the grid running; :func:`dump` then
raises, so a module with a failed cell exits non-zero.  The driver
(benchmarks/run.py) does the same per benchmark module.
"""

from __future__ import annotations

import json
import pathlib
import time
import traceback
from typing import Callable

from repro.api.problems import rcv1_like as _rcv1_like_builder
from repro.api.spec import ExperimentSpec
from repro.core.simulate import ClusterModel

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_DIR = ROOT / "experiments" / "bench"
TRAJECTORY = ROOT / "BENCH_SWEEP.json"


def emit(name: str, us_per_call: float, derived) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def dump(name: str, payload, *, specs=None, seed=None, errors=None) -> None:
    """Write a bench payload with reproducibility provenance.

    ``specs``: the ExperimentSpec(s) the trajectories came from (single spec
    or a list); ``seed``: the driving seed when no spec applies.
    ``errors``: failed-cell records from :func:`run_cell` -- written into the
    document (as ``errors``) so a raising cell leaves a visible trace in the
    artifact instead of a silently missing row; any record then raises
    :class:`CellsFailed` once the document is written, so the module (and
    ``benchmarks/run.py``) exits non-zero.
    """
    import jax

    if isinstance(specs, ExperimentSpec):
        specs = [specs]
    provenance = {"jax_version": jax.__version__}
    if specs:
        provenance["specs"] = [s.to_dict() for s in specs]
        provenance["seed"] = specs[0].seed if seed is None else seed
    elif seed is not None:
        provenance["seed"] = seed
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    doc = {"provenance": provenance, "data": payload}
    if errors is not None:
        doc["errors"] = list(errors)
    (OUT_DIR / f"{name}.json").write_text(json.dumps(doc, indent=1))
    if errors:
        raise CellsFailed(f"{name}: {len(errors)} cell(s) failed: "
                          + "; ".join(f"{e['cell']}: {e['error']}"
                                      for e in errors))


class CellsFailed(RuntimeError):
    """A benchmark recorded failed cells (see :func:`dump`)."""


def append_trajectory(entry: dict) -> None:
    """Append one run's headline perf numbers to the top-level
    ``BENCH_SWEEP.json`` trajectory (a JSON list, one entry per
    perf-carrying ``benchmarks/run.py`` invocation) so perf regressions
    are visible across PRs without diffing full bench dumps.

    Entries with no perf section are dropped.  ``--quick`` smoke runs are
    dropped too UNLESS they carry a ``serve`` section: executor/sweep
    wall-clocks are noise at smoke scale, but serving latency and coalesce
    factor are policy-dominated, so the quick serve cell is a real data
    point and the trajectory captures it alongside the full-scale numbers.
    """
    has_perf = ("executor" in entry or "sweep" in entry or "serve" in entry
                or "straggler_zoo" in entry or "chaos" in entry)
    if not has_perf or (entry.get("quick") and "serve" not in entry
                        and "chaos" not in entry):
        return
    doc = []
    if TRAJECTORY.exists():
        try:
            doc = json.loads(TRAJECTORY.read_text())
        except json.JSONDecodeError:
            doc = []  # a corrupt trajectory must not fail the bench run
        if not isinstance(doc, list):
            doc = []
    doc.append(entry)
    TRAJECTORY.write_text(json.dumps(doc, indent=1) + "\n")


def trajectory_entry(quick: bool, failures: list,
                     modules_run: list[str]) -> dict:
    """Summarize ONE bench run into a trajectory entry: wall-clock +
    dispatch counts per regime for the executor and sweep benchmarks.

    A section is included only when its producing module ran -- and did not
    fail -- in THIS invocation (``modules_run`` minus the failures), so
    every number in an entry was measured under the entry's own ``quick``
    flag and device configuration: neither a ``--only`` run nor a crashed
    module ever copies stale numbers from an earlier run's dumps.
    """
    import jax

    entry: dict = {
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "quick": quick,
        "jax_version": jax.__version__,
        "modules_run": list(modules_run),
        "failed_modules": [f["cell"] for f in failures],
    }
    fresh = set(modules_run) - set(entry["failed_modules"])
    exec_path = OUT_DIR / "executor_scaling.json"
    if "benchmarks.bench_engine" in fresh and exec_path.exists():
        data = json.loads(exec_path.read_text())["data"]
        entry["executor"] = {
            regime: {"event_wall_s": row["event"]["wall_s"],
                     "scan_wall_s": row["scan"]["wall_s"],
                     "event_dispatches": row["event"]["device_dispatches"],
                     "scan_dispatches": row["scan"]["device_dispatches"]}
            for regime, row in data["executor"]["regimes"].items()}
    sweep_path = OUT_DIR / "sweep_scaling.json"
    if "benchmarks.bench_sweep_scaling" in fresh and sweep_path.exists():
        doc = json.loads(sweep_path.read_text())["data"]
        entry["n_devices"] = doc.get("n_devices")
        entry["n_cores"] = doc.get("n_cores")
        keep = ("sweep_sharded_wall_s", "sweep_vmap_wall_s",
                "percell_scan_wall_s", "percell_event_wall_s",
                "sweep_dispatches", "percell_scan_dispatches",
                "mesh_speedup_vs_vmap", "speedup_vs_percell_event",
                "speedup_vs_percell_scan")
        rows = dict(doc.get("regimes", {}))
        if "lag_grid" in doc:
            rows["lag_grid"] = doc["lag_grid"]
        entry["sweep"] = {
            regime: {k: row[k] for k in keep if k in row}
            for regime, row in rows.items()}
    zoo_path = OUT_DIR / "straggler_zoo.json"
    if ("benchmarks.bench_straggler_zoo" in fresh and zoo_path.exists()
            and not quick):
        # Sim-time-to-gap is a model quantity, not a wall-clock, but it IS
        # the zoo's headline claim (partial_work harvests stragglers); only
        # full-scale runs are trustworthy, quick grids stop too early.
        data = json.loads(zoo_path.read_text())["data"]
        ttg = data.get("time_to_gap") or {}
        if ttg:
            entry["straggler_zoo"] = {
                delay: {k: row.get(k) for k in
                        ("target_gap", "group_s", "partial_s",
                         "sim_time_speedup")}
                for delay, row in ttg.items()}
    serve_path = OUT_DIR / "serve.json"
    if "benchmarks.bench_serve" in fresh and serve_path.exists():
        data = json.loads(serve_path.read_text())["data"]
        entry["serve"] = {k: data.get(k) for k in (
            "sustained_req_per_s", "latency_p50_s", "latency_p99_s",
            "coalesce_factor", "compile_cache_hit_rate", "n_requests",
            "offered_rate_hz", "batches", "solo_requests")}
    chaos_path = OUT_DIR / "chaos.json"
    if "benchmarks.bench_chaos" in fresh and chaos_path.exists():
        data = json.loads(chaos_path.read_text())["data"]
        window, recovery = data.get("window", {}), data.get("recovery", {})
        entry["chaos"] = {
            **{k: window.get(k) for k in (
                "goodput_req_per_s", "hung_jobs", "succeeded", "failed",
                "latency_p50_s", "n_requests")},
            "counters": window.get("counters"),
            "resume_wall_s": recovery.get("resume_wall_s"),
            "recovery_speedup_vs_fresh":
                recovery.get("recovery_speedup_vs_fresh"),
            "resume_bit_identical": recovery.get("resume_bit_identical"),
            "cluster": {k: data.get("cluster", {}).get(k) for k in (
                "goodput_jobs_per_s", "hung_jobs", "n_jobs", "n_replicas",
                "takeovers", "takeover_recovery_ticks", "fenced_results",
                "dropped_messages", "deduped_results")},
        }
    return entry


def run_cell(errors: list, cell: str, fn: Callable, *args, **kw):
    """Run one grid cell, recording (not raising) its failure.

    On an exception: appends ``{"cell", "error", "traceback"}`` to
    ``errors``, emits an ``error/<cell>`` CSV row so the live output shows
    the hole, and returns ``None`` (callers skip the row).  Pass ``errors``
    on to :func:`dump` so the artifact carries the record.
    """
    try:
        return fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 - the point is to record, not mask
        errors.append({
            "cell": cell,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc(limit=10),
        })
        emit(f"error/{cell}", 0.0, type(e).__name__)
        return None


def rcv1_like(K: int = 4, seed: int = 7, d: int = 2048, n_per_worker: int = 192):
    """Scaled-down stand-in for the paper's RCV1 split (no network access).

    Thin wrapper over the ``rcv1_like`` problem-registry entry so ad-hoc
    callers and spec-driven runs build the identical dataset.
    """
    return _rcv1_like_builder(K=K, seed=seed, d=d, n_per_worker=n_per_worker)


def cluster(K: int, sigma: float = 1.0, jitter: float = 0.0) -> ClusterModel:
    return ClusterModel(num_workers=K, straggler_sigma=sigma, jitter=jitter)


def timed(fn: Callable, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # us
