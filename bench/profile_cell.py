#!/usr/bin/env python3
"""Profile one benchmark cell with the program's own spans and scopes.

    python3 bench/profile_cell.py --workload <cell> --seed <n>
                                  [--small] [--keep <dir>] [--cost <n>]

Runs the cell's set-up and traced window exactly as ``bench/run.py --trace
1`` does (same configuration, traffic, job module, warm-up and window), then
reads the trace with :mod:`bench.program_trace` and prints one JSON line:
the window, device time per program, device self time per ``acpd.*``
scope, the ``repro.*`` host spans, idle time by the innermost span of
either family, and per round of the window:

* ``solve_ms`` / ``worker_state_ms`` / ``filter_ms``: self time under
  ``acpd.solve`` / ``acpd.worker_state`` / ``acpd.filter``;
* ``host_ms``: time in ``repro.round`` and ``repro.certificate`` spans less
  the ``repro.engine.sync`` and ``repro.certificate.sync`` spans inside
  them;
* ``host_own_ms``: ``host_ms`` less the split's wait for the worker
  program (``split_wait_s``): the host's own work;
* ``host_syncs``: the program's ``host_syncs`` counter over its
  ``event_rounds``, both over the traced window;
* ``split_own_ms_per_arrival``: the ``repro.engine.split`` spans less
  their wait, over the window's ``event_arrivals``.

``--small`` shrinks the configuration to 384 rows and d = 2,048 (the size
of ``bench/tests/data/gap_trace_scoped.xplane.pb.gz``); ``--keep`` copies
the profiler's file there, gzipped.  ``--cost n`` then times the window
with the profiler off and on, on the same ``n`` seeds: for gap traffic the
wall time from the certificate after ``trace_after`` rounds to the one
``trace_rounds`` later, for sweep traffic one grid.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench import program_trace, run, trace  # noqa: E402
from bench.common import unit_seed  # noqa: E402

SMALL = {"rows": 16 * 24, "features": 2048, "nnz_per_row": 24}


def per_round(summary: dict, rounds: int, counts: dict) -> dict:
    """The window's layers a round; ``counts`` are the program's counters
    over the window."""
    scopes, spans = summary["scopes"], summary["spans"]

    def seconds(*names):
        return sum(spans[n]["seconds"] for n in names if n in spans)

    host = (seconds("repro.round", "repro.certificate")
            - seconds("repro.engine.sync", "repro.certificate.sync"))
    own = host - summary["split_wait_s"]
    out = {"host_ms": 1e3 * host / rounds, "host_own_ms": 1e3 * own / rounds}
    for key, scope in (("solve_ms", "acpd.solve"),
                       ("worker_state_ms", "acpd.worker_state"),
                       ("filter_ms", "acpd.filter")):
        if scope in scopes:
            out[key] = 1e3 * scopes[scope] / rounds
    if counts.get("event_rounds"):
        out["host_syncs"] = counts["host_syncs"] / counts["event_rounds"]
    if counts.get("event_arrivals") and program_trace.SPLIT in spans:
        out["split_own_ms_per_arrival"] = 1e3 * (
            spans[program_trace.SPLIT]["seconds"] - summary["split_wait_s"]
        ) / counts["event_arrivals"]
    return out


class CountingTracer(run.Tracer):
    """:class:`run.Tracer` that also takes the program's counters over the
    traced window: ``counts`` is their change from start to stop."""

    def __init__(self, jax, log_dir: str):
        super().__init__(jax, log_dir)
        self.counts = {}

    def start(self) -> None:
        from repro.core.tracing import STATS

        if self.state == "idle":
            self._before = dict(STATS)
        super().start()

    def stop(self) -> None:
        from repro.core.tracing import STATS

        if self.state == "on":
            self.counts = {k: STATS[k] - self._before[k] for k in STATS}
        super().stop()


def coverage(summary: dict) -> dict:
    """The shares the scopes and spans account for, percent."""
    scopes, modules = summary["scopes"], summary["modules"]
    out = {}
    worker = modules.get("_worker_rounds_fused", {}).get("seconds")
    if worker:
        out["solve_filter_of_worker"] = 100 * (
            scopes.get("acpd.solve", 0) + scopes.get("acpd.filter", 0)
        ) / worker
        out["scoped_of_worker"] = out["solve_filter_of_worker"] + 100 * (
            scopes.get("acpd.worker_state", 0) / worker)
    grid = modules.get("_sweep_scan", {}).get("seconds")
    if grid:
        out["scoped_of_sweep_scan"] = 100 * (
            sum(v for k, v in scopes.items()
                if k.startswith(program_trace.SCOPE)
                and k != "acpd.certificate")) / grid
    idle = sum(v for _, v in summary["idle_gaps"])
    if idle:
        out["idle_under_repro"] = 100 * sum(
            v for k, v in summary["idle_gaps"]
            if k.startswith(program_trace.PROGRAM_SPAN)) / idle
    return out


def gap_window_seconds(job, seed: int, profiled: bool) -> tuple:
    """Wall seconds and rounds from the certificate after ``trace_after``
    rounds to the one ``trace_rounds`` later, profiler on or off."""
    import jax

    t = job.ctx.traffic
    first, last = t["trace_after"], t["trace_after"] + t["trace_rounds"]
    log_dir = tempfile.mkdtemp(prefix="bench-cost-")
    tracer = run.Tracer(jax, log_dir)
    session = job._session(seed, t["max_outer"], job.target)
    rounds, t0, out = 0, None, None
    try:
        for ev in session.events():
            kind = type(ev).__name__
            rounds += kind == "RoundEvent"
            if kind != "EvalEvent":
                continue
            if rounds == first:
                if profiled:
                    tracer.start()
                t0 = time.perf_counter()
            elif rounds == last and t0 is not None:
                out = (time.perf_counter() - t0, last - first)
                break
    finally:
        tracer.stop()
        shutil.rmtree(log_dir, ignore_errors=True)
    return out


def sweep_seconds(job, grid: int, profiled: bool) -> tuple:
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-cost-")
    tracer = run.Tracer(jax, log_dir)
    try:
        if profiled:
            tracer.start()
        t0 = time.perf_counter()
        job._grid(job._seeds(grid))
        seconds = time.perf_counter() - t0
    finally:
        tracer.stop()
        shutil.rmtree(log_dir, ignore_errors=True)
    return seconds, job.ctx.traffic["rounds"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--keep", default=None)
    ap.add_argument("--cost", type=int, default=0)
    args = ap.parse_args(argv)
    args.seconds, args.control = 0.0, None
    cell = run.resolve(args.workload)
    if args.small:
        cell["config"]["dataset"].update(SMALL)

    import jax

    run.configure_cache(jax)
    # JAX keys its persistent cache on the program with its debug info
    # stripped, and the scopes live in that debug info: without this, a
    # program compiled from source with other scopes (an older commit's)
    # is loaded with that source's op names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    devices = run.chip_devices(jax, cell["entry"]["chips"])
    from repro import api

    clock = run.CompileClock()
    jax.monitoring.register_event_time_span_listener(clock)
    ctx = run.Context(args, cell, jax, api, devices, clock)
    job = ctx.job = run.load_module(cell["jobs"]).Job(ctx)
    job.warm()

    log_dir = tempfile.mkdtemp(prefix="bench-profile-")
    tracer = CountingTracer(jax, log_dir)
    try:
        window = job.run(0.0, tracer)
    finally:
        tracer.stop()
    try:
        path = trace.find_xplane(log_dir)
        summary = program_trace.summarize(program_trace.load(path))
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            kept = os.path.join(args.keep, f"{args.workload}"
                                f"{'.small' if args.small else ''}"
                                f".xplane.pb.gz")
            with open(path, "rb") as src, gzip.open(kept, "wb") as dst:
                shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)

    rounds = window.traced_rounds
    line = {
        "workload": args.workload, "seed": args.seed, "small": args.small,
        "device": devices[0].device_kind, "traced_rounds": rounds,
        "traced_evals": window.traced_evals, "jobs": len(window.answers),
        "failed": window.failed,
        "window_s": summary["window_s"], "busy_s": summary["busy_s"],
        "idle_share": 100 * (1 - summary["busy_s"] / summary["window_s"]),
        "per_round": per_round(summary, rounds, tracer.counts),
        "counts": {k: v for k, v in tracer.counts.items() if v},
        "coverage": coverage(summary),
        "modules": summary["modules"], "scopes": summary["scopes"],
        "spans": summary["spans"], "split_wait_s": summary["split_wait_s"],
        "idle_gaps": summary["idle_gaps"],
        "top_ops": summary["top_ops"], "dropped": summary["dropped"],
    }
    cost = []
    for i in range(args.cost):
        if ctx.traffic["kind"] == "gap":
            run_of = {"seed": unit_seed(args.seed, i)}
            off = gap_window_seconds(job, run_of["seed"], False)
            on = gap_window_seconds(job, run_of["seed"], True)
        else:  # grids past the traced one, each with seeds of its own
            run_of = {"grid": 1000 + i}
            off = sweep_seconds(job, run_of["grid"], False)
            on = sweep_seconds(job, run_of["grid"], True)
        cost.append(dict(run_of, off_s_per_round=off[0] / off[1],
                         on_s_per_round=on[0] / on[1]))
    if cost:
        line["cost"] = cost
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
