#!/usr/bin/env python3
"""Run one benchmark cell of ACPD on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

Everything is found by name.  ``BENCHMARK.json`` at the checkout root maps
the cell to a configuration and a traffic mix; the configuration is
``bench/configs/<config>.json`` (dataset shape, loss, cluster), the traffic
``bench/traffic/<traffic>.json`` (the job users submit), whose ``kind``
names its job module ``bench/traffic/<kind>.py``; the limits of the cell's
correctness check are ``bench/limits/<cell>.json``; each per-layer metric
is read by ``bench/metrics/<metric>.py``.

A run: set-up (imports, device, data from ``--seed``, the problem on the
chip, warm-up of every shape the window uses), then ``--seconds`` of jobs
back to back, then the check of every answer the window produced against
the plain reference (:mod:`bench.reference`).  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window is one job under the profiler and the result carries the per-layer
metrics, ``busy_s``/``window_s`` and a ``breakdown``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, then ``checks``: each
compared number beside its limit); the same checks are the last lines of
standard error.  With no TPU, fewer chips than the cell asks for, or no
``src/repro`` next to ``bench/``, it prints no result and exits non-zero.

``--control high`` (not used by the benchmark's own runs) replaces the
program's reported certificates by the reference's computed one precision
step lower, to show that the check fails them.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# Reported through jax.monitoring for each XLA compilation.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot measure: no result line, non-zero exit."""


class CompileClock:
    """Collects the start times of JAX's backend compilations."""

    def __init__(self):
        self.starts: list[float] = []

    def __call__(self, event, start, end, **_):
        if event == BACKEND_COMPILE:
            self.starts.append(start)

    def compiles_between(self, t0: float, t1: float) -> int:
        """Backend compilations that began in [t0, t1] (time.time())."""
        return sum(1 for s in self.starts if t0 <= s <= t1)


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise Refused(f"missing {path.relative_to(ROOT)}") from None


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def resolve(cell: str) -> dict:
    """The cell's entry, configuration, traffic, job module, limits and
    metric readers."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise Refused(f"no workload {cell!r} in BENCHMARK.json")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = load_json(ROOT / config_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    jobs = BENCH / "traffic" / f"{traffic['kind']}.py"
    if not jobs.is_file():
        raise Refused(f"missing {jobs.relative_to(ROOT)}")

    def applies(metric):
        return cell in metric.get("workloads", [cell])

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in reported]
    for m in per_layer:
        if not (BENCH / "metrics" / f"{m['name']}.py").is_file():
            raise Refused(f"missing bench/metrics/{m['name']}.py")
    limits = load_json(BENCH / "limits" / f"{cell}.json")
    return dict(entry=entry, config=config, traffic=traffic, jobs=jobs,
                limits=limits, end_to_end=end_to_end, per_layer=per_layer)


def check_layout() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no src/repro next to {BENCH}: run from a checkout "
                      f"of the repository")


class Context:
    """What a traffic job module and a metric reader see of the run."""

    def __init__(self, args, cell: dict, jax, api, devices, clock):
        self.args = args
        self.seed = args.seed
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.limits = cell["limits"]
        self.jax = jax
        self.api = api
        self.devices = devices
        self.clock = clock
        self.trace_summary: dict | None = None
        self.job = None  # the traffic job module's Job
        self.window = None  # its Window after the run
        self.window_wall = (0.0, 0.0)  # time.time() around the window

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        return self.jax.profiler.TraceAnnotation(name)


def configure_cache(jax) -> None:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however fast
    it compiles, so that only a cell's first run in a checkout compiles."""
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chip_devices(jax, chips: int) -> list:
    """The cell's chips; refuses when JAX has no TPU or too few."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX sees "
                      f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_per_layer(ctx: Context, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Tracer:
    """The profiler for one traced window: a traffic job module starts it
    where the window begins and stops it where it ends (each at most once)."""

    def __init__(self, jax, log_dir: str):
        self.jax, self.log_dir = jax, log_dir
        self.state = "idle"

    def start(self) -> None:
        if self.state == "idle":
            options = self.jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            self.jax.profiler.start_trace(self.log_dir,
                                          profiler_options=options)
            self.state = "on"

    def stop(self) -> None:
        if self.state == "on":
            self.jax.profiler.stop_trace()
            self.state = "done"


def traced(ctx: Context, job) -> dict:
    """Run the traced window; returns the trace's summary."""
    from bench import trace

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    tracer = Tracer(ctx.jax, log_dir)
    try:
        try:
            ctx.window = job.run(ctx.args.seconds, tracer)
        finally:
            tracer.stop()
        summary = trace.summarize(trace.load(log_dir))
        if summary["dropped"]:
            print("bench: the trace dropped buffers; its per-layer numbers "
                  "miss operations", file=sys.stderr)
        return summary
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("high",), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        check_layout()
        cell = resolve(args.workload)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    configure_cache(jax)
    try:
        devices = chip_devices(jax, cell["entry"]["chips"])
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    from repro import api

    clock = CompileClock()
    jax.monitoring.register_event_time_span_listener(clock)
    ctx = Context(args, cell, jax, api, devices, clock)
    job = ctx.job = load_module(cell["jobs"]).Job(ctx)
    job.warm()
    setup_s = time.time() - T_START

    t0 = time.time()
    if args.trace:
        ctx.trace_summary = traced(ctx, job)
    else:
        ctx.window = job.run(args.seconds, None)
    ctx.window_wall = (t0, time.time())
    window = ctx.window
    peak = memory_peak(devices)
    job.release()
    if args.control:
        for c in job.check(window):
            print(f"sound check {c['name']}: {c['value']!r}", file=sys.stderr)
    checks = job.check(window, control=args.control)
    correct = all(c["ok"] for c in checks)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": window.attempted,
            "failed": window.failed}
    if args.trace:
        summary = ctx.trace_summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        line["metrics"] = read_per_layer(ctx, cell["per_layer"])
        line["breakdown"] = {"device_ops": summary["top_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    else:
        values = dict(job.metrics(window), setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
    line["device"] = device
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
