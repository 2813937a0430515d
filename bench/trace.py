"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace (``*.xplane.pb`` under ``<log_dir>/plugins/profile/<run>/``) holds
one plane per TPU (``/device:TPU:<i>``) whose ``XLA Modules`` line has one
event per program launch and whose ``XLA Ops`` line has one event per
operation, and a host plane whose threads carry the harness's own
``jax.profiler.TraceAnnotation`` spans (all named ``bench.*``).  Times are
nanoseconds on one clock.

:func:`summarize` turns that into:

* ``window_s``: the traced window, the ``bench.window`` host span;
* ``busy_s``: per device, the union of its op intervals inside the window,
  averaged over the devices;
* ``modules``: per program name, device seconds and launches (averaged over
  the devices);
* ``collective_s`` / ``collective_exposed_s``: time in cross-chip
  collectives, and the part of it with no other op running on that chip;
* ``top_ops``: the operations that took most device time, as
  ``<program>/<op>``;
* ``idle_gaps``: idle device time inside the window by the innermost
  ``bench.*`` host span that was open at each gap's midpoint;
* ``dropped``: the chip's trace buffers overflowed, so ops are missing.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

WINDOW_SPAN = "bench.window"
DROPPED = "Trace Buffers Dropped"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Device:
    ops: list  # (name, start_ns, end_ns)
    modules: list  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> Device
    host_spans: list  # (name, start_ns, end_ns), bench.* only
    dropped: bool = False  # the device's trace buffers overflowed


def program_name(module: str) -> str:
    """``jit__worker_rounds_fused(123)`` -> ``_worker_rounds_fused``."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    return name[4:] if name.startswith("jit_") else name


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read one ``*.xplane.pb`` (gzipped when it ends in ``.gz``), or the
    newest one under a profiler log dir."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices, host, dropped = {}, [], False
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = Device([], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "XLA Modules":
                    dev.modules = _events(line)
                elif line.name == "XLA TraceMe":
                    dropped |= any(e.name == DROPPED for e in line.events)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[0].startswith("bench."))
    return Trace(devices, host, dropped)


def _events(line) -> list:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append((e.name, start, start + float(e.duration_ns)))
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` between sorted busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` -> ``%fusion.3
    fusion``: the instruction's name and opcode, without its shapes."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    opcode = re.search(r"\s([a-z][\w-]*)\(", " " + rest)
    return f"{name} {opcode.group(1)}" if opcode else name


def _innermost(spans: list, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no bench span"


def _owner(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][2] >= t:
        return program_name(modules[i][0])
    return "?"


def summarize(trace: Trace, top: int = 10) -> dict:
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    windows = [(s, e) for name, s, e in trace.host_spans
               if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        events = [ev for d in trace.devices.values() for ev in d.ops]
        lo, hi = min(ev[1] for ev in events), max(ev[2] for ev in events)
    spans = [sp for sp in trace.host_spans if sp[0] != WINDOW_SPAN]
    n_dev = len(trace.devices)
    busy_ns = coll_ns = exposed_ns = 0.0
    module_ns = collections.Counter()
    module_n = collections.Counter()
    op_ns = collections.Counter()
    idle_ns = collections.Counter()
    for dev in trace.devices.values():
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev.ops
               if e > lo and s < hi]
        busy = union((s, e) for _, s, e in ops)
        busy_ns += total(busy)
        coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        other = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        coll_ns += total(coll)
        exposed_ns += total(coll) - overlap(coll, other)
        mods = sorted((m for m in dev.modules if m[2] > lo and m[1] < hi),
                      key=lambda m: m[1])
        for name, s, e in mods:
            module_ns[program_name(name)] += min(e, hi) - max(s, lo)
            module_n[program_name(name)] += 1
        starts = [m[1] for m in mods]
        for name, s, e in ops:
            op_ns[f"{_owner(mods, starts, s)}/{op_name(name)}"] += e - s
        for s, e in gaps(busy, lo, hi):
            idle_ns[_innermost(spans, (s + e) / 2)] += e - s
    ns = 1e-9 / n_dev
    return {
        "devices": n_dev,
        "dropped": trace.dropped,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * ns,
        "modules": {k: {"seconds": v * ns, "launches": module_n[k] / n_dev}
                    for k, v in module_ns.items()},
        "collective_s": coll_ns * ns,
        "collective_exposed_s": exposed_ns * ns,
        "top_ops": [[k, v * ns] for k, v in op_ns.most_common(top)],
        "idle_gaps": [[k, v * ns] for k, v in idle_ns.most_common(top)],
    }
