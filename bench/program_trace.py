"""The program's own instrumentation in a JAX profiler trace.

:mod:`bench.trace` reads a trace from the outside: device time per XLA
program, and idle gaps by the harness's ``bench.*`` spans.  The program
marks its own layers too (``repro.core.tracing``):

* host spans named ``repro.<layer>.<what>`` (``repro.round``,
  ``repro.engine.worker_dispatch``, ``repro.certificate.sync``, ...), on
  the same host threads and clock as the harness's;
* device scopes (``jax.named_scope``) named ``acpd.*`` (``acpd.solve``,
  ``acpd.filter``, ``acpd.server_apply``, ``acpd.aggregate``,
  ``acpd.certificate``).  A scope is not an event: it sits in each op's
  ``tf_op`` stat, the op-name path of the JAX code that emitted the op
  (``jit(_worker_rounds_fused)/while/body/acpd.filter/jit(topk)/sort:``).
  ``jax.profiler.ProfileData`` does not expose event-metadata stats, so
  :func:`op_paths` reads that one stat from the ``XSpace`` protobuf.

:func:`summarize` adds to :func:`bench.trace.summarize`'s keys:

* ``scopes``: device self time inside the window by ``acpd.*`` scope
  (:func:`scope_seconds`), ops in none under ``unscoped``;
* ``spans``: per ``repro.*`` name, its time inside the window, its self
  time (less the spans nested in it) and its count;
* ``split_wait_s``: the part of the ``repro.engine.split`` spans spent
  waiting for the worker program (:func:`blocked_ns`): its first eager
  slice blocks until the program's results exist, so the host's own work
  is the split less this;
* ``idle_gaps``: as :mod:`bench.trace`'s, each gap named by the innermost
  span of either family open at its midpoint (:class:`Innermost`, which
  gives ``bench.trace``'s answer by bisection instead of a scan of every
  span for every gap).

``python3 bench/profile_cell.py`` runs a cell's traced window and prints
this summary.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import heapq
import os

from bench import trace

PROGRAM_SPAN = "repro."
SCOPE = "acpd."
UNSCOPED = "unscoped"
TF_OP = "tf_op"


@dataclasses.dataclass
class ProgramTrace:
    # bench.trace's view of the trace, with the spans of both families.
    trace: trace.Trace
    # Per device plane, the tf_op of each op in ``trace.devices[plane].ops``
    # ("" for an op without one); no entry when the counts disagree.
    tf_ops: dict


def load(path: str) -> ProgramTrace:
    """What :func:`bench.trace.load` reads, plus the program's spans and
    each device op's ``tf_op``, from one read and one parse of the file."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace.find_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    devices, host, dropped = {}, [], False
    for plane in data.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            dev = trace.Device([], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = trace._events(line)
                elif line.name == "XLA Modules":
                    dev.modules = trace._events(line)
                elif line.name == "XLA TraceMe":
                    dropped |= any(e.name == trace.DROPPED
                                   for e in line.events)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in trace._events(line)
                            if e[0].startswith(("bench.", PROGRAM_SPAN)))
    paths = {name: ops for name, ops in op_paths(raw).items()
             if name in devices and len(ops) == len(devices[name].ops)}
    return ProgramTrace(trace.Trace(devices, host, dropped), paths)


# -- the tf_op stat, read from the XSpace protobuf itself ----------------
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
# XPlane.name = 2, lines = 3, event_metadata = 4, stat_metadata = 5 (maps:
# key = 1, value = 2); XLine.name = 2, events = 4; XEvent.metadata_id = 1;
# XEventMetadata.stats = 5; XStat.metadata_id = 1, str_value = 5,
# ref_value = 7; XStatMetadata.name = 2.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of the message in ``buf[lo:hi]``: an int
    for a varint, a ``(start, end)`` slice for a length-delimited field,
    None for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, entry) -> tuple:
    key = value = None
    for f, v in _fields(buf, *entry):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_paths(raw: bytes) -> dict:
    """Per device plane name, the ``tf_op`` of each event of its ``XLA
    Ops`` line, in the line's order ("" where an op has none)."""
    out = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, lines, metas, stat_names = None, [], [], {}
        for g, v in _fields(raw, *plane):
            if g == 2:
                name = _text(raw, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                sid, meta = _map_entry(raw, v)
                for h, w in _fields(raw, *meta):
                    if h == 2:
                        stat_names[sid] = _text(raw, w)
        if name is None or not trace._DEVICE_PLANE.match(name):
            continue
        tf_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
        paths = {}
        for entry in metas:
            mid, meta = _map_entry(raw, entry)
            for h, stat in _fields(raw, *meta):
                if h != 5:
                    continue
                stat = dict(_fields(raw, *stat))
                if stat.get(1) != tf_id:
                    continue
                if 5 in stat:
                    paths[mid] = _text(raw, stat[5])
                elif 7 in stat:
                    paths[mid] = stat_names.get(stat[7], "")
        for line in lines:
            fields = list(_fields(raw, *line))
            if not any(g == 2 and _text(raw, v) == "XLA Ops"
                       for g, v in fields):
                continue
            ops = []
            for g, event in fields:
                if g == 4:
                    mid = next((v for h, v in _fields(raw, *event) if h == 1),
                               0)
                    ops.append(paths.get(mid, ""))
            out[name] = ops
    return out


# -- device time by scope ----------------------------------------------------


def scope_of(tf_op: str) -> str | None:
    """The innermost ``acpd.*`` component of an op-name path, else None:
    ``jit(f)/while/body/acpd.solve/jit(g)/mul:`` -> ``acpd.solve``."""
    found = [c.partition(":")[0] for c in tf_op.split("/")
             if c.startswith(SCOPE)]
    return found[-1] if found else None


def scope_seconds(ops: list, tf_ops: list) -> collections.Counter:
    """Device self time of ``ops`` (``(name, start, end)``, one program
    launch's) by scope, in the ops' time unit.

    Op intervals nest (a ``while`` contains its body's ops), so each op
    counts its self time: its interval less the ops nested in it.  An op
    with a ``tf_op`` goes to the innermost ``acpd.*`` scope in it, or to
    ``unscoped``.  XLA's loops and copies carry no ``tf_op``: such an op
    takes the one scope that the ops nested in it resolve to (a loop inside
    the solve is solve time); while its scope is unknown, its time goes to
    the op around it, and at the top to the one scope of the launch's other
    ops (a program whose code is all in one scope), else to ``unscoped``.
    """
    out = collections.Counter()
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    stack = []  # [end, self time, scope or None, scopes inside, handed up]

    def close(frame):
        _, own, scope, inside, handed = frame
        if scope is None and len(inside) == 1:
            scope = next(iter(inside))
        parent = stack[-1] if stack else None
        if scope is not None:
            out[scope] += own + handed
            if parent:
                parent[3].add(scope)
        elif parent:
            parent[4] += own + handed
        else:
            out[None] += own + handed

    for i in order:
        _, start, end = ops[i]
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][1] -= min(end, stack[-1][0]) - start
        path = tf_ops[i] if tf_ops else ""
        scope = (scope_of(path) or UNSCOPED) if path else None
        stack.append([end, end - start, scope, set(), 0.0])
    while stack:
        close(stack.pop())
    unknown = out.pop(None, 0)
    if unknown:
        known = [k for k in out if out[k]]
        one = known[0] if len(known) == 1 and known[0] != UNSCOPED else None
        out[one or UNSCOPED] += unknown
    return out


def launch_seconds(ops: list, tf_ops: list,
                   modules: list) -> collections.Counter:
    """:func:`scope_seconds` of each program launch in ``modules``
    (``(name, start, end)``), summed; ops outside every launch count as
    one more."""
    launches = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in launches]
    groups = collections.defaultdict(list)
    for i, (_, start, _) in enumerate(ops):
        j = bisect.bisect_right(starts, start) - 1
        groups[j if j >= 0 and launches[j][2] >= start else -1].append(i)
    out = collections.Counter()
    for idx in groups.values():
        out.update(scope_seconds([ops[i] for i in idx],
                                 [tf_ops[i] for i in idx] if tf_ops else []))
    return out


# -- host spans ------------------------------------------------------------


def span_seconds(spans: list, lo: float, hi: float) -> dict:
    """Per ``repro.*`` span name: ``[total, self, count]`` of its spans
    inside ``[lo, hi]``, self time less the spans (of either family) nested
    in each."""
    inside = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in spans
                     if e > lo and s < hi), key=lambda x: (x[1], -x[2]))
    out = {}
    stack = []  # [name, end, self time]

    def close(frame):
        name, _, own = frame
        if name.startswith(PROGRAM_SPAN):
            out[name][1] += own

    for name, start, end in inside:
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        if name.startswith(PROGRAM_SPAN):
            row = out.setdefault(name, [0.0, 0.0, 0])
            row[0] += end - start
            row[2] += 1
        stack.append([name, end, end - start])
    while stack:
        close(stack.pop())
    return out


# The host span whose first eager op blocks until a worker program's results
# are ready, and those programs.
SPLIT = "repro.engine.split"
WORKER_PROGRAMS = ("_worker_rounds_fused", "_worker_rounds_lag_fused",
                   "_worker_chunk_rounds_fused")


def blocked_ns(spans: list, modules: list, lo: float, hi: float) -> float:
    """Time the :data:`SPLIT` spans inside ``[lo, hi]`` spent waiting: each
    from its start to the end of the last worker program launched before
    it ended (``modules`` are a device's ``(name, start, end)`` launches)."""
    launches = sorted((s, e) for n, s, e in modules
                      if trace.program_name(n) in WORKER_PROGRAMS)
    starts = [s for s, _ in launches]
    out = 0.0
    for name, start, end in spans:
        if name != SPLIT or end <= lo or start >= hi:
            continue
        i = bisect.bisect_left(starts, end) - 1
        if i >= 0:
            out += max(0.0, min(end, hi, launches[i][1]) - max(start, lo))
    return out


class Innermost:
    """The innermost of ``spans`` open at a time ``t``: the shortest span
    with ``start <= t <= end``, the first listed among equals (the answer of
    ``bench.trace._innermost``).  One sweep over the spans' boundaries
    records the answer at each boundary and just after it; a call bisects
    them."""

    NONE = "no bench span"

    def __init__(self, spans: list):
        self.points = sorted({s for _, s, _ in spans}
                             | {e for _, _, e in spans})
        self.at, self.after = [], []
        by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
        heap, nxt = [], 0  # (length, index) of every span opened so far
        for p in self.points:
            while nxt < len(by_start) and spans[by_start[nxt]][1] <= p:
                i = by_start[nxt]
                heapq.heappush(heap, (spans[i][2] - spans[i][1], i))
                nxt += 1
            # A span closed before p is closed for good: times only grow.
            while heap and spans[heap[0][1]][2] < p:
                heapq.heappop(heap)
            self.at.append(spans[heap[0][1]][0] if heap else self.NONE)
            while heap and spans[heap[0][1]][2] <= p:
                heapq.heappop(heap)
            self.after.append(spans[heap[0][1]][0] if heap else self.NONE)

    def __call__(self, t: float) -> str:
        i = bisect.bisect_left(self.points, t)
        if i < len(self.points) and self.points[i] == t:
            return self.at[i]
        return self.after[i - 1] if i else self.NONE


# -- the summary --------------------------------------------------------------


def summarize(pt: ProgramTrace, top: int = 10) -> dict:
    """:func:`bench.trace.summarize` of the trace with the spans of both
    families, plus ``scopes``, ``spans`` and ``split_wait_s`` (seconds,
    averaged over the devices like the rest)."""
    out = trace.summarize(pt.trace, top)
    windows = [(s, e) for name, s, e in pt.trace.host_spans
               if name == trace.WINDOW_SPAN]
    devices = pt.trace.devices
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(op[1] for d in devices.values() for op in d.ops)
        hi = max(op[2] for d in devices.values() for op in d.ops)
    spans = [sp for sp in pt.trace.host_spans if sp[0] != trace.WINDOW_SPAN]
    innermost = Innermost(spans)
    scope_ns, idle_ns = collections.Counter(), collections.Counter()
    wait_ns = 0.0
    for plane, dev in devices.items():
        wait_ns += blocked_ns(spans, dev.modules, lo, hi)
        kept = [i for i, (_, s, e) in enumerate(dev.ops) if e > lo and s < hi]
        ops = [(dev.ops[i][0], max(dev.ops[i][1], lo), min(dev.ops[i][2], hi))
               for i in kept]
        paths = pt.tf_ops.get(plane)
        scope_ns.update(launch_seconds(
            ops, [paths[i] for i in kept] if paths else [], dev.modules))
        busy = trace.union((s, e) for _, s, e in ops)
        for s, e in trace.gaps(busy, lo, hi):
            idle_ns[innermost((s + e) / 2)] += e - s
    ns = 1e-9 / len(devices)
    out["idle_gaps"] = [[k, v * ns] for k, v in idle_ns.most_common(top)]
    out["scopes"] = {k: v * ns for k, v in scope_ns.most_common()}
    out["split_wait_s"] = wait_ns * ns
    out["spans"] = {k: {"seconds": v[0] * 1e-9, "self_seconds": v[1] * 1e-9,
                        "count": v[2]}
                    for k, v in sorted(span_seconds(spans, lo, hi).items())}
    return out
