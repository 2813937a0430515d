"""The reading of the program's own spans and scopes from a profiler trace
(:mod:`bench.program_trace`), on hand-made traces and on traces recorded on
the chip."""

import os
import random

import pytest

from bench import program_trace as pt
from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "gap_trace.xplane.pb.gz")
SCOPED = os.path.join(DATA, "gap_trace_scoped.xplane.pb.gz")


# -- a hand-made XSpace in protobuf wire format ------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _message(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _xspace() -> bytes:
    """A host plane, and one TPU plane whose ``XLA Ops`` line holds six
    events of four ops: one whose ``tf_op`` is a string, one whose
    ``tf_op`` refers to a stat's name, and two with none."""
    stat_meta = [(7, "flops"), (9, "tf_op"), (11, "jit(f)/acpd.filter/sort:")]
    event_meta = {
        1: ("%while.1 = while(...)", []),
        2: ("%fusion.2 = fusion(...)",
            [_message((1, 9), (5, "jit(f)/while/body/acpd.solve/mul:"))]),
        3: ("%sort.3 = sort(...)", [_message((1, 9), (7, 11))]),
        4: ("%copy.4 = copy(...)", [_message((1, 7), (4, 12))]),
    }
    plane = [(1, 5), (2, "/device:TPU:0")]
    events = [_message((1, mid), (2, 100 * i), (3, 50))
              for i, mid in enumerate([1, 2, 2, 3, 4, 4])]
    plane.append((3, _message((2, "XLA Modules"))))
    plane.append((3, _message((2, "XLA Ops"), *[(4, e) for e in events])))
    for mid, (name, stats) in event_meta.items():
        meta = _message((1, mid), (2, name), *[(5, s) for s in stats])
        plane.append((4, _message((1, mid), (2, meta))))
    for sid, name in stat_meta:
        plane.append((5, _message((1, sid), (2, _message((1, sid),
                                                          (2, name))))))
    host = _message((2, "/host:CPU"), (3, _message((2, "python"))))
    return _message((1, host), (1, _message(*plane)))


def test_op_paths_read_the_tf_op_stat_in_event_order():
    assert pt.op_paths(_xspace()) == {"/device:TPU:0": [
        "", "jit(f)/while/body/acpd.solve/mul:",
        "jit(f)/while/body/acpd.solve/mul:", "jit(f)/acpd.filter/sort:",
        "", ""]}


def test_scope_of_takes_the_innermost_acpd_component():
    assert pt.scope_of("jit(f)/acpd.solve/jit(g)/acpd.filter/sort:") == \
        "acpd.filter"
    assert pt.scope_of("jit(_worker_rounds_fused)/while/body/closed_call/"
                       "acpd.solve/jit(solve_subproblem)/dot_general:") == \
        "acpd.solve"
    assert pt.scope_of("jit(primal_objective)/dot_general:") is None


def test_self_time_goes_to_the_scope_inside_a_while():
    ms = 1e6
    solve = "jit(f)/while/body/acpd.solve/mul:"
    ops = [
        ("%while.outer", 0, 10 * ms),  # no tf_op: its body holds two scopes
        ("%while.inner", 1 * ms, 6 * ms),  # no tf_op: solve's step loop
        ("%fusion.a", 2 * ms, 3 * ms),  # solve
        ("%copy.b", 3 * ms, 3.5 * ms),  # no tf_op, inside the solve loop
        ("%fusion.c", 4 * ms, 5 * ms),  # solve
        ("%sort.d", 7 * ms, 9 * ms),  # filter
        ("%copy.e", 11 * ms, 12 * ms),  # no tf_op, at the top
    ]
    paths = ["", "", solve, "", solve, "jit(f)/acpd.filter/sort:", ""]
    got = pt.scope_seconds(ops, paths)
    # The step loop's own 2.5 ms and the copy in it are solve time; the
    # outer loop's own 3 ms (two scopes inside) and the top copy are not.
    assert got == {"acpd.solve": 5 * ms, "acpd.filter": 2 * ms,
                   "unscoped": 4 * ms}
    assert sum(got.values()) == pytest.approx(
        trace.total(trace.union((s, e) for _, s, e in ops)))
    # Without any tf_op, everything is unscoped.
    assert pt.scope_seconds(ops, []) == {"unscoped": 11 * ms}


def test_a_launch_in_one_scope_takes_its_unscoped_loops():
    ms = 1e6
    apply_ = "jit(_server_apply_fused)/acpd.server_apply/add:"
    ops = [("%while.scatter", 0, 4 * ms), ("%fusion.body", 1 * ms, 2 * ms),
           ("%add.1", 5 * ms, 6 * ms),  # the first launch ends here
           ("%while.2", 7 * ms, 9 * ms), ("%add.2", 10 * ms, 11 * ms)]
    paths = ["", "", apply_, "", "jit(g)/add:"]
    modules = [("jit__server_apply_fused(1)", 0, 6 * ms),
               ("jit_g(2)", 7 * ms, 11 * ms)]
    # The first program's code is all server apply, so its loop (and the
    # loop's body with no tf_op) is too; the second's scope is none.
    assert pt.launch_seconds(ops, paths, modules) == {
        "acpd.server_apply": 5 * ms, "unscoped": 3 * ms}


def test_span_seconds_by_name_with_self_time():
    spans = [("bench.events", 0, 100), ("repro.round", 10, 90),
             ("repro.engine.sync", 20, 30), ("repro.engine.sync", 40, 60),
             ("repro.round", 95, 130)]
    got = pt.span_seconds(spans, 0, 120)
    assert got == {"repro.round": [105, 75, 2],
                   "repro.engine.sync": [30, 30, 2]}


def test_split_waits_until_its_worker_program_ends():
    ms = 1e6
    modules = [("jit__worker_rounds_fused(1)", 0, 50 * ms),
               ("jit__server_apply_fused(2)", 55 * ms, 56 * ms),
               ("jit__worker_rounds_fused(1)", 60 * ms, 100 * ms)]
    spans = [("repro.round", 0, 200 * ms),
             # Opens while the first launch runs: waits 40 of its 45 ms.
             ("repro.engine.split", 10 * ms, 55 * ms),
             # Opens after the second launch ended: no wait.
             ("repro.engine.split", 120 * ms, 125 * ms),
             ("repro.engine.sync", 56 * ms, 58 * ms)]
    assert pt.blocked_ns(spans, modules, 0, 200 * ms) == 40 * ms
    # Clipped to the window.
    assert pt.blocked_ns(spans, modules, 30 * ms, 200 * ms) == 20 * ms
    assert pt.blocked_ns(spans, [], 0, 200 * ms) == 0


def test_profile_cell_reads_the_window_counters(monkeypatch):
    import types

    from bench import profile_cell
    from repro.core import tracing

    profiler = types.SimpleNamespace(
        ProfileOptions=types.SimpleNamespace, stop_trace=lambda: None,
        start_trace=lambda *a, **k: None)
    tracer = profile_cell.CountingTracer(
        types.SimpleNamespace(profiler=profiler), "unused")
    monkeypatch.setitem(tracing.STATS, "event_rounds", 7)
    tracer.start()
    for key, n in (("event_rounds", 10), ("event_arrivals", 88),
                   ("host_syncs", 20)):
        monkeypatch.setitem(tracing.STATS, key, tracing.STATS[key] + n)
    tracer.stop()
    tracer.stop()  # a second stop changes nothing
    assert {k: v for k, v in tracer.counts.items() if v} == {
        "event_rounds": 10, "event_arrivals": 88, "host_syncs": 20}
    summary = {
        "scopes": {"acpd.solve": 1.2, "acpd.filter": 0.1},
        "spans": {"repro.round": {"seconds": 1.5},
                  "repro.certificate": {"seconds": 0.1},
                  "repro.engine.sync": {"seconds": 0.02},
                  "repro.certificate.sync": {"seconds": 0.08},
                  "repro.engine.split": {"seconds": 1.3}},
        "split_wait_s": 1.22}
    got = profile_cell.per_round(summary, 10, tracer.counts)
    assert got == {"host_ms": pytest.approx(150.0),
                   "host_own_ms": pytest.approx(28.0),
                   "solve_ms": pytest.approx(120.0),
                   "filter_ms": pytest.approx(10.0), "host_syncs": 2.0,
                   "split_own_ms_per_arrival": pytest.approx(80 / 88)}


def test_innermost_matches_the_scan_of_every_span():
    rng = random.Random(7)
    spans = []
    for i in range(300):
        s = rng.randrange(0, 1000)
        spans.append((f"s{i}", s, s + rng.randrange(0, 80)))
    lookup = pt.Innermost(spans)
    for t in [x / 2 for x in range(-4, 2200)]:
        assert lookup(t) == trace._innermost(spans, t), t


def test_innermost_on_the_hand_trace_and_the_recorded_trace():
    hand = [("bench.events", 0, 6), ("bench.result", 6, 10)]
    lookup = pt.Innermost(hand)
    assert [lookup(t) for t in (-1, 0, 3, 6, 8, 10, 11)] == [
        "no bench span", "bench.events", "bench.events", "bench.result",
        "bench.result", "bench.result", "no bench span"]
    # The recorded trace holds no program span: the gaps read as before.
    got = pt.summarize(pt.load(RECORDED))
    gaps = dict(got["idle_gaps"])
    assert gaps["bench.events"] == pytest.approx(0.23744651)
    assert gaps["bench.session_init"] == pytest.approx(0.006452911)
    assert got["spans"] == {}
    assert got["scopes"] == {"unscoped": pytest.approx(got["busy_s"])}


@pytest.fixture(scope="module")
def scoped():
    """``python3 bench/profile_cell.py --workload rcv1.acpd.gap --small``'s
    profiler file: rounds 11-20 of a run at 384 rows and d = 2,048 on one
    TPU v5 lite, with the program's spans and scopes."""
    return pt.summarize(pt.load(SCOPED))


def test_scoped_trace_reduces_as_it_did_on_the_chip(scoped):
    # The numbers the same reduction printed in that run's result line.
    assert scoped["window_s"] == pytest.approx(0.22008846)
    assert scoped["busy_s"] == pytest.approx(0.017551028)
    assert scoped["scopes"] == {
        "acpd.solve": pytest.approx(0.014457761),
        "acpd.filter": pytest.approx(0.001723439),
        "unscoped": pytest.approx(0.000990793),
        "acpd.worker_state": pytest.approx(0.000322814),
        "acpd.server_apply": pytest.approx(5.6221e-05)}
    spans = scoped["spans"]
    assert spans["repro.round"] == {"seconds": pytest.approx(0.214649919),
                                    "self_seconds": pytest.approx(
                                        0.001207059), "count": 10}
    assert spans["repro.engine.split"]["seconds"] == pytest.approx(
        0.171897812)
    assert scoped["split_wait_s"] == pytest.approx(0.006957489)
    assert spans["repro.engine.sync"]["count"] == 10
    assert spans["repro.certificate"]["count"] == 2
    assert scoped["idle_gaps"][:3] == [
        ["repro.engine.split", pytest.approx(0.163717514)],
        ["repro.engine.server_dispatch", pytest.approx(0.020956862)],
        ["repro.engine.sync", pytest.approx(0.004561011)]]


def test_scoped_trace_splits_the_worker_program(scoped):
    worker = scoped["modules"]["_worker_rounds_fused"]["seconds"]
    solve_filter = scoped["scopes"]["acpd.solve"] + \
        scoped["scopes"]["acpd.filter"]
    assert solve_filter >= 0.9 * worker
    # At this size the worker's slices of X are small: its state in and
    # out is a few percent of the program (at rcv1's full size, 22%).
    assert scoped["scopes"]["acpd.worker_state"] < 0.05 * worker
    assert sum(scoped["scopes"].values()) == pytest.approx(scoped["busy_s"])


def test_scoped_trace_puts_idle_under_program_spans(scoped):
    idle = sum(v for _, v in scoped["idle_gaps"])
    under = sum(v for k, v in scoped["idle_gaps"] if k.startswith("repro."))
    assert idle == pytest.approx(scoped["window_s"] - scoped["busy_s"])
    assert under >= 0.9 * idle


def test_host_syncs_reader(monkeypatch):
    from bench import run
    from repro.core import executor

    reader = run.load_module(run.BENCH / "metrics" / "host_syncs_per_round.py")
    monkeypatch.setattr(executor, "STATS", {"event_rounds": 90,
                                            "host_syncs": 180})
    assert reader.read(None) == 2.0
    # A program that does not count them: no reading, no error.
    monkeypatch.setattr(executor, "STATS", {"sweep_calls": 3})
    assert reader.read(None) is None
