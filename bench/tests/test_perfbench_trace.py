"""The trace reduction on hand-made events and on a trace recorded on the
chip."""

import os

import pytest

from bench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "gap_trace.xplane.pb.gz")


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]


def test_gaps_and_overlap():
    busy = [(1, 3), (5, 6)]
    assert trace.gaps(busy, 0, 8) == [(0, 1), (3, 5), (6, 8)]
    assert trace.overlap([(0, 4), (6, 9)], [(2, 7)]) == 3


def test_program_name():
    assert trace.program_name("jit__worker_rounds_fused(42)") == \
        "_worker_rounds_fused"
    assert trace.program_name("jit_primal_objective") == "primal_objective"


def test_op_name_keeps_name_and_opcode():
    assert trace.op_name(
        "%while.28 = (s32[]{:T(128)}, f32[16,32]{1,0:T(8,128)}) "
        "while((s32[]{:T(128)}, f32[16,32]{1,0:T(8,128)}) %tuple.121), "
        "condition=%c, body=%b") == "%while.28 while"
    assert trace.op_name(
        "%dynamic-slice_reduce_fusion.17 = f32[47236]{0:T(1024)S(1)} "
        "fusion(f32[1265,47236]{1,0:T(8,128)} %g.881), kind=kLoop") == \
        "%dynamic-slice_reduce_fusion.17 fusion"
    assert trace.op_name("fusion.1") == "fusion.1"


def _hand_trace():
    ms = 1e6  # events are in ns
    dev = trace.Device(
        ops=[("fusion.1", 1 * ms, 3 * ms), ("all-reduce.2", 2 * ms, 5 * ms),
             ("fusion.3", 7 * ms, 8 * ms)],
        modules=[("jit_step(1)", 1 * ms, 5 * ms),
                 ("jit_cert(2)", 7 * ms, 8 * ms)])
    host = [("bench.window", 0, 10 * ms), ("bench.events", 0, 6 * ms),
            ("bench.result", 6 * ms, 10 * ms)]
    return trace.Trace({"/device:TPU:0": dev}, host)


def test_summarize_by_hand():
    s = trace.summarize(_hand_trace())
    assert s["window_s"] == pytest.approx(10e-3)
    assert s["busy_s"] == pytest.approx(5e-3)  # [1,5] and [7,8]
    assert s["modules"]["step"] == {"seconds": pytest.approx(4e-3),
                                    "launches": 1}
    assert s["collective_s"] == pytest.approx(3e-3)
    assert s["collective_exposed_s"] == pytest.approx(2e-3)  # [3,5]
    ops = dict(s["top_ops"])
    assert ops["step/all-reduce.2"] == pytest.approx(3e-3)
    assert ops["cert/fusion.3"] == pytest.approx(1e-3)
    gaps = dict(s["idle_gaps"])
    # Idle [0,1] under bench.events; [5,7] (midpoint 6, where both spans
    # are open: the shorter wins) and [8,10] under bench.result.
    assert gaps["bench.events"] == pytest.approx(1e-3)
    assert gaps["bench.result"] == pytest.approx(4e-3)


def test_summarize_refuses_a_trace_without_a_chip():
    with pytest.raises(ValueError, match="no TPU"):
        trace.summarize(trace.Trace({}, []))


@pytest.fixture(scope="module")
def recorded():
    """The profiler's own file from a traced run of ``rcv1.acpd.gap`` at a
    small size (384 rows, d = 2,048) on one TPU v5 lite, gzipped; its
    window ran from the session's start to its 10th round."""
    return trace.summarize(trace.load(RECORDED))


def test_recorded_trace_reduces_as_it_did_on_the_chip(recorded):
    # The numbers the same reduction printed in that run's result line.
    assert recorded["devices"] == 1 and not recorded["dropped"]
    assert recorded["busy_s"] == pytest.approx(0.020746243)
    assert recorded["window_s"] == pytest.approx(0.264645664)
    assert recorded["top_ops"][0] == [
        "_worker_rounds_fused/%while.28 while", pytest.approx(0.020417016)]
    gaps = dict(recorded["idle_gaps"])
    assert gaps["bench.events"] == pytest.approx(0.23744651)
    assert gaps["bench.session_init"] == pytest.approx(0.006452911)


def test_recorded_trace_names_the_layers(recorded):
    modules = recorded["modules"]
    for program in ("_worker_rounds_fused", "_server_apply_fused",
                    "primal_from_dual", "primal_objective", "dual_objective"):
        assert modules[program]["seconds"] > 0, program
    # The session's first launch of all 16 workers, then 10 rounds (9
    # groups of B = 8 and one full barrier), each one worker dispatch and
    # one server apply; two certificates.
    assert modules["_worker_rounds_fused"]["launches"] == 11
    assert modules["_server_apply_fused"]["launches"] == 10
    assert modules["primal_from_dual"]["launches"] >= 2
    busy, window = recorded["busy_s"], recorded["window_s"]
    assert 0 < busy <= window
    assert sum(m["seconds"] for m in modules.values()) <= window
    assert recorded["collective_s"] == 0
    idle = sum(v for _, v in recorded["idle_gaps"])
    assert idle == pytest.approx(window - busy)
