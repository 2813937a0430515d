"""Scan-fused executor: bit-for-bit equivalence with the event engine across
the protocol x delay zoo grid, the one-dispatch-per-run contract, eval-batch
bucketing, and the batched sweep runner."""

import dataclasses

import numpy as np
import pytest

from repro import api
from repro.core import baselines, engine, executor
from repro.core.simulate import ClusterModel

K, D = 4, 256


def _cluster(delay="constant", delay_params=None, sigma=5.0, **kw):
    return ClusterModel(num_workers=K, straggler_sigma=sigma,
                        delay_model=delay,
                        delay_params=tuple((delay_params or {}).items()), **kw)


def _assert_runs_identical(got, want):
    assert len(got.records) == len(want.records)
    for rg, rw in zip(got.records, want.records):
        for f in dataclasses.fields(rg):
            a, b = getattr(rg, f.name), getattr(rw, f.name)
            assert a == b, (f.name, a, b, rg.iteration)
    np.testing.assert_array_equal(got.w, want.w)
    np.testing.assert_array_equal(got.alpha, want.alpha)
    if want.alpha_applied is None:
        assert got.alpha_applied is None
    else:
        np.testing.assert_array_equal(got.alpha_applied, want.alpha_applied)


def _run(problem, method, cluster, executor_name, *, num_outer=3,
         eval_every=2, seed=0):
    session = api.Session(problem, method, cluster, num_outer=num_outer,
                          eval_every=eval_every, seed=seed,
                          executor=executor_name)
    res = session.run()
    return res, session


# ---------------------------------------------------------------------------
# Bit-for-bit equivalence across the zoo grid.
# ---------------------------------------------------------------------------

# The four scan-capable protocols at zoo-preset shapes (scaled down).
_METHODS = {
    "sync": lambda: baselines.cocoa_plus(K, H=48),
    "cocoa": lambda: baselines.cocoa_v1(K, H=48),
    "cocoa_plus": lambda: baselines.cocoa_plus_solver(
        K, H=48, local_solver="accelerated"),
    "lag": lambda: baselines.acpd_lag(K, D, B=2, T=6, rho_d=32, gamma=0.5,
                                      H=48),
}

_ZOO_DELAYS = {
    "constant": {},
    "shifted_exponential": {"tail_mean": 1.0},
    "pareto": {"shape": 1.8, "scale": 0.5},
    "markov": {"p_slow": 0.1, "p_recover": 0.25, "slow_factor": 8.0},
    "bandwidth_coupled": {"link_slowdown": 20.0},
}


@pytest.mark.parametrize("delay", sorted(_ZOO_DELAYS))
@pytest.mark.parametrize("protocol", sorted(_METHODS))
def test_scan_matches_event_bit_for_bit(small_problem, protocol, delay):
    """The acceptance contract: executor='scan' reproduces executor='event'
    exactly -- trajectories, byte/time accounting, certificates -- for every
    supported (protocol, delay) zoo cell; the one unsupported cell
    (lag x markov, per-launch chain draws) must fall back loudly."""
    method = _METHODS[protocol]()
    cluster = _cluster(delay, _ZOO_DELAYS[delay],
                       sigma=1.0 if delay == "bandwidth_coupled" else 5.0)
    ok, why = executor.scan_supported(method, cluster)
    if not ok:
        assert (protocol, delay) == ("lag", "markov"), (protocol, delay, why)
        _, session = _run(small_problem, method, cluster, "auto",
                          num_outer=1)
        assert session.executor == "event"  # auto falls back
        with pytest.raises(ValueError, match="markov"):
            api.Session(small_problem, method, cluster, num_outer=1,
                        executor="scan")
        return
    ev, _ = _run(small_problem, method, cluster, "event")
    sc, session = _run(small_problem, method, cluster, "scan")
    assert session.executor == "scan"
    _assert_runs_identical(sc, ev)


@pytest.mark.parametrize("protocol", ["sync", "lag"])
def test_scan_handles_empty_round_budget(small_problem, protocol):
    """num_outer=0 must behave like the event executor: empty records,
    zero-initialized state, no crash."""
    res, _ = _run(small_problem, _METHODS[protocol](), _cluster(), "scan",
                  num_outer=0)
    assert res.records == []
    assert not res.w.any()


def test_scan_is_the_auto_choice_for_lockstep(small_problem):
    _, session = _run(small_problem, baselines.cocoa_plus(K, H=16),
                      _cluster(), "auto", num_outer=1)
    assert session.executor == "scan"


@pytest.mark.parametrize("protocol", ["group", "async", "adaptive_b"])
def test_event_protocols_stay_on_the_queue(small_problem, protocol):
    method = {
        "group": lambda: baselines.acpd(K, D, B=2, T=4, rho_d=32, H=16),
        "async": lambda: baselines.acpd_async(K, D, T=4, rho_d=32, H=16),
        "adaptive_b": lambda: baselines.acpd_adaptive(K, D, T=4, rho_d=32,
                                                      H=16),
    }[protocol]()
    _, session = _run(small_problem, method, _cluster(), "auto", num_outer=1)
    assert session.executor == "event"
    with pytest.raises(ValueError, match="executor='scan'"):
        api.Session(small_problem, method, _cluster(), num_outer=1,
                    executor="scan")


def test_scan_early_stop_routing(small_problem):
    """target_gap scans for lockstep (in-graph certificates + done mask);
    time_budget and non-lockstep early stop keep the event loop."""
    m = baselines.cocoa_plus(K, H=16)
    with pytest.raises(ValueError, match="executor='scan'"):
        api.Session(small_problem, m, _cluster(), num_outer=1,
                    executor="scan", time_budget=1.0)
    with pytest.raises(ValueError, match="unknown executor"):
        api.Session(small_problem, m, _cluster(), num_outer=1,
                    executor="fused")
    # auto + target_gap: lockstep scans, lag falls back to the event loop.
    s = api.Session(small_problem, m, _cluster(), num_outer=1,
                    target_gap=1e-12)
    assert s.executor == "scan"
    s = api.Session(small_problem, _METHODS["lag"](), _cluster(),
                    num_outer=1, target_gap=1e-12)
    assert s.executor == "event"
    with pytest.raises(ValueError, match="executor='scan'"):
        api.Session(small_problem, _METHODS["lag"](), _cluster(),
                    num_outer=1, executor="scan", target_gap=1e-12)
    # auto + time_budget: event for everyone.
    s = api.Session(small_problem, m, _cluster(), num_outer=1,
                    time_budget=1.0)
    assert s.executor == "event"
    # auto + target_gap caps the round budget: the gap scan computes masked
    # rounds to the end, so huge budgets stay on the stop-at-the-hit event
    # loop (forcing executor="scan" still overrides).
    big = executor.GAP_SCAN_AUTO_MAX_ROUNDS + 1
    s = api.Session(small_problem, m, _cluster(), num_outer=big,
                    target_gap=1e-12)
    assert s.executor == "event"
    s = api.Session(small_problem, m, _cluster(), num_outer=big,
                    target_gap=1e-12, executor="scan")
    assert s.executor == "scan"


@pytest.mark.parametrize("protocol", sorted(executor.LOCKSTEP_PROTOCOLS))
def test_target_gap_scan_matches_event_stream(small_problem, protocol):
    """The early-stop satellite contract: a target_gap run on the scan
    backend reproduces the event loop's streamed session exactly -- the
    same interleaved event sequence, the same truncation point, the same
    certificates -- both when the target is hit mid-run and when the budget
    completes first."""
    method = _METHODS[protocol]()
    # A target the run reaches partway: the 4th eval boundary's gap.
    probe, _ = _run(small_problem, method, _cluster(), "scan", num_outer=30,
                    eval_every=2)
    for target, want_reason in (
            (probe.records[3].gap * 1.0000001, "target_gap"),
            (probe.records[-1].gap * 0.5, "completed")):
        kw = dict(num_outer=30, eval_every=2, seed=0, target_gap=target)
        sessions = {}
        events = {}
        for exe in ("event", "scan"):
            sessions[exe] = api.Session(small_problem, method, _cluster(),
                                        executor=exe, **kw)
            events[exe] = list(sessions[exe])
        assert sessions["scan"].executor == "scan"
        assert [type(e) for e in events["event"]] == \
            [type(e) for e in events["scan"]]
        for a, b in zip(events["event"], events["scan"]):
            assert a == b, (a, b)
        assert events["scan"][-1].reason == want_reason
        _assert_runs_identical(sessions["scan"].result(),
                               sessions["event"].result())


def test_scan_session_streams_the_same_events(small_problem):
    """The executor axis must be invisible to event-stream consumers: same
    event types, same payloads, in the same order."""
    m = baselines.cocoa_plus(K, H=32)
    kw = dict(num_outer=4, eval_every=2, seed=1)
    ev = list(api.Session(small_problem, m, _cluster(), executor="event",
                          **kw))
    sc = list(api.Session(small_problem, m, _cluster(), executor="scan",
                          **kw))
    assert [type(e) for e in ev] == [type(e) for e in sc]
    for a, b in zip(ev, sc):
        assert a == b, (a, b)


# ---------------------------------------------------------------------------
# The one-dispatch-per-run contract.
# ---------------------------------------------------------------------------


@pytest.fixture
def dispatch_counter():
    """Snapshot executor.STATS around a test: compiled-call and retrace
    counts for the scan backends."""
    before = dict(executor.STATS)
    yield lambda: {k: executor.STATS[k] - before[k] for k in executor.STATS}


def test_lockstep_one_compiled_call_per_run(small_problem, dispatch_counter):
    m = baselines.cocoa_plus(K, H=16)
    for seed in range(3):
        _run(small_problem, m, _cluster(), "scan", num_outer=2, seed=seed)
    delta = dispatch_counter()
    assert delta["lockstep_calls"] == 3
    # Same shapes across seeds: at most ONE fresh trace for the whole batch.
    assert delta["lockstep_traces"] <= 1


def test_lag_one_compiled_call_per_run(small_problem, dispatch_counter):
    m = _METHODS["lag"]()
    for seed in range(2):
        _run(small_problem, m, _cluster(), "scan", num_outer=1, seed=seed)
    delta = dispatch_counter()
    assert delta["lag_calls"] == 2
    assert delta["lag_traces"] <= 1


def test_lag_scan_round_count_scales_free_of_dispatches(small_problem,
                                                        dispatch_counter):
    """More rounds must NOT mean more compiled calls (the whole point):
    double the budget, still one call."""
    m = _METHODS["lag"]()
    _run(small_problem, m, _cluster(), "scan", num_outer=2)
    assert dispatch_counter()["lag_calls"] == 1


def test_lag_accounts_replays_the_clock_and_checks_the_pop_order():
    """The host replay of a lag run's accounting: IEEE float64 clocks from
    the device's decisions, and a loud failure if the device popped the
    workers in another order than the float64 arrival times give."""
    needs, T, lat, bw = np.asarray([1]), 2, 1e-3, 1e3
    durations = np.asarray([[1.0, 2.0], [0.5, 0.5]])
    lf = np.ones(2)
    init_bytes = np.asarray([8, 8])
    reply_bytes, launch_bytes = np.asarray([[16, 0]]), np.asarray([[8, 0]])
    (acct,) = executor.lag_accounts(needs, T, durations, lf, lat, bw,
                                    init_bytes, np.asarray([[0, 1]]),
                                    reply_bytes, launch_bytes)
    assert acct.sim_time == 0.0 + 1.0 + (lat + 8 / bw)  # worker 0 first
    assert (acct.arrivals, acct.bytes_up, acct.bytes_down) == (1, 24, 16)
    assert acct.comm_time == (lat + 8 / bw) + (lat + 8 / bw) \
        + (lat + 16 / bw) + (lat + 8 / bw)
    with pytest.raises(RuntimeError, match="popped workers"):
        executor.lag_accounts(needs, T, durations, lf, lat, bw, init_bytes,
                              np.asarray([[1, 0]]), reply_bytes,
                              launch_bytes)


# ---------------------------------------------------------------------------
# Deferred-eval bucketing.
# ---------------------------------------------------------------------------


def test_eval_bucket_sizes():
    assert [engine._bucket_size(n) for n in (1, 2, 3, 4, 5, 8, 9, 17)] == \
        [1, 2, 4, 4, 8, 8, 16, 32]


def test_eval_bucketing_reuses_one_compile(small_problem):
    """Snapshot counts within one power-of-two bucket must share a compiled
    eval (the retrace-per-count behavior this fixes), without moving any
    record value (lax.map rows are independent; pinned by the equivalence
    suite above)."""
    m = baselines.cocoa_plus(K, H=16)
    # Warm the 8-bucket (5 snapshots), then 6, 7, 8 must not retrace.
    _run(small_problem, m, _cluster(), "scan", num_outer=5, eval_every=1)
    cache = engine._eval_batched._cache_size()
    for outer in (6, 7, 8):
        _run(small_problem, m, _cluster(), "scan", num_outer=outer,
             eval_every=1)
    assert engine._eval_batched._cache_size() == cache


# ---------------------------------------------------------------------------
# The batched sweep runner.
# ---------------------------------------------------------------------------


def test_sweep_map_mode_is_bit_identical_to_single_runs(small_problem,
                                                        dispatch_counter):
    m = baselines.cocoa_plus(K, H=32)
    variants = api.run_lockstep_sweep(
        small_problem, m, _cluster(), num_outer=4, seeds=(0, 5),
        gammas=(1.0, 0.5), eval_every=2, batch="map")
    assert [(v.seed, v.gamma) for v in variants] == [
        (0, 1.0), (0, 0.5), (5, 1.0), (5, 0.5)]
    assert dispatch_counter()["sweep_calls"] == 1  # 4 runs, one dispatch
    for v in variants:
        single, _ = _run(small_problem, dataclasses.replace(m, gamma=v.gamma),
                         _cluster(), "scan", num_outer=4, eval_every=2,
                         seed=v.seed)
        _assert_runs_identical(v.result, single)


def test_sweep_vmap_mode_converges_deterministically(small_problem):
    m = baselines.cocoa_plus(K, H=32)
    a = api.run_lockstep_sweep(small_problem, m, _cluster(), num_outer=4,
                               seeds=(0, 1), eval_every=2)
    b = api.run_lockstep_sweep(small_problem, m, _cluster(), num_outer=4,
                               seeds=(0, 1), eval_every=2)
    for va, vb in zip(a, b):
        _assert_runs_identical(va.result, vb.result)
        assert va.result.records[-1].gap < va.result.records[0].gap
    # Seed sweeps share the method's timing model but not trajectories.
    assert a[0].result.records[-1].gap != a[1].result.records[-1].gap


def test_sweep_with_no_eval_boundaries(small_problem):
    """eval_every > num_outer: empty records per variant, like a Session
    with the same parameters (used to crash in the padded eval)."""
    m = baselines.cocoa_plus(K, H=16)
    variants = api.run_lockstep_sweep(small_problem, m, _cluster(),
                                      num_outer=2, seeds=(0,), eval_every=5)
    assert variants[0].result.records == []
    assert np.isfinite(variants[0].result.w).all()


def test_sweep_rejects_event_only_protocols(small_problem):
    with pytest.raises(ValueError, match="lockstep"):
        api.run_lockstep_sweep(small_problem,
                               baselines.acpd(K, D, H=16), _cluster(),
                               num_outer=1)


def test_sweep_spec_entry(small_problem):
    spec = api.build_preset("zoo-constant", quick=True)
    variants = api.sweep_spec(spec, "CoCoA+", seeds=(0, 1), batch="map")
    assert len(variants) == 2
    for v in variants:
        assert v.result.records[-1].gap < v.result.records[0].gap


# ---------------------------------------------------------------------------
# Spec threading.
# ---------------------------------------------------------------------------


def test_spec_executor_field_round_trips():
    spec = api.build_preset("zoo-constant", quick=True)
    assert spec.executor == "auto"
    forced = dataclasses.replace(spec, executor="event")
    back = api.ExperimentSpec.from_json(forced.to_json())
    assert back == forced
    # Old spec JSONs without the field keep working.
    d = spec.to_dict()
    del d["executor"]
    assert api.ExperimentSpec.from_dict(d).executor == "auto"


def test_experiment_threads_spec_executor(small_problem):
    spec = api.build_preset("zoo-constant", quick=True)
    exp = api.Experiment(dataclasses.replace(spec, executor="event"))
    assert exp.session(spec.methods[0]).executor == "event"
    exp = api.Experiment(spec)
    assert exp.session(spec.methods[0]).executor == "scan"  # CoCoA+ is sync
