"""The universal sweep runner (api.run_sweep): protocol x delay x seed x
gamma grids as one compiled call, the shard axis, the eligibility matrix,
and the grid-shape retrace contract.

The single-run executor equivalence suite lives in tests/test_executor.py;
this module pins the SWEEP layer on top of it: per-cell bit-identity of
``batch="map"`` sweeps against ``Session(executor="scan")`` (and therefore
against the event engine), delay-axis batching for lag, pow2 cell padding,
and -- in a 4-fake-device subprocess -- that ``shard="cells"`` changes
nothing but the wall clock.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro.core import baselines, executor
from repro.core.simulate import ClusterModel

K, D = 4, 256

# The delay axis used across this module: every pre-sampleable zoo entry.
SWEEP_DELAYS = (("constant", {}),
                ("shifted_exponential", {"tail_mean": 1.0}),
                ("pareto", {"shape": 1.8, "scale": 0.5}))


def _cluster(delay="constant", delay_params=None, sigma=5.0):
    return ClusterModel(num_workers=K, straggler_sigma=sigma,
                        delay_model=delay,
                        delay_params=tuple((delay_params or {}).items()))


def _lag():
    return baselines.acpd_lag(K, D, B=2, T=6, rho_d=32, gamma=0.5, H=48)


def _assert_result_identical(got, want):
    assert len(got.records) == len(want.records)
    for rg, rw in zip(got.records, want.records):
        assert rg == rw, (rg, rw)
    np.testing.assert_array_equal(got.w, want.w)
    np.testing.assert_array_equal(got.alpha, want.alpha)
    if want.alpha_applied is not None:
        np.testing.assert_array_equal(got.alpha_applied, want.alpha_applied)


@pytest.fixture
def dispatch_counter():
    before = dict(executor.STATS)
    yield lambda: {k: executor.STATS[k] - before[k] for k in executor.STATS}


# ---------------------------------------------------------------------------
# The acceptance grid: lag x delay x seed, ONE compiled call, bit-identical.
# ---------------------------------------------------------------------------


def test_lag_delay_seed_grid_is_one_call_and_bit_identical(small_problem,
                                                           dispatch_counter):
    """The tentpole contract: a lag x (constant, shifted_exponential,
    pareto) x 4-seed grid runs as ONE compiled call and, under
    ``batch="map"``/``shard="none"``, every cell is bit-identical to its
    per-cell ``Session`` run."""
    m = _lag()
    variants = api.run_sweep(small_problem, m, _cluster(), num_outer=2,
                             seeds=(0, 1, 2, 3), delays=SWEEP_DELAYS,
                             eval_every=2, batch="map", shard="none")
    delta = dispatch_counter()
    assert delta["sweep_lag_calls"] == 1  # 12 runs, one dispatch
    assert len(variants) == 12
    assert [(v.delay, v.seed) for v in variants[:5]] == [
        ("constant", 0), ("constant", 1), ("constant", 2), ("constant", 3),
        ("shifted_exponential", 0)]
    for v in variants:
        single = api.Session(
            small_problem, m, _cluster(v.delay, dict(SWEEP_DELAYS)[v.delay]),
            num_outer=2, eval_every=2, seed=v.seed, executor="scan").run()
        _assert_result_identical(v.result, single)


def test_lockstep_delay_axis_rides_free(small_problem, dispatch_counter):
    """Lockstep cells share trajectories across the delay axis (timing is
    host accounting), so the delay axis multiplies variants but not
    compiled work -- and each variant still matches its single run."""
    m = baselines.cocoa_plus(K, H=32)
    variants = api.run_sweep(small_problem, m, _cluster(), num_outer=4,
                             seeds=(0, 5), gammas=(1.0, 0.5),
                             delays=SWEEP_DELAYS, eval_every=2, batch="map",
                             shard="none")
    assert dispatch_counter()["sweep_calls"] == 1
    assert len(variants) == 12  # 3 delays x 2 seeds x 2 gammas
    seen = set()
    for v in variants:
        seen.add((v.delay, v.seed, v.gamma))
        single = api.Session(
            small_problem, dataclasses.replace(m, gamma=v.gamma),
            _cluster(v.delay, dict(SWEEP_DELAYS)[v.delay]),
            num_outer=4, eval_every=2, seed=v.seed, executor="scan").run()
        _assert_result_identical(v.result, single)
    assert len(seen) == 12
    # Same (seed, gamma), different delay: identical trajectory, different
    # simulated clock.
    a = next(v for v in variants if (v.delay, v.seed, v.gamma)
             == ("constant", 0, 1.0))
    b = next(v for v in variants if (v.delay, v.seed, v.gamma)
             == ("pareto", 0, 1.0))
    np.testing.assert_array_equal(a.result.w, b.result.w)
    assert a.result.records[-1].sim_time != b.result.records[-1].sim_time


def test_lag_sweep_distinguishes_same_delay_different_params(small_problem):
    """Regression: two entries of the SAME delay model with different params
    must each get their own duration stream (the cache used to key by name
    alone, silently reusing the first entry's timing)."""
    m = _lag()
    pa, pb = {"shape": 1.8, "scale": 0.5}, {"shape": 1.1, "scale": 5.0}
    variants = api.run_sweep(small_problem, m, _cluster(), num_outer=1,
                             seeds=(0,), delays=(("pareto", pa),
                                                 ("pareto", pb)),
                             eval_every=2, batch="map", shard="none")
    assert len(variants) == 2
    for v, params in zip(variants, (pa, pb)):
        single = api.Session(small_problem, m, _cluster("pareto", params),
                             num_outer=1, eval_every=2, seed=0,
                             executor="scan").run()
        _assert_result_identical(v.result, single)
    assert (variants[0].result.records[-1].sim_time
            != variants[1].result.records[-1].sim_time)


def test_lag_sweep_rejects_unsampleable_delay(small_problem):
    with pytest.raises(ValueError, match="markov"):
        api.run_sweep(small_problem, _lag(), _cluster(), num_outer=1,
                      delays=("constant", ("markov", {"p_slow": 0.1})))


def test_run_sweep_rejects_group_family(small_problem):
    with pytest.raises(ValueError, match="scan-capable"):
        api.run_sweep(small_problem, baselines.acpd(K, D, H=16), _cluster(),
                      num_outer=1)


def test_run_sweep_rejects_empty_axes(small_problem):
    m = baselines.cocoa_plus(K, H=16)
    for kw in (dict(seeds=()), dict(gammas=()), dict(delays=())):
        with pytest.raises(ValueError, match="empty"):
            api.run_sweep(small_problem, m, _cluster(), num_outer=1, **kw)


@pytest.mark.parametrize("method", ["lockstep", "lag"])
def test_lower_sweep_sizes_the_dispatch_without_running_it(
        small_problem, dispatch_counter, method):
    """lower_sweep hands back the very computation run_sweep dispatches
    (same jit, same cache key), compiled on demand and never run."""
    m = baselines.cocoa_plus(K, H=16) if method == "lockstep" else _lag()
    kw = dict(num_outer=1, seeds=(0, 1), gammas=(1.0, 0.5),
              delays=SWEEP_DELAYS, eval_every=1)
    lowered = api.lower_sweep(small_problem, m, _cluster(), **kw)
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes >= small_problem.X.nbytes
    prefix = "sweep" if method == "lockstep" else "sweep_lag"
    assert dispatch_counter()[f"{prefix}_calls"] == 0  # nothing dispatched
    assert dispatch_counter()[f"{prefix}_traces"] == 1
    api.run_sweep(small_problem, m, _cluster(), **kw)
    # The dispatch reuses the trace: it is the same jitted computation.
    assert dispatch_counter()[f"{prefix}_calls"] == 1
    assert dispatch_counter()[f"{prefix}_traces"] == 1


# ---------------------------------------------------------------------------
# Grid-shape retrace contract (the pow2 cell-padding satellite).
# ---------------------------------------------------------------------------


def test_grid_shapes_within_a_bucket_share_one_compile(small_problem,
                                                       dispatch_counter):
    """Distinct (n_seeds, n_gammas) grids used to retrace per shape; with
    the cell axis padded to pow2 buckets, every grid that lands in the same
    bucket reuses one compile (and bigger grids add at most log-many)."""
    m = baselines.cocoa_plus(K, H=16)
    api.run_sweep(small_problem, m, _cluster(), num_outer=3, seeds=(0, 1, 2),
                  eval_every=2, batch="map", shard="none")  # warm the 4-bucket
    warm = dict(executor.STATS)
    grids = [dict(seeds=(0,), gammas=(1.0, 0.7, 0.4, 0.2)),
             dict(seeds=(0, 1), gammas=(1.0, 0.5)),
             dict(seeds=(0, 1, 2, 3))]
    for g in grids:
        api.run_sweep(small_problem, m, _cluster(), num_outer=3,
                      eval_every=2, batch="map", shard="none", **g)
    assert executor.STATS["sweep_traces"] == warm["sweep_traces"]
    assert executor.STATS["sweep_calls"] == warm["sweep_calls"] + 3
    # The eval axis buckets the same way: cadences whose boundary counts
    # land in one pow2 bucket share the compile too.
    api.run_sweep(small_problem, m, _cluster(), num_outer=9, seeds=(0, 1),
                  eval_every=2, batch="map", shard="none")  # 4 boundaries
    warm_eval = executor.STATS["sweep_traces"]
    api.run_sweep(small_problem, m, _cluster(), num_outer=9, seeds=(0, 1),
                  eval_every=3, batch="map", shard="none")  # 3 -> pads to 4
    assert executor.STATS["sweep_traces"] == warm_eval
    # The same contract holds for the lag grid (its own jit entry).
    mlag = _lag()
    api.run_sweep(small_problem, mlag, _cluster(), num_outer=1,
                  seeds=(0, 1, 2), eval_every=2, batch="map", shard="none")
    warm_lag = executor.STATS["sweep_lag_traces"]
    api.run_sweep(small_problem, mlag, _cluster(), num_outer=1,
                  seeds=(5, 6, 7, 8), eval_every=2, batch="map", shard="none")
    assert executor.STATS["sweep_lag_traces"] == warm_lag


# ---------------------------------------------------------------------------
# The eligibility matrix: protocol x delay x executor x shard.
# ---------------------------------------------------------------------------

# Where every (protocol, delay) cell must route under executor="auto", and
# which shard axes a sweep of it may use on a multi-device host.  This is the
# full current registry; a new protocol/delay entry must extend it (the
# completeness asserts below fail otherwise), so routing can never regress
# silently.
_EXPECTED_EXECUTOR = {
    # protocol: {delay: "scan" | "event"}
    "sync": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "scan"),
    "cocoa": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "scan"),
    "cocoa_plus": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "scan"),
    "lag": {"constant": "scan", "shifted_exponential": "scan",
            "pareto": "scan", "bandwidth_coupled": "scan",
            "markov": "event"},
    "group": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "event"),
    "async": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "event"),
    "adaptive_b": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "event"),
    # partial_work scans solo when the (round, chunk, worker) duration
    # stream is pre-sampleable (lag's rule, per chunk); markov's stateful
    # per-launch draws keep the event queue.  Membership schedules and
    # pw_quantum also force event, but the matrix row is the static-cluster
    # default (those cases are pinned in tests/test_partial_work.py).
    "partial_work": {"constant": "scan", "shifted_exponential": "scan",
                     "pareto": "scan", "bandwidth_coupled": "scan",
                     "markov": "event"},
    # Rack-dependent pop counts are host-adaptive: always the event queue.
    "hierarchical_b": dict.fromkeys(
        ["constant", "shifted_exponential", "pareto", "markov",
         "bandwidth_coupled"], "event"),
}

_ZOO_PARAMS = {
    "constant": {},
    "shifted_exponential": {"tail_mean": 1.0},
    "pareto": {"shape": 1.8, "scale": 0.5},
    "markov": {"p_slow": 0.1, "p_recover": 0.25, "slow_factor": 8.0},
    "bandwidth_coupled": {"link_slowdown": 20.0},
}

_MATRIX_METHODS = {
    "sync": lambda: baselines.cocoa_plus(K, H=16),
    "cocoa": lambda: baselines.cocoa_v1(K, H=16),
    "cocoa_plus": lambda: baselines.cocoa_plus_solver(
        K, H=16, local_solver="accelerated"),
    "lag": lambda: baselines.acpd_lag(K, D, B=2, T=4, rho_d=32, gamma=0.5,
                                      H=16),
    "group": lambda: baselines.acpd(K, D, B=2, T=4, rho_d=32, H=16),
    "async": lambda: baselines.acpd_async(K, D, T=4, rho_d=32, H=16),
    "adaptive_b": lambda: baselines.acpd_adaptive(K, D, T=4, rho_d=32, H=16),
    "partial_work": lambda: baselines.acpd_partial_work(
        K, D, B=2, T=4, rho_d=32, H=16, n_chunks=2),
    "hierarchical_b": lambda: baselines.acpd_hierarchical(
        K, D, T=4, rho_d=32, H=16, n_racks=2, rack_b=1),
}


def test_eligibility_matrix_is_complete():
    """The expectation table must cover the full current registries."""
    from repro.core import delays as delays_lib
    from repro.core import engine as engine_lib

    protocols = {p for p in engine_lib.available_protocols()
                 if not p.endswith("_example")}
    assert protocols == set(_EXPECTED_EXECUTOR), (
        "a protocol entered/left the registry; extend the eligibility matrix")
    delays = {d for d in delays_lib.available_delays()
              if not d.endswith("_example")}
    for protocol, row in _EXPECTED_EXECUTOR.items():
        assert set(row) == delays, (
            f"delay registry changed; extend the {protocol!r} matrix row")


@pytest.mark.parametrize("protocol", sorted(_EXPECTED_EXECUTOR))
def test_eligibility_matrix_executor_routing(small_problem, protocol):
    """executor='auto' routes every (protocol, delay) cell exactly as the
    matrix says -- constructing the Session, not just asking the predicate."""
    for delay, want in _EXPECTED_EXECUTOR[protocol].items():
        method = _MATRIX_METHODS[protocol]()
        cluster = _cluster(delay, _ZOO_PARAMS[delay],
                           sigma=1.0 if delay == "bandwidth_coupled" else 5.0)
        ok, _ = executor.scan_supported(method, cluster)
        assert ("scan" if ok else "event") == want, (protocol, delay)
        session = api.Session(small_problem, method, cluster, num_outer=1,
                              executor="auto")
        assert session.executor == want, (protocol, delay)
        # Sweep eligibility follows the same predicate, except for
        # partial_work: it scans SOLO (per-chunk carries are per-run state)
        # but never batches into shared sweep cells.
        if protocol == "partial_work":
            swept, why = api.sweep_supported(method, cluster)
            assert not swept and "sweep" in why
        else:
            assert api.sweep_supported(method, cluster)[0] == ok


def test_eligibility_matrix_shard_routing():
    """resolve_shard: exactly which (protocol, shard, device-count) cells
    produce a sharded plan, which degrade to 'none', and which refuse."""
    lockstep = sorted(executor.LOCKSTEP_PROTOCOLS)
    for protocol in lockstep + ["lag"]:
        # One device: every request degrades to the unsharded path...
        for shard in ("auto", "none", "cells"):
            plan = api.resolve_shard(shard, protocol=protocol, num_workers=K,
                                     n_devices=1)
            assert plan == api.ShardPlan("none", 1), (protocol, shard)
        # ... and with 4 devices, auto/cells shard the cell axis.
        for shard in ("auto", "cells"):
            plan = api.resolve_shard(shard, protocol=protocol, num_workers=K,
                                     n_devices=4)
            assert plan == api.ShardPlan("cells", 4), (protocol, shard)
        assert api.resolve_shard("none", protocol=protocol, num_workers=K,
                                 n_devices=4) == api.ShardPlan("none", 1)
    # Worker sharding: lockstep only, largest pow2 divisor of K that fits.
    for protocol in lockstep:
        assert api.resolve_shard("workers", protocol=protocol, num_workers=4,
                                 n_devices=4) == api.ShardPlan("workers", 4)
        assert api.resolve_shard("workers", protocol=protocol, num_workers=6,
                                 n_devices=4) == api.ShardPlan("workers", 2)
        assert api.resolve_shard("workers", protocol=protocol, num_workers=5,
                                 n_devices=4) == api.ShardPlan("none", 1)
        assert api.resolve_shard("workers", protocol=protocol, num_workers=4,
                                 n_devices=1) == api.ShardPlan("none", 1)
    with pytest.raises(ValueError, match="workers"):
        api.resolve_shard("workers", protocol="lag", num_workers=K,
                          n_devices=4)
    with pytest.raises(ValueError, match="unknown shard"):
        api.resolve_shard("mesh", protocol="sync", num_workers=K)
    # Non-pow2 device counts shard over the largest pow2 subset.
    assert api.resolve_shard("cells", protocol="sync", num_workers=K,
                             n_devices=6) == api.ShardPlan("cells", 4)


def test_shard_auto_degrades_to_none_on_one_device(small_problem):
    """This test process has one CPU device: shard='auto' (and 'cells') must
    produce exactly the shard='none' results -- the 1-device fallback of the
    acceptance contract."""
    m = baselines.cocoa_plus(K, H=16)
    kw = dict(num_outer=3, seeds=(0, 1), eval_every=2, batch="map")
    none = api.run_sweep(small_problem, m, _cluster(), shard="none", **kw)
    for shard in ("auto", "cells"):
        got = api.run_sweep(small_problem, m, _cluster(), shard=shard, **kw)
        for a, b in zip(got, none):
            _assert_result_identical(a.result, b.result)


# ---------------------------------------------------------------------------
# Spec-level threading.
# ---------------------------------------------------------------------------


def test_spec_shard_field_round_trips():
    spec = api.build_preset("zoo-constant", quick=True)
    assert spec.shard == "auto"
    forced = dataclasses.replace(spec, shard="cells")
    assert api.ExperimentSpec.from_json(forced.to_json()) == forced
    d = spec.to_dict()
    del d["shard"]  # old spec JSONs keep their meaning
    assert api.ExperimentSpec.from_dict(d).shard == "auto"


def test_sweep_spec_lag_entry_with_delay_axis(small_problem):
    spec = api.build_preset("zoo-constant", quick=True)
    variants = api.sweep_spec(spec, "ACPD-LAG", seeds=(0, 1),
                              delays=SWEEP_DELAYS, batch="map")
    assert len(variants) == 6
    assert {v.delay for v in variants} == {n for n, _ in SWEEP_DELAYS}
    for v in variants:
        assert v.result.records[-1].gap < v.result.records[0].gap


def test_sweep_spec_threads_spec_shard(small_problem, monkeypatch):
    """sweep_spec forwards the spec's shard field to run_sweep."""
    spec = dataclasses.replace(api.build_preset("zoo-constant", quick=True),
                               shard="none")
    seen = {}
    real = api.sweep.run_sweep

    def spy(*a, **kw):
        seen["shard"] = kw["shard"]
        return real(*a, **kw)

    monkeypatch.setattr(api.sweep, "run_sweep", spy)
    api.sweep_spec(spec, "CoCoA+", batch="map")
    assert seen["shard"] == "none"
    api.sweep_spec(spec, "CoCoA+", batch="map", shard="auto")
    assert seen["shard"] == "auto"


# ---------------------------------------------------------------------------
# The sharded path, end to end (4 fake host devices in a subprocess).
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    from repro import api
    from repro.core import baselines
    from repro.core.simulate import ClusterModel

    K, D = 4, 256
    prob = api.ProblemSpec("rcv1_like",
                           {"K": K, "d": D, "n_per_worker": 32}).build()
    cl = ClusterModel(num_workers=K, straggler_sigma=5.0)
    delays = (("constant", {}), ("shifted_exponential", {"tail_mean": 1.0}),
              ("pareto", {"shape": 1.8, "scale": 0.5}))
    out = {"n_devices": len(jax.devices())}

    def identical(a, b):
        return all((np.asarray(va.result.w) == np.asarray(vb.result.w)).all()
                   and [r.gap for r in va.result.records]
                   == [r.gap for r in vb.result.records]
                   and [r.sim_time for r in va.result.records]
                   == [r.sim_time for r in vb.result.records]
                   for va, vb in zip(a, b))

    m = baselines.cocoa_plus(K, H=16)
    kw = dict(num_outer=3, seeds=(0, 1, 2), gammas=(1.0, 0.5), eval_every=2)
    none = api.run_sweep(prob, m, cl, batch="map", shard="none", **kw)
    cells = api.run_sweep(prob, m, cl, batch="map", shard="cells", **kw)
    auto = api.run_sweep(prob, m, cl, batch="map", shard="auto", **kw)
    out["lockstep_cells_identical"] = identical(none, cells)
    out["lockstep_auto_identical"] = identical(none, auto)
    out["auto_plan"] = list(api.resolve_shard(
        "auto", protocol="sync", num_workers=K).__dict__.values())

    workers = api.run_sweep(prob, m, cl, batch="map", shard="workers", **kw)
    out["workers_allclose"] = all(
        np.allclose(np.asarray(va.result.w), np.asarray(vb.result.w),
                    rtol=1e-5, atol=1e-6)
        for va, vb in zip(none, workers))

    mlag = baselines.acpd_lag(K, D, B=2, T=4, rho_d=32, gamma=0.5, H=16)
    lkw = dict(num_outer=1, seeds=(0, 1, 2, 3), delays=delays, eval_every=2)
    lnone = api.run_sweep(prob, mlag, cl, batch="map", shard="none", **lkw)
    lcells = api.run_sweep(prob, mlag, cl, batch="map", shard="cells", **lkw)
    out["lag_cells_identical"] = identical(lnone, lcells)
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def shard_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_sharded_cells_bit_identical_on_four_devices(shard_subprocess):
    """The mesh acceptance contract: with 4 host devices, shard='cells'
    (and 'auto', which resolves to it) reproduces the unsharded sweep
    bit-for-bit for lockstep AND lag grids."""
    out = shard_subprocess
    assert out["n_devices"] == 4
    assert out["auto_plan"] == ["cells", 4]
    assert out["lockstep_cells_identical"]
    assert out["lockstep_auto_identical"]
    assert out["lag_cells_identical"]


def test_sharded_workers_allclose_on_four_devices(shard_subprocess):
    """shard='workers' re-associates the per-round aggregate (one psum per
    round): deterministic and numerically equal, not bit-equal."""
    assert shard_subprocess["workers_allclose"]
