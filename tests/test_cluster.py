"""Replicated serve cluster: leases, heartbeats, takeover, chaos (PR 10).

Pins the cross-process robustness contract on top of the PR-9 serve stack:

* lease acquisition is mutually exclusive under genuinely concurrent
  claimants, and takeover of an expired lease has exactly one winner with
  the epoch bumped (the fencing token);
* an in-process cluster delivers every tenant's stream bit-identical to a
  solo ``Session`` run -- replication changes availability, not results;
* a replica SIGKILLed (in-process: the uncatchable ``ReplicaKilled``)
  mid-checkpoint-segment leaves its lease to expire; a peer steals it and
  resumes from the shared checkpoint directory bit-identically to an
  uninterrupted run;
* delivery is exactly-once under ``net_duplicate`` and converges under
  ``net_drop`` (at-least-once re-send + link-once result records);
* nothing ever hangs under ``net_partition``: the client's bounded wait
  raises the typed ``ClusterUnavailableError``, or a live peer serves;
* replaying one ``(seed, fault model, submission order)`` schedule
  reproduces the identical counters -- chaos is deterministic;
* the result cache (TTL + LRU) and the injectable clock behave exactly;
* one REAL subprocess scenario: ``python -m repro serve --replica-of``
  replicas, a real ``SIGKILL``, and a peer takeover observed end to end.
"""

import importlib.util
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import api
from repro.core import baselines, faults
from repro.core.simulate import ClusterModel
from repro.serve import (
    CellDivergenceError,
    ClusterClient,
    ClusterReplica,
    ClusterUnavailableError,
    CoalescePolicy,
    ExperimentService,
    LeaseManager,
    ManualClock,
    RecoveryPolicy,
    SpecValidationError,
    TTLCache,
    job_key,
    run_cluster,
)

K, D = 4, 256
REPO = pathlib.Path(__file__).resolve().parents[1]


def _problem_spec(seed=0):
    return api.ProblemSpec("linear_synthetic",
                           {"num_workers": K, "n_per_worker": 48, "d": D,
                            "nnz_per_row": 12, "seed": seed, "lam": 1e-3})


def _spec(name="t", seed=0, num_outer=4, eval_every=2, **kw):
    method = baselines.cocoa_plus(K, H=8)
    return api.ExperimentSpec(
        name=name, problem=_problem_spec(),
        cluster=ClusterModel(num_workers=K, straggler_sigma=5.0,
                             delay_model="constant"),
        methods=(api.MethodEntry(method, num_outer),),
        eval_every=eval_every, seed=seed, **kw)


def _policy(**kw):
    kw.setdefault("batch", "map")
    kw.setdefault("shard", "none")
    kw.setdefault("max_wait_s", 0.0)
    kw.setdefault("max_tenant_depth", 8)
    return CoalescePolicy(**kw)


def _service_kwargs():
    return dict(policy=_policy(),
                recovery=RecoveryPolicy(backoff_base_s=0.001))


def _replicas(cluster_dir, clock, ids, fault_by_id=None, **kw):
    fault_by_id = fault_by_id or {}
    return [ClusterReplica(cluster_dir, rid, clock=clock,
                           fault=fault_by_id.get(rid),
                           service_kwargs=_service_kwargs(), **kw)
            for rid in ids]


def _solo_events(spec):
    entry = spec.methods[0]
    sess = api.Session(spec.problem.build(), entry.config, spec.cluster,
                       num_outer=entry.num_outer, seed=spec.seed,
                       eval_every=spec.eval_every)
    events = list(sess.events())
    return events, sess.result()


def _reference_run(spec, checkpoint_dir):
    """An UNINTERRUPTED run of ``spec`` through a solo service -- the
    bit-identity oracle for checkpointed cluster jobs."""
    svc = ExperimentService(_policy(), checkpoint_dir=checkpoint_dir)
    h = svc.submit("ref", spec)
    svc.drain()
    return list(h.events(timeout=60)), h.result(timeout=60)


# ---------------------------------------------------------------------------
# Lease substrate: mutual exclusion, expiry, takeover, fencing.
# ---------------------------------------------------------------------------


class TestLeases:
    def test_concurrent_claim_has_exactly_one_winner(self, tmp_path):
        n = 8
        managers = [LeaseManager(tmp_path, f"r{i}") for i in range(n)]
        barrier = threading.Barrier(n)
        wins = [None] * n

        def claim(i):
            barrier.wait()
            wins[i] = managers[i].try_acquire("job-x", epoch=0)

        threads = [threading.Thread(target=claim, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        winners = [i for i, w in enumerate(wins) if w is not None]
        assert len(winners) == 1
        lease = managers[0].read_lease("job-x")
        assert lease["owner"] == f"r{winners[0]}"
        assert lease["epoch"] == 0

    def test_concurrent_takeover_has_exactly_one_winner(self, tmp_path):
        clock = ManualClock()
        owner = LeaseManager(tmp_path, "dead", clock=clock, lease_ttl_s=5.0)
        owner.heartbeat()
        assert owner.try_acquire("job-x") is not None
        clock.advance(6.0)  # heartbeat goes stale -> owner presumed dead

        n = 6
        managers = [LeaseManager(tmp_path, f"r{i}", clock=clock,
                                 lease_ttl_s=5.0) for i in range(n)]
        for m in managers:
            m.heartbeat()  # claimants are alive -- only "dead" stays stale
        barrier = threading.Barrier(n)
        wins = [None] * n

        def steal(i):
            barrier.wait()
            wins[i] = managers[i].try_takeover("job-x")

        threads = [threading.Thread(target=steal, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        winners = [i for i, w in enumerate(wins) if w is not None]
        assert len(winners) == 1
        lease = owner.read_lease("job-x")
        assert lease["owner"] == f"r{winners[0]}"
        assert lease["epoch"] == 1  # the fencing token moved

    def test_epoch_fences_a_superseded_owner(self, tmp_path):
        clock = ManualClock()
        r0 = LeaseManager(tmp_path, "r0", clock=clock, lease_ttl_s=5.0)
        r1 = LeaseManager(tmp_path, "r1", clock=clock, lease_ttl_s=5.0)
        r0.heartbeat()
        r1.heartbeat()
        assert r0.try_acquire("j") is not None
        assert r0.still_owner("j", 0)
        clock.advance(6.0)
        r1.heartbeat()  # r1 stays alive; r0's beat is now stale
        stolen = r1.try_takeover("j")
        assert stolen is not None and stolen["epoch"] == 1
        # the resurrected r0 must discard, not deliver
        assert not r0.still_owner("j", 0)
        assert not r0.release("j", 0)
        assert r1.still_owner("j", 1)
        assert r1.release("j", 1)

    def test_self_owned_lease_never_expires(self, tmp_path):
        clock = ManualClock()
        r0 = LeaseManager(tmp_path, "r0", clock=clock, lease_ttl_s=5.0)
        lease = r0.try_acquire("j")
        clock.advance(100.0)  # r0 never even heartbeat
        assert not r0.expired(lease)
        other = LeaseManager(tmp_path, "r1", clock=clock, lease_ttl_s=5.0)
        assert other.expired(lease)

    def test_takeover_refuses_a_live_owner(self, tmp_path):
        clock = ManualClock()
        r0 = LeaseManager(tmp_path, "r0", clock=clock, lease_ttl_s=5.0)
        r1 = LeaseManager(tmp_path, "r1", clock=clock, lease_ttl_s=5.0)
        r0.heartbeat()
        r0.try_acquire("j")
        assert r1.try_takeover("j") is None
        assert r0.still_owner("j", 0)

    def test_membership_ages_and_retire_withdraws(self, tmp_path):
        clock = ManualClock()
        r0 = LeaseManager(tmp_path, "r0", clock=clock, lease_ttl_s=5.0)
        r1 = LeaseManager(tmp_path, "r1", clock=clock, lease_ttl_s=5.0)
        r0.heartbeat()
        clock.advance(3.0)
        r1.heartbeat()
        m = r0.membership()
        assert m["r0"]["age_s"] == 3.0 and m["r0"]["alive"]
        assert m["r1"]["age_s"] == 0.0 and m["r1"]["alive"]
        clock.advance(3.0)
        m = r0.membership()
        assert not m["r0"]["alive"] and m["r1"]["alive"]
        r1.retire()
        assert "r1" not in r0.membership()


# ---------------------------------------------------------------------------
# Fault-free cluster: delivery is bit-identical to solo sessions.
# ---------------------------------------------------------------------------


class TestClusterDelivery:
    def test_cluster_run_is_bit_identical_to_solo(self, tmp_path):
        clock = ManualClock()
        replicas = _replicas(tmp_path, clock, ["r0", "r1", "r2"])
        client = ClusterClient(tmp_path, clock=clock)
        specs = {"alice": _spec(seed=0), "bob": _spec(seed=1)}
        keys = {t: client.submit(t, s) for t, s in specs.items()}
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0 and not summary["dead"]
        for tenant, spec in specs.items():
            events, result = client.try_result(keys[tenant])
            solo_events, solo_result = _solo_events(spec)
            assert events == solo_events
            np.testing.assert_array_equal(result.w, solo_result.w)
            np.testing.assert_array_equal(result.alpha, solo_result.alpha)

    def test_job_key_is_idempotent_and_tenant_scoped(self):
        a, b = _spec(seed=0), _spec(seed=0)
        assert job_key("t", a, None) == job_key("t", b, None)
        assert job_key("t", a, None) != job_key("u", a, None)
        assert job_key("t", a, None) != job_key("t", _spec(seed=1), None)

    def test_resubmitting_identical_work_reuses_the_job(self, tmp_path):
        clock = ManualClock()
        replicas = _replicas(tmp_path, clock, ["r0"])
        client = ClusterClient(tmp_path, clock=clock)
        k1 = client.submit("t", _spec(seed=0))
        k2 = client.submit("t", _spec(seed=0))
        assert k1 == k2
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        assert replicas[0].counters["completed"] == 1  # ran ONCE
        assert len(list((tmp_path / "results").glob("*.json"))) == 1

    def test_invalid_spec_is_rejected_client_side(self, tmp_path):
        client = ClusterClient(tmp_path, clock=ManualClock())
        with pytest.raises(SpecValidationError):
            client.submit("t", _spec(checkpoint_every=0))

    def test_replica_error_arrives_as_the_original_typed_error(
            self, tmp_path):
        clock = ManualClock()
        replicas = _replicas(
            tmp_path, clock, ["r0"],
            fault_by_id={"r0": faults.get_fault("nan_poison")(seed=3,
                                                              count=1)})
        client = ClusterClient(tmp_path, clock=clock)
        key = client.submit("t", _spec(seed=0))
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        assert replicas[0].counters["errored"] == 1
        with pytest.raises(CellDivergenceError):
            client.try_result(key)
        assert client.counters["errored"] == 1

    def test_health_reports_cluster_membership_and_leases(self, tmp_path):
        clock = ManualClock()
        replicas = _replicas(tmp_path, clock, ["r0", "r1"])
        client = ClusterClient(tmp_path, clock=clock)
        client.submit("t", _spec(seed=0))
        run_cluster(replicas, client)
        health = replicas[0].service.health()
        assert "breaker_states" in health
        cluster = health["cluster"]
        assert cluster["replica_id"] == "r0"
        assert set(cluster["membership"]) == {"r0", "r1"}
        assert cluster["leases"] == {}  # released after delivery
        assert cluster["transport"]["sent"] > 0


# ---------------------------------------------------------------------------
# Kill + takeover: a peer resumes the checkpointed run bit-identically.
# ---------------------------------------------------------------------------


class TestKillAndTakeover:
    def test_killed_mid_segment_peer_resumes_bit_identically(self, tmp_path):
        cluster_dir = tmp_path / "cluster"
        spec = _spec(seed=0, num_outer=6, checkpoint_every=2)
        ref_events, ref_result = _reference_run(spec, tmp_path / "ref")

        clock = ManualClock()
        kill = faults.get_fault("replica_kill")(replica="r0", at_segment=2)
        replicas = _replicas(cluster_dir, clock, ["r0", "r1"],
                             fault_by_id={"r0": kill}, lease_ttl_s=5.0)
        client = ClusterClient(cluster_dir, clock=clock)
        key = client.submit("t", spec)
        summary = run_cluster(replicas, client, clock=clock, advance_s=1.0)

        assert "r0" in summary["dead"]
        assert "checkpoint segment starting round 2" in summary["dead"]["r0"]
        assert summary["hung_jobs"] == 0
        assert replicas[0].counters["claims"] == 1
        assert replicas[1].counters["takeovers"] == 1

        events, result = client.try_result(key)
        assert events == ref_events
        np.testing.assert_array_equal(result.w, ref_result.w)
        np.testing.assert_array_equal(result.alpha, ref_result.alpha)
        record = json.loads(
            (cluster_dir / "results" / f"{key}.json").read_text())
        assert record["owner"] == "r1" and record["epoch"] == 1

    def test_replica_killed_at_tick_leaves_peers_serving(self, tmp_path):
        clock = ManualClock()
        kill = faults.get_fault("replica_kill")(replica="r0", after_steps=1)
        replicas = _replicas(tmp_path, clock, ["r0", "r1"],
                             fault_by_id={"r0": kill})
        client = ClusterClient(tmp_path, clock=clock)
        keys = [client.submit("t", _spec(seed=i)) for i in range(2)]
        summary = run_cluster(replicas, client, clock=clock, advance_s=1.0)
        assert summary["dead"] == {
            "r0": "replica r0 killed at scheduler tick 1"}
        assert summary["hung_jobs"] == 0
        assert replicas[1].counters["completed"] == 2
        for key in keys:
            assert client.try_result(key) is not None


# ---------------------------------------------------------------------------
# Network faults: exactly-once, drop convergence, partition no-hang.
# ---------------------------------------------------------------------------


class TestNetworkFaults:
    def test_exactly_once_under_duplication(self, tmp_path):
        clock = ManualClock()
        dup = faults.get_fault("net_duplicate")
        replicas = _replicas(
            tmp_path, clock, ["r0"],
            fault_by_id={"r0": dup(seed=6, rate=1.0, kinds="result")})
        client = ClusterClient(
            tmp_path, clock=clock,
            fault=dup(seed=5, rate=1.0, kinds="job"))
        spec = _spec(seed=0)
        key = client.submit("t", spec)
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        assert client.transport.counters["duplicated"] >= 1
        assert replicas[0].transport.counters["duplicated"] >= 1
        assert replicas[0].transport.counters["deduped_results"] >= 1
        assert replicas[0].counters["completed"] == 1
        assert len(list((tmp_path / "results").glob("*.json"))) == 1
        events, result = client.try_result(key)
        solo_events, solo_result = _solo_events(spec)
        assert events == solo_events
        np.testing.assert_array_equal(result.w, solo_result.w)

    def test_at_least_once_converges_under_drops(self, tmp_path):
        clock = ManualClock()
        drop = faults.get_fault("net_drop")
        replicas = _replicas(
            tmp_path, clock, ["r0"],
            fault_by_id={"r0": drop(seed=4, rate=0.6, kinds="result")})
        client = ClusterClient(
            tmp_path, clock=clock,
            fault=drop(seed=3, rate=0.6, kinds="job"))
        key = client.submit("t", _spec(seed=0))
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        # drops genuinely happened; fresh fate draws on re-send converged
        assert (client.transport.counters["dropped"] >= 1
                or replicas[0].transport.counters["dropped"] >= 1)
        assert client.try_result(key) is not None

    def test_partitioned_cluster_never_hangs_the_client(self, tmp_path):
        clock = ManualClock()
        part = faults.get_fault("net_partition")(replica="r0", start_tick=0)
        replicas = _replicas(tmp_path, clock, ["r0"],
                             fault_by_id={"r0": part})
        client = ClusterClient(tmp_path, clock=clock)
        key = client.submit("t", _spec(seed=0))
        summary = run_cluster(replicas, client, max_ticks=10)
        assert summary["hung_jobs"] == 1  # nobody served it...
        assert replicas[0].counters["partitioned_ticks"] == 10
        # ...but the client's wait is BOUNDED: typed error, no hang.  The
        # shared ManualClock makes the deadline pass without real sleeping.
        with pytest.raises(ClusterUnavailableError):
            client.result(key, timeout_s=5.0, poll_s=1.0)
        with pytest.raises(ClusterUnavailableError):
            client.events(key, timeout_s=5.0, poll_s=1.0)
        assert client.counters["unavailable"] == 2

    def test_partition_heals_and_the_job_completes(self, tmp_path):
        clock = ManualClock()
        part = faults.get_fault("net_partition")(replica="r0", start_tick=1,
                                                 duration=3)
        replicas = _replicas(tmp_path, clock, ["r0"],
                             fault_by_id={"r0": part})
        client = ClusterClient(tmp_path, clock=clock)
        key = client.submit("t", _spec(seed=0))
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        assert replicas[0].counters["partitioned_ticks"] == 3
        assert client.try_result(key) is not None

    def test_live_peer_serves_around_a_partitioned_replica(self, tmp_path):
        clock = ManualClock()
        part = faults.get_fault("net_partition")(replica="r0", start_tick=0)
        replicas = _replicas(tmp_path, clock, ["r0", "r1"],
                             fault_by_id={"r0": part})
        client = ClusterClient(tmp_path, clock=clock)
        key = client.submit("t", _spec(seed=0))
        summary = run_cluster(replicas, client)
        assert summary["hung_jobs"] == 0
        assert replicas[1].counters["completed"] == 1
        assert replicas[0].counters["completed"] == 0
        assert client.try_result(key) is not None


# ---------------------------------------------------------------------------
# Determinism: one (seed, fault model, submission order) -> one schedule.
# ---------------------------------------------------------------------------


class TestChaosDeterminism:
    @staticmethod
    def _chaos_run(cluster_dir):
        clock = ManualClock()
        chaos = faults.get_fault("cluster_chaos")(
            seed=11, kill_replica="r0", at_segment=2, drop_rate=0.15)
        replicas = _replicas(cluster_dir, clock, ["r0", "r1", "r2"],
                             fault_by_id={"r0": chaos}, lease_ttl_s=2.5)
        client = ClusterClient(cluster_dir, clock=clock)
        keys = [client.submit("t", _spec(seed=i, num_outer=6,
                                         checkpoint_every=2))
                for i in range(3)]
        summary = run_cluster(replicas, client, clock=clock, advance_s=1.0,
                              max_ticks=100)
        return summary, [client.try_result(k) is not None for k in keys]

    def test_replaying_the_schedule_reproduces_identical_counters(
            self, tmp_path):
        first, done_a = self._chaos_run(tmp_path / "a")
        second, done_b = self._chaos_run(tmp_path / "b")
        assert first["hung_jobs"] == 0 and all(done_a)
        assert "r0" in first["dead"]
        assert sum(r["takeovers"] for r in first["replicas"].values()) == 1
        # the acceptance bar: the ENTIRE summary -- ticks, deaths, client
        # counters, per-replica transport + recovery counters -- replays
        assert first == second
        assert done_a == done_b


# ---------------------------------------------------------------------------
# Result cache: TTL + LRU, and the service-level hit path.
# ---------------------------------------------------------------------------


class TestResultCache:
    def test_lru_eviction_order(self):
        cache = TTLCache(max_entries=2)
        cache.put("a", 1)
        cache.put("b", 2)
        assert cache.get("a") == (True, 1)  # refreshes a
        cache.put("c", 3)                   # evicts b, the LRU entry
        assert cache.get("b") == (False, None)
        assert cache.get("a") == (True, 1)
        assert cache.get("c") == (True, 3)
        assert cache.stats()["evicted_lru"] == 1

    def test_ttl_expiry_on_the_injected_clock(self):
        clock = ManualClock()
        cache = TTLCache(max_entries=8, ttl_s=10.0, clock=clock)
        cache.put("a", 1)
        clock.advance(9.0)
        assert cache.get("a") == (True, 1)
        clock.advance(2.0)
        assert cache.get("a") == (False, None)
        stats = cache.stats()
        assert stats["evicted_ttl"] == 1
        assert stats["hits"] == 1 and stats["misses"] == 1

    def test_zero_entries_disables_the_cache(self):
        cache = TTLCache(max_entries=0)
        cache.put("a", 1)
        assert len(cache) == 0
        assert cache.get("a") == (False, None)

    def test_service_result_cache_short_circuits_identical_work(self):
        svc = ExperimentService(_policy(), result_cache_entries=8)
        spec = _spec(seed=0)
        h1 = svc.submit("alice", spec)
        svc.drain()
        events1 = list(h1.events(timeout=30))
        solo = svc.counters["solo_requests"]
        batches = svc.counters["batches"]
        # same WORK, different tenant: served from the result cache without
        # touching the dispatch path at all
        h2 = svc.submit("bob", _spec(seed=0))
        events2 = list(h2.events(timeout=30))
        assert events2 == events1
        np.testing.assert_array_equal(h2.result(timeout=30).w,
                                      h1.result(timeout=30).w)
        assert svc.counters["result_cache_hits"] == 1
        assert svc.counters["solo_requests"] == solo
        assert svc.counters["batches"] == batches
        assert svc.stats()["result_cache"]["hits"] == 1

    def test_service_backoff_runs_on_the_injected_clock(self):
        # Three attempts with a 10s backoff base would real-sleep ~30s; on
        # the ManualClock the test is instant and the retries still happen.
        clock = ManualClock()
        svc = ExperimentService(
            _policy(),
            recovery=RecoveryPolicy(backoff_base_s=10.0, max_attempts=3),
            fault=faults.get_fault("transient_executor")(seed=0, failures=2),
            clock=clock)
        h = svc.submit("a", _spec(seed=0))
        svc.drain()
        assert h.result(timeout=30) is not None
        assert svc.counters["retries"] == 2
        assert clock.monotonic() > 0.0  # the backoff "slept" on this clock


# ---------------------------------------------------------------------------
# The real thing: subprocess replicas, a real SIGKILL, a real takeover.
# ---------------------------------------------------------------------------


class TestSubprocessCluster:
    @pytest.mark.skipif(importlib.util.find_spec("libtpu") is None,
                        reason="no TPU plugin installed")
    def test_replica_without_its_tpu_fails_at_start(self, tmp_path):
        """With the TPU plugin installed but no usable chip (none here, or
        one held by another process), a replica exits at start with JAX's
        reason instead of quietly serving from the CPU."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        env["TPU_LOG_DIR"] = "disabled"
        out = subprocess.run(
            [sys.executable, "-m", "repro", "serve", "--replica-of",
             str(tmp_path), "--replica-id", "r0"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode != 0
        assert "replica cannot use its TPU" in out.stderr

    def _spawn(self, cluster_dir, replica_id, log, fault=None, params=None):
        env = dict(os.environ)
        env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "repro", "serve",
               "--replica-of", str(cluster_dir), "--replica-id", replica_id,
               "--lease-ttl", "2.0", "--step-interval", "0.05"]
        if fault is not None:
            cmd += ["--fault-model", fault,
                    "--fault-params", json.dumps(params or {})]
        return subprocess.Popen(cmd, cwd=REPO, env=env,
                                stdout=log, stderr=subprocess.STDOUT)

    def test_sigkilled_replica_is_taken_over_by_a_subprocess_peer(
            self, tmp_path):
        cluster_dir = tmp_path / "cluster"
        cluster_dir.mkdir()
        spec = _spec(seed=0, num_outer=6, checkpoint_every=2)
        ref_events, ref_result = _reference_run(spec, tmp_path / "ref")

        client = ClusterClient(cluster_dir)  # system clock: real processes
        key = client.submit("t", spec)

        r1 = None
        with open(tmp_path / "r0.log", "w") as log0, \
                open(tmp_path / "r1.log", "w") as log1:
            r0 = self._spawn(cluster_dir, "r0", log0, fault="replica_kill",
                             params={"replica": "r0", "at_segment": 2})
            try:
                # r0 claims the job, checkpoints segment [0, 2), and takes a
                # REAL self-SIGKILL at the start of segment 2.
                r0.wait(timeout=300)
                assert r0.returncode == -signal.SIGKILL
                lease = LeaseManager(cluster_dir, "observer").read_lease(key)
                assert lease is not None and lease["owner"] == "r0"

                # The peer finds the stale heartbeat, steals the lease, and
                # resumes from r0's durable checkpoint.
                r1 = self._spawn(cluster_dir, "r1", log1)
                events = client.events(key, timeout_s=300, poll_s=0.2)
                result = client.result(key, timeout_s=10)
            finally:
                for proc in (r0, r1):
                    if proc is not None and proc.poll() is None:
                        proc.kill()
                        proc.wait(timeout=30)

        assert events == ref_events
        np.testing.assert_array_equal(result.w, ref_result.w)
        np.testing.assert_array_equal(result.alpha, ref_result.alpha)
        record = json.loads(
            (cluster_dir / "results" / f"{key}.json").read_text())
        assert record["owner"] == "r1" and record["epoch"] == 1
