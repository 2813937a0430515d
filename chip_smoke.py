#!/usr/bin/env python3
"""ACPD end to end on a TPU at RCV1 width, checked against the host CPU.

Each phase runs one user path of ``repro.api`` on the chip, then the same
spec on the host's CPU backend in the same process, and checks that the two
agree:

  k. kernel     -- one worker's local pass on the SDCA kernel against the
                   jnp path over the same visit order, both on the chip, in
                   both tilings of X (by worker, and by row with a last
                   tile of 5 rows)
  a. group      -- the paper's Algorithms 1+2 on the event engine: the server
                   applies the first B=8 of K=16 replies, with a full barrier
                   every T=10 rounds, under pareto stragglers and the paper's
                   rho*d = 1000 top-k filter
  b. cocoa_plus -- the lockstep scan (one compiled scan per run)
  c. lag        -- the scan with the B-of-K event queue in the graph: float64
                   arrival times order the replies on the device, and the
                   executor replays the clocks on the host and checks the
                   device's pop order against them every round; then
                   the same spec on the event engine, both on the chip
  d. run_sweep  -- one compiled seed x gamma grid of cocoa_plus, sized from its
                   own memory analysis to fit the chip
  e. service    -- an in-process ExperimentService coalescing two tenants'
                   requests into one batch

The problem has the shape of LIBSVM's rcv1.binary: d = 47,236 features and
74 nonzeros per row (density about 0.16%), ridge loss, generated from
``--seed``.  Its train split has 20,242 rows, which K = 16 does not divide;
20,240 = 16 x 1,265 are used.  Each worker makes one local pass per round
(H = 1,265).

Usage:
  python chip_smoke.py                 # one chip: phases k, a-e
  python chip_smoke.py --four-chips    # run_sweep sharded over four chips
                                       # (cells, workers) vs one chip

Exits non-zero, printing no result line, when JAX finds no TPU, when the
repository's ``src/`` is not next to this file, or when any check fails.
On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

K, N_K, D, NNZ_PER_ROW = 16, 1_265, 47_236, 74
H = N_K  # one local pass per round
RHO_D = 1000  # the paper's top-k budget, rho * d
B, T = 8, 10  # group size and full-sync period of the B-of-K protocols
ROUNDS = 3  # lockstep rounds per run
GAMMAS = (1.0, 0.5)

# Chip and CPU run the same float32 programs with every dot at HIGHEST
# precision, so they differ only in the order of float32 reductions (XLA:TPU
# against XLA:CPU).  That perturbs each SDCA step at about 1e-7 relative, and
# the H x rounds sequential steps compound it; 1e-4 leaves two orders of
# magnitude above that and stays far below what one round moves the gap.
RTOL = 1e-4

# XLA compile-path events; their union in time is a phase's compile seconds.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CheckFailed(AssertionError):
    """A chip result disagreed with its reference."""


class CompileClock:
    """Collects the time spans JAX spends tracing, lowering and compiling."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []

    def __call__(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def seconds_since(self, t0: float) -> float:
        """Wall seconds since ``t0`` covered by at least one span."""
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def close(chip: float, ref: float, scale: float, what: str) -> None:
    check(math.isfinite(chip) and abs(chip - ref) <= RTOL * abs(scale),
          f"{what}: chip {chip!r} vs reference {ref!r} (rtol {RTOL} of "
          f"{abs(scale)!r})")


def compare_records(chip, ref, what: str) -> None:
    """Eval records: host accounting exactly, certificates within RTOL."""
    check(len(chip) == len(ref),
          f"{what}: {len(chip)} eval records vs {len(ref)}")
    for a, b in zip(chip, ref):
        where = f"{what} round {a.iteration}"
        for field in ("iteration", "sim_time", "bytes_up", "bytes_down",
                      "compute_time", "comm_time"):
            check(getattr(a, field) == getattr(b, field),
                  f"{where}: {field} {getattr(a, field)!r} vs "
                  f"{getattr(b, field)!r}")
        close(a.primal, b.primal, b.primal, f"{where} primal")
        close(a.dual, b.dual, b.dual, f"{where} dual")
        # The gaps are differences of the two terms and inherit their
        # absolute error, so they are held to RTOL of the primal.
        close(a.gap, b.gap, b.primal, f"{where} gap")
        close(a.gap_server, b.gap_server, b.primal, f"{where} server gap")


def round_stream(events):
    """The per-round host accounting of a session's event stream."""
    from repro.api import RoundEvent

    return [dataclasses.astuple(e) for e in events
            if isinstance(e, RoundEvent)]


def compare_streams(chip_events, ref_events, what: str) -> None:
    """Every round's clock, arrivals, bytes and times exactly: under B-of-K
    arrivals these follow from the order the replies arrived in."""
    a, b = round_stream(chip_events), round_stream(ref_events)
    check(len(a) == len(b), f"{what}: {len(a)} rounds vs {len(b)}")
    for x, y in zip(a, b):
        check(x == y, f"{what}: round accounting {x} vs {y}")


class Smoke:
    """Shared state of one run: devices, problem, results, printed lines."""

    def __init__(self, jax, seed: int):
        self.jax = jax
        self.seed = seed
        self.tpu = jax.devices()[0]
        self.cpu = jax.devices("cpu")[0]
        self.clock = CompileClock()
        jax.monitoring.register_event_time_span_listener(self.clock)
        self.failures: list[str] = []

    def emit(self, **line) -> None:
        print(json.dumps(line), flush=True)

    def peak(self, device=None) -> int | None:
        stats = (device or self.tpu).memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def timed(self, fn):
        """(result, compile seconds, run seconds) of one call on the chip.
        The results are host arrays, so the call has waited for the chip."""
        t0 = time.time()
        out = fn()
        wall = time.time() - t0
        compile_s = self.clock.seconds_since(t0)
        return out, compile_s, wall - compile_s

    def phase(self, name: str, fn) -> None:
        try:
            line = fn()
        except Exception as e:  # noqa: BLE001 -- report, then run the rest
            traceback.print_exc()
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
            self.emit(phase=name, ok=False, error=f"{type(e).__name__}: {e}")
            return
        self.emit(phase=name, ok=True, device=self.tpu.device_kind,
                  peak_bytes_in_use=self.peak(), **line)


def build_problem(smoke, api):
    spec = api.ProblemSpec("linear_synthetic", dict(
        num_workers=K, n_per_worker=N_K, d=D, nnz_per_row=NNZ_PER_ROW,
        seed=smoke.seed, loss="ridge"))
    t0 = time.time()
    problem = spec.build()
    smoke.jax.block_until_ready(problem.X)
    setup_s = time.time() - t0
    check(problem.X.devices() == {smoke.tpu},
          f"problem.X lives on {problem.X.devices()}, not the TPU")
    smoke.emit(phase="setup", rows=K * N_K, d=D, x_bytes=problem.X.nbytes,
               x_device=str(smoke.tpu), setup_s=setup_s)
    return spec, problem


def specs(api, problem_spec, seed):
    """The phases' experiment specs (the problem is built once and shared)."""
    from repro.core import baselines
    from repro.core.acpd import MethodConfig
    from repro.core.simulate import ClusterModel

    cluster = ClusterModel(num_workers=K, delay_model="pareto")
    cocoa = MethodConfig(name="CoCoA+", protocol="cocoa_plus", B=K, H=H)

    def spec(name, method, num_outer, eval_every, executor):
        return api.ExperimentSpec(
            name=name, problem=problem_spec, cluster=cluster,
            methods=(api.MethodEntry(method, num_outer),),
            eval_every=eval_every, seed=seed, executor=executor)

    return {
        "group": spec("smoke-group", baselines.acpd(
            K, D, B=B, T=T, rho_d=RHO_D, H=H), 1, T // 2, "event"),
        "cocoa_plus": spec("smoke-cocoa-plus", cocoa, ROUNDS, 1, "scan"),
        "lag": spec("smoke-lag", baselines.acpd_lag(
            K, D, B=B, T=T, rho_d=RHO_D, H=H), 1, T // 2, "scan"),
    }


def run_session(api, problem, spec):
    entry = spec.methods[0]
    session = api.Session(problem, entry.config, spec.cluster,
                          num_outer=entry.num_outer, seed=spec.seed,
                          eval_every=spec.eval_every,
                          executor=spec.executor)
    events = list(session.events())
    return events, session.result()


def kernel_pass_phase(smoke, problem, reps: int = 5) -> dict:
    """One worker's pass of H steps on the SDCA kernel against the jnp path
    (:func:`repro.core.sdca.solve_subproblem_indices` on ``X[k]``), both on
    the chip, from the same w_eff, duals and visit order: worker 5 of the
    rcv1-width X (tiled by worker), then worker 2 of its first 3 workers'
    first 1,261 rows (tiled by row, so the last 8-row tile holds 5 rows;
    the pass starts on them)."""
    jax, jnp = smoke.jax, smoke.jax.numpy
    from repro.core.sdca import solve_subproblem_indices, visit_order
    from repro.kernels import sdca_inner

    check(sdca_inner.applies(smoke.tpu.platform, problem.loss, N_K, D, H),
          "the kernel does not apply at this width")
    lam, n, sigma_p = problem.lam, K * N_K, float(B)
    k1, k2, k3 = jax.random.split(jax.random.key(smoke.seed), 3)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def one(X, y, k, idx):
        n_k, d = X.shape[1:]
        w_eff = 1e-2 * jax.random.normal(k1, (d,), X.dtype)
        alpha = 1e-2 * jax.random.normal(k2, (n_k,), X.dtype)
        norms = jnp.sum(X[k] * X[k], axis=-1)
        kernel = jax.jit(lambda X: sdca_inner.sdca_pass(
            w_eff, alpha, X, k, y[k], norms, lam, n, sigma_p, idx,
            loss=problem.loss))
        xla = jax.jit(lambda X: solve_subproblem_indices(
            w_eff, alpha, X[k], y[k], norms, lam, n, sigma_p, idx,
            loss=problem.loss))

        def seconds(fn):
            out = jax.block_until_ready(fn(X))
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn(X))
            return out, (time.perf_counter() - t0) / reps

        (da, v), kernel_s = seconds(kernel)
        (da_ref, v_ref), xla_s = seconds(xla)
        out = dict(rel_dalpha=rel(da, da_ref), rel_v=rel(v, v_ref),
                   tiled_by_worker=sdca_inner._tiled_by_worker(*X.shape[:2]),
                   n_k=n_k, kernel_us_per_step=1e6 * kernel_s / idx.size,
                   xla_us_per_step=1e6 * xla_s / idx.size)
        check(out["rel_dalpha"] <= RTOL and out["rel_v"] <= RTOL,
              f"kernel pass vs jnp path, n_k={n_k}: rel. error dalpha "
              f"{out['rel_dalpha']!r}, v {out['rel_v']!r} (rtol {RTOL})")
        return out

    rcv1 = one(problem.X, problem.y, 5, visit_order(k3, N_K, H))
    n_k = N_K - 4
    tail = jnp.arange(n_k // 8 * 8, n_k, dtype=jnp.int32)
    rows = one(problem.X[:3, :n_k], problem.y[:3, :n_k], 2, jnp.concatenate(
        [tail, visit_order(k3, n_k, H - tail.size)]))
    check(rcv1["tiled_by_worker"] and not rows["tiled_by_worker"],
          "the two passes do not cover both tilings")
    return dict(steps=H, rcv1=rcv1, row_tiles=rows)


def one_chip(smoke) -> None:
    from repro import api
    from repro.core import objectives
    from repro.serve import CoalescePolicy, ExperimentService

    jax = smoke.jax
    problem_spec, problem = build_problem(smoke, api)
    cpu_problem = dataclasses.replace(
        problem, X=jax.device_put(problem.X, smoke.cpu),
        y=jax.device_put(problem.y, smoke.cpu))
    zeros = jax.numpy.zeros(problem.y.shape, problem.X.dtype)
    gap0 = objectives.gap_certificate(problem, zeros)["gap"]
    smoke.emit(phase="initial", gap=gap0)
    phase_specs = specs(api, problem_spec, smoke.seed)
    cpu_runs, chip_runs = {}, {}

    def session_phase(name):
        spec = phase_specs[name]
        (events, result), compile_s, run_s = smoke.timed(
            lambda: run_session(api, problem, spec))
        with jax.default_device(smoke.cpu):
            cpu_events, cpu_result = run_session(api, cpu_problem, spec)
        cpu_runs[name] = (cpu_events, cpu_result)
        chip_runs[name] = (events, result)
        compare_streams(events, cpu_events, name)
        compare_records(result.records, cpu_result.records, name)
        gap = result.records[-1].gap
        check(math.isfinite(gap) and gap < gap0,
              f"{name}: final gap {gap!r} not below the initial {gap0!r}")
        return dict(compile_s=compile_s, run_s=run_s,
                    rounds=len(round_stream(events)), gap_chip=gap,
                    gap_cpu=cpu_result.records[-1].gap)

    smoke.phase("k-kernel_pass", lambda: kernel_pass_phase(smoke, problem))
    smoke.phase("a-group", lambda: session_phase("group"))
    smoke.phase("b-cocoa_plus", lambda: session_phase("cocoa_plus"))
    smoke.phase("c-lag", lambda: session_phase("lag"))

    def lag_event_phase():
        """The lag scan against the event engine, both on the chip."""
        spec = dataclasses.replace(phase_specs["lag"], executor="event")
        scan_events, scan_result = chip_runs["lag"]
        (events, result), compile_s, run_s = smoke.timed(
            lambda: run_session(api, problem, spec))
        compare_streams(events, scan_events, "lag event vs scan")
        compare_records(result.records, scan_result.records,
                        "lag event vs scan")
        return dict(compile_s=compile_s, run_s=run_s,
                    gap_event=result.records[-1].gap,
                    gap_scan=scan_result.records[-1].gap)

    smoke.phase("c2-lag_event", lag_event_phase)

    base = phase_specs["cocoa_plus"]
    method, cluster = base.methods[0].config, base.cluster
    sweep_kw = dict(num_outer=ROUNDS, gammas=GAMMAS,
                    eval_every=base.eval_every)
    cpu_sweep = {}

    def sweep_phase():
        stats = smoke.tpu.memory_stats()
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        seeds = [smoke.seed, smoke.seed + 1]
        while True:
            mem = api.lower_sweep(problem, method, cluster, seeds=seeds,
                                  **sweep_kw).compile().memory_analysis()
            need = mem.temp_size_in_bytes + mem.output_size_in_bytes
            if need <= free or len(seeds) == 1:
                break
            seeds = seeds[:len(seeds) // 2]
        check(need <= free, f"the smallest grid needs {need} bytes of "
              f"{free} free on the chip")
        variants, compile_s, run_s = smoke.timed(
            lambda: api.run_sweep(problem, method, cluster, seeds=seeds,
                                  **sweep_kw))
        with jax.default_device(smoke.cpu):
            cpu_variants = api.run_sweep(cpu_problem, method, cluster,
                                         seeds=seeds, **sweep_kw)
        check(len(variants) == len(seeds) * len(GAMMAS),
              f"{len(variants)} sweep cells")
        for v, c in zip(variants, cpu_variants):
            where = f"sweep seed={v.seed} gamma={v.gamma}"
            check((v.seed, v.gamma) == (c.seed, c.gamma), where)
            compare_records(v.result.records, c.result.records, where)
            cpu_sweep[v.seed, v.gamma] = c.result
            gap = v.result.records[-1].gap
            check(math.isfinite(gap) and gap < gap0,
                  f"{where}: final gap {gap!r} not below {gap0!r}")
        return dict(compile_s=compile_s, run_s=run_s, cells=len(variants),
                    grid_bytes=need, free_bytes=free,
                    gap_chip=[v.result.records[-1].gap for v in variants],
                    gap_cpu=[c.result.records[-1].gap
                             for c in cpu_variants])

    smoke.phase("d-run_sweep", sweep_phase)

    # The service builds its own copy of the dataset on the chip; drop this
    # one first so the two never share the chip's memory.
    del problem, zeros

    def service_phase():
        tenants = {"alice": base, "bob": dataclasses.replace(
            base, name="smoke-tenant-b", seed=smoke.seed + 1)}
        svc = ExperimentService(CoalescePolicy(max_batch=len(tenants)))

        def serve():
            handles = {t: svc.submit(t, s) for t, s in tenants.items()}
            svc.drain()
            return {t: (list(h.events()), h.result())
                    for t, h in handles.items()}

        served, compile_s, run_s = smoke.timed(serve)
        check(svc.counters["batches"] == 1
              and svc.counters["batched_requests"] == len(tenants),
              f"the two requests were not coalesced into one batch: "
              f"{svc.counters}")
        # References: alice's spec is phase b's (its CPU session); bob's is
        # the seed+1, gamma=1 cell of phase d's CPU sweep.
        events, result = served["alice"]
        cpu_events, cpu_result = cpu_runs["cocoa_plus"]
        compare_streams(events, cpu_events, "service alice")
        compare_records(result.records, cpu_result.records, "service alice")
        bob = served["bob"][1]
        ref = cpu_sweep.get((smoke.seed + 1, GAMMAS[0]))
        check(ref is not None, "no CPU sweep cell for bob's spec")
        compare_records(bob.records, ref.records, "service bob")
        return dict(compile_s=compile_s, run_s=run_s,
                    note="run_s includes the service building its own copy "
                         "of the dataset",
                    gap_chip=[served[t][1].records[-1].gap for t in tenants],
                    gap_cpu=[cpu_result.records[-1].gap,
                             ref.records[-1].gap],
                    coalesced=svc.counters["batched_requests"])

    smoke.phase("e-service", service_phase)


def four_chips(smoke) -> None:
    """run_sweep sharded over four chips against the same grid on one."""
    import numpy as np

    from repro import api

    jax = smoke.jax
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 TPUs, found "
          f"{len(devices)}")
    problem_spec, problem = build_problem(smoke, api)
    base = specs(api, problem_spec, smoke.seed)["cocoa_plus"]
    method, cluster = base.methods[0].config, base.cluster
    kw = dict(num_outer=ROUNDS, seeds=(smoke.seed, smoke.seed + 1),
              gammas=GAMMAS, eval_every=base.eval_every, batch="map")
    runs = {}

    def form(shard):
        def run():
            variants, compile_s, run_s = smoke.timed(
                lambda: api.run_sweep(problem, method, cluster, shard=shard,
                                      **kw))
            runs[shard] = variants
            line = dict(compile_s=compile_s, run_s=run_s,
                        plan=dataclasses.astuple(api.resolve_shard(
                            shard, protocol=method.protocol, num_workers=K)),
                        peak_bytes_per_device=[smoke.peak(d)
                                               for d in devices[:4]],
                        gap_chip=[v.result.records[-1].gap
                                  for v in variants])
            if shard != "none":
                line.update(compare(shard))
            return line
        return run

    def compare(shard):
        ref = runs["none"]
        got = runs[shard]
        check(len(got) == len(ref), f"{shard}: {len(got)} cells")
        if shard == "cells":
            same = all(
                np.array_equal(a.result.w, b.result.w)
                and np.array_equal(a.result.alpha, b.result.alpha)
                and [dataclasses.astuple(r) for r in a.result.records]
                == [dataclasses.astuple(r) for r in b.result.records]
                for a, b in zip(got, ref))
            check(same, "shard='cells' is not bit-identical to one chip")
            return dict(bit_identical=same)
        for a, b in zip(got, ref):
            compare_records(a.result.records, b.result.records,
                            f"{shard} seed={a.seed} gamma={a.gamma}")
        return dict(rtol=RTOL)

    smoke.phase("sweep-none-1chip", form("none"))
    smoke.phase("sweep-workers-4chips", form("workers"))
    smoke.phase("sweep-cells-4chips", form("cells"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_sweep sharded over four chips "
                         "(cells, workers) and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and the runs")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {__file__}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{device.platform}); nothing was run", file=sys.stderr)
        return 1
    smoke = Smoke(jax, args.seed)
    smoke.emit(phase="start", device=device.device_kind,
               count=len(jax.devices()), jax=jax.__version__,
               compile_cache=cache_dir)
    try:
        (four_chips if args.four_chips else one_chip)(smoke)
    except Exception as e:  # noqa: BLE001 -- set-up failed: no phase ran
        traceback.print_exc()
        smoke.failures.append(f"setup: {type(e).__name__}: {e}")
    if smoke.failures:
        print("chip_smoke: FAILED\n  " + "\n  ".join(smoke.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
