"""Jobs of ``gap`` traffic: runs to a duality-gap target, back to back.

Each job is one ``repro.api.Session`` of the traffic's protocol, from its
construction to its ``StopEvent`` once the streamed gap reaches
``target_fraction`` of the initial gap; job ``i`` has the seed
``unit_seed(--seed, i)``.  The window starts jobs until ``--seconds`` have
passed and lets the last one finish.  ``time_to_gap_s`` is the total time
of the jobs over their count.

Traced, the window is one job, with the profiler on from its certificate
after ``trace_after`` rounds to its certificate ``trace_rounds`` rounds
later: one period of the protocol in its steady state.

The check takes every job's answer: the server-visible duals
(``alpha_applied``), the server's model ``w`` and the last certificate it
reported, and holds them to the reference:

* ``unstopped``: jobs that ended without reaching the target (limit 0);
* ``gap_over_target``: the reference's gap of the returned duals over the
  target, the worst job;
* ``gap_rel_err``: the reported gap against the reference's, relative;
* ``gap_server_rel_err``: the reported ``P(w) - D(alpha)`` against the
  reference's, relative.
"""

from __future__ import annotations

import dataclasses
import sys
import time
import traceback

import numpy as np

from bench import data as bench_data
from bench import reference
from bench.common import WARM_UP, limit, relative, unit_seed, worst


@dataclasses.dataclass
class Answer:
    seed: int
    seconds: float
    rounds: int
    evals: int
    reason: str
    gap: float
    gap_server: float
    alpha: np.ndarray
    w: np.ndarray


@dataclasses.dataclass
class Window:
    seconds: float
    answers: list
    attempted: int
    failed: int
    traced_rounds: int = 0
    traced_evals: int = 0


class Job:
    def __init__(self, ctx):
        from repro.core.acpd import MethodConfig
        from repro.core.objectives import Problem
        from repro.core.simulate import ClusterModel

        self.ctx = ctx
        cfg, t = ctx.config, ctx.traffic
        K, n_k, d = bench_data.shape_of(cfg)
        self.shape = (K, n_k, d)
        self.sparse = bench_data.generate(cfg, ctx.seed)
        X, y = bench_data.device_arrays(self.sparse, K, n_k, ctx.devices[0])
        self.lam = cfg["problem"]["lam"]
        self.problem = Problem(X=X, y=y, lam=self.lam,
                               loss=cfg["problem"]["loss"])
        self.method = MethodConfig(
            name=t["protocol"], protocol=t["protocol"], B=t["B"], T=t["T"],
            rho=min(1.0, t["rho_d"] / d), gamma=t["gamma"],
            H=n_k * t["local_passes"])
        self.cluster = ClusterModel(num_workers=K,
                                    delay_model=cfg["cluster"]["delay_model"])
        self.target = t["target_fraction"] * reference.initial_gap(
            self.sparse)

    def _session(self, seed: int, num_outer: int, target: float):
        return self.ctx.api.Session(
            self.problem, self.method, self.cluster, num_outer=num_outer,
            seed=seed, eval_every=self.ctx.traffic["eval_every"],
            target_gap=target)

    def warm(self) -> None:
        """One period of rounds with streamed certificates: every worker
        group size, both server-apply sizes and the certificate."""
        session = self._session(unit_seed(self.ctx.seed, WARM_UP), 1, -1.0)
        session.run()

    def _one(self, i: int, tracer):
        ctx, t = self.ctx, self.ctx.traffic
        seed = unit_seed(ctx.seed, i)
        window_span = ctx.annotate("bench.window")
        traced = None
        t0 = time.perf_counter()
        with ctx.annotate("bench.session_init"):
            session = self._session(seed, t["max_outer"], self.target)
        events = session.events()
        rounds = evals = 0
        last_eval = stop = None
        while True:
            with ctx.annotate("bench.events"):
                ev = next(events, None)
            if ev is None:
                break
            kind = type(ev).__name__
            if kind == "RoundEvent":
                rounds += 1
            elif kind == "EvalEvent":
                evals += 1
                last_eval = ev
            elif kind == "StopEvent":
                stop = ev
            if tracer is None or kind != "EvalEvent":
                continue
            if rounds == t["trace_after"]:
                tracer.start()
                window_span.__enter__()
                opened = (rounds, evals)
            elif traced is None and rounds == (t["trace_after"]
                                               + t["trace_rounds"]):
                window_span.__exit__(None, None, None)
                tracer.stop()
                traced = (rounds - opened[0], evals - opened[1])
        if tracer is not None and traced is None:
            raise RuntimeError(f"job {i} stopped after {rounds} rounds, "
                               f"before its traced window closed")
        with ctx.annotate("bench.result"):
            result = session.result()
        seconds = time.perf_counter() - t0
        print(f"job {i}: seed {seed} rounds {rounds} evals {evals} "
              f"seconds {seconds!r} stop {stop.reason}", file=sys.stderr)
        answer = Answer(seed, seconds, rounds, evals, stop.reason,
                        last_eval.gap, last_eval.gap_server,
                        np.asarray(result.alpha_applied), np.asarray(result.w))
        return answer, traced

    def run(self, seconds: float, tracer=None) -> Window:
        window = Window(0.0, [], 0, 0)
        t0 = time.perf_counter()
        i = 0
        while True:
            window.attempted += 1
            try:
                answer, counts = self._one(i, tracer)
                window.answers.append(answer)
                if tracer:
                    window.traced_rounds, window.traced_evals = counts
            except Exception:  # noqa: BLE001 -- a failed job is counted
                traceback.print_exc(file=sys.stderr)
                window.failed += 1
            i += 1
            if tracer or time.perf_counter() - t0 >= seconds:
                break
        window.seconds = time.perf_counter() - t0
        return window

    def metrics(self, window: Window) -> dict:
        done = window.answers
        return {"time_to_gap_s": sum(a.seconds for a in done) / len(done)}

    def release(self) -> None:
        self.problem = None

    def check(self, window: Window, control: str | None = None) -> list:
        limits = self.ctx.limits
        sparse, lam = self.sparse, self.lam
        over, rel, rel_srv = [], [], []
        for a in window.answers:
            ref = reference.certificate(sparse, lam, a.alpha)
            ref_srv = reference.primal(sparse, lam, a.w) - ref.dual
            gap, gap_srv = a.gap, a.gap_server
            if control:
                low = reference.certificate(sparse, lam, a.alpha, True)
                gap = low.gap
                gap_srv = reference.primal(sparse, lam, a.w, True) - low.dual
            over.append(ref.gap / self.target)
            rel.append(relative(gap - ref.gap, ref.gap))
            rel_srv.append(relative(gap_srv - ref_srv, ref_srv))
        unstopped = window.failed + sum(a.reason != "target_gap"
                                        for a in window.answers)
        return [
            limit("unstopped", unstopped, limits["unstopped"]),
            limit("gap_over_target", worst(over), limits["gap_over_target"]),
            limit("gap_rel_err", worst(rel), limits["gap_rel_err"]),
            limit("gap_server_rel_err", worst(rel_srv),
                  limits["gap_server_rel_err"]),
        ]

