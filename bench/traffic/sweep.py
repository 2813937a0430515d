"""Jobs of ``sweep`` traffic: seed x gamma grids through ``run_sweep``.

Each job is one ``repro.api.run_sweep`` call: ``seeds_per_grid`` seeds
(the next ones of ``unit_seed(--seed, i)``) times the traffic's
``gammas``, each cell ``rounds`` lockstep rounds with a certificate every
``eval_every``.  The window starts grids until ``--seconds`` have passed
and lets the last one finish.  ``sweep_cells_per_s`` is the cells of all
grids over the window's seconds.  Traced, the window is one grid.

The check takes every cell of every grid: its final duals ``alpha``, model
``w`` and last reported certificate, and holds them to the reference:

* ``w_rel_err``: ``||w - w(alpha)|| / ||w(alpha)||``, the primal-dual map
  that the lockstep rounds keep;
* ``gap_rel_err``: the reported gap against the reference's, relative;
* ``gap_server_rel_err``: the reported ``P(w) - D(alpha)`` against the
  reference's, relative;
* ``gap_progress``: the reference's gap of the returned duals over the
  initial gap: a run that moves nothing reads 1.
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
    gamma: float
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
    grids: int = 0
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
        self.sparse = bench_data.generate(cfg, ctx.seed)
        X, y = bench_data.device_arrays(self.sparse, K, n_k, ctx.devices[0])
        self.lam = cfg["problem"]["lam"]
        self.problem = Problem(X=X, y=y, lam=self.lam,
                               loss=cfg["problem"]["loss"])
        self.method = MethodConfig(name=t["protocol"], protocol=t["protocol"],
                                   B=K, H=n_k * t["local_passes"])
        self.cluster = ClusterModel(num_workers=K,
                                    delay_model=cfg["cluster"]["delay_model"])
        self.gap0 = reference.initial_gap(self.sparse)

    def _grid(self, seeds):
        t = self.ctx.traffic
        return self.ctx.api.run_sweep(
            self.problem, self.method, self.cluster, num_outer=t["rounds"],
            seeds=seeds, gammas=t["gammas"], eval_every=t["eval_every"],
            shard="none")

    def _seeds(self, grid: int) -> list:
        per = self.ctx.traffic["seeds_per_grid"]
        return [unit_seed(self.ctx.seed, grid * per + j) for j in range(per)]

    def warm(self) -> None:
        per = self.ctx.traffic["seeds_per_grid"]
        self._grid([unit_seed(self.ctx.seed, WARM_UP + j)
                    for j in range(per)])

    def run(self, seconds: float, tracer=None) -> Window:
        ctx, t = self.ctx, self.ctx.traffic
        cells = t["seeds_per_grid"] * len(t["gammas"])
        window = Window(0.0, [], 0, 0)
        t0 = time.perf_counter()
        while True:
            window.attempted += cells
            if tracer:
                tracer.start()
            try:
                with ctx.annotate("bench.window"):
                    with ctx.annotate("bench.grid"):
                        variants = self._grid(self._seeds(window.grids))
                    with ctx.annotate("bench.result"):
                        for v in variants:
                            last = v.result.records[-1]
                            window.answers.append(Answer(
                                v.seed, v.gamma, last.gap, last.gap_server,
                                np.asarray(v.result.alpha),
                                np.asarray(v.result.w)))
            except Exception:  # noqa: BLE001 -- a failed grid is counted
                traceback.print_exc(file=sys.stderr)
                window.failed += cells
            window.grids += 1
            if tracer:
                tracer.stop()
                break
            if time.perf_counter() - t0 >= seconds:
                break
        window.seconds = time.perf_counter() - t0
        if tracer:
            window.traced_rounds = t["rounds"]
            window.traced_evals = cells * (t["rounds"] // t["eval_every"])
        return window

    def metrics(self, window: Window) -> dict:
        return {"sweep_cells_per_s": len(window.answers) / window.seconds}

    def release(self) -> None:
        self.problem = None

    def check(self, window: Window, control: str | None = None) -> list:
        limits = self.ctx.limits
        sparse, lam = self.sparse, self.lam
        w_err, progress, rel, rel_srv = [], [], [], []
        for a in window.answers:
            ref = reference.certificate(sparse, lam, a.alpha)
            w, gap, gap_srv = a.w, a.gap, a.gap_server
            if control:
                low = reference.certificate(sparse, lam, a.alpha, True)
                w, gap = low.w_alpha, low.gap
                gap_srv = reference.primal(sparse, lam, a.w, True) - low.dual
            w_err.append(relative(np.linalg.norm(w - ref.w_alpha),
                                  np.linalg.norm(ref.w_alpha)))
            progress.append(ref.gap / self.gap0)
            rel.append(relative(gap - ref.gap, ref.gap))
            ref_srv = reference.primal(sparse, lam, a.w) - ref.dual
            rel_srv.append(relative(gap_srv - ref_srv, ref_srv))
        if window.failed:
            w_err.append(float("inf"))
        return [
            limit("w_rel_err", worst(w_err), limits["w_rel_err"]),
            limit("gap_rel_err", worst(rel), limits["gap_rel_err"]),
            limit("gap_server_rel_err", worst(rel_srv),
                  limits["gap_server_rel_err"]),
            limit("gap_progress", worst(progress), limits["gap_progress"]),
        ]
