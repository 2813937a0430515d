"""Benchmark driver: one module per paper table/figure + the roofline reader
and the engine microbenchmark.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

``--quick`` runs every benchmark at smoke scale (tiny K, num_outer, H) --
seconds instead of minutes; used by ``make check`` / scripts/check.sh as the
CI-style sanity gate that the whole bench surface still executes.

This is the ONE driver: ``python -m repro bench [--quick] [--only ...]``
forwards here, so the CLI and ``python -m benchmarks.run`` stay in lockstep.
"""

from __future__ import annotations

import argparse
import sys
import time


def _analysis_findings() -> dict:
    """Static-analysis debt alongside the perf numbers: total lint findings
    over src/ plus how many are new vs the checked-in baseline, so the
    trajectory shows contract debt shrinking, not just wall-clock."""
    try:
        from repro.analysis import Baseline, lint_paths
        from repro.analysis.cli import BASELINE_NAME, _repo_root

        root = _repo_root()
        findings = lint_paths([root / "src"], root=root)
        new, accepted, stale = Baseline.load(
            root / BASELINE_NAME).split(findings)
        return {"total": len(findings), "new": len(new),
                "baseline": len(accepted), "stale": len(stale)}
    except Exception as e:  # never fail a bench run over the analyzer
        return {"error": repr(e)}


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="smoke mode: tiny K/num_outer/H per benchmark")
    parser.add_argument("--only", default=None,
                        help="substring filter on benchmark module names")
    args = parser.parse_args(argv)

    from benchmarks import (bench_chaos, bench_engine,
                            bench_fig3_convergence, bench_fig4a_rho,
                            bench_fig4b_scaling, bench_fig5_realenv,
                            bench_serve, bench_straggler_zoo,
                            bench_sweep_scaling, bench_table1, common,
                            roofline)

    mods = [bench_table1, bench_fig3_convergence, bench_fig4a_rho,
            bench_fig4b_scaling, bench_fig5_realenv, bench_straggler_zoo,
            bench_engine, bench_sweep_scaling, bench_serve, bench_chaos,
            roofline]
    if args.only:
        mods = [m for m in mods if args.only in m.__name__]
        if not mods:
            print(f"# no benchmark matches --only={args.only!r}",
                  file=sys.stderr)
            return

    print("name,us_per_call,derived")
    t0 = time.time()
    failures: list[dict] = []
    for mod in mods:
        # A raising benchmark must not silently truncate the suite: record
        # the failure (CSV row + JSON artifact) and keep going.
        common.run_cell(failures, mod.__name__, mod.main, quick=args.quick)
    failure_file = common.OUT_DIR / "bench_failures.json"
    if failures:
        common.dump("bench_failures", {"failed_modules": failures})
    elif failure_file.exists():
        failure_file.unlink()  # clean run: drop the stale failure record
    # Append this run's headline perf numbers to the top-level trajectory
    # (BENCH_SWEEP.json) so perf regressions are visible across PRs.
    entry = common.trajectory_entry(
        args.quick, failures, [m.__name__ for m in mods])
    entry["analysis_findings"] = _analysis_findings()
    common.append_trajectory(entry)
    print(f"# all benchmarks done in {time.time() - t0:.1f}s"
          + (f" ({len(failures)} FAILED)" if failures else ""),
          file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    main()
