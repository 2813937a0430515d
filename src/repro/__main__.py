"""``python -m repro``: the unified experiment CLI.

Subcommands:

* ``run <spec.json>``  -- execute an :class:`repro.api.ExperimentSpec` file,
  streaming session events (round/sync/eval/stop) to stdout; early stop on
  the spec's ``target_gap`` / ``time_budget``. ``--out`` writes the full
  record trajectories + provenance as JSON.
* ``spec <preset>``    -- print a preset spec (see ``repro.api.presets``) as
  JSON, ready to edit and feed back to ``run``.
* ``bench``            -- the benchmark driver; ``--quick`` and ``--only``
  are forwarded to ``benchmarks/run.py`` so both entry points share one
  driver (run from the repo root with ``PYTHONPATH=src``).
* ``analyze``          -- the static analyzer (AST lint + trace-time
  contract checks, see :mod:`repro.analysis` and docs/static-analysis.md);
  exits nonzero on findings not in ``ANALYSIS_BASELINE.json`` or on a
  failed contract.
* ``serve``            -- the persistent multi-tenant experiment service
  over HTTP (:mod:`repro.serve`, docs/serving.md): POST /submit specs,
  GET /events/<job>, GET /stats; coalesces compatible tenant requests into
  shared compiled sweep batches.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def _cmd_run(args) -> int:
    import jax

    from repro import api

    spec = api.ExperimentSpec.load(args.spec)
    if args.target_gap is not None:
        spec = dataclasses.replace(spec, target_gap=args.target_gap)
    if args.time_budget is not None:
        spec = dataclasses.replace(spec, time_budget=args.time_budget)
    if args.checkpoint_every is not None:
        spec = dataclasses.replace(spec, checkpoint_every=args.checkpoint_every)
    if spec.checkpoint_every is not None and args.checkpoint_dir is None:
        print("error: spec sets checkpoint_every; pass --checkpoint-dir for "
              "the snapshots", file=sys.stderr)
        return 2
    print(f"# spec {spec.name!r}: {len(spec.methods)} method(s), "
          f"problem={spec.problem.kind}, K={spec.cluster.num_workers}, "
          f"target_gap={spec.target_gap}, time_budget={spec.time_budget}"
          + (f", checkpoint_every={spec.checkpoint_every}"
             if spec.checkpoint_every is not None else ""))
    exp = api.Experiment(spec, checkpoint_dir=args.checkpoint_dir)
    results = {}
    for entry in spec.methods:
        name = entry.config.name
        print(f"== {name} (protocol={entry.config.protocol}, "
              f"num_outer={entry.num_outer}) ==")
        session = exp.session(entry)
        for ev in session:
            if isinstance(ev, api.EvalEvent):
                print(f"  eval  it={ev.iteration:5d} t={ev.sim_time:9.4f}s "
                      f"gap={ev.gap:.3e} up={ev.bytes_up / 1e6:.2f}MB "
                      f"down={ev.bytes_down / 1e6:.2f}MB")
            elif isinstance(ev, api.SyncEvent):
                if args.verbose:
                    print(f"  sync  it={ev.iteration:5d} t={ev.sim_time:9.4f}s")
            elif isinstance(ev, api.RoundEvent):
                if args.verbose:
                    print(f"  round it={ev.iteration:5d} t={ev.sim_time:9.4f}s "
                          f"arrivals={ev.arrivals}")
            elif isinstance(ev, api.StopEvent):
                print(f"  stop  reason={ev.reason} it={ev.iteration} "
                      f"t={ev.sim_time:.4f}s")
        results[name] = session.result()

    for name, res in results.items():
        last = res.records[-1]
        t = res.time_to_gap(spec.target_gap) if spec.target_gap else None
        extra = (f" time_to_gap({spec.target_gap:g})="
                 f"{t:.4f}s" if t is not None else "")
        print(f"{name:12s} rounds={last.iteration:5d} gap={last.gap:.3e}"
              f" sim_t={last.sim_time:.4f}s{extra}")

    if args.out:
        payload = {
            "spec": spec.to_dict(),
            "provenance": {"jax_version": jax.__version__,
                           "seed": spec.seed},
            "results": {name: res.as_dict() for name, res in results.items()},
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.out}")
    return 0


def _cmd_spec(args) -> int:
    from repro import api

    kwargs = {"quick": args.quick} if args.quick else {}
    spec = api.build_preset(args.preset, **kwargs)
    print(spec.to_json())
    return 0


def _cmd_bench(args) -> int:
    try:
        from benchmarks.run import main as bench_main
    except ImportError:
        print("error: the 'benchmarks' package is not importable; run from "
              "the repo root (python -m repro bench) with PYTHONPATH=src",
              file=sys.stderr)
        return 2
    argv = []
    if args.quick:
        argv.append("--quick")
    if args.only:
        argv.extend(["--only", args.only])
    bench_main(argv)
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="repro", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="execute an ExperimentSpec JSON file")
    p_run.add_argument("spec", help="path to a spec JSON "
                       "(see `python -m repro spec <preset>`)")
    p_run.add_argument("--out", default=None,
                       help="write records + provenance JSON here")
    p_run.add_argument("--target-gap", type=float, default=None,
                       help="override the spec's early-stop duality gap")
    p_run.add_argument("--time-budget", type=float, default=None,
                       help="override the spec's simulated-time budget (s)")
    p_run.add_argument("--verbose", action="store_true",
                       help="also stream per-round and sync events")
    p_run.add_argument("--checkpoint-every", type=int, default=None,
                       help="snapshot the run state every N rounds "
                            "(resumable; overrides the spec's "
                            "checkpoint_every)")
    p_run.add_argument("--checkpoint-dir", default=None,
                       help="where checkpoint snapshots live; re-running "
                            "the same spec resumes from the latest one")
    p_run.set_defaults(fn=_cmd_run)

    p_spec = sub.add_parser("spec", help="print a preset spec as JSON")
    from repro.api.presets import PRESETS

    p_spec.add_argument("preset", choices=sorted(PRESETS))
    p_spec.add_argument("--quick", action="store_true",
                        help="smoke-scale variant")
    p_spec.set_defaults(fn=_cmd_spec)

    p_bench = sub.add_parser(
        "bench", help="run the benchmark driver (shared with benchmarks/run.py)")
    p_bench.add_argument("--quick", action="store_true",
                         help="smoke mode: tiny K/num_outer/H per benchmark")
    p_bench.add_argument("--only", default=None,
                         help="substring filter on benchmark module names")
    p_bench.set_defaults(fn=_cmd_bench)

    # `analyze` and `serve` own their flag surfaces; forward the raw
    # remainder so `repro analyze --update-baseline` / `repro serve --port`
    # etc. just work.
    sub.add_parser(
        "analyze", add_help=False,
        help="static analysis: project lint + trace-contract checks "
             "(docs/static-analysis.md)").set_defaults(fn=None)
    sub.add_parser(
        "serve", add_help=False,
        help="multi-tenant experiment service over HTTP "
             "(docs/serving.md)").set_defaults(fn=None)

    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "analyze":
        from repro.analysis.cli import main as analyze_main

        return analyze_main(argv[1:])
    if argv and argv[0] == "serve":
        from repro.serve.http import main as serve_main

        serve_main(argv[1:])
        return 0

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    sys.exit(main())
