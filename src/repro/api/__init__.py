"""The public API surface: declarative specs + streaming sessions.

Everything a caller needs lives here:

* :class:`ExperimentSpec` / :class:`MethodEntry` / :class:`ProblemSpec` --
  declarative, JSON-round-trippable experiment descriptions;
* :class:`Session` / :class:`Experiment` and the typed event stream
  (:class:`RoundEvent`, :class:`EvalEvent`, :class:`SyncEvent`,
  :class:`StopEvent`) -- streaming execution with early stop, on either
  execution backend (``executor="auto"|"event"|"scan"`` -- the scan-fused
  whole-run executor is bit-identical to the event loop, see
  docs/performance.md);
* :func:`run_sweep` / :func:`sweep_spec` -- whole delay x seed x gamma
  grids of any scan-capable method (lockstep AND ``lag``) as ONE compiled
  computation, optionally sharded over the local device mesh
  (``shard="auto"|"none"|"cells"|"workers"``; :func:`run_lockstep_sweep`
  is the lockstep-only compat wrapper); :func:`run_sweep_cells` runs an
  EXPLICIT list of :class:`SweepCellSpec` cells through the same compiled
  callables -- the entry point the multi-tenant service layer
  (:mod:`repro.serve`) batches coalesced tenant requests through;
* the :mod:`repro.core.compress` ``Compressor`` registry (re-exported) --
  the shared payload-compression extension point for both the simulator and
  the transformer exchange path;
* the :mod:`repro.core.delays` ``DelayModel`` registry (re-exported) -- the
  pluggable worker-delay axis (``ClusterModel.delay_model``);
* preset spec builders for the paper's figures plus the straggler-zoo
  family (:mod:`repro.api.presets`).

CLI: ``python -m repro run spec.json`` / ``python -m repro spec <preset>`` /
``python -m repro bench [--quick] [--only ...]``.

Legacy one-shot entry points (``repro.core.acpd.run_method``,
``repro.core.engine.run_method``) remain as thin wrappers that drain a
Session and fold the events into a ``RunResult``.
"""

from repro.api.presets import PRESETS, build_preset  # noqa: F401
from repro.api.problems import (  # noqa: F401
    ProblemSpec,
    available_problems,
    build_problem,
    register_problem,
)
from repro.api.session import (  # noqa: F401
    EvalEvent,
    Experiment,
    RoundEvent,
    Session,
    SessionEvent,
    StopEvent,
    SyncEvent,
)
from repro.api.spec import ExperimentSpec, MethodEntry  # noqa: F401
from repro.api.sweep import (  # noqa: F401
    ShardPlan,
    SweepCellSpec,
    SweepVariant,
    lower_sweep,
    resolve_shard,
    run_lockstep_sweep,
    run_sweep,
    run_sweep_cells,
    sweep_spec,
    sweep_supported,
)
from repro.core.compress import (  # noqa: F401
    Compressor,
    available_compressors,
    get_compressor,
    register_compressor,
)
from repro.core.delays import (  # noqa: F401
    DelayModel,
    available_delays,
    get_delay,
    register_delay,
)
from repro.core.solvers import (  # noqa: F401
    available_solvers,
    get_solver,
    register_solver,
)

__all__ = [
    "Compressor",
    "DelayModel",
    "EvalEvent",
    "Experiment",
    "ExperimentSpec",
    "MethodEntry",
    "PRESETS",
    "ProblemSpec",
    "RoundEvent",
    "Session",
    "SessionEvent",
    "ShardPlan",
    "StopEvent",
    "SweepCellSpec",
    "SweepVariant",
    "SyncEvent",
    "available_compressors",
    "available_delays",
    "available_problems",
    "available_solvers",
    "build_preset",
    "build_problem",
    "get_compressor",
    "get_delay",
    "get_solver",
    "lower_sweep",
    "register_compressor",
    "register_delay",
    "register_solver",
    "resolve_shard",
    "run_lockstep_sweep",
    "run_sweep",
    "run_sweep_cells",
    "sweep_spec",
    "sweep_supported",
]
