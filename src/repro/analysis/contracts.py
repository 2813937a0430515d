"""Trace-time contract analyzer: the dispatch invariants, read off the IR.

Layer 2 of ``python -m repro analyze`` (layer 1 is the AST lint,
:mod:`repro.analysis.lint`).  Where the lint reasons about *source*, this
module lowers the repo's key traced entry points with tiny abstract inputs
and asserts the PR-1/4/5 performance contracts from the jaxpr / compiled
HLO alone -- no timing, no runtime counters:

* ``lockstep-scan-fusion`` / ``lag-scan-fusion`` -- the whole-run executors
  (:func:`repro.core.executor.lockstep_run_traced`, ``lag_run_traced``)
  stage as exactly ONE top-level ``lax.scan`` of length R (the PR-4
  one-dispatch-per-run contract; an accidental Python-loop unroll or a
  second scan shows up here before it shows up in wall clock).
* ``lockstep-no-host-callbacks`` / ``lag-no-host-callbacks`` -- no callback
  primitive anywhere in the jaxpr and no callback custom-call in the
  compiled HLO: nothing on the scan path ever re-enters Python.
* ``engine-donation-aliasing`` -- the event engine's donated fused jits
  (``_worker_rounds_fused``, ``_server_apply_fused``, ``_lag_window_append``)
  really alias their donated operands: the lowered module carries the donor
  annotations and the compiled executable reports input-output aliasing
  (donation that silently degrades to a copy doubles HBM per dispatch).
* ``sweep-bucket-cache-sharing`` -- the PR-5 contract that grids of
  different shapes share one compile: two sweeps whose cell counts and eval
  cadences fall in the same pow2 bucket produce *identical* jit cache keys
  (same static arguments, same operand avals) for
  :func:`repro.api.sweep._sweep_scan`, checked without compiling anything.

Everything runs on abstract values (``jax.eval_shape``-sized toy shapes:
K=2 workers, n_k=3, d=4, R=3 rounds), so the whole pass is a few hundred
milliseconds of tracing on CPU.  Each check returns a
:class:`ContractResult`; the CLI fails on any ``ok=False``.
"""

from __future__ import annotations

import dataclasses

# ---------------------------------------------------------------------------
# Results.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ContractResult:
    """One trace-time contract verdict."""

    name: str
    ok: bool
    detail: str

    def format(self) -> str:
        mark = "ok" if self.ok else "FAIL"
        return f"contract {self.name}: {mark} -- {self.detail}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# IR inspection helpers.
# ---------------------------------------------------------------------------

# Primitive names that re-enter Python from inside a trace.  Matching is by
# substring on the primitive name so new spellings (pure_callback,
# io_callback, debug_callback, python_callback, outside_call) stay covered.
_CALLBACK_TOKENS = ("callback", "outside_call", "infeed", "outfeed")


def _iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def callback_primitives(jaxpr) -> list[str]:
    """Names of callback-style primitives anywhere in the jaxpr."""
    return sorted({
        e.primitive.name for e in _iter_eqns(jaxpr)
        if any(tok in e.primitive.name for tok in _CALLBACK_TOKENS)})


def top_level_scans(jaxpr) -> list[int]:
    """Lengths of the scans at the TOP level of the jaxpr (not nested)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    return [int(e.params["length"]) for e in jaxpr.eqns
            if e.primitive.name == "scan"]


def hlo_callback_sites(hlo_text: str) -> list[str]:
    """Lines of a compiled HLO dump that call back into Python."""
    return [ln.strip() for ln in hlo_text.splitlines()
            if "custom-call" in ln and "callback" in ln]


def donation_evidence(lowered, compiled) -> tuple[bool, bool]:
    """(lowered module carries donor annotations, compiled executable
    reports input-output aliasing)."""
    ltxt = lowered.as_text()
    donor = ("jax.buffer_donor" in ltxt) or ("tf.aliasing_output" in ltxt)
    try:
        ctxt = compiled.as_text()
    except Exception:  # backend without HLO text dumps
        ctxt = ""
    return donor, "input_output_alias" in ctxt


# ---------------------------------------------------------------------------
# Tiny abstract problem (shared by all checks).
# ---------------------------------------------------------------------------

_K, _NK, _D, _R = 2, 3, 4, 3


def _tiny_lockstep_args():
    import jax
    import jax.numpy as jnp

    key = jax.random.key(0)
    X = jnp.zeros((_K, _NK, _D), jnp.float32)
    y = jnp.ones((_K, _NK), jnp.float32)
    norms_sq = jnp.ones((_K, _NK), jnp.float32)
    return (key, X, y, norms_sq, jnp.float32(0.1), jnp.int32(_K * _NK),
            jnp.float32(float(_K)), jnp.float32(1.0))


def _tiny_lag_args():  # analysis: x64-ok (caller wraps in enable_x64)
    import jax
    import jax.numpy as jnp

    key, X, y, norms_sq, lam, n, sigma_p, gamma = _tiny_lockstep_args()
    return (key, X, y, norms_sq, lam, n, sigma_p, gamma,
            jnp.float32(1.0),                       # xi
            jnp.ones((_R + 1, _K), jnp.float64),    # durations (t=0 + rounds)
            jnp.full((_R,), 1, jnp.int64),          # needs
            jnp.asarray(16, jnp.int64),             # up_bytes
            jnp.asarray(4, jnp.int64),              # heartbeat_bytes
            jnp.asarray(0.001, jnp.float64),        # latency
            jnp.asarray(1e6, jnp.float64),          # bandwidth
            jnp.ones((_K,), jnp.float64))           # link_factors


# ---------------------------------------------------------------------------
# The checks.
# ---------------------------------------------------------------------------


def check_lockstep_contracts() -> list[ContractResult]:
    """``lockstep_run_traced``: one scan of length R, zero host callbacks,
    both in the jaxpr and in the compiled HLO."""
    import jax

    from repro.core import solvers
    from repro.core.executor import lockstep_run_traced

    def entry(*args):
        return lockstep_run_traced(
            *args, loss="smoothed_hinge", num_steps=2,
            solver=solvers.get_solver("sdca"), length=_R)

    args = _tiny_lockstep_args()
    jaxpr = jax.make_jaxpr(entry)(*args)
    out = []

    scans = top_level_scans(jaxpr)
    out.append(ContractResult(
        "lockstep-scan-fusion", scans == [_R],
        f"top-level scans (lengths) = {scans}, want one scan of length "
        f"{_R} (whole run staged as a single scan)"))

    prims = callback_primitives(jaxpr)
    lowered = jax.jit(entry).lower(*args)
    hlo = hlo_callback_sites(lowered.compile().as_text())
    ok = not prims and not hlo
    out.append(ContractResult(
        "lockstep-no-host-callbacks", ok,
        "no callback primitives in the jaxpr and no callback custom-calls "
        "in the compiled HLO" if ok else
        f"callback primitives {prims}, HLO callback sites {hlo}"))
    return out


def check_lag_contracts() -> list[ContractResult]:
    """``lag_run_traced`` under ``enable_x64``: same two contracts (the
    in-graph event queue adds sort/cond/top_k -- none may call home)."""
    import jax

    from repro.core import compress
    from repro.core.executor import lag_run_traced

    def entry(*args):
        return lag_run_traced(
            *args, loss="smoothed_hinge", num_steps=2,
            comp=compress.Dense(rho=1.0), length=_R, lag_window=2,
            dense_reply_bytes=_D * 4)

    out = []
    with jax.enable_x64(True):
        args = _tiny_lag_args()
        jaxpr = jax.make_jaxpr(entry)(*args)
        scans = top_level_scans(jaxpr)
        # The staged structure is exactly: the t=0 launch wave (a rank scan
        # over the K workers) followed by ONE round scan of length R.
        out.append(ContractResult(
            "lag-scan-fusion", scans == [_K, _R],
            f"top-level scans (lengths) = {scans}, want the K={_K} initial "
            f"launch wave + one round scan of length {_R} (whole run staged "
            f"as a single round scan)"))

        prims = callback_primitives(jaxpr)
        hlo = hlo_callback_sites(jax.jit(entry).lower(*args)
                                 .compile().as_text())
    ok = not prims and not hlo
    out.append(ContractResult(
        "lag-no-host-callbacks", ok,
        "no callback primitives in the jaxpr and no callback custom-calls "
        "in the compiled HLO" if ok else
        f"callback primitives {prims}, HLO callback sites {hlo}"))
    return out


def check_engine_donation() -> list[ContractResult]:
    """The engine's donated fused jits really alias donated buffers."""
    import jax
    import jax.numpy as jnp

    from repro.core import compress, engine

    key, X, y, norms_sq, lam, n, sigma_p, gamma = _tiny_lockstep_args()
    idxs = jnp.zeros((1,), jnp.int32)
    w = jnp.zeros((_D,), jnp.float32)
    alpha = jnp.zeros((_K, _NK), jnp.float32)
    residual = jnp.zeros((_K, _D), jnp.float32)
    w_rows = jnp.zeros((_K, _D), jnp.float32)
    comp = compress.Dense(rho=1.0)

    targets = {
        "_worker_rounds_fused": lambda: engine._worker_rounds_fused.lower(
            key, w, alpha, residual, X, y, norms_sq, idxs, lam, n, sigma_p,
            gamma, loss="smoothed_hinge", num_steps=2, comp=comp),
        "_server_apply_fused": lambda: engine._server_apply_fused.lower(
            w, w_rows, w_rows, alpha, idxs, (w,), (alpha[0],),
            jnp.ones((1,), bool), gamma),
        "_lag_window_append": lambda: engine._lag_window_append.lower(
            jnp.zeros((_K, 2), jnp.float32), jnp.zeros((_K,), jnp.int32),
            idxs, jnp.ones((1,), jnp.float32)),
    }
    out = []
    for name, lower in targets.items():
        lowered = lower()
        donor, aliased = donation_evidence(lowered, lowered.compile())
        out.append(ContractResult(
            f"donation-{name}", donor and aliased,
            f"lowered donor annotation={donor}, compiled "
            f"input_output_alias={aliased} (donated carries must alias, "
            f"not copy)"))
    return out


def check_sweep_bucket_sharing() -> list[ContractResult]:
    """Two grids in the same pow2 bucket produce the SAME jit cache key.

    A ``jax.jit`` cache entry is keyed on (static arguments, operand
    avals).  ``run_sweep`` routes every grid through ``_padded_cells`` /
    ``_padded_eval_idx`` before touching ``_sweep_scan``, so the check
    builds the padded operand avals + static argument tuple for a 3-cell
    grid with 3 eval boundaries and a 4-cell grid with 4 eval boundaries
    (same buckets) and asserts they are identical -- byte-for-byte the
    same cache key, with no compile and no tracing.
    """
    import jax
    import jax.numpy as jnp

    from repro.api.sweep import _padded_cells, _padded_eval_idx

    def cache_key(num_cells, evals):
        cells = _padded_cells(list(range(num_cells)), n_shards=1)
        V = len(cells)
        eval_idx_static = _padded_eval_idx(evals)
        E = len(eval_idx_static)
        avals = tuple(
            jax.ShapeDtypeStruct(s, d) for s, d in (
                ((V,), jax.random.key(0).dtype),     # keys
                ((_K, _NK, _D), jnp.float32),        # X
                ((_K, _NK), jnp.float32),            # y
                ((_K, _NK), jnp.float32),            # norms_sq
                ((), jnp.float32), ((), jnp.int32),  # lam, n
                ((V,), jnp.float32),                 # sigma_ps
                ((V,), jnp.float32),                 # gammas
                ((E,), jnp.int32),                   # eval_idx (gather)
            ))
        statics = ("smoothed_hinge", 2, "sdca", _R, "vmap", 1)
        return (statics, tuple((a.shape, str(a.dtype)) for a in avals))

    key_a = cache_key(3, [0, 1, 2])   # 3 cells, 3 boundaries -> bucket 4, 4
    key_b = cache_key(4, [0, 1, 2, 2])  # 4 cells, 4 boundaries -> same
    ok = key_a == key_b
    return [ContractResult(
        "sweep-bucket-cache-sharing", ok,
        "3-cell/3-eval and 4-cell/4-eval grids pad to identical jit cache "
        "keys (shared compile)" if ok else
        f"cache keys differ: {key_a} vs {key_b}")]


def run_contracts(*, include_lag: bool = True) -> list[ContractResult]:
    """Run every contract check; import failures become failed results
    rather than crashes, so the CLI always reports per-contract."""
    suites = [check_lockstep_contracts, check_engine_donation,
              check_sweep_bucket_sharing]
    if include_lag:
        suites.insert(1, check_lag_contracts)
    out: list[ContractResult] = []
    for suite in suites:
        try:
            out.extend(suite())
        except Exception as e:  # pragma: no cover - environment failure
            out.append(ContractResult(suite.__name__, False,
                                      f"analyzer error: {e!r}"))
    return out
