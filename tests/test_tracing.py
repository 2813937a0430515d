"""The engine's instrumentation (``repro.core.tracing``): the counters at the
event loop's boundaries, the host spans a profiler records, the device
scopes in the compiled programs, and that none of it changes a result."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import baselines, engine, executor, tracing
from repro.core.simulate import ClusterModel

K, D = 4, 512
B, T, EVAL_EVERY, OUTER = 2, 3, 3, 2


def _group():
    return baselines.acpd(K, D, B=B, T=T, rho_d=32, gamma=0.5, H=32)


def _cluster():
    return ClusterModel(num_workers=K, straggler_sigma=2.0)


def _session(problem, eval_mode="stream", seed=3):
    return api.Session(problem, _group(), _cluster(), num_outer=OUTER,
                       seed=seed, eval_every=EVAL_EVERY, eval_mode=eval_mode,
                       executor="event")


def test_executor_stats_is_the_tracing_dict():
    assert executor.STATS is tracing.STATS
    assert executor.reset_stats is tracing.reset_stats


@pytest.mark.parametrize("eval_mode,syncs_per_eval", [
    ("stream", 5),  # primal, dual, gap, primal_server, gap_server
    ("batched", 0),  # certificates deferred past the loop: not a round's
])
def test_event_loop_counters(small_problem, eval_mode, syncs_per_eval):
    before = dict(tracing.STATS)
    _session(small_problem, eval_mode).run()
    delta = {k: tracing.STATS[k] - before[k] for k in tracing.STATS}
    rounds = OUTER * T
    evals = rounds // EVAL_EVERY
    assert delta["event_rounds"] == rounds
    # B arrivals a round, K at each T-th (the full barrier).
    assert delta["event_arrivals"] == OUTER * ((T - 1) * B + K)
    # One reply-nnz read a round (sparse replies), plus the certificates'.
    assert delta["host_syncs"] == rounds + syncs_per_eval * evals


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("n_chunks", [1, 2])
def test_solve_pass_counters(small_problem, monkeypatch, on_tpu, n_chunks):
    """Every worker pass the engine dispatches counts once in
    ``solve_passes``, and in ``solve_kernel_passes`` too where the kernel
    applies: nowhere on the CPU, everywhere for this ridge problem on a
    TPU (the protocol's platform stood in for here)."""
    from repro.kernels import sdca_inner

    if on_tpu:
        applies = sdca_inner.applies
        monkeypatch.setattr(sdca_inner, "applies",
                            lambda _platform, *rest: applies("tpu", *rest))
    method = _group()
    if n_chunks > 1:
        method = dataclasses.replace(method, protocol="partial_work",
                                     n_chunks=n_chunks)
    before = dict(tracing.STATS)
    session = api.Session(small_problem, method, _cluster(),
                          num_outer=OUTER, seed=3, eval_every=EVAL_EVERY,
                          executor="event")
    session.run()
    delta = {k: tracing.STATS[k] - before[k] for k in tracing.STATS}
    # The t=0 wave launches all K workers; each pass that arrives (all of
    # its chunks: no worker drops out here) relaunches its worker.
    launches = K + delta["event_arrivals"] // n_chunks
    assert delta["solve_passes"] == n_chunks * launches
    assert delta["solve_kernel_passes"] == (delta["solve_passes"] if on_tpu
                                            else 0)


def _results_and_sweep(problem):
    result = _session(problem).run()
    variants = api.run_sweep(problem, baselines.cocoa_plus(K, H=32),
                             _cluster(), num_outer=3, seeds=[1, 2],
                             gammas=[1.0, 0.5], eval_every=3, shard="none")
    return result, variants


@pytest.fixture(scope="module")
def profiled(small_problem, tmp_path_factory):
    """The same group run and sweep, bare and under the profiler."""
    bare = _results_and_sweep(small_problem)
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        traced = _results_and_sweep(small_problem)
    return bare, traced, log_dir


def test_profiler_changes_no_result(profiled):
    (run_a, sweep_a), (run_b, sweep_b), _ = profiled
    np.testing.assert_array_equal(run_a.w, run_b.w)
    np.testing.assert_array_equal(run_a.alpha, run_b.alpha)
    np.testing.assert_array_equal(run_a.alpha_applied, run_b.alpha_applied)
    assert run_a.records == run_b.records
    for a, b in zip(sweep_a, sweep_b, strict=True):
        np.testing.assert_array_equal(a.result.w, b.result.w)
        np.testing.assert_array_equal(a.result.alpha, b.result.alpha)
        assert a.result.records == b.result.records


def test_profile_holds_the_program_spans_nested(profiled):
    from jax.profiler import ProfileData

    *_, log_dir = profiled
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    spans, rounds = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
                    if e.name == "repro.round":
                        rounds.append(dict(e.stats)["round"])
    names = {name for _, _, name in spans}
    assert names == {
        "repro.session.init", "repro.round", "repro.engine.queue",
        "repro.engine.server_dispatch", "repro.engine.sync",
        "repro.engine.delay_sample", "repro.engine.worker_dispatch",
        "repro.engine.split", "repro.certificate", "repro.certificate.sync",
        "repro.sweep.prepare", "repro.sweep.dispatch", "repro.sweep.fetch",
        "repro.sweep.records"}
    # Round 0 is the first launch; then one span per round.
    assert sorted(rounds) == list(range(OUTER * T + 1))
    # Spans nest or are disjoint: none stays open across another's end.
    stack = []
    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        assert not stack or end <= stack[-1][1], (name, stack[-1][2])
        stack.append((start, end, name))


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes(compiled_text: str) -> set:
    return {c.partition(":")[0] for path in _op_names(compiled_text)
            for c in path.split("/") if c.startswith("acpd.")}


def _worker_rounds(problem):
    proto = engine.GroupProtocol(problem, _group(), _cluster(), seed=0)
    idxs = jnp.asarray([0, 2], jnp.int32)
    return engine._worker_rounds_fused.lower(
        proto.key, proto.w_local, proto.alpha, proto.residual, problem.X,
        problem.y, proto.norms_sq, idxs, problem.lam, proto.n,
        proto.sigma_p, 0.5, loss=problem.loss, num_steps=8, comp=proto.comp)


def _server_apply(problem):
    proto = engine.GroupProtocol(problem, _group(), _cluster(), seed=0)
    idxs = jnp.asarray([0, 2], jnp.int32)
    payloads = (jnp.ones((D,)), jnp.ones((D,)))
    snaps = (proto.alpha[0], proto.alpha[2])
    return engine._server_apply_fused.lower(
        proto.w_server, proto.dw_tilde, proto.w_local, proto.alpha_applied,
        idxs, payloads, snaps, jnp.asarray([True, True]), 0.5)


def _sweep_scan(problem):
    return api.lower_sweep(problem, baselines.cocoa_plus(K, H=8), _cluster(),
                           num_outer=2, seeds=[0], gammas=[1.0],
                           shard="none")


def _eval_batched(problem):
    ws = jnp.zeros((2, D))
    alphas = jnp.zeros((2,) + problem.y.shape)
    return engine._eval_batched.lower(ws, alphas, problem.X, problem.y,
                                      problem.lam, loss=problem.loss)


@pytest.mark.parametrize("lower,scopes", [
    (_worker_rounds, {tracing.SOLVE, tracing.WORKER_STATE, tracing.FILTER}),
    (_server_apply, {tracing.SERVER_APPLY}),
    (_sweep_scan, {tracing.SOLVE, tracing.AGGREGATE}),
    (_eval_batched, {tracing.CERTIFICATE}),
], ids=["worker_rounds", "server_apply", "sweep_scan", "eval_batched"])
def test_device_scopes_in_compiled_programs(small_problem, lower, scopes):
    text = lower(small_problem).compile().as_text()
    assert _scopes(text) == scopes

