"""A gap cell on the CPU at a small size: correct as it stands, and not
correct with the timed path broken underneath, once for each fault a
one-chip run to a gap can have."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.tests.cpu_cell import run_cell

CELL = "rcv1.acpd.gap"
FEW_ROUNDS = {"max_outer": 6}  # a run that cannot reach the target stops


@pytest.fixture
def fresh_programs():
    """Patched functions are traced anew, and nothing patched stays cached."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound(monkeypatch, capsys):
    line = run_cell(monkeypatch, capsys, CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"time_to_gap_s", "setup_s"}
    assert list(line)[-1] == "checks"


def test_state_left_unchanged(monkeypatch, capsys, fresh_programs):
    from repro.core import engine

    def unchanged(key, w_local, alpha, residual, X, y, norms_sq, idxs, *a,
                  **kw):
        return (key, alpha, residual, alpha[idxs],
                jnp.zeros((idxs.shape[0],) + residual.shape[1:],
                          residual.dtype))

    monkeypatch.setattr(engine, "_worker_rounds_fused", unchanged)
    line = run_cell(monkeypatch, capsys, CELL, traffic=FEW_ROUNDS)
    assert not line["correct"]
    assert line["checks"]["unstopped"]["value"] >= 1


def test_half_the_rows_left_out_of_the_mean(monkeypatch, capsys,
                                             fresh_programs):
    from repro.core import objectives

    certificate = objectives.gap_certificate

    def half(problem, alpha, w=None):
        h = problem.X.shape[1] // 2
        return certificate(dataclasses.replace(
            problem, X=problem.X[:, :h], y=problem.y[:, :h]),
            alpha[:, :h], w)

    monkeypatch.setattr(objectives, "gap_certificate", half)
    line = run_cell(monkeypatch, capsys, CELL, traffic=FEW_ROUNDS)
    assert not line["correct"]


def test_answer_altered_where_produced(monkeypatch, capsys):
    from repro.core import engine

    finalize = engine.GroupProtocol.finalize

    def altered(self, records):
        result = finalize(self, records)
        result.alpha_applied = result.alpha_applied.copy()
        result.alpha_applied[0, 0] += 1.0
        return result

    monkeypatch.setattr(engine.GroupProtocol, "finalize", altered)
    line = run_cell(monkeypatch, capsys, CELL)
    assert not line["correct"]
    rel = line["checks"]["gap_rel_err"]
    assert rel["value"] > rel["limit"]


def test_control_is_not_correct(monkeypatch, capsys):
    """The reference one precision step below, in the program's place, at
    the smallest size where its error reaches the cell's limit."""
    line = run_cell(monkeypatch, capsys, CELL, control="high",
                    dataset={"rows": 1024, "features": 8192,
                             "nnz_per_row": 32})
    assert not line["correct"]
    rel = line["checks"]["gap_rel_err"]
    assert rel["value"] > rel["limit"]
