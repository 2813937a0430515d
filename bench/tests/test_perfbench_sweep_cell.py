"""A sweep cell on the CPU at a small size: correct as it stands, and not
correct with the timed path broken underneath, once for each fault a
one-chip grid can have."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.cpu_cell import run_cell

CELL = "rcv1.cocoa_plus.sweep"
# At the small size 10 rounds leave about half the initial gap; 40 reach the
# stage (about a sixth) that the cell's own size reaches in 10.
ROUNDS = {"rounds": 40}


@pytest.fixture
def fresh_programs():
    """Patched functions are traced anew, and nothing patched stays cached."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound(monkeypatch, capsys):
    line = run_cell(monkeypatch, capsys, CELL, traffic=ROUNDS)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"sweep_cells_per_s", "setup_s"}


def test_state_left_unchanged(monkeypatch, capsys, fresh_programs):
    from repro.core import executor

    def unchanged(key, X, y, norms_sq, lam, n, sigma_p, gamma, *, loss,
                  num_steps, solver, length):
        K, n_k, d = X.shape
        w, alpha = jnp.zeros((d,), X.dtype), jnp.zeros((K, n_k), X.dtype)
        return (w, alpha, jnp.zeros((length, d), X.dtype),
                jnp.zeros((length, K, n_k), X.dtype))

    monkeypatch.setattr(executor, "lockstep_run_traced", unchanged)
    line = run_cell(monkeypatch, capsys, CELL, traffic=ROUNDS)
    assert not line["correct"]
    progress = line["checks"]["gap_progress"]
    assert progress["value"] == pytest.approx(1.0)


def test_half_the_workers_left_out_of_the_sum(monkeypatch, capsys,
                                             fresh_programs):
    from repro.core import engine

    solves = engine._lockstep_local_solves

    def half(*a, **kw):
        dalpha, v = solves(*a, **kw)
        keep = jnp.arange(v.shape[0]) < v.shape[0] // 2
        return dalpha, jnp.where(keep[:, None], v, 0.0) * 2.0

    monkeypatch.setattr(engine, "_lockstep_local_solves", half)
    line = run_cell(monkeypatch, capsys, CELL, traffic=ROUNDS)
    assert not line["correct"]
    w_err = line["checks"]["w_rel_err"]
    assert w_err["value"] > w_err["limit"]


def test_answer_altered_where_produced(monkeypatch, capsys):
    from repro.api import sweep

    lockstep = sweep._run_lockstep_sweep

    def altered(*a, **kw):
        out = lockstep(*a, **kw)
        v = out[0]
        w = np.array(v.result.w)
        w[0] += 1.0
        return [dataclasses.replace(v, result=dataclasses.replace(
            v.result, w=w))] + out[1:]

    monkeypatch.setattr(sweep, "_run_lockstep_sweep", altered)
    line = run_cell(monkeypatch, capsys, CELL, traffic=ROUNDS)
    assert not line["correct"]
    w_err = line["checks"]["w_rel_err"]
    assert w_err["value"] > w_err["limit"]


def test_control_is_not_correct(monkeypatch, capsys):
    """The reference one precision step below, in the program's place."""
    line = run_cell(monkeypatch, capsys, CELL, control="high",
                    traffic=ROUNDS)
    assert not line["correct"]
    w_err = line["checks"]["w_rel_err"]
    assert w_err["value"] > w_err["limit"]
