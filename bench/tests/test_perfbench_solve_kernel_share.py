"""The ``solve_kernel_share.gap`` reader against the program's counters."""

import pytest


@pytest.mark.parametrize("stats,expect", [
    ({"solve_passes": 640, "solve_kernel_passes": 640}, 1.0),
    ({"solve_passes": 640, "solve_kernel_passes": 0}, 0.0),
    ({"solve_passes": 640, "solve_kernel_passes": 160}, 0.25),
    # A program that does not count passes, or ran none: no reading.
    ({"event_rounds": 90, "host_syncs": 180}, None),
    ({"solve_passes": 0, "solve_kernel_passes": 0}, None),
])
def test_solve_kernel_share_reader(monkeypatch, stats, expect):
    from bench import run
    from repro.core import executor

    reader = run.load_module(run.BENCH / "metrics"
                             / "solve_kernel_share.gap.py")
    monkeypatch.setattr(executor, "STATS", stats)
    assert reader.read(None) == expect
