"""Drive ``bench/run.py`` in this process on the CPU at a small size: the
harness's look for a chip and its compile-cache set-up are skipped, the
rest of a run (data, warm-up, window, check, result line) is the real one."""

import json

from bench import run

SMALL = {"rows": 16 * 24, "features": 2048, "nnz_per_row": 24}


def run_cell(monkeypatch, capsys, cell, *, seed=2**31 + 5, seconds=0.01,
             control=None, dataset=SMALL, traffic=None):
    """The result line of one small run of ``cell`` on the CPU."""
    resolve = run.resolve

    def small(name):
        resolved = resolve(name)
        resolved["config"]["dataset"].update(dataset)
        resolved["traffic"].update(traffic or {})
        return resolved

    monkeypatch.setattr(run, "resolve", small)
    monkeypatch.setattr(run, "chip_devices",
                        lambda jax, chips: jax.devices()[:1])
    monkeypatch.setattr(run, "configure_cache", lambda jax: None)
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]
    if control:
        argv += ["--control", control]
    capsys.readouterr()
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
