"""BENCHMARK.json names only files that exist, in the form the harness
reads, and every cell resolves to its configuration, traffic, job module and
metric readers."""

import json
import os
import re

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells(bench_json=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert {"setup_s"} <= {m["name"] for m in bench["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all("\n" not in layer for layer in layers)


def test_every_config_is_used_and_its_file_is_under_paths(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_metric_workloads_report_what_they_move(bench):
    cell_names = {w["name"] for w in bench["workloads"]}
    reports = {c: {"setup_s"} for c in cell_names}
    for m in bench["end_to_end"]:
        for c in m.get("workloads", cell_names):
            reports[c].add(m["name"])
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cell_names
        for c in m["workloads"]:
            assert m["moves"] in reports[c], (m["name"], c)
    for c, names in reports.items():
        assert len(names) >= 2, c


@pytest.mark.parametrize("cell", cells())
def test_cell_resolves(cell):
    resolved = run.resolve(cell)
    assert resolved["jobs"].is_file()
    assert resolved["per_layer"]
    assert {m["name"] for m in resolved["end_to_end"]} >= {"setup_s"}


def test_unknown_cell_is_refused():
    with pytest.raises(run.Refused, match="no workload"):
        run.resolve("no.such.cell")
