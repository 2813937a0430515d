"""The static analyzer's own contract: every rule fires on its seeded
fixture at the right file:line, pragmas suppress, the repo lints clean
against the checked-in baseline, and the trace-time contracts hold.

Fixture modules live in tests/fixtures/analysis/ -- linted as source,
never imported.  Each violating line carries a ``# VIOLATION`` marker
(twice when one line yields two findings), so expectations live next to
the code they describe instead of as brittle line-number tables here.
"""

import pathlib
import re

import pytest

from repro.analysis import cli, contracts, lint
from repro.analysis.findings import Baseline, Finding

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "fixtures" / "analysis"

_MARKER = re.compile(r"# VIOLATION")


def marked_lines(path: pathlib.Path) -> dict[int, int]:
    """{line number: expected finding count} from the # VIOLATION markers."""
    out = {}
    for i, text in enumerate(path.read_text().splitlines(), start=1):
        n = len(_MARKER.findall(text))
        if n:
            out[i] = n
    return out


def lint_fixture(name: str, rule: str) -> list[Finding]:
    return lint.lint_paths([FIXTURES / name], root=ROOT, rules=[rule])


# ---------------------------------------------------------------------------
# Layer 1: each rule fires exactly on its fixture's marked lines.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule", [
    ("bad_mesh.py", "mesh-via-make-mesh"),
    ("bad_host_sync.py", "traced-host-sync"),
    ("bad_traced_span.py", "traced-span"),
    ("bad_donation.py", "jit-donation"),
    ("bad_f64.py", "f64-without-x64"),
    ("bad_registry.py", "registry-hooks"),
    ("bad_serve_typed_errors.py", "typed-errors"),
])
def test_rule_fires_at_marked_lines(fixture, rule):
    expected = marked_lines(FIXTURES / fixture)
    assert expected, f"{fixture} lost its # VIOLATION markers"
    findings = lint_fixture(fixture, rule)
    got: dict[int, int] = {}
    for f in findings:
        assert f.rule == rule
        assert f.path.endswith(fixture), f.path
        got[f.line] = got.get(f.line, 0) + 1
    assert got == expected, (
        f"{fixture}: findings at {got}, markers at {expected}\n"
        + "\n".join(f.format() for f in findings))


def test_all_rules_together_report_only_marked_lines():
    """Running the full default rule set over one fixture must not produce
    cross-rule false positives on the clean lines."""
    findings = lint.lint_paths([FIXTURES / "bad_donation.py"], root=ROOT)
    lines = {f.line for f in findings}
    assert lines == set(marked_lines(FIXTURES / "bad_donation.py"))


def test_pragmas_suppress_everything():
    findings = lint.lint_paths([FIXTURES / "ok_pragmas.py"], root=ROOT)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_host_code_is_not_flagged():
    """The reachability analysis: `host_report` uses the same host-sync
    calls as the traced `step` but is unreachable from any traced root."""
    findings = lint_fixture("bad_host_sync.py", "traced-host-sync")
    assert findings  # the traced ones do fire
    assert all(f.context != "host_report" for f in findings)


def test_finding_format_is_clickable():
    f = lint_fixture("bad_f64.py", "f64-without-x64")[0]
    assert f.format().startswith("tests/fixtures/analysis/bad_f64.py:7: ")


# ---------------------------------------------------------------------------
# The rule registry mirrors the protocol/compressor registry idiom.
# ---------------------------------------------------------------------------


def test_rule_registry():
    rules = lint.available_rules()
    for name in ("mesh-via-make-mesh", "traced-host-sync", "traced-span",
                 "jit-donation", "f64-without-x64", "registry-hooks",
                 "typed-errors"):
        assert name in rules
        assert lint.get_rule(name).description
    with pytest.raises(ValueError, match="unknown analysis rule"):
        lint.get_rule("nope")


def test_example_rules_excluded_from_default_set():
    @lint.register_rule("no-print-example")
    class NoPrint(lint.Rule):
        description = "test-only"

        def check(self, module, project):
            return []

    try:
        assert "no-print-example" in lint.available_rules()
        assert "no-print-example" not in lint.default_rules()
    finally:
        del lint._RULES["no-print-example"]


def test_lint_source_snippet_api():
    """The docs-guide entry point: lint an in-memory snippet."""
    findings = lint.lint_source(
        "import jax\nm = jax.sharding.Mesh(None, ('x',))\n",
        rules=["mesh-via-make-mesh"])
    assert [f.line for f in findings] == [2]


# ---------------------------------------------------------------------------
# Baseline: content-based fingerprints + split semantics.
# ---------------------------------------------------------------------------


def test_baseline_fingerprints_survive_line_shifts():
    src = "import jax.numpy as jnp\n\ndef t():\n    return jnp.float64\n"
    shifted = "import jax.numpy as jnp\n\n\n\n\ndef t():\n    return jnp.float64\n"
    a = lint.lint_source(src, path="m.py", rules=["f64-without-x64"])
    b = lint.lint_source(shifted, path="m.py", rules=["f64-without-x64"])
    assert a[0].line != b[0].line
    assert a[0].fingerprint == b[0].fingerprint


def test_baseline_split(tmp_path):
    findings = lint_fixture("bad_f64.py", "f64-without-x64")
    path = tmp_path / "baseline.json"
    Baseline.write(path, findings)
    loaded = Baseline.load(path)
    new, accepted, stale = loaded.split(findings)
    assert (new, len(accepted), stale) == ([], len(findings), set())
    new, accepted, stale = loaded.split([])
    assert new == [] and accepted == [] and len(stale) == len(findings)
    # Missing file == empty baseline: everything is new.
    empty = Baseline.load(tmp_path / "missing.json")
    new, _, _ = empty.split(findings)
    assert len(new) == len(findings)


# ---------------------------------------------------------------------------
# The acceptance bar: repo lints clean, seeded fixtures fail, via the CLI.
# ---------------------------------------------------------------------------


def test_repo_lints_clean_against_checked_in_baseline():
    findings = lint.lint_paths([ROOT / "src"], root=ROOT)
    baseline = Baseline.load(ROOT / "ANALYSIS_BASELINE.json")
    new, accepted, stale = baseline.split(findings)
    assert new == [], "new findings:\n" + "\n".join(f.format() for f in new)
    assert not stale, f"stale baseline entries: {stale}"
    assert accepted == [], "the baseline holds no accepted findings"


def test_cli_exits_nonzero_on_seeded_fixture(tmp_path, capsys):
    rc = cli.main(["--no-contracts", "--baseline",
                   str(tmp_path / "empty.json"),
                   "--paths", str(FIXTURES / "bad_donation.py")])
    assert rc == 1
    out = capsys.readouterr().out
    assert "jit-donation" in out and "bad_donation.py" in out


def test_cli_exits_zero_on_clean_input(tmp_path, capsys):
    rc = cli.main(["--no-contracts", "--baseline",
                   str(tmp_path / "empty.json"),
                   "--paths", str(FIXTURES / "ok_pragmas.py")])
    assert rc == 0
    assert "0 new finding(s)" in capsys.readouterr().out


def test_cli_update_baseline_roundtrip(tmp_path, capsys):
    base = tmp_path / "b.json"
    args = ["--baseline", str(base),
            "--paths", str(FIXTURES / "bad_f64.py"), "--no-contracts"]
    assert cli.main(args + ["--update-baseline"]) == 0
    capsys.readouterr()
    assert cli.main(args) == 0  # accepted now
    assert "1 baseline-accepted" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Layer 2: the trace-time contracts (the PR-4/5 dispatch story, pinned).
# ---------------------------------------------------------------------------


def test_lockstep_contracts_hold():
    """Pin: lockstep_run_traced stages as ONE scan of length R with zero
    host callbacks, in the jaxpr and in the compiled HLO."""
    results = {r.name: r for r in contracts.check_lockstep_contracts()}
    assert results["lockstep-scan-fusion"].ok, results
    assert results["lockstep-no-host-callbacks"].ok, results


def test_lag_contracts_hold():
    results = {r.name: r for r in contracts.check_lag_contracts()}
    assert results["lag-scan-fusion"].ok, results
    assert results["lag-no-host-callbacks"].ok, results


def test_engine_donation_aliases_buffers():
    """Pin: the engine's donated fused jits carry donor annotations in the
    lowered module AND input-output aliasing in the compiled executable."""
    results = contracts.check_engine_donation()
    assert len(results) == 3
    for r in results:
        assert r.ok, r.format()


def test_sweep_bucket_cache_sharing():
    (r,) = contracts.check_sweep_bucket_sharing()
    assert r.ok, r.format()


def test_callback_scan_helpers_detect_seeded_callback():
    """The IR helpers are not vacuous: a pure_callback IS detected."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((), jnp.float32), x)

    jaxpr = jax.make_jaxpr(f)(jnp.float32(0.0))
    assert contracts.callback_primitives(jaxpr)
    hlo = jax.jit(f).lower(jnp.float32(0.0)).compile().as_text()
    assert contracts.hlo_callback_sites(hlo)
