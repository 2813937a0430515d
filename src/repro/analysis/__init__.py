"""Static analysis for the repro engine: AST lint + trace-time contracts.

Two layers behind one CLI (``python -m repro analyze``):

* :mod:`repro.analysis.lint` -- rule registry + AST lint enforcing the
  purity / donation / mesh / x64 invariants on source.
* :mod:`repro.analysis.contracts` -- lowers the traced entry points with
  abstract inputs and asserts the scan-fusion / no-callback / donation /
  bucket-cache contracts from the jaxpr and compiled HLO.
* :mod:`repro.analysis.findings` -- findings + the checked-in baseline
  (``ANALYSIS_BASELINE.json``) that separates accepted debt from
  regressions.

Extension guide: ``docs/static-analysis.md`` (executed by
tests/test_docs.py).
"""

from repro.analysis.findings import Baseline, Finding, sort_findings
from repro.analysis.lint import (Rule, available_rules, default_rules,
                                 get_rule, lint_paths, lint_project,
                                 lint_source, parse_project, register_rule)

__all__ = [
    "Baseline", "Finding", "Rule", "available_rules", "default_rules",
    "get_rule", "lint_paths", "lint_project", "lint_source",
    "parse_project", "register_rule", "sort_findings",
]
