"""graftlint — the repo's AST-rule static-analysis engine (ISSUE 15).

Three generations of review-hardening caught the same bug classes by
hand: data-dependent op-scans sneaking into kernels, host syncs inside
jitted code, blocking I/O under the scheduler condition variable, and
metric/clock discipline drift. graftlint pins those invariants as
auto-discovering AST rules instead of per-directory module-count pins
someone forgets to bump.

Entry points:

* ``python -m tools.graftlint [paths...]`` — the CLI (``scripts/lint.sh``)
* :class:`tools.graftlint.engine.Linter` — the library API
  (``tests/test_lint.py``)

Rule catalog + suppression/baseline workflow: docs/static-analysis.md.
"""

from tools.graftlint.engine import Baseline, Finding, Linter, Result

__all__ = ["Baseline", "Finding", "Linter", "Result"]
