"""``python -m tools.graftlint`` — the CLI (scripts/lint.sh wraps it).

Exit codes: 0 clean, 1 unsuppressed findings, 2 usage error.
"""

from __future__ import annotations

import argparse
import os
import sys

from tools.graftlint.engine import (DEFAULT_BASELINE_RELPATH, Baseline,
                                    Linter)
from tools.graftlint.report import render_json, render_text
from tools.graftlint.rules import rule_ids

DEFAULT_PATHS = ["titan_tpu", "tests"]
DEFAULT_BASELINE = DEFAULT_BASELINE_RELPATH


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="AST-rule static analysis for the titan_tpu tree "
                    "(rule catalog: docs/static-analysis.md)")
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/dirs to lint (default: "
                         f"{' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--root", default=os.getcwd(),
                    help="repo root scopes/baseline resolve against "
                         "(default: cwd)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids/aliases to run "
                         "(default: all)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: {DEFAULT_BASELINE} "
                         "under --root when present; 'none' disables)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="re-grandfather: write every current finding "
                         "to the baseline file and exit 0")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="include suppressed findings in text output")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    ids = rule_ids()
    if args.list_rules:
        from tools.graftlint.rules import default_rules
        for cls in default_rules():
            print(f"{cls.alias:>3} {cls.id:<16} {cls.description}")
        return 0

    root = os.path.abspath(args.root)
    baseline_path = args.baseline
    if baseline_path is None:
        cand = os.path.join(root, DEFAULT_BASELINE)
        baseline_path = cand if os.path.exists(cand) else "none"
    if args.write_baseline or baseline_path == "none":
        # regeneration re-grandfathers from scratch — the target not
        # existing yet is the bootstrap case, not an error
        baseline = Baseline()
    elif not os.path.exists(baseline_path):
        print(f"graftlint: baseline file not found: {baseline_path} "
              "(pass --baseline none to lint without one)",
              file=sys.stderr)
        return 2
    else:
        baseline = Baseline.load(baseline_path)

    rules = None
    if args.rules:
        wanted = set()
        for tok in args.rules.split(","):
            tok = tok.strip()
            if tok not in ids:
                print(f"graftlint: unknown rule {tok!r} "
                      f"(known: {', '.join(sorted(ids))})",
                      file=sys.stderr)
                return 2
            wanted.add(ids[tok])
        from tools.graftlint.rules import default_rules
        rules = [c for c in default_rules() if c.id in wanted]

    paths = args.paths or [p for p in DEFAULT_PATHS
                           if os.path.exists(os.path.join(root, p))]
    if not paths:
        print("graftlint: nothing to lint", file=sys.stderr)
        return 2

    linter = Linter(root=root, rules=rules, baseline=baseline)
    result = linter.run(paths)

    if args.write_baseline:
        target = baseline_path if baseline_path != "none" \
            else os.path.join(root, DEFAULT_BASELINE)
        for f in result.findings:       # re-grandfather everything
            if f.suppressed == "baseline":
                f.suppressed = None
        Baseline.from_findings(result.findings).write(target)
        print(f"graftlint: wrote {target} "
              f"({len(result.unsuppressed)} entr(ies))")
        return 0

    print(render_json(result, root) if args.json
          else render_text(result, show_suppressed=args.show_suppressed))
    return 1 if result.unsuppressed else 0


if __name__ == "__main__":
    sys.exit(main())
