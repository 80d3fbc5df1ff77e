"""jaxlint CLI — ``python -m tools.jaxlint <paths...>``.

Exit status: 0 when every file is clean (or every finding is waived
with a reason), 1 when there are findings, 2 on usage errors.  This is
the contract ``tests/test_lint.py`` gates tier-1 on.
"""

from __future__ import annotations

import argparse
from typing import List, Optional

from .linter import RULES, lint_paths


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.jaxlint",
        description="Tracing-safety & dtype-discipline static analyzer "
                    "for the apex_tpu stack (rules J001-J007; see "
                    "docs/jaxlint.md).")
    ap.add_argument("paths", nargs="*",
                    help="files or directory trees to lint "
                         "(e.g. apex_tpu examples tools)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--select", metavar="CODES", default=None,
                    help="comma-separated rule codes to report "
                         "(default: all)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for code, desc in sorted(RULES.items()):
            print(f"{code}  {desc}")
        return 0
    if not args.paths:
        ap.print_usage()
        print("error: no paths given (and --list-rules not requested)")
        return 2

    try:
        findings = lint_paths(args.paths)
    except FileNotFoundError as e:
        print(f"error: {e}")
        return 2
    if args.select:
        keep = {c.strip() for c in args.select.split(",")}
        findings = [f for f in findings if f.rule in keep]
    for f in findings:
        print(f.render())
    if findings:
        by_rule: dict = {}
        for f in findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        summary = ", ".join(f"{k}: {v}" for k, v in sorted(by_rule.items()))
        n_adv = sum(1 for f in findings if f.advisory)
        print(f"jaxlint: {len(findings)} finding(s) ({summary})")
        if n_adv == len(findings):
            # Advisory-only (e.g. J011 fusion advice): reported but not
            # a failure — the code is correct, just slower than the
            # fused path the message names.
            print(f"jaxlint: all {n_adv} advisory — not failing")
            return 0
        return 1
    print("jaxlint: clean")
    return 0
