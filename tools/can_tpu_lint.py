#!/usr/bin/env python
"""can_tpu source linter CLI (can_tpu/analysis/source_lint.py rules).

Usage::

    python tools/can_tpu_lint.py                  # lint the tree
    python tools/can_tpu_lint.py can_tpu/serve    # subset of paths
    python tools/can_tpu_lint.py --rules SWALLOW,LOCKHELD
    python tools/can_tpu_lint.py --json           # machine-readable
    python tools/can_tpu_lint.py --list-rules

Exit codes: 0 = clean (zero unbaselined findings AND zero stale baseline
entries), 1 = findings / stale baseline, 2 = usage error (bad pragma,
unknown rule, unreadable baseline or source).

The committed baseline (``tools/lint_baseline.json``) carries findings
the tree accepts without a source pragma; a baselined finding that no
longer fires FAILS the run (baselines can't rot) — fix it by deleting
the entry.  In-source suppression: ``# can-tpu-lint:
disable=RULE(reason)`` on the finding's line or the line above.

No jax import — this runs in milliseconds anywhere.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from can_tpu.analysis import source_lint as sl  # noqa: E402

DEFAULT_BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="JAX/concurrency-aware linter for the can_tpu tree")
    ap.add_argument("paths", nargs="*",
                    help="files or directories (default: the library "
                         "and tools)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule subset")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true",
                    help="report raw findings without baseline matching")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, doc in sorted(sl.RULES.items()):
            print(f"{rule:9s} {doc}")
        return 0

    paths = None
    if args.paths:
        paths = []
        for p in args.paths:
            if os.path.isdir(p):
                for dirpath, _dirs, files in os.walk(p):
                    paths.extend(os.path.join(dirpath, f)
                                 for f in sorted(files)
                                 if f.endswith(".py"))
            else:
                paths.append(p)
    rules = args.rules.split(",") if args.rules else None

    try:
        findings, suppressed = sl.lint_paths(REPO, paths, rules=rules)
        if args.no_baseline:
            new, stale = findings, []
        elif paths is not None or rules is not None:
            # a subset run hasn't scanned the files/rules the baseline's
            # other entries live in — matching against it would report
            # false staleness; report raw findings instead
            print("[can_tpu_lint] subset run: baseline matching skipped",
                  file=sys.stderr)
            new, stale = findings, []
        else:
            baseline = sl.load_baseline(args.baseline)
            new, stale = sl.check_baseline(findings, baseline)
    except sl.LintUsageError as e:
        print(f"can_tpu_lint error: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({
            "findings": [vars(f) for f in new],
            "stale_baseline": [list(fp) for fp in stale],
            "suppressed": suppressed,
        }, indent=1))
    else:
        for f in new:
            print(f.render())
        for fp in stale:
            print(f"stale baseline entry (finding no longer fires — "
                  f"delete it from {os.path.relpath(args.baseline, REPO)}):"
                  f" {fp[0]} [{fp[1]}] {fp[2]!r}")
        ok = not new and not stale
        print(f"can_tpu_lint: {len(new)} finding(s), {len(stale)} stale "
              f"baseline entr(ies), {suppressed} pragma-suppressed — "
              f"{'OK' if ok else 'FAIL'}")
    return 0 if not new and not stale else 1


if __name__ == "__main__":
    raise SystemExit(main())
