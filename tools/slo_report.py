#!/usr/bin/env python
"""Grade a finished run's telemetry against an SLO spec.

The live engine (``can_tpu/obs/slo.py``) watches the bus and pages on
fast burn; this tool is the SAME arithmetic replayed offline over a
telemetry artifact — a per-host JSONL, a ``--telemetry-dir``, an
incident bundle's ring dump, or a FleetCollector snapshot — clocked by
the events' own timestamps, so a violation here is exactly the alert
the live run would have fired.  For a collector snapshot the manifest's
MEASURED clock offsets are applied before the merge (obs/join.py), so
this replay reproduces the live collector's global burn sequence
bit-identically — the fleet observability plane's correctness oracle.
Plain run dirs are graded on raw timestamps: post-hoc skew ESTIMATION
is deliberately off here (a legitimately staggered start is not clock
skew, and grading must never re-time events on a guess).

    python tools/slo_report.py runs/exp1/ --spec slo_spec.json
    python tools/slo_report.py runs/exp1/telemetry.host0.jsonl \
        --spec slo_spec.json --json
    python tools/slo_report.py runs/exp1/incidents/incident-...-h0-.../ \
        --spec slo_spec.json        # grade a bundle's last-N-events ring

Two violation classes (see ``obs.slo.grade_events``):

* fast burn — an objective's burn rate met ``burn_alert`` on EVERY
  window at some evaluation (the pager moment);
* budget — the run's total bad fraction exceeded the error budget even
  though no single window alerted (slow leak).

Exit codes (CI gates on them):
  0  every graded objective within budget, no fast burns
  1  at least one violation (each printed naming objective + window)
  2  usage error: missing/invalid spec, unreadable target, no events

Pure host-side file reading — no JAX import, safe on any machine the
artifact was copied to (same contract as tools/telemetry_report.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from can_tpu.obs.join import (  # noqa: E402
    load_joined_events,
    resolve_telemetry_source,
)
from can_tpu.obs.slo import grade_events, load_slo_spec  # noqa: E402


def resolve_paths(target: str) -> list:
    """Telemetry file -> [it]; run dir / collector snapshot -> its
    per-host files; incident bundle dir (has incident.json) -> its ring
    dump.  Thin alias of the shared ``obs/join.py`` resolution, kept
    for the tool's public surface."""
    return resolve_telemetry_source(target)[0]


def _fmt_burns(worst: dict) -> str:
    if not worst:
        return "-"
    return " ".join(f"[{w}s]={b:g}" for w, b in worst.items())


def format_grade(grade: dict, *, spec_path: str, target: str) -> str:
    lines = [f"# slo report — {target} vs {spec_path}: "
             f"{grade['events']} events, {grade['evaluations']} "
             f"evaluations, "
             f"{'VIOLATED' if grade['violations'] else 'PASS'}"]
    for name, row in grade["objectives"].items():
        if not row["samples"]:
            lines.append(f"objective {name}: no samples (not graded)")
            continue
        status = "ok"
        if any(v["objective"] == name for v in grade["violations"]):
            status = "VIOLATED"
        elif not row["graded"]:
            status = "under min_samples (not graded)"
        lines.append(
            f"objective {name}: samples={row['samples']} "
            f"good={row['good']} bad={row['bad']} "
            f"bad_frac={row['bad_frac']:g} budget={row['budget']:g} "
            f"worst_burn {_fmt_burns(row['worst_burn'])}  {status}")
    for v in grade["violations"]:
        lines.append(f"VIOLATION {v['objective']} (window {v['window']}): "
                     f"{v['detail']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("target", help="telemetry JSONL file, a run dir of "
                                  "telemetry.host*.jsonl, or an incident "
                                  "bundle directory")
    p.add_argument("--spec", required=True,
                   help="SLO spec JSON (see slo_spec.json)")
    p.add_argument("--json", action="store_true",
                   help="emit the grade dict as JSON instead of a table")
    args = p.parse_args(argv)
    try:
        spec = load_slo_spec(args.spec)
    except (OSError, ValueError) as e:
        print(f"slo_report: bad spec: {e}", file=sys.stderr)
        return 2
    try:
        # estimate=False: snapshot manifests' MEASURED offsets apply,
        # but plain run dirs are never re-timed on a guess
        events, _, _ = load_joined_events(args.target, estimate=False)
    except SystemExit as e:  # usage-class failure: exit 2, not 1
        print(f"slo_report: {e}", file=sys.stderr)
        return 2
    except OSError as e:
        print(f"slo_report: cannot read {args.target}: {e}",
              file=sys.stderr)
        return 2
    if not events:
        print(f"slo_report: no telemetry events in {args.target}",
              file=sys.stderr)
        return 2
    grade = grade_events(events, spec)
    if args.json:
        print(json.dumps({"target": args.target, "spec": args.spec,
                          **grade}))
    else:
        print(format_grade(grade, spec_path=args.spec, target=args.target))
    return 1 if grade["violations"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
