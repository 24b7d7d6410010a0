#!/usr/bin/env python
"""Commit-ready plan-space ablation artifact (PLAN_ABLATION_r*.json).

Runs ``bench_suite.bench_plan_space`` — the SIMULATED sweep over the
batch planner's candidate space (plan mode x launch pricing x batch) on
the suite's varres distribution under the v5e HBM cap — and writes one
JSON document with the per-candidate records plus a headline block
comparing the r5 shipped plan (legacy mode, 2.0 Mpx launch pricing:
30.67% schedule overhead at b16) against the round-8 cost-model planner
at device-regime pricing, which is the configuration the suite's quoted
steady-state compute number actually runs in.

Host-only and deterministic (the plan is a pure function of the shape
histogram and the planner config): the overhead numbers in the artifact
reproduce bit-exactly on any machine; only the ``plan_s`` timing fields
are host-dependent (median-of-k with recorded spread).

    python tools/plan_ablation.py --out PLAN_ABLATION_r08.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def headline(records: list) -> dict:
    """The acceptance comparison: b16 varres, same max_launch_px cap."""
    def find(mode, mpx):
        for r in records:
            if (r["batch"] == 16 and r["plan_mode"] == mode
                    and r["launch_cost_mpx"] == mpx):
                return r
        raise SystemExit(f"sweep missing b16 {mode} L={mpx}")

    from can_tpu.cli.common import DEVICE_LAUNCH_COST_MPX

    baseline = find("legacy", 2.0)   # == the r5 sweep's shipped plan
    tuned = find("cost", DEVICE_LAUNCH_COST_MPX)
    same_l = find("cost", 2.0)       # search contribution, pricing held
    return {
        "config": "b16 varres, max_buckets=24, v5e HBM cap "
                  f"({baseline['max_launch_mpx']} Mpx/launch)",
        "baseline_legacy_2mpx_pricing": {
            "schedule_overhead": baseline["value"],
            "padding_overhead": baseline["padding_overhead"],
            "programs": baseline["programs"],
        },
        "cost_planner_same_pricing": {
            "schedule_overhead": same_l["value"],
            "padding_overhead": same_l["padding_overhead"],
            "programs": same_l["programs"],
            "note": "search contribution alone: boundary placement + "
                    "exact menus + packing, launch price held at the "
                    "baseline's 2.0 Mpx — the model still trades pixels "
                    "for launches at that price",
        },
        "cost_planner_device_pricing": {
            "schedule_overhead": tuned["value"],
            "padding_overhead": tuned["padding_overhead"],
            "programs": tuned["programs"],
            "note": "the regime the quoted steady-state compute number "
                    "runs in (launches overlapped with compute): the "
                    "round-8 bench default",
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="PLAN_ABLATION_r08.json")
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--round", type=int, default=8, dest="round_no")
    args = p.parse_args(argv)

    from bench_suite import bench_plan_space

    records = bench_plan_space(repeats=args.repeats)
    doc = {
        "round": args.round_no,
        "note": "Simulated plan-space sweep (host-only, deterministic): "
                "the batch planner's schedule for the bench varres "
                "distribution under the v5e per-launch HBM cap, legacy "
                "vs cost-model planner across launch pricings. "
                "Overheads are exact properties of the emitted schedule; "
                "the b16 legacy L=2.0 row reproduces the r5 sweep's "
                "0.3067 bit-for-bit. plan_s fields are this host's plan "
                "build time (median of repeats, spread recorded).",
        "headline": headline(records),
        "results": records,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"# wrote {args.out}")
    print(json.dumps(doc["headline"], indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
