"""Read, on the chip and in one process, the numbers ``correct`` compares:
from sound runs of the program over many seeds, and from the control (for a
training cell the reference in a lower precision in the program's place, for
a serving cell the program's own int8 path).  The limits in the
configuration files were set from this tool's output (PERF.md section 2).

    python3 -m benchmark.tools.calibrate --workload train-sha-varres \\
        --seeds 101,102,103 --first-steps-only --control int8,bf16params
    python3 -m benchmark.tools.calibrate --workload serve-shb-closed \\
        --seeds 101,102,103 --seconds 3 --control int8
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--control", default="")
    p.add_argument("--control-seeds", type=int, default=3,
                   help="how many of the seeds also read the control")
    p.add_argument("--first-steps-only", action="store_true",
                   help="training: the first steps and the comparison, no window")
    args = p.parse_args(argv)
    from benchmark import run

    modes = tuple(m for m in args.control.split(",") if m)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        with_control = i < args.control_seeds
        # a control named after a serve dtype ("program:int8") switches the
        # program's own path on; any other is a mode of the reference
        ref_modes = tuple(m for m in modes if not m.startswith("program:"))
        line = run.run_cell(args.workload, seed, args.seconds, False,
                            control_modes=ref_modes if with_control else (),
                            first_steps_only=args.first_steps_only)
        row = {"seed": seed, "sound": line["numbers"], "correct": line["correct"],
               "metrics": line["metrics"], "control": dict(line.get("control") or {})}
        if with_control:
            for mode in modes:
                if mode.startswith("program:"):
                    c = run.run_cell(args.workload, seed, args.seconds, False,
                                     serve_dtype=mode.split(":", 1)[1])
                    row["control"][mode] = c["numbers"]
        rows.append(row)
        print("[calibrate] " + json.dumps(rows[-1]), flush=True)
    keys = sorted(rows[0]["sound"])
    for k in keys:
        sound = max(r["sound"][k] for r in rows)
        line = f"[summary] {k}: sound max {sound:.6g} over {len(rows)} seeds"
        for mode in modes:
            vals = [r["control"][mode][k] for r in rows
                    if r.get("control") and mode in r["control"] and k in r["control"][mode]]
            if vals:
                line += f"; control {mode} min {min(vals):.6g} over {len(vals)}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
