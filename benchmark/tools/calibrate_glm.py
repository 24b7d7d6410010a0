"""``calibrate_lm.py`` for the latent-attention cell: read, on the chip and
in one process, the numbers ``correct`` compares, one launch a seed and no
window.  Sound runs over many seeds; on the first ``--control-seeds`` of
them also the controls, each in the program's place on the same sequences
(the reference with 8-bit weights; the softmax scale 1/sqrt(qk_nope); the
latent's norm left out; rotary embedding on the no-rotary part too; top-k
weights left unnormalised; one expert zeroed), and on the first
``--program-seeds`` the PROGRAM broken underneath: its decode step writing
the latent one position late and, with ``--slice-offset``, one prefill slice
writing its cache rows a slot early.  The limits in the configuration file
were set from this tool's output and from the cell's own runs (PERF.md
section 2).

    python3 -m benchmark.tools.calibrate_glm --workload serve-glm-agent16k-closed \\
        --seeds 101,102 --control-seeds 1 --program-seeds 1
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark.tools.calibrate_lm import slice_offset

BROKEN_SLICE = 3   # of 8: slots 6-7; slot 6's generated ids are compared
CONTROLS = ("int8", "variant:scale_nope", "variant:no_kv_norm",
            "variant:rope_on_nope", "variant:unnormalised_topk",
            "variant:expert_zeroed")


def late_write(programs):
    """Break the timed path: every decode step writes its latent and its
    rotary key one position after the token's own (the last wraps)."""
    from can_tpu.ops import attention

    sound = attention.write_row
    attention.write_row = lambda cache, new, pos: sound(
        cache, new, (pos + 1) % cache.shape[1])
    late_write.undo = lambda: setattr(attention, "write_row", sound)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=1)
    p.add_argument("--program-seeds", type=int, default=1)
    p.add_argument("--slice-offset", action="store_true")
    args = p.parse_args(argv)
    from benchmark import run

    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        line = run.run_cell(args.workload, seed, 0.0, False,
                            control_modes=CONTROLS if i < args.control_seeds else (),
                            first_steps_only=True)
        row = {"seed": seed, "sound": line["numbers"], "correct": line["correct"],
               "control": dict(line.get("control") or {}),
               "memory_peak_bytes": line["device"].get("memory_peak_bytes")}
        if i < args.program_seeds:
            try:
                late = run.run_cell(args.workload, seed, 0.0, False,
                                    break_path=late_write, first_steps_only=True)
            finally:
                late_write.undo()
            row["control"]["program:late_write"] = late["numbers"]
            if args.slice_offset:
                off = run.run_cell(args.workload, seed, 0.0, False,
                                   break_path=slice_offset(BROKEN_SLICE),
                                   first_steps_only=True)
                row["control"]["program:slice_offset"] = off["numbers"]
        rows.append(row)
        print("[calibrate] " + json.dumps(row), flush=True)
    for k in sorted(rows[0]["sound"]):
        line = (f"[summary] {k}: sound max {max(r['sound'][k] for r in rows):.6g} "
                f"min {min(r['sound'][k] for r in rows):.6g} over {len(rows)} seeds")
        for mode in sorted({m for r in rows for m in r["control"]}):
            vals = [r["control"][mode][k] for r in rows
                    if mode in r["control"] and k in r["control"][mode]]
            if vals:
                line += f"; {mode} min {min(vals):.6g} over {len(vals)}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
