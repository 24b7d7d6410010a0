"""Read, on the chip and in one process, the numbers ``correct`` compares in
a language-model cell: one launch a seed and no window.  Sound runs over
many seeds; on the first ``--control-seeds`` of them also the controls, each
in the program's place on the same sequences (the reference with 8-bit
weights; a window one too wide; rotary embedding on the full layer too;
top-k weights left unnormalised; one held expert zeroed), and two runs of the
PROGRAM broken underneath: its decode step writing the cache one position
late, and one prefill slice writing its cache rows at the wrong slots (a
slice that holds none of the requests whose logits are compared: the
generated ids have to catch it).  The limits in the configuration file were
set from this tool's output (PERF.md section 2).

    python3 -m benchmark.tools.calibrate_lm --workload serve-exaone-chat-closed \\
        --seeds 101,102,103,104,105,106 --control-seeds 3 --program-seeds 1
"""

from __future__ import annotations

import argparse
import json
import sys

BROKEN_SLICE = 3   # of 8: slots 23-31, none of them probed for its logits
CONTROLS = ("int8", "variant:window+1", "variant:rope_on_full",
            "variant:unnormalised_topk", "variant:expert_zeroed")


def late_write(programs):
    """Break the timed path: every decode step writes its key and value one
    slot after the token's own (the last slot wraps)."""
    from can_tpu.ops import attention

    sound = attention.write_slot
    attention.write_slot = lambda cache, new, slot: sound(
        cache, new, (slot + 1) % cache.shape[2])
    late_write.undo = lambda: setattr(attention, "write_slot", sound)


def slice_offset(index: int):
    """Break the timed path: prefill slice ``index`` of every launch writes
    its cache rows one slot early (over the slice before it; its own last
    slot stays empty).  Prefill's own logits and first tokens do not change:
    what the slots decode from does."""
    def breaker(programs):
        sound = programs.prefill_slice

        def early(params, batch, cache, start):
            at = index * batch["tokens"].shape[0]
            return sound(params, batch, cache,
                         start - (start == at).astype(start.dtype))

        programs.prefill_slice = early
    return breaker


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--program-seeds", type=int, default=1,
                   help="how many seeds also run the PROGRAM broken (its "
                        "decode writing late; prefill slice BROKEN_SLICE "
                        "writing its rows a slot early)")
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
            off = run.run_cell(args.workload, seed, 0.0, False,
                               break_path=slice_offset(BROKEN_SLICE),
                               first_steps_only=True)
            row["control"]["program:slice_offset"] = off["numbers"]
        rows.append(row)
        print("[calibrate] " + json.dumps(row), flush=True)
    for k in sorted(rows[0]["sound"]):
        line = (f"[summary] {k}: sound max {max(r['sound'][k] for r in rows):.6g} "
                f"min {min(r['sound'][k] for r in rows):.6g} over {len(rows)} seeds")
        modes = sorted({m for r in rows for m in r["control"]})
        for mode in modes:
            vals = [r["control"][mode][k] for r in rows
                    if mode in r["control"] and k in r["control"][mode]]
            if vals:
                line += f"; {mode} min {min(vals):.6g} over {len(vals)}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
