"""``calibrate_mimo_v2_flash.py`` for LongCat-Flash's cell: read, on the chip
and in one process, the numbers ``correct`` compares, one launch a seed and
no window.  Sound runs over many seeds; on the first ``--control-seeds`` of
them also the controls that are a different COMPUTATION, each in the
program's place on the same sequences (the reference with 8-bit weights; with
every activation rounded to one mantissa bit fewer than bfloat16; one held
expert zeroed; the identity experts' term left out; the top-12 weights
normalised; sigmoid scores in place of the softmax; the factor 6 left out;
either latent's factor left out; the expert layer fed the second sublayer's
stream), and on the first ``--program-seeds`` the PROGRAM broken underneath,
one run each:

* ``leaves_swapped``: a layer's second sublayer reads the first one's cache
  leaves in every decode step;
* ``late_write``: decode writes its latent and its rotary key one position
  late (``calibrate_glm.late_write``: the two models share the layer).

The limits in the configuration file were set from this tool's output
(PERF.md section 2).

    python3 -m benchmark.tools.calibrate_longcat_flash \\
        --workload serve-longcat-reason1k-closed \\
        --seeds 101,102,103 --control-seeds 3 --program-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark.tools.calibrate_glm import late_write

CONTROLS = ("int8", "bf16-1", "variant:expert_zeroed", "variant:no_zero_term",
            "variant:normalised_topk", "variant:sigmoid_scoring",
            "variant:no_scale_factor", "variant:no_q_scale",
            "variant:no_kv_scale", "variant:sequential_block")


def leaves_swapped(programs):
    """Break the timed path: in every decode step a layer's second sublayer
    attends over the FIRST sublayer's leaves (its own row written into
    them)."""
    from can_tpu.models import longcat_flash as model
    from can_tpu.ops import cache_layout

    sound = model._decode_block
    first, second = cache_layout.latent_leaves(0), cache_layout.latent_leaves(1)

    def swapped(layer, kind, x, entry, *rest):
        entry = dict(entry, **{mine: entry[other]
                               for mine, other in zip(second, first)})
        return sound(layer, kind, x, entry, *rest)

    model._decode_block = swapped
    leaves_swapped.undo = lambda: setattr(model, "_decode_block", sound)


PROGRAM_BREAKS = {"leaves_swapped": leaves_swapped, "late_write": late_write}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--program-seeds", type=int, default=3)
    p.add_argument("--controls", default=",".join(CONTROLS))
    p.add_argument("--breaks", default=",".join(PROGRAM_BREAKS))
    args = p.parse_args(argv)
    import gc

    from benchmark import run

    seeds = [int(s) for s in args.seeds.split(",")]
    controls = tuple(c for c in args.controls.split(",") if c)
    breaks = [b for b in args.breaks.split(",") if b]
    rows = []
    for i, seed in enumerate(seeds):
        gc.collect()    # the run before held 10.3 GB of weights on the device
        line = run.run_cell(args.workload, seed, 0.0, False,
                            control_modes=controls if i < args.control_seeds else (),
                            first_steps_only=True)
        row = {"seed": seed, "sound": line["numbers"], "correct": line["correct"],
               "control": dict(line.get("control") or {}),
               "memory_peak_bytes": line["device"].get("memory_peak_bytes")}
        if i < args.program_seeds:
            for name in breaks:
                breaker = PROGRAM_BREAKS[name]
                gc.collect()
                try:
                    broken = run.run_cell(args.workload, seed, 0.0, False,
                                          break_path=breaker,
                                          first_steps_only=True)
                finally:
                    breaker.undo()
                row["control"]["program:" + name] = broken["numbers"]
        rows.append(row)
        print("[calibrate] " + json.dumps(row), flush=True)
    for k in sorted(rows[0]["sound"]):
        line = (f"[summary] {k}: sound max {max(r['sound'][k] for r in rows):.6g} "
                f"min {min(r['sound'][k] for r in rows):.6g} over {len(rows)} seeds")
        for mode in sorted({m for r in rows for m in r["control"]}):
            vals = [r["control"][mode][k] for r in rows
                    if mode in r["control"] and k in r["control"][mode]]
            if vals:
                line += (f"; {mode} min {min(vals):.6g} max {max(vals):.6g} "
                         f"over {len(vals)}")
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
