"""``calibrate_falcon_h1.py`` for the short-convolution model's cell: read, on
the chip and in one process, the numbers ``correct`` compares, one launch a
seed and no window.  Sound runs over many seeds; on the first
``--control-seeds`` of them also the controls that are a different
COMPUTATION, each in the program's place on the same sequences (the
reference with 8-bit weights; with every activation rounded to one mantissa
bit fewer than bfloat16; the gate ``B`` left out; the gate ``C`` left out;
q/k norm left out; top-4 weights unnormalised; the bias added to the
weights; one held expert zeroed), and on the first ``--program-seeds`` the
PROGRAM broken underneath, one run each:

* ``tail_late``: the convolution's tail gathered one position late;
* ``tail_after_padding``: the tail gathered at the bucket's end, behind the
  padding, and not at the prompt's own length;
* ``late_write``: decode writes its key and value one position late.

The limits in the configuration file were set from this tool's output
(PERF.md section 2).

    python3 -m benchmark.tools.calibrate_lfm2_moe --workload serve-lfm2-chat-closed \\
        --seeds 101,102,103,104,105,106 --control-seeds 3 --program-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark.tools.calibrate_falcon_h1 import tail_late
from benchmark.tools.calibrate_lm import late_write

CONTROLS = ("int8", "bf16-1", "variant:no_gate_b", "variant:no_gate_c",
            "variant:no_qk_norm", "variant:unnormalised_topk",
            "variant:bias_in_weights", "variant:expert_zeroed")


def tail_after_padding(programs):
    """Break the timed path: the convolution's tail is gathered as if every
    prompt filled its bucket (the inputs at the padding's last positions)."""
    import jax.numpy as jnp

    from can_tpu.ops import ssm

    sound = ssm.conv_tail
    ssm.conv_tail = lambda x, lengths, width: sound(
        x, jnp.full_like(lengths, x.shape[1]), width)
    tail_after_padding.undo = lambda: setattr(ssm, "conv_tail", sound)


PROGRAM_BREAKS = {"tail_late": tail_late,
                  "tail_after_padding": tail_after_padding,
                  "late_write": late_write}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--program-seeds", type=int, default=3)
    p.add_argument("--breaks", default=",".join(PROGRAM_BREAKS))
    args = p.parse_args(argv)
    import gc

    from benchmark import run

    seeds = [int(s) for s in args.seeds.split(",")]
    breaks = [b for b in args.breaks.split(",") if b]
    rows = []
    for i, seed in enumerate(seeds):
        gc.collect()    # the run before held 7.5 GB of weights on the device
        line = run.run_cell(args.workload, seed, 0.0, False,
                            control_modes=CONTROLS if i < args.control_seeds else (),
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
