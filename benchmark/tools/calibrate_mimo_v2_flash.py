"""``calibrate_lfm2_moe.py`` for MiMo-V2-Flash's cell: read, on the chip and in
one process, the numbers ``correct`` compares, one launch a seed and no
window.  Sound runs over many seeds; on the first ``--control-seeds`` of them
also the controls that are a different COMPUTATION, each in the program's
place on the same sequences (the reference with 8-bit weights; with every
activation rounded to one mantissa bit fewer than bfloat16; the sink left
out; the sink given a value row; the window one shorter and one longer; a
full layer grouped as a window layer is; rotary over the whole head; the two
thetas swapped; the value scale left out; top-8 weights unnormalised; the
bias added to the weights; one held expert zeroed), and on the first
``--program-seeds`` the PROGRAM broken underneath, one run each:

* ``ring_rolled``: a prefill's ring entry handed over one slot on (every key
  of a window layer in its neighbour's slot, so that the first decode steps
  overwrite the newest keys and not the oldest);
* ``late_write``: decode writes its key and value one position late.

The limits in the configuration file were set from this tool's output
(PERF.md section 2).

    python3 -m benchmark.tools.calibrate_mimo_v2_flash --workload serve-mimo-doc8k-closed \\
        --seeds 101,102,103 --control-seeds 3 --program-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark.tools.calibrate_lm import late_write

CONTROLS = ("int8", "bf16-1", "variant:no_sink", "variant:sink_value",
            "variant:window_minus_1", "variant:window_plus_1",
            "variant:full_groups_of_window", "variant:rope_whole_head",
            "variant:thetas_swapped", "variant:no_value_scale",
            "variant:unnormalised_topk", "variant:bias_in_weights",
            "variant:expert_zeroed")


def ring_rolled(programs):
    """Break the timed path: a window layer's prefill hands its ring over
    rolled by one slot."""
    import jax.numpy as jnp

    from can_tpu.ops import attention

    sound = attention.ring_entry

    def rolled(*args, **kw):
        return {n: jnp.roll(a, 1, axis=2) for n, a in sound(*args, **kw).items()}

    attention.ring_entry = rolled
    ring_rolled.undo = lambda: setattr(attention, "ring_entry", sound)


PROGRAM_BREAKS = {"ring_rolled": ring_rolled, "late_write": late_write}


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
        gc.collect()    # the run before held 6.9 GB of weights on the device
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
