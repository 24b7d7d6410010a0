"""``calibrate_falcon_h1.py`` for the power-retention model's cell: read, on
the chip and in one process, the numbers ``correct`` compares, one launch a
seed and no window.  Sound runs over many seeds; on the first
``--control-seeds`` of them also the controls that are a different
COMPUTATION, each in the program's place on the same sequences (the
reference with 8-bit weights; the gate left out, ``g`` = 1; the normaliser
left out; the scale left out of the power, which the normaliser divides out
again, so that one reads like the yardstick; the head norm left out; rotary
left out), and on the first ``--program-seeds`` the PROGRAM broken
underneath, one run each:

* ``state_bf16``: the cache keeps ``S`` and ``z`` in bfloat16 (written rounded
  by the prefill, rounded again by every decode step): the nearest precision
  below the float32 the configuration states;
* ``state_after_padding``: the prompt form is told every prompt fills its
  bucket, so the state handed to decode is the bucket's end's, not the
  prompt's;
* ``update_late``: a decode step answers from the state BEFORE its own
  token's update (the update still happens: the next step sees it).

The limits in the configuration file were set from this tool's output
(PERF.md section 2).

    python3 -m benchmark.tools.calibrate_brumby --workload serve-brumby-gen512-closed \\
        --seeds 101,102,103,104,105,106 --control-seeds 3 --program-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys

CONTROLS = ("int8", "variant:no_gate", "variant:no_normaliser",
            "variant:no_scale", "variant:no_head_norm", "variant:no_rope")


def state_bf16(programs):
    """Break the timed path: every layer's ``S`` and ``z`` are allocated in
    bfloat16 (the layout is the programs' own copy; the step keeps the dtype
    it is handed)."""
    programs.cache_layout = tuple(
        spec._replace(dtypes=tuple((n, "bfloat16") for n, _ in spec.dtypes))
        for spec in programs.cache_layout)
    state_bf16.undo = lambda: None


def state_after_padding(programs):
    """Break the timed path: the prompt form is told every prompt fills its
    bucket, so it advances over the padding."""
    import jax.numpy as jnp

    from can_tpu.ops import retention

    sound = retention.power_retention_chunked
    retention.power_retention_chunked = lambda q, k, v, log_g, lengths, **kw: sound(
        q, k, v, log_g, jnp.full_like(lengths, q.shape[1]), **kw)
    state_after_padding.undo = lambda: setattr(
        retention, "power_retention_chunked", sound)


def update_late(programs):
    """Break the timed path: a step's answer is read from the state as it
    stood before the step (a step with no key and a gate of one), the state
    itself moved on as it should be."""
    import jax.numpy as jnp

    from can_tpu.ops import retention

    sound = retention.power_retention_step

    def late(S, z, q, k, v, log_g, active=None):
        y, _, _ = sound(S, z, q, jnp.zeros_like(k), v, jnp.zeros_like(log_g),
                        active)
        return (y, *sound(S, z, q, k, v, log_g, active)[1:])

    retention.power_retention_step = late
    update_late.undo = lambda: setattr(retention, "power_retention_step", sound)


PROGRAM_BREAKS = {"state_bf16": state_bf16,
                  "state_after_padding": state_after_padding,
                  "update_late": update_late}


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
        gc.collect()    # the run before held 8.4 GB of weights on the device
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
