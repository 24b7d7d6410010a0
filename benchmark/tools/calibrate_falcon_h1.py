"""``calibrate_glm.py`` for the state-space hybrid's cell: read, on the chip
and in one process, the numbers ``correct`` compares, one launch a seed and
no window.  Sound runs over many seeds; on the first ``--control-seeds`` of
them also the controls that are a different COMPUTATION, each in the
program's place on the same sequences (the reference with 8-bit weights;
its recurrent state rounded to bfloat16 at every position; the gated norm
over all channels; ``key_multiplier`` left out; ``ssm_multipliers`` left
out), and on the first ``--program-seeds`` the PROGRAM broken underneath,
one run each:

* ``state_bf16``: the cache keeps the recurrent state in bfloat16 (written
  rounded by the prefill, rounded again by every decode step): what the
  float32 the configuration states is checked against;
* ``state_after_padding``: the prefill's recurrence runs over the padding,
  so the state handed to decode is the bucket's end's, not the prompt's;
* ``tail_late``: the convolution's tail gathered one position late;
* ``late_write``: decode writes its key and value one position late.

The limits in the configuration file were set from this tool's output
(PERF.md section 2).

    python3 -m benchmark.tools.calibrate_falcon_h1 --workload serve-falconh1-chat-closed \\
        --seeds 101,102,103,104,105,106 --control-seeds 3 --program-seeds 3
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark.tools.calibrate_lm import late_write

CONTROLS = ("int8", "variant:state_bf16", "variant:norm_all_channels",
            "variant:no_key_multiplier", "variant:no_ssm_multipliers")


def state_bf16(programs):
    """Break the timed path: the state leaf of every layer's cache entry is
    allocated in bfloat16 (the layout is the programs' own copy)."""
    from can_tpu.ops import cache_layout as layout

    programs.cache_layout = tuple(
        tuple(s._replace(dtypes=tuple((n, "bfloat16") for n, _ in s.dtypes))
              if s.kind == layout.STATE else s for s in layout.parts(layer))
        for layer in programs.cache_layout)
    state_bf16.undo = lambda: None


def state_after_padding(programs):
    """Break the timed path: the chunked recurrence is told every prompt
    fills its bucket, so it advances over the padding."""
    import jax.numpy as jnp

    from can_tpu.ops import ssm

    sound = ssm.ssd_chunked
    ssm.ssd_chunked = lambda x, dt, A, B, C, D, lengths, **kw: sound(
        x, dt, A, B, C, D, jnp.full_like(lengths, x.shape[1]), **kw)
    state_after_padding.undo = lambda: setattr(ssm, "ssd_chunked", sound)


def tail_late(programs):
    """Break the timed path: the convolution's tail is gathered as if every
    prompt were one token longer (its newest input is the first padding)."""
    from can_tpu.ops import ssm

    sound = ssm.conv_tail
    ssm.conv_tail = lambda x, lengths, width: sound(x, lengths + 1, width)
    tail_late.undo = lambda: setattr(ssm, "conv_tail", sound)


PROGRAM_BREAKS = {"state_bf16": state_bf16,
                  "state_after_padding": state_after_padding,
                  "tail_late": tail_late, "late_write": late_write}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--program-seeds", type=int, default=3)
    p.add_argument("--breaks", default=",".join(PROGRAM_BREAKS))
    args = p.parse_args(argv)
    from benchmark import run

    import gc

    seeds = [int(s) for s in args.seeds.split(",")]
    breaks = [b for b in args.breaks.split(",") if b]
    rows = []
    for i, seed in enumerate(seeds):
        gc.collect()    # the run before held 10.5 GB of weights on the device
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
