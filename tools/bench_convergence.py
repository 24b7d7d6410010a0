"""Repeatable real-chip convergence benchmark (VERDICT r3 item 4).

Round 3's real-TPU end-to-end CLI run (60 synthetic images at the Part-A
shape histogram, ``--bf16 --u8-input``, 6 epochs, MAE 18.99 -> 10.06)
existed only as a log in git history.  This scripts it: one command
re-runs the exact recipe on the chip and checks the per-epoch eval-MAE
trajectory against the committed golden band below — the TPU-side
convergence regression net the CPU-mesh goldens (tests/test_golden.py)
can't provide.  GOLDEN_TPU_MAES below was recorded on the live chip in
round 5 (two back-to-back runs, zero drift); if it is ever reset to
None the check degrades to the loose convergence gate and reports
``golden_ok: null``.

Run (single process, real TPU):
    python tools/bench_convergence.py            # check against golden
    python tools/bench_convergence.py --record   # print fresh goldens
CPU smoke: add ``--platform cpu --scale 0.125`` (no golden check — the
TPU goldens don't transfer across backends; the run must still converge).

Output: one JSON line.  The quality bar this stands in for is the reference's
checkpoint-backed dataset claim (reference README.md:37, test.py:69).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import shutil
import sys
import tempfile
import time
from contextlib import redirect_stdout

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.rehearse_part_a import PART_A_SHAPES, _scaled_sizes  # noqa: E402

# Committed golden trajectory: eval MAE per epoch, measured on the real
# v5e chip (bf16 compute, u8 input, batch 8, lr 2e-6, seed 0).
# Recorded round 5 (2026-07-31) via two back-to-back `--record` runs;
# the runs agreed to all four printed decimals (zero
# observed drift — the program, schedule, and bf16 accumulation order
# are fully deterministic for this recipe on v5e).  The 2% band is
# therefore pure headroom for future jaxlib/compiler bumps.
GOLDEN_TPU_MAES = [12.7073, 18.9851, 14.0405, 10.0567, 11.0823, 10.4693]
GOLDEN_RTOL = 0.02

N_TRAIN, N_TEST = 60, 16
EPOCHS, BATCH, LR, SEED = 6, 8, 2e-6, 0


def run(root: str, *, platform: str = "default", scale: float = 1.0) -> dict:
    from can_tpu.cli.train import main as train_main
    from can_tpu.data import make_synthetic_dataset

    sizes = _scaled_sizes(scale)
    for split, n, s in (("train", N_TRAIN, SEED), ("test", N_TEST, SEED + 1)):
        make_synthetic_dataset(os.path.join(root, f"{split}_data"), n,
                               sizes=sizes, seed=s)
    ckdir = os.path.join(root, "checkpoints")
    argv = ["--data_root", root, "--epochs", str(EPOCHS),
            "--batch-size", str(BATCH), "--lr", str(LR),
            "--seed", str(SEED), "--bf16", "--u8-input",
            "--checkpoint-dir", ckdir, "--eval-interval", "1"]
    if platform != "default":
        argv += ["--platform", platform]

    buf = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, s):
            buf.write(s)
            sys.__stdout__.write(s)
            return len(s)

    t0 = time.perf_counter()
    with redirect_stdout(Tee()):
        rc = train_main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"train CLI failed rc={rc}")
    maes = [float(m) for m in re.findall(r"\bmae=([0-9.eE+-]+)",
                                         buf.getvalue())]
    if len(maes) != EPOCHS:
        raise RuntimeError(f"expected {EPOCHS} eval MAEs, parsed {maes}")
    return {"maes": maes, "wall_s": round(wall, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="",
                    help="work dir (default: fresh temp dir, removed after)")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu", "tpu"])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shape-histogram scale (0.125 for CPU smoke)")
    ap.add_argument("--record", action="store_true",
                    help="print the measured trajectory as a new golden "
                         "instead of checking")
    args = ap.parse_args()

    if args.platform != "cpu":
        # fail fast on an unreachable backend instead of hanging (CPU runs must
        # NOT touch the default backend before --platform cpu applies)
        from can_tpu.utils import await_devices, emit_null_result

        await_devices(on_timeout=emit_null_result(
            "convergence_tpu_part_a_histogram"))
    root = args.root or tempfile.mkdtemp(prefix="can_tpu_conv_bench_")
    try:
        res = run(root, platform=args.platform, scale=args.scale)
    finally:
        if not args.root:
            shutil.rmtree(root, ignore_errors=True)

    maes = res["maes"]
    # Loose gate: the trajectory must come down 25% from its PEAK, and
    # the low must occur AT/AFTER the peak (a run that only climbs never
    # passes).  Peak-anchored rather than first-eval-anchored because
    # epoch 0's eval already reflects a full epoch of training and can
    # land below later epochs — the committed golden starts at 12.71 and
    # peaks at 18.99 (its CHANGES r3 prose quoted peak->best), so
    # anchoring on maes[0] made a genuinely converged run report
    # converged=false.
    # ... while still requiring the run to end below where it started,
    # so a post-epoch-0 blow-up that only partially recovers stays red.
    peak_i = maes.index(max(maes))
    converged = bool(min(maes[peak_i:]) < 0.75 * max(maes)
                     and min(maes[peak_i:]) < maes[0])
    on_tpu_recipe = args.platform != "cpu" and args.scale == 1.0
    drift = None
    if args.record:
        print(f"GOLDEN_TPU_MAES = {[round(m, 4) for m in maes]}")
        ok = converged
    elif on_tpu_recipe and GOLDEN_TPU_MAES is not None:
        drift = float(np.max(np.abs(np.array(maes) / np.array(GOLDEN_TPU_MAES)
                                    - 1.0)))
        # Reproducing the committed golden within band is the gate: the
        # golden's own convergence was validated at record time, so a
        # zero-drift match must pass regardless of the loose heuristic.
        ok = drift <= GOLDEN_RTOL
    else:
        # cross-backend run, or golden not yet recorded: convergence gate
        if on_tpu_recipe:
            print("# no golden recorded yet — run with --record on a chip "
                  "and commit the trajectory", file=sys.stderr, flush=True)
        ok = converged
    golden_checked = drift is not None
    print(json.dumps({
        "metric": "convergence_tpu_part_a_histogram",
        "value": round(min(maes), 4),
        "unit": "MAE (synthetic, lower=better)",
        "maes": [round(m, 4) for m in maes],
        "converged": converged,
        # null until a --record golden exists: 'true' must only ever mean
        # the committed trajectory reproduced within the band
        "golden_ok": ok if golden_checked else None,
        "golden_rtol": GOLDEN_RTOL if drift is not None else None,
        "max_drift": round(drift, 5) if drift is not None else None,
        "wall_s": res["wall_s"],
        "recipe": {"n_train": N_TRAIN, "epochs": EPOCHS, "batch": BATCH,
                   "lr": LR, "flags": "--bf16 --u8-input", "seed": SEED},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
