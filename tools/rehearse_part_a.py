"""Dress-rehearse the ShanghaiTech Part-A recipe end-to-end.

The reference's one published number is checkpoint-backed paper parity on
Part-A (reference README.md:37, test.py:69: MAE ~62.3).  The dataset and
pretrained weights don't exist in this environment — but every OTHER
ingredient of the README recipe ("Reproducing the paper number") is
mechanical, and this script proves the whole chain executes:

1. synthesise a torchvision-layout VGG-16 state dict and ``torch.save`` it
   (stands in for the downloaded ``vgg16.pth``);
2. ``tools/convert_vgg16.py --pth`` -> ``vgg16_frontend.npz`` (the OIHW ->
   HWIO ordinal copy, reference model/CANNet.py:26-35);
3. synthesise train/test sets at the real Part-A image-shape histogram
   (scaled by ``--scale`` for CPU smoke runs);
4. train with the EXACT documented flag path — ``--vgg16-npz``, batch 1
   per replica, SGD momentum 0.95 / wd 0, best-MAE checkpointing;
5. evaluate the best checkpoint through ``can_tpu.cli.test``.

Exit 0 == the only missing ingredient for paper parity is the data itself.

Usage (full-shape rehearsal on a TPU host):
    python tools/rehearse_part_a.py --root /tmp/rehearsal --epochs 3
CPU smoke (the opt-in test): add ``--scale 0.125 --platform cpu``.
"""

from __future__ import annotations

import argparse
import io
import os
import re
import sys
from contextlib import redirect_stdout

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Approximate ShanghaiTech Part-A image-shape histogram: 300 train images of
# wildly varying resolution, clustered at 768x1024 with a long tail (the
# published dataset's shapes; the reference trains on them at batch 1,
# train.py:177).  (H, W, relative weight).
PART_A_SHAPES = (
    (768, 1024, 8),
    (576, 864, 3),
    (600, 800, 2),
    (480, 640, 2),
    (704, 1024, 1),
    (1024, 768, 1),
    (384, 512, 1),
    (312, 496, 1),
)


def _scaled_sizes(scale: float):
    sizes = []
    for h, w, weight in PART_A_SHAPES:
        hs = max(64, int(round(h * scale / 8)) * 8)
        ws = max(64, int(round(w * scale / 8)) * 8)
        sizes.extend([(hs, ws)] * weight)
    return tuple(sizes)


def make_fake_vgg16_pth(path: str, seed: int = 0) -> None:
    """torchvision-vgg16-layout state dict with random weights (the stand-in
    for the real download; shapes are the genuine VGG-16 ones)."""
    import torch

    from tools.convert_vgg16 import VGG16_CONV_FEATURE_IDX

    channels = (3, 64, 64, 128, 128, 256, 256, 256, 512, 512, 512)
    rng = np.random.default_rng(seed)
    sd = {}
    for i, k in enumerate(VGG16_CONV_FEATURE_IDX):
        cin, cout = channels[i], channels[i + 1]
        sd[f"features.{k}.weight"] = torch.tensor(
            rng.normal(0, 0.05, (cout, cin, 3, 3)).astype(np.float32))
        sd[f"features.{k}.bias"] = torch.tensor(
            rng.normal(0, 0.01, (cout,)).astype(np.float32))
    torch.save(sd, path)


def run(root: str, *, epochs: int = 3, scale: float = 1.0,
        platform: str = "default", n_train: int = 24, n_test: int = 8,
        lr: float = 2e-6, seed: int = 0) -> dict:
    """Execute the rehearsal; returns {"maes": [...], "best_mae": float,
    "eval_rc": int, "eval_mae": float}."""
    from can_tpu.cli.test import main as test_main
    from can_tpu.cli.train import main as train_main
    from can_tpu.data import make_synthetic_dataset
    from tools.convert_vgg16 import state_dict_to_npz_arrays  # noqa: F401 (import check)

    os.makedirs(root, exist_ok=True)
    pth = os.path.join(root, "vgg16.pth")
    npz = os.path.join(root, "vgg16_frontend.npz")
    make_fake_vgg16_pth(pth, seed=seed)

    # step 2: the real converter, exactly as the README invokes it
    import tools.convert_vgg16 as cv

    argv, sys.argv = sys.argv, ["convert_vgg16.py", "--pth", pth, "--out", npz]
    try:
        cv.main()
    finally:
        sys.argv = argv
    assert os.path.isfile(npz)

    sizes = _scaled_sizes(scale)
    for split, n, s in (("train", n_train, seed), ("test", n_test, seed + 1)):
        make_synthetic_dataset(os.path.join(root, f"{split}_data"), n,
                               sizes=sizes, seed=s)

    ckdir = os.path.join(root, "checkpoints")
    train_argv = ["--data_root", root, "--epochs", str(epochs),
                  "--batch-size", "1", "--lr", str(lr),
                  "--vgg16-npz", npz, "--seed", str(seed),
                  "--checkpoint-dir", ckdir, "--eval-interval", "1"]
    if platform != "default":
        train_argv += ["--platform", platform]

    class Tee(io.TextIOBase):
        def __init__(self, buf):
            self._buf = buf

        def write(self, s):
            self._buf.write(s)
            sys.__stdout__.write(s)
            return len(s)

    buf = io.StringIO()
    with redirect_stdout(Tee(buf)):
        rc = train_main(train_argv)
    if rc != 0:
        raise RuntimeError(f"train CLI failed rc={rc}")
    maes = [float(m) for m in re.findall(r"\bmae=([0-9.eE+-]+)", buf.getvalue())]
    if len(maes) != epochs:
        raise RuntimeError(f"expected {epochs} eval MAEs, parsed {maes}")

    eval_argv = ["--data_root", root, "--checkpoint-dir", ckdir]
    if platform != "default":
        eval_argv += ["--platform", platform]
    ebuf = io.StringIO()
    with redirect_stdout(Tee(ebuf)):
        eval_rc = test_main(eval_argv)
    m = re.search(r"MAE=([0-9.eE+-]+)", ebuf.getvalue())
    eval_mae = float(m.group(1)) if m else float("nan")

    # MAE of a predict-zero model on the test split (= mean GT count):
    # the absolute learned-ness bar for the gate — "flat" is only a
    # floor if the flat level actually beats not predicting at all
    import glob

    gts = sorted(glob.glob(os.path.join(root, "test_data", "ground_truth",
                                        "*.npy")))
    zero_mae = float(np.mean([abs(float(np.load(g).sum())) for g in gts]))
    return {"maes": maes, "best_mae": min(maes), "eval_rc": eval_rc,
            "eval_mae": eval_mae, "zero_mae": zero_mae}


def convergence_verdict(maes, zero_mae, eval_rc, eval_mae) -> dict:
    """The success gate, as data (main prints it; tests pin it).

    The gate's job is catching divergence (lr too high for the pixel
    scale — the r4 finding) and chain breakage, NOT demanding visible
    progress after epoch 0 on a short rehearsal: at full scale with the
    reference's 500-epoch lr (1e-7), the r5 chip run hit its floor in
    epoch 0 (MAE 9.43) and wiggled <2% after — a healthy run the old
    strict-improvement check called FAILED.  So: later epochs must either
    improve on the first or stay within a 5% band of it, AND the TAIL
    must end in band — `improved` alone passes an improve-then-diverge
    run (MAE dips in epoch 1, then climbs without bound), which is
    exactly the divergence this gate exists to catch (ADVICE r5).
    """
    maes = list(maes)
    improved = len(maes) > 1 and min(maes[1:]) < maes[0]
    flat = len(maes) > 1 and max(maes[1:]) <= maes[0] * 1.05
    tail_ok = maes[-1] <= maes[0] * 1.05
    # absolute learned-ness bar: flat (or improved) is only meaningful if
    # the level beats a predict-zero model — a frozen-params run that
    # never learns (lr resolved to 0, grads zeroed) is flat AT or above
    # the predict-zero MAE (its random un-trained densities can't track
    # GT), so require ≥10% below it (code-review r5).  Calibration: the
    # r5 full-scale chip run at the reference's 500-epoch lr (1e-7) for
    # 3 epochs reached 9.43 vs predict-zero 11.23 (16% better) — a
    # tighter margin fails honest short rehearsals at untuned lr.
    learned = min(maes) < 0.90 * zero_mae
    ok = bool(eval_rc == 0 and np.isfinite(eval_mae)
              and learned and tail_ok and (improved or flat))
    return {"ok": ok, "improved": improved, "flat": flat,
            "tail_ok": tail_ok, "learned": learned}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--epochs", type=int, default=3,
                    help="must be >= 2 (the success gate compares later "
                         "epochs' MAE against the first)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shape-histogram scale (0.125 for CPU smoke)")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu", "tpu"])
    ap.add_argument("--lr", type=float, default=2e-6,
                    help="default tuned for --scale 0.125. The MSE-sum "
                         "loss makes gradients grow with pixel count, so "
                         "scale the lr DOWN as --scale goes up (measured: "
                         "2e-6 diverges at scale 0.25; 5e-7 converges); "
                         "at full scale use ~1e-7 like the reference "
                         "(train.py:177)")
    args = ap.parse_args()
    if args.epochs < 2:
        ap.error("--epochs must be >= 2 (the success gate needs a later "
                 "epoch to compare against the first)")
    if args.platform != "cpu":
        # fail fast on an unreachable backend instead of hanging (CPU runs must
        # not touch the default backend before --platform cpu applies)
        from can_tpu.utils import await_devices, emit_null_result

        await_devices(on_timeout=emit_null_result("part_a_rehearsal"))
    res = run(args.root, epochs=args.epochs, scale=args.scale,
              platform=args.platform, lr=args.lr)
    print(f"[rehearsal] eval MAEs per epoch: {res['maes']}")
    print(f"[rehearsal] best-checkpoint eval CLI: rc={res['eval_rc']} "
          f"MAE={res['eval_mae']:.3f}")
    verdict = convergence_verdict(res["maes"], res["zero_mae"],
                                  res["eval_rc"], res["eval_mae"])
    maes = res["maes"]
    print(f"[rehearsal] best MAE {min(maes):.3f} vs predict-zero "
          f"{res['zero_mae']:.3f} (learned bar 0.90x: "
          f"{'pass' if verdict['learned'] else 'FAIL'})")
    if not verdict["tail_ok"]:
        print(f"[rehearsal] tail MAE {maes[-1]:.3f} diverged past the "
              f"first epoch's 5% band ({maes[0] * 1.05:.3f})")
    note = ("executes end to end"
            + ("" if verdict["improved"]
               else " (MAE flat at floor from epoch 0)"))
    print(f"[rehearsal] {'OK' if verdict['ok'] else 'FAILED'} — recipe "
          f"chain {note if verdict['ok'] else 'broke'}")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
