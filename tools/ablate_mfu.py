"""MFU-plateau probe: selective remat of full-res activations (VERDICT r4
weak-6 — "one more idea with a plausible mechanism, then close the axis").

The r4 profile shows the headline step fusion-saturated at ~60% of v5e
bf16 peak; the residual is HBM traffic, dominated by save-for-backward
activations — the largest of which are the full-resolution stem tensors
(bf16[B,H,W,64], 2x lane-padded; same tensors that dominate the OOM dump,
cli/common.py activation_bytes).  Mechanism under test: recompute exactly
those tensors in the backward instead of reading them back, via
``jax.checkpoint`` + ``save_anything_except_these_names`` over the
``checkpoint_name`` tags in models/cannet.py.  The recompute cost is tiny
(stem convs are <1% of step FLOPs) while the saved reads are the largest
single activations — if bandwidth is the binding constraint this HELPS;
if the gain is zero the plateau is not activation-read-bound and the
axis closes with that number.

Variants (cumulative exclusion, finest first):
  baseline    — no remat (the shipped headline config)
  stem        — recompute frontend convs 0-1 (full res, 64ch)
  half        — + convs 2-3 (1/2 res, 128ch)
  quarter     — + convs 4-6 (1/4 res, 256ch)
  full_remat  — jax.checkpoint of the whole forward (the r2 ablation)

Since round 9 the tool reports through the perf-attribution layer
instead of hand math: each variant's step is wrapped in
``obs.RecompileTracker`` with a ``ProgramCostLedger`` on the bus, so its
XLA ``cost_analysis()`` flops/bytes are read at compile time and joined
with the measured steady-state step time against the device peak table
(``cli/common.py local_device_peaks``) — the JSON now carries per-variant
**MFU**, HBM-bandwidth utilisation, and the roofline class next to
img/s, which is exactly the compute-vs-bandwidth split the remat
variants exist to probe.  On CPU the peak table is labelled NOMINAL:
MFU values are relative-only there (the variant ORDERING is still
meaningful, the absolute numbers are not).

Run on the chip: ``python tools/ablate_mfu.py`` (~2 min; one compile per
variant).  CPU smoke: ``ABLATE_PLATFORM=cpu ABLATE_STEPS=2 ABLATE_BATCH=2
ABLATE_H=64 ABLATE_W=64 python tools/ablate_mfu.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEM_NAMES = ("frontend0.pre", "frontend0", "frontend1.pre", "frontend1")
HALF_NAMES = STEM_NAMES + ("frontend2.pre", "frontend2",
                           "frontend3.pre", "frontend3")
QUARTER_NAMES = HALF_NAMES + ("frontend4.pre", "frontend4",
                              "frontend5.pre", "frontend5",
                              "frontend6.pre", "frontend6")


def main() -> None:
    if os.environ.get("ABLATE_PLATFORM") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    from can_tpu.utils import bench_device, emit_null_result

    # not a TPU and the CPU not requested (ABLATE_PLATFORM=cpu) -> exit 2
    device = bench_device(on_timeout=emit_null_result("ablate_mfu"))
    import jax
    import jax.numpy as jnp

    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer
    from can_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    b = int(os.environ.get("ABLATE_BATCH", "16"))
    h = int(os.environ.get("ABLATE_H", "576"))
    w = int(os.environ.get("ABLATE_W", "768"))
    steps = int(os.environ.get("ABLATE_STEPS", "20"))
    ndev = jax.device_count()
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    local_b = b * ndev
    batch = Batch(
        image=rng.normal(size=(local_b, h, w, 3)).astype(np.float32),
        dmap=rng.uniform(size=(local_b, h // 8, w // 8, 1)).astype(np.float32),
        pixel_mask=np.ones((local_b, h // 8, w // 8, 1), np.float32),
        sample_mask=np.ones((local_b,), np.float32),
    )
    gbatch = make_global_batch(batch, mesh)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))

    except_names = jax.checkpoint_policies.save_anything_except_these_names
    variants = {
        "baseline": dict(remat=False),
        "stem": dict(remat=True, remat_policy=except_names(*STEM_NAMES)),
        "half": dict(remat=True, remat_policy=except_names(*HALF_NAMES)),
        "quarter": dict(remat=True, remat_policy=except_names(*QUARTER_NAMES)),
        "full_remat": dict(remat=True),
    }

    # the perf-attribution ledger: per-variant cost_analysis() at compile
    # time (via RecompileTracker), steady-state seconds observed after the
    # timed loop, MFU/roofline against the device peak table
    from can_tpu.obs import ProgramCostLedger, RecompileTracker, Telemetry

    tel = Telemetry()
    tel.ledger = ledger = ProgramCostLedger(compute="bf16")

    results = {}
    losses = {}
    for name, kw in variants.items():
        state = create_train_state(cannet_init(jax.random.key(0)), opt)
        step = make_dp_train_step(cannet_apply, opt, mesh,
                                  compute_dtype=jnp.bfloat16, **kw)
        # per-variant tracker name => per-variant ledger row (the image
        # signature alone is identical across variants)
        step = RecompileTracker(step, tel, name=name)
        for _ in range(3):
            state, metrics = step(state, gbatch)
        float(jax.device_get(metrics["loss"]))  # fence
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, gbatch)
        losses[name] = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        ledger.observe(name, gbatch["image"].shape, dt, n=steps)
        results[name] = round(local_b * steps / dt, 2)
        row = next(r for r in ledger.rows() if r["name"] == name)
        # each field guards its own None: a half-reporting cost_analysis()
        # can yield mfu without bw_util (flops but no bytes) or vice versa
        parts = []
        if row["mfu"] is not None:
            parts.append(f"MFU {row['mfu']:.3f}")
        if row["bw_util"] is not None:
            parts.append(f"bw {row['bw_util']:.3f}")
        if row["roofline"] not in (None, "unknown"):
            parts.append(f"[{row['roofline']}-bound]")
        print(f"[ablate_mfu] {name:10s}: {results[name]:8.2f} img/s"
              + ("  " + "  ".join(parts) if parts else "  (no cost analysis)"))

    # remat changes memory/bandwidth, never math: same-trajectory check
    base = losses["baseline"]
    for name, loss in losses.items():
        assert np.isfinite(loss) and abs(loss - base) / abs(base) < 5e-2, (
            name, loss, base)

    # syncBN-variant sweep (r10): the moments-path A/B the --bn-impl flag
    # exposes, attributed the same way — per-variant cost_analysis bytes
    # is the number that argues the one-pass rebuild (two-pass streams
    # each BN layer's activation through HBM twice).  ABLATE_SYNCBN=0
    # skips (halves the chip time when only the remat axis is wanted).
    if os.environ.get("ABLATE_SYNCBN", "1") != "0":
        import functools

        from can_tpu.models import init_batch_stats
        from can_tpu.models.cannet import LocalOps
        from can_tpu.ops.bn_moments import make_bn_ops

        from can_tpu.utils import pallas_interpret

        # from the REQUESTED platform: a TPU run cannot end up interpreted
        interpret = pallas_interpret()
        print(f"[ablate_mfu] pallas kernel "
              f"{'INTERPRETED' if interpret else 'compiled'}")
        bn_losses = {}
        for impl in ("twopass", "onepass", "pallas"):
            if impl == "pallas" and ndev > 1:
                # the train CLI's refusal, mirrored: no GSPMD partitioning
                # rule for pallas_call — under the jit-sharded dp step the
                # forced gather would corrupt exactly the A/B this sweep
                # reports (run on 1 device or via --sp for this variant)
                print("[ablate_mfu] syncbn_pallas: skipped on the "
                      f"{ndev}-device GSPMD dp step")
                continue
            name = f"syncbn_{impl}"
            bn_ops = make_bn_ops(impl, interpret=interpret)
            apply_fn = (cannet_apply if bn_ops is None else
                        functools.partial(cannet_apply,
                                          ops=LocalOps(bn_ops=bn_ops)))
            # fresh params per variant: the step donates its state
            bn_params = cannet_init(jax.random.key(0), batch_norm=True)
            state = create_train_state(bn_params, opt,
                                       init_batch_stats(bn_params))
            step = make_dp_train_step(apply_fn, opt, mesh,
                                      compute_dtype=jnp.bfloat16)
            step = RecompileTracker(step, tel, name=name)
            for _ in range(3):
                state, metrics = step(state, gbatch)
            float(jax.device_get(metrics["loss"]))
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, gbatch)
            bn_losses[name] = float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            ledger.observe(name, gbatch["image"].shape, dt, n=steps)
            results[name] = round(local_b * steps / dt, 2)
            row = next(r for r in ledger.rows() if r["name"] == name)
            parts = []
            if row["mfu"] is not None:
                parts.append(f"MFU {row['mfu']:.3f}")
            if row["bw_util"] is not None:
                parts.append(f"bw {row['bw_util']:.3f}")
            if row["bytes_accessed"]:
                parts.append(f"{row['bytes_accessed'] / 1e9:.3f} GB")
            if row["roofline"] not in (None, "unknown"):
                parts.append(f"[{row['roofline']}-bound]")
            print(f"[ablate_mfu] {name:16s}: {results[name]:8.2f} img/s"
                  + ("  " + "  ".join(parts)
                     if parts else "  (no cost analysis)"))
        # the moments path changes reduction order, never the model: the
        # variants must sit on one trajectory (vs each other, not vs the
        # no-BN baseline — a BN model is a different model)
        bn_base = bn_losses["syncbn_twopass"]
        for name, loss in bn_losses.items():
            assert np.isfinite(loss) and (
                abs(loss - bn_base) / abs(bn_base) < 5e-2), (
                name, loss, bn_base)

    rows = {r["name"]: {"mfu": r["mfu"], "bw_util": r["bw_util"],
                        "roofline": r["roofline"],
                        "gbytes": (round(r["bytes_accessed"] / 1e9, 3)
                                   if r["bytes_accessed"] else None),
                        "gflops": (round(r["flops"] / 1e9, 2)
                                   if r["flops"] else None)}
            for r in ledger.rows()}
    peaks = ledger.peaks
    print(json.dumps({"config": f"{h}x{w} b{b} bf16 x{steps}steps",
                      "img_per_s": results, "mfu": rows,
                      "peak_source": peaks.source if peaks else None,
                      "peak_nominal": bool(peaks and peaks.nominal),
                      **device}))


if __name__ == "__main__":
    main()
