"""Benchmark: CANNet training throughput (images/sec) on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} that
names the device it ran on (platform / device_kind / device_count).  It
times the chip: when the first JAX device is not a TPU it exits 2 instead
of timing whatever came up — unless the CPU was requested explicitly
(``JAX_PLATFORMS=cpu``, a plumbing smoke whose number is not a device
metric).

Baseline note: the reference publishes NO throughput numbers (BASELINE.md) —
its only number is a quality claim (ShanghaiTech-A MAE ~62.3).  For
``vs_baseline`` we use the BASELINE.json north star "≥ H100x8 DDP images/sec"
prorated per chip: a DDP rank training CANNet at batch 1 sustains an
estimated 25 img/s on one H100 (FLOP-model derivation in BASELINE.md:
1.24 TFLOP/step at 576x768, ~6% of TF32 peak for a batch-1 variable-shape
loop; defensible band 20-40).  The estimate is emitted in the JSON as
``baseline_estimate`` so the assumption is visible.  One v5e chip at bf16
beating one H100 at fp32 on this CNN means the whole-pod target is met at
equal chip counts.

For the multi-config benchmark sweep (variable-resolution bucketed pipeline,
high-res eval, f32 vs bf16 — the BASELINE.json config list) run
``python bench_suite.py``; this file stays single-config because the driver
parses exactly one JSON line.

Config: batch 16 per chip of 576x768 synthetic images (ShanghaiTech-A
scale), bf16 compute / f32 params, full train step (fwd + bwd + SGD update),
steady state over 20 steps after 3 warmup steps.  Override via env:
BENCH_BATCH, BENCH_H, BENCH_W, BENCH_STEPS, BENCH_F32=1.

BENCH_TELEMETRY_DIR=<dir>: additionally record compile / step_window /
memory / bench events to <dir>/telemetry.host0.jsonl — the SAME schema the
train CLI writes, so BENCH artifacts and training runs are directly
comparable (tools/telemetry_report.py reads both).  Unset (the driver's
configuration), the hot loop is byte-identical to before — telemetry costs
nothing when off.

Measured history (one v5e chip, 576x768): bf16 b4 41.8 -> b8 85.5 ->
b16 92.7 img/s (b32 88.7; the batch=1-per-device reference habit leaves
half the chip idle); full-f32 b16 61.8 img/s.
"""

import json
import os
import time

import numpy as np

# img/s of one H100 DDP rank running the reference's training loop —
# an ESTIMATE (FLOP-model derivation and the 20-40 defensible band in
# BASELINE.md).  Single source of truth; bench_suite.py imports it.
BASELINE_IMG_PER_S_H100 = 25.0


def main() -> None:
    # config is known before any device touch: the timeout null line can
    # carry the SAME parameterized metric name a successful run would,
    # so artifact consumers see a null in the real series, not a gap
    b = int(os.environ.get("BENCH_BATCH", "16"))
    h = int(os.environ.get("BENCH_H", "576"))
    w = int(os.environ.get("BENCH_W", "768"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    warmup = 3
    f32 = bool(os.environ.get("BENCH_F32"))
    metric = (f"cannet_train_img_per_s_{h}x{w}_b{b}"
              f"{'_f32' if f32 else '_bf16'}")

    # fail fast if backend acquisition hangs — one stderr line and exit 3
    # beats a silently hung driver; the JSON null line makes the recorded
    # artifact self-describing (r5).  No TPU and no explicit CPU request:
    # exit 2 (utils.bench_device).
    from can_tpu.utils import bench_device, emit_null_result

    device = bench_device(on_timeout=emit_null_result(
        metric, unit="images/sec", vs_baseline=None))
    import jax
    import jax.numpy as jnp

    from can_tpu.utils import enable_compilation_cache

    enable_compilation_cache()  # warm driver re-runs skip the ~30 s compile

    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import (
        make_dp_train_step,
        make_global_batch,
        make_mesh,
    )
    from can_tpu.data.batching import Batch
    from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer

    compute_dtype = None if f32 else jnp.bfloat16

    apply_fn = cannet_apply
    ndev = jax.device_count()
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    local_b = b * ndev  # single process: local == global
    batch = Batch(
        image=rng.normal(size=(local_b, h, w, 3)).astype(np.float32),
        dmap=rng.uniform(size=(local_b, h // 8, w // 8, 1)).astype(np.float32),
        pixel_mask=np.ones((local_b, h // 8, w // 8, 1), np.float32),
        sample_mask=np.ones((local_b,), np.float32),
    )
    gbatch = make_global_batch(batch, mesh)

    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    state = create_train_state(cannet_init(jax.random.key(0)), opt)
    step = make_dp_train_step(apply_fn, opt, mesh,
                              compute_dtype=compute_dtype)

    tel = None
    raw_step = step
    if os.environ.get("BENCH_TELEMETRY_DIR"):
        from can_tpu import obs

        tel = obs.open_host_telemetry(os.environ["BENCH_TELEMETRY_DIR"])
        tel.emit("run", config={"metric": metric, "batch": b, "h": h,
                                "w": w, "steps": steps, "f32": f32,
                                "devices": ndev})
        # first call per signature = the compile bill, attributed.  The
        # wrapper covers only WARMUP (where the compile happens); the
        # timed loop below runs the raw step so the measured number is
        # the same with telemetry on or off.
        step = obs.RecompileTracker(step, tel, name="bench_step")

    # fence with an actual D2H fetch of a value the last step produced
    for _ in range(warmup):
        state, metrics = step(state, gbatch)
    float(jax.device_get(metrics["loss"]))

    step = raw_step  # timed loop bypasses any telemetry wrapper
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, gbatch)
    loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    assert np.isfinite(loss), f"non-finite bench loss {loss}"

    img_per_s = local_b * steps / dt
    per_chip = img_per_s / ndev
    record = {
        "metric": metric,
        "value": round(img_per_s, 3),
        "unit": "images/sec",
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_S_H100, 3),
        "baseline_estimate": BASELINE_IMG_PER_S_H100,
        **device,
    }
    if tel is not None:
        # the steady-state window as ONE step_window event (the timed loop
        # itself stays uninstrumented — no per-step host work in the
        # measurement), plus a memory snapshot and the result record
        tel.emit("step_window", phase="bench", steps=steps,
                 seconds=round(dt, 4), images=local_b * steps,
                 samples_s=[], mean_step_s=round(dt / steps, 6),
                 img_per_s=round(img_per_s, 3))
        obs.emit_memory(tel, where="bench_steady_state")
        tel.emit("bench", **record)
        tel.close()
    print(json.dumps(record))


if __name__ == "__main__":
    main()
