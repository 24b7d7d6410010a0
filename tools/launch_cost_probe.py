"""Validate ``--launch-cost-mpx auto`` against REAL train-step dispatches.

The auto mode prices the remnant planner's launch cost from a tiny-op
probe (cli/common.py measure_launch_cost_mpx).  A real train step
marshals more arguments and bigger buffers, so the probe is a suspected
mild underestimate (VERDICT r4 weak-2/next-6).  This tool measures both
on the current backend:

* the tiny-op probe (blocking per call, as shipped);
* per-call host time of the ACTUAL compiled dp train step at several
  small shapes, blocking per step exactly like the train loop's metric
  fetch; a linear fit t(px) = launch + px/rate separates the fixed
  dispatch cost (intercept) from compute (slope).

Output: one JSON line with probe_ms, step_launch_ms (intercept),
ratio, and the fitted device rate — the CHANGES.md r5 table's row for
this host.  Run on both the CPU backend (LAUNCH_PROBE_PLATFORM=cpu) and
the chip to fill both rows.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    if os.environ.get("LAUNCH_PROBE_PLATFORM") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    from can_tpu.utils import bench_device, emit_null_result

    # not a TPU and the CPU not requested (LAUNCH_PROBE_PLATFORM=cpu) ->
    # exit 2
    device = bench_device(on_timeout=emit_null_result("launch_cost_probe"))
    import jax
    import jax.numpy as jnp

    from can_tpu.cli.common import MODEL_MPX_PER_S, measure_launch_cost_mpx
    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer
    from can_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    probe_ms = measure_launch_cost_mpx() / MODEL_MPX_PER_S * 1e3

    ndev = jax.device_count()
    mesh = make_mesh()
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    repeats = int(os.environ.get("LAUNCH_PROBE_REPEATS", "10"))
    # The fit needs shapes whose COMPUTE spans well past the per-step
    # noise (~±8 ms was seen in r5), or slope and intercept are not
    # identifiable (code-review r5: the original ≤0.098 Mpx sweep put
    # ~2 ms of compute against ±8 ms noise and fitted noise).  On an
    # accelerator, go up to the headline shape (7.08 Mpx ≈ 170 ms of
    # compute at the measured ~42 Mpx/s); the CPU backend keeps the tiny
    # sweep — its fixed cost is optimizer-update-dominated either way
    # and big shapes would take minutes per step on one core.
    if device["platform"] == "cpu":
        shapes = ((1, 64, 64), (1, 128, 128), (2, 128, 128), (2, 192, 256))
    else:
        shapes = ((1, 64, 64), (2, 192, 256), (4, 576, 768),
                  (8, 576, 768), (16, 576, 768))
    rng = np.random.default_rng(0)
    xs, ts = [], []
    for b, h, w in shapes:
        local_b = b * ndev
        batch = Batch(
            image=rng.normal(size=(local_b, h, w, 3)).astype(np.float32),
            dmap=rng.uniform(size=(local_b, h // 8, w // 8, 1)).astype(np.float32),
            pixel_mask=np.ones((local_b, h // 8, w // 8, 1), np.float32),
            sample_mask=np.ones((local_b,), np.float32),
        )
        gbatch = make_global_batch(batch, mesh)
        state = create_train_state(cannet_init(jax.random.key(0)), opt)
        step = make_dp_train_step(cannet_apply, opt, mesh,
                                  compute_dtype=jnp.bfloat16)
        for _ in range(3):
            state, metrics = step(state, gbatch)
        float(jax.device_get(metrics["loss"]))
        per = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            state, metrics = step(state, gbatch)
            # per-step sync: measures the SYNCHRONOUS dispatch+compute+
            # fetch path (an upper bound — the train loop windows its
            # metric fetches over check_every=8 steps, amortising the
            # completion sync; the dispatch path is per-launch either way)
            float(jax.device_get(metrics["loss"]))
            per.append(time.perf_counter() - t0)
        t_ms = float(np.median(per) * 1e3)
        xs.append(local_b * h * w / 1e6)  # Mpx
        ts.append(t_ms)
        print(f"[launch_probe] step b{b} {h}x{w}: {t_ms:.2f} ms/call "
              f"({xs[-1]:.3f} Mpx)", flush=True)

    # t(px) = launch + px / rate
    slope, intercept = np.polyfit(xs, ts, 1)
    rate_mpx_s = 1e3 / slope if slope > 0 else float("inf")
    resid_ms = float(np.std(np.array(ts) - (slope * np.array(xs) + intercept)))
    out = {
        **device,
        "probe_ms": round(probe_ms, 3),
        "step_launch_ms": round(float(intercept), 3),
        "ratio_step_over_probe": round(float(intercept) / probe_ms, 2)
        if probe_ms > 0 else None,
        "fit_rate_mpx_per_s": round(rate_mpx_s, 1),
        "fit_resid_ms": round(resid_ms, 2),
        "shapes_ms": dict(zip([f"b{b}_{h}x{w}" for b, h, w in shapes],
                              [round(t, 2) for t in ts])),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
