"""Benchmark sweep over the BASELINE.json config list (one JSON line each).

``bench.py`` stays single-config (the driver parses exactly one line); this
suite measures what that number can't — the throughput that actually
predicts training time on real data:

1. fixed-shape train, bf16 and f32 (576x768 b16 — ShanghaiTech-A scale);
2. the REAL pipeline on a variable-resolution dataset: ShardedBatcher with
   the auto bucket ladder + host->device prefetch + the windowed-metrics
   epoch loop, reporting first-epoch (compile-heavy) vs steady-state img/s
   and the compile (distinct-shape) count — BASELINE.json config 3;
3. high-resolution eval (1536x2048, batch 1) — the UCF-QNRF analogue,
   BASELINE.json config 5;
4. the HOST pipeline on real files: JPEG decode + density .npy load +
   resize + flip + pad, no device involved — the img/s the host can feed
   the chip, at worker counts 0/4/8 (the reference's DataLoader
   num_workers knob, train.py:90, measured instead of assumed).

A persistent XLA compilation cache is enabled by default (disable with
BENCH_SUITE_NO_CACHE=1): a second fresh-process run reports
``compile_epoch_s`` near zero, and the pipeline config also measures the
in-process warm-restart epoch (executables dropped, disk cache kept) as
``warm_compile_epoch_s``.

Run: ``python bench_suite.py`` (real TPU; single process only), or
``BENCH_SUITE_PLATFORM=cpu8`` for a smoke run on an 8-device CPU mesh.
Smaller/faster: ``BENCH_SUITE_QUICK=1``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from bench import BASELINE_IMG_PER_S_H100 as BASELINE_EST

# BENCH_TELEMETRY_DIR wiring (see bench.py): every record is ALSO emitted
# as a ``bench`` event, and the pipeline configs run their epochs with the
# telemetry bus attached — so suite artifacts carry the same compile /
# step_window / stall / memory stream a training run does.  None when the
# env var is unset: zero cost.
_TELEMETRY = None


_DEVICE: dict = {}  # platform / device_kind / device_count, set by main()


def _emit(metric: str, value: float, unit: str, *, per_chip: float = None,
          **extra) -> None:
    rec = {"metric": metric, "value": round(value, 3), "unit": unit,
           **_DEVICE}
    if per_chip is not None:
        rec["vs_baseline"] = round(per_chip / BASELINE_EST, 3)
        rec["baseline_estimate"] = BASELINE_EST
    rec.update(extra)
    if _TELEMETRY is not None:
        _TELEMETRY.emit("bench", **rec)
    print(json.dumps(rec), flush=True)


class SynthVarResDataset:
    """ShanghaiTech-A-like resolution mix, served from one pre-generated
    buffer (items are views into it — per-item host cost is just the
    pad_batch copy, so the bench isolates the batching/padding/prefetch/
    transfer/compute pipeline rather than random-number generation).

    40% of items sit at the dominant 768x1024; the rest spread uniformly —
    the clustered-but-wild histogram real crowd datasets have."""

    def __init__(self, n: int, seed: int = 0, lo: int = 384, hi: int = 1024,
                 dominant=(768, 1024), u8: bool = False):
        rng = np.random.default_rng(seed)
        self.sizes = []
        for _ in range(n):
            if rng.uniform() < 0.4:
                h, w = dominant
            else:
                h = int(rng.integers(lo, hi + 1))
                w = int(rng.integers(lo, hi + 1))
            self.sizes.append(((h // 8) * 8, (w // 8) * 8))
        mh = max(h for h, _ in self.sizes) + 64
        mw = max(w for _, w in self.sizes) + 64
        img = rng.random((mh, mw, 3), dtype=np.float32)
        self._img_buf = (img * 255).astype(np.uint8) if u8 else img
        self._dmap_buf = rng.random((mh // 8, mw // 8, 1), dtype=np.float32)
        self._offs = [(int(rng.integers(0, 64)), int(rng.integers(0, 64)))
                      for _ in range(n)]

    def __len__(self):
        return len(self.sizes)

    def snapped_shape(self, i):
        return self.sizes[i]

    def __getitem__(self, i, rng=None):
        h, w = self.sizes[i]
        ro, co = self._offs[i]
        img = self._img_buf[ro:ro + h, co:co + w]
        dmap = self._dmap_buf[ro // 8:ro // 8 + h // 8,
                              co // 8:co // 8 + w // 8]
        return img, dmap


def bench_fixed(jnp, compute_dtype, *, b, h, w, steps, warmup=3):
    import jax

    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer

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
    state = create_train_state(cannet_init(jax.random.key(0)), opt)
    step = make_dp_train_step(cannet_apply, opt, mesh, compute_dtype=compute_dtype)
    for _ in range(warmup):
        state, metrics = step(state, gbatch)
    float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, gbatch)
    loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    assert np.isfinite(loss)
    img_per_s = local_b * steps / dt
    tag = "f32" if compute_dtype is None else "bf16"
    _emit(f"train_fixed_{h}x{w}_b{b}_{tag}", img_per_s, "images/sec",
          per_chip=img_per_s / ndev)


def bench_pipeline(jnp, compute_dtype, *, n_images, batch, epochs,
                   lo=384, hi=1024, dominant=(768, 1024), u8=False,
                   remat="off"):
    """The number that predicts real training time: variable-resolution
    images through the full pipeline (bucketing, padding, per-shape
    compiles) into the sharded train step.

    Two throughputs are reported:

    * ``value`` — steady-state img/s over the epoch's PRE-STAGED device
      batches (bucket-shape switching and donation included; host->device
      transfer excluded, and steps are dispatched back-to-back with ONE
      terminal fetch — the train loop's windowed metric fetch every
      check_every=8 steps is NOT in this number, so on a dispatch-bound
      host the loop achieves somewhat less; the end_to_end entry
      carries that cost).
    * ``end_to_end_img_per_s`` — the same epoch through ``train_one_epoch``
      with prefetch, transfers included (``transfer_mb_per_batch``
      quantifies the H2D pressure).  How far it falls below ``value`` on
      a host that holds its own chip is not measured yet (ROADMAP
      Speed 1).
    """
    import jax

    from can_tpu.data import ShardedBatcher
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
        train_one_epoch,
    )

    ndev = jax.device_count()
    mesh = make_mesh()
    ds = SynthVarResDataset(n_images, lo=lo, hi=hi, dominant=dominant, u8=u8)
    max_buckets = int(os.environ.get("BENCH_SUITE_MAX_BUCKETS", "24"))
    # remnant sub-batches on by default (the CLI default); quantum = ndev so
    # every sub-batch still splits across the dp mesh axis
    remnant = not os.environ.get("BENCH_SUITE_NO_REMNANT")
    from can_tpu.cli.common import DEVICE_LAUNCH_COST_MPX, max_launch_pixels

    # the QUOTED number below is steady-state compute (launches enqueued
    # back-to-back), so the schedule is planned at DEVICE-regime launch
    # pricing — the r5 suite planned at the CLI default's 2.0 Mpx and then
    # paid 30.7% pixel overhead (b16) in the very regime that gets
    # launches nearly free (VERDICT r5 item 7).  Override the env var to
    # study dispatch-bound pricing.
    launch_mpx = float(os.environ.get("BENCH_SUITE_LAUNCH_COST_MPX",
                                      str(DEVICE_LAUNCH_COST_MPX)))
    plan_mode = os.environ.get("BENCH_SUITE_PLAN_MODE", "cost")
    cap = (max_launch_pixels(bf16=compute_dtype is not None, shards=ndev)
           if remnant else None)
    batcher = ShardedBatcher(ds, batch * ndev, shuffle=True, seed=0,
                             pad_multiple="auto", max_buckets=max_buckets,
                             remnant_sizes=remnant, batch_quantum=ndev,
                             launch_cost_px=launch_mpx * 1e6,
                             max_launch_px=cap, plan_mode=plan_mode)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    state = create_train_state(cannet_init(jax.random.key(0)), opt)
    put = lambda b: make_global_batch(b, mesh)

    def make_step():
        # per-bucket remat (VERDICT r3 item 3): THE CLI's dispatch, shared
        # via make_bucketed_train_step — jax.checkpoint only on bucket
        # shapes the policy flags, so b16 varres runs where it used to OOM
        from can_tpu.cli.common import make_bucketed_train_step, make_remat_policy

        policy = make_remat_policy(remat, global_batch=batch * ndev,
                                   bf16=compute_dtype is not None,
                                   shards=ndev)
        return make_bucketed_train_step(cannet_apply, opt, mesh,
                                        compute_dtype=compute_dtype,
                                        policy=policy)

    step = make_step()

    # epoch 0 end-to-end: pays every bucket-shape compile (near zero on a
    # second fresh process once the persistent cache is populated).  With
    # BENCH_TELEMETRY_DIR the epochs run with the bus attached: per-shape
    # compile events, step windows, and stall accounting land in the same
    # JSONL schema a training run writes.
    t0 = time.perf_counter()
    state, s0 = train_one_epoch(step, state, batcher.epoch(0), put_fn=put,
                                epoch=0, show_progress=False,
                                telemetry=_TELEMETRY)
    compile_epoch_s = time.perf_counter() - t0

    # steady-state end-to-end (transfers + prefetch overlap included)
    state, s1 = train_one_epoch(step, state, batcher.epoch(1), put_fn=put,
                                epoch=1, show_progress=False,
                                telemetry=_TELEMETRY)

    # warm restart: drop the in-memory executables (what a fresh process
    # starts without) but keep the on-disk cache — the epoch now measures
    # deserialisation instead of compilation.  Only meaningful when the
    # persistent cache is active (auto mode skips the CPU smoke backend).
    warm_compile_epoch_s = None
    if jax.config.jax_compilation_cache_dir:
        jax.clear_caches()
        step = make_step()
        t0 = time.perf_counter()
        state, _ = train_one_epoch(step, state, batcher.epoch(1), put_fn=put,
                                   epoch=1, show_progress=False)
        warm_compile_epoch_s = round(time.perf_counter() - t0, 1)

    # steady-state compute: stage one epoch's batches on device, then step
    staged = [put(b) for b in batcher.epoch(2)]
    jax.block_until_ready(staged[-1]["image"])
    n_imgs = sum(float(np.sum(jax.device_get(g["sample_mask"]))) for g in staged)
    mb = sum(g["image"].nbytes for g in staged) / 1e6 / len(staged)
    for g in staged:  # warm pass (shapes already compiled in epoch 0)
        state, metrics = step(state, g)
    float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(max(1, epochs - 1)):
        for g in staged:
            state, metrics = step(state, g)
    float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    compute_img_per_s = n_imgs * max(1, epochs - 1) / dt

    tag = ("f32" if compute_dtype is None else "bf16") + ("_u8" if u8 else "")
    if remat != "off":
        tag += f"_remat_{remat}"
    # the QUOTED varres number (VERDICT r4 missing-4) is the end-to-end
    # one: pipeline + transfer + compute through train_one_epoch with
    # prefetch overlap — emitted as its own record so it can't be
    # mistaken for the staged-compute ceiling below
    _emit(f"train_pipeline_varres_b{batch}_{tag}_end_to_end",
          s1.img_per_s, "images/sec", per_chip=s1.img_per_s / ndev,
          steady_state_compute_img_per_s=round(compute_img_per_s, 3))
    planner = batcher.planner_stats(1) if remnant else {}
    if _TELEMETRY is not None and planner:
        _TELEMETRY.emit("data.planner", realized_programs=s1.programs,
                        **planner)
    _emit(f"train_pipeline_varres_b{batch}_{tag}", compute_img_per_s,
          "images/sec", per_chip=compute_img_per_s / ndev,
          end_to_end_img_per_s=round(s1.img_per_s, 3),
          compile_epoch_s=round(compile_epoch_s, 1),
          warm_compile_epoch_s=warm_compile_epoch_s,
          transfer_mb_per_batch=round(mb, 1),
          distinct_shapes=s1.distinct_shapes,
          programs=batcher.program_count(1),
          padding_overhead=round(batcher.padding_overhead(), 4),
          schedule_overhead=round(batcher.schedule_overhead(1), 4),
          max_buckets=max_buckets,
          remnant_batches=remnant,
          launch_cost_mpx=launch_mpx,
          plan_mode=plan_mode,
          lowered_launches=planner.get("lowered_launches"),
          buckets=batcher.describe_buckets())


def bench_host_pipeline(*, n_images, batch, h=576, w=768, workers=(0, 4, 8),
                        jpeg_quality=90, repeats=5, cache_mb=1024):
    """Host-side materialisation rate on REAL files — no device anywhere.

    Writes n JPEG images + full-res float32 ``.npy`` density maps (the
    on-disk format the reference trains from), then times full
    ``ShardedBatcher.epoch`` passes — JPEG decode, grayscale/alpha
    handling, flip, /8-snap cv2 resize, normalise, pad — at each worker
    count, across the pipeline's storage tiers: legacy decode, the
    prepared 1/8-density store (data/prepared.py — the offline bake that
    kills the per-epoch 1.7 MB density load+resize), and the prepared
    store plus the in-RAM decoded-item cache (the dataset-fits-in-RAM
    ceiling).  The chip consumed ~95 img/s at 576x768 in the r2-r5 driver
    runs (historical); this measures whether the host can feed it.

    VARIANCE-AWARE (VERDICT r5 weak #2): each configuration times
    ``repeats`` distinct epochs and reports the MEDIAN as ``value`` plus
    the min/max/spread — single-epoch timings on a small n_images wobble
    enough (~±5% observed) to manufacture non-monotonic worker-count
    "anomalies" out of noise, which is exactly what the spread field now
    makes checkable.
    """
    import shutil
    import tempfile

    import cv2
    from PIL import Image

    from can_tpu.data import CrowdDataset, ItemCache, ShardedBatcher
    from can_tpu.data.prepared import write_store

    tmp = tempfile.mkdtemp(prefix="can_tpu_hostbench_")
    img_dir = os.path.join(tmp, "images")
    gt_dir = os.path.join(tmp, "ground_truth")
    os.makedirs(img_dir)
    os.makedirs(gt_dir)
    rng = np.random.default_rng(0)
    try:
        for i in range(n_images):
            # smooth-ish content so JPEG size/decode cost is realistic
            # (pure noise decodes slower than photographs)
            base = rng.integers(0, 256, (h // 8, w // 8, 3), np.uint8)
            arr = cv2.resize(base, (w, h), interpolation=cv2.INTER_LINEAR)
            Image.fromarray(arr).save(
                os.path.join(img_dir, f"img_{i:04d}.jpg"),
                quality=jpeg_quality)
            np.save(os.path.join(gt_dir, f"img_{i:04d}.npy"),
                    rng.random((h, w), np.float32))
        write_store(img_dir, gt_dir)
        # (u8, prepared, cached): u8 = the --u8-input transfer mode
        # (flip/resize on bytes, no host normalise); prepared = the baked
        # 1/8 store; cached = + bounded decoded-item LRU
        configs = [(False, False, False), (True, False, False),
                   (False, True, False), (True, True, False),
                   (True, True, True)]
        combos = []
        for u8, prep, cached in configs:
            cache = ItemCache(int(cache_mb * 1e6)) if cached else None
            ds = CrowdDataset(img_dir, gt_dir, gt_downsample=8,
                              phase="train", u8_output=u8,
                              prepared="auto" if prep else "off",
                              item_cache=cache)
            assert (ds.prepared is not None) == prep, ds.prepared_note
            tag = (("_u8" if u8 else "") + ("_prepared" if prep else "")
                   + ("_cache" if cached else ""))
            for wk in workers:
                batcher = ShardedBatcher(ds, batch, shuffle=True, seed=0,
                                         pad_multiple="auto", num_workers=wk)
                combos.append({"tag": tag, "wk": wk, "batcher": batcher,
                               "cache": cache, "rates": [],
                               "cache_delta": {"hits": 0, "misses": 0,
                                               "evictions": 0}})
        try:
            # warm fs cache / thread pools (a second epoch for the cached
            # combos so both flip orientations are mostly resident), then
            # time epochs ROUND-ROBIN across combos: host-load drift over
            # the suite's runtime lands on every combo instead of biasing
            # whichever config ran last (measured ~15% drift on the 2-cpu
            # bench host — enough to invert a sequential comparison)
            for c in combos:
                for we in range(2 if c["cache"] is not None else 1):
                    list(c["batcher"].epoch(we))
            for rep in range(repeats):
                for c in combos:
                    cache = c["cache"]
                    before = cache.stats() if cache is not None else None
                    t0 = time.perf_counter()
                    n_done = sum(b.num_valid
                                 for b in c["batcher"].epoch(2 + rep))
                    c["rates"].append(n_done / (time.perf_counter() - t0))
                    if cache is not None:
                        # attribute counter deltas to THIS combo's timed
                        # epochs — the cache object is shared across the
                        # config's worker counts, so cumulative totals
                        # describe no single measurement
                        after = cache.stats()
                        for k in c["cache_delta"]:
                            c["cache_delta"][k] += after[k] - before[k]
        finally:
            for c in combos:
                c["batcher"].close()  # 15 abandoned pools leaked threads
        for c in combos:
            rates = sorted(c["rates"])
            med = float(np.median(rates))
            extra = {}
            if c["cache"] is not None:
                d = dict(c["cache_delta"])
                got = d["hits"] + d["misses"]
                d["hit_rate"] = round(d["hits"] / got, 4) if got else None
                d["bytes"] = c["cache"].stats()["bytes"]
                extra["cache"] = d
            _emit(f"host_pipeline_{h}x{w}_b{batch}_w{c['wk']}{c['tag']}",
                  med, "images/sec", workers=c["wk"],
                  cpus=os.cpu_count(), n_images=n_images,
                  repeats=repeats,
                  img_per_s_min=round(rates[0], 3),
                  img_per_s_max=round(rates[-1], 3),
                  spread_pct=round(100 * (rates[-1] - rates[0])
                                   / max(med, 1e-9), 1),
                  **extra)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_eval_pipeline(jnp, compute_dtype, *, n_images, batch, lo, hi,
                        dominant, u8=False):
    """End-to-end ``evaluate()``: host materialisation + H2D transfer +
    device compute + windowed metric fetches, with the background-thread
    prefetch OFF vs ON (VERDICT r4 weak-1: eval used to pay every
    transfer in series with the device; this measures what
    prefetch_to_device buys on this host — expect a large move where
    dispatch latency dominates, small where H2D is already cheap).
    Metrics must be bit-identical across depths (asserted)."""
    import jax

    from can_tpu.data import ShardedBatcher
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_eval_step, make_global_batch, make_mesh
    from can_tpu.train import evaluate

    ndev = jax.device_count()
    mesh = make_mesh()
    ds = SynthVarResDataset(n_images, lo=lo, hi=hi, dominant=dominant, u8=u8)
    batcher = ShardedBatcher(ds, batch * ndev, shuffle=False, seed=0,
                             pad_multiple="auto", max_buckets=8,
                             remnant_sizes=True, batch_quantum=ndev)
    params = cannet_init(jax.random.key(0))
    ev = make_dp_eval_step(cannet_apply, mesh, compute_dtype=compute_dtype)
    put = lambda b: make_global_batch(b, mesh)

    # one throwaway pass pays the per-bucket-shape compiles
    evaluate(ev, params, batcher.epoch(0), put_fn=put,
             dataset_size=batcher.dataset_size)
    got = {}
    for depth in (0, 2):
        t0 = time.perf_counter()
        got[depth] = evaluate(ev, params, batcher.epoch(0), put_fn=put,
                              dataset_size=batcher.dataset_size,
                              prefetch=depth)
        got[depth]["img_per_s"] = n_images / (time.perf_counter() - t0)
    assert got[0]["mae"] == got[2]["mae"], "prefetch changed eval math"
    tag = ("f32" if compute_dtype is None else "bf16") + ("_u8" if u8 else "")
    dom = f"{dominant[0]}x{dominant[1]}"
    for depth in (0, 2):
        v = got[depth]["img_per_s"]
        _emit(f"eval_pipeline_varres_{dom}_b{batch}_{tag}_prefetch{depth}",
              v, "images/sec", per_chip_img_per_s=round(v / ndev, 3),
              buckets=batcher.describe_buckets())
    batcher.close()


def bench_plan_space(*, n_images=64, batches=(8, 16), repeats=5,
                     max_buckets=24,
                     launch_costs_mpx=None) -> list:
    """Plan-space ablation tier: SIMULATED (host-only, no device) sweep
    over the batch planner's candidate space on the suite's varres
    distribution, under the v5e HBM cap the r5 chip run hit.

    For every (batch, plan mode, launch pricing) candidate the tier
    builds the full epoch plan and reports predicted cost (the planner's
    own model) NEXT TO realized cost re-derived from the emitted
    schedule — the two must agree exactly (a divergence is a planner
    bug; ``predicted_eq_realized`` makes it greppable), plus the
    padding/schedule overheads, program/launch/lowered counts, and the
    plan build wall time, median-of-``repeats`` with min/max/spread and
    rounds interleaved round-robin across candidates (PR-3
    variance-aware style — build time is the only measured quantity
    here, and host drift lands on every candidate instead of the last).

    The b16 x legacy x 2.0-Mpx row reproduces the r5 chip sweep's 30.67%
    schedule overhead bit-exactly on any host; the b16 x cost x
    device-pricing row is the round-8 headline (VERDICT r5 item 7).
    """
    from can_tpu.cli.common import (
        DEVICE_LAUNCH_COST_MPX,
        hbm_bytes_for_device_kind,
        max_launch_pixels,
    )
    from can_tpu.data import ShardedBatcher

    if launch_costs_mpx is None:
        launch_costs_mpx = (2.0, 0.5, DEVICE_LAUNCH_COST_MPX)
    # the r5 chip configuration: v5e spec HBM (the spec fallback was
    # what capped that run), bf16, single chip
    cap = max_launch_pixels(bf16=True, shards=1,
                            hbm_bytes=hbm_bytes_for_device_kind("TPU v5e"))
    ds = SynthVarResDataset(n_images)
    combos = [{"batch": b, "mode": mode, "mpx": mpx, "times": []}
              for b in batches
              for mode in ("legacy", "cost")
              for mpx in launch_costs_mpx]

    def build(c):
        t0 = time.perf_counter()
        sb = ShardedBatcher(ds, c["batch"], shuffle=True, seed=0,
                            pad_multiple="auto", max_buckets=max_buckets,
                            remnant_sizes=True, batch_quantum=1,
                            launch_cost_px=c["mpx"] * 1e6,
                            max_launch_px=cap, plan_mode=c["mode"])
        sb.planner_stats(1)  # force the plan + schedule walk
        return sb, time.perf_counter() - t0

    records = []
    for rep in range(repeats):
        for c in combos:
            sb, dt = build(c)
            c["times"].append(dt)
            if rep == repeats - 1:
                c["batcher"] = sb
    for c in combos:
        sb = c["batcher"]
        st = sb.planner_stats(1)
        times = sorted(c["times"])
        med = float(np.median(times))
        name = (f"plan_space_varres_b{c['batch']}_{c['mode']}"
                f"_L{str(c['mpx']).replace('.', 'p')}")
        extra = dict(
            plan_mode=c["mode"], launch_cost_mpx=c["mpx"],
            batch=c["batch"], max_buckets=max_buckets,
            max_launch_mpx=round(cap / 1e6, 3),
            padding_overhead=st["padding_overhead"],
            programs=st["program_count"],
            launches=st["batches_per_epoch"],
            lowered_launches=st.get("lowered_launches"),
            menu_sizes=st.get("menu_sizes"),
            predicted_cost_mpx=round(st.get("plan_cost_px",
                                            st["realized_cost_px"]) / 1e6, 3),
            realized_cost_mpx=round(st["realized_cost_px"] / 1e6, 3),
            predicted_eq_realized=bool(
                abs(st.get("plan_cost_px", st["realized_cost_px"])
                    - st["realized_cost_px"]) < 1.0),
            plan_s=round(med, 4),
            plan_s_min=round(times[0], 4), plan_s_max=round(times[-1], 4),
            spread_pct=round(100 * (times[-1] - times[0])
                             / max(med, 1e-9), 1),
            buckets=sb.describe_buckets(),
        )
        _emit(name, st["schedule_overhead"], "overhead_frac", **extra)
        records.append({"metric": name, "value": st["schedule_overhead"],
                        "unit": "overhead_frac", **extra})
    return records


def bench_perf_ledger(jnp, compute_dtype, *, n_images=32, batch=2,
                      lo=64, hi=160, dominant=(128, 160),
                      out_path=None) -> list:
    """Perf-attribution tier: run the varres pipeline with the
    ProgramCostLedger armed and emit the ledger as bench records + one
    committed artifact (``PERF_LEDGER_cpu_r09.json``).

    The per-program flops/bytes come from XLA ``cost_analysis()`` and are
    DETERMINISTIC for a given jax version and config — which is what makes
    this tier gateable: ``tools/ci_bench_gate.sh`` compare-only mode
    (CI_BENCH_ONLY=perf) trips when a model or XLA change silently moves a
    compiled program's cost.  MFU / mean_s ride along as extra fields
    (informational — timing noise on the CPU box, and the CPU peak is
    labelled NOMINAL), value = gflops is what gates.  Small shapes by
    design, in quick AND full mode: the ledger's bookkeeping is
    shape-agnostic, and chip-scale numbers belong to telemetry_report on
    real runs, not this CPU gate.
    """
    import jax

    from can_tpu import obs
    from can_tpu.cli.common import DEVICE_LAUNCH_COST_MPX
    from can_tpu.data import ShardedBatcher
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
        train_one_epoch,
    )

    ndev = jax.device_count()
    mesh = make_mesh()
    ds = SynthVarResDataset(n_images, lo=lo, hi=hi, dominant=dominant)
    batcher = ShardedBatcher(ds, batch * ndev, shuffle=True, seed=0,
                             pad_multiple="auto", max_buckets=8,
                             remnant_sizes=True, batch_quantum=ndev,
                             launch_cost_px=DEVICE_LAUNCH_COST_MPX * 1e6)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    state = create_train_state(cannet_init(jax.random.key(0)), opt)
    step = make_dp_train_step(cannet_apply, opt, mesh,
                              compute_dtype=compute_dtype)
    put = lambda b: make_global_batch(b, mesh)

    tel = _TELEMETRY if _TELEMETRY is not None else obs.Telemetry()
    prev_ledger = tel.ledger
    # The suite shares one Telemetry across tiers, and earlier tiers (same
    # synth distribution, fresh jit steps) may already hold this tier's
    # exact train_step signatures in signature_registry — which would
    # suppress ledger.register for those programs (dropping them from the
    # gate artifact) AND fold their genuine first-call compile time into
    # the steady-state means.  Scope a clean registry for the tier.
    prev_reg = tel.signature_registry.pop("train_step", None)
    tel.ledger = ledger = obs.ProgramCostLedger(
        compute="bf16" if compute_dtype is not None else "f32",
        plan_launch_cost_px=DEVICE_LAUNCH_COST_MPX * 1e6)
    try:
        # epoch 0 pays the compiles (registering every program's cost);
        # epoch 1 provides the steady-state timings MFU joins against
        state, _ = train_one_epoch(step, state, batcher.epoch(0),
                                   put_fn=put, epoch=0,
                                   show_progress=False, telemetry=tel)
        state, _ = train_one_epoch(step, state, batcher.epoch(1),
                                   put_fn=put, epoch=1,
                                   show_progress=False, telemetry=tel)
    finally:
        tel.ledger = prev_ledger
        if prev_reg is not None:
            tel.signature_registry["train_step"] = prev_reg
        else:
            tel.signature_registry.pop("train_step", None)
        batcher.close()

    tag = "f32" if compute_dtype is None else "bf16"
    records = []
    for r in ledger.rows():
        if r["name"] != "train_step" or not r["flops"]:
            continue
        b_, h_, w_ = r["shape"][0], r["shape"][1], r["shape"][2]
        rec = {"metric": f"perf_ledger_train_{h_}x{w_}_b{b_}_{tag}",
               "value": round(r["flops"] / 1e9, 3), "unit": "gflops",
               "bytes_gb": (round(r["bytes_accessed"] / 1e9, 4)
                            if r["bytes_accessed"] else None),
               "intensity_flop_per_byte": r["intensity"],
               "roofline": r["roofline"],
               "mfu": r["mfu"], "bw_util": r["bw_util"],
               "mean_step_s": r["mean_s"], "launches": r["launches"]}
        records.append(rec)
        if _TELEMETRY is not None:
            _TELEMETRY.emit("bench", **rec)
        print(json.dumps(rec), flush=True)
    summary = ledger.summary()
    out = out_path or os.environ.get("BENCH_PERF_LEDGER_OUT")
    if not out:
        # the committed gate baseline is only the default for an EXPLICIT
        # perf-only run (the documented regeneration command); the perf
        # tier riding along in a full suite run writes the bench_serve
        # -style _local name instead of silently dirtying the checkout
        out = ("PERF_LEDGER_cpu_r09.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "perf"
               else "PERF_LEDGER_local.json")
    doc = {"metric": "perf_ledger",
           "config": {"n_images": n_images, "batch": batch, "lo": lo,
                      "hi": hi, "dominant": list(dominant), "tag": tag,
                      "devices": ndev,
                      "platform": jax.devices()[0].platform},
           "summary": summary,
           "detail": ledger.rows(),
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# perf ledger: {len(records)} programs, "
          f"mfu_weighted={summary.get('mfu_weighted')} "
          f"(peak {summary.get('peak_source')}) -> {out}", flush=True)
    return records


def bench_bn(jnp, compute_dtype, *, b=2, h=64, w=64, steps=3,
             out_path=None) -> list:
    """BatchNorm-moments tier: the syncBN train step per moments path —
    plain (no-BN ceiling) vs masked-twopass vs onepass vs pallas
    (interpreted only when the CPU was requested) — attributed through the
    ProgramCostLedger.

    Two gateable records per variant, both from deterministic XLA
    ``cost_analysis()`` (same contract as the perf tier):

    * unit ``gflops`` — two-sided (a BN path must not silently gain or
      lose work);
    * unit ``gbytes`` — gated UPWARD only (bytes growing = the moments
      path lost a fusion; shrinking is the improvement this tier exists
      to hold).  The r10 acceptance pin rides this artifact: the onepass
      rows must show strictly fewer bytes than the twopass rows
      (tests/test_batchnorm.py::TestBNBenchArtifact).

    img/s and MFU ride as informational extras (CPU timing noise — the
    committed artifact's numbers gate nothing).  A running-stats parity
    delta vs twopass is recorded per variant: the bench double-checks the
    test suite's numerics pin on the exact shapes it prices.
    """
    import functools

    import jax

    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init, init_batch_stats
    from can_tpu.models.cannet import LocalOps
    from can_tpu.obs.costs import ProgramCostLedger
    from can_tpu.ops.bn_moments import make_bn_ops
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import (
        batch_signature,
        create_train_state,
        make_lr_schedule,
        make_optimizer,
    )

    from can_tpu.utils import pallas_interpret

    ndev = jax.device_count()
    mesh = make_mesh()
    # from the REQUESTED platform: a TPU run cannot end up interpreted
    interpret = pallas_interpret()
    print(f"# bn tier: pallas kernel "
          f"{'INTERPRETED' if interpret else 'compiled'}", flush=True)
    rng = np.random.default_rng(0)
    local_b = b * ndev
    # real padding in the batch so the MASKED moments are what's priced:
    # the last /8-row of every map is bucket padding and the final slot is
    # a dead fill slot — all-ones masks would let XLA fold the multiply
    pm = np.ones((local_b, h // 8, w // 8, 1), np.float32)
    pm[:, -1] = 0.0
    sm = np.ones((local_b,), np.float32)
    sm[-1] = 0.0
    batch = Batch(
        image=rng.normal(size=(local_b, h, w, 3)).astype(np.float32),
        dmap=rng.uniform(size=(local_b, h // 8, w // 8, 1)).astype(np.float32),
        pixel_mask=pm,
        sample_mask=sm,
    )
    gbatch = make_global_batch(batch, mesh)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    plain_params = cannet_init(jax.random.key(0))
    bn_params = cannet_init(jax.random.key(0), batch_norm=True)

    variants = [("plain", "none"), ("syncbn_twopass", "twopass"),
                ("syncbn_onepass", "onepass"), ("syncbn_pallas", "pallas")]
    tag = "f32" if compute_dtype is None else "bf16"
    compute = "bf16" if compute_dtype is not None else "f32"
    records = []
    detail = []
    stats_by_variant = {}
    for name, impl in variants:
        if impl == "pallas" and ndev > 1:
            # same refusal as the train CLI: pallas_call has no GSPMD
            # partitioning rule, and this tier prices the jit-sharded dp
            # step — a forced gather would corrupt the A/B bytes.  The
            # committed baseline is devices=1 (like the perf tier).
            print(f"# bn tier: skipping {name} on the {ndev}-device GSPMD "
                  "dp step (no pallas partitioning rule)", flush=True)
            continue
        ledger = ProgramCostLedger(compute=compute)
        if impl == "none":
            apply_fn, params, stats = cannet_apply, plain_params, None
        else:
            bn_ops = make_bn_ops(impl, interpret=interpret)
            apply_fn = (cannet_apply if bn_ops is None else
                        functools.partial(cannet_apply,
                                          ops=LocalOps(bn_ops=bn_ops)))
            params, stats = bn_params, init_batch_stats(bn_params)
        state = create_train_state(params, opt, stats)
        step = make_dp_train_step(apply_fn, opt, mesh, donate=False,
                                  compute_dtype=compute_dtype)
        # deterministic cost BEFORE the timed loop (registration also
        # pays the compile, so the loop below times steady state)
        ledger.register(name, batch_signature(gbatch), fn=step,
                        args=(state, gbatch))
        state, metrics = step(state, gbatch)  # warm + the parity state
        float(jax.device_get(metrics["loss"]))
        if state.batch_stats is not None:
            stats_by_variant[name] = jax.device_get(state.batch_stats)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, gbatch)
        float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        ledger.observe(name, gbatch["image"].shape, dt, n=steps)
        (row,) = ledger.rows()
        parity = None
        if name in stats_by_variant and "syncbn_twopass" in stats_by_variant \
                and name != "syncbn_twopass":
            ref = stats_by_variant["syncbn_twopass"]
            got = stats_by_variant[name]
            # scale-relative per leaf (max delta over the leaf's own max
            # magnitude): elementwise relative error on near-zero running
            # -stat entries would read bf16 rounding as divergence
            parity = max(
                float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                      / max(float(np.max(np.abs(np.asarray(b)))), 1e-6))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)))
        extra = dict(
            bytes_gb=(round(row["bytes_accessed"] / 1e9, 4)
                      if row["bytes_accessed"] else None),
            img_per_s=round(local_b * steps / dt, 2),
            mean_step_s=row["mean_s"], mfu=row["mfu"],
            roofline=row["roofline"], interpret=(impl == "pallas"
                                                 and interpret),
            parity_vs_twopass_max_rel=(round(parity, 6)
                                       if parity is not None else None),
        )
        stem = f"bn_train_{h}x{w}_b{b}_{tag}_{name}"
        recs = []
        if row["flops"]:
            # same rule as bytes below: a backend that stops reporting
            # flops must fail the gate loudly (missing metric -> removed/
            # min-overlap), never pass vacuously on an incomparable null
            recs.append({"metric": stem, "unit": "gflops",
                         "value": round(row["flops"] / 1e9, 3), **extra})
        if row["bytes_accessed"]:
            recs.append({"metric": f"bn_bytes_{h}x{w}_b{b}_{tag}_{name}",
                         "value": round(row["bytes_accessed"] / 1e9, 4),
                         "unit": "gbytes", "variant": name})
        for r in recs:
            records.append(r)
            if _TELEMETRY is not None:
                _TELEMETRY.emit("bench", **r)
            print(json.dumps(r), flush=True)
        detail.extend(ledger.rows())

    out = out_path or os.environ.get("BENCH_BN_OUT")
    if not out:
        # committed gate baseline only for the EXPLICIT bn-only run, same
        # rule as the perf tier's artifact
        out = ("BENCH_BN_cpu_r10.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "bn"
               else "BENCH_BN_local.json")
    doc = {"metric": "bench_bn",
           "config": {"b": b, "h": h, "w": w, "steps": steps, "tag": tag,
                      "devices": ndev,
                      "platform": jax.devices()[0].platform},
           "detail": detail,
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# bn tier: {len(records)} records -> {out}", flush=True)
    return records


def bench_serve_fleet(*, replicas=2, modes=("f32", "bf16", "int8"),
                      n_requests=32, repeats=3, max_batch=4,
                      rate_rps=None, out_path=None) -> list:
    """Serving-fleet tier: the FULL fleet stack (queue -> batcher ->
    work-stealing replicas) per ``--serve-dtype`` mode, open-loop at a
    FIXED offered rate so p99 is comparable run-to-run (an adaptive rate
    would change the offered load between baseline and fresh run, making
    the latency gate meaningless).

    Per mode: ``serve_fleet_p99_<mode>`` (unit ``ms``: bench_compare
    gates latency UPWARD-only) and ``serve_fleet_rps_<mode>`` (unit
    ``req/s``: gates downward), both median-of-``repeats`` with the
    measured min/max ``spread_pct`` recorded — the gate's noise floor,
    same discipline as the host tier.  Quantized modes also record their
    f32 parity-ladder grade (context, never gated: it is deterministic
    and pinned by tests/test_fleet.py instead)."""
    import statistics

    import jax

    from bench_serve import run_open_loop
    from can_tpu.models import cannet_init
    from can_tpu.obs import Telemetry
    from can_tpu.serve import (
        CountService,
        FleetEngine,
        ServeEngine,
        parity_report,
        prepare_image,
    )
    from can_tpu.serve.quant import param_bytes

    if rate_rps is None:
        # BELOW the CPU gate box's ~5 req/s fleet capacity on purpose: an
        # offered rate past saturation turns p99 into an end-of-arrivals
        # backlog measure that grows with request count — stable gating
        # needs the queue to drain between bursts (~75% utilization).
        # Real-chip sweeps override BENCH_FLEET_RATE upward.
        rate_rps = float(os.environ.get("BENCH_FLEET_RATE", "4"))
    if len(jax.devices()) < replicas:
        # the tier pins one device per replica; a plain 1-device suite
        # run must skip it, not abort the whole suite (the CI gate runs
        # it via BENCH_SUITE_PLATFORM=cpu8)
        print(f"# fleet tier skipped: {len(jax.devices())} device(s) < "
              f"replicas={replicas} (use BENCH_SUITE_PLATFORM=cpu8 or a "
              f"multi-chip host)", flush=True)
        return []
    params = cannet_init(jax.random.key(0))
    sizes = [(64, 64), (96, 64)]
    ladder = (tuple(sorted({h for h, _ in sizes})),
              tuple(sorted({w for _, w in sizes})))
    buckets = [(h, w) for h in ladder[0] for w in ladder[1]]
    rng = np.random.default_rng(7)
    images = [prepare_image(
        (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
        for h, w in sizes]
    records = []
    ref_engine = None
    for mode in modes:
        tel = Telemetry()
        fleet = FleetEngine(params, replicas=replicas, serve_dtype=mode,
                            telemetry=tel, name=f"fleet_{mode}")
        svc = CountService(fleet, max_batch=max_batch, max_wait_ms=2.0,
                           queue_capacity=256, bucket_ladder=ladder,
                           telemetry=tel)
        warm = svc.warmup(buckets)
        parity = None
        if mode != "f32":
            if ref_engine is None:
                ref_engine = ServeEngine(params, telemetry=tel,
                                         name="fleet_parity_f32")
            quant = ServeEngine(params, serve_dtype=mode, telemetry=tel,
                                name=f"fleet_parity_{mode}")
            parity = parity_report(quant, ref_engine, images)
        p99s, rpss, rejects = [], [], 0
        with svc:
            for rep in range(repeats):
                o = run_open_loop(svc, images, n_requests, rate_rps,
                                  deadline_ms=30_000, seed=rep)
                p99s.append(o["p99_ms"])
                rpss.append(o["throughput_rps"])
                rejects += o["rejected"]
        st = svc.stats()
        spread = lambda xs: round(  # noqa: E731
            100.0 * (max(xs) - min(xs)) / max(statistics.median(xs), 1e-9),
            1)
        # compile budget is menu-aware since r14: one program per
        # (bucket, menu size, dtype) per replica (can_tpu/sched)
        menu_len = len(svc.sched.menu) if svc.sched is not None else 1
        base = {"replicas": replicas, "serve_dtype": mode,
                "offered_rps": rate_rps, "requests": n_requests,
                "repeats": repeats, "rejects": rejects,
                "warmup_compiles": warm["compiles"],
                "compiles_bounded":
                    fleet.compile_count
                    <= len(buckets) * replicas * menu_len,
                "param_bytes": param_bytes(
                    fleet.replicas[0].engine.params),
                "replica_batches": {k: v["batches"]
                                    for k, v in st["replicas"].items()}}
        if parity is not None:
            base["parity_grade"] = parity["grade"]
            base["parity_worst_rel"] = parity["worst_rel_count_delta"]
        rec_p99 = {"metric": f"serve_fleet_p99_{mode}",
                   "value": round(statistics.median(p99s), 3),
                   "unit": "ms", "spread_pct": spread(p99s), **base}
        rec_rps = {"metric": f"serve_fleet_rps_{mode}",
                   "value": round(statistics.median(rpss), 2),
                   "unit": "req/s", "spread_pct": spread(rpss), **base}
        for rec in (rec_p99, rec_rps):
            records.append(rec)
            if _TELEMETRY is not None:
                _TELEMETRY.emit("bench", **rec)
            print(json.dumps(rec), flush=True)
    out = out_path or os.environ.get("BENCH_FLEET_OUT")
    if not out:
        # committed gate baseline only for an explicit fleet-only run
        # (same overwrite rule as the perf/bn tiers)
        out = ("BENCH_FLEET_cpu_r11.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "fleet"
               else "BENCH_FLEET_local.json")
    doc = {"metric": "serve_fleet",
           "config": {"replicas": replicas, "modes": list(modes),
                      "requests": n_requests, "repeats": repeats,
                      "rate_rps": rate_rps, "max_batch": max_batch,
                      "buckets": [f"{h}x{w}" for h, w in buckets],
                      "platform": jax.devices()[0].platform},
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# fleet tier: {len(records)} records over {len(modes)} modes "
          f"-> {out}", flush=True)
    return records


def bench_sched(*, n_requests=32, repeats=3, max_batch=4,
                max_wait_ms=50.0, out_path=None) -> list:
    """Scheduling-core tier (r14): serve fill % / p99 / time-to-flush at
    LOW and MIXED load through the priced menu+flush core
    (can_tpu/sched), with the pre-r14 timer+pad-to-max arm measured in
    the SAME run as context — the committed artifact is the receipt
    that fill strictly improved at both loads with p99 no worse.

    Single engine on one device (runs on the plain CI box: no cpu8);
    mixed load reuses the fleet tier's offered-rate discipline (fixed
    rate below saturation so p99 is comparable run-to-run).  Gated
    records: ``serve_sched_fill_{low,mixed}`` (unit ``fill_pct``,
    bench_compare gates DOWNWARD only — fill dropping is the
    regression), ``serve_sched_p99_{low,mixed}`` (ms, upward),
    ``serve_sched_ttf_p95_low`` (ms, upward: submit->assembly wait at
    low load, the time-to-flush distribution vs the old timer), and
    ``serve_sched_rps_mixed`` (req/s, downward).  Each record carries
    the legacy arm's number as ``legacy_*`` context plus the
    predicted==realized receipt (``cost_mismatches`` must be 0)."""
    import statistics

    import jax

    from bench_serve import run_open_loop
    from can_tpu.models import cannet_init
    from can_tpu.obs import Telemetry
    from can_tpu.serve import CountService, ServeEngine, prepare_image

    low_rate = float(os.environ.get("BENCH_SCHED_LOW_RATE", "2"))
    mixed_rate = float(os.environ.get("BENCH_SCHED_MIXED_RATE", "4"))
    params = cannet_init(jax.random.key(0))
    sizes = [(64, 64), (96, 64)]
    ladder = (tuple(sorted({h for h, _ in sizes})),
              tuple(sorted({w for _, w in sizes})))
    buckets = [(h, w) for h in ladder[0] for w in ladder[1]]
    rng = np.random.default_rng(7)
    images = [prepare_image(
        (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
        for h, w in sizes]

    def run_arm(tag, **svc_kw):
        mism = [0]
        tel = Telemetry([_SchedMismatchSink(mism)])
        engine = ServeEngine(params, telemetry=tel, name=f"sched_{tag}")
        svc = CountService(engine, max_batch=max_batch,
                           max_wait_ms=max_wait_ms, queue_capacity=256,
                           bucket_ladder=ladder, telemetry=tel, **svc_kw)
        warm = svc.warmup(buckets)
        out = {"warmup_compiles": warm["compiles"]}
        with svc:
            for phase, rate in (("low", low_rate), ("mixed", mixed_rate)):
                p99s, rpss, fills, ttfs = [], [], [], []
                for rep in range(repeats):
                    before = svc.stats()
                    o = run_open_loop(svc, images, n_requests, rate,
                                      deadline_ms=30_000, seed=rep)
                    after = svc.stats()
                    slots = after["batch_slots"] - before["batch_slots"]
                    valid = after["batch_valid"] - before["batch_valid"]
                    p99s.append(o["p99_ms"])
                    rpss.append(o["throughput_rps"])
                    fills.append(100.0 * valid / max(slots, 1))
                    if o["queue_wait_p95_ms"] is not None:
                        ttfs.append(o["queue_wait_p95_ms"])
                out[phase] = {"p99_ms": p99s, "rps": rpss, "fill": fills,
                              "ttf_p95_ms": ttfs}
        out["cost_mismatches"] = mism[0]
        out["compile_count"] = engine.compile_count
        return out

    # the priced arm (the r14 default) and the pre-r14 timer+pad arm,
    # same run, same offered traffic — the improvement receipt
    sched_arm = run_arm("priced")
    legacy_arm = run_arm("legacy", menu_budget=1, flush_policy="timer")

    med = statistics.median
    spread = lambda xs: round(  # noqa: E731
        100.0 * (max(xs) - min(xs)) / max(abs(med(xs)), 1e-9), 1)
    base = {"requests": n_requests, "repeats": repeats,
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "low_rate_rps": low_rate, "mixed_rate_rps": mixed_rate,
            "conditions": "fleet_r11-style fixed offered rate, 30s "
                          "deadline, buckets 64x64/96x64",
            "cost_mismatches": sched_arm["cost_mismatches"],
            "warmup_compiles": sched_arm["warmup_compiles"]}
    records = []

    def rec(metric, vals, unit, **extra):
        records.append({"metric": metric, "value": round(med(vals), 3),
                        "unit": unit, "spread_pct": spread(vals),
                        **base, **extra})

    rec("serve_sched_fill_low", sched_arm["low"]["fill"], "fill_pct",
        legacy_fill=round(med(legacy_arm["low"]["fill"]), 2))
    rec("serve_sched_fill_mixed", sched_arm["mixed"]["fill"], "fill_pct",
        legacy_fill=round(med(legacy_arm["mixed"]["fill"]), 2))
    rec("serve_sched_p99_low", sched_arm["low"]["p99_ms"], "ms",
        legacy_p99_ms=round(med(legacy_arm["low"]["p99_ms"]), 3))
    rec("serve_sched_p99_mixed", sched_arm["mixed"]["p99_ms"], "ms",
        legacy_p99_ms=round(med(legacy_arm["mixed"]["p99_ms"]), 3))
    rec("serve_sched_ttf_p95_low", sched_arm["low"]["ttf_p95_ms"], "ms",
        legacy_ttf_p95_ms=round(med(legacy_arm["low"]["ttf_p95_ms"]), 3))
    rec("serve_sched_rps_mixed", sched_arm["mixed"]["rps"], "req/s",
        legacy_rps=round(med(legacy_arm["mixed"]["rps"]), 2))
    for r in records:
        if _TELEMETRY is not None:
            _TELEMETRY.emit("bench", **r)
        print(json.dumps(r), flush=True)

    out = out_path or os.environ.get("BENCH_SCHED_OUT")
    if not out:
        # committed gate baseline only for an explicit sched-only run
        # (the perf/bn/fleet/autoscale no-self-overwrite rule, 5th use)
        out = ("BENCH_SCHED_cpu_r14.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "sched"
               else "BENCH_SCHED_local.json")
    doc = {"metric": "serve_sched",
           "config": {**base,
                      "platform": jax.devices()[0].platform},
           "legacy_arm": {k: legacy_arm[k] for k in ("low", "mixed",
                                                     "warmup_compiles",
                                                     "cost_mismatches")},
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# sched tier: {len(records)} records -> {out}", flush=True)
    return records


class _SchedMismatchSink:
    """Counts serve.batch events whose predicted cost != realized cost —
    the core's invariant, carried as a receipt in the sched artifact."""

    def __init__(self, counter):
        self._c = counter

    def emit(self, event):
        from can_tpu.sched.core import costs_match

        if event.get("kind") != "serve.batch":
            return
        p = event.get("payload", {})
        if not costs_match(p.get("predicted_cost_px"),
                           p.get("realized_cost_px")):
            self._c[0] += 1

    def close(self):
        pass


def _run_stream_load(service, images, *, n_streams, frames, rate_rps,
                     deadline_ms, seed, use_streams=True, seqs=None):
    """Open-loop stream driver: ``n_streams`` synthetic cameras sending
    ``frames`` frames each at an aggregate Poisson ``rate_rps``, with
    monotonic per-stream frame_seq (``use_streams=False`` is the legacy
    no-session arm: the SAME traffic as stateless requests).  Consults
    the fault injector's stream grammar (``stream_burst`` rate spikes,
    ``frame_gap`` dup/out-of-order delivery) per frame, like the chaos
    test's driver.  Returns fresh/degraded latencies, stalenesses, and
    rejects by reason."""
    from can_tpu.serve import RejectedError
    from can_tpu.testing.faults import active_injector

    rng = np.random.default_rng(seed)
    seqs = seqs if seqs is not None else {k: 0 for k in range(n_streams)}
    tickets = []

    def submit(k, seq_override=None):
        sid = f"cam{k}"
        if not use_streams:
            tickets.append(service.submit(images[k % len(images)],
                                          deadline_ms=deadline_ms))
            return
        if seq_override is None:
            seqs[k] += 1
            fs = seqs[k]
        else:
            fs = seq_override
        tickets.append(service.submit(images[k % len(images)],
                                      deadline_ms=deadline_ms,
                                      stream_id=sid, frame_seq=fs))

    t0 = time.perf_counter()
    next_t = 0.0
    for f in range(frames):
        for k in range(n_streams):
            next_t += float(rng.exponential(1.0 / rate_rps))
            sleep = t0 + next_t - time.perf_counter()
            if sleep > 0:
                time.sleep(sleep)
            inj = active_injector()
            if inj is not None:
                d = inj.on_stream_frame(stream=f"cam{k}", frame=f + 1)
                if d is not None and d["kind"] == "stream_burst":
                    for _ in range(d["burst"]):
                        submit(k)
                elif d is not None:  # frame_gap
                    submit(k, seq_override=(seqs[k] if d["mode"] == "dup"
                                            else max(seqs[k] - 2, 0)))
            submit(k)
    fresh, degraded, staleness = [], [], []
    rejects = {}
    for t in tickets:
        try:
            res = t.result(timeout=120.0)
            if getattr(res, "degraded", False):
                degraded.append(res.latency_s)
                if res.staleness_s is not None:
                    staleness.append(res.staleness_s)
            else:
                fresh.append(res.latency_s)
        except RejectedError as e:
            rejects[e.reason] = rejects.get(e.reason, 0) + 1
    wall = time.perf_counter() - t0
    return {"submitted": len(tickets), "fresh": fresh,
            "degraded": degraded, "staleness": staleness,
            "rejects": rejects, "wall_s": wall,
            "served_rps": (len(fresh) + len(degraded)) / max(wall, 1e-9)}


def bench_stream(*, n_streams=4, frames=8, repeats=3, max_batch=4,
                 out_path=None) -> list:
    """Streaming-session tier (r15): sustained per-stream p99 and
    streams-per-device at a fixed deadline, and the degradation ladder
    under 2x overload — with the legacy (no-session) arm driven by the
    SAME traffic in the SAME run.  The committed artifact is the
    receipt that the ladder ENGAGES under overload (degraded fraction
    > 0 where the legacy arm can only reject) and that degraded answers
    are CHEAP (their p99 is the EWMA-lookup cost, not a launch).

    Phases per arm: capacity probe (a back-to-back burst measures the
    box's served rate — "2x overload" means 2x THAT, not 2x an
    arbitrary offered rate), sustained at ``BENCH_STREAM_RATE`` (default
    4 req/s aggregate, below capacity), then overload at 2x measured
    capacity.  Gated records: ``serve_stream_p99_sustained`` (ms,
    upward), ``serve_stream_rps_sustained`` (req/s, downward),
    ``serve_stream_streams_per_device`` (unit ``streams``, downward-
    gated — how many fixed-rate cameras one device sustains inside the
    deadline), ``serve_stream_degraded_p99_2x`` (ms, upward: degraded
    answers must stay cheap) and ``serve_stream_fresh_p99_2x`` (ms,
    upward).  ``serve_stream_degraded_frac_2x`` (unit ``frac``) rides
    ungated as the ladder-engagement receipt, with the legacy arm's
    reject fraction as context."""
    import statistics

    import jax

    from can_tpu.models import cannet_init
    from can_tpu.obs import Telemetry
    from can_tpu.serve import CountService, ServeEngine, prepare_image

    rate = float(os.environ.get("BENCH_STREAM_RATE", "4"))
    deadline_ms = float(os.environ.get("BENCH_STREAM_DEADLINE_MS", "2000"))
    params = cannet_init(jax.random.key(0))
    sizes = [(64, 64)]
    ladder = ((64,), (64,))
    rng = np.random.default_rng(7)
    images = [prepare_image(
        (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
        for h, w in sizes]

    def run_arm(tag, use_streams):
        tel = Telemetry()
        engine = ServeEngine(params, telemetry=tel, name=f"stream_{tag}")
        svc = CountService(engine, max_batch=max_batch, max_wait_ms=5.0,
                           queue_capacity=64, bucket_ladder=ladder,
                           telemetry=tel,
                           degrade_policy="priced" if use_streams
                           else "off")
        svc.warmup(sizes)
        out = {"sustained": [], "overload": []}
        with svc:
            # capacity probe: a burst of stateless requests back to
            # back — the served rate the overload phase doubles
            burst = [svc.submit(images[0], deadline_ms=30_000)
                     for _ in range(4 * max_batch)]
            t0 = time.perf_counter()
            for t in burst:
                t.result(timeout=120.0)
            cap_rps = len(burst) / max(time.perf_counter() - t0, 1e-9)
            out["capacity_rps"] = round(cap_rps, 2)
            seqs = {k: 0 for k in range(n_streams)}
            for rep in range(repeats):
                out["sustained"].append(_run_stream_load(
                    svc, images, n_streams=n_streams, frames=frames,
                    rate_rps=rate, deadline_ms=deadline_ms, seed=rep,
                    use_streams=use_streams, seqs=seqs))
                # overload runs LONGER than sustained (4x the frames):
                # the ladder triggers on accumulated backlog, and a
                # fraction-of-a-second burst would end before the
                # per-stream outstanding ever crossed its allowance
                out["overload"].append(_run_stream_load(
                    svc, images, n_streams=n_streams, frames=4 * frames,
                    rate_rps=2.0 * cap_rps, deadline_ms=deadline_ms,
                    seed=100 + rep, use_streams=use_streams, seqs=seqs))
            out["stream_stats"] = svc.stats()["streams"]
        return out

    stream_arm = run_arm("sessions", True)
    legacy_arm = run_arm("legacy", False)

    med = statistics.median
    p99 = lambda xs: (  # noqa: E731
        float(np.percentile(np.asarray(xs, np.float64) * 1e3, 99))
        if xs else None)
    spread = lambda xs: round(  # noqa: E731
        100.0 * (max(xs) - min(xs)) / max(abs(med(xs)), 1e-9), 1)

    sus_p99 = [p99(r["fresh"]) for r in stream_arm["sustained"]]
    sus_rps = [r["served_rps"] for r in stream_arm["sustained"]]
    # streams-per-device at the fixed deadline: how many cameras at
    # this per-stream frame rate one device absorbs while serving
    # inside the deadline — served rate over the per-stream offered rate
    per_stream_rate = rate / n_streams
    spd = [r["served_rps"] / per_stream_rate
           for r in stream_arm["sustained"]]
    ov_fresh_p99 = [p99(r["fresh"]) for r in stream_arm["overload"]]
    ov_deg_p99 = [p99(r["degraded"]) for r in stream_arm["overload"]
                  if r["degraded"]]
    deg_frac = [len(r["degraded"]) / max(r["submitted"], 1)
                for r in stream_arm["overload"]]
    leg_sus_p99 = [p99(r["fresh"]) for r in legacy_arm["sustained"]]
    leg_rej_frac = [sum(r["rejects"].values()) / max(r["submitted"], 1)
                    for r in legacy_arm["overload"]]

    base = {"n_streams": n_streams, "frames": frames, "repeats": repeats,
            "max_batch": max_batch, "rate_rps": rate,
            "deadline_ms": deadline_ms,
            "capacity_rps": stream_arm["capacity_rps"],
            "conditions": "single device, 64x64 bucket, capacity-probed "
                          "2x overload, sessions vs legacy same run"}
    records = []

    def rec(metric, vals, unit, **extra):
        vals = [v for v in vals if v is not None]
        if not vals:
            return
        records.append({"metric": metric, "value": round(med(vals), 3),
                        "unit": unit, "spread_pct": spread(vals),
                        **base, **extra})

    rec("serve_stream_p99_sustained", sus_p99, "ms",
        legacy_p99_ms=(round(med([x for x in leg_sus_p99
                                  if x is not None]), 3)
                       if any(x is not None for x in leg_sus_p99)
                       else None))
    rec("serve_stream_rps_sustained", sus_rps, "req/s")
    rec("serve_stream_streams_per_device", spd, "streams")
    leg_ov_p99 = [p99(r["fresh"]) for r in legacy_arm["overload"]]
    rec("serve_stream_fresh_p99_2x", ov_fresh_p99, "ms",
        legacy_p99_2x_ms=(round(med([x for x in leg_ov_p99
                                     if x is not None]), 3)
                          if any(x is not None for x in leg_ov_p99)
                          else None))
    rec("serve_stream_degraded_p99_2x", ov_deg_p99, "ms")
    rec("serve_stream_degraded_frac_2x", deg_frac, "frac",
        legacy_reject_frac=round(med(leg_rej_frac), 4),
        stream_stats=stream_arm["stream_stats"])
    for r in records:
        if _TELEMETRY is not None:
            _TELEMETRY.emit("bench", **r)
        print(json.dumps(r), flush=True)

    out = out_path or os.environ.get("BENCH_STREAM_OUT")
    if not out:
        # committed gate baseline only for an explicit stream-only run
        # (the perf/bn/fleet/autoscale/sched no-self-overwrite rule,
        # 6th use)
        out = ("BENCH_STREAM_cpu_r15.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "stream"
               else "BENCH_STREAM_local.json")
    doc = {"metric": "serve_stream",
           "config": {**base, "platform": jax.devices()[0].platform},
           "legacy_arm": {
               "capacity_rps": legacy_arm["capacity_rps"],
               "overload_reject_frac": round(med(leg_rej_frac), 4),
               "sustained_p99_ms": [x for x in leg_sus_p99],
               "overload_p99_ms": [x for x in leg_ov_p99],
           },
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# stream tier: {len(records)} records -> {out}", flush=True)
    return records


def bench_autoscale(*, replicas=2, n_requests=32, repeats=3, max_batch=4,
                    rate_rps=None, out_path=None) -> list:
    """Self-healing/autoscale tier (ISSUE 13): time-to-first-ready for a
    recovery-path replica, cold (live compiles) vs AOT-loaded
    (deserialized executables), and open-loop p99 THROUGH a mid-run
    scale-up event.

    Records: ``serve_autoscale_ttfr_cold`` / ``serve_autoscale_ttfr_aot``
    (unit ``s``: bench_compare gates duration UPWARD via its smaller-is-
    better rule) and ``serve_autoscale_p99_scaleup`` (unit ``ms``, fixed
    offered rate — the fleet tier's comparable-run discipline), each
    median-of-``repeats`` with the measured spread recorded as the
    gate's noise floor.  The AOT row also carries ``compiles`` (0 — the
    zero-new-compiles receipt tests/test_autoscale.py pins)."""
    import statistics
    import tempfile

    import jax

    from bench_serve import measure_time_to_first_ready, run_open_loop
    from can_tpu.models import cannet_init
    from can_tpu.obs import Telemetry
    from can_tpu.serve import (
        CountService,
        FleetEngine,
        load_aot_bundle,
        prepare_image,
    )

    if rate_rps is None:
        # below the 2-replica CPU box's saturation (the fleet tier's
        # rule): p99 must measure latency, not end-of-run backlog
        rate_rps = float(os.environ.get("BENCH_AUTOSCALE_RATE", "4"))
    need = replicas + 1  # the scale-up's spare device
    if len(jax.devices()) < need:
        print(f"# autoscale tier skipped: {len(jax.devices())} device(s) "
              f"< replicas+1={need} (use BENCH_SUITE_PLATFORM=cpu8 or a "
              f"multi-chip host)", flush=True)
        return []
    params = cannet_init(jax.random.key(0))
    sizes = [(64, 64), (96, 64)]
    ladder = (tuple(sorted({h for h, _ in sizes})),
              tuple(sorted({w for _, w in sizes})))
    buckets = [(h, w) for h in ladder[0] for w in ladder[1]]
    rng = np.random.default_rng(7)
    images = [prepare_image(
        (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
        for h, w in sizes]
    tel = Telemetry()
    fleet = FleetEngine(params, replicas=replicas, telemetry=tel,
                        name="autoscale_fleet",
                        devices=jax.devices()[:need])
    # pinned to the pre-r14 single-size/timer config: this tier measures
    # AOT vs cold recovery mechanics, and its committed r13 baseline was
    # recorded at one program per (bucket, dtype) — the scheduler's own
    # tier (bench_sched) measures the menu
    svc = CountService(fleet, max_batch=max_batch, max_wait_ms=2.0,
                       queue_capacity=256, bucket_ladder=ladder,
                       telemetry=tel, menu_budget=1, flush_policy="timer")
    warm = svc.warmup(buckets)
    with tempfile.TemporaryDirectory() as aot_dir:
        manifest = fleet.bake_aot(aot_dir)
        bundle = load_aot_bundle(aot_dir)
        # time-to-first-ready on the SPARE device (exactly what a
        # resurrection or scale-up pays), cold vs AOT, interleaved so
        # host drift hits both arms equally (the host-tier discipline)
        spare = jax.devices()[replicas]
        cold_s, aot_s = [], []
        aot_compiles = cold_compiles = 0
        for rep in range(repeats):
            c = measure_time_to_first_ready(
                params, device=spare, bucket_shapes=buckets,
                max_batch=max_batch, telemetry=tel,
                name=f"ttfr_cold_{rep}")
            a = measure_time_to_first_ready(
                params, device=spare, bucket_shapes=buckets,
                max_batch=max_batch, aot_bundle=bundle, telemetry=tel,
                name=f"ttfr_aot_{rep}")
            cold_s.append(c["time_to_first_ready_s"])
            aot_s.append(a["time_to_first_ready_s"])
            cold_compiles = max(cold_compiles, c["compiles"])
            aot_compiles = max(aot_compiles, a["compiles"])

        # p99 through a scale-up: fixed-rate open loop; at 1/3 of the
        # arrivals the fleet grows onto the spare device from the bundle
        fleet.load_aot(aot_dir)
        p99s, rejects, scale_reports = [], 0, []
        with svc:
            for rep in range(repeats):
                trigger_at = n_requests // 3
                fired = []

                def on_arrival(i, _fired=fired):
                    if i == trigger_at and not _fired:
                        _fired.append(True)
                        scale_reports.append(
                            fleet.add_replica(reason="bench_scaleup"))

                o = run_open_loop(svc, images, n_requests, rate_rps,
                                  deadline_ms=30_000, seed=rep,
                                  on_arrival=on_arrival)
                p99s.append(o["p99_ms"])
                rejects += o["rejected"]
                if fired:
                    fleet.remove_replica(reason="bench_reset")
        spread = lambda xs: round(  # noqa: E731
            100.0 * (max(xs) - min(xs)) / max(statistics.median(xs), 1e-9),
            1)
        base = {"replicas": replicas, "offered_rps": rate_rps,
                "requests": n_requests, "repeats": repeats,
                "warmup_compiles": warm["compiles"],
                "aot_programs": len(manifest["programs"]),
                "aot_devices": len({p["device_id"]
                                    for p in manifest["programs"]})}
        records = [
            {"metric": "serve_autoscale_ttfr_cold",
             "value": round(statistics.median(cold_s), 3), "unit": "s",
             "spread_pct": spread(cold_s), "compiles": cold_compiles,
             **base},
            {"metric": "serve_autoscale_ttfr_aot",
             "value": round(statistics.median(aot_s), 3), "unit": "s",
             "spread_pct": spread(aot_s), "compiles": aot_compiles,
             **base},
            {"metric": "serve_autoscale_p99_scaleup",
             "value": round(statistics.median(p99s), 3), "unit": "ms",
             "spread_pct": spread(p99s), "rejects": rejects,
             "scale_ttfr_s": [r["time_to_first_ready_s"]
                              for r in scale_reports],
             "scale_compiles": [r["warmup_compiles"]
                                for r in scale_reports], **base},
        ]
    for rec in records:
        if _TELEMETRY is not None:
            _TELEMETRY.emit("bench", **rec)
        print(json.dumps(rec), flush=True)
    out = out_path or os.environ.get("BENCH_AUTOSCALE_OUT")
    if not out:
        # committed gate baseline only for an explicit autoscale-only
        # run (the perf/bn/fleet no-self-overwrite rule)
        out = ("BENCH_AUTOSCALE_cpu_r13.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "autoscale"
               else "BENCH_AUTOSCALE_local.json")
    doc = {"metric": "serve_autoscale",
           "config": {"replicas": replicas, "requests": n_requests,
                      "repeats": repeats, "rate_rps": rate_rps,
                      "max_batch": max_batch,
                      "buckets": [f"{h}x{w}" for h, w in buckets],
                      "platform": jax.devices()[0].platform},
           "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# autoscale tier: {len(records)} records -> {out}",
          flush=True)
    return records


def _rss_mb() -> float:
    """Current process resident set, MB (/proc VmRSS; ru_maxrss peak as
    the fallback on boxes without /proc)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_obsplane(*, hosts=4, events_per_host=2000, batch=200,
                   repeats=3, out_path=None) -> list:
    """Fleet-observability-plane tier (r16): the collector's ingest
    throughput, steady-state memory, and scrape cost at ``hosts``
    simulated pushers (obs/collector.py).

    Pure host-side — no device work; the numbers bound how much fleet
    telemetry one collector absorbs before it, not the run, is the
    bottleneck.  The workload is the real push path end to end: batched
    JSONL bodies through ``ingest_push`` (parse + skew sampling + gauges
    + ring + watermark merge) with the global SLO engine grading the
    merged stream, one host running 120 s fast to keep the correction
    in the measured path.  Gated records: ``obsplane_ingest_events_per_s``
    (events/s, downward = regression), ``obsplane_rss_mb`` (mb, upward =
    the bounded-ring discipline leaked; rings and pending queues are the
    ONLY per-host state allowed to grow), ``obsplane_scrape_ms`` (ms —
    the /metrics text render over the full fleet)."""
    import statistics

    from can_tpu.obs.collector import FleetCollector
    from can_tpu.obs.slo import load_slo_spec

    spec = load_slo_spec(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "slo_spec.json"))
    base_ts = 1_000_000.0
    # host 1 runs 120 s fast: every rep exercises offset freezing and
    # the corrected-release path, not just the zero-skew fast path
    skews = {h: (120.0 if h == 1 else 0.0) for h in range(hosts)}

    def host_batches(h):
        evs = []
        for i in range(events_per_host):
            ts = base_ts + skews[h] + i * 0.05
            if i % 50 == 0:
                evs.append({"ts": ts, "host_id": h, "kind": "heartbeat",
                            "payload": {"seq": i // 50,
                                        "start_ts": base_ts + skews[h]}})
            else:
                evs.append({"ts": ts, "host_id": h,
                            "kind": "serve.request",
                            "payload": {"latency_s":
                                        0.02 if i % 10 else 3.0}})
        return ["\n".join(json.dumps(e) for e in evs[j:j + batch]) + "\n"
                for j in range(0, len(evs), batch)]

    bodies = {h: [b.encode() for b in host_batches(h)] for h in
              range(hosts)}
    total_events = hosts * events_per_host
    med = statistics.median
    spread = lambda xs: round(  # noqa: E731
        100.0 * (max(xs) - min(xs)) / max(abs(med(xs)), 1e-9), 1)
    rates, scrapes, rss = [], [], []
    evals = None
    for rep in range(repeats):
        col = FleetCollector(spec, poll_interval_s=3600.0)
        n_batches = max(len(bodies[h]) for h in bodies)
        t0 = time.perf_counter()
        for j in range(n_batches):  # interleaved, like real pushers
            for h in range(hosts):
                if j < len(bodies[h]):
                    col.ingest_push(bodies[h][j])
            col.poll(now=base_ts + (j + 1) * batch * 0.05)
        col.drain(now=base_ts + events_per_host * 0.05)
        rates.append(total_events / (time.perf_counter() - t0))
        t_s = [0.0] * 10
        for k in range(len(t_s)):
            s0 = time.perf_counter()
            text = col.render_metrics()
            t_s[k] = (time.perf_counter() - s0) * 1e3
        assert "can_tpu_slo_burn_global" in text
        scrapes.append(med(t_s))
        rss.append(_rss_mb())
        if evals is None:
            evals = len(col.evals())
        col.close(drain=False)
    base = {"hosts": hosts, "events_per_host": events_per_host,
            "batch": batch, "repeats": repeats, "evaluations": evals,
            "conditions": "push path end-to-end (JSONL parse -> merge "
                          "-> global SLO engine), host 1 skewed +120s"}
    records = [
        {"metric": "obsplane_ingest_events_per_s",
         "value": round(med(rates), 1), "unit": "events/s",
         "spread_pct": spread(rates), **base},
        {"metric": "obsplane_rss_mb", "value": round(med(rss), 1),
         "unit": "mb", "spread_pct": spread(rss), **base},
        {"metric": "obsplane_scrape_ms", "value": round(med(scrapes), 3),
         "unit": "ms", "spread_pct": spread(scrapes), **base},
    ]
    for r in records:
        if _TELEMETRY is not None:
            _TELEMETRY.emit("bench", **r)
        print(json.dumps(r), flush=True)
    out = out_path or os.environ.get("BENCH_OBSPLANE_OUT")
    if not out:
        # committed gate baseline only for an explicit obsplane-only run
        # (the perf/bn/fleet/autoscale/sched/stream no-self-overwrite
        # rule, 7th use)
        out = ("BENCH_OBSPLANE_cpu_r16.json"
               if os.environ.get("BENCH_SUITE_ONLY") == "obsplane"
               else "BENCH_OBSPLANE_local.json")
    doc = {"metric": "obsplane", "config": base, "results": records}
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# obsplane tier: {len(records)} records -> {out}", flush=True)
    return records


def bench_highres_eval(jnp, compute_dtype, *, h, w, steps, warmup=2):
    import jax

    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_eval_step, make_global_batch, make_mesh
    ndev = jax.device_count()
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    local_b = ndev  # one image per chip: the reference's batch-1 eval habit
    batch = Batch(
        image=rng.normal(size=(local_b, h, w, 3)).astype(np.float32),
        dmap=rng.uniform(size=(local_b, h // 8, w // 8, 1)).astype(np.float32),
        pixel_mask=np.ones((local_b, h // 8, w // 8, 1), np.float32),
        sample_mask=np.ones((local_b,), np.float32),
    )
    gbatch = make_global_batch(batch, mesh)
    params = cannet_init(jax.random.key(0))
    ev = make_dp_eval_step(cannet_apply, mesh, compute_dtype=compute_dtype)
    for _ in range(warmup):
        m = ev(params, gbatch, None)
    jax.device_get(m)
    t0 = time.perf_counter()
    for _ in range(steps):
        m = ev(params, gbatch, None)
    jax.device_get(m)
    dt = time.perf_counter() - t0
    img_per_s = local_b * steps / dt
    tag = "f32" if compute_dtype is None else "bf16"
    _emit(f"eval_highres_{h}x{w}_b1_{tag}", img_per_s, "images/sec",
          per_chip_img_per_s=round(img_per_s / ndev, 3))


def main() -> None:
    if os.environ.get("BENCH_SUITE_PLATFORM") == "cpu8":
        from __graft_entry__ import _ensure_cpu_flags

        _ensure_cpu_flags(8)
        import jax

        jax.config.update("jax_platforms", "cpu")
    from can_tpu.utils import bench_device, emit_null_result

    # fail fast on an unreachable backend (null line, exit 3) or on a
    # backend that is not a TPU without the CPU being requested (exit 2);
    # every record _emit prints names the device it ran on
    _DEVICE.update(bench_device(on_timeout=emit_null_result("bench_suite")))
    import jax  # noqa: F811
    import jax.numpy as jnp

    if not os.environ.get("BENCH_SUITE_NO_CACHE"):
        from can_tpu.utils import enable_compilation_cache

        cache = enable_compilation_cache()
        print(f"# compilation cache: {cache}", flush=True)

    quick = bool(os.environ.get("BENCH_SUITE_QUICK"))
    only = os.environ.get("BENCH_SUITE_ONLY", "")  # substring filter
    print(f"# bench_suite devices={_DEVICE['device_count']} "
          f"platform={_DEVICE['platform']} "
          f"kind={_DEVICE['device_kind']} quick={quick}", flush=True)

    global _TELEMETRY
    if os.environ.get("BENCH_TELEMETRY_DIR"):
        from can_tpu import obs

        _TELEMETRY = obs.open_host_telemetry(
            os.environ["BENCH_TELEMETRY_DIR"])
        _TELEMETRY.emit("run", config={"suite": True, "quick": quick,
                                       "only": only,
                                       "devices": jax.device_count()})

    def want(name: str) -> bool:
        return only in name

    if quick:
        if want("fixed"):
            bench_fixed(jnp, jnp.bfloat16, b=1, h=128, w=160, steps=4)
            bench_fixed(jnp, None, b=1, h=128, w=160, steps=4)
        if want("pipeline") or want("u8"):
            if want("pipeline"):
                bench_pipeline(jnp, jnp.bfloat16, n_images=16, batch=1,
                               epochs=2, lo=64, hi=160, dominant=(128, 160))
            bench_pipeline(jnp, jnp.bfloat16, n_images=16, batch=1, epochs=2,
                           lo=64, hi=160, dominant=(128, 160), u8=True)
        if want("eval"):
            bench_highres_eval(jnp, jnp.bfloat16, h=256, w=256, steps=4)
            bench_eval_pipeline(jnp, jnp.bfloat16, n_images=8, batch=2,
                                lo=64, hi=160, dominant=(128, 160))
            bench_eval_pipeline(jnp, jnp.bfloat16, n_images=8, batch=2,
                                lo=64, hi=160, dominant=(128, 160), u8=True)
        if want("host"):
            bench_host_pipeline(n_images=16, batch=4, h=128, w=160,
                                workers=(0, 4), repeats=3)
        if want("plan"):
            bench_plan_space(repeats=2)
        if want("perf"):
            bench_perf_ledger(jnp, jnp.bfloat16)
        if want("bn"):
            bench_bn(jnp, jnp.bfloat16)
        if want("fleet"):
            bench_serve_fleet(n_requests=16, repeats=2)
        if want("autoscale"):
            bench_autoscale(n_requests=16, repeats=2)
        if want("sched"):
            bench_sched(n_requests=16, repeats=2)
        if want("stream"):
            bench_stream(n_streams=2, frames=6, repeats=2)
        if want("obsplane"):
            bench_obsplane(hosts=2, events_per_host=800, repeats=2)
    else:
        if want("fixed"):
            bench_fixed(jnp, jnp.bfloat16, b=16, h=576, w=768, steps=20)
            bench_fixed(jnp, None, b=16, h=576, w=768, steps=20)
        if want("pipeline"):
            bench_pipeline(jnp, jnp.bfloat16, n_images=64, batch=8, epochs=3)
        if want("pipeline") or want("u8"):
            bench_pipeline(jnp, jnp.bfloat16, n_images=64, batch=8, epochs=3,
                           u8=True)
        if want("b16varres"):
            # VERDICT r3 item 3: b16 varres used to OOM on the largest
            # bucket; per-bucket auto remat must let it run end-to-end
            bench_pipeline(jnp, jnp.bfloat16, n_images=64, batch=16,
                           epochs=3, remat="auto")
        if want("eval"):
            bench_highres_eval(jnp, jnp.bfloat16, h=1536, w=2048, steps=8)
            # the 576x768-dominant b16 eval config the r4 verdict expects
            # to move materially with prefetch
            bench_eval_pipeline(jnp, jnp.bfloat16, n_images=48, batch=16,
                                lo=384, hi=768, dominant=(576, 768))
            # the u8 transfer mode of the same config (VERDICT r5 weak #3:
            # eval_pipeline had no _u8 entry, so the 4x-transfer-cut mode
            # was only ever measured on the train path)
            bench_eval_pipeline(jnp, jnp.bfloat16, n_images=48, batch=16,
                                lo=384, hi=768, dominant=(576, 768),
                                u8=True)
        if want("host"):
            bench_host_pipeline(n_images=48, batch=8, workers=(0, 4, 8))
        if want("plan"):
            # simulated: runs (and means the same) on any backend
            bench_plan_space()
        if want("perf"):
            # same small-shape config as quick mode ON PURPOSE: the gate
            # baseline (PERF_LEDGER_cpu_r09.json) must be reproducible on
            # the CPU CI box either way
            bench_perf_ledger(jnp, jnp.bfloat16)
        if want("bn"):
            # same rule as the perf tier: one small config in both modes,
            # reproducible on the CPU gate box (BENCH_BN_cpu_r10.json)
            bench_bn(jnp, jnp.bfloat16)
        if want("fleet"):
            # small shapes + fixed offered rate, reproducible on the CPU
            # gate box (BENCH_FLEET_cpu_r11.json); chip-scale serving
            # numbers come from bench_serve.py open-loop sweeps
            bench_serve_fleet()
        if want("autoscale"):
            # same reproducible-on-the-gate-box rule
            # (BENCH_AUTOSCALE_cpu_r13.json)
            bench_autoscale()
        if want("sched"):
            # scheduling-core tier: single engine, no cpu8 needed
            # (BENCH_SCHED_cpu_r14.json)
            bench_sched()
        if want("stream"):
            # streaming-session tier: single engine, capacity-probed 2x
            # overload, sessions + legacy arms (BENCH_STREAM_cpu_r15.json)
            bench_stream()
        if want("obsplane"):
            # fleet-observability tier: pure host-side, 4 simulated
            # pushers through the real ingest path
            # (BENCH_OBSPLANE_cpu_r16.json)
            bench_obsplane()

    if _TELEMETRY is not None:
        from can_tpu.obs import emit_memory

        emit_memory(_TELEMETRY, where="suite_end")
        _TELEMETRY.close()
        _TELEMETRY = None


if __name__ == "__main__":
    main()
