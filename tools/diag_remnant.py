"""Diagnose the varres remnant-batch throughput regression (round 4).

Round 3's varres schedule (9 full-gbs batches, 21.7% waste) ran at
56.3 img/s; the remnant schedule (25 batches incl. small sub-batches,
10.9% waste) measured 35.8 — killing dead slots LOST 20 img/s.  Candidate
causes, separated here on staged device batches:

A. per-batch step times by (shape, batch): small-batch chip inefficiency;
B. program-interleave cost: the same batches run grouped-by-program vs in
   schedule order — a gap means executable switching (param relayout /
   instruction reload) dominates;
C. the no-remnant baseline, same process, for the r3 comparison point.

Run (single process, real TPU): python tools/diag_remnant.py
"""

from __future__ import annotations

import collections
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def stage(batcher, put, epoch=2):
    staged = []
    for b in batcher.epoch(epoch):
        staged.append(put(b))
    return staged


def run_epoch(step, state, staged, reps=2):
    import jax

    for g in staged:  # warm
        state, m = step(state, g)
    float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(reps):
        for g in staged:
            state, m = step(state, g)
    float(jax.device_get(m["loss"]))
    dt = time.perf_counter() - t0
    imgs = sum(float(np.sum(jax.device_get(g["sample_mask"]))) for g in staged)
    return state, imgs * reps / dt


def per_batch_times(step, state, staged, reps=3):
    import jax

    rows = collections.defaultdict(list)
    for g in staged:  # warm every program
        state, m = step(state, g)
    float(jax.device_get(m["loss"]))
    for g in staged:
        t0 = time.perf_counter()
        for _ in range(reps):
            state, m = step(state, g)
        float(jax.device_get(m["loss"]))
        dt = (time.perf_counter() - t0) / reps
        shape = tuple(int(s) for s in g["image"].shape[:3])
        rows[shape].append(dt)
    return state, rows


def main():
    from bench_suite import SynthVarResDataset

    from can_tpu.data import ShardedBatcher
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
    from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer
    from can_tpu.utils import bench_device, enable_compilation_cache

    # not a TPU and the CPU not requested (JAX_PLATFORMS=cpu) -> exit 2
    device = bench_device()
    enable_compilation_cache()
    import jax
    import jax.numpy as jnp

    print(f"# diag_remnant {device}", flush=True)
    ndev = jax.device_count()
    mesh = make_mesh()
    put = lambda b: make_global_batch(b, mesh)
    ds = SynthVarResDataset(64)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=ndev))
    state = create_train_state(cannet_init(jax.random.key(0)), opt)
    step = make_dp_train_step(cannet_apply, opt, mesh,
                              compute_dtype=jnp.bfloat16)

    for remnant in (True, False):
        batcher = ShardedBatcher(ds, 8 * ndev, shuffle=True, seed=0,
                                 pad_multiple="auto", max_buckets=24,
                                 remnant_sizes=remnant, batch_quantum=ndev)
        staged = stage(batcher, put)
        jax.block_until_ready(staged[-1]["image"])
        tag = "remnant" if remnant else "legacy "
        # schedule order (what the epoch actually runs)
        state, sched_ips = run_epoch(step, state, staged)
        # grouped by program: same batches, all same-shape consecutive
        grouped = sorted(staged, key=lambda g: tuple(g["image"].shape))
        state, grouped_ips = run_epoch(step, state, grouped)
        print(f"[{tag}] batches={len(staged)} schedule-order={sched_ips:.1f} "
              f"grouped-by-program={grouped_ips:.1f} img/s", flush=True)
        if remnant:
            state, rows = per_batch_times(step, state, staged)
            print("  per-(B,H,W) mean step ms / imgs-per-s-equivalent:")
            for shape in sorted(rows):
                ts = rows[shape]
                b = shape[0]
                ms = 1e3 * float(np.mean(ts))
                print(f"    {shape}: {ms:7.1f} ms  n={len(ts)} "
                      f"({b / np.mean(ts):6.1f} img/s)", flush=True)


if __name__ == "__main__":
    main()
