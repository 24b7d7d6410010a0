"""Driver of the training cells: the program's own data path and loop
(``CrowdDataset`` -> ``ShardedBatcher`` -> ``train_one_epoch``), built the way
``can_tpu.cli.train`` builds them at its defaults, timed in whole epochs."""

from __future__ import annotations

import itertools
import os
import time


class StepProbe:
    """The benchmark's own call boundary around the jitted step: a
    ``TraceAnnotation`` per launch, a log of what was launched, and during
    set-up's first steps a copy of what ``correct`` compares."""

    def __init__(self, step):
        import jax

        self._jax = jax
        self.step = step
        self.launches = []       # appended only while ``log`` is True
        self.spans = []          # (name, t0, t1) on perf_counter, likewise
        self.log = False
        self.capture = None      # dict while the first steps run
        if hasattr(step, "jit_for"):
            self.jit_for = step.jit_for

    def __call__(self, state, batch):
        b, h, w, _ = batch["image"].shape
        t0 = time.perf_counter()
        with self._jax.profiler.TraceAnnotation("bench:launch", shape=f"{b}x{h}x{w}"):
            state, metrics = self.step(state, batch)
        if self.log:
            self.spans.append(("bench:launch", t0, time.perf_counter()))
            self.launches.append({"key": f"{b}x{h}x{w}", "batch": b, "h": h, "w": w,
                                  "images": None, "metrics": metrics})
        cap = self.capture
        if cap is not None:
            cap["losses"].append(metrics["loss"])
            if len(cap["losses"]) == 1:
                cap["grad1"] = self._jax.device_get(state.opt_state[0].trace)
        return state, metrics


def run(cell, seed, seconds, trace, env):
    cfg, traffic = cell.config, cell.traffic
    # -- data: before JAX is touched ------------------------------------
    from benchmark.harness import datagen

    sizes = datagen.sizes_for(traffic, seed)
    data_root = os.path.join(env.cache_dir, "data", cell.name)
    writer = datagen.DatasetWriter(data_root, sizes, seed)

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices, _ = env.open_devices(cell.chips)
    from can_tpu.cli.common import (
        agreed_device_memory_bytes,
        build_mesh_and_batch,
        make_bucketed_train_step,
        make_remat_policy,
        max_launch_pixels,
        resolve_launch_cost_px,
        resolve_sp_padding,
    )
    from can_tpu.data import CrowdDataset, ShardedBatcher
    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply
    from can_tpu.parallel import make_global_batch
    from can_tpu.sched import prefetch_depth_for
    from can_tpu.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
        train_one_epoch,
    )

    from benchmark.harness import correct, estimator, weights

    bf16 = cfg["compute_dtype"] == "bfloat16"
    t_chip = time.perf_counter()
    params = weights.make_params(seed)
    jax.block_until_ready(params)
    t_params = time.perf_counter()
    img_root, gt_root = writer.wait()
    t_data = time.perf_counter()
    dataset = CrowdDataset(img_root, gt_root, gt_downsample=8, phase="train",
                           prepared="auto")
    mesh, host_batch, dp = build_mesh_and_batch(int(cfg["batch_per_chip"]), 1)
    if dp != cell.chips:
        raise RuntimeError(f"mesh spans {dp} devices, the cell asks for {cell.chips}")
    pad_multiple, min_pad, min_bucket_h = resolve_sp_padding("auto", 1)
    hbm = agreed_device_memory_bytes()
    batcher = ShardedBatcher(
        dataset, host_batch, shuffle=True, seed=seed, process_index=0,
        process_count=1, pad_multiple=pad_multiple, min_pad_multiple=min_pad,
        min_bucket_h=min_bucket_h, num_workers=min(8, os.cpu_count() or 1),
        max_buckets=24, remnant_sizes=True, batch_quantum=dp, plan_mode="cost",
        launch_cost_px=resolve_launch_cost_px(2.0),
        max_launch_px=max_launch_pixels(bf16=bf16, hbm_bytes=hbm, shards=dp))
    optimizer = make_optimizer(make_lr_schedule(float(cfg["lr"]), world_size=dp))
    state = create_train_state(params, optimizer, None)
    policy = make_remat_policy("auto", global_batch=host_batch, bf16=bf16,
                               hbm_bytes=hbm, shards=dp)
    probe = StepProbe(make_bucketed_train_step(
        cannet_apply, optimizer, mesh,
        compute_dtype=jnp.bfloat16 if bf16 else None, policy=policy))
    if env.break_path:
        env.break_path(probe)
    prefetch = prefetch_depth_for(batcher)
    fed = []

    def put(batch):
        if fed is not None and len(fed) < env.first_steps:
            fed.append({k: np.array(getattr(batch, k)) for k in
                        ("image", "dmap", "pixel_mask", "sample_mask")})
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:put"):
            out = make_global_batch(batch, mesh)
        if probe.log:
            probe.spans.append(("bench:put", t0, time.perf_counter()))
        return out

    # -- warm up every (shape, size) program of the plan on batches whose
    # sample mask is zero: the gradient is exactly zero, the state unchanged
    schedule = batcher.global_schedule(0)
    programs = sorted({(key, len(group)) for key, group in schedule})
    t0 = time.perf_counter()
    # (the first program runs twice: its first call sees the state as it was
    # made, every later call the replicated state a step returns, and jit
    # traces the two apart)
    warm = programs + programs[:1]
    if env.first_steps_only:
        # calibration reads the first steps alone: their programs, after the
        # same first call on the state as it was made
        warm = programs[:1] + sorted({(key, len(group)) for key, group
                                      in schedule[:env.first_steps]})
    for (h, w), size in warm:
        zero = {"image": np.zeros((size, h, w, 3), np.float32),
                "dmap": np.zeros((size, h // 8, w // 8, 1), np.float32),
                "pixel_mask": np.zeros((size, h // 8, w // 8, 1), np.float32),
                "sample_mask": np.zeros((size,), np.float32)}
        state, m = probe(state, make_global_batch(Batch(**zero), mesh))
        jax.block_until_ready(m)
    print(f"[setup] chip up at {t_chip - env.t0:.1f}s, weights at {t_params - env.t0:.1f}s, "
          f"data at {t_data - env.t0:.1f}s; {len(warm) - 1} programs warm in "
          f"{time.perf_counter() - t0:.1f}s; plan: {len(schedule)} launches/epoch, "
          f"pixel overhead {batcher.schedule_overhead(0):.1%}", flush=True)

    # -- the first steps, through the window's own call and feed ---------
    got = {"params0": jax.device_get(state.params)}
    probe.capture = {"losses": []}
    loop = dict(put_fn=put, show_progress=False, prefetch=prefetch)
    state, _ = train_one_epoch(
        probe, state, itertools.islice(batcher.epoch(0), env.first_steps),
        epoch=0, **loop)
    got["losses"] = [float(x) for x in probe.capture["losses"]]
    got["grad1"] = probe.capture["grad1"]
    got["params_end"] = jax.device_get(state.params)
    probe.capture = None
    first_batches, fed = list(fed), None

    # -- the window: whole epochs, completion to completion -------------
    telemetry = sink = None
    if trace:
        from can_tpu.obs import Telemetry

        sink = env.ListSink()
        telemetry = Telemetry([sink])
    compiles0 = env.compiles.count
    boundaries, losses, epoch = [], [], 1
    t_start = time.perf_counter()
    env.setup_done(t_start)
    while not env.first_steps_only:
        state, stats = train_one_epoch(probe, state, batcher.epoch(epoch),
                                       epoch=epoch, telemetry=telemetry, **loop)
        boundaries.append(time.perf_counter())
        losses.append(stats.loss)
        epoch += 1
        if boundaries[-1] - t_start >= seconds:
            break
    compiled = env.compiles.count - compiles0
    end_to_end, counters = {}, {"compiles_in_window": compiled}
    if boundaries:
        est = estimator.summarise(t_start, boundaries, float(len(dataset)))
        print("[segments] img/s per epoch: "
              + " ".join(f"{r:.3f}" for r in est["segments"])
              + f" | median {est['segment_median']:.3f} | completed / wall {est['rate']:.3f}"
              + f" | loss {losses[0]:.6g} -> {losses[-1]:.6g}", flush=True)
        end_to_end = {traffic["rate_metric"]: est["rate"]}
        counters.update(rate=est,
                        plan_pixel_overhead_pct=100.0 * batcher.schedule_overhead(0),
                        programs=batcher.program_count(0))

    # -- the traced launches ---------------------------------------------
    reduced = None
    if trace and boundaries:
        stalls = [e["payload"] for e in sink.events if e["kind"] == "stall"]
        counters["input_stall_pct"] = (100.0 * sum(s["seconds"] for s in stalls)
                                       / est["window_s"])
        probe.log = True
        tdir = env.start_trace()
        state, _ = train_one_epoch(
            probe, state, itertools.islice(batcher.epoch(epoch), env.traced_launches),
            epoch=epoch, **loop)
        anchor = time.perf_counter()  # the loop's last fetch has just returned
        env.stop_trace()
        probe.log = False
        for l in probe.launches:
            l["images"] = int(l.pop("metrics")["num_valid"])
        reduced = env.reduce_trace(tdir, probe.launches, spans=probe.spans,
                                   anchor=anchor, program_prefix="jit_train_step",
                                   n_devices=dp, train=True)

    # -- free the program's state, then the reference --------------------
    report = env.device_report(devices)
    batcher.close()
    del state, params
    from benchmark.reference import cannet_ref

    p0 = jax.tree.map(jnp.asarray, got["params0"])
    ref_losses, ref_g1, ref_end = cannet_ref.train_steps(p0, first_batches, "f32")
    # the optimiser gets the gradient of (summed loss / replicas), at lr x replicas
    ref = {"losses": ref_losses, "params0": got["params0"], "params_end": ref_end,
           "grad1": jax.tree.map(lambda g: g / dp, ref_g1)}
    # the yardstick of the per-leaf gap: what bfloat16 arithmetic itself does
    # to the first gradient on this seed (the reference computed in bfloat16)
    _, yard_g1 = cannet_ref.loss_and_grad(p0, first_batches[0], "bf16")
    yard = jax.tree.map(lambda g: np.asarray(g) / dp, yard_g1)
    numbers = correct.train_numbers(got, ref, yard)
    for mode in env.control_modes:
        # the control: the reference in a lower precision, in the program's place
        c_losses, c_g1, c_end = cannet_ref.train_steps(p0, first_batches, mode)
        control = correct.train_numbers(
            {"losses": c_losses, "params0": got["params0"], "params_end": c_end,
             "grad1": jax.tree.map(lambda g: g / dp, c_g1)}, ref, yard, " " + mode)
        print(f"[control {mode}] " + " ".join(f"{k}={v:.6g}" for k, v in control.items()),
              flush=True)
        env.control_numbers[mode] = control
    numbers["compiles_in_window"] = float(compiled)
    ok = correct.judge(numbers, cfg["limits"])
    images = int(len(dataset) * len(boundaries))
    return env.Result(correct=ok, attempted=images, failed=0,
                      end_to_end=end_to_end, counters=counters,
                      reduced=reduced, device=report, numbers=numbers)
