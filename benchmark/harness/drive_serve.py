"""Driver of the serving cells: ``ServeEngine`` + ``CountService`` in
process, built the way ``can_tpu.cli.serve`` builds them at its defaults,
under a closed loop (``bench_serve.py::run_closed_loop``'s, kept in flight
by one thread).  The rate is all completed requests over the whole window;
the segments of equal work are printed beside it."""

from __future__ import annotations

import collections
import time


class EngineProbe:
    """The benchmark's own call boundary around ``predict_batch``."""

    def __init__(self, engine):
        import jax

        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "_jax", jax)
        object.__setattr__(self, "launches", [])
        object.__setattr__(self, "spans", [])
        object.__setattr__(self, "log", False)
        object.__setattr__(self, "corrupt", None)

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        if name in ("log", "corrupt"):
            object.__setattr__(self, name, value)
        else:
            setattr(self._engine, name, value)

    def predict_batch(self, batch, *, want_density=False):
        b, h, w, _ = batch.image.shape
        t0 = time.perf_counter()
        with self._jax.profiler.TraceAnnotation("bench:launch", shape=f"{b}x{h}x{w}"):
            out = self._engine.predict_batch(batch, want_density=want_density)
        if self.log:
            self.spans.append(("bench:launch", t0, time.perf_counter()))
            self.launches.append({"key": f"{b}x{h}x{w}", "batch": b, "h": h, "w": w,
                                  "images": int(batch.sample_mask.sum())})
        if self.corrupt is not None:
            out = self.corrupt(out)
        return out


def _closed_loop(service, images, outstanding, group, density_every, seconds,
                 segment, limit=None):
    """A closed loop of ``outstanding`` requests kept in flight by ONE thread:
    it waits for the oldest ``group`` answers (one batch's worth; the service
    resolves a batch together) and submits ``group`` new requests in their
    place.  Ends at the first segment boundary at or after ``seconds`` (or
    after ``limit`` requests when given).  Returns the completion log.

    One thread, because the service runs in this process: 32 client threads
    woken one by one while the batcher thread still resolves their batch
    take the GIL from it, a few resubmit before it drains its queue, and the
    stray requests are flushed as 15 + 1 or 12 + 4 for the rest of the run
    (5 runs of 14; PERF.md section 6).  Clients of a deployed service are
    other processes and cannot do that."""
    from can_tpu.serve import RejectedError

    log, failed, tickets = [], 0, collections.deque()
    target = limit
    t_start = time.perf_counter()

    def submit(n):
        for _ in range(n):
            i = submit.next
            if target is not None and i >= target:
                return
            submit.next = i + 1
            want = density_every and i % density_every == density_every - 1
            tickets.append((i, service.submit(images[i % len(images)],
                                              want_density=bool(want))))
    submit.next = 0

    submit(outstanding)
    while tickets:
        n = min(group, len(tickets))
        for _ in range(n):
            i, ticket = tickets.popleft()
            try:
                res = ticket.result(120.0)
                log.append((time.perf_counter(), i, res))
            except RejectedError:
                failed += 1
        if target is None and time.perf_counter() - t_start >= seconds:
            target = -(-submit.next // segment) * segment
        submit(n)
    return t_start, log, failed


def run(cell, seed, seconds, trace, env):
    import jax
    import numpy as np

    cfg, traffic = cell.config, cell.traffic
    devices, _ = env.open_devices(cell.chips)
    from can_tpu.obs import Telemetry
    from can_tpu.serve import CountService, ServeEngine

    from benchmark.harness import correct, estimator, weights

    h, w = traffic["image_hw"]
    max_batch = int(cfg["max_batch"])
    n_img = int(traffic["distinct_images"])
    rng = np.random.default_rng(seed)
    block = rng.standard_normal((n_img, h, w, 3), dtype=np.float32)
    images = [block[i] for i in range(n_img)]
    sink = env.ListSink()
    telemetry = Telemetry([sink])
    params = weights.make_params(seed)
    engine = EngineProbe(ServeEngine(params, serve_dtype=env.serve_dtype or cfg["serve_dtype"],
                                     telemetry=telemetry))
    if env.break_path:
        env.break_path(engine)
    capacity = int(cfg["queue_capacity"])
    service = CountService(engine, max_batch=max_batch,
                           max_wait_ms=float(cfg["max_wait_ms"]),
                           queue_capacity=capacity,
                           high_water=max(1, (3 * capacity) // 4),
                           bucket_ladder=((h,), (w,)), telemetry=telemetry)
    t0 = time.perf_counter()
    report = service.warmup([(h, w)])
    print(f"[setup] {report['compiles']} programs warm in "
          f"{time.perf_counter() - t0:.1f}s (menu {service.sched.menu if service.sched else max_batch})",
          flush=True)
    service.start()
    density_every = int(traffic.get("density_every", 0))
    counters = {}
    compiles0 = env.compiles.count
    stats0 = service.stats()
    sink.events.clear()
    if traffic["generator"] != "closed_loop":
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    segment = int(traffic["segment_requests"])
    outstanding = int(traffic["clients"])
    env.setup_done(time.perf_counter())
    t_start, log, failed = _closed_loop(service, images, outstanding, max_batch,
                                        density_every, seconds, segment)
    est = estimator.summarise(
        t_start, estimator.boundaries_from_log([r[0] for r in log], segment),
        float(segment))
    print("[segments] req/s per %d requests: " % segment
          + " ".join(f"{r:.3f}" for r in est["segments"])
          + f" | median {est['segment_median']:.3f} | completed / wall {est['rate']:.3f}",
          flush=True)
    end_to_end = {"req_per_s": est["rate"]}
    counters["rate"] = est
    compiled = env.compiles.count - compiles0
    stats1 = service.stats()
    slots = stats1["batch_slots"] - stats0["batch_slots"]
    counters["batch_fill_pct"] = 100.0 * (stats1["batch_valid"] - stats0["batch_valid"]) / max(slots, 1)
    batches = [e["payload"] for e in sink.events if e["kind"] == "serve.batch"]
    counters["exec_ms_per_img"] = (1e3 * sum(b["execute_s"] for b in batches)
                                   / max(sum(b["valid"] for b in batches), 1))
    counters["compiles_in_window"] = compiled
    sizes = {}
    for b in batches:
        sizes[(b["size"], b["valid"])] = sizes.get((b["size"], b["valid"]), 0) + 1
    print("[batches] (slots, valid): count  " + "  ".join(
        f"({s},{v}): {n}" for (s, v), n in sorted(sizes.items(), reverse=True)),
        flush=True)

    reduced = None
    if trace:
        engine.log = True
        tdir = env.start_trace()
        _closed_loop(service, images, outstanding, max_batch, 0, 0.0, 1,
                     limit=env.traced_launches * max_batch)
        env.stop_trace()
        engine.log = False
        # predict_batch returns once the counts are fetched: its last return
        # is the end of the last program, give or take the fetch
        reduced = env.reduce_trace(tdir, engine.launches, spans=engine.spans,
                                   anchor=engine.spans[-1][2],
                                   program_prefix="jit_predict", n_devices=1,
                                   train=False)

    service.close()
    dev = env.device_report(devices)
    engine.release_buffers()
    del service, engine

    # -- the reference: every distinct image once, float32 ----------------
    from benchmark.reference import cannet_ref

    ones = {"dmap": np.zeros((1, h // 8, w // 8, 1), np.float32),
            "pixel_mask": np.ones((1, h // 8, w // 8, 1), np.float32),
            "sample_mask": np.ones((1,), np.float32)}
    sampled = [r for r in log if r[2].density is not None][
        : int(traffic.get("density_compared", 8))]

    def answers(mode):
        """What ``mode`` of the reference answers to the window's requests."""
        counts, mass, dens = [], [], []
        for img in images:
            c, d = cannet_ref.predict(params, dict(ones, image=img[None]), mode, block=1)
            counts.append(float(c[0]))
            mass.append(float(np.abs(d[0]).sum()))
            dens.append(d[0])
        return {"counts": [counts[r[1] % n_img] for r in log],
                "mass": [mass[r[1] % n_img] for r in log],
                "densities": [dens[r[1] % n_img] for r in sampled],
                "by_image": (counts, mass, dens)}

    ref, yard = answers("f32"), answers("bf16")
    (rc, rm, rd), (yc, _, yd) = ref.pop("by_image"), yard.pop("by_image")
    served = {r[1] % n_img: r[2].count for r in log}
    fmt = lambda xs: "[" + " ".join(f"{float(x):.4g}" for x in xs) + "]"
    print("[counts] by image: float32 " + fmt(rc) + " mass " + fmt(rm)
          + " served - float32 " + fmt(served.get(i, float("nan")) - c
                                       for i, c in enumerate(rc))
          + " bfloat16 - float32 " + fmt(y - c for y, c in zip(yc, rc))
          + " bfloat16 map L2 gap " + fmt(np.linalg.norm(y - d) for y, d in zip(yd, rd))
          + " served map L2 gap, sampled " + " ".join(
              f"{r[1] % n_img}:{np.linalg.norm(np.asarray(r[2].density) - rd[r[1] % n_img]):.4g}"
              for r in sampled), flush=True)
    numbers = correct.serve_numbers([r[2].count for r in log],
                                    [r[2].density for r in sampled], ref, yard)
    numbers["compiles_in_window"] = float(compiled)
    for mode in env.control_modes:
        # the control: the reference in a lower precision, in the program's
        # place, on the same images
        got = answers(mode)
        control = correct.serve_numbers(got["counts"], got["densities"], ref, yard)
        print(f"[control {mode}] " + " ".join(f"{k}={v:.6g}" for k, v in control.items()),
              flush=True)
        env.control_numbers[mode] = control
    ok = correct.judge(numbers, cfg["limits"])
    return env.Result(correct=ok and failed == 0, attempted=len(log) + failed,
                      failed=failed, end_to_end=end_to_end, counters=counters,
                      reduced=reduced, device=dev, numbers=numbers)
