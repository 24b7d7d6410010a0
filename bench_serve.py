"""Benchmark: online serving latency/throughput through can_tpu/serve.

Drives the FULL serving stack (queue -> micro-batcher thread -> jitted
engine) with mixed-resolution synthetic requests, two ways:

* **closed loop** — K concurrent clients, each waiting for its result
  before sending the next request: measures the stack's sustainable
  throughput and the latency it gives cooperative clients.
* **open loop** — Poisson arrivals at a target rate that does NOT slow
  down when the service does (the real-traffic model): measures tail
  latency under pressure and exercises the deadline + backpressure
  rejection paths (a closed loop can never overload the queue, so it
  never tests them).

Emits ONE JSON report to ``BENCH_SERVE_<tag>.json`` and prints it; fields:
per-phase p50/p95/p99 latency (ms), throughput (req/s), reject rate, plus
mean batch fill, compile count vs bucket count, and the telemetry-derived
event totals.  Config via env (defaults are CPU-smoke scale — one v5e chip
serves far bigger shapes; override for real runs):

    BENCH_SERVE_REQUESTS=96   requests per phase
    BENCH_SERVE_CLIENTS=8     closed-loop concurrent clients
    BENCH_SERVE_RATE=0        open-loop arrivals/s (0 = 2x measured
                              closed-loop throughput, guaranteeing pressure)
    BENCH_SERVE_MAX_BATCH=8   micro-batch size
    BENCH_SERVE_MAX_WAIT_MS=5 flush deadline
    BENCH_SERVE_DEADLINE_MS=2000  open-loop request deadline
    BENCH_SERVE_SIZES=60x60,90x90,64x90,90x64   request resolutions
    BENCH_SERVE_OUT=local     report tag
    BENCH_SERVE_REPLICAS=0    0/1 = single ServeEngine; >= 2 = FleetEngine
                              with that many device-pinned replicas
                              (artifact becomes BENCH_SERVE_FLEET_<tag>)
    BENCH_SERVE_DTYPE=f32     predict-program mode (f32 | bf16 | int8);
                              quantized modes also run the f32 parity
                              ladder and record the graded rung
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np


def _sizes_from_env() -> list:
    spec = os.environ.get("BENCH_SERVE_SIZES", "60x60,90x90,64x90,90x64")
    return [(int(h), int(w)) for h, w in
            (part.split("x") for part in spec.split(","))]


def _percentiles_ms(latencies_s: list) -> dict:
    if not latencies_s:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None,
                "max_ms": None}
    arr = np.asarray(latencies_s, np.float64) * 1e3
    return {"p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p95_ms": round(float(np.percentile(arr, 95)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
            "max_ms": round(float(arr.max()), 3)}


def _queue_wait_p95_ms(queue_waits_s: list):
    """p95 of the submit->assembly waits the span timestamps price — the
    number that says whether tail latency is batching or the device."""
    if not queue_waits_s:
        return None
    arr = np.asarray(queue_waits_s, np.float64) * 1e3
    return round(float(np.percentile(arr, 95)), 3)


def run_closed_loop(service, images, n_requests: int, n_clients: int) -> dict:
    """K clients, each submit->wait->repeat; returns latency/throughput."""
    from can_tpu.serve import RejectedError

    latencies, queue_waits, rejects = [], [], [0]
    lock = threading.Lock()
    counter = [0]

    def client():
        while True:
            with lock:
                i = counter[0]
                if i >= n_requests:
                    return
                counter[0] += 1
            try:
                res = service.predict(images[i % len(images)],
                                      timeout=120.0)
                with lock:
                    latencies.append(res.latency_s)
                    if res.queue_wait_s is not None:
                        queue_waits.append(res.queue_wait_s)
            except RejectedError:
                with lock:
                    rejects[0] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    done = len(latencies)
    return {"requests": n_requests, "completed": done,
            "rejected": rejects[0],
            "reject_rate": round(rejects[0] / max(n_requests, 1), 4),
            "throughput_rps": round(done / wall, 2),
            "wall_s": round(wall, 3),
            "queue_wait_p95_ms": _queue_wait_p95_ms(queue_waits),
            **_percentiles_ms(latencies)}


def run_open_loop(service, images, n_requests: int, rate_rps: float,
                  deadline_ms: float, seed: int = 0,
                  on_arrival=None) -> dict:
    """Poisson arrivals at ``rate_rps``; every request carries a deadline.
    Tickets are collected afterwards — arrival timing never blocks on
    results, so the service feels true open-loop pressure.
    ``on_arrival(i)`` fires before request ``i`` is submitted — the
    autoscale tier uses it to trigger a mid-run scale-up and measure p99
    THROUGH the transition."""
    from can_tpu.serve import RejectedError

    rng = np.random.default_rng(seed)
    tickets = []
    t0 = time.perf_counter()
    next_t = 0.0
    for i in range(n_requests):
        next_t += float(rng.exponential(1.0 / rate_rps))
        sleep = t0 + next_t - time.perf_counter()
        if sleep > 0:
            time.sleep(sleep)
        if on_arrival is not None:
            on_arrival(i)
        tickets.append(service.submit(images[i % len(images)],
                                      deadline_ms=deadline_ms))
    latencies, queue_waits, rejects = [], [], 0
    for t in tickets:
        try:
            res = t.result()
            latencies.append(res.latency_s)
            if res.queue_wait_s is not None:
                queue_waits.append(res.queue_wait_s)
        except RejectedError:
            rejects += 1
    wall = time.perf_counter() - t0
    return {"requests": n_requests, "completed": len(latencies),
            "rejected": rejects,
            "reject_rate": round(rejects / max(n_requests, 1), 4),
            "offered_rps": round(rate_rps, 2),
            "throughput_rps": round(len(latencies) / wall, 2),
            "wall_s": round(wall, 3),
            "queue_wait_p95_ms": _queue_wait_p95_ms(queue_waits),
            **_percentiles_ms(latencies)}


def measure_time_to_first_ready(params, *, device, bucket_shapes,
                                max_batch: int, serve_dtype: str = "f32",
                                aot_bundle=None, telemetry=None,
                                name: str = "ttfr") -> dict:
    """Build + fully warm ONE replica engine on ``device`` — the
    recovery-path latency the self-healing fleet pays for a resurrection
    or scale-up.  Cold = live trace+compile per bucket; with an AOT
    bundle = deserialized executables (zero new compiles, pinned via the
    returned ``compiles``).  ``name`` must be unique per call: the
    signature registry is per program name, and a reused name would hide
    the cold path's compiles."""
    from can_tpu.obs import Telemetry
    from can_tpu.serve import ServeEngine

    tel = telemetry if telemetry is not None else Telemetry()
    t0 = time.perf_counter()
    aot_tab = (aot_bundle.programs_for(device)
               if aot_bundle is not None else None)
    engine = ServeEngine(params, device=device, serve_dtype=serve_dtype,
                         telemetry=tel, name=name, aot_programs=aot_tab)
    rep = engine.warmup(bucket_shapes, max_batch)
    return {"time_to_first_ready_s": round(time.perf_counter() - t0, 3),
            "compiles": rep["compiles"], "aot_hits": engine.aot_hits}


def main() -> None:
    if os.environ.get("BENCH_SERVE_PLATFORM") == "cpu8":
        # 8 virtual CPU devices (the fleet needs one device per replica;
        # same smoke-mesh trick as bench_suite BENCH_SUITE_PLATFORM=cpu8)
        from __graft_entry__ import _ensure_cpu_flags

        _ensure_cpu_flags(8)
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "96"))
    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "0"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "8"))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "5"))
    deadline_ms = float(os.environ.get("BENCH_SERVE_DEADLINE_MS", "2000"))
    tag = os.environ.get("BENCH_SERVE_OUT", "local")
    replicas = int(os.environ.get("BENCH_SERVE_REPLICAS", "0"))
    serve_dtype = os.environ.get("BENCH_SERVE_DTYPE", "f32")
    sizes = _sizes_from_env()

    import jax

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
    from can_tpu.utils import bench_device, enable_compilation_cache

    # no TPU and the CPU not requested -> exit 2, never a silent CPU run
    device = bench_device()
    enable_compilation_cache(None)  # no-op on CPU, warm restarts on TPU
    # serving cost is weight-independent: random init serves the same
    # FLOPs a trained checkpoint would (swap in cli/serve.py for accuracy)
    params = cannet_init(jax.random.key(0))
    telemetry = Telemetry()  # in-memory bus: engine compile attribution

    ladder = (tuple(sorted({-(-h // 8) * 8 for h, _ in sizes})),
              tuple(sorted({-(-w // 8) * 8 for _, w in sizes})))
    buckets = [(h, w) for h in ladder[0] for w in ladder[1]]
    fleet = replicas >= 2
    if fleet:
        engine = FleetEngine(params, replicas=replicas,
                             serve_dtype=serve_dtype, telemetry=telemetry)
    else:
        engine = ServeEngine(params, serve_dtype=serve_dtype,
                             telemetry=telemetry)
    service = CountService(engine, max_batch=max_batch,
                           max_wait_ms=max_wait_ms,
                           queue_capacity=max(64, 4 * max_batch),
                           high_water=max(48, 3 * max_batch),
                           bucket_ladder=ladder, telemetry=telemetry)
    t0 = time.perf_counter()
    warm = service.warmup(buckets)

    rng = np.random.default_rng(7)
    images = [prepare_image(
        (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
        for h, w in sizes]

    # quantized modes carry a parity receipt: the same images through a
    # fresh engine of this mode vs the f32 reference, graded on the
    # committed count-delta tolerance ladder (serve/quant.py)
    parity = None
    if serve_dtype != "f32":
        ref = ServeEngine(params, telemetry=telemetry, name="parity_f32")
        quant = ServeEngine(params, serve_dtype=serve_dtype,
                            telemetry=telemetry,
                            name=f"parity_{serve_dtype}")
        parity = parity_report(quant, ref, images)

    with service:
        closed = run_closed_loop(service, images, n_requests, n_clients)
        if rate <= 0:
            rate = 2.0 * max(closed["throughput_rps"], 1.0)
        open_ = run_open_loop(service, images, n_requests, rate,
                              deadline_ms)
    stats = service.stats()

    # compile budget: one program per (bucket, menu size, dtype) PER
    # replica engine (the r14 sub-batch menu rides the warmup)
    menu = service.sched.menu if service.sched is not None else (max_batch,)
    compile_budget = len(buckets) * max(replicas, 1) * len(menu)
    report = {
        "metric": f"cannet_serve_b{max_batch}_w{int(max_wait_ms)}ms"
                  + (f"_r{replicas}" if fleet else "")
                  + (f"_{serve_dtype}" if serve_dtype != "f32" else ""),
        "unit": "ms latency / req_s",
        "config": {"requests": n_requests, "clients": n_clients,
                   "max_batch": max_batch, "menu": list(menu),
                   "max_wait_ms": max_wait_ms,
                   "deadline_ms": deadline_ms,
                   "replicas": replicas if fleet else 1,
                   "serve_dtype": serve_dtype,
                   "sizes": [f"{h}x{w}" for h, w in sizes],
                   "buckets": [f"{h}x{w}" for h, w in buckets],
                   **device},
        "warmup": warm,
        "compile_count": engine.compile_count,
        "bucket_count": len(buckets),
        "compiles_bounded": engine.compile_count <= compile_budget,
        # the tree the replicas actually hold resident — measuring it
        # (instead of re-quantizing) cannot diverge from what is served
        "param_bytes": param_bytes(
            engine.replicas[0].engine.params if fleet else engine.params),
        "closed_loop": closed,
        "open_loop": open_,
        "mean_batch_fill": stats["mean_batch_fill"],
        "batches": stats["batches"],
        "wall_s": round(time.perf_counter() - t0, 3),
    }
    if parity is not None:
        report["parity_vs_f32"] = parity
    if fleet:
        report["replica_stats"] = stats["replicas"]
        report["live_replicas"] = stats["live_replicas"]
    out = (f"BENCH_SERVE_FLEET_{tag}.json" if fleet
           else f"BENCH_SERVE_{tag}.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    print(f"[bench_serve] wrote {out}")


if __name__ == "__main__":
    main()
