"""Online serving CLI: load a checkpoint, warm up the bucket programs,
answer count/density requests over HTTP.

The reference repo has no request-level inference at all (test.py is batch
evaluation of a directory); this is the front door the ROADMAP's
"serves heavy traffic" north star needs.  Checkpoint loading — Orbax dir,
reference ``.pth``, or converted ``.npz`` — is shared with the eval CLI
(``cli/test.py::load_params``), so anything you can evaluate you can serve.

    python -m can_tpu.cli.serve --torch-pth epoch_354.pth \
        --bucket-shapes 384x512,512x768,768x1024 --max-batch 8 \
        --max-wait-ms 5 --port 8000

    curl -X POST --data-binary @img.npy \
        'http://127.0.0.1:8000/predict?deadline_ms=200'
"""

from __future__ import annotations

import argparse
import re
from typing import List, Tuple


def parse_bucket_shapes(spec: str) -> List[Tuple[int, int]]:
    """'384x512,512x768' -> [(384, 512), (512, 768)] (validated /8)."""
    shapes = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        m = re.fullmatch(r"(\d+)x(\d+)", part)
        if not m:
            raise argparse.ArgumentTypeError(
                f"bad bucket shape {part!r} (want HxW, e.g. 384x512)")
        h, w = int(m.group(1)), int(m.group(2))
        if h % 8 or w % 8:
            raise argparse.ArgumentTypeError(
                f"bucket shape {h}x{w} must be multiples of 8 (the "
                f"density grid)")
        shapes.append((h, w))
    if not shapes:
        raise argparse.ArgumentTypeError("no bucket shapes given")
    return sorted(set(shapes))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="CANNet online serving")
    # checkpoint source — same flags and conflict rules as the eval CLI
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="Orbax checkpoint dir (default ./checkpoints)")
    p.add_argument("--epoch", type=int, default=None,
                   help="checkpoint epoch (default: best by MAE, else latest)")
    p.add_argument("--torch-pth", type=str, default="",
                   help="serve a REFERENCE torch checkpoint directly")
    p.add_argument("--params-npz", type=str, default="",
                   help="serve a tools/import_torch_checkpoint.py .npz")
    p.add_argument("--syncBN", action="store_true",
                   help="checkpoint is the BatchNorm model variant")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model-config", type=str, default="",
                   help="serve the model this configuration file describes "
                        "(benchmark/configs/*.json's format; a language "
                        "model: POST /generate) instead of CANNet from a "
                        "checkpoint; its weights are made from --seed")
    # serving policy
    p.add_argument("--bucket-shapes", type=parse_bucket_shapes,
                   default=parse_bucket_shapes("384x512,512x768,768x1024"),
                   help="comma-separated HxW bucket ladder; requests snap "
                        "UP to the smallest covering shape per axis — one "
                        "XLA program each, all compiled at startup")
    p.add_argument("--max-batch", type=int, default=8,
                   help="requests per micro-batch (every launch pads to "
                        "exactly this, so batch size is static)")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="latency CAP on batching: the priced flush "
                        "deadline (can_tpu/sched) never waits past this; "
                        "for a model the scheduling core cannot price "
                        "(--model-config: a language model) it is the "
                        "fixed flush deadline itself")
    p.add_argument("--queue-capacity", type=int, default=64,
                   help="hard bound on queued requests (beyond: queue_full)")
    p.add_argument("--high-water", type=int, default=None,
                   help="queue depth that starts load shedding "
                        "(backpressure rejects until half-drained); "
                        "default: 3/4 of capacity")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request deadline (expired requests "
                        "are rejected, never dispatched); requests may "
                        "override per call")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve-engine replicas, one per device of the "
                        "mesh (>= 2 builds the FleetEngine: work-stealing "
                        "dispatch, quarantine-on-failure, blue/green "
                        "/rollout; 1 keeps the single-engine service)")
    p.add_argument("--serve-dtype", type=str, default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="predict-program mode (serve/quant.py): f32 = "
                        "bit-parity with offline evaluate(); bf16 = bf16 "
                        "params+compute at MXU rate; int8 = weight-only "
                        "post-training quantization (per-channel scales, "
                        "f32 accumulation, 4x smaller resident params) — "
                        "each priced by the committed parity ladder")
    p.add_argument("--bf16", action="store_true",
                   help="LEGACY bf16 compute with f32 params (counts "
                        "shift ~1e-3 relative); superseded by "
                        "--serve-dtype bf16, conflict if both given")
    # self-healing fleet (ISSUE 13; all fleet-mode only)
    p.add_argument("--aot-bundle", type=str, default="",
                   help="load AOT-serialized predict executables from "
                        "this bundle dir (serve/aot.py): warmup, "
                        "resurrection, and scale-up DESERIALIZE instead "
                        "of compiling — seconds to ready, zero new "
                        "compiles; a stale bundle (params/dtype/jax "
                        "mismatch) is refused with the axis named")
    p.add_argument("--aot-bake", type=str, default="",
                   help="after warmup, serialize the compiled predict "
                        "grid for EVERY device into this bundle dir "
                        "(written beside the checkpoint is the "
                        "convention) and keep serving — the artifact "
                        "--aot-bundle loads on the next start")
    p.add_argument("--autoscale-max", type=int, default=0,
                   help="enable the autoscaler with this replica "
                        "ceiling (> --replicas; 0 = off): the fleet "
                        "grows on sustained queue depth / p99-over-"
                        "deadline / SLO burn and shrinks when idle, "
                        "with hysteresis + cooldown — zero-drop "
                        "transitions either way")
    p.add_argument("--autoscale-min", type=int, default=None,
                   help="autoscaler floor (default: --replicas)")
    p.add_argument("--autoscale-interval-s", type=float, default=1.0,
                   help="autoscaler evaluation period")
    p.add_argument("--probe-cooldown-s", type=float, default=5.0,
                   help="probation cooldown before a quarantined "
                        "replica's first health probe (backoff doubles "
                        "per failed probe, jittered)")
    p.add_argument("--watchdog-slack", type=float, default=10.0,
                   help="hang-watchdog deadline = cost-ledger expected "
                        "execute time x this slack (per bucket)")
    p.add_argument("--watchdog-default-s", type=float, default=30.0,
                   help="hang-watchdog deadline before any timing "
                        "exists (or without a ledger)")
    # per-stream sessions (serve/streams.py)
    p.add_argument("--stream-ttl-s", type=float, default=300.0,
                   help="evict a stream session idle this long (host-"
                        "side state: count/density EWMA, frame sequence, "
                        "sticky replica pin — clients opt in per request "
                        "with ?stream_id=...&frame_seq=N)")
    p.add_argument("--degrade-policy", type=str, default="priced",
                   choices=["priced", "off"],
                   help="priced: the per-stream degradation ladder — "
                        "full inference -> frame-skip (answer from the "
                        "session EWMA, labelled degraded+staleness, no "
                        "launch) -> reject, driven by arrival rate vs "
                        "the sched core's priced drain cost with "
                        "hysteresis; off: sessions + sticky routing + "
                        "sequence hygiene only, never skip a frame")
    p.add_argument("--max-body-mb", type=float, default=64.0,
                   help="HTTP 413 any POST body over this many MiB "
                        "BEFORE reading it (one unbounded multi-GB "
                        "upload would OOM the serve host)")
    p.add_argument("--u8-warmup", action="store_true",
                   help="also pre-compile uint8-input programs, for "
                        "clients POSTing ?raw=1 (pixels stay bytes on the "
                        "wire and into HBM; normalise-on-device, like the "
                        "train CLI's --u8-input)")
    # front end
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    # plumbing shared with the other CLIs
    p.add_argument("--platform", type=str, default="default",
                   choices=["default", "cpu", "tpu"])
    p.add_argument("--compile-cache", type=str, default="auto",
                   help="persistent XLA compilation-cache dir ('auto' = "
                        "where JAX_COMPILATION_CACHE_DIR says, else "
                        "<repo>/.jax_cache; 'off' disables) — makes "
                        "warm restarts deserialise the bucket programs "
                        "instead of recompiling")
    p.add_argument("--telemetry-dir", type=str, default="",
                   help="write serve.request/serve.batch/serve.reject "
                        "JSONL here (tools/telemetry_report.py summarises)")
    p.add_argument("--telemetry-heartbeat-s", type=float, default=60.0)
    p.add_argument("--profile-dir", type=str, default="",
                   help="jax.profiler trace output dir (with --trace-steps)")
    p.add_argument("--trace-steps", type=str, default="",
                   help="profile only launched batches START:STOP (python "
                        "slice semantics, counted from the first batch "
                        "after warmup) into --profile-dir (the device "
                        "alone; tools/trace_export.py --profile draws the "
                        "serve.* spans beside it)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus-text /metrics + /healthz on this "
                        "port (0 = ephemeral): the service's /stats "
                        "counters (requests, rejects, queue depth, "
                        "latency percentiles) in the SAME format and "
                        "labels as the train CLI's gauges — one scrape "
                        "config covers training and serving")
    p.add_argument("--metrics-host", type=str, default="127.0.0.1",
                   help="bind address for --metrics-port")
    p.add_argument("--collector-push", type=str, default="",
                   metavar="URL",
                   help="stream telemetry to a FleetCollector "
                        "(can_tpu.cli.collect) at URL — best-effort "
                        "batched JSONL over HTTP (see the train CLI)")
    p.add_argument("--incident-dir", type=str, default="",
                   help="arm the incident layer: a replica quarantine, a "
                        "fast SLO burn, or a SIGTERM dumps a "
                        "self-contained bundle (flight-recorder ring + "
                        "gauges + live serve stats + stacks) here — see "
                        "the train CLI / obs/incidents.py")
    p.add_argument("--slo-spec", type=str, default="",
                   help="JSON SLO spec (slo_spec.json): serve p99 vs "
                        "deadline, reject rate, ... evaluated live as "
                        "multi-window burn rates; can_tpu_slo_* gauges "
                        "on /metrics are the autoscaler's signal")
    return p.parse_args(argv)


def _run_config_for(checkpoint_dir, torch_pth, params_npz):
    """Run config for the drift guard — imported .pth/.npz checkpoints
    carry none, so the guard degrades to skipped for them (same as
    resume).  One helper so serve-time and rollout-time agree forever."""
    from can_tpu.utils import load_run_config

    if torch_pth or params_npz:
        return None
    return load_run_config(checkpoint_dir)


def make_rollout_loader(base_args):
    """Checkpoint loader for the HTTP /rollout endpoint: a JSON source
    spec (same keys as the CLI flags) -> (params, batch_stats,
    run_config).  Reuses the eval CLI's validated loading path, so
    anything you can serve you can roll out."""
    import argparse as _ap

    def load(spec: dict):
        from can_tpu.cli.test import load_params, validate_params_source

        allowed = {"checkpoint_dir", "epoch", "params_npz", "torch_pth",
                   "syncBN"}
        unknown = set(spec) - allowed
        if unknown:
            raise ValueError(f"unknown rollout keys: {sorted(unknown)} "
                             f"(allowed: {sorted(allowed)})")
        # an imported-source spec (torch_pth / params_npz) must NOT
        # inherit the serving checkpoint_dir: validate_params_source
        # rejects the combination, which would 409 every such rollout
        imported = bool(spec.get("torch_pth") or spec.get("params_npz"))
        ns = _ap.Namespace(
            # default to the SERVING run's directory (a bare {"epoch": N}
            # rolls forward within it), exactly like syncBN below — an
            # unrelated ./checkpoints fallback could silently flip the
            # fleet to a different run's weights
            checkpoint_dir=spec.get(
                "checkpoint_dir",
                None if imported else base_args.checkpoint_dir),
            epoch=spec.get("epoch"),
            torch_pth=spec.get("torch_pth", ""),
            params_npz=spec.get("params_npz", ""),
            syncBN=bool(spec.get("syncBN", base_args.syncBN)),
            seed=base_args.seed)
        try:
            validate_params_source(ns)
            params, batch_stats = load_params(ns)
        except SystemExit as e:
            # the loading path speaks CLI (SystemExit); over HTTP that
            # must become a 409-able error, not a dead handler thread
            raise ValueError(str(e)) from None
        run_config = _run_config_for(ns.checkpoint_dir, ns.torch_pth,
                                     ns.params_npz)
        return params, batch_stats, run_config

    return load


def build_service(args, telemetry=None):
    """Engine + service from parsed args (no networking) — the seam the
    tests drive; ``main`` adds HTTP around it."""
    import jax.numpy as jnp
    import numpy as np

    from can_tpu.cli.test import load_params
    from can_tpu.serve import CountService, FleetEngine, ServeEngine

    if args.model_config:
        return _build_configured(args, telemetry)
    if args.bf16 and args.serve_dtype != "f32":
        raise SystemExit("--bf16 is the legacy f32-params/bf16-compute "
                         "mode; with --serve-dtype use the mode itself "
                         "(drop --bf16)")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    if args.stream_ttl_s <= 0:
        raise SystemExit(f"--stream-ttl-s must be positive, got "
                         f"{args.stream_ttl_s}")
    if args.max_body_mb <= 0:
        raise SystemExit(f"--max-body-mb must be positive, got "
                         f"{args.max_body_mb}")
    fleet_only = ["--aot-bundle", "--aot-bake", "--autoscale-max"]
    if args.replicas <= 1 and (args.aot_bundle or args.aot_bake
                               or args.autoscale_max):
        raise SystemExit(f"{'/'.join(fleet_only)} need fleet mode "
                         f"(--replicas >= 2)")
    if args.autoscale_max and args.autoscale_max <= args.replicas:
        raise SystemExit(f"--autoscale-max ({args.autoscale_max}) must "
                         f"exceed --replicas ({args.replicas})")
    if args.autoscale_max and args.autoscale_min is not None:
        # validate BEFORE the checkpoint load: AutoscalePolicy would
        # reject these anyway, but only after minutes of load+warmup
        if not 1 <= args.autoscale_min <= args.autoscale_max:
            raise SystemExit(
                f"--autoscale-min ({args.autoscale_min}) must be in "
                f"[1, --autoscale-max={args.autoscale_max}]")
    params, batch_stats = load_params(args)
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    if args.replicas > 1:
        from can_tpu.serve import AotStaleError

        run_config = _run_config_for(args.checkpoint_dir, args.torch_pth,
                                     args.params_npz)
        try:
            engine = FleetEngine(
                params, batch_stats, replicas=args.replicas,
                serve_dtype=args.serve_dtype,
                compute_dtype=compute_dtype,
                telemetry=telemetry, run_config=run_config,
                aot_bundle=args.aot_bundle or None,
                probe_cooldown_s=args.probe_cooldown_s,
                watchdog_slack=args.watchdog_slack,
                watchdog_default_s=args.watchdog_default_s)
        except AotStaleError as e:
            # a stale bundle silently falling back to minutes of
            # compiles defeats the flag's whole point: refuse, name the
            # axis, point at the re-bake
            raise SystemExit(f"--aot-bundle refused: {e}")
    else:
        engine = ServeEngine(params, batch_stats,
                             serve_dtype=args.serve_dtype,
                             compute_dtype=compute_dtype,
                             telemetry=telemetry)
    high_water = (args.high_water if args.high_water is not None
                  else max(1, (3 * args.queue_capacity) // 4))
    shapes = args.bucket_shapes
    ladder = (tuple(sorted({h for h, _ in shapes})),
              tuple(sorted({w for _, w in shapes})))
    service = CountService(engine, max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           queue_capacity=args.queue_capacity,
                           high_water=high_water,
                           default_deadline_ms=args.deadline_ms,
                           bucket_ladder=ladder, telemetry=telemetry,
                           stream_ttl_s=args.stream_ttl_s,
                           degrade_policy=args.degrade_policy,
                           max_body_mb=args.max_body_mb)
    if args.replicas > 1:
        # the /rollout endpoint's checkpoint loader (fleet only: a single
        # engine has no staging replica to warm on)
        service.rollout_loader = make_rollout_loader(args)
    # the ladder's cross product is the compile universe; warm it ALL so
    # no live request ever pays a compile
    grid = [(h, w) for h in ladder[0] for w in ladder[1]]
    dtypes = (np.float32, np.uint8) if args.u8_warmup else (np.float32,)
    try:
        report = service.warmup(grid, dtypes=dtypes)
    except Exception as e:
        from can_tpu.serve import AotStaleError

        if isinstance(e, AotStaleError):
            # warmup re-checks the batch-geometry axes (max_batch,
            # bucket grid) the constructor can't know yet — same clean
            # refusal as a construction-time mismatch
            raise SystemExit(f"--aot-bundle refused: {e}")
        raise
    reps = f" x {args.replicas} replicas" if args.replicas > 1 else ""
    aot = " [AOT]" if args.replicas > 1 and args.aot_bundle else ""
    print(f"[serve] warmup: {report['compiles']} programs over "
          f"{report['shapes']} bucket shapes{reps} "
          f"[{args.serve_dtype}]{aot} in {report['seconds']:.1f}s")
    if args.replicas > 1 and args.aot_bake:
        manifest = engine.bake_aot(args.aot_bake)
        engine.load_aot(args.aot_bake)  # this run heals from it too
        print(f"[serve] AOT bundle: {len(manifest['programs'])} programs "
              f"over {len(engine._devices_all)} devices -> "
              f"{args.aot_bake} ({manifest['bake_seconds']:.1f}s)")
    if args.replicas > 1 and args.autoscale_max:
        from can_tpu.serve import Autoscaler, AutoscalePolicy

        policy = AutoscalePolicy(
            min_replicas=(args.autoscale_min
                          if args.autoscale_min is not None
                          else args.replicas),
            max_replicas=args.autoscale_max,
            p99_high_s=(args.deadline_ms / 1e3
                        if args.deadline_ms else None),
            interval_s=args.autoscale_interval_s)
        gauges = getattr(telemetry, "_gauge_sink", None)
        service.autoscaler = Autoscaler(service, policy, gauges=gauges)
        print(f"[serve] autoscaler armed: {policy.min_replicas}.."
              f"{policy.max_replicas} replicas, "
              f"eval every {policy.interval_s:g}s")
    return service


def _build_configured(args, telemetry):
    """The service of ``--model-config``: ``serve.build_model_service``,
    the construction the benchmark's driver uses, then its warm-up."""
    import json

    from can_tpu.serve import build_model_service

    if args.replicas != 1:
        raise SystemExit("--model-config serves one engine in process "
                         "(drop --replicas)")
    with open(args.model_config) as f:
        config = json.load(f)
    try:
        service = build_model_service(config, seed=args.seed,
                                      telemetry=telemetry)
    except ValueError as e:
        raise SystemExit(f"--model-config refused: {e}")
    report = service.warmup()
    print(f"[serve] warmup: {report['compiles']} programs over "
          f"{report['shapes']} length buckets x {report['sizes']} launch "
          f"sizes in {report['seconds']:.1f}s")
    return service


def main(argv=None) -> int:
    args = parse_args(argv)
    from can_tpu.cli.test import validate_params_source

    if not args.model_config:
        validate_params_source(args)  # the corrected sentinel logic, shared
    from can_tpu.cli.train import (
        apply_compile_cache,
        apply_platform,
        build_telemetry,
        validate_incident_args,
        validate_trace_args,
    )
    from can_tpu.parallel import init_runtime, process_index, shutdown_runtime
    from can_tpu.serve import serve_http

    trace_window = validate_trace_args(args)
    validate_incident_args(args)
    apply_platform(args)
    topo = init_runtime()
    apply_compile_cache(args, announce=True)
    print(f"[runtime] {topo}")
    telemetry, heartbeat, exporter = build_telemetry(
        args, host_id=process_index(), trace_window=trace_window)
    try:
        service = build_service(args, telemetry=telemetry)
        if exporter is not None:
            # serve's counters in the same scrape as the bus gauges
            exporter.add_stats_source("serve", service.stats)
        with service:
            httpd = serve_http(service, host=args.host, port=args.port)
            endpoints = ("POST /generate" if args.model_config
                         else "POST /predict") + ", GET /healthz, GET /stats"
            if args.replicas > 1:
                endpoints += ", POST /rollout"
            print(f"[serve] listening on http://{args.host}:{args.port} "
                  f"({endpoints})")
            try:
                httpd.serve_forever()
            except KeyboardInterrupt:
                print("[serve] shutting down")
            finally:
                httpd.server_close()
        return 0
    finally:
        from can_tpu.obs import shutdown_telemetry

        # deterministic order shared with the SIGTERM path (lifecycle.py)
        shutdown_telemetry(telemetry, heartbeat=heartbeat,
                           exporter=exporter)
        shutdown_runtime()


if __name__ == "__main__":
    raise SystemExit(main())
