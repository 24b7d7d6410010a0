"""Distributed training CLI — the reference's ``train.py`` re-done TPU-first.

Reference launch (README.md:24-26):
    python -m torch.distributed.launch --nproc_per_node=N --use_env train.py
TPU launch: ONE command per host (chips are addressed through the mesh, not
one process per accelerator):
    python -m can_tpu.cli.train --data_root ... [--sp K] [--bf16]

Flag-compatibility with reference train.py:175-195, with its dead/broken
flags made real:
* ``--data_root`` actually selects the dataset (reference parses it but
  hardcodes VisDrone paths, train.py:49-57);
* ``--lrf`` is a real cosine decay to lr*lrf (reference parses, never uses);
* ``--seed`` gives full reproducibility (reference seeds only CUDA with
  time.time(), train.py:66,71);
* ``--syncBN`` trains the real BatchNorm variant of the model with
  cross-replica statistics (the reference's flag is a no-op because its
  CANNet has no BN layers, SURVEY §2);
* eval MAE uses the true dataset size (reference divides by the
  padding-inflated sampler total, train.py:157).
"""

from __future__ import annotations

import argparse
import datetime
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from can_tpu.cli.common import (
    SpatialStepCache,
    build_mesh_and_batch,
    make_cached_sp_eval_step,
    make_remat_policy,
    parse_pad_multiple,
    resolve_launch_cost_px,
    resolve_split_roots,
    resolve_sp_padding,
)
from can_tpu.data import CrowdDataset, ShardedBatcher
from can_tpu.models import (
    cannet_apply,
    cannet_init,
    init_batch_stats,
    load_vgg16_frontend,
)
from can_tpu.parallel import (
    init_runtime,
    is_main_process,
    make_dp_eval_step,
    make_global_batch,
    process_count,
    process_index,
    shutdown_runtime,
)
from can_tpu.parallel.spatial import make_sp_train_step
from can_tpu.train import (
    NonFiniteLossError,
    create_train_state,
    evaluate,
    make_lr_schedule,
    make_optimizer,
    train_one_epoch,
)
from can_tpu.utils import CheckpointManager, MetricLogger, profile_trace


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="CANNet TPU distributed training")
    # reference-compatible flags (train.py:175-195)
    p.add_argument("--epochs", type=int, default=500)
    p.add_argument("--batch-size", type=int, default=1,
                   help="images per data-parallel replica (reference: per GPU)")
    p.add_argument("--lr", type=float, default=1e-7)
    p.add_argument("--lrf", type=float, default=1.0,
                   help="final lr fraction for cosine decay (1.0 = constant)")
    p.add_argument("--syncBN", action="store_true",
                   help="train the BatchNorm variant of CANNet; batch stats "
                        "are computed over the global sharded batch, i.e. "
                        "cross-replica synchronized (the reference's flag is "
                        "a no-op because its model has no BN layers)")
    p.add_argument("--wandb", action="store_true")
    p.add_argument("--show", action="store_true",
                   help="save eval sample density visualizations")
    p.add_argument("--data_root", type=str, default="",
                   help="ShanghaiTech-layout root "
                        "(<root>/<split>_data/{images,ground_truth})")
    # VisDrone-style layouts: images and density maps in unrelated trees
    # (the reference hardcodes such a pair, train.py:54-57)
    p.add_argument("--train-image-root", type=str, default="")
    p.add_argument("--train-gt-root", type=str, default="")
    p.add_argument("--test-image-root", type=str, default="")
    p.add_argument("--test-gt-root", type=str, default="")
    p.add_argument("--init_checkpoint", "--init-checkpoint", type=str,
                   default="",
                   help="checkpoint dir to resume from (latest epoch); "
                        "underscore spelling is the reference's, dashed "
                        "alias matches this CLI's convention")
    p.add_argument("--init-torch-pth", type=str, default="",
                   help="warm-start params from a REFERENCE torch "
                        "checkpoint (e.g. the published epoch_354.pth) — "
                        "the reference's --init_checkpoint .pth workflow "
                        "(its train.py:98-102,113), but with STRICT layout "
                        "validation instead of strict=False; params only "
                        "(optimizer/step start fresh)")
    # TPU-native knobs
    p.add_argument("--checkpoint-dir", type=str, default="./checkpoints")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sp", type=int, default=1,
                   help="spatial (image-height) shards per replica")
    p.add_argument("--pad-multiple", type=parse_pad_multiple, default="auto",
                   help="bucket H,W up to this multiple; 'auto' (default) "
                        "picks the smallest multiple that bounds the number "
                        "of distinct compiled shapes; 'exact' buckets by "
                        "exact snapped shape (zero padding, unbounded "
                        "compiles on wild datasets)")
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 compute (f32 params/accumulation on TPU; "
                        "on cpu/gpu backends bf16 may accumulate at lower "
                        "precision)")
    p.add_argument("--u8-input", action="store_true",
                   help="ship uint8 pixels to the device and normalise "
                        "inside the compiled step: 4x less host->device "
                        "traffic, XLA fuses the normalise into the first "
                        "conv (pixels differ from the f32 path only by u8 "
                        "rounding in the resize)")
    p.add_argument("--remat", nargs="?", const="on", default="auto",
                   choices=["auto", "on", "off"],
                   help="rematerialise the forward in backward "
                        "(jax.checkpoint): ~1/3 more FLOPs for far less "
                        "activation HBM. 'auto' (default) enables it per "
                        "bucket shape, only where the activation estimate "
                        "would overflow HBM (cli/common.py "
                        "make_remat_policy); bare --remat forces it on, "
                        "'off' disables")
    p.add_argument("--vgg16-npz", type=str, default="",
                   help="pretrained VGG-16 frontend .npz (tools/convert_vgg16.py)")
    p.add_argument("--eval-interval", type=int, default=1,
                   help="evaluate+checkpoint every N epochs (>= 1; the "
                        "final epoch always evaluates)")
    p.add_argument("--profile-dir", type=str, default="")
    p.add_argument("--trace-steps", type=str, default="",
                   help="jax.profiler trace WINDOW by run-local step range, "
                        "START:STOP slice semantics (e.g. 10:13 = steps "
                        "10..12) into --profile-dir — instead of the "
                        "whole-run trace a bare --profile-dir captures")
    p.add_argument("--telemetry-dir", type=str, default="",
                   help="write structured telemetry JSONL here (one "
                        "telemetry.host{k}.jsonl per host: compile / "
                        "step_window / stall / memory / heartbeat / epoch "
                        "events; summarize with tools/telemetry_report.py)")
    p.add_argument("--telemetry-heartbeat-s", type=float, default=60.0,
                   help="heartbeat event interval (with --telemetry-dir): "
                        "a hung run leaves a last-known-good timestamp; "
                        "<= 0 disables the heartbeat thread")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus-text /metrics + /healthz on this "
                        "port (0 = ephemeral): live step/loss/grad-norm "
                        "gauges, compile/stall/alert counters — fed by an "
                        "in-memory sink on the telemetry bus; also enables "
                        "the run-health detectors (health.alert events). "
                        "Default off: no exporter thread, no extra "
                        "instrumentation")
    p.add_argument("--metrics-host", type=str, default="127.0.0.1",
                   help="bind address for --metrics-port (0.0.0.0 to let "
                        "a fleet scraper reach every host)")
    p.add_argument("--collector-push", type=str, default="",
                   metavar="URL",
                   help="stream this host's telemetry to a FleetCollector "
                        "(can_tpu.cli.collect) at URL as batched JSONL "
                        "over HTTP POST /ingest — live fleet-level "
                        "gauges, global SLO burn, clock-skew-corrected "
                        "liveness.  Best-effort: a dead collector costs "
                        "dropped batches (counted), never the run")
    p.add_argument("--incident-dir", type=str, default="",
                   help="arm the incident layer (obs/incidents.py): a "
                        "flight-recorder ring retains the last N events "
                        "of telemetry, and any trigger — NaN/stall-budget "
                        "health alert, replica quarantine, unhandled loop "
                        "exception, SIGTERM/preemption — dumps a "
                        "self-contained bundle (ring + gauges + cost "
                        "ledger + all-thread stacks + device memory + "
                        "run config) into this directory, rate-limited "
                        "and retention-bounded.  Default off: no "
                        "recorder, no signal hook")
    p.add_argument("--slo-spec", type=str, default="",
                   help="JSON SLO spec (see slo_spec.json): objectives "
                        "evaluated live as multi-window error-budget "
                        "burn rates over the telemetry stream — slo.burn "
                        "events, can_tpu_slo_* gauges on /metrics, and "
                        "incident bundles on fast burn (with "
                        "--incident-dir).  Grade a finished run with "
                        "tools/slo_report.py")
    p.add_argument("--max-steps-per-epoch", type=int, default=0,
                   help="truncate epochs (smoke tests); 0 = full epoch")
    p.add_argument("--platform", type=str, default="default",
                   choices=["default", "cpu", "tpu"],
                   help="force a JAX platform (cpu + "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                        "gives an N-device virtual mesh)")
    p.add_argument("--num-workers", type=int, default=None,
                   help="host data-loading threads per process (decode + "
                        "resize + pad; the reference's DataLoader "
                        "num_workers, train.py:90). Default: min(8, cpus); "
                        "0 = load in the main thread")
    p.add_argument("--prepared-root", type=str, default="auto",
                   help="prepared 1/8-density store (tools/prepare_data.py "
                        "--prepared): 'auto' (default) probes each split's "
                        "<gt_root>/prepared and falls back to the legacy "
                        "decode path when absent/stale; 'off' disables; a "
                        "path points at a root holding per-split stores "
                        "(<path>/train, <path>/test) and MUST validate")
    p.add_argument("--item-cache-mb", type=float, default=0.0,
                   help="bounded in-RAM LRU over fully-decoded items, in "
                        "MB (shared across train+test splits; 0 = off): "
                        "datasets that fit decode once, then epochs serve "
                        "from memory — counters land as data.cache "
                        "telemetry events")
    p.add_argument("--allow-config-change", action="store_true",
                   help="permit resuming (--init_checkpoint) with "
                        "schedule-bearing flags (lr/lrf/epochs/batch/seed/"
                        "syncBN/bf16) that differ from the ones the "
                        "checkpoint was trained with — without this flag, "
                        "drift is an error, not a silent schedule break")
    p.add_argument("--max-buckets", type=int, default=24,
                   help="compile budget for --pad-multiple auto: max "
                        "distinct batch shapes per step. More buckets = "
                        "less padding (straggler merging keeps the number "
                        "of shapes actually compiled well under the "
                        "budget), and the persistent compilation cache "
                        "makes the one-time bill cheap")
    p.add_argument("--no-remnant-batches", action="store_true",
                   help="disable remnant sub-batches: with --pad-multiple "
                        "auto, straggler groups normally run at a small "
                        "menu of static sub-batch sizes (near-zero dead "
                        "slots; each (shape x size) program counts against "
                        "--max-buckets) instead of padding to the full "
                        "global batch")
    from can_tpu.cli.common import parse_launch_cost

    p.add_argument("--launch-cost-mpx", type=parse_launch_cost, default=2.0,
                   help="fixed cost of one extra step launch, in "
                        "megapixel-equivalents, for the remnant planner's "
                        "pixels-vs-launches trade. The default is a "
                        "conservative constant, not measured on the "
                        "current machine; 'auto' measures this host's "
                        "dispatch overhead at startup (sub-ms dispatch "
                        "unlocks exact straggler splits)")
    p.add_argument("--bn-impl", choices=("twopass", "onepass", "pallas"),
                   default="onepass",
                   help="SyncBN batch-moments path (only meaningful with "
                        "--syncBN): 'onepass' (default) computes per-channel "
                        "(sum, sumsq, count) in one read of each BN layer's "
                        "feature map and issues ONE packed collective per "
                        "layer — measured strictly fewer HBM bytes per "
                        "lowered program than 'twopass' (the original "
                        "mean-then-variance math, kept bit-compatible for "
                        "A/B, mirroring --plan-mode legacy); 'pallas' "
                        "additionally fuses the mask multiply into a TPU "
                        "kernel (ops/pallas_bn.py; interpreted when the "
                        "CPU is requested, jnp twin for shapes the kernel "
                        "cannot tile — both printed at step build)")
    p.add_argument("--plan-mode", choices=("cost", "legacy"), default="cost",
                   help="batch-plan search: 'cost' (default) plans bucket "
                        "boundaries, per-cell batch sizes, and remnant "
                        "menus jointly under one cost model "
                        "(area*slots + launch_cost*launches, HBM cap "
                        "respected); 'legacy' is the pre-r8 heuristic "
                        "planner, kept for A/B comparison")
    p.add_argument("--elastic-dir", type=str, default="",
                   help="arm elastic shrink-and-continue training "
                        "(parallel/elastic.py): a shared signal directory "
                        "(shared FS on a pod) polled for preemption "
                        "leave/dead files — written by a preempted host's "
                        "SIGTERM hook or tools/run_monitor.py "
                        "--emit-signal.  On an agreed signal, all hosts "
                        "checkpoint at a bounded barrier, leavers exit "
                        "cleanly, survivors re-rendezvous at the shrunk "
                        "world, the planner replans the interrupted "
                        "epoch's remaining items, lr/global-batch rescale "
                        "with dp, and training continues — recorded as "
                        "one elastic.transition telemetry event.  "
                        "Default off: no hook, no per-step polling")
    p.add_argument("--elastic-check-every", type=int, default=4,
                   help="steps between elastic agreement polls (each is "
                        "one small host allgather at world > 1; smaller "
                        "reacts faster, larger costs less)")
    p.add_argument("--compile-cache", type=str, default="auto",
                   help="persistent XLA compilation-cache dir ('auto' = "
                        "where JAX_COMPILATION_CACHE_DIR says, else "
                        "<repo>/.jax_cache; 'off' disables): warm "
                        "restarts skip the per-bucket-shape compile bill")
    return p.parse_args(argv)


def apply_platform(args) -> None:
    if args.platform != "default":
        jax.config.update("jax_platforms", args.platform)


def validate_trace_args(args):
    """Parse ``--trace-steps`` (SystemExit on malformed specs, BEFORE any
    runtime init) and require the trace destination."""
    from can_tpu.obs import parse_trace_steps

    try:
        window = parse_trace_steps(getattr(args, "trace_steps", ""))
    except ValueError as e:
        raise SystemExit(str(e))
    if window and not args.profile_dir:
        raise SystemExit("--trace-steps needs --profile-dir (the trace's "
                         "output directory)")
    return window


def validate_incident_args(args) -> None:
    """Pure arg/path validation for the incident/SLO flags — run BEFORE
    any runtime init (a typo'd spec must not cost a multi-host
    rendezvous, the same contract as the dataset path checks).  Shared
    by all three CLIs."""
    spec_path = getattr(args, "slo_spec", "")
    if spec_path:
        from can_tpu.obs.slo import load_slo_spec

        try:
            # stash the PARSED spec: build_telemetry runs after
            # init_runtime, and re-reading the file there would reopen
            # the post-rendezvous failure window this validation closes
            # (a spec replaced mid-launch on a shared FS)
            args._slo_spec_parsed = load_slo_spec(spec_path)
        except OSError as e:
            raise SystemExit(f"--slo-spec: cannot read {spec_path}: {e}")
        except ValueError as e:
            raise SystemExit(f"--slo-spec: {e}")
    incident_dir = getattr(args, "incident_dir", "")
    if incident_dir:
        import os as _os

        try:
            _os.makedirs(incident_dir, exist_ok=True)
        except OSError as e:
            raise SystemExit(f"--incident-dir: cannot create "
                             f"{incident_dir}: {e}")


def build_telemetry(args, *, host_id: int, trace_window, logger=None,
                    install_signals: bool = True):
    """The CLIs' shared wiring: per-host JSONL sink (``--telemetry-dir``),
    MetricLogger adapter (epoch scalars keep flowing to stdout/wandb
    unchanged), optional step-range trace window, heartbeat thread, and —
    with ``--metrics-port`` — an in-memory gauge sink plus the live
    Prometheus exporter (obs/exporter.py).  ``--incident-dir`` adds the
    flight recorder + IncidentManager (+ the SIGTERM/preemption hook,
    unless ``install_signals=False`` — in-process tests must not retarget
    the interpreter's signal table); ``--slo-spec`` adds the SLO
    burn-rate engine.  Returns
    ``(telemetry, heartbeat_or_None, exporter_or_None)`` — tear the
    stack down with ``obs.shutdown_telemetry`` (one deterministic order
    for clean exit and SIGTERM alike).

    ``--collector-push URL`` adds a best-effort push sink streaming the
    bus to a FleetCollector; ``CAN_TPU_HOST_ID`` overrides the host id
    on every emitted event (several processes on one machine all read
    ``process_index() == 0`` — the fleet view needs them distinct)."""
    from can_tpu import obs

    env_hid = os.environ.get("CAN_TPU_HOST_ID", "")
    if env_hid:
        try:
            host_id = int(env_hid)
        except ValueError:
            raise SystemExit(f"CAN_TPU_HOST_ID: not an int: {env_hid!r}")
    trace = (obs.StepTraceWindow(args.profile_dir, *trace_window)
             if trace_window else None)
    extra = [obs.MetricLoggerSink(logger)] if logger is not None else []
    collector_url = getattr(args, "collector_push", "")
    if collector_url:
        extra.append(obs.CollectorPushSink(collector_url))
    exporter = None
    gauges = None
    metrics_port = getattr(args, "metrics_port", None)
    incident_dir = getattr(args, "incident_dir", "")
    slo_spec_path = getattr(args, "slo_spec", "")
    if metrics_port is not None or incident_dir or slo_spec_path:
        # the gauge sink exists for ANY of its three consumers: the
        # scrape endpoint, the bundle's gauges.json snapshot, and the
        # SLO layer's can_tpu_slo_* exports
        gauges = obs.GaugeSink()
        extra.append(gauges)
    if metrics_port is not None:
        exporter = obs.MetricsExporter(
            gauges, host=getattr(args, "metrics_host", "127.0.0.1"),
            port=metrics_port).start()
        print(f"[metrics] /metrics + /healthz on "
              f"http://{exporter.host}:{exporter.port}")
    recorder = None
    if incident_dir:
        recorder = obs.FlightRecorder()
        extra.append(recorder)
    if args.telemetry_dir:
        tel = obs.open_host_telemetry(args.telemetry_dir, host_id=host_id,
                                      extra_sinks=extra, trace=trace)
    else:
        tel = obs.Telemetry(extra, host_id=host_id, trace=trace)
    # the gauge sink rides the bus handle (like .ledger/.spans): the
    # serve CLI's autoscaler reads can_tpu_slo_alerting from it
    tel._gauge_sink = gauges
    # performance-attribution collaborators ride the same arming rule as
    # the loop instrumentation: any consumer (JSONL artifact, live
    # /metrics scraper, trace window, incident recorder, SLO engine)
    # arms the cost ledger + span tracer; a default run constructs
    # neither, so nothing new can touch its hot path.  The ledger prices
    # MFU against the run's COMPUTE dtype.
    if (args.telemetry_dir or exporter is not None or trace_window
            or incident_dir or slo_spec_path):
        tel.ledger = obs.ProgramCostLedger(
            compute="bf16" if getattr(args, "bf16", False) else "f32")
        tel.spans = obs.SpanTracer(tel)
        if trace is not None:
            trace.spans = tel.spans
    run_config = {k: v for k, v in vars(args).items()
                  if isinstance(v, (str, int, float, bool, type(None)))}
    if slo_spec_path:
        # the spec validate_incident_args already parsed (pre-init, so
        # a bad file can't cost a rendezvous); loaded here only for
        # callers that skipped validation.  Watcher order vs the
        # incident manager is irrelevant — slo.burn alerts reach it
        # through the bus's own watcher fan-out.
        spec = getattr(args, "_slo_spec_parsed", None)
        if spec is None:
            spec = obs.load_slo_spec(slo_spec_path)
        tel.watchers.append(obs.SloEngine(spec, tel))
    if incident_dir:
        manager = obs.IncidentManager(tel, recorder,
                                      incident_dir=incident_dir,
                                      gauges=gauges,
                                      run_config=run_config,
                                      host_id=host_id)
        tel.watchers.append(manager)
        tel.incidents = manager
        if install_signals:
            # SIGTERM/preemption: dump + flush a bundle, then SystemExit
            # into the CLI's finally -> shutdown_telemetry (same order
            # as a clean exit); None off the main thread
            obs.install_sigterm_handler(manager)
    tel.emit("run", config=run_config)
    # heartbeat whenever an artifact OR a live consumer wants liveness:
    # the exporter's last_heartbeat_ts gauge is the probe's staleness
    # signal, the ring's heartbeat tail dates a preempted bundle, and
    # heartbeats drive SLO evaluation on otherwise-quiet runs
    hb = (obs.Heartbeat(tel, args.telemetry_heartbeat_s)
          if (args.telemetry_dir or exporter is not None or incident_dir
              or slo_spec_path) else None)
    return tel, hb, exporter


def apply_compile_cache(args, *, announce: bool = False) -> None:
    from can_tpu.utils import enable_compilation_cache

    spec = getattr(args, "compile_cache", "auto")
    try:
        cache_dir = enable_compilation_cache(None if spec == "auto" else spec)
    except ValueError as e:
        raise SystemExit(f"--compile-cache: {e}")
    if announce and cache_dir:
        print(f"[xla] persistent compilation cache at {cache_dir}")


def resolve_num_workers(args) -> int:
    if getattr(args, "num_workers", None) is not None:
        return max(0, args.num_workers)
    return min(8, os.cpu_count() or 1)


def main(argv=None) -> int:
    args = parse_args(argv)
    # pure arg/path validation BEFORE any runtime init: a typo'd path must
    # not cost a multi-host rendezvous
    if args.eval_interval < 1:
        # 0 conventionally means 'off' elsewhere, but here it would
        # ZeroDivisionError only AFTER a full epoch trained with nothing
        # checkpointed (code-review r5) — reject before any work
        raise SystemExit("--eval-interval must be >= 1 (the final epoch "
                         "always evaluates; large values approximate "
                         "'rarely')")
    if args.elastic_check_every < 1:
        raise SystemExit("--elastic-check-every must be >= 1")
    train_img, train_gt = resolve_split_roots(
        "train", args.train_image_root, args.train_gt_root, args.data_root)
    test_img, test_gt = resolve_split_roots(
        "test", args.test_image_root, args.test_gt_root, args.data_root)
    if args.init_torch_pth:
        if args.syncBN:
            raise SystemExit("--init-torch-pth holds the reference model "
                             "(no BatchNorm); drop --syncBN")
        if args.vgg16_npz:
            raise SystemExit("--init-torch-pth already contains the trained "
                             "frontend; drop --vgg16-npz")
        if args.init_checkpoint:
            raise SystemExit("--init-torch-pth (fresh warm-start) and "
                             "--init_checkpoint (full-state resume) "
                             "conflict — the resume would silently replace "
                             "the warm-started params; pick one")
        if not os.path.isfile(args.init_torch_pth):
            raise SystemExit(f"no such checkpoint file: {args.init_torch_pth}")
    if args.item_cache_mb < 0:
        raise SystemExit("--item-cache-mb must be >= 0")
    # resume-config guard (pure file reading, BEFORE any runtime init):
    # a schedule-bearing flag that silently differs from the checkpoint's
    # run breaks the cosine schedule / data order the resumed state
    # assumes — fail here unless the drift is explicitly allowed
    run_cfg = {"lr": args.lr, "lrf": args.lrf, "epochs": args.epochs,
               "batch_size": args.batch_size, "seed": args.seed,
               "syncBN": bool(args.syncBN), "bf16": bool(args.bf16)}
    from can_tpu.utils.checkpoint import (
        ConfigDriftError,
        check_resume_config,
        has_checkpoint,
        load_run_config,
        save_run_config,
    )

    if args.init_checkpoint:
        from can_tpu.parallel.elastic import load_manifest as _el_manifest

        saved_cfg = load_run_config(args.init_checkpoint)
        # guard only REAL resumes: a config with no checkpoint beside it
        # (a run that crashed before its first save) cold-starts, and a
        # cold start has no restored schedule to protect.  A preemption
        # BEFORE the first epoch save leaves no integer step dir but DOES
        # leave an elastic manifest + shrink checkpoint — that mid-epoch
        # state's schedule needs the guard every bit as much (elastic is
        # a world change, never a licence for schedule drift).
        # world_size itself is checked POST-init (dp is unknown before
        # devices exist) with the elastic allowance — strip it here
        resumable = (has_checkpoint(args.init_checkpoint)
                     or _el_manifest(args.init_checkpoint) is not None)
        if saved_cfg is not None and resumable:
            sched_cfg = {k: v for k, v in saved_cfg.items()
                         if k != "world_size"}
            try:
                drifted = check_resume_config(sched_cfg, run_cfg,
                                              allow=args.allow_config_change)
            except ConfigDriftError as e:
                raise SystemExit(f"{e} (pass --allow-config-change to "
                                 "resume with the new schedule anyway)")
            if drifted:
                print(f"[resume] config drift allowed: {', '.join(drifted)}")
    trace_window = validate_trace_args(args)
    validate_incident_args(args)
    # per-step instrumentation is on when ANY consumer exists: JSONL
    # artifact, trace window, live /metrics scraper, incident recorder,
    # or SLO engine.  Known before any runtime work so the step builders
    # can compile the health scalars in; a default run keeps the exact
    # pre-PR programs.
    instrument = bool(args.telemetry_dir or trace_window
                      or args.metrics_port is not None
                      or args.incident_dir or args.slo_spec)
    apply_platform(args)
    topo = init_runtime()
    # the elastic supervisor's SIGTERM hook: installed AFTER init_runtime
    # (jax.distributed.initialize registers XLA's own preemption notifier
    # at initialize, clobbering handlers installed earlier) and BEFORE
    # the incident manager's (build_telemetry, inside the generation
    # loop): the manager then dumps the preemption bundle FIRST and
    # chains here — which sets the leaving flag and RETURNS, spending the
    # grace window on the shrink choreography instead of exiting
    # mid-collective
    supervisor = None
    if args.elastic_dir:
        from can_tpu.parallel.elastic import ElasticSupervisor

        supervisor = ElasticSupervisor(
            args.elastic_dir, check_every=args.elastic_check_every)
        supervisor.install_signal_hook()
    apply_compile_cache(args, announce=is_main_process())
    if is_main_process():
        print(f"[runtime] {topo}")
        print(f"[start] {datetime.datetime.now():%Y-%m-%d %H:%M:%S}")
        if args.syncBN:
            print("[model] BatchNorm variant; stats sync across replicas "
                  f"via global-batch reductions (moments path: "
                  f"{args.bn_impl})")
    return _run_elastic_generations(
        args, run_cfg, topo, supervisor=supervisor,
        trace_window=trace_window, instrument=instrument,
        split_roots=(train_img, train_gt, test_img, test_gt),
        save_run_config=save_run_config,
        check_resume_config=check_resume_config)


def _run_elastic_generations(args, run_cfg, topo, *, supervisor,
                             trace_window, instrument, split_roots,
                             save_run_config, check_resume_config) -> int:
    """The generation loop: build the world, train; on an agreed elastic
    shrink, checkpoint + tear down + re-rendezvous and loop — every
    iteration is one runtime generation (parallel/runtime.py).  The
    telemetry stack and datasets are built once and survive transitions;
    everything device-bound (mesh, steps, batchers, state) is rebuilt
    per generation.  Pre-elastic runs execute exactly one iteration."""
    from can_tpu.parallel import elastic as el
    from can_tpu.utils.checkpoint import CheckpointIOError, ConfigDriftError

    train_img, train_gt, test_img, test_gt = split_roots
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    pad_multiple, min_pad, min_bucket_h = resolve_sp_padding(
        args.pad_multiple, args.sp)

    from can_tpu.cli.common import split_prepared_spec
    from can_tpu.data import ItemCache, StaleStoreError

    # datasets + item cache are world-INDEPENDENT (host-side decode):
    # built once, they survive elastic transitions — only device-bound
    # objects rebuild per generation
    item_cache = (ItemCache(int(args.item_cache_mb * 1e6))
                  if args.item_cache_mb > 0 else None)
    try:
        train_ds = CrowdDataset(train_img, train_gt, gt_downsample=8,
                                phase="train", u8_output=args.u8_input,
                                prepared=split_prepared_spec(
                                    args.prepared_root, "train"),
                                item_cache=item_cache)
        test_ds = CrowdDataset(test_img, test_gt, gt_downsample=8,
                               phase="test", u8_output=args.u8_input,
                               prepared=split_prepared_spec(
                                   args.prepared_root, "test"),
                               item_cache=item_cache)
    except StaleStoreError as e:
        raise SystemExit(f"--prepared-root {args.prepared_root}: {e}")
    num_workers = resolve_num_workers(args)

    # cross-generation context: the telemetry stack is built by the FIRST
    # generation and survives transitions (the elastic.transition event
    # rides the same bus as everything else); pending_manifest hands the
    # shrink record from the dying generation to the next iteration
    ctx = {"telemetry": None, "heartbeat": None, "exporter": None,
           "logger": None, "pending_manifest": None, "best_mae": None,
           "generations": 0}

    def run_generation():
        """One runtime generation: build the world at the CURRENT
        process_count/device set, (elastic-)resume, train.  Returns
        ("done"|"abort", rc) or ("reform", None) or ("leave", rc)."""
        from can_tpu.utils.checkpoint import has_checkpoint, load_run_config

        ctx["generations"] += 1
        first_gen = ctx["generations"] == 1
        main_proc = is_main_process()
        mesh, host_batch, dp = build_mesh_and_batch(args.batch_size, args.sp)
        # SyncBN moments path (ops/bn_moments.py): built only for
        # --syncBN so a default run constructs nothing new — its lowered
        # step must stay byte-identical (tests/test_batchnorm.py)
        bn_ops = None
        if args.syncBN:
            from can_tpu.ops.bn_moments import make_bn_ops

            if args.bn_impl == "pallas":
                if args.sp == 1 and dp > 1:
                    # pallas_call has no GSPMD partitioning rule: under
                    # the jit-sharded dp step it would force a gather;
                    # inside the sp shard_map body it composes fine
                    raise SystemExit(
                        "--bn-impl pallas needs --sp > 1 (the kernel "
                        "runs per-device inside shard_map) or a single "
                        "device; use onepass for the GSPMD data-parallel "
                        "step")
                from can_tpu.utils import pallas_interpret

                bn_ops = make_bn_ops("pallas", interpret=pallas_interpret())
            else:
                bn_ops = make_bn_ops(args.bn_impl)
        if args.sp > 1 and main_proc and first_gen and pad_multiple != "auto":
            print(f"[data] sp={args.sp}: padding H,W to multiples of "
                  f"{pad_multiple}")
        import math as _math

        # legal remnant sub-batch sizes must split evenly across hosts
        # AND across the mesh's dp axis (make_global_batch shards the
        # leading dim).  The quantum is a property of THIS generation's
        # world: after a shrink the planner replans under the new one.
        quantum = _math.lcm(dp, process_count())
        common = dict(seed=args.seed, process_index=process_index(),
                      process_count=process_count(),
                      pad_multiple=pad_multiple,
                      min_pad_multiple=min_pad, min_bucket_h=min_bucket_h,
                      num_workers=num_workers, max_buckets=args.max_buckets,
                      remnant_sizes=not args.no_remnant_batches,
                      batch_quantum=quantum, plan_mode=args.plan_mode,
                      launch_cost_px=resolve_launch_cost_px(
                          args.launch_cost_mpx,
                          announce=main_proc and first_gen))
        # HBM agreed across hosts (min) ONCE PER GENERATION: both the
        # launch cap and the remat policy must be identical on every host
        # or the lockstep schedule deadlocks (ADVICE r4)
        from can_tpu.cli.common import (
            agreed_device_memory_bytes,
            device_memory_sources,
        )

        hbm = agreed_device_memory_bytes()
        if main_proc and first_gen:
            limit, spec = device_memory_sources()
            print(f"[hbm] per-device cap {hbm} bytes (memory_stats "
                  f"bytes_limit={limit}, spec table={spec})")
        ndev = dp * args.sp  # devices per launch
        if not args.no_remnant_batches:
            # HBM cap per launch: bucket cells too big for the full
            # global batch run at a smaller menu size instead of OOMing
            from can_tpu.cli.common import max_launch_pixels

            train_common = dict(common,
                                max_launch_px=max_launch_pixels(
                                    bf16=args.bf16, hbm_bytes=hbm,
                                    shards=ndev))
        else:
            train_common = common
        train_batcher = ShardedBatcher(train_ds, host_batch, shuffle=True,
                                       **train_common)
        test_batcher = ShardedBatcher(test_ds, host_batch, shuffle=False,
                                      **common)
        if main_proc:
            print(f"[data] train={len(train_ds)} test={len(test_ds)} "
                  f"host_batch={host_batch} dp={dp} sp={args.sp} "
                  f"workers={num_workers}")
            # compile-count telemetry: every distinct bucket shape
            # compiles its own executable — the first-epoch compile bill
            for tag, b in (("train", train_batcher), ("test", test_batcher)):
                n = b.distinct_shapes(0)
                print(f"[data] {tag}: buckets={b.describe_buckets()} -> "
                      f"{n} distinct batch shapes, "
                      f"{b.program_count(0)} (shape x size) programs "
                      f"(plan={b.plan_mode}, "
                      f"padding overhead {b.padding_overhead():.1%}, "
                      f"schedule overhead {b.schedule_overhead(0):.1%})")
                if n > 4 * b.max_buckets:
                    print(f"[data] WARNING: {n} shapes will each compile "
                          f"a program; use --pad-multiple auto to bound "
                          f"this")

        # identical init on every host by construction: same seed/key
        params = cannet_init(jax.random.key(args.seed),
                             batch_norm=args.syncBN)
        if bn_ops is not None and bn_ops.impl == "pallas" and main_proc \
                and first_gen:
            # the kernel's two silent exits, said out loud at step build:
            # which mode it runs in, and per planned bucket how many BN
            # layers its shape gate lets through
            from can_tpu.cli.common import bn_kernel_routing

            mode = ("INTERPRETED" if bn_ops.interpret
                    else "compiled (not interpreted)")
            print(f"[model] pallas BN-moments kernel: {mode}, platform "
                  f"{jax.devices()[0].platform}")
            for hw in sorted({k for k, _ in train_batcher.global_schedule(0)}):
                k, t = bn_kernel_routing(params, hw, sp=args.sp,
                                         interpret=bn_ops.interpret)
                print(f"[model] bucket {hw[0]}x{hw[1]}: {k} BN layers -> "
                      f"pallas kernel, {t} -> jnp onepass twin "
                      f"(C % 128 / W % 8 gate)")
        if args.vgg16_npz:
            params = load_vgg16_frontend(params, args.vgg16_npz)
            if main_proc and first_gen:
                print(f"[init] loaded pretrained VGG-16 frontend from "
                      f"{args.vgg16_npz}")
        if args.init_torch_pth:
            # the reference's .pth warm-start — params from the torch
            # checkpoint, optimizer/step fresh; deterministic file read
            # on every host => identical init holds
            from can_tpu.utils.torch_import import load_torch_checkpoint

            params = load_torch_checkpoint(args.init_torch_pth)
            if main_proc and first_gen:
                print(f"[init] warm-started params from reference "
                      f"checkpoint {args.init_torch_pth}")

        # the epoch-0 count is exact for EVERY epoch (the plan is a pure
        # function of the shape histogram), so the cosine schedule's
        # endpoint lands exactly on the last step.  After an elastic
        # shrink this recomputes at dp': world_size=dp' IS the linear
        # lr-rescaling rule, and total_steps re-prices the remaining run
        # at the new schedule granularity — both recorded in the
        # elastic.transition event.
        steps_per_epoch = train_batcher.batches_per_epoch(0)
        # priced prefetch depth (the scheduling core's 4th consumer):
        # tiny launches amortise the per-launch dispatch overhead over
        # little compute and need a deeper host pipeline; the historical
        # depth=2 is exactly what the pricing returns for normal batches
        from can_tpu.sched import prefetch_depth_for

        prefetch = prefetch_depth_for(train_batcher)
        # computed ONCE per generation: the depth is a pure function of
        # the batcher's epoch-invariant schedule, and global_schedule(0)
        # is an O(dataset) rebuild — not something the per-epoch eval
        # block should pay
        eval_prefetch = prefetch_depth_for(test_batcher)
        schedule = make_lr_schedule(args.lr, world_size=dp,
                                    total_steps=args.epochs * steps_per_epoch,
                                    lrf=args.lrf)
        optimizer = make_optimizer(schedule)
        state = create_train_state(params, optimizer,
                                   init_batch_stats(params))

        ckpt = CheckpointManager(args.checkpoint_dir)
        # NOTE: the run config (incl. this generation's world_size) is
        # persisted AFTER resume resolution — on an in-place resume
        # (--init_checkpoint == --checkpoint-dir) writing it first would
        # overwrite the saved world_size the drift check below is about
        # to read, neutering the guard

        # -- resume resolution -------------------------------------------
        # priority: an in-process shrink manifest (the generation that
        # just dissolved), else — first generation only — a live elastic
        # manifest in --init_checkpoint (cold restart after preemption),
        # else the normal latest-epoch resume.
        manifest = None
        resumed_from = None
        manifest_dir = None
        start_epoch = 0
        resumed_best = ctx["best_mae"]
        include = None
        if ctx["pending_manifest"] is not None:
            manifest = ctx["pending_manifest"]
            ctx["pending_manifest"] = None
            resumed_from = "in_process"
            manifest_dir = args.checkpoint_dir
        elif first_gen and args.init_checkpoint:
            probe = CheckpointManager(args.init_checkpoint)
            try:
                latest = probe.latest_epoch()
                m = el.load_manifest(args.init_checkpoint)
                if el.manifest_is_live(m, latest):
                    manifest = m
                    resumed_from = "cold_restart"
                    manifest_dir = args.init_checkpoint
                    resumed_best = probe.best_metric()
                    # the drift guard with the ELASTIC allowance: the
                    # live manifest is the permit for a dp-only world
                    # change — anything else would have failed the
                    # schedule-key check pre-init
                    saved_cfg = load_run_config(args.init_checkpoint)
                    if (saved_cfg is not None
                            and "world_size" in saved_cfg):
                        drifted = check_resume_config(
                            {"world_size": saved_cfg["world_size"]},
                            {"world_size": dp},
                            allow=args.allow_config_change,
                            allow_elastic=True)
                        if drifted and main_proc:
                            print(f"[elastic] world drift permitted by "
                                  f"the live transition manifest: "
                                  f"world_size "
                                  f"{saved_cfg['world_size']} -> {dp}")
                else:
                    # the drift guard's world check: a saved world_size
                    # that differs from this world is only legal when an
                    # elastic transition explains it
                    saved_cfg = load_run_config(args.init_checkpoint)
                    if (saved_cfg is not None
                            and has_checkpoint(args.init_checkpoint)
                            and "world_size" in saved_cfg):
                        try:
                            check_resume_config(
                                {"world_size": saved_cfg["world_size"]},
                                {"world_size": dp},
                                allow=args.allow_config_change,
                                allow_elastic=False)
                        except ConfigDriftError as e:
                            raise SystemExit(
                                f"{e} — the checkpoint trained at a "
                                f"different world size and no live "
                                f"elastic manifest explains the change "
                                f"(pass --allow-config-change to resume "
                                f"on the new world anyway)")
                    if latest is not None:
                        state = probe.restore(state)
                        start_epoch = latest + 1
                        # carry the prior leg's best forward so
                        # [best]/[done] report the RUN's best
                        resumed_best = probe.best_metric()
                        if main_proc:
                            print(f"[resume] epoch {latest} from "
                                  f"{args.init_checkpoint}"
                                  + (f" (best so far {resumed_best:.3f})"
                                     if resumed_best is not None else ""))
                    elif main_proc:
                        print(f"[resume] no checkpoint in "
                              f"{args.init_checkpoint}; cold start")
            finally:
                # the restore manager must not stay alive for the whole
                # run — its stale step/metrics view aliases ckpt's
                # directory on an in-place resume (code-review r5)
                probe.close()
        if manifest is not None:
            # elastic resume: restore the EXACT mid-epoch state from the
            # shrink checkpoint, replan the interrupted epoch's remaining
            # items at this world's quantum (exact coverage: consumed ∪
            # remaining = the epoch, pinned by tests), rescale via the
            # dp'-built schedule above
            emgr = CheckpointManager(
                os.path.join(manifest_dir, el.ELASTIC_SUBDIR))
            try:
                state = emgr.restore(state,
                                     epoch=int(manifest["transition_id"]))
            finally:
                emgr.close()
            start_epoch = int(manifest["epoch"])
            rem = el.remaining_items(manifest, len(train_ds))
            include = set(rem) if rem else None
            if not rem:
                start_epoch += 1  # interrupted exactly at the epoch end
            if supervisor is not None:
                # inherit the transition's host bookkeeping (rank
                # re-numbering + handled leavers) so a stale signal file
                # cannot re-trigger the shrink this manifest records
                supervisor.adopt_manifest(manifest)
            if main_proc:
                w_old = manifest["world_old"]
                print(f"[elastic] resuming generation "
                      f"{manifest['generation']} transition: epoch "
                      f"{manifest['epoch']} step {manifest['steps_done']}"
                      f", world {w_old['processes']}proc/dp{w_old['dp']}"
                      f" -> {process_count()}proc/dp{dp}, "
                      f"{len(rem)} item(s) remaining ({resumed_from})")
        if main_proc:
            # persist the schedule-bearing config + this generation's
            # world beside the checkpoints (AFTER the resume resolution
            # read the previous one): the NEXT resume checks flag drift,
            # and a dp-only world change is legal exactly when an
            # elastic manifest explains it
            save_run_config(args.checkpoint_dir,
                            dict(run_cfg, world_size=dp))

        apply_fn = cannet_apply
        if bn_ops is not None and args.sp == 1:
            import functools

            from can_tpu.models.cannet import LocalOps

            # the BN-moments seam rides LocalOps;
            # dp-path only (the sp step takes bn_ops directly)
            apply_fn = functools.partial(cannet_apply,
                                         ops=LocalOps(bn_ops=bn_ops))
        remat_policy = make_remat_policy(args.remat,
                                         global_batch=args.batch_size * dp,
                                         bf16=args.bf16,
                                         announce=main_proc and first_gen,
                                         hbm_bytes=hbm, shards=ndev)
        if args.sp > 1:
            cache = SpatialStepCache(
                lambda hw: make_sp_train_step(optimizer, mesh, hw,
                                              compute_dtype=compute_dtype,
                                              remat=remat_policy(hw),
                                              health_metrics=instrument,
                                              bn_ops=bn_ops))

            def train_step(state, batch):
                return cache(tuple(batch["image"].shape[1:3]))(state, batch)

            # cost-ledger seam: the underlying jitted step for these
            # args, so cost_analysis() reads through the closure
            train_step.jit_for = lambda state, batch: cache(
                tuple(batch["image"].shape[1:3]))
            eval_step = make_cached_sp_eval_step(
                mesh, compute_dtype=compute_dtype)
        else:
            from can_tpu.cli.common import make_bucketed_train_step

            train_step = make_bucketed_train_step(
                apply_fn, optimizer, mesh, compute_dtype=compute_dtype,
                policy=remat_policy, health_metrics=instrument)
            eval_step = make_dp_eval_step(apply_fn, mesh,
                                          compute_dtype=compute_dtype)
        # batches are H-sharded when sp > 1 (train and eval both)
        put = lambda b: make_global_batch(b, mesh, spatial=args.sp > 1)

        if first_gen:
            ctx["logger"] = MetricLogger(
                use_wandb=args.wandb, enabled=main_proc,
                name=f"bs{args.batch_size}x{dp}", config=vars(args),
                run_id_file=os.path.join(args.checkpoint_dir,
                                         "wandb_run_id.txt"))
            # telemetry: per-host JSONL (+ MetricLogger adapter),
            # heartbeat thread, and the step-range trace trigger — built
            # ONCE; elastic transitions keep emitting into the same bus
            ctx["telemetry"], ctx["heartbeat"], ctx["exporter"] = \
                build_telemetry(args, host_id=process_index(),
                                trace_window=trace_window,
                                logger=ctx["logger"])
            if supervisor is not None:
                supervisor.telemetry = ctx["telemetry"]
            # prepared-store status: one data.prepared event per split
            for split, d in (("train", train_ds), ("test", test_ds)):
                ctx["telemetry"].emit("data.prepared", split=split,
                                      **d.prepared_note)
            if main_proc:
                print("[data] prepared store: " + " ".join(
                    f"{split}={'on' if d.prepared_note['active'] else 'legacy(' + str(d.prepared_note['reason']) + ')'}"
                    for split, d in (("train", train_ds),
                                     ("test", test_ds))))
        if not first_gen:
            # a transition may have promoted a DIFFERENT host to main
            # (the old rank 0 left): the once-constructed logger follows
            # the role, or stdout/wandb epoch rows silently stop for the
            # rest of the run.  (A wandb stream stays owned by the
            # original main if it left — re-initialising a wandb run
            # mid-process isn't supported; stdout rows resume.)
            ctx["logger"].enabled = main_proc
        telemetry = ctx["telemetry"]
        if telemetry.ledger is not None:
            # the drift gauge's denominator: the launch cost THIS run's
            # plans were priced at
            telemetry.ledger.plan_launch_cost_px = common["launch_cost_px"]
        if manifest is not None:
            # the transition record: world change + rescaling, exactly
            # once per transition (survivor leg or cold restart).
            # Through the supervisor when armed — its transitions
            # counter then covers cold restarts too
            topo_now = {"generation": runtime_generation(),
                        "process_count": process_count()}
            emitter = (supervisor.emit_transition
                       if supervisor is not None else None)
            if emitter is None:
                def emitter(m, t, **kw):
                    el.emit_transition(telemetry, m, t, **kw)
            emitter(manifest, topo_now, new_dp=dp,
                    remaining=0 if include is None else len(include),
                    global_batch_new=host_batch * process_count(),
                    resumed_from=resumed_from)
        # the LOOPS are instrumented only when something consumes
        # per-step data: the default run's hot path stays byte-identical
        loop_tel = telemetry if instrument else None
        from can_tpu.obs import HealthMonitor

        health = HealthMonitor(telemetry) if loop_tel is not None else None
        best_mae = (float("inf") if resumed_best is None
                    else float(resumed_best))
        world_closed = False  # elastic branch closes early, pre-reform
        try:
            with profile_trace(None if trace_window
                               else (args.profile_dir or None),
                               spans=telemetry.spans):
                for epoch in range(start_epoch, args.epochs):
                    inc = include if epoch == start_epoch else None
                    total = (steps_per_epoch if inc is None else
                             len(train_batcher.global_schedule(epoch, inc)))
                    batches = train_batcher.epoch(epoch, inc)
                    if args.max_steps_per_epoch:
                        import itertools

                        batches = itertools.islice(
                            batches, args.max_steps_per_epoch)
                    on_step = (supervisor.step_hook(epoch)
                               if supervisor is not None else None)
                    try:
                        state, stats = train_one_epoch(
                            train_step, state, batches, put_fn=put,
                            epoch=epoch, show_progress=main_proc,
                            total=total, telemetry=loop_tel,
                            health=health, on_step=on_step,
                            prefetch=prefetch)
                    except el.ElasticInterrupt as interrupt:
                        # the agreed shrink point: flush any in-flight
                        # async save FIRST (its arrays must reach disk
                        # while the old world's backends are alive),
                        # checkpoint at a bounded barrier, then leave or
                        # re-form
                        ckpt.wait()
                        sched = train_batcher.global_schedule(epoch, inc)
                        # prior coverage exists only while TRAINING the
                        # resumed remainder itself (inc is not None): a
                        # manifest whose remainder was empty bumped
                        # start_epoch, and its consumed set belongs to
                        # the FINISHED epoch, not this one
                        prior = (manifest.get("consumed", ())
                                 if manifest is not None
                                 and inc is not None else ())
                        new_manifest = supervisor.shrink(
                            interrupt, state=interrupt.state, epoch=epoch,
                            checkpoint_dir=args.checkpoint_dir,
                            schedule=sched, dp=dp, sp=args.sp,
                            batch_size=host_batch, prior_consumed=prior)
                        ctx["best_mae"] = (None if best_mae == float("inf")
                                           else best_mae)
                        # device-bound teardown BEFORE leave/reform:
                        # reform() resets the PJRT backends, and the
                        # generation's finally must not wait on Orbax
                        # ops whose arrays' backend no longer exists
                        train_batcher.close()
                        test_batcher.close()
                        ckpt.close()
                        world_closed = True
                        if process_index() in new_manifest["leavers"]:
                            if main_proc:
                                print("[elastic] leaving after shrink "
                                      "checkpoint (preempted)")
                            return ("leave", supervisor.leave())
                        supervisor.reform(new_manifest)
                        ctx["pending_manifest"] = new_manifest
                        return ("reform", None)
                    # every epoch: loss, throughput, shape count
                    epoch_metrics = {
                        "train_loss": stats.loss,
                        "lr": float(schedule(int(state.step))),
                        "img_per_s": round(stats.img_per_s, 2),
                        "epoch_s": round(stats.seconds, 2),
                        "distinct_shapes": stats.distinct_shapes,
                    }

                    # always evaluate+checkpoint the FINAL epoch too
                    eval_epoch = ((epoch + 1) % args.eval_interval == 0
                                  or epoch == args.epochs - 1)
                    if eval_epoch:
                        metrics = evaluate(
                            eval_step, state.params, test_batcher.epoch(0),
                            put_fn=put,
                            dataset_size=test_batcher.dataset_size,
                            batch_stats=state.batch_stats,
                            telemetry=loop_tel,
                            prefetch=eval_prefetch)
                        mae = metrics["mae"]
                        epoch_metrics.update(mae=mae, mse=metrics["mse"])
                    # through the bus: MetricLoggerSink forwards scalars
                    # to stdout/wandb; JSONL records the epoch event
                    telemetry.emit("epoch", step=epoch, **epoch_metrics)
                    telemetry.emit("data.planner", step=epoch,
                                   realized_programs=stats.programs,
                                   **train_batcher.planner_stats(epoch))
                    if item_cache is not None:
                        telemetry.emit("data.cache", step=epoch,
                                       **item_cache.stats())
                    if eval_epoch:
                        ckpt.save(epoch, state, mae=mae,
                                  extra={"mse": metrics["mse"]})
                        if mae < best_mae:
                            best_mae = mae
                            ctx["best_mae"] = best_mae
                            if main_proc:
                                print(f"[best] epoch {epoch}: "
                                      f"MAE {mae:.3f}")
                        if args.show and main_proc:
                            _save_sample_viz(args, state, test_ds, epoch,
                                             ctx["logger"])
        except NonFiniteLossError as e:
            print(f"[abort] {e}", file=sys.stderr)
            return ("abort", 1)
        except CheckpointIOError as e:
            # the typed give-up after exhausted retries: one incident
            # bundle (when armed), then a clean abort — the run cannot
            # promise resumability without its checkpoint
            inc_mgr = getattr(telemetry, "incidents", None)
            if inc_mgr is not None:
                inc_mgr.on_exception(e, phase="checkpoint")
            print(f"[abort] {e}", file=sys.stderr)
            return ("abort", 1)
        finally:
            if not world_closed:
                train_batcher.close()
                test_batcher.close()
                ckpt.wait()
                ckpt.close()
        ctx["best_mae"] = None if best_mae == float("inf") else best_mae
        if main_proc:
            print(f"[done] best MAE {best_mae:.3f}")
        return ("done", 0)

    from can_tpu.parallel.runtime import generation as runtime_generation

    try:
        while True:
            outcome, rc = run_generation()
            if outcome != "reform":
                return rc
            # else: a new generation formed — loop rebuilds the world
    finally:
        # one deterministic teardown order for clean exit, abort, leave,
        # AND the SIGTERM path (obs/lifecycle.py): heartbeat ->
        # watchers+sinks -> exporter; then the supervisor's signal hook
        # and the runtime (idempotent after a leave)
        if ctx["telemetry"] is not None:
            from can_tpu.obs import shutdown_telemetry

            shutdown_telemetry(ctx["telemetry"], heartbeat=ctx["heartbeat"],
                               exporter=ctx["exporter"])
        if ctx["logger"] is not None:
            ctx["logger"].finish()
        if supervisor is not None:
            supervisor.close()
        shutdown_runtime()  # the reference never calls its cleanup()



_viz_forward = None  # module-level so repeat shapes hit the jit cache


def _save_sample_viz(args, state, test_ds, epoch, logger) -> None:
    from can_tpu.utils import save_density_visualization

    global _viz_forward
    if _viz_forward is None:
        from can_tpu.cli.common import make_inference_forward

        _viz_forward = make_inference_forward()
    from can_tpu.data import normalize_host

    idx = int(np.random.default_rng((args.seed, epoch)).integers(len(test_ds)))
    img, gt = test_ds[idx]
    img = normalize_host(img)  # no-op for the f32 path
    # This runs on rank 0 ONLY, so it must not issue a computation over
    # the globally-committed params (unmatched multi-host computation =
    # error or pod wedge, code-review r5): pull the replicated params to
    # host (a local read of addressable shards) and jit over local
    # arrays instead.
    host_params = jax.device_get(state.params)
    host_stats = (jax.device_get(state.batch_stats)
                  if state.batch_stats is not None else None)
    et = _viz_forward(host_params, jnp.asarray(img)[None], host_stats)
    out_dir = os.path.join(args.checkpoint_dir, "temp")
    paths = save_density_visualization(img, gt, np.asarray(et)[0], out_dir,
                                       tag=f"epoch{epoch}")
    logger.log_images(paths, caption=f"epoch {epoch}", step=epoch)


if __name__ == "__main__":
    raise SystemExit(main())
