"""Evaluation / inference CLI — the reference's ``test.py`` re-done.

``cal_mae`` (reference test.py:10-35) → dataset MAE/MSE from a checkpoint;
``estimate_density_map`` (test.py:38-62) → save a single image's predicted
density map.  Paths come from flags instead of the reference's hardcoded
ShanghaiA locations (test.py:67-69).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from can_tpu.cli.common import (
    build_mesh_and_batch,
    make_cached_sp_eval_step,
    parse_pad_multiple,
    resolve_launch_cost_px,
    resolve_split_roots,
    resolve_sp_padding,
)
from can_tpu.data import CrowdDataset, ShardedBatcher
from can_tpu.models import cannet_apply, cannet_init, init_batch_stats
from can_tpu.parallel import (
    init_runtime,
    make_dp_eval_step,
    make_global_batch,
    process_count,
    process_index,
    shutdown_runtime,
)
from can_tpu.train import create_train_state, evaluate, make_lr_schedule, make_optimizer
from can_tpu.utils import CheckpointManager, save_density_visualization


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="CANNet TPU evaluation")
    p.add_argument("--data_root", type=str, default="",
                   help="ShanghaiTech-layout root "
                        "(<root>/<split>_data/{images,ground_truth})")
    p.add_argument("--image-root", type=str, default="",
                   help="explicit image dir (VisDrone-style layouts); "
                        "pair with --gt-root")
    p.add_argument("--gt-root", type=str, default="")
    p.add_argument("--split", type=str, default="test", choices=["train", "test"])
    # default=None sentinel, resolved to ./checkpoints AFTER conflict
    # checks: the --torch-pth conflict must key on "flag was provided",
    # not on the literal default string (ADVICE r5 — an explicit
    # `--checkpoint-dir ./checkpoints` used to slip through)
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="Orbax checkpoint dir (default ./checkpoints)")
    p.add_argument("--epoch", type=int, default=None,
                   help="checkpoint epoch (default: best by MAE, else latest)")
    p.add_argument("--torch-pth", type=str, default="",
                   help="evaluate a REFERENCE torch checkpoint directly "
                        "(e.g. the published epoch_354.pth, reference "
                        "test.py:69) — imported via utils/torch_import.py, "
                        "no prior conversion needed")
    p.add_argument("--params-npz", type=str, default="",
                   help="evaluate a tools/import_torch_checkpoint.py .npz "
                        "(torch-free path)")
    p.add_argument("--batch-size", type=int, default=1,
                   help="images per data-parallel replica")
    p.add_argument("--sp", type=int, default=1,
                   help="spatial (image-height) shards per replica — for "
                        "images too large for one chip (UCF-QNRF scale); "
                        "forces bucket shapes to multiples of 8*sp")
    p.add_argument("--pad-multiple", type=parse_pad_multiple, default="exact",
                   help="'exact' (default): per-resolution compiles but "
                        "bit-exact boundary math — eval is the parity "
                        "oracle, so correctness beats compile time here; "
                        "'auto' bounds compiled shapes (padding shifts the "
                        "conv boundary, perturbing edge-adjacent cells); "
                        "or an int multiple")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--show-index", type=int, default=None,
                   help="also save a density-map visualization of this item")
    p.add_argument("--out-dir", type=str, default="./eval_out")
    p.add_argument("--platform", type=str, default="default",
                   choices=["default", "cpu", "tpu"])
    p.add_argument("--syncBN", action="store_true",
                   help="checkpoint is the BatchNorm model variant")
    p.add_argument("--u8-input", action="store_true",
                   help="ship uint8 pixels, normalise on device (see train "
                        "CLI; pixels differ by u8 resize rounding, so keep "
                        "the default f32 for bit-exact paper numbers)")
    p.add_argument("--num-workers", type=int, default=None,
                   help="host data-loading threads (default: min(8, cpus); "
                        "0 = main thread)")
    p.add_argument("--prepared-root", type=str, default="auto",
                   help="prepared 1/8-density store: 'auto' (default) "
                        "probes <gt_root>/prepared and falls back to the "
                        "legacy decode when absent/stale; 'off' disables; "
                        "a path points at a root holding per-split stores "
                        "(<path>/<split>, the train CLI's and "
                        "--prepared-out's layout) and MUST validate "
                        "(numerics are bit-identical either way — see "
                        "tools/prepare_data.py --prepared)")
    p.add_argument("--item-cache-mb", type=float, default=0.0,
                   help="bounded in-RAM LRU over decoded items, in MB "
                        "(0 = off).  A single eval pass decodes each "
                        "unique item once regardless — this pays off for "
                        "fill-slot duplicates and for callers that loop "
                        "evaluations in one process")
    p.add_argument("--compile-cache", type=str, default="auto",
                   help="persistent XLA compilation-cache dir ('auto' = "
                        "where JAX_COMPILATION_CACHE_DIR says, else "
                        "<repo>/.jax_cache; 'off' disables)")
    p.add_argument("--profile-dir", type=str, default="",
                   help="jax.profiler trace output dir (with --trace-steps)")
    p.add_argument("--trace-steps", type=str, default="",
                   help="trace WINDOW by eval-batch range, START:STOP "
                        "slice semantics, into --profile-dir")
    p.add_argument("--telemetry-dir", type=str, default="",
                   help="write structured telemetry JSONL here (same "
                        "schema as the train CLI; one file per host)")
    p.add_argument("--telemetry-heartbeat-s", type=float, default=60.0,
                   help="heartbeat event interval (with --telemetry-dir)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus-text /metrics + /healthz on this "
                        "port during the eval (0 = ephemeral; see the "
                        "train CLI — long high-res evals are worth "
                        "watching too)")
    p.add_argument("--metrics-host", type=str, default="127.0.0.1",
                   help="bind address for --metrics-port")
    p.add_argument("--collector-push", type=str, default="",
                   metavar="URL",
                   help="stream telemetry to a FleetCollector "
                        "(can_tpu.cli.collect) at URL — best-effort "
                        "batched JSONL over HTTP (see the train CLI)")
    p.add_argument("--incident-dir", type=str, default="",
                   help="arm the incident layer: flight-recorder ring + "
                        "trigger-dumped bundles + SIGTERM/preemption "
                        "hook (see the train CLI; long high-res evals "
                        "die to preemption too)")
    p.add_argument("--slo-spec", type=str, default="",
                   help="JSON SLO spec evaluated live as multi-window "
                        "burn rates (see the train CLI / slo_spec.json)")
    p.add_argument("--max-buckets", type=int, default=24,
                   help="compile budget for --pad-multiple auto (distinct "
                        "(shape x batch-size) programs)")
    p.add_argument("--no-remnant-batches", action="store_true",
                   help="with --pad-multiple auto, pad straggler groups to "
                        "the full batch instead of emitting smaller "
                        "sub-batches (see train CLI)")
    from can_tpu.cli.common import parse_launch_cost

    p.add_argument("--launch-cost-mpx", type=parse_launch_cost, default=2.0,
                   help="per-launch cost for the remnant planner, in "
                        "megapixel-equivalents, or 'auto' to measure this "
                        "host's dispatch overhead (see train CLI)")
    return p.parse_args(argv)


def validate_params_source(args) -> None:
    """Reject conflicting/invalid checkpoint-source flags, then resolve the
    ``--checkpoint-dir`` default.  Shared by the eval and serve CLIs (both
    load params through :func:`load_params`); pure arg validation — safe
    to run before any runtime init."""
    import os as _os

    if args.torch_pth and args.params_npz:
        raise SystemExit("give --torch-pth OR --params-npz, not both")
    if (args.torch_pth or args.params_npz) and args.syncBN:
        raise SystemExit("--torch-pth/--params-npz hold the reference "
                         "model (no BatchNorm); drop --syncBN")
    # imported params are a complete model: checkpoint-selection flags
    # would be silently ignored, so reject them like the conflicts above
    if (args.torch_pth or args.params_npz) and args.epoch is not None:
        raise SystemExit("--epoch selects an Orbax checkpoint epoch; it "
                         "does not apply to --torch-pth/--params-npz")
    if (args.torch_pth or args.params_npz) \
            and args.checkpoint_dir is not None:
        raise SystemExit("--checkpoint-dir is ignored with "
                         "--torch-pth/--params-npz; drop one of them")
    for p in (args.torch_pth, args.params_npz):
        if p and not _os.path.isfile(p):
            raise SystemExit(f"no such checkpoint file: {p}")
    if args.checkpoint_dir is None:
        args.checkpoint_dir = "./checkpoints"


def load_params(args):
    """Restore (params, batch_stats) from the checkpoint manager (best epoch
    by default), or import reference/converted weights directly."""
    if args.torch_pth or args.params_npz:
        if args.torch_pth:
            from can_tpu.utils.torch_import import load_torch_checkpoint

            params = load_torch_checkpoint(args.torch_pth)
            print(f"[load] reference torch checkpoint {args.torch_pth}")
        else:
            from can_tpu.utils.torch_import import load_params_npz

            params = load_params_npz(args.params_npz)
            print(f"[load] imported params {args.params_npz}")
        return params, None
    params = cannet_init(jax.random.key(args.seed), batch_norm=args.syncBN)
    optimizer = make_optimizer(make_lr_schedule(1e-7))
    state = create_train_state(params, optimizer, init_batch_stats(params))
    ckpt = CheckpointManager(args.checkpoint_dir)
    epoch = args.epoch
    if epoch is None:
        epoch = ckpt.best_epoch()
    if epoch is None:  # no metrics recorded: fall back to latest
        epoch = ckpt.latest_epoch()
    state = ckpt.restore(state, epoch=epoch)
    ckpt.close()
    print(f"[load] epoch {epoch} from {args.checkpoint_dir}")
    return state.params, state.batch_stats


def main(argv=None) -> int:
    args = parse_args(argv)
    # pure arg/path validation BEFORE runtime init / checkpoint restore
    img_root, gt_root = resolve_split_roots(
        args.split, args.image_root, args.gt_root, args.data_root,
        flag_stem="")
    validate_params_source(args)
    if args.item_cache_mb < 0:
        raise SystemExit("--item-cache-mb must be >= 0")
    from can_tpu.cli.train import (
        apply_compile_cache,
        apply_platform,
        build_telemetry,
        resolve_num_workers,
        validate_incident_args,
        validate_trace_args,
    )

    trace_window = validate_trace_args(args)
    validate_incident_args(args)
    apply_platform(args)
    topo = init_runtime()
    apply_compile_cache(args, announce=process_index() == 0)
    if process_index() == 0:
        print(f"[runtime] {topo}")
    telemetry, heartbeat, exporter = build_telemetry(
        args, host_id=process_index(), trace_window=trace_window)
    # loop instrumentation only when something consumes it (see train CLI)
    loop_tel = telemetry if (args.telemetry_dir or trace_window
                             or exporter is not None or args.incident_dir
                             or args.slo_spec) else None
    try:
        params, batch_stats = load_params(args)
        compute_dtype = jnp.bfloat16 if args.bf16 else None
        from can_tpu.data import ItemCache, StaleStoreError

        item_cache = (ItemCache(int(args.item_cache_mb * 1e6))
                      if args.item_cache_mb > 0 else None)
        from can_tpu.cli.common import split_prepared_spec

        try:
            ds = CrowdDataset(img_root, gt_root, gt_downsample=8,
                              phase="test", u8_output=args.u8_input,
                              prepared=split_prepared_spec(
                                  args.prepared_root, args.split),
                              item_cache=item_cache)
        except StaleStoreError as e:
            raise SystemExit(f"--prepared-root {args.prepared_root}: {e}")
        telemetry.emit("data.prepared", split=args.split,
                       **ds.prepared_note)
        if process_index() == 0:
            note = ds.prepared_note
            print(f"[data] prepared store: "
                  f"{'on' if note['active'] else 'legacy(' + str(note['reason']) + ')'}")
        # per-host slice of the lockstep schedule, like the train CLI —
        # without this a multi-host pod would feed every image
        # process_count times
        mesh, host_batch, dp = build_mesh_and_batch(args.batch_size, args.sp)
        # params device-resident + replicated ONCE: the imported-checkpoint
        # paths return host numpy trees, and feeding those to the jitted
        # eval step would re-upload all ~74 MB of weights EVERY batch
        # (review r5).  No-op cost for the already-resident Orbax path.
        from can_tpu.parallel import replicated_sharding

        params = jax.device_put(params, replicated_sharding(mesh))
        if batch_stats is not None:
            batch_stats = jax.device_put(batch_stats,
                                         replicated_sharding(mesh))
        pad_multiple, min_pad, min_bucket_h = resolve_sp_padding(
            args.pad_multiple, args.sp)
        if args.sp > 1 and pad_multiple != args.pad_multiple:
            # never silently trade away the exact-shape default: sp changes
            # the reported numbers' boundary math, so say so
            print(f"[data] sp={args.sp}: bucket H padded to multiples of "
                  f"{8 * args.sp} (exact shapes can't shard)")
        import math as _math

        batcher = ShardedBatcher(ds, host_batch, shuffle=False,
                                 pad_multiple=pad_multiple,
                                 min_pad_multiple=min_pad,
                                 min_bucket_h=min_bucket_h,
                                 process_index=process_index(),
                                 process_count=process_count(),
                                 num_workers=resolve_num_workers(args),
                                 max_buckets=args.max_buckets,
                                 remnant_sizes=not args.no_remnant_batches,
                                 batch_quantum=_math.lcm(dp, process_count()),
                                 launch_cost_px=resolve_launch_cost_px(
                                     args.launch_cost_mpx))
        if process_index() == 0:
            # main-process-only: the telemetry re-scans every image header,
            # and a pod would otherwise emit one duplicate line per process
            sched = batcher.schedule_overhead(0)
            pad = batcher.padding_overhead()
            print(f"[data] buckets={batcher.describe_buckets()} -> "
                  f"{batcher.distinct_shapes(0)} distinct batch shapes "
                  f"(padding overhead {pad:.1%}, "
                  f"schedule overhead {sched:.1%})")
            # fill-slot component alone (schedule_overhead also contains
            # per-item padding, which a smaller batch would NOT fix)
            fill = (1 + sched) / (1 + pad) - 1
            if fill > 0.5:
                if not args.no_remnant_batches:
                    # remnant covers already shrank every launch to the
                    # smallest legal size, so what remains is the batch
                    # quantum: each launch must split across the dp mesh
                    # axis and every host
                    print(f"[data] hint: batch fill slots add {fill:.0%} "
                          f"compute — the per-launch floor is "
                          f"{batcher.batch_quantum} images "
                          f"(lcm of dp={dp} and {process_count()} "
                          f"host(s)); a tiny eval set can't fill it "
                          f"(evaluate on fewer devices to lower the "
                          f"floor)")
                else:
                    print(f"[data] hint: batch fill slots add {fill:.0%} "
                          "compute (small eval set spread over many "
                          "shapes at this batch size) — drop "
                          "--no-remnant-batches or use a smaller "
                          "--batch-size")
        if args.sp > 1:
            eval_step = make_cached_sp_eval_step(mesh,
                                                 compute_dtype=compute_dtype)
        else:
            eval_step = make_dp_eval_step(cannet_apply, mesh,
                                          compute_dtype=compute_dtype)
        try:
            from can_tpu.sched import prefetch_depth_for

            metrics = evaluate(eval_step, params, batcher.epoch(0),
                               put_fn=lambda b: make_global_batch(
                                   b, mesh, spatial=args.sp > 1),
                               dataset_size=batcher.dataset_size,
                               show_progress=True, batch_stats=batch_stats,
                               telemetry=loop_tel,
                               prefetch=prefetch_depth_for(batcher))
        finally:
            batcher.close()
        telemetry.emit("epoch", step=0, phase="eval", mae=metrics["mae"],
                       mse=metrics["mse"], num_images=metrics["num_images"])
        if item_cache is not None:
            telemetry.emit("data.cache", step=0, **item_cache.stats())
        print(f"[result] images={metrics['num_images']} "
              f"MAE={metrics['mae']:.3f} MSE={metrics['mse']:.3f}")

        if args.show_index is not None and jax.process_index() == 0:
            # rank-0 only: every rank running this branch would (a) build
            # the sp viz mesh from GLOBAL devices non-addressable off
            # host 0 and crash, and (b) race identical PNG writes over
            # shared storage (code-review r5)
            from can_tpu.data import normalize_host

            img, gt = ds[args.show_index]
            img = normalize_host(img)  # no-op for the f32 path
            if args.sp > 1:
                # H-sharded forward — the image may not fit one chip (the
                # reason --sp was requested); pad H to the sp constraints
                # and crop the density map back.  BN checkpoints ride along:
                # eval-mode BN consumes replicated running stats.
                from can_tpu.parallel import make_mesh
                from can_tpu.parallel.spatial import make_spatial_apply

                h0, w0 = img.shape[:2]
                need = 8 * args.sp
                ph = max(-(-h0 // need) * need, 16 * args.sp)
                pimg = np.zeros((ph, w0, 3), np.float32)
                pimg[:h0] = img
                # one image: a dp=1 x sp viz mesh (the eval mesh shards the
                # batch dim over dp, which a single image can't fill)
                # LOCAL devices: rank 0 cannot address other hosts' chips
                viz_mesh = make_mesh(jax.local_devices()[:args.sp], dp=1,
                                     sp=args.sp)
                fwd = make_spatial_apply(viz_mesh, (ph, w0),
                                         compute_dtype=compute_dtype)
                # params live on the eval mesh; rehome them for the viz mesh
                host_params = jax.device_get(params)
                host_stats = (jax.device_get(batch_stats)
                              if batch_stats is not None else None)
                et = np.asarray(fwd(host_params, jnp.asarray(pimg)[None],
                                    host_stats))[0]
                et = et[: h0 // 8]
            else:
                from can_tpu.cli.common import make_inference_forward

                # host copies: the eval loop may have committed params to
                # the global mesh; a rank-local jit must not consume them
                host_params = jax.device_get(params)
                host_stats = (jax.device_get(batch_stats)
                              if batch_stats is not None else None)
                et = np.asarray(make_inference_forward()(
                    host_params, jnp.asarray(img)[None], host_stats))[0]
            paths = save_density_visualization(
                img, gt, et, args.out_dir,
                tag=f"{args.split}_{args.show_index}")
            print(f"[viz] wrote {paths}")
        return 0
    finally:
        from can_tpu.obs import shutdown_telemetry

        # deterministic order shared with the SIGTERM path (lifecycle.py)
        shutdown_telemetry(telemetry, heartbeat=heartbeat,
                           exporter=exporter)
        shutdown_runtime()  # the reference leaks its process group (SURVEY §3.1)


if __name__ == "__main__":
    raise SystemExit(main())
