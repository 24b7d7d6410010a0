"""Explain the batch schedule the planner would run for a dataset.

Operator observability for the r4 scheduling machinery: prints the bucket
policy, every (shape x batch-size) program, each epoch launch with its
fill, and the overhead accounting — without touching any device.  Use it
to answer "why is my epoch N steps?" or "what will --max-buckets /
--launch-cost-mpx change?" before spending a compile bill.

    python tools/explain_schedule.py --image-root .../images \\
        --gt-root .../ground_truth --batch-size 8 [--pad-multiple auto]
        [--max-buckets 24] [--launch-cost-mpx 2.0|auto is device-bound:
        pass a number here] [--bf16] [--dp N --hosts M]

Everything is computed from image headers only (the batcher's
shape-schedule API), so it runs in seconds on any machine.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from can_tpu.cli.common import parse_pad_multiple
    from can_tpu.data import CrowdDataset, ShardedBatcher

    ap = argparse.ArgumentParser()
    ap.add_argument("--image-root", required=True)
    ap.add_argument("--gt-root", default="",
                    help="density-map root (defaults to image root's "
                         "sibling ground_truth; only headers are read, so "
                         "a missing gt tree is fine for explaining)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="images per data-parallel replica")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel size the run will use")
    ap.add_argument("--hosts", type=int, default=1)
    ap.add_argument("--pad-multiple", type=parse_pad_multiple, default="auto")
    ap.add_argument("--max-buckets", type=int, default=24)
    ap.add_argument("--launch-cost-mpx", type=float, default=2.0)
    ap.add_argument("--no-remnant-batches", action="store_true")
    ap.add_argument("--bf16", action="store_true",
                    help="size the HBM pixel cap for bf16 compute (f32 "
                         "halves the cap)")
    ap.add_argument("--eval", action="store_true",
                    help="explain the EVAL CLI's schedule instead of the "
                         "train one: unshuffled, and no HBM launch cap "
                         "(eval has no backward)")
    ap.add_argument("--hbm-gib", type=float, default=16.0,
                    help="device HBM the pixel cap is sized for. The real "
                         "train CLI autodetects this from the attached "
                         "device; this tool never touches a device, so "
                         "pass your chip's HBM to match (default: the "
                         "16 GiB v5e the cap was calibrated on)")
    ap.add_argument("--epoch", type=int, default=0)
    ap.add_argument("--sweep-launch-cost", action="store_true",
                    help="instead of one explanation, sweep launch-cost "
                         "pricing over 0..4 Mpx and print where the PLAN "
                         "actually changes — the sensitivity table behind "
                         "'--launch-cost-mpx auto needs no correction' "
                         "(CHANGES.md r5): plans are typically flat below "
                         "0.05 Mpx (sub-ms hosts) and above ~1 Mpx "
                         "(slow-dispatch hosts), so only 2.5-25 ms dispatch costs are "
                         "decision-sensitive")
    args = ap.parse_args()

    import math

    if (args.batch_size * args.dp) % args.hosts:
        ap.error(f"--hosts {args.hosts} must divide the global batch "
                 f"({args.batch_size} x dp {args.dp} = "
                 f"{args.batch_size * args.dp})")
    gt_root = args.gt_root or os.path.join(
        os.path.dirname(args.image_root.rstrip("/")), "ground_truth")
    # scheduling only touches image headers, so a missing/partial gt tree
    # doesn't matter here
    ds = CrowdDataset(args.image_root, gt_root, gt_downsample=8,
                      phase="train")
    quantum = math.lcm(args.dp, args.hosts)
    cap = None
    if not args.no_remnant_batches and not args.eval:
        from can_tpu.cli.common import max_launch_pixels

        cap = max_launch_pixels(bf16=args.bf16,
                                hbm_bytes=int(args.hbm_gib * 1024 ** 3),
                                shards=args.dp)
    common = dict(shuffle=not args.eval, seed=0,
                  process_count=args.hosts,
                  pad_multiple=args.pad_multiple,
                  max_buckets=args.max_buckets,
                  remnant_sizes=not args.no_remnant_batches,
                  batch_quantum=quantum, max_launch_px=cap)
    host_bs = args.batch_size * args.dp // args.hosts

    gbs = args.batch_size * args.dp
    print(f"dataset: {len(ds)} images, global batch {gbs} "
          f"(dp={args.dp} x per-replica {args.batch_size}), "
          f"launch quantum {quantum}")
    if args.sweep_launch_cost:
        prev = None
        for mpx in (0.0, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 4.0):
            bb = ShardedBatcher(ds, host_bs, launch_cost_px=mpx * 1e6,
                                **common)
            key = (bb.batches_per_epoch(args.epoch),
                   round(bb.schedule_overhead(args.epoch), 4),
                   bb.program_count(args.epoch))
            mark = ("   (baseline)" if prev is None
                    else "" if key == prev else "   <-- plan changed")
            print(f"  launch_cost {mpx:5.2f} Mpx: launches={key[0]:>4} "
                  f"overhead={key[1]:7.2%} programs={key[2]:>3}{mark}")
            prev = key
        return 0
    b = ShardedBatcher(ds, host_bs,
                       launch_cost_px=args.launch_cost_mpx * 1e6, **common)
    print(f"buckets: {b.describe_buckets()}")
    sched = b.global_schedule(args.epoch)
    programs = collections.Counter((k, len(g)) for k, g in sched)
    print(f"programs: {len(programs)} distinct (shape x batch) — the XLA "
          f"compile bill (persistent cache pays it once)")
    for (k, size), n in sorted(programs.items()):
        px = k[0] * k[1] * size / 1e6
        print(f"  {k[0]:>5}x{k[1]:<5} batch {size:>3}  x{n:>3} launches "
              f"({px:6.1f} Mpx each)")
    valid = sum(1 for _, g in sched for _, v in g if v)
    slots = sum(len(g) for _, g in sched)
    print(f"epoch: {len(sched)} launches, {slots} slots / {valid} images "
          f"({slots - valid} fill)")
    print(f"padding overhead {b.padding_overhead():.1%}, schedule "
          f"overhead {b.schedule_overhead(args.epoch):.1%} (pixels beyond "
          f"the images' own)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
