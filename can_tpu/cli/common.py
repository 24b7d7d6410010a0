"""Shared CLI plumbing: dataset roots, mesh/batch arithmetic, step caches."""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Dict, Optional, Tuple

import jax

from can_tpu.parallel import make_mesh


def parse_pad_multiple(value):
    """CLI --pad-multiple value -> ShardedBatcher pad_multiple.

    "auto" (the default): pick from the dataset's shape histogram so the
    step compiles at most ``max_buckets`` programs; "exact"/"none"/"0":
    exact snapped shapes (zero padding, bit-exact reference loss math, but
    one compile per distinct resolution); otherwise an integer multiple.
    """
    if value is None:
        return None
    s = str(value).strip().lower()
    if s == "auto":
        return "auto"
    if s in ("exact", "none", "0"):
        return None
    return int(s)


def resolve_sp_padding(pad_multiple, sp: int):
    """Bucket constraints under spatial parallelism, shared by both CLIs.

    Returns (pad_multiple, min_pad_multiple, min_bucket_h).  Only the
    sharded H axis carries sp constraints (spatial.py shards P(data,
    spatial, None, None)); W keeps the cheaper /8 snap:
    * bucket H must be a multiple of 8*sp so max-pool windows never
      straddle shard boundaries (spatial.py _check_spatial_shapes);
    * bucket H must be >= 16*sp so each shard owns >= 2 feature rows (the
      dilated-conv halo) — short images are padded up instead of crashing
      the step factory mid-eval.
    """
    if sp <= 1:
        return pad_multiple, None, None
    need = 8 * sp
    if pad_multiple is None:  # exact shapes can't guarantee divisibility
        pad_multiple = (need, 8)
    elif isinstance(pad_multiple, int):
        mh = pad_multiple if pad_multiple % need == 0 else (
            -(-pad_multiple // need) * need)
        pad_multiple = (mh, pad_multiple)
    return pad_multiple, (need, None), 16 * sp


def dataset_roots(data_root: str, split: str) -> Tuple[str, str]:
    """ShanghaiTech-style layout (the reference comments these path pairs,
    train.py:49-52): <root>/<split>_data/images + .../ground_truth."""
    base = os.path.join(data_root, f"{split}_data")
    img, gt = os.path.join(base, "images"), os.path.join(base, "ground_truth")
    for p in (img, gt):
        if not os.path.isdir(p):
            raise FileNotFoundError(
                f"expected dataset directory {p} (ShanghaiTech layout: "
                f"<data_root>/{split}_data/{{images,ground_truth}})")
    return img, gt


def resolve_split_roots(split: str, image_root: str, gt_root: str,
                        data_root: str, *,
                        flag_stem: Optional[str] = None) -> Tuple[str, str]:
    """Explicit per-split roots (VisDrone-style layouts, where images and
    density maps live in unrelated trees — the reference hardcodes such a
    pair, train.py:54-57) win over the ShanghaiTech ``data_root``
    convention.  Either give BOTH roots for the split, or a data_root.

    flag_stem: prefix of the caller's flags ("train-"/"test-" in the train
    CLI, "" in the eval CLI) so error messages name flags that exist.
    Pure argument/isdir checks — call straight after parse_args, before any
    runtime/checkpoint work.
    """
    stem = f"{split}-" if flag_stem is None else flag_stem
    if image_root or gt_root:
        if not (image_root and gt_root):
            raise SystemExit(
                f"give both --{stem}image-root and --{stem}gt-root "
                f"(or neither, with --data_root)")
        for p in (image_root, gt_root):
            if not os.path.isdir(p):
                raise SystemExit(f"no such dataset directory: {p}")
        return image_root, gt_root
    if not data_root:
        raise SystemExit(
            f"need --data_root or --{stem}image-root/--{stem}gt-root")
    return dataset_roots(data_root, split)


def split_prepared_spec(spec: str, split: str) -> str:
    """``--prepared-root`` value -> ``CrowdDataset(prepared=...)`` for one
    split.  'auto'/'off' pass through; a path is a root holding per-split
    stores (``<path>/train``, ``<path>/test`` — what
    ``tools/prepare_data.py --prepared-out`` writes for multi-split runs).
    """
    if spec in ("auto", "off"):
        return spec
    return os.path.join(spec, split)


def build_mesh_and_batch(batch_size: int, sp: int) -> Tuple:
    """Mesh over all devices with ``sp`` spatial shards; returns
    (mesh, per_host_batch, dp).

    ``batch_size`` is PER DATA-PARALLEL REPLICA (the reference's per-GPU
    batch, train.py:177); global batch = batch_size * dp.
    """
    ndev = jax.device_count()
    if ndev % sp:
        raise ValueError(f"--sp {sp} does not divide device count {ndev}")
    dp = ndev // sp
    mesh = make_mesh(dp=dp, sp=sp)
    if jax.process_count() > 1 and sp > 1:
        # The spatial axis must stay WITHIN one host: make_global_batch
        # feeds each host's full-height slabs, so an sp group spanning
        # processes would make make_array_from_process_local_data stitch
        # different hosts' images vertically into one double-height
        # "image" and halo-exchange across the seam — silently wrong
        # gradients (code-review r5).  Verify on the built mesh (exact
        # regardless of create_device_mesh's ordering).
        for row in mesh.devices:
            if len({d.process_index for d in row}) > 1:
                raise ValueError(
                    f"--sp {sp} spans multiple hosts "
                    f"({jax.local_device_count()} local devices/host); "
                    "spatial sharding must stay within one host — lower "
                    "--sp or use more data-parallel replicas")
    global_batch = batch_size * dp
    nproc = jax.process_count()
    if global_batch % nproc:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {nproc}")
    return mesh, global_batch // nproc, dp


def activation_bytes(batch: int, h: int, w: int, *,
                     bf16: bool = False) -> int:
    """Peak train-step HBM footprint estimate for one CANNet launch.

    Linear in pixels; the constant is MEASURED, not analytic: the r4 OOM
    dump for b16 x 1016x1024 bf16 (16.65 Mpx) showed a 16.97 GiB program —
    ~1030 B/px — dominated by the full-res backward temporaries
    (bf16[B,H,W,64] conv-transpose + select_and_scatter buffers, each with
    2x lane-padding on the 64-channel dim).  jax.checkpoint barely moves
    this peak (the temporaries live INSIDE the rematerialised backward
    segment), which is why the planner's per-launch pixel cap
    (max_launch_pixels), not remat, is the primary fits-in-HBM mechanism.
    Consistent with every observed fit: b16 576x768 (7.5 GiB est) and
    b8 1016x1024 (8.8 GiB est) train fine; b16 1016x1024 (17.6 GiB est)
    OOMs with or without remat.  f32 doubles the bf16 footprint.
    """
    per_px = 1030.0 if bf16 else 2060.0
    return int(batch * h * w * per_px)


# HBM per JAX device by hardware generation — spec constants, not guesses
# (substring-matched against ``device_kind``).  The fallback for a PJRT
# client whose ``memory_stats()`` carries no ``bytes_limit``: without a
# number BOTH fits-in-HBM mechanisms switch off (max_launch_pixels ->
# None, remat policy -> never), and a b16 x 1016x1024 varres launch once
# compiled at 16.97 GiB and OOM'd a 15.75 GiB chip that way.  On a TPU a
# device kind this table does not know is an error
# (``UnknownDeviceKindError``), never "no cap".
# NOTE these are the SPEC totals, which are strictly larger than what a
# program can allocate: PJRT reserves a slice for itself before reporting
# ``bytes_limit`` (that OOM dump showed 15.75 GiB usable of the 16 GiB
# spec, ~0.984), so ``hbm_bytes_for_device_kind`` derates by
# ``_PJRT_SPEC_DERATE`` rather than handing the planner bytes the runtime
# will never grant.  The train CLI prints both numbers (``[hbm]``).
# ORDERED: lite/cost-optimised variants before their generation's bare
# entry, so "v5lite..." never hits the bare "v5" (v5p) row and "v4i"
# never gets a full v4's 32 GiB.
_PJRT_SPEC_DERATE = 0.97  # spec -> typical usable bytes_limit fraction
_HBM_BY_DEVICE_KIND = (
    ("v5lite", 16 << 30),    # v5e ("TPU v5 lite", "TPU v5litepod-N")
    ("v5e", 16 << 30),
    ("v5p", 95 << 30),
    ("v5", 95 << 30),        # bare "TPU v5" = v5p (v5e always says lite/e)
    ("v6lite", 32 << 30),    # Trillium
    ("v6e", 32 << 30),
    ("v4i", 8 << 30),
    ("v4lite", 8 << 30),
    ("v4", 32 << 30),
    ("v3", 16 << 30),        # per core (= per JAX device)
    ("v2", 8 << 30),
)


# Peak compute / HBM bandwidth per JAX device by hardware generation —
# spec constants for the perf-attribution layer (obs/costs.py), matched
# exactly like _HBM_BY_DEVICE_KIND above (substring, first entry wins,
# lite variants before their generation's bare row).  Units: FLOP/s at the
# bf16 MXU rate, and HBM bytes/s.  v2/v3 rows are PER CORE (= per JAX
# device); v4+ are per chip.  The f32 peak is modelled as bf16/2 — the
# MXU takes bf16 inputs with f32 accumulation, and f32-input matmuls run
# at roughly half rate; an approximation, but MFU consumers only need a
# stable denominator, not a guarantee (the roofline CLASS depends only on
# the ridge ratio, which the /2 preserves).
_PEAK_BY_DEVICE_KIND = (
    ("v5lite", (197e12, 819e9)),   # v5e
    ("v5e", (197e12, 819e9)),
    ("v5p", (459e12, 2765e9)),
    ("v5", (459e12, 2765e9)),      # bare "TPU v5" = v5p (see HBM table)
    ("v6lite", (918e12, 1640e9)),  # Trillium
    ("v6e", (918e12, 1640e9)),
    ("v4i", (138e12, 614e9)),
    ("v4lite", (138e12, 614e9)),
    ("v4", (275e12, 1228e9)),
    ("v3", (61.5e12, 450e9)),      # per core (123 TFLOP/s / 900 GB/s chip)
    ("v2", (22.5e12, 300e9)),
)

# CPU pseudo-peaks: NOMINAL placeholders (≈ a laptop core's order of
# magnitude), flagged nominal=True so every consumer can say "relative
# only".  They exist so the MFU/roofline plumbing is exercisable (and
# tier-1 testable) on the CPU backend — unlike the HBM table, nothing
# here feeds scheduling, so a labelled fiction is acceptable where an
# unlabelled one would not be.
_CPU_NOMINAL_PEAKS = (5e10, 1e10)


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Peak rates for one device kind (the roofline's two ceilings)."""

    flops_bf16: float    # FLOP/s at the bf16 MXU rate
    flops_f32: float     # approximated as bf16/2 (see table note)
    hbm_bytes_s: float   # HBM bandwidth, bytes/s
    source: str          # "spec:<kind>" or "nominal:cpu"
    nominal: bool = False

    def flops(self, compute: str = "f32") -> float:
        return self.flops_bf16 if compute == "bf16" else self.flops_f32

    def ridge(self, compute: str = "f32") -> float:
        """Arithmetic intensity (FLOP/byte) where the roofline bends."""
        return self.flops(compute) / self.hbm_bytes_s


def _match_device_table(kind: str, table):
    """Substring-match a ``device_kind`` against an ordered spec table:
    case-insensitive, spaces stripped, first entry wins (lite variants
    are listed before their generation's bare row).  Single-sourced so
    ``_HBM_BY_DEVICE_KIND`` and ``_PEAK_BY_DEVICE_KIND`` can never
    diverge in matching rules — returns ``(matched_key, value)`` or
    ``(None, None)``."""
    k = kind.lower().replace(" ", "")
    for sub, val in table:
        if sub in k:
            return sub, val
    return None, None


class UnknownDeviceKindError(ValueError):
    """A TPU whose ``device_kind`` the spec tables do not know.  An error,
    not a default: "no HBM cap" or "no peaks" on a chip nobody has a row
    for would let every downstream number be computed against nothing."""

    def __init__(self, kind: str, table: str):
        super().__init__(
            f"TPU device_kind {kind!r} is not in {table} "
            f"(can_tpu/cli/common.py) — add its row before running on it")


def device_peaks_for_kind(kind: str) -> Optional[DevicePeaks]:
    """Spec peaks for a TPU ``device_kind`` string, or None when the
    generation isn't recognised (same matching rules as
    ``hbm_bytes_for_device_kind``)."""
    sub, val = _match_device_table(kind, _PEAK_BY_DEVICE_KIND)
    if sub is None:
        return None
    flops, bw = val
    return DevicePeaks(flops_bf16=float(flops),
                       flops_f32=float(flops) / 2.0,
                       hbm_bytes_s=float(bw), source=f"spec:{sub}")


def local_device_peaks() -> Optional[DevicePeaks]:
    """Peaks for THIS host's first local device: the spec table on TPU
    (an unknown kind raises ``UnknownDeviceKindError``), the
    labelled-nominal CPU entry on the CPU backend (so MFU gauges stay
    exercisable in tests), None anywhere else."""
    dev = jax.local_devices()[0]
    if dev.platform == "tpu":
        peaks = device_peaks_for_kind(dev.device_kind)
        if peaks is None:
            raise UnknownDeviceKindError(dev.device_kind,
                                         "_PEAK_BY_DEVICE_KIND")
        return peaks
    if dev.platform == "cpu":
        f, bw = _CPU_NOMINAL_PEAKS
        return DevicePeaks(flops_bf16=f, flops_f32=f, hbm_bytes_s=bw,
                           source="nominal:cpu", nominal=True)
    return None


def hbm_bytes_for_device_kind(kind: str) -> Optional[int]:
    """USABLE HBM bytes for a TPU ``device_kind`` string (spec total
    derated by the typical PJRT reservation, ``_PJRT_SPEC_DERATE`` — a
    real client's ``bytes_limit`` always comes in under spec), or None if
    the generation isn't recognised.  Matched case-insensitively with
    spaces stripped, first entry wins ("TPU v5 lite" and "TPU
    v5litepod-8" both hit "v5lite"; bare "TPU v5" falls through to the
    v5p row)."""
    sub, size = _match_device_table(kind, _HBM_BY_DEVICE_KIND)
    if sub is None:
        return None
    return int(size * _PJRT_SPEC_DERATE)


def device_memory_sources() -> Tuple[Optional[int], Optional[int]]:
    """``(bytes_limit, spec_bytes)`` for the first LOCAL device: what the
    PJRT client's ``memory_stats()`` reports, and what the spec table
    says for its kind.  CPU has neither (``(None, None)``); on a TPU the
    spec row must exist — ``UnknownDeviceKindError`` otherwise.

    ``jax.local_devices()``, not ``jax.devices()``: on a multi-host pod
    devices()[0] is non-addressable for every rank but 0, so its
    memory_stats() fails there and ranks would silently diverge on
    whether an HBM cap exists (ADVICE r4, high)."""
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    limit = int(stats["bytes_limit"]) if stats and stats.get("bytes_limit") \
        else None
    if dev.platform != "tpu":
        return limit, None
    spec = hbm_bytes_for_device_kind(dev.device_kind)
    if spec is None:
        raise UnknownDeviceKindError(dev.device_kind, "_HBM_BY_DEVICE_KIND")
    return limit, spec


def device_memory_bytes() -> Optional[int]:
    """Per-LOCAL-device HBM: ``memory_stats()['bytes_limit']`` when the
    PJRT client reports it, else the spec size for the device kind
    (``device_memory_sources``).  None means 'no device memory ceiling'
    (CPU): there, inventing a number would let a fictitious 16 GiB drive
    real scheduling (launch caps, remat, LR-schedule step counts) on
    backends whose only limit is host RAM.  Multi-host callers must
    still AGREE the value — use agreed_device_memory_bytes()."""
    limit, spec = device_memory_sources()
    return limit if limit is not None else spec


def agreed_device_memory_bytes() -> Optional[int]:
    """device_memory_bytes() agreed across processes (min), for anything
    that feeds the LOCKSTEP schedule: every host must derive the same
    max_launch_px / remat decisions or make_array_from_process_local_data
    deadlocks on mismatched batch plans.  Min is the conservative
    agreement; a host with no ceiling (None) forces None everywhere
    (heterogeneous backends shouldn't invent a cap for the others).
    Collective — call AFTER init_runtime, identically on every host."""
    from can_tpu.parallel import agree_min_value, process_count

    mem = device_memory_bytes()
    if process_count() < 2:
        return mem
    import numpy as _np

    agreed = float(agree_min_value(_np.float64(-1.0 if mem is None else mem)))
    return None if agreed < 0 else int(agreed)


_DETECT = object()  # sentinel: "autodetect HBM" vs an explicit None cap


def max_launch_pixels(*, bf16: bool, ceiling_frac: float = 0.92,
                      hbm_bytes=_DETECT, shards: int = 1) -> Optional[float]:
    """Per-launch pixel budget (batch * H * W, GLOBAL units — the planner
    prices launches in global pixels) for the remnant planner's HBM cap
    (ShardedBatcher max_launch_px), or None on backends with no
    device-memory ceiling (CPU) — there the cap would be fiction and
    would shift batch counts (hence LR schedules) vs the TPU run.

    ``shards``: devices each launch is split across (mesh dp*sp).  The
    train step shards the batch over dp and H over sp, so per-DEVICE
    pixels = global pixels / shards; the B/px constant below is
    per-device (calibrated at dp=sp=1), so the global cap scales by
    ``shards`` — without this, a dp=4 pod would cap launches 4x smaller
    than what fits (ADVICE r4, medium).

    Calibrated to the measured worst case, not the analytic optimum: even
    WITH remat, the b16 x 1016x1024 backward peaked at ~17.2 GiB for
    16.65 Mpx (~1030 B/px: the full-res conv-transpose temporaries plus
    XLA's 2x lane-padding on the 64-channel stem dominate, r4 OOM dump).
    ~1100 B/px (bf16; f32 doubles it) against ``ceiling_frac`` of HBM
    admits every configuration that has been seen to fit (b16 768x1024,
    b8 1016x1024) and rejects the one that OOM'd.  ``hbm_bytes``
    overrides autodetection (tests pin it; multi-host CLIs pass the
    agreed_device_memory_bytes() value so every host caps identically).
    """
    mem = device_memory_bytes() if hbm_bytes is _DETECT else hbm_bytes
    if mem is None:
        return None
    per_px = 1100.0 if bf16 else 2200.0
    return ceiling_frac * mem / per_px * shards


def make_remat_policy(remat_flag: str, *, global_batch: int,
                      bf16: bool, budget_frac: float = 0.80,
                      announce: bool = False,
                      hbm_bytes=_DETECT, shards: int = 1):
    """Per-bucket rematerialisation decision (VERDICT r3 item 3).

    ``--remat on`` / ``off`` force the choice globally; ``auto`` (default)
    enables jax.checkpoint only for bucket shapes whose estimated peak
    footprint exceeds ``budget_frac`` of device HBM — the narrow band
    just under the per-launch pixel cap, where shaving the cross-segment
    activations buys headroom.  Small buckets keep the full-speed
    backward; shapes beyond the cap never launch at that batch at all
    (the planner's max_launch_px runs them at a smaller menu size — the
    reference's only fits-anything answer was batch-1, train.py:177).

    Returns ``policy(image_hw, batch=None) -> bool`` (batch defaults to the
    full global batch; remnant sub-batches pass their smaller actual size,
    so a big-shape straggler can still skip remat).

    ``shards`` (mesh dp*sp): the footprint estimate is for the GLOBAL
    launch but HBM is per-device and the step shards batch over dp / H
    over sp, so the estimate is divided by ``shards`` before comparing —
    otherwise dp>1 meshes over-trigger remat (ADVICE r4, medium).
    Multi-host callers pass hbm_bytes=agreed_device_memory_bytes().
    """
    if remat_flag in ("on", "off"):
        return lambda hw, batch=None: remat_flag == "on"
    mem = device_memory_bytes() if hbm_bytes is _DETECT else hbm_bytes
    if mem is None:
        # no device-memory ceiling reported (CPU backend): auto-remat
        # would be keyed to a made-up number — keep the fast backward
        return lambda hw, batch=None: False
    budget = int(mem * budget_frac)

    def policy(hw, batch=None):
        b = batch or global_batch
        need = activation_bytes(b, hw[0], hw[1], bf16=bf16) // shards > budget
        if need and announce and (b, hw) not in policy._said:
            policy._said.add((b, hw))
            print(f"[remat] bucket {hw[0]}x{hw[1]} (batch {b}): activation "
                  f"estimate exceeds {budget_frac:.0%} of HBM -> "
                  f"rematerialising backward for this bucket")
        return need

    policy._said = set()
    return policy


MODEL_MPX_PER_S = 42.0  # CANNet bf16 train-step device rate (v5e measured:
# 94.9 img/s x 0.442 Mpx at 576x768) — converts dispatch ms to the
# pixel-equivalents the remnant planner prices launches in

# Per-launch cost in the DEVICE regime: what one extra launch costs when
# dispatch is overlapped with compute (steps enqueued back-to-back, the
# loop's windowed fetch amortising the sync) — the regime a healthy
# prefetching train loop runs in.  The pixel-independent device work per
# launch is chiefly the optimizer update (~300 MB param/momentum traffic
# ≈ 0.4 ms ≈ 0.017 Mpx on v5e) plus executable switch + infeed
# bookkeeping; 0.05 Mpx (~1.2 ms) is that with ~3x slack.  This is NOT
# the dispatch-bound number: a host whose launches serialize on a slow
# dispatch path must price with --launch-cost-mpx auto / the CLI default
# instead.  No CLI prices from this constant: the planner's golden-plan
# tests (tests/test_planner.py) do.
# Both constants — this one and the CLIs' --launch-cost-mpx default of
# 2.0 — are NOT MEASURED on the current machine (ROADMAP Design 4).
DEVICE_LAUNCH_COST_MPX = 0.05


def measure_launch_cost_mpx(*, probes: int = 30,
                            device_rate_mpx_s: float = MODEL_MPX_PER_S) -> float:
    """Measure per-launch dispatch overhead and convert to Mpx-equivalents
    (the remnant planner's unit).  Times a tiny jitted op, BLOCKING on
    each call (device_get inside the loop): JAX enqueues dispatches ahead
    of execution, so an unblocked loop would hide the per-launch
    round-trip on exactly the high-latency hosts 'auto' exists to
    detect (ADVICE r4).  Each probe measures the full dispatch+completion
    path with near-zero compute; the median is the fixed launch cost.
    Note this is an UPPER bound on what the train loop pays per launch:
    the loop fetches metrics once per ``check_every`` window (8 steps),
    amortising the completion sync, while the dispatch-path cost is
    paid per launch regardless — so on the hosts where 'auto' matters
    the bound is tight, and elsewhere both numbers sit in the planner's
    flat region.

    Calibration status (r5, tools/launch_cost_probe.py): the probe
    measures DISPATCH only; a real train step also pays
    pixel-independent device work each launch — chiefly
    the optimizer update (~300 MB of param/momentum traffic ≈ 0.4 ms ≈
    0.015 Mpx-equivalents on v5e) plus argument marshaling.  That
    omission cannot change a plan: the remnant planner's decisions are
    flat across [0, 0.05] Mpx and across [1, 4] Mpx on a Part-A-like
    shape histogram; the sensitive band (0.1-1 Mpx ≈ 2.5-25 ms) is exactly
    where dispatch dominates and the probe measures the dominant term
    directly.  So: no correction applied, by measurement rather than
    hope.  Costs one trivial compile at startup.
    """
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    float(jax.device_get(f(x)))  # compile + settle
    times = []
    for _ in range(probes):
        t0 = time.perf_counter()
        float(jax.device_get(f(x)))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * device_rate_mpx_s


def parse_launch_cost(value):
    """argparse type for --launch-cost-mpx: 'auto' or a float — validated
    AT PARSE TIME (a typo'd value must not cost a multi-host rendezvous,
    same contract as the path checks)."""
    s = str(value).strip().lower()
    if s == "auto":
        return "auto"
    try:
        return float(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or a number, got {value!r}")


def resolve_launch_cost_px(spec, *, announce: bool = False) -> float:
    """CLI --launch-cost-mpx value (parse_launch_cost output) -> planner
    pixel units.  'auto' measures the host's dispatch overhead
    (measure_launch_cost_mpx) and, on multi-host runs, averages it across
    processes with ``reduce_value`` so every host prices launches
    identically — the remnant planner's lockstep schedule depends on all
    hosts computing the SAME plan.  A number is used as given (the CLI
    default 2.0 is a constant, not measured on the current machine).
    Call AFTER init_runtime."""
    if spec == "auto":
        import numpy as _np

        from can_tpu.parallel import process_count, reduce_value

        mpx = measure_launch_cost_mpx()
        if process_count() > 1:
            mpx = float(reduce_value(_np.float32(mpx), average=True))
        if announce:
            print(f"[planner] measured launch overhead ~"
                  f"{mpx / MODEL_MPX_PER_S * 1e3:.1f} ms/launch -> "
                  f"launch cost {mpx:.2f} Mpx"
                  + (" (mean across hosts)" if process_count() > 1 else ""))
        return mpx * 1e6
    return float(spec) * 1e6


def make_bucketed_train_step(apply_fn, optimizer, mesh, *, compute_dtype,
                             policy, health_metrics: bool = False):
    """Data-parallel train step with per-bucket remat dispatch: two jitted
    step objects (remat on/off); jit caches per batch shape under each, so
    every bucket runs the cheapest variant the ``policy`` (make_remat_policy)
    allows.  Shared by the train CLI and the benchmark's train driver, so
    the benchmark measures exactly the CLI's dispatch.  health_metrics:
    in-program grad/update norms for the run-health layer (default off —
    identical programs)."""
    from can_tpu.parallel import make_dp_train_step

    steps = {flag: make_dp_train_step(apply_fn, optimizer, mesh,
                                      compute_dtype=compute_dtype,
                                      remat=flag,
                                      health_metrics=health_metrics)
             for flag in (False, True)}

    def train_step(state, batch):
        shape = batch["image"].shape
        return steps[policy(tuple(shape[1:3]), batch=shape[0])](state, batch)

    # cost-ledger seam (obs/costs.py): the jitted step this batch would
    # dispatch to, so a ProgramCostLedger can AOT-read cost_analysis()
    # through the remat dispatch closure
    train_step.jit_for = lambda state, batch: steps[
        policy(tuple(batch["image"].shape[1:3]),
               batch=batch["image"].shape[0])]
    return train_step


def bn_kernel_routing(params, image_hw: Tuple[int, int], *,
                      interpret: bool, sp: int = 1) -> Tuple[int, int]:
    """``(kernel_layers, twin_layers)``: how many BN layers of one
    train-mode forward at bucket ``image_hw`` take the Pallas moments
    kernel, and how many its jnp one-pass twin (the kernel's shape gate,
    ``ops/pallas_bn.supports``).  Asked of the model itself — an abstract
    trace (``jax.eval_shape``: no compile, no device work) of the
    per-device block, H/sp rows under spatial sharding."""
    import functools

    import jax.numpy as jnp

    from can_tpu.models import cannet_apply, init_batch_stats
    from can_tpu.models.cannet import LocalOps
    from can_tpu.ops.bn_moments import BNOps, masked_moments_pallas

    routed: list = []
    probe = BNOps(impl="pallas", interpret=interpret,
                  masked_moments=functools.partial(
                      masked_moments_pallas, interpret=interpret,
                      routed=routed))
    h, w = image_hw[0] // sp, image_hw[1]
    jax.eval_shape(
        lambda p, x, stats, mask: cannet_apply(
            p, x, ops=LocalOps(bn_ops=probe), batch_stats=stats,
            train=True, pixel_mask=mask),
        params, jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32),
        init_batch_stats(params),
        jax.ShapeDtypeStruct((1, h // 8, w // 8, 1), jnp.float32))
    kernel = sum(took for took, _ in routed)
    return kernel, len(routed) - kernel


def make_inference_forward():
    """Jitted single-image forward that handles both model variants:
    ``fwd(params, image, batch_stats_or_None)`` (shared by the train CLI's
    --show visualization and the test CLI's --show-index)."""
    import jax as _jax

    from can_tpu.models import cannet_apply

    def _fwd(params, x, batch_stats):
        if batch_stats is not None:
            return cannet_apply(params, x, batch_stats=batch_stats,
                                train=False)
        return cannet_apply(params, x)

    return _jax.jit(_fwd)


class SpatialStepCache:
    """Per-image-shape cache of spatial train steps (each H x W bucket shape
    compiles its own shard_map program, mirroring jit's per-shape cache)."""

    def __init__(self, factory):
        self._factory = factory
        self._steps: Dict[Tuple[int, int], object] = {}

    def __call__(self, image_hw: Tuple[int, int]):
        step = self._steps.get(image_hw)
        if step is None:
            step = self._steps[image_hw] = self._factory(image_hw)
        return step


def make_cached_sp_eval_step(mesh, *, compute_dtype=None):
    """Bucket-shape-cached spatial eval step (shared by both CLIs)."""
    from can_tpu.parallel.spatial import make_sp_eval_step

    cache = SpatialStepCache(
        lambda hw: make_sp_eval_step(mesh, hw, compute_dtype=compute_dtype))

    def eval_step(params, batch, batch_stats=None):
        hw = (batch["image"].shape[1], batch["image"].shape[2])
        return cache(hw)(params, batch, batch_stats)

    # cost-ledger seam, as in make_bucketed_train_step
    eval_step.jit_for = lambda params, batch, batch_stats=None: cache(
        (batch["image"].shape[1], batch["image"].shape[2]))
    return eval_step
