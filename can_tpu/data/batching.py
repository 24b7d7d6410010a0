"""Static-shape batching for variable-resolution images + host sharding.

The reference handles variable resolution with batch_size=1 and fully dynamic
shapes (reference: train.py:84-91,177) — a non-starter under XLA, where every
distinct shape is a recompile.  TPU-first design:

* **Shape bucketing.** Items are grouped by their post-snap (H, W) — either
  exactly (``pad_multiple=None``: zero padding, bit-exact reference math),
  rounded up to a multiple (bounded compile count for wild datasets), or
  ``pad_multiple="auto"``: the batcher reads the dataset's shape histogram
  (header-only) and picks the smallest multiple that keeps the number of
  distinct bucket shapes — i.e. XLA compilations — at or under
  ``max_buckets``.  Each bucket shape compiles once; afterwards every batch
  of that shape reuses the executable.  (The reference recompiles nothing
  because torch is eager — but it also gets none of XLA's fusion; bounded
  bucketing is the TPU-native trade.)
* **Masking.** A per-image validity flag plus a per-cell mask over the 1/8
  density grid make padded pixels and fill items contribute exactly zero to
  loss/metrics, so MSE-sum and MAE match the reference's per-image math.
* **Cost-model batch planning.** In ladder+remnant mode the epoch's
  launch plan — per-cell full-batch sizes (lowered under the HBM cap),
  straggler covers at exact quantum-multiple sizes, group merges, and the
  bucket boundaries themselves — is searched by one explicit objective,
  ``area * padded_slots + launch_cost_px * n_launches``, in
  ``data/planner.py`` (r8; ``plan_mode="legacy"`` keeps the pre-r8
  heuristics for A/B).
* **Lockstep host sharding.** Every process computes the SAME global batch
  schedule from the same seed (the dataset listing is sorted, the shuffle is
  keyed on (seed, epoch)), then materialises only its own slice of each
  global batch.  All hosts therefore step through identical batch counts and
  shapes — the invariant ``jax.make_array_from_process_local_data`` needs —
  which is the role ``DistributedSampler`` plays in the reference
  (train.py:79-88).  Short batches are filled with ``sample_mask=0`` slots
  instead of the reference's wrap-around duplicates, fixing its biased eval
  denominator (train.py:157 divides by ``total_size`` incl. duplicates).
* **Determinism.** The flip RNG is keyed on (seed, epoch, item index), so any
  host resuming at any point reproduces the same stream.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Batch:
    """One static-shape (per-host slice of a) batch.

    image: (B, H, W, 3) float32, normalised; zero-padded outside each item.
    dmap: (B, H/ds, W/ds, 1) float32 target density.
    pixel_mask: (B, H/ds, W/ds, 1) float32 — 1 on valid density cells.
    sample_mask: (B,) float32 — 1 for real items, 0 for fill slots.
    """

    image: np.ndarray
    dmap: np.ndarray
    pixel_mask: np.ndarray
    sample_mask: np.ndarray

    @property
    def num_valid(self) -> int:
        return int(self.sample_mask.sum())


def _ceil_bound(v: int, bounds: Tuple[int, ...]) -> int:
    """Smallest ladder bound >= v (bounds sorted ascending; last covers max)."""
    for b in bounds:
        if b >= v:
            return b
    return bounds[-1]


def snap_to_bucket(hw: Tuple[int, int], *,
                   ladder: Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]] = None,
                   pad_multiple: Optional[Tuple[int, int]] = None,
                   min_bucket_h: Optional[int] = None) -> Tuple[int, int]:
    """Bucket (H, W) for one snapped item shape — the single source of the
    shape→bucket mapping, shared by the offline ``ShardedBatcher`` and the
    online ``serve`` micro-batcher so both paths pad identically.

    ladder: per-axis upper bounds ((H bounds), (W bounds)) — each axis snaps
    up to its smallest covering bound (items above the top bound get the top
    bound; callers size the ladder from their shape distribution).
    pad_multiple: (mh, mw) round-up multiples, used when no ladder is given.
    Neither -> exact shape (zero padding).
    """
    if ladder is not None:
        hb, wb = ladder
        key = (_ceil_bound(hw[0], hb), _ceil_bound(hw[1], wb))
    elif pad_multiple is not None:
        mh, mw = pad_multiple
        key = (math.ceil(hw[0] / mh) * mh, math.ceil(hw[1] / mw) * mw)
    else:
        key = hw
    if min_bucket_h is not None and key[0] < min_bucket_h:
        key = (min_bucket_h, key[1])
    return key


def _merge_partial_groups(partials, gbs: int):
    """Improvement-only pairwise merging of partial batch groups.

    Every partial group pays for ``gbs`` slots at its bucket shape whatever
    its fill; on wild datasets with many buckets the dead slots can cost
    more compute than the padding itself (measured: the bench distribution
    wastes 2x more pixels in dead slots than in padding at 16 buckets).
    Repeatedly merge the pair of groups whose union — at the JOIN bucket
    (elementwise max, so still a ladder grid cell: no new compiles) — costs
    fewer padded pixels than the two groups separately; stop when no merge
    improves.  Deterministic: inputs arrive key-sorted and ties pick the
    lexicographically first pair, so every host computes the same schedule.
    """

    def cost(key, n_items):
        return key[0] * key[1] * gbs * (-(-n_items // gbs))

    partials = [(k, list(g)) for k, g in partials]
    full = []
    while len(partials) > 1:
        best = None
        for i in range(len(partials)):
            ki, gi = partials[i]
            for j in range(i + 1, len(partials)):
                kj, gj = partials[j]
                join = (max(ki[0], kj[0]), max(ki[1], kj[1]))
                gain = (cost(ki, len(gi)) + cost(kj, len(gj))
                        - cost(join, len(gi) + len(gj)))
                if gain > 0 and (best is None or gain > best[0]):
                    best = (gain, i, j, join)
        if best is None:
            break
        _, i, j, join = best
        merged = partials[i][1] + partials[j][1]
        partials = [p for t, p in enumerate(partials) if t not in (i, j)]
        # a strictly-improving merge never overflows gbs: for a+b > gbs the
        # join would cost two batches at >= the average of the two shapes.
        # Guard the invariant anyway (full batches peel off) so a future
        # cost-function tweak can't silently emit oversized groups.
        while len(merged) > gbs:
            full.append((join, merged[:gbs]))
            merged = merged[gbs:]
        if merged:
            partials.append((join, merged))
    return full + partials


class StagingBatch:
    """A ``Batch``'s four arrays at one bucket's top launch size, kept
    across launches: ``pad_batch(out=...)`` assembles into a leading view
    of them and writes only what changed since the last launch.

    Owned by whoever allocates it (``serve.MicroBatcher``: per bucket and
    image dtype a ring of as many as launches may be in flight).  A
    ``Batch`` assembled into it is a VIEW: it is good until the next
    ``pad_batch`` into the same buffer, so the owner assembles into a
    buffer again only once nothing reads its previous batch any more (the
    batcher: when the ``dispatch`` it handed that batch to has returned
    or raised; until then the launch holds the buffer and the next one is
    assembled into another of the ring).
    Nothing but ``pad_batch`` may write the arrays: ``extent`` is what
    lets it skip the zeroing."""

    def __init__(self, bucket_hw: Tuple[int, int], slots: int, ds: int,
                 img_dtype):
        bh, bw = bucket_hw
        gh, gw = bh // ds, bw // ds
        self.image = np.zeros((slots, bh, bw, 3), img_dtype)
        self.dmap = np.zeros((slots, gh, gw, 1), np.float32)
        self.pixel_mask = np.zeros((slots, gh, gw, 1), np.float32)
        self.sample_mask = np.zeros((slots,), np.float32)
        # per slot, the (h, w) of the item last written there: the slot is
        # zero outside [:h, :w] of image and outside [:h // ds, :w // ds]
        # of dmap and pixel_mask
        self.extent: List[Tuple[int, int]] = [(0, 0)] * slots

    @property
    def nbytes(self) -> int:
        return (self.image.nbytes + self.dmap.nbytes
                + self.pixel_mask.nbytes + self.sample_mask.nbytes)


def _zero_stale(a: np.ndarray, prev: Tuple[int, int],
                new: Tuple[int, int]) -> None:
    """Zero the part of ``a[:prev[0], :prev[1]]`` that ``a[:new[0],
    :new[1]]`` does not cover (the new item overwrites the rest)."""
    (ph, pw), (h, w) = prev, new
    if ph > h:
        a[h:ph, :pw] = 0
    if pw > w:
        a[:min(h, ph), w:pw] = 0


def pad_batch(items, bucket_hw: Tuple[int, int], batch_size: int,
              valid_flags, ds: int,
              out: Optional[StagingBatch] = None) -> Batch:
    """Assemble variable-size (img, dmap) numpy pairs into one padded Batch.

    The image buffer keeps the items' dtype: float32 for the normalised
    host path, uint8 for the device-normalised transfer path (where the
    step zeroes padded pixels in normalised space via the upsampled
    pixel_mask, so both paths see identical zero padding).

    out: None allocates the four arrays fresh and the caller owns them
    (training, eval, warmup, the fleet).  A ``StagingBatch`` of this
    bucket and dtype with at least ``batch_size`` slots is filled in place
    instead, and the returned Batch is its leading ``[:batch_size]`` view
    holding the same bytes a fresh call would: the items are copied in and
    only what is stale is zeroed (what a slot's previous item covered and
    the new one does not; dead slots an earlier launch filled).  The view
    is free for the next assembly when its reader is done with it, which
    is the buffer's owner's to know."""
    if out is not None:
        return _pad_into(out, items, bucket_hw, batch_size, valid_flags, ds)
    bh, bw = bucket_hw
    gh, gw = bh // ds, bw // ds
    img_dtype = items[0][0].dtype if items else np.float32
    image = np.zeros((batch_size, bh, bw, 3), img_dtype)
    dmap = np.zeros((batch_size, gh, gw, 1), np.float32)
    pixel_mask = np.zeros((batch_size, gh, gw, 1), np.float32)
    sample_mask = np.zeros((batch_size,), np.float32)
    for slot, ((img, dm), valid) in enumerate(zip(items, valid_flags)):
        h, w = img.shape[:2]
        image[slot, :h, :w] = img
        dmap[slot, : h // ds, : w // ds] = dm
        pixel_mask[slot, : h // ds, : w // ds] = 1.0
        sample_mask[slot] = float(valid)
    return Batch(image, dmap, pixel_mask, sample_mask)


def _pad_into(out: StagingBatch, items, bucket_hw: Tuple[int, int],
              batch_size: int, valid_flags, ds: int) -> Batch:
    bh, bw = bucket_hw
    batch = Batch(out.image[:batch_size], out.dmap[:batch_size],
                  out.pixel_mask[:batch_size], out.sample_mask[:batch_size])
    if (batch.image.shape != (batch_size, bh, bw, 3)
            or batch.dmap.shape != (batch_size, bh // ds, bw // ds, 1)
            or (items and items[0][0].dtype != out.image.dtype)):
        raise ValueError(
            f"staging buffer {out.image.shape} {out.image.dtype} cannot "
            f"hold {batch_size} slots of bucket {bh}x{bw} (ds {ds})"
            + (f" {items[0][0].dtype}" if items else ""))

    def restage(slot: int, h: int, w: int) -> None:
        # zero first, then record, then (the caller) write: whatever
        # raises in between, the slot is zero outside the recorded extent
        prev, grid = out.extent[slot], (h // ds, w // ds)
        _zero_stale(batch.image[slot], prev, (h, w))
        prev = (prev[0] // ds, prev[1] // ds)
        _zero_stale(batch.dmap[slot], prev, grid)
        _zero_stale(batch.pixel_mask[slot], prev, grid)
        out.extent[slot] = (h, w)

    batch.sample_mask[:] = 0.0
    filled = 0
    for slot, ((img, dm), valid) in enumerate(zip(items, valid_flags)):
        h, w = img.shape[:2]
        restage(slot, h, w)
        batch.image[slot, :h, :w] = img
        batch.dmap[slot, : h // ds, : w // ds] = dm
        batch.pixel_mask[slot, : h // ds, : w // ds] = 1.0
        batch.sample_mask[slot] = float(valid)
        filled = slot + 1
    for slot in range(filled, batch_size):
        if out.extent[slot] != (0, 0):
            restage(slot, 0, 0)
    return batch


class ShardedBatcher:
    """Shuffled, shape-bucketed, lockstep-sharded batch iterator.

    dataset: needs ``__len__``, ``snapped_shape(i) -> (H, W)`` and
      ``__getitem__(i, rng) -> (img HWC, dmap hw1)``.
    batch_size: items **per host** per emitted batch; the global batch is
      ``batch_size * process_count``.
    pad_multiple: None → bucket by exact snapped shape (reference-exact
      math); int (multiple of ``ds``) → round H, W up to it (fewer compiles).
    """

    def __init__(self, dataset, batch_size: int, *, shuffle: bool = True,
                 seed: int = 0, process_index: int = 0, process_count: int = 1,
                 pad_multiple=None, ds: int = 8, max_buckets: int = 8,
                 min_pad_multiple: Optional[int] = None,
                 min_bucket_h: Optional[int] = None,
                 num_workers: int = 0,
                 remnant_sizes: bool = False,
                 batch_quantum: Optional[int] = None,
                 launch_cost_px: float = 2e6,
                 max_launch_px: Optional[float] = None,
                 plan_mode: str = "cost"):
        if plan_mode not in ("cost", "legacy"):
            raise ValueError(f"unknown plan_mode {plan_mode!r}")
        self.dataset = dataset
        self.batch_size = int(batch_size)
        # "cost": the round-8 cost-model planner (data/planner.py) — exact
        # remnant menus, full-cell batch-size pricing under the HBM cap,
        # merge + local-search packing, and plan-cost-scored ladder grids.
        # "legacy": the pre-r8 heuristics, kept bit-compatible as the
        # baseline of tests/fixtures/PLAN_ABLATION_r08.json and escape
        # hatch.
        self.plan_mode = plan_mode
        # remnant sub-batches (ladder mode only): emit partial groups at a
        # small menu of sub-batch sizes instead of padding every straggler
        # group to the full global batch — see _partial_plan.  Off by
        # default because legal sub-sizes depend on topology the batcher
        # can't see: every emitted global batch must divide by the mesh's
        # dp axis AND by process_count, which is what ``batch_quantum``
        # (global-batch units; callers pass lcm(dp, process_count))
        # promises.  The CLIs enable it with the right quantum.
        self.remnant_sizes = bool(remnant_sizes)
        self.batch_quantum = int(batch_quantum or process_count or 1)
        # fixed cost of one extra step launch, in pixel-equivalents, for
        # the remnant planner's pixels-vs-launches trade (see _decompose).
        # The default is deliberately conservative (~a 1-2 Mpx image's
        # compute): hosts with sub-ms dispatch can pass ~5e4 to unlock
        # exact splits; at ~2 Mpx per launch splitting is a net loss.
        # Not measured on the current machine (ROADMAP Design 4)
        self.launch_cost_px = float(launch_cost_px)
        # HBM ceiling per launch, in pixels (batch * H * W): bucket cells
        # whose full-batch launch would overflow device memory run at the
        # largest menu size that fits instead (the train step's activation
        # footprint is linear in pixels — cli/common.py max_launch_pixels
        # derives the value from HBM).  Ladder+remnant mode only; None =
        # uncapped.  This is what makes big-batch training runnable on
        # wild datasets whose largest shapes don't fit at the global batch
        # (the reference's only fits-anything answer was batch-1,
        # reference train.py:177).
        self.max_launch_px = (None if max_launch_px is None
                              else float(max_launch_px))
        self._cap_warned: set = set()
        self._plan_cache = None
        # last subset schedule, keyed (epoch, frozenset(include)): the
        # elastic resume asks for the identical subset schedule 2-3
        # times (progress total, epoch(), a possible second shrink) and
        # each build pays an uncached planner run over the subset
        self._subset_cache: Optional[Tuple[Tuple[int, frozenset], list]] = None
        # last FULL epoch schedule, keyed by epoch: batches_per_epoch,
        # the epoch iterator, planner_stats, and the r14 prefetch
        # pricing all ask for the same epoch's schedule — each rebuild
        # is an O(dataset) sort+group, and the schedule is a pure
        # function of (seed, epoch, histogram)
        self._epoch_cache: Optional[Tuple[int, list]] = None
        # host loader threads (the reference's DataLoader num_workers,
        # train.py:90, done with threads: PIL decode / cv2 resize release
        # the GIL, and threads share the process — no pickling, no fork
        # hazards next to a live JAX runtime).  0 = main-thread loading.
        self.num_workers = int(num_workers)
        self._pool: Optional[ThreadPoolExecutor] = None
        self.shuffle = shuffle
        self.seed = int(seed)
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.ds = int(ds)
        self.max_buckets = int(max_buckets)
        # snapped shapes are immutable per item: cache them so repeated
        # schedule builds (batches_per_epoch + every epoch) don't re-open
        # every image header
        self._shape_cache: Dict[int, Tuple[int, int]] = {}
        # floor on bucket height (spatial parallelism: each H-shard must own
        # >= 2 feature rows, cli/common.py resolve_sp_padding) — callers
        # pass a value compatible with their pad multiple
        self.min_bucket_h = min_bucket_h
        self.bucket_ladder: Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]] = None
        if self.remnant_sizes:
            gbs = self.batch_size * self.process_count
            if self.batch_quantum % self.process_count:
                raise ValueError(
                    f"batch_quantum ({self.batch_quantum}) must be a multiple "
                    f"of process_count ({self.process_count}) so every host "
                    f"slices an equal share of each sub-batch")
            if gbs % self.batch_quantum:
                raise ValueError(
                    f"global batch ({gbs}) must be a multiple of "
                    f"batch_quantum ({self.batch_quantum})")
        if pad_multiple == "auto":
            pad_multiple = self._resolve_auto_buckets(min_pad_multiple)
        # int -> same multiple both axes; (mh, mw) -> per-axis (spatial
        # parallelism constrains only the sharded H axis, so W keeps the
        # cheaper /ds multiple)
        if isinstance(pad_multiple, int):
            pad_multiple = (pad_multiple, pad_multiple)
        if pad_multiple is not None:
            for m in pad_multiple:
                if m % self.ds != 0:
                    raise ValueError(
                        f"pad_multiple ({pad_multiple}) must be multiples of "
                        f"the density downsample factor ({self.ds})")
        self.pad_multiple = pad_multiple

    def _item_shape(self, idx: int) -> Tuple[int, int]:
        hw = self._shape_cache.get(idx)
        if hw is None:
            hw = self._shape_cache[idx] = self.dataset.snapped_shape(idx)
        return hw

    @staticmethod
    def _axis_bounds(values, k: int, floor: int) -> Tuple[int, ...]:
        """k quantile upper bounds for one axis, rounded up to ``floor``
        multiples (so every bucket H works under spatial sharding too) —
        the coordinate-descent seed."""
        vs = sorted(values)
        n = len(vs)
        bounds = set()
        for i in range(1, k + 1):
            v = vs[-(-i * n // k) - 1]  # ceil(i*n/k)-1: i-th quantile's top
            bounds.add(-(-v // floor) * floor)
        return tuple(sorted(bounds))

    @staticmethod
    def _dp_axis_bounds(values, weights, k: int, floor: int) -> Tuple[int, ...]:
        """EXACT optimal <=k upper bounds for one axis minimising
        ``sum_i weights[i] * bound(values[i])`` (bounds restricted to
        ``floor`` multiples of observed values).  O(k n^2) DP over the n
        distinct candidates, vectorised; n is small (distinct snapped
        extents)."""
        cands = sorted({-(-v // floor) * floor for v in values})
        n = len(cands)
        if n <= k:
            return tuple(cands)
        wsum = {c: 0.0 for c in cands}
        for v, wt in zip(values, weights):
            wsum[-(-v // floor) * floor] += float(wt)
        pre = np.concatenate([[0.0], np.cumsum([wsum[c] for c in cands])])
        c_arr = np.asarray(cands, dtype=np.float64)
        inf = np.inf
        # f[m, j]: min cost covering candidates[0..j] with m bounds, bound at j
        f = np.full((k + 1, n), inf)
        f[1] = c_arr * pre[1:]
        choice = np.zeros((k + 1, n), dtype=np.int64)
        for m in range(2, k + 1):
            # cost(i -> j) = f[m-1, i] + c_j * (pre[j+1] - pre[i+1]), i < j
            prev = f[m - 1][:, None]  # (n, 1) over i
            trans = prev + c_arr[None, :] * (pre[1:][None, :] - pre[1:][:, None])
            trans = np.where(np.tri(n, n, -1, dtype=bool).T, trans, inf)
            choice[m] = np.argmin(trans, axis=0)
            f[m] = trans[choice[m], np.arange(n)]
        m_best = int(np.argmin(f[1:, n - 1])) + 1
        bounds, j, m = [], n - 1, m_best
        while m >= 1:
            bounds.append(cands[j])
            j, m = int(choice[m][j]), m - 1
        return tuple(sorted(bounds))

    def _resolve_auto_buckets(self, min_pad_multiple: Optional[int]) -> Optional[int]:
        """Choose static bucket shapes so each train/eval step compiles at
        most ``max_buckets`` programs.

        Snapped shapes are already multiples of ``ds``, so when the exact
        shape set is small enough, exact bucketing (None) wins: zero
        padding, bit-exact reference loss math.  Otherwise build a
        per-axis quantile ladder: split the H and W histograms into
        kH x kW quantile cells (every (kH, kW) split of the budget is
        scored by its padded-area overhead and the cheapest wins), and pad
        each image up to its cell's (H, W) upper bounds.  This beats any
        single global multiple on wild datasets — buckets concentrate
        where the shapes actually are.
        """
        shapes = [self._item_shape(i) for i in range(len(self.dataset))]
        if not shapes:
            return None
        if min_pad_multiple is None or isinstance(min_pad_multiple, int):
            min_pad_multiple = (min_pad_multiple, min_pad_multiple)
        floors = []
        for m in min_pad_multiple:
            f = max(self.ds, int(m or 0))
            if f % self.ds:
                f = -(-f // self.ds) * self.ds
            floors.append(f)
        floor_h, floor_w = floors
        if (floor_h == floor_w == self.ds
                and len(set(shapes)) <= self.max_buckets):
            return None
        hs = [h for h, _ in shapes]
        ws = [w for _, w in shapes]
        # cost mode + remnant sizes: boundary placement joins the plan
        # search — every (kh, kw) grid with kh*kw <= max_buckets is
        # descended and scored by the FULL plan cost of the schedule it
        # induces (padding AND dead slots AND launches, under the HBM
        # cap), because the padded-area score is blind to how counts
        # split across cells: at b16 a padding-optimal 24-cell ladder
        # leaves ~2.7 items per cell and the remnant covers/merges then
        # cost 3x the padding they saved (r5 chip sweep, 30.7%
        # schedule overhead).  Other modes keep the padded-area score
        # over budget-saturating grids (pre-r8 behaviour).
        cost_scored = self.plan_mode == "cost" and self.remnant_sizes
        candidates = ((kh, kw)
                      for kh in range(1, self.max_buckets + 1)
                      for kw in ((range(1, self.max_buckets // kh + 1))
                                 if cost_scored
                                 else (self.max_buckets // kh,))
                      if kw >= 1)
        best = None
        seen = set()
        for kh, kw in candidates:
            # seed with quantiles, then coordinate-descend: each axis's
            # bounds are re-solved EXACTLY (weighted 1-D DP) holding the
            # other axis fixed — the weight of an item along H is its
            # current padded W and vice versa, so each pass minimises the
            # true padded area.  Converges in 2-3 passes.
            hb = self._axis_bounds(hs, kh, floor_h)
            wb = self._axis_bounds(ws, kw, floor_w)
            for _ in range(3):
                hb2 = self._dp_axis_bounds(
                    hs, [_ceil_bound(w, wb) for w in ws], kh, floor_h)
                wb2 = self._dp_axis_bounds(
                    ws, [_ceil_bound(h, hb2) for h in hs], kw, floor_w)
                if (hb2, wb2) == (hb, wb):
                    break
                hb, wb = hb2, wb2
            if len(hb) * len(wb) > self.max_buckets or (hb, wb) in seen:
                continue
            seen.add((hb, wb))
            if cost_scored:
                score = self._ladder_plan_cost((hb, wb), shapes)
            else:
                score = sum(_ceil_bound(h, hb) * _ceil_bound(w, wb)
                            for h, w in shapes)
            if best is None or score < best[0]:
                best = (score, hb, wb)
        if best is None:  # budget < any grid: one bucket covering the max
            hb = (-(-max(hs) // floor_h) * floor_h,)
            wb = (-(-max(ws) // floor_w) * floor_w,)
            best = (0, hb, wb)
        _, hb, wb = best
        self.bucket_ladder = (hb, wb)
        return None

    def _ladder_plan_cost(self, ladder, shapes) -> float:
        """Plan cost of the full epoch schedule a candidate ladder would
        induce — the cost-mode score for ``_resolve_auto_buckets``.
        Cell counts are vectorised (the sweep visits ~max_buckets*H(max_
        buckets) candidate grids and may not cost O(n_items) Python per
        grid on large datasets).  Warnings stay silent here (only the
        CHOSEN ladder's plan warns, via _partial_plan)."""
        from can_tpu.sched import offline_planner

        hb, wb = ladder
        hs = np.asarray([h for h, _ in shapes])
        ws = np.asarray([w for _, w in shapes])
        hb_arr = np.asarray(hb)
        wb_arr = np.asarray(wb)
        hi = np.minimum(np.searchsorted(hb_arr, hs), len(hb) - 1)
        wi = np.minimum(np.searchsorted(wb_arr, ws), len(wb) - 1)
        snapped_h = hb_arr[hi]
        if self.min_bucket_h is not None:
            snapped_h = np.maximum(snapped_h, self.min_bucket_h)
        cells, ncell = np.unique(
            np.stack([snapped_h, wb_arr[wi]], axis=1),
            axis=0, return_counts=True)
        counts = {(int(h), int(w)): int(c)
                  for (h, w), c in zip(cells, ncell)}
        planner = offline_planner(self._cost_model(),
                                  max_buckets=self.max_buckets,
                                  mode=self.plan_mode)
        return planner.plan_with_fallback(counts).cost

    def padding_overhead(self) -> float:
        """Fraction of padded-batch pixels that are fill (0 = exact shapes).
        Uses the full dataset histogram, weighting each item by its bucket."""
        shapes = [self._item_shape(i) for i in range(len(self.dataset))]
        if not shapes:
            return 0.0
        item_area = sum(h * w for h, w in shapes)
        bucket_area = sum(bh * bw for bh, bw in map(self._bucket_key, shapes))
        return bucket_area / max(item_area, 1) - 1.0

    def schedule_overhead(self, epoch: int = 0) -> float:
        """TRUE fraction of step compute wasted in this epoch's schedule:
        padded pixels AND dead fill slots, over valid item pixels.  (
        ``padding_overhead`` counts only the per-item padding; on small or
        wildly-shaped datasets the dead slots of partial batches dominate.)
        """
        valid_px = 0
        used_px = 0
        for key, group in self.global_schedule(epoch):
            used_px += key[0] * key[1] * len(group)
            for idx, valid in group:
                if valid:
                    h, w = self._item_shape(idx)
                    valid_px += h * w
        return used_px / max(valid_px, 1) - 1.0

    def describe_buckets(self) -> str:
        """One-line bucket-policy summary for startup telemetry."""
        if self.bucket_ladder is not None:
            hb, wb = self.bucket_ladder
            return f"auto ladder H{list(hb)} x W{list(wb)}"
        if self.pad_multiple is None:
            return "exact shapes"
        mh, mw = self.pad_multiple
        if mh == mw:
            return f"multiple of {mh}"
        return f"H multiple of {mh}, W multiple of {mw}"

    def distinct_shapes(self, epoch: int = 0) -> int:
        """Number of distinct bucket shapes in this epoch's schedule — a
        lower bound on XLA compile count for the train step."""
        return len({key for key, _ in self.global_schedule(epoch)})

    @property
    def dataset_size(self) -> int:
        """True dataset length — the unbiased eval denominator."""
        return len(self.dataset)

    def _bucket_key(self, hw: Tuple[int, int]) -> Tuple[int, int]:
        return snap_to_bucket(hw, ladder=self.bucket_ladder,
                              pad_multiple=self.pad_multiple,
                              min_bucket_h=self.min_bucket_h)

    def _remnant_menu(self) -> Tuple[int, ...]:
        """Legal sub-batch sizes (global units), descending — every size a
        quantum multiple, so it divides cleanly into per-host slices and
        dp shards (batch_quantum contract).  Cost mode: every quantum
        multiple up to the global batch (exact-size remnant launches;
        the program budget prunes).  Legacy: gbs + quantum * 2^j."""
        from can_tpu.data.planner import remnant_menu

        return remnant_menu(self.batch_size * self.process_count,
                            self.batch_quantum, mode=self.plan_mode)

    def _cost_model(self, menu: Optional[Tuple[int, ...]] = None):
        from can_tpu.data.planner import PlanCostModel

        return PlanCostModel(menu=menu or self._remnant_menu(),
                             launch_cost_px=self.launch_cost_px,
                             max_launch_px=self.max_launch_px)

    def _menu_for(self, key: Tuple[int, int],
                  menu: Tuple[int, ...]) -> Tuple[int, ...]:
        """Menu filtered by the per-launch pixel cap for this cell; the
        smallest size always survives (the floor below which the batcher
        cannot subdivide — the quantum).  When even the quantum exceeds
        the cap, the cell launches anyway at the floor size — warned
        loudly ONCE, because the cap's no-OOM promise no longer holds for
        that cell (the alternative, refusing the item, would silently
        drop data)."""
        model = self._cost_model(menu)
        kept = model.fitting(key)
        if self.max_launch_px is not None and not model.fits(key, min(menu)):
            if key not in self._cap_warned:
                self._cap_warned.add(key)
                print(f"[batching] WARNING: bucket {key[0]}x{key[1]} exceeds "
                      f"the per-launch pixel cap even at the minimum batch "
                      f"{min(menu)} ({min(menu) * key[0] * key[1] / 1e6:.1f} "
                      f"Mpx > {self.max_launch_px / 1e6:.1f} Mpx) — "
                      f"launching anyway; expect HBM pressure (shrink "
                      f"batch_quantum or image sizes)")
        return kept

    @staticmethod
    def _decompose(n: int, menu: Tuple[int, ...], area: float = 1.0,
                   launch_cost: float = 0.0) -> Tuple[int, ...]:
        """Exact launch-size cover DP — see ``planner.decompose`` (moved
        there in r8 so the cost model, the ablation tool, and the batcher
        share one implementation; this alias keeps the planner's unit
        surface stable)."""
        from can_tpu.data.planner import decompose

        return decompose(n, menu, area, launch_cost)

    def _cell_counts(self) -> Dict[Tuple[int, int], int]:
        counts = getattr(self, "_cell_counts_cache", None)
        if counts is None:
            counts = self._cell_counts_cache = dict(collections.Counter(
                self._bucket_key(self._item_shape(i))
                for i in range(len(self.dataset))))
        return counts

    def _plan_for_counts(self, counts: Dict[Tuple[int, int], int]):
        """One ``planner.Plan`` for an arbitrary cell-count histogram —
        the full epoch's (cached by ``_partial_plan``) or an elastic
        REMAINDER's (the uncovered items of an interrupted epoch,
        replanned at the new world's quantum; ``global_schedule``'s
        ``include`` path).  A pure function of (counts, cost model,
        budget), so every host derives the identical plan.  Construction
        routes through the scheduling core (``sched.offline_planner`` —
        the r14 one-core refactor); plans are bit-identical to the
        pre-r14 direct ``GlobalPlanner`` (pinned by the legacy
        comparator in tests/test_sched.py)."""
        from can_tpu.sched import offline_planner

        def warn(msg):
            tag = msg[:40]
            if tag not in self._cap_warned:
                self._cap_warned.add(tag)
                print(f"[batching] WARNING: {msg}")

        planner = offline_planner(self._cost_model(),
                                  max_buckets=self.max_buckets,
                                  mode=self.plan_mode, warn=warn)
        return planner.plan_with_fallback(counts)

    def _partial_plan(self):
        """Epoch-invariant launch plan for ladder+remnant mode.

        An item's bucket cell is a pure function of its shape, so each
        cell's item count — hence its full/remnant split — is identical
        in every epoch; only WHICH items fill the slots varies with the
        shuffle.  The plan is therefore computed once from the shape
        histogram by ``planner.GlobalPlanner`` (full-cell batch sizing
        under the HBM cap, remnant menu composition, merge + local-search
        packing, program-budget levers) and cached.  Returns a
        ``planner.Plan``; ``legacy_fallback=True`` means the
        pad-every-straggler-to-gbs path proved cheaper and
        ``global_schedule`` falls through to it.
        """
        if self._plan_cache is not None:
            return self._plan_cache
        self._plan_cache = self._plan_for_counts(self._cell_counts())
        return self._plan_cache

    def program_count(self, epoch: int = 0) -> int:
        """Distinct (bucket shape, batch size) pairs in this epoch's
        schedule — the train step's true XLA compile count (with remnant
        sub-batches, shapes alone undercount)."""
        return len({(key, len(group))
                    for key, group in self.global_schedule(epoch)})

    def planner_stats(self, epoch: int = 0) -> Dict[str, object]:
        """One flat dict of planner decisions + realized schedule
        economics for this epoch — the payload of the ``data.planner``
        telemetry event (live gauges on the /metrics exporter).  Predicted
        numbers come from the cost model; realized ones are re-derived
        from the emitted schedule, so
        a divergence between the two is a planner bug, not noise (pinned
        by test)."""
        sched = self.global_schedule(epoch)
        used_px = sum(k[0] * k[1] * len(g) for k, g in sched)
        valid_px = sum(h * w for h, w in
                       (self._item_shape(i) for i in range(len(self.dataset))))
        stats = {
            "plan_mode": self.plan_mode,
            "padding_overhead": round(self.padding_overhead(), 4),
            "schedule_overhead": round(used_px / max(valid_px, 1) - 1.0, 4),
            "program_count": len({(k, len(g)) for k, g in sched}),
            "batches_per_epoch": len(sched),
            "realized_px": float(used_px),
            "realized_cost_px": float(used_px
                                      + self.launch_cost_px * len(sched)),
            "launch_cost_px": float(self.launch_cost_px),
            "max_launch_px": self.max_launch_px,
            "max_buckets": self.max_buckets,
        }
        if self.bucket_ladder is not None and self.remnant_sizes:
            plan = self._partial_plan()
            stats.update(
                plan_cost_px=float(plan.cost),
                plan_scheduled_px=float(plan.scheduled_px),
                plan_launches=plan.launches,
                plan_programs=len(plan.programs),
                lowered_cells=plan.lowered_cells,
                lowered_launches=plan.lowered_launches,
                legacy_fallback=plan.legacy_fallback,
                menu_sizes=len(plan.menu),
            )
        return stats

    def global_schedule(self, epoch: int, include: Optional[set] = None
                        ) -> List[Tuple[Tuple[int, int], List[Tuple[int, bool]]]]:
        """Deterministic global batch plan: [(bucket_hw, [(idx, valid)] of
        length global_batch)] — identical on every host for a given
        (seed, epoch).

        ``include`` restricts the plan to a subset of item indices — the
        elastic-resume path: the uncovered REMAINDER of an interrupted
        epoch is replanned (fresh ``_plan_for_counts`` over the subset
        histogram, at THIS batcher's quantum — i.e. the shrunk world's)
        while keeping the epoch's shuffle order, so consumed ∪ scheduled
        covers the epoch exactly once across the transition.  Every host
        passes the same set (derived from the shared elastic manifest)
        and computes the identical plan; the last subset schedule is
        memoised (the resume leg asks for it 2-3 times)."""
        if include is None:
            if self._epoch_cache is not None \
                    and self._epoch_cache[0] == epoch:
                return self._epoch_cache[1]
            sched = self._build_schedule(epoch, None)
            self._epoch_cache = (epoch, sched)
            return sched
        key = (epoch, frozenset(int(i) for i in include))
        if self._subset_cache is not None and self._subset_cache[0] == key:
            return self._subset_cache[1]
        sched = self._build_schedule(epoch, set(key[1]))
        self._subset_cache = (key, sched)
        return sched

    def _build_schedule(self, epoch: int, include: Optional[set]):
        n = len(self.dataset)
        if self.shuffle:
            order = np.random.default_rng((self.seed, epoch)).permutation(n)
        else:
            order = np.arange(n)
        if include is not None:
            include = set(int(i) for i in include)
            order = np.asarray([i for i in order.tolist() if i in include],
                               dtype=np.int64)
        gbs = self.batch_size * self.process_count
        remnant_mode = self.remnant_sizes
        menu = self._remnant_menu() if remnant_mode else None

        plan = None
        if self.bucket_ladder is not None and remnant_mode:
            # remnant sub-batches: the epoch-invariant plan (_partial_plan,
            # a pure function of the shape histogram — identical on every
            # host and in every epoch; the shuffle only decides which
            # concrete items fill the slots) fixes each cell's full-launch
            # sizes AND the straggler groups' join cells + part sizes.
            # An ``include`` subset gets its own (uncached) plan over the
            # subset histogram.  legacy_fallback means the planner proved
            # the pad-every-straggler-to-gbs path cheaper — fall through.
            if include is None:
                plan = self._partial_plan()
            else:
                plan = self._plan_for_counts(dict(collections.Counter(
                    self._bucket_key(self._item_shape(int(i)))
                    for i in order.tolist())))
            if plan.legacy_fallback:
                plan = None
        if plan is not None:
            # stream full launches as cells fill: each cell's planned part
            # sizes are descending, so thresholds are hit in order
            next_full = {k: list(parts)
                         for k, parts in plan.full_parts.items()}
            pending = {}
            schedule = []
            for idx in order.tolist():
                key = self._bucket_key(self._item_shape(idx))
                group = pending.setdefault(key, [])
                group.append((idx, True))
                parts = next_full.get(key)
                if parts and len(group) == parts[0]:
                    schedule.append((key, group))
                    pending[key] = []
                    parts.pop(0)
            for pg in plan.groups:
                items = [it for k in pg.sources for it in pending.get(k, [])]
                pos = 0
                for size in pg.parts:
                    take = items[pos:pos + size]
                    pos += size
                    if len(take) < size:
                        take = take + [(take[0][0], False)] * (size - len(take))
                    schedule.append((pg.key, take))
            return schedule

        full_size = {}  # per-cell full-batch size (pixel cap may shrink it)

        def cell_full(key):
            s = full_size.get(key)
            if s is None:
                s = full_size[key] = (max(self._menu_for(key, menu))
                                      if remnant_mode else gbs)
            return s

        pending: Dict[Tuple[int, int], List[Tuple[int, bool]]] = {}
        schedule = []
        for idx in order.tolist():
            key = self._bucket_key(self._item_shape(idx))
            group = pending.setdefault(key, [])
            group.append((idx, True))
            if len(group) == cell_full(key):
                schedule.append((key, group))
                pending[key] = []
        if self.bucket_ladder is None and self.remnant_sizes:
            # exact / fixed-multiple modes: remnant sizes WITHOUT merging,
            # COVER-ONLY (a single part per straggler group: the smallest
            # menu size that fits it).  Shape joins would break these
            # modes' padding promises, and a multi-part split would mint
            # extra (shape, size) programs — cover-only keeps both
            # invariants: exactly legacy's launch and program counts, with
            # the (shape, cover) program replacing (shape, gbs).  This is
            # what makes small-eval-set batch>1 eval cheap: the reference
            # evaluates at batch 1 with zero waste (test.py:16-35); a
            # 16-image eval split at batch 8 used to be ~70% fill slots
            # here (the round-3 startup hint).
            for key, group in sorted(((k, g) for k, g in pending.items()
                                      if g), key=lambda kg: kg[0]):
                fits = [s for s in self._menu_for(key, menu)
                        if s >= len(group)]
                size = min(fits) if fits else max(self._menu_for(key, menu))
                pos = 0
                while pos < len(group):  # >1 round only under a pixel cap
                    take = group[pos:pos + size]
                    pos += size
                    if len(take) < size:
                        take = take + [(take[0][0], False)] * (size - len(take))
                    schedule.append((key, take))
            return schedule
        partials = sorted(((k, g) for k, g in pending.items() if g),
                          key=lambda kg: kg[0])
        if self.bucket_ladder is not None:
            # ladder mode only: merge straggler groups upward when that
            # costs fewer padded pixels than their dead slots would.  Joins
            # are elementwise maxes of ladder bounds, i.e. grid cells, so
            # the compile bound holds.  Exact mode skips this (a merge
            # would break its zero-padding promise); fixed-multiple mode
            # skips it too — there the join space is the cross product of
            # observed extents and each epoch's shuffle could mint novel
            # shapes, i.e. unbounded mid-run compiles.
            partials = _merge_partial_groups(partials, gbs)
        for key, group in partials:
            if len(group) < gbs:
                # fill dead slots (static shape, zero weight) instead of the
                # reference's wrap-around duplicates.
                group = group + [(group[0][0], False)] * (gbs - len(group))
            schedule.append((key, group))
        return schedule

    def batches_per_epoch(self, epoch: int = 0) -> int:
        return len(self.global_schedule(epoch))

    def epoch(self, epoch: int, include: Optional[set] = None) -> Iterator[Batch]:
        """Yield this host's slice of each global batch, in schedule order.

        With ``num_workers > 0``, item loads (decode + resize + flip) run on
        a thread pool across a sliding window of upcoming batches — both
        intra-batch (wide batches) and inter-batch (batch_size=1, the
        reference's default) parallelism.  Output order and content are
        identical to the serial path: each item's RNG is keyed on
        (seed, epoch, idx), so determinism is independent of thread timing.

        ``include`` yields only the subset schedule (see
        ``global_schedule``) — the elastic remainder of an interrupted
        epoch.  Item RNG keys are unchanged, so a subset item's
        flip/augmentation is bit-identical to the one the uninterrupted
        epoch would have applied.
        """
        def host_slice(group):
            # groups are gbs long, except remnant sub-batches (menu sizes,
            # always a multiple of process_count by the quantum contract)
            sub = len(group) // self.process_count
            lo = self.process_index * sub
            return group[lo:lo + sub]

        schedule = self.global_schedule(epoch, include)
        pool = self._ensure_pool()
        if pool is None:
            for key, group in schedule:
                yield self._materialise(key, host_slice(group), epoch)
            return
        # enough batches in flight to keep every worker busy even at
        # batch_size=1, but bounded so at most `window` decoded batches
        # wait in host RAM
        window = max(2, -(-self.num_workers // max(self.batch_size, 1)) + 1)
        inflight = collections.deque()

        def submit(key, group):
            futs = [pool.submit(self._load_item, int(idx), epoch)
                    for idx, _ in group]
            return key, group, futs

        i = 0
        try:
            while i < len(schedule) or inflight:
                while i < len(schedule) and len(inflight) < window:
                    key, group = schedule[i]
                    inflight.append(submit(key, host_slice(group)))
                    i += 1
                key, group, futs = inflight.popleft()
                items = [f.result() for f in futs]
                yield pad_batch(items, key, len(group),
                                [v for _, v in group], self.ds)
        finally:
            # an abandoned generator (break mid-epoch, error downstream)
            # must not leave up to window*batch_size decode tasks running
            for _, _, futs in inflight:
                for f in futs:
                    f.cancel()

    def close(self) -> None:
        """Shut down the loader thread pool (idempotent).  The batcher
        stays usable — the pool is re-created on the next epoch() — so
        this is a resource release, not a terminal state."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)

    def __enter__(self) -> "ShardedBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        # can-tpu-lint: disable=SWALLOW(interpreter-teardown finalizer; close() is the real, loud API)
        except Exception:
            pass

    def _ensure_pool(self) -> Optional[ThreadPoolExecutor]:
        if self.num_workers > 0 and self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.num_workers,
                thread_name_prefix="can_tpu_loader")
        return self._pool

    def _load_item(self, idx: int, epoch: int):
        rng = np.random.default_rng((self.seed, epoch, idx))
        return self.dataset.__getitem__(idx, rng=rng)

    def _materialise(self, key, group, epoch: int) -> Batch:
        items = [self._load_item(int(idx), epoch) for idx, _ in group]
        return pad_batch(items, key, len(group), [v for _, v in group], self.ds)
