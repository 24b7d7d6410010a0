"""Cost-model-driven batch planning for the varres bucket schedule.

Until round 7 the batch planner was three ad-hoc heuristics scattered
through ``batching.py``: ``_menu_for`` capped a too-big cell to the largest
remnant-menu size that fit HBM, ``_partial_plan`` greedily merged straggler
groups pairwise and dropped the smallest menu size when over the compile
budget, and ``_decompose`` ran a per-cell DP — each locally sensible, none
sharing an objective, and the measured result was a 30.7% schedule
overhead for b16 varres vs 21.7% at b8 (r5 chip sweep, VERDICT r5
item 7).  This module replaces them with ONE explicit objective,

    plan_cost = area * padded_slots + launch_cost_px * n_launches

(the unit is pixels; ``launch_cost_px`` converts a step launch's fixed
dispatch/device overhead into pixel-equivalents, calibrated by
``cli/common.py::measure_launch_cost_mpx`` — probe-vs-step ratio 1.15 on
chip, r5) and a deterministic search over the joint plan space:

* **per-cell batch size** — a cell whose full global batch exceeds the
  ``max_launch_px`` HBM cap prices EVERY fitting launch size (full-cell
  lowered runs vs cap-to-menu decompositions) and runs the cheapest;
* **remnant menu composition** — cost mode plans over every multiple of
  the batch quantum (dp-divisibility is the only hard divisibility
  constraint; the old power-of-two menu was a compile-count convenience),
  letting straggler groups launch at their EXACT size instead of padding
  up to the next power of two; the budget loop drops sizes when the
  program count would exceed ``max_buckets``;
* **group packing** — greedy pairwise merging is kept but extended with
  steepest-descent local search (move one source cell between groups,
  extract one back out), so a bad early join can be undone;
* **bucket-boundary placement** — ``ShardedBatcher._resolve_auto_buckets``
  scores every (kh, kw) ladder grid with kh*kw <= max_buckets by the FULL
  plan cost of the schedule it induces (not by padded area alone, which is
  blind to dead slots and launch counts), via ``GlobalPlanner.plan``.

Everything is a pure function of the shape histogram and the planner
config, so every host computes bit-identical plans (the lockstep-schedule
contract) and the plan is identical across epochs (the shuffle only
permutes which items fill the slots).

``mode="legacy"`` preserves the round-5 behaviour exactly (max-fitting
full size, power-of-two menu, pairwise merge + drop-smallest) — it is the
baseline arm of the golden plan ``tests/fixtures/PLAN_ABLATION_r08.json``
and the escape hatch if a regression ever points here.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, List, NamedTuple, Optional, Tuple

Key = Tuple[int, int]


def decompose(n: int, menu: Tuple[int, ...], area: float = 1.0,
              launch_cost: float = 0.0) -> Tuple[int, ...]:
    """Cover ``n`` items with menu-size parts minimising
    ``area * total_slots + launch_cost * n_parts`` — exact bottom-up DP
    (n is at most a few global batches; recursion would blow the stack at
    batch_quantum=1, ADVICE r4).

    Ties on cost prefer fewer launches, then the lexicographically
    smallest part tuple — the determinism the multi-host byte-identical
    plan contract rests on.  Parts return descending, so any fill slots
    land in the final (smallest) part."""
    base = (0.0, 0, ())
    best = [base] * (n + 1 if n > 0 else 1)
    for r in range(1, n + 1):
        best[r] = min(
            (area * s + launch_cost + sub[0], 1 + sub[1], (s,) + sub[2])
            for s in menu
            for sub in (best[r - s] if r > s else base,))
    return tuple(sorted(best[n if n > 0 else 0][2], reverse=True))


@dataclasses.dataclass(frozen=True)
class PlanCostModel:
    """The planner's single pricing function.

    menu: legal launch sizes (global units), descending; every size is a
      multiple of the batch quantum so any launch splits evenly across
      hosts and the mesh dp axis.
    launch_cost_px: fixed cost of one step launch, in pixel-equivalents.
    max_launch_px: HBM ceiling per launch (batch * H * W), or None.
    """

    menu: Tuple[int, ...]
    launch_cost_px: float = 0.0
    max_launch_px: Optional[float] = None

    @staticmethod
    def area(key: Key) -> int:
        return key[0] * key[1]

    def fits(self, key: Key, size: int) -> bool:
        return (self.max_launch_px is None
                or size * self.area(key) <= self.max_launch_px)

    def fits_any(self, key: Key, menu: Optional[Tuple[int, ...]] = None) -> bool:
        return any(self.fits(key, s) for s in (menu or self.menu))

    def fitting(self, key: Key,
                menu: Optional[Tuple[int, ...]] = None) -> Tuple[int, ...]:
        """Menu filtered by the per-launch pixel cap; the smallest size
        always survives (the quantum floor — refusing the cell would drop
        data, so an over-cap floor launch is the documented degradation,
        warned by the caller)."""
        menu = menu or self.menu
        kept = tuple(s for s in menu if self.fits(key, s))
        return kept or (min(menu),)

    def parts(self, key: Key, count: int,
              menu: Optional[Tuple[int, ...]] = None) -> Tuple[int, ...]:
        """Cheapest launch-size cover of ``count`` items in this cell."""
        return decompose(count, self.fitting(key, menu), float(self.area(key)),
                         self.launch_cost_px)

    def parts_cost(self, key: Key, parts: Tuple[int, ...]) -> float:
        return self.area(key) * sum(parts) + self.launch_cost_px * len(parts)

    def cell_cost(self, key: Key, count: int,
                  menu: Optional[Tuple[int, ...]] = None) -> float:
        return self.parts_cost(key, self.parts(key, count, menu))

    def full_size(self, key: Key, count: int) -> int:
        """Launch size for this cell's full (exactly-filled) runs: every
        fitting size is priced over the WHOLE cell (full chunks at that
        size + the cheapest decomposition of the remainder) and the
        cheapest wins — 'run the whole cell at a lower batch' is a
        first-class candidate, not a cap fallback.  Ties prefer the
        larger size (fewer, fuller launches)."""
        fit = self.fitting(key)
        if count <= 0 or len(fit) == 1:
            return max(fit)

        def whole_cell_cost(s: int) -> float:
            n_full = count // s
            rem = count - n_full * s
            cost = n_full * (self.area(key) * s + self.launch_cost_px)
            if rem:
                cost += self.cell_cost(key, rem)
            return cost

        return max(fit, key=lambda s: (-whole_cell_cost(s), s))


class PlannedGroup(NamedTuple):
    """One remnant launch group: stragglers from ``sources`` cells run at
    the elementwise-max ``key`` in launches of sizes ``parts``."""

    key: Key
    sources: Tuple[Key, ...]
    count: int
    parts: Tuple[int, ...]


class Plan(NamedTuple):
    """A complete epoch-invariant launch plan for one shape histogram."""

    full_parts: Dict[Key, Tuple[int, ...]]  # exactly-filled launches/cell
    groups: Tuple[PlannedGroup, ...]        # remnant groups (may have fill)
    menu: Tuple[int, ...]                   # after any budget drops
    programs: FrozenSet[Tuple[Key, int]]    # distinct (shape, size) pairs
    cost: float                             # model cost of the whole plan
    scheduled_px: float                     # area * slots over all launches
    launches: int
    legacy_fallback: bool = False           # pad-to-gbs path proved cheaper

    @property
    def lowered_cells(self) -> int:
        """Cells whose full runs launch below the top menu size (the
        HBM-cap batch-lowering the r5 verdict asked to price, item 7)."""
        if not self.full_parts:
            return 0
        top = max(self.menu)
        return sum(1 for parts in self.full_parts.values()
                   if parts and parts[0] < top)

    @property
    def lowered_launches(self) -> int:
        if not self.full_parts:
            return 0
        top = max(self.menu)
        return sum(sum(1 for p in parts if p < top)
                   for parts in self.full_parts.values())


class GlobalPlanner:
    """Search the joint plan space for one shape-count histogram.

    mode="cost" (default): full-cell size pricing, exact-size menus,
    merge + move/extract local search, drop-any-size budget lever.
    mode="legacy": the pre-r8 heuristics, bit-compatible — the ablation
    baseline.
    """

    def __init__(self, model: PlanCostModel, *, max_buckets: int,
                 mode: str = "cost",
                 warn: Optional[Callable[[str], None]] = None):
        if mode not in ("cost", "legacy"):
            raise ValueError(f"unknown planner mode {mode!r}")
        self.model = model
        self.max_buckets = int(max_buckets)
        self.mode = mode
        self.warn = warn or (lambda msg: None)
        self._parts_cache: Dict[Tuple, Tuple[int, ...]] = {}
        self._floor_warned: set = set()

    # -- cached pricing ---------------------------------------------------
    def _parts(self, key: Key, count: int,
               menu: Tuple[int, ...]) -> Tuple[int, ...]:
        ck = (key, count, menu)
        got = self._parts_cache.get(ck)
        if got is None:
            got = self._parts_cache[ck] = self.model.parts(key, count, menu)
        return got

    def _cost(self, key: Key, count: int, menu: Tuple[int, ...]) -> float:
        return self.model.parts_cost(key, self._parts(key, count, menu))

    # -- the search -------------------------------------------------------
    def plan(self, counts: Dict[Key, int]) -> Plan:
        model = self.model
        menu = tuple(sorted(model.menu, reverse=True))

        full_parts: Dict[Key, Tuple[int, ...]] = {}
        pool: List[Tuple[Key, int]] = []  # (cell key, remnant count)
        for k, c in sorted(counts.items()):
            if self.mode == "cost":
                cf = model.full_size(k, c)
            else:
                cf = max(model.fitting(k))
            if not model.fits(k, min(menu)) and k not in self._floor_warned:
                self._floor_warned.add(k)
                self.warn(
                    f"bucket {k[0]}x{k[1]} exceeds the per-launch pixel cap "
                    f"even at the minimum batch {min(menu)} "
                    f"({min(menu) * model.area(k) / 1e6:.1f} Mpx > "
                    f"{(model.max_launch_px or 0) / 1e6:.1f} Mpx) — "
                    f"launching anyway; expect HBM pressure (shrink "
                    f"batch_quantum or image sizes)")
            if c >= cf:
                full_parts[k] = (cf,) * (c // cf)
            if c % cf:
                pool.append((k, c % cf))

        groups: List[FrozenSet[int]] = [frozenset({i})
                                        for i in range(len(pool))]

        def join_of(srcs: FrozenSet[int]) -> Key:
            return (max(pool[i][0][0] for i in srcs),
                    max(pool[i][0][1] for i in srcs))

        def count_of(srcs: FrozenSet[int]) -> int:
            return sum(pool[i][1] for i in srcs)

        def gcost(srcs: FrozenSet[int], m: Tuple[int, ...]) -> float:
            if not srcs:
                return 0.0
            return self._cost(join_of(srcs), count_of(srcs), m)

        def gfits(srcs: FrozenSet[int], m: Tuple[int, ...]) -> bool:
            # the no-OOM promise outranks the compile budget: never create
            # a join cell with NO cap-fitting launch size — the floor
            # fallback would launch it above the cap (code-review r5)
            return model.fits_any(join_of(srcs), m)

        def programs_of(m: Tuple[int, ...]) -> FrozenSet[Tuple[Key, int]]:
            ps = {(k, s) for k, parts in full_parts.items() for s in parts}
            for g in groups:
                j = join_of(g)
                ps.update((j, s) for s in self._parts(j, count_of(g), m))
            return frozenset(ps)

        def resort():
            # keep the candidate enumeration order (hence tie-breaking)
            # independent of lever history: the pre-r8 planner re-sorted
            # its (key, count, sources) triples after every merge, and the
            # byte-identical multi-host plan contract rides on it
            groups.sort(key=lambda g: (join_of(g), count_of(g),
                                       tuple(sorted(pool[i][0]
                                                    for i in g))))

        # Two phases, each provably terminating (interleaving improvement
        # moves with forced budget merges could cycle: an extract can
        # undo the merge the budget just forced):
        #
        # Phase A (cost mode only) — steepest-descent improvement: MERGE
        # two groups at their elementwise-max join cell, MOVE one source
        # cell between groups, or EXTRACT one back out, cheapest
        # (most negative cost delta) first; strictly decreasing cost over
        # a finite state space, so it terminates.
        if self.mode == "cost":
            while True:
                best = None
                for i in range(len(groups)):
                    for j in range(i + 1, len(groups)):
                        u = groups[i] | groups[j]
                        if not gfits(u, menu):
                            continue
                        d = (gcost(u, menu) - gcost(groups[i], menu)
                             - gcost(groups[j], menu))
                        if d < -1e-9 and (best is None or d < best[0]):
                            best = (d, "merge", (i, j))
                    if len(groups[i]) <= 1:
                        continue
                    for s in sorted(groups[i]):
                        rest = groups[i] - {s}
                        base_d = gcost(rest, menu) - gcost(groups[i], menu)
                        for j in range(len(groups)):
                            if j == i:
                                continue
                            u = groups[j] | {s}
                            if not gfits(u, menu):
                                continue
                            d = (base_d + gcost(u, menu)
                                 - gcost(groups[j], menu))
                            if d < -1e-9 and (best is None or d < best[0]):
                                best = (d, "move", (i, j, s))
                        d = base_d + gcost(frozenset({s}), menu)
                        if d < -1e-9 and (best is None or d < best[0]):
                            best = (d, "extract", (i, s))
                if best is None:
                    break
                _, lever, payload = best
                if lever == "merge":
                    i, j = payload
                    groups[i] = groups[i] | groups[j]
                    groups.pop(j)
                elif lever == "move":
                    i, j, s = payload
                    groups[j] = groups[j] | {s}
                    groups[i] = groups[i] - {s}
                    groups = [g for g in groups if g]
                else:
                    i, s = payload
                    groups[i] = groups[i] - {s}
                    groups.append(frozenset({s}))
                    groups = [g for g in groups if g]
                resort()

        # Phase B — the budget loop (both modes; ≡ the pre-r8 loop when
        # no moves preceded it): improvement MERGES always apply, forced
        # merges and menu DROPS only while the program count exceeds
        # ``max_buckets``.  Merges shrink the group list and drops shrink
        # the menu, so this terminates too.
        while True:
            over = len(programs_of(menu)) > self.max_buckets
            best = None  # (delta, lever, payload)
            for i in range(len(groups)):
                for j in range(i + 1, len(groups)):
                    u = groups[i] | groups[j]
                    if not gfits(u, menu):
                        continue
                    d = (gcost(u, menu)
                         - gcost(groups[i], menu) - gcost(groups[j], menu))
                    if (d < -1e-9 or over) and (best is None or d < best[0]):
                        best = (d, "merge", (i, j))
            if over and len(menu) > 1:
                # DROP a menu size (remnant decompositions only; the
                # quantum always survives, and under a cap a size may only
                # go if every CURRENT group keeps a fitting launch size) —
                # cost mode may drop ANY size, legacy only the smallest
                # (menu is descending: the last index)
                droppable = (range(len(menu) - 1) if self.mode == "cost"
                             else (len(menu) - 1,))
                for di in droppable:
                    m2 = menu[:di] + menu[di + 1:]
                    if not all(gfits(g, m2) for g in groups):
                        continue
                    d = (sum(gcost(g, m2) for g in groups)
                         - sum(gcost(g, menu) for g in groups))
                    if best is None or d < best[0]:
                        best = (d, "drop", di)
            if best is None or (best[0] >= -1e-9 and not over):
                if over:
                    self.warn(
                        f"{len(programs_of(menu))} programs exceed "
                        f"max_buckets={self.max_buckets} — the per-launch "
                        f"pixel cap prevents further merging; expect extra "
                        f"XLA compiles")
                break
            _, lever, payload = best
            if lever == "merge":
                i, j = payload
                groups[i] = groups[i] | groups[j]
                groups.pop(j)
            else:
                menu = menu[:payload] + menu[payload + 1:]
            resort()

        planned = tuple(sorted(
            PlannedGroup(join_of(g),
                         tuple(sorted({pool[i][0] for i in g})),
                         count_of(g),
                         self._parts(join_of(g), count_of(g), menu))
            for g in groups))
        scheduled = (sum(model.area(k) * sum(parts)
                         for k, parts in full_parts.items())
                     + sum(model.area(pg.key) * sum(pg.parts)
                           for pg in planned))
        launches = (sum(len(p) for p in full_parts.values())
                    + sum(len(pg.parts) for pg in planned))
        return Plan(full_parts=full_parts, groups=planned, menu=menu,
                    programs=programs_of(menu),
                    cost=scheduled + model.launch_cost_px * launches,
                    scheduled_px=float(scheduled), launches=launches)

    def plan_with_fallback(self, counts: Dict[Key, int]) -> Plan:
        """``plan`` guarded by the legacy-padding safety net: when no
        pixel cap is in force, never schedule more pixels than the
        pad-every-straggler-to-gbs path would (legacy pads to the FULL
        global batch, which is exactly what a capped cell must not
        launch, so the net is skipped under a cap).  The fallback Plan
        carries the REAL economics of the pad-to-gbs schedule (pixels,
        launches, programs) — these feed the data.planner gauges, which
        must never report a zero-pixel plan for a schedule that launches
        everything."""
        plan = self.plan(counts)
        if self.model.max_launch_px is not None:
            return plan
        legacy = self._legacy_pad_plan(counts)
        if legacy is not None and legacy.cost < plan.cost:
            return legacy
        return plan

    def _legacy_pad_plan(self, counts: Dict[Key, int]) -> Optional[Plan]:
        """The pad-every-straggler-to-gbs schedule as a Plan (the exact
        economics of the path global_schedule falls through to)."""
        from can_tpu.data.batching import _merge_partial_groups

        gbs = max(self.model.menu)
        lc = self.model.launch_cost_px
        partials = [(k, [(k, True)] * (c % gbs))
                    for k, c in sorted(counts.items()) if c % gbs]
        if not partials:
            return None
        merged = _merge_partial_groups(partials, gbs)
        full = {k: (gbs,) * (c // gbs)
                for k, c in sorted(counts.items()) if c >= gbs}
        launches = (sum(len(p) for p in full.values())
                    + sum(-(-len(g) // gbs) for _, g in merged))
        scheduled = (sum(self.model.area(k) * sum(p)
                         for k, p in full.items())
                     + sum(self.model.area(k) * gbs * (-(-len(g) // gbs))
                           for k, g in merged))
        programs = frozenset({(k, gbs) for k in full}
                             | {(k, gbs) for k, _ in merged})
        return Plan(full_parts=full, groups=(), menu=(gbs,),
                    programs=programs, cost=scheduled + lc * launches,
                    scheduled_px=float(scheduled), launches=launches,
                    legacy_fallback=True)


def schedule_coverage(schedule) -> Dict[int, int]:
    """Valid-slot occurrences per item index over a realized schedule —
    the exact-coverage invariant's measurable form.  A correct epoch (or
    an elastic remainder replanned at a new quantum after a shrink)
    covers each of its items EXACTLY once: ``schedule_coverage(sched) ==
    {i: 1 for i in items}``.  Fill slots (valid=False) are excluded — a
    duplicated index with a zero sample mask contributes nothing.  Used
    by the elastic tests and the supervisor's resume-time sanity check;
    pure and jax-free."""
    seen: Dict[int, int] = {}
    for _key, group in schedule:
        for idx, valid in group:
            if valid:
                seen[int(idx)] = seen.get(int(idx), 0) + 1
    return seen


def remnant_menu(gbs: int, quantum: int, *, mode: str = "cost") -> Tuple[int, ...]:
    """Legal launch sizes (global units), descending.

    cost mode: every multiple of the quantum up to the global batch — the
    only hard constraint is dp-divisibility (every size splits evenly
    across hosts and mesh dp shards), so straggler groups can launch at
    their exact size; the program-budget lever drops sizes when compiles
    would exceed ``max_buckets``.  legacy mode: the full batch plus
    quantum * 2^j halvings (the pre-r8 compile-count convenience).
    """
    if mode == "cost":
        return tuple(range(gbs, 0, -quantum))
    menu = {gbs}
    s = quantum
    while s < gbs:
        menu.add(s)
        s *= 2
    return tuple(sorted(menu, reverse=True))
