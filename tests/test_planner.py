"""Cost-model planner (can_tpu/data/planner.py) + its r8 satellites:
optimality and invariant properties, the acceptance headline pin, planner
telemetry gauges/report, the scaling projection, and the CI bench gate.

The heavier schedule-level fuzz (coverage, quantum divisibility, cap,
epoch invariance, host lockstep, never-worse-than-legacy) lives in
tests/test_data.py::TestRemnantSubBatches::test_planner_invariants_fuzz
and runs against the SAME default (cost) planner; this file covers what
that sweep cannot see from the outside."""

from __future__ import annotations

import itertools
import json
import os

import numpy as np
import pytest

from can_tpu.data.batching import ShardedBatcher
from can_tpu.data.planner import (
    GlobalPlanner,
    PlanCostModel,
    decompose,
    remnant_menu,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the r5 chip configuration's per-launch pixel cap (v5e spec HBM via the
# device-kind fallback, bf16, single chip) — what the r5 sweep ran under
V5E_CAP = 0.92 * (16 * 2**30 * 0.97) / 1100.0


class _ShapeDs:
    def __init__(self, shapes):
        self.shapes = list(shapes)

    def __len__(self):
        return len(self.shapes)

    def snapped_shape(self, i):
        return self.shapes[i]


def bench_shapes(n=64, seed=0):
    """A Part-A-like histogram: 40% at 768x1024, the rest uniform in
    384..1024 per side, snapped to 8 (the draws behind
    tests/fixtures/PLAN_ABLATION_r08.json)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        if rng.uniform() < 0.4:
            h, w = 768, 1024
        else:
            h = int(rng.integers(384, 1025))
            w = int(rng.integers(384, 1025))
        out.append(((h // 8) * 8, (w // 8) * 8))
    return out


def mk(shapes, bs, **kw):
    kw.setdefault("max_buckets", 24)
    kw.setdefault("batch_quantum", 1)
    kw.setdefault("launch_cost_px", 2e6)
    return ShardedBatcher(_ShapeDs(shapes), bs, shuffle=True, seed=0,
                          pad_multiple="auto", remnant_sizes=True, **kw)


class TestPlanCostModel:
    def test_decompose_is_the_shared_implementation(self):
        # the batcher's staticmethod is an alias, not a fork
        assert (ShardedBatcher._decompose(13, (16, 8, 4, 2, 1), 1.0, 0.0)
                == decompose(13, (16, 8, 4, 2, 1), 1.0, 0.0) == (8, 4, 1))

    def test_remnant_menu_modes(self):
        assert remnant_menu(16, 1, mode="cost") == tuple(range(16, 0, -1))
        assert remnant_menu(16, 4, mode="cost") == (16, 12, 8, 4)
        assert remnant_menu(16, 1, mode="legacy") == (16, 8, 4, 2, 1)
        assert remnant_menu(12, 3, mode="legacy") == (12, 6, 3)

    def test_fitting_respects_cap_with_quantum_floor(self):
        m = PlanCostModel(menu=(8, 4, 2, 1), max_launch_px=4 * 100 * 100)
        assert m.fitting((100, 100)) == (4, 2, 1)
        # even the quantum over the cap -> floor fallback, never empty
        assert m.fitting((1000, 1000)) == (1,)
        assert m.fitting((10, 10)) == (8, 4, 2, 1)

    def test_full_size_prices_every_fitting_size(self):
        """Brute force: the chosen full-cell launch size minimises the
        whole-cell cost (full chunks at s + cheapest remainder cover)
        over every cap-fitting size — 'run the whole cell at a lower
        batch' is priced, not assumed away (VERDICT r5 item 7)."""
        rng = np.random.default_rng(7)
        for _ in range(60):
            q = int(rng.choice([1, 2, 4]))
            gbs = q * int(rng.choice([2, 4, 8]))
            menu = remnant_menu(gbs, q, mode="cost")
            area = float(rng.integers(64, 2048) * 64)
            lc = float(rng.choice([0.0, area / 2, 2 * area, 20 * area]))
            cap = float(rng.choice([0, area * gbs / 2, area * gbs * 2]))
            m = PlanCostModel(menu=menu, launch_cost_px=lc,
                              max_launch_px=cap or None)
            count = int(rng.integers(1, 3 * gbs))
            key = (int(area // 64), 64)

            def whole(s):
                n_full = count // s
                rem = count - n_full * s
                c = n_full * (m.area(key) * s + lc)
                if rem:
                    c += m.cell_cost(key, rem)
                return c

            got = m.full_size(key, count)
            fit = m.fitting(key)
            assert got in fit
            assert whole(got) == pytest.approx(min(whole(s) for s in fit))
            # ties prefer the larger size (fewer, fuller launches)
            assert all(whole(s) > whole(got) - 1e-9 for s in fit if s > got)

    def test_cell_parts_match_brute_force_with_cap(self):
        """decompose through the model (cap-filtered menu) is a true
        optimum: brute force over all covers agrees on cost."""
        rng = np.random.default_rng(11)
        for _ in range(30):
            menu = tuple(sorted({int(x) for x in
                                 rng.choice([1, 2, 3, 4, 6, 8, 12],
                                            size=rng.integers(1, 4))},
                                reverse=True))
            area = float(rng.integers(1, 50))
            lc = float(rng.choice([0.0, 1.0, 7.5]))
            cap = float(rng.choice([0, area * max(menu) / 2]))
            m = PlanCostModel(menu=menu, launch_cost_px=lc,
                              max_launch_px=cap or None)
            key = (1, int(area))
            n = int(rng.integers(1, 20))
            parts = m.parts(key, n)
            fit = m.fitting(key)
            assert all(p in fit for p in parts)
            best = None
            for k in range(1, n // min(fit) + 2):
                for combo in itertools.combinations_with_replacement(
                        sorted(fit, reverse=True), k):
                    if sum(combo) >= n:
                        c = area * sum(combo) + lc * k
                        best = c if best is None else min(best, c)
            assert m.parts_cost(key, parts) == pytest.approx(best)


class TestGlobalPlannerProperties:
    def test_plan_never_worse_than_unmerged(self):
        """The search starts from per-cell plans and only applies
        improving levers (budget permitting), so within budget the final
        cost can't exceed the no-merge baseline."""
        rng = np.random.default_rng(3)
        for _ in range(15):
            cells = {(int(rng.integers(4, 20)) * 8,
                      int(rng.integers(4, 20)) * 8): int(rng.integers(1, 30))
                     for _ in range(int(rng.integers(2, 9)))}
            gbs = 16
            m = PlanCostModel(menu=remnant_menu(gbs, 1),
                              launch_cost_px=float(rng.choice([0, 5e4, 2e6])))
            plan = GlobalPlanner(m, max_buckets=64).plan(cells)
            unmerged = sum(m.cell_cost(k, c) for k, c in cells.items())
            assert plan.cost <= unmerged + 1e-6

    def test_program_budget_or_cap_warning(self):
        # many distinct tiny cells, budget 3: the plan must land at <= 3
        # programs (no cap in the way)
        cells = {(64 + 8 * i, 64): 3 for i in range(12)}
        m = PlanCostModel(menu=(8, 4, 2, 1), launch_cost_px=0.0)
        plan = GlobalPlanner(m, max_buckets=3).plan(cells)
        assert len(plan.programs) <= 3
        ids = sum(c for c in cells.values())
        assert sum(sum(p.parts) for p in plan.groups) \
            + sum(sum(ps) for ps in plan.full_parts.values()) >= ids

    def test_lowered_full_cell_runs_under_cap(self):
        """A cell whose full batch exceeds the HBM cap runs WHOLE-CELL
        at a lowered size: full launches below gbs, all under the cap,
        and the lowered counts surface in planner_stats/Plan."""
        shapes = ([(800, 800)] * 30 + [(784, 792)] * 10
                  + [(400, 400)] * 12 + [(392, 384)] * 6
                  + [(240, 240)] * 4 + [(160, 168)] * 2)
        cap = 8 * 800 * 800  # the big cell fits at most batch 8
        b = mk(shapes, 16, max_buckets=4, launch_cost_px=0.05e6,
               max_launch_px=cap)
        assert b.bucket_ladder is not None  # ladder mode, not exact
        plan = b._partial_plan()
        big = max(plan.full_parts)
        assert all(p <= 8 for p in plan.full_parts[big])
        assert plan.lowered_launches > 0 and plan.lowered_cells > 0
        st = b.planner_stats(0)
        assert st["lowered_launches"] == plan.lowered_launches
        for k, g in b.global_schedule(0):
            assert k[0] * k[1] * len(g) <= cap

    def test_predicted_cost_equals_realized(self):
        """The model's plan cost must equal the cost re-derived from the
        emitted schedule — exactly.  A drift here means the planner is
        optimising a fiction."""
        rng = np.random.default_rng(19)
        for trial in range(5):
            shapes = bench_shapes(n=int(rng.integers(20, 70)), seed=trial)
            b = mk(shapes, int(rng.choice([8, 16])),
                   launch_cost_px=float(rng.choice([0.05e6, 0.5e6, 2e6])),
                   max_launch_px=V5E_CAP if trial % 2 else None)
            st = b.planner_stats(1)
            if "plan_cost_px" in st:
                # holds for the legacy-fallback arm too: its Plan carries
                # the pad-to-gbs schedule's REAL economics (code-review r8)
                assert st["plan_cost_px"] == pytest.approx(
                    st["realized_cost_px"]), trial

    def test_cost_mode_dominates_legacy_under_its_own_model(self):
        """At ANY launch price, the searched plan never costs more than
        the legacy heuristics' plan under the same model — the point of
        replacing three heuristics with one objective."""
        shapes = bench_shapes()
        for bs in (8, 16):
            for lc in (0.05e6, 2e6):
                cost = mk(shapes, bs, launch_cost_px=lc,
                          max_launch_px=V5E_CAP)
                legacy = mk(shapes, bs, launch_cost_px=lc,
                            max_launch_px=V5E_CAP, plan_mode="legacy")

                def realized(b):
                    return sum(k[0] * k[1] * len(g) + b.launch_cost_px
                               for k, g in b.global_schedule(1))

                assert realized(cost) <= realized(legacy) + 1e-6, (bs, lc)


class TestAcceptanceHeadline:
    """ISSUE 5 acceptance: b16-varres-equivalent schedule overhead
    0.3067 -> <= 0.24 under the same max_launch_px cap, padding not
    regressing, program count <= max_buckets.  Pinned here so the
    committed PLAN_ABLATION artifact can't silently rot."""

    def test_legacy_reproduces_r5(self):
        legacy = mk(bench_shapes(), 16, launch_cost_px=2e6,
                    max_launch_px=V5E_CAP, plan_mode="legacy")
        assert legacy.schedule_overhead(1) == pytest.approx(0.3067, abs=5e-4)
        assert legacy.padding_overhead() == pytest.approx(0.0961, abs=5e-4)

    def test_cost_planner_meets_target_at_device_pricing(self):
        from can_tpu.cli.common import DEVICE_LAUNCH_COST_MPX

        b = mk(bench_shapes(), 16,
               launch_cost_px=DEVICE_LAUNCH_COST_MPX * 1e6,
               max_launch_px=V5E_CAP)
        assert b.schedule_overhead(1) <= 0.24
        assert b.padding_overhead() <= 0.0961 + 5e-4  # no padding regression
        assert b.program_count(1) <= 24

    def test_cost_planner_improves_even_at_2mpx_pricing(self):
        b = mk(bench_shapes(), 16, launch_cost_px=2e6, max_launch_px=V5E_CAP)
        assert b.schedule_overhead(1) < 0.3067 - 1e-3


class TestPlannerTelemetry:
    def test_gauge_sink_exports_planner_gauges(self):
        from can_tpu.obs.exporter import GaugeSink

        g = GaugeSink()
        g.emit({"kind": "data.planner", "step": 0, "payload": {
            "schedule_overhead": 0.11, "padding_overhead": 0.0961,
            "program_count": 9, "lowered_launches": 2,
            "plan_mode": "cost", "legacy_fallback": False}})
        text = g.render()
        assert "can_tpu_planner_schedule_overhead 0.11" in text
        assert "can_tpu_planner_program_count 9" in text
        assert "can_tpu_planner_lowered_launches 2" in text
        # strings/bools are not gauges
        assert "plan_mode" not in text and "legacy_fallback" not in text

    def test_report_summarizes_planner_events(self):
        from can_tpu.obs.report import format_report, summarize

        events = [{"ts": 1.0, "kind": "data.planner", "step": e,
                   "host_id": 0, "payload": {
                       "plan_mode": "cost", "padding_overhead": 0.0961,
                       "schedule_overhead": 0.1, "program_count": 9,
                       "lowered_launches": 3, "realized_programs": 9}}
                  for e in (0, 1)]
        s = summarize(events)
        assert s["planner_schedule_overhead"] == 0.1
        assert s["planner_programs"] == 9
        assert s["planner_realized_programs"] == 9
        out = format_report(s)
        assert "batch planner" in out and "mode=cost" in out
        assert "(realized 9)" in out and "lowered=3" in out

    def test_epoch_stats_programs_alias(self):
        from can_tpu.train.loop import EpochStats

        assert EpochStats(0.0, distinct_shapes=7).programs == 7


class TestPlanSpaceTier:

    def test_committed_ablation_artifact_consistent(self):
        path = os.path.join(REPO, "tests", "fixtures",
                            "PLAN_ABLATION_r08.json")
        doc = json.load(open(path))
        head = doc["headline"]
        assert head["baseline_legacy_2mpx_pricing"]["schedule_overhead"] \
            == pytest.approx(0.3067, abs=5e-4)
        assert head["cost_planner_device_pricing"]["schedule_overhead"] \
            <= 0.24
        assert (head["cost_planner_device_pricing"]["padding_overhead"]
                <= head["baseline_legacy_2mpx_pricing"]["padding_overhead"]
                + 5e-4)
