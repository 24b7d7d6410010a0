"""The scheduling core (can_tpu/sched): priced sub-batch menu, priced
flush deadlines, cost/deadline-aware dispatch ordering, and the
one-registry guarantees across offline / serve / audit.

Covers the r14 acceptance set: menu selection vs brute force, the
predicted==realized invariant, bit-identical offline plans under the
extracted core, zero new compiles under mixed traffic with the menu
warmed, AOT bundle staleness on a menu change, deadline-ordering
starvation bounds, the audit's one-registry mutation teeth, and the
scheduler gauges/report row.
"""

from __future__ import annotations

import itertools
import json
import os

import numpy as np
import pytest

from can_tpu.sched import (
    DEFAULT_LAUNCH_COST_SLOTS,
    ServeSched,
    cover_cost,
    default_serve_menu,
    offline_planner,
    pick_work,
    prefetch_depth,
    select_menu,
)
from can_tpu.sched.core import prefetch_depth_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# -- menu selection -------------------------------------------------------
class TestMenuSelection:
    def brute_force(self, max_batch, budget, lc, weights=None):
        """Independent enumeration: every size subset containing
        max_batch, scored by expected cover cost."""
        w = weights or [1.0] * max_batch
        best = None
        for k in range(0, budget):
            for extra in itertools.combinations(
                    range(max_batch - 1, 0, -1), k):
                menu = (max_batch,) + extra
                cost = sum(w[n - 1] * cover_cost(n, menu, lc)
                           for n in range(1, max_batch + 1))
                key = (cost, len(menu), menu)
                if best is None or key < best:
                    best = key
        return best[2]

    @pytest.mark.parametrize("max_batch", [1, 2, 4, 8])
    @pytest.mark.parametrize("budget", [1, 2, 3, 4])
    def test_matches_brute_force(self, max_batch, budget):
        for lc in (0.05, 0.25, 1.0, 4.0):
            got = select_menu(max_batch, budget=budget,
                              launch_cost_slots=lc)
            assert got == self.brute_force(max_batch, budget, lc)

    def test_contains_max_batch_and_respects_budget(self):
        for mb in (2, 4, 8):
            for budget in (1, 2, 3):
                menu = select_menu(mb, budget=budget)
                assert max(menu) == mb
                assert len(menu) <= budget
                assert menu == tuple(sorted(menu, reverse=True))

    def test_budget_one_is_legacy(self):
        assert select_menu(8, budget=1) == (8,)

    def test_skewed_weights_move_the_menu(self):
        # all mass on n=1: the 1-slot program must be in the menu
        w = [1.0] + [0.0] * 7
        assert 1 in select_menu(8, budget=2, weights=w)

    def test_huge_launch_cost_prefers_fewer_sizes(self):
        # at a launch cost far above a slot, splitting never pays and
        # extra sizes can't reduce expected cost enough to matter —
        # the tie rule keeps the menu small
        menu = select_menu(4, budget=4, launch_cost_slots=100.0)
        assert max(menu) == 4

    def test_deterministic(self):
        assert select_menu(8) == select_menu(8) == default_serve_menu(8)


# -- predicted == realized ------------------------------------------------
class TestCoverInvariant:
    @pytest.mark.parametrize("max_batch", [2, 4, 8])
    def test_every_part_is_its_valid_counts_cover(self, max_batch):
        """Each DP part is exactly full or the tail whose size equals its
        remainder's cheapest single-launch cover — the invariant that
        lets the service recompute predicted cost independently."""
        for budget in (1, 2, 3):
            s = ServeSched(max_batch, max_wait_s=0.01, menu_budget=budget)
            for n in range(1, max_batch + 1):
                parts = s.parts_for(n)
                pos = 0
                for size in parts:
                    take = min(size, n - pos)
                    pos += take
                    assert s.cover_one(take) == size, (n, parts)
                assert pos == n

    def test_cost_functions_agree(self):
        s = ServeSched(4, max_wait_s=0.01)
        area = 64 * 64
        # a launch of cover_one(v) slots realizes exactly the predicted px
        for v in range(1, 5):
            assert s.predicted_cost_px(area, v) == \
                s.realized_cost_px(area, s.cover_one(v))


# -- priced flush deadlines -----------------------------------------------
class TestFlushPricing:
    def make(self, max_batch=4, max_wait_s=0.1, **kw):
        return ServeSched(max_batch, max_wait_s=max_wait_s, **kw)

    def test_full_group_flushes_now(self):
        s = self.make()
        assert s.flush_at("k", 4, t0=0.0, t_last=0.0, now=5.0) <= 5.0

    def test_cold_start_is_the_timer(self):
        # no arrival-rate evidence: the priced deadline IS t0 + max_wait
        s = self.make()
        assert s.flush_at("k", 1, t0=1.0, t_last=1.0, now=1.0) == \
            pytest.approx(1.1)

    def test_deadline_slack_bounds_the_wait(self):
        s = self.make(max_wait_s=10.0)
        at = s.flush_at("k", 1, t0=0.0, t_last=0.0, now=0.0,
                        deadline_ts=0.05)
        assert at == pytest.approx(0.05)

    def test_low_rate_flushes_immediately(self):
        # observed gap ~5 s >> the 100 ms window: waiting cannot beat
        # amortization — a lone request flushes NOW, not at the timer
        s = self.make()
        for i in range(4):
            s.observe_arrival("k", 5.0 * i)
        now = 20.0
        assert s.flush_at("k", 1, t0=now, t_last=now, now=now) == now

    def test_fast_rate_waits_for_the_next_arrival(self):
        # observed gap 10 ms inside a 100 ms window: wait ~2 gaps past
        # the last arrival, bounded by the window
        s = self.make()
        for i in range(5):
            s.observe_arrival("k", 0.01 * i)
        t_last = 0.04
        at = s.flush_at("k", 1, t0=t_last, t_last=t_last, now=t_last)
        assert t_last < at <= t_last + 0.1
        assert at == pytest.approx(t_last + 2 * 0.01, rel=0.3)

    def test_no_gain_flushes_now(self):
        # menu (4,2,1): a group of 2 is an exact menu fit and C(2)+C(1)
        # == C(3), so waiting saves nothing — flush immediately
        s = self.make()
        for i in range(5):
            s.observe_arrival("k", 0.01 * i)
        assert s.coalesce_gain(2) <= 1e-12
        now = 0.05
        assert s.flush_at("k", 2, t0=now, t_last=now, now=now) == now

    def test_timer_policy_ignores_pricing(self):
        s = self.make(priced_flush=False)
        for i in range(5):
            s.observe_arrival("k", 5.0 * i)
        assert s.flush_at("k", 1, t0=100.0, t_last=100.0, now=100.0) == \
            pytest.approx(100.1)


# -- the batcher on the core ----------------------------------------------
class TestBatcherWithCore:
    def make(self, dispatch, *, max_batch=4, max_wait_ms=100.0,
             menu_budget=3, priced=True):
        from can_tpu.serve import BoundedRequestQueue, MicroBatcher
        from can_tpu.sched import ServeSched

        clock = FakeClock()
        q = BoundedRequestQueue(64, clock=clock)
        sched = ServeSched(max_batch, max_wait_s=max_wait_ms / 1e3,
                           menu_budget=menu_budget, priced_flush=priced)
        b = MicroBatcher(q, dispatch, max_batch=max_batch,
                         max_wait_ms=max_wait_ms, clock=clock, sched=sched)
        return q, b, clock

    @staticmethod
    def req(h=64, w=64, clock=None, deadline_s=None):
        from can_tpu.serve import ServeRequest

        return ServeRequest(np.zeros((h, w, 3), np.float32),
                            deadline_s=deadline_s, clock=clock)

    def test_partial_flush_launches_exact_menu_size(self):
        calls = []

        def d(bucket, batch, requests):
            calls.append(batch.image.shape[0])
            for r in requests:
                r.reject("error", "test")

        q, b, clock = self.make(d, max_batch=4)  # menu (4, 2, 1)
        q.offer(self.req(clock=clock))
        q.offer(self.req(clock=clock))
        b.intake()
        clock.t = 0.2
        b.poll(clock.t)
        assert calls == [2]  # a 2-slot program, not max_batch=4

    def test_flush_covers_with_multiple_parts(self):
        calls = []

        def d(bucket, batch, requests):
            calls.append((batch.image.shape[0], len(requests)))
            for r in requests:
                r.reject("error", "test")

        q, b, clock = self.make(d, max_batch=4)
        for _ in range(3):
            q.offer(self.req(clock=clock))
        b.intake()
        clock.t = 0.2
        n = b.poll(clock.t)
        # 3 requests over menu (4,2,1): parts (2,1) — two exact launches
        assert n == 2 and calls == [(2, 2), (1, 1)]

    def test_pump_wakes_at_priced_deadline_not_poll_grain(self):
        # next_wake_s must be the exact earliest flush deadline: with a
        # 2 ms max_wait and the 50 ms default idle poll, a fixed-grain
        # pump would wait 25x the deadline
        q, b, clock = self.make(lambda *a: None, max_wait_ms=2.0)
        q.offer(self.req(clock=clock))
        b.intake()
        assert b.next_wake_s(clock.t) == pytest.approx(0.002)
        # once the rate estimate says "no arrival coming", the deadline
        # is NOW and the wake bound collapses to zero
        for i in range(4):
            b.sched.observe_arrival((64, 64, "float32"), 5.0 * i)
        assert b.next_wake_s(clock.t) == 0.0

    def test_legacy_batcher_unchanged_without_core(self):
        from can_tpu.serve import BoundedRequestQueue, MicroBatcher

        calls = []

        def d(bucket, batch, requests):
            calls.append(batch.image.shape[0])
            for r in requests:
                r.reject("error", "test")

        clock = FakeClock()
        q = BoundedRequestQueue(64, clock=clock)
        b = MicroBatcher(q, d, max_batch=4, max_wait_ms=100.0, clock=clock)
        q.offer(self.req(clock=clock))
        b.intake()
        assert b.next_wake_s(clock.t) == pytest.approx(0.05)  # idle grain
        clock.t = 0.1
        b.poll(clock.t)
        assert calls == [4]  # padded to max_batch, the pre-r14 contract

    def test_sched_max_batch_mismatch_refused(self):
        from can_tpu.serve import BoundedRequestQueue, MicroBatcher
        from can_tpu.sched import ServeSched

        with pytest.raises(ValueError, match="one core, one top size"):
            MicroBatcher(BoundedRequestQueue(4), lambda *a: None,
                         max_batch=8,
                         sched=ServeSched(4, max_wait_s=0.01))


# -- offline plans bit-identical under the extracted core ------------------
class TestOfflineBitIdentical:
    def test_offline_planner_is_the_global_planner(self):
        from can_tpu.data.planner import GlobalPlanner, PlanCostModel

        model = PlanCostModel(menu=(16, 8, 4, 2, 1), launch_cost_px=5e4,
                              max_launch_px=2e6)
        counts = {(512, 512): 37, (768, 512): 11, (1024, 768): 3}
        via_core = offline_planner(model, max_buckets=12).plan(counts)
        direct = GlobalPlanner(model, max_buckets=12).plan(counts)
        assert via_core == direct

    def test_batcher_plans_unchanged(self):
        """The ShardedBatcher routed through sched.offline_planner emits
        byte-identical schedules and predicted==realized stats."""
        from can_tpu.data import ShardedBatcher

        rng = np.random.default_rng(5)
        shapes = [(int(rng.integers(8, 40)) * 8,
                   int(rng.integers(8, 40)) * 8) for _ in range(60)]

        class ShapeOnly:
            def __len__(self):
                return len(shapes)

            def snapped_shape(self, i):
                return shapes[i]

        b = ShardedBatcher(ShapeOnly(), 8, shuffle=True, seed=0,
                           pad_multiple="auto", max_buckets=8,
                           remnant_sizes=True, batch_quantum=1,
                           launch_cost_px=0.05e6)
        stats = b.planner_stats(0)
        assert stats["plan_cost_px"] == stats["realized_cost_px"]
        sched = b.global_schedule(0)
        from can_tpu.data.planner import schedule_coverage

        assert schedule_coverage(sched) == {i: 1
                                            for i in range(len(shapes))}

    def test_committed_golden_plan_reproduces(self):
        """The r8 padding-floor headline must survive the refactor: the
        cost-mode plan at device pricing reproduces the committed
        0.0961 overhead bit-for-bit (the acceptance pin)."""
        with open(os.path.join(REPO, "tests", "fixtures",
                               "PLAN_ABLATION_r08.json")) as f:
            doc = json.load(f)
        headline = doc["headline"]["cost_planner_device_pricing"]
        assert headline["schedule_overhead"] == 0.0961
        # the full reproduction runs in test_planner's acceptance pins;
        # here we pin that the committed artifact is intact and that the
        # core path produced identical plans (test above)


# -- dispatch ordering ----------------------------------------------------
class _Item:
    _seq = iter(range(10_000))

    def __init__(self, *, t_enqueue=0.0, cost_px=1.0, min_deadline=None,
                 redispatches=0):
        self.t_enqueue = t_enqueue
        self.seq = next(self._seq)
        self.cost_px = cost_px
        self.min_deadline = min_deadline
        self.redispatches = redispatches


class TestDispatchOrdering:
    def test_cheapest_first_when_relaxed(self):
        items = [_Item(cost_px=9.0), _Item(cost_px=1.0),
                 _Item(cost_px=5.0)]
        assert pick_work(items, now=0.0) == 1

    def test_deadline_pressure_wins_over_cost(self):
        items = [_Item(cost_px=1.0),
                 _Item(cost_px=100.0, min_deadline=0.3)]
        # the expensive item's deadline is inside the pressure window:
        # it runs first or it expires
        assert pick_work(items, now=0.0, pressure_s=0.5) == 1

    def test_urgent_items_order_edf(self):
        items = [_Item(min_deadline=0.4), _Item(min_deadline=0.1),
                 _Item(min_deadline=0.2)]
        assert pick_work(items, now=0.0, pressure_s=0.5) == 1

    def test_redispatched_batch_is_urgent(self):
        items = [_Item(cost_px=0.5),
                 _Item(cost_px=50.0, redispatches=1)]
        assert pick_work(items, now=0.0) == 1

    def test_starvation_bound(self):
        """An old expensive deadline-less item must not be bypassed
        forever: past starvation_age_s it outranks every fresh cheap
        item."""
        old = _Item(t_enqueue=0.0, cost_px=100.0)
        items = [old] + [_Item(t_enqueue=5.0, cost_px=0.1)
                         for _ in range(10)]
        # young: cheapest fresh item wins
        assert pick_work(items, now=1.0, starvation_age_s=2.0) != 0
        # aged past the bound: the starved item is promoted and wins
        assert pick_work(items, now=5.0, starvation_age_s=2.0) == 0

    def test_expiring_deadline_beats_starved_deadline_less(self):
        """The review-found ordering hole: a deadline-less item promoted
        by age must NOT outrank work that is about to expire — it cannot
        expire itself, only wait one more drain."""
        starved = _Item(t_enqueue=0.0, cost_px=1.0)  # aged, no deadline
        expiring = _Item(t_enqueue=4.9, cost_px=100.0, min_deadline=5.3)
        idx = pick_work([starved, expiring], now=5.0,
                        starvation_age_s=2.0, pressure_s=0.5)
        assert idx == 1

    def test_fifo_tie_break_within_class(self):
        a, b = _Item(cost_px=1.0), _Item(cost_px=1.0)
        assert pick_work([a, b], now=0.0) == 0

    def test_fleet_priced_order_serves_pressured_batch_first(self):
        """White-box: _pop_next_locked under a fake clock orders a
        deadline-pressured batch ahead of cheaper fresh work."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve import ServeRequest
        from can_tpu.serve.fleet import _WorkItem

        clock = FakeClock()

        def item(h, w, deadline_s=None, seq=0):
            img = np.zeros((h, w, 3), np.float32)
            dm = np.zeros((h // 8, w // 8, 1), np.float32)
            batch = pad_batch([(img, dm)], (h, w), 1, [True], 8)
            r = ServeRequest(img, deadline_s=deadline_s, clock=clock)
            return _WorkItem((h, w), batch, [r], t_enqueue=clock.t,
                             seq=seq)

        cheap = item(64, 64, seq=0)
        pressured = item(128, 128, deadline_s=0.2, seq=1)
        idx = pick_work([cheap, pressured], now=0.0, pressure_s=0.5)
        assert idx == 1
        assert pressured.cost_px > cheap.cost_px  # cost alone says cheap


# -- serve end to end: menu warmed, zero new compiles ----------------------
@pytest.fixture(scope="module")
def menu_service():
    import jax

    from can_tpu import obs
    from can_tpu.models import cannet_init
    from can_tpu.serve import CountService, ServeEngine

    params = cannet_init(jax.random.key(0))
    tel = obs.Telemetry()
    engine = ServeEngine(params, telemetry=tel, name="sched_test")
    svc = CountService(engine, max_batch=4, max_wait_ms=2.0,
                       bucket_ladder=((64, 96), (64, 96)), telemetry=tel)
    yield svc, engine


class TestServeMenuEndToEnd:
    def test_zero_new_compiles_under_mixed_traffic(self, menu_service):
        svc, engine = menu_service
        grid = [(h, w) for h in (64, 96) for w in (64, 96)]
        rep = svc.warmup(grid)
        # budget: one program per (bucket, menu size)
        assert rep["compiles"] <= len(grid) * len(svc.sched.menu)
        before = engine.compile_count
        rng = np.random.default_rng(3)
        from can_tpu.serve import prepare_image

        images = [prepare_image(
            (rng.uniform(0, 1, (h, w, 3)) * 255).astype(np.uint8))
            for h, w in [(60, 60), (90, 90), (64, 90), (90, 64)]]
        with svc:
            tickets = [svc.submit(images[i % len(images)])
                       for i in range(24)]
            counts = [t.result(30.0).count for t in tickets]
        assert len(counts) == 24
        # every flush size was a warmed menu size: no new programs
        assert engine.compile_count == before

    def test_serve_batch_carries_sched_economics(self, menu_service):
        """serve.batch events carry padded_slots / fill_pct and the
        predicted==realized cost pair."""
        import jax

        from can_tpu import obs
        from can_tpu.models import cannet_init
        from can_tpu.serve import CountService, ServeEngine, prepare_image

        events = []

        class Sink:
            def emit(self, e):
                events.append(e)

            def close(self):
                pass

        tel = obs.Telemetry([Sink()])
        params = cannet_init(jax.random.key(0))
        engine = ServeEngine(params, telemetry=tel, name="sched_ev")
        svc = CountService(engine, max_batch=4, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        img = prepare_image(
            (np.random.default_rng(0).uniform(0, 1, (64, 64, 3))
             * 255).astype(np.uint8))
        with svc:
            svc.predict(img)
        batches = [e for e in events if e["kind"] == "serve.batch"]
        assert batches
        p = batches[-1]["payload"]
        assert p["padded_slots"] == p["size"] - p["valid"]
        assert p["fill_pct"] == pytest.approx(100.0 * p["valid"]
                                              / p["size"])
        assert p["predicted_cost_px"] == p["realized_cost_px"]

    def test_single_request_fills_its_launch(self, menu_service):
        """The headline: a lone request launches a 1-slot program (fill
        100%), not a max_batch-padded one.  Fresh service around the
        module engine (a closed CountService stays closed)."""
        from can_tpu.serve import CountService, prepare_image

        _, engine = menu_service
        svc = CountService(engine, max_batch=4, max_wait_ms=2.0,
                           bucket_ladder=((64, 96), (64, 96)),
                           telemetry=engine.telemetry)
        img = prepare_image(
            (np.random.default_rng(1).uniform(0, 1, (64, 64, 3))
             * 255).astype(np.uint8))
        with svc:
            res = svc.predict(img)
        assert res.batch_fill == 1.0


# -- AOT staleness on a menu change ---------------------------------------
class TestAotMenuAxis:
    def test_batch_sizes_axis(self, tmp_path, monkeypatch):
        import jax

        from can_tpu.serve.aot import AotBundle, AotStaleError

        dev = jax.devices()[0]
        manifest = {"version": 1, "jax_version": jax.__version__,
                    "platform": dev.platform,
                    "device_kind": dev.device_kind,
                    "serve_dtype": "f32", "ds": 8,
                    "max_batch": 4, "batch_sizes": [4, 2, 1],
                    "bucket_shapes": [[64, 64]],
                    "signature_sha": "s", "programs": []}
        b = AotBundle(str(tmp_path), manifest)
        # matching menu: fine
        b.check(sig_sha="s", serve_dtype="f32", ds=8,
                batch_sizes=(4, 2, 1))
        # changed menu: stale on the batch_sizes axis
        with pytest.raises(AotStaleError) as e:
            b.check(sig_sha="s", serve_dtype="f32", ds=8,
                    batch_sizes=(4, 3, 1))
        assert e.value.axis == "batch_sizes"
        # pre-menu bundle (no batch_sizes key): reads as {max_batch}
        del manifest["batch_sizes"]
        b2 = AotBundle(str(tmp_path), manifest)
        b2.check(sig_sha="s", serve_dtype="f32", ds=8, batch_sizes=(4,))
        with pytest.raises(AotStaleError):
            b2.check(sig_sha="s", serve_dtype="f32", ds=8,
                     batch_sizes=(4, 2))


# -- one-registry audit teeth ---------------------------------------------
class TestAuditRegistry:
    def test_contract_pins_the_menu_programs(self):
        with open(os.path.join(REPO, "PROGRAM_CONTRACTS.json")) as f:
            contract = json.load(f)
        from can_tpu.analysis import hlo_audit as ha

        expected = set(ha.expected_serve_programs())
        contracted = {n for n in contract["programs"]
                      if n.startswith("serve_predict")}
        assert expected == contracted
        assert contract["program_budget"] >= len(ha.PROGRAM_BUILDERS)
        assert contract["generated"]["serve_menu"] == \
            list(ha.serve_menu_sizes())

    def test_menu_change_outside_registry_turns_audit_red(self,
                                                          monkeypatch):
        """The mutation: changing the serve menu anywhere but the
        registry (sched.default_serve_menu + --update) must fail the
        audit with the divergence named."""
        from can_tpu.analysis import hlo_audit as ha
        from can_tpu.sched import core as sched_core

        with open(os.path.join(REPO, "PROGRAM_CONTRACTS.json")) as f:
            contract = json.load(f)
        monkeypatch.setattr(sched_core, "default_serve_menu",
                            lambda mb, budget=3: (mb,))
        monkeypatch.setattr("can_tpu.sched.default_serve_menu",
                            lambda mb, budget=3: (mb,))
        violations = ha.audit_programs(contract)
        assert any(v.invariant == "serve_menu_registry"
                   for v in violations)

    def test_program_budget_enforced(self, monkeypatch):
        from can_tpu.analysis import hlo_audit as ha

        with open(os.path.join(REPO, "PROGRAM_CONTRACTS.json")) as f:
            contract = json.load(f)
        contract["program_budget"] = len(ha.PROGRAM_BUILDERS) - 1
        violations = ha.audit_programs(contract)
        assert any(v.invariant == "program_budget" for v in violations)


# -- prefetch pricing ------------------------------------------------------
class TestPrefetchPricing:
    def test_depth_formula(self):
        # normal batches at bench pricing: the classic double buffer
        assert prefetch_depth(1e6, 0.05e6) == 2
        # tiny launches: overhead dominates, pipeline deepens (clamped)
        assert prefetch_depth(1e4, 0.05e6) == 4
        assert prefetch_depth(1e4, 1e9, hi=4) == 4
        assert prefetch_depth(1e9, 0.0) == 2

    def test_depth_for_batcher(self):
        from can_tpu.data import ShardedBatcher

        shapes = [(64, 64)] * 16

        class ShapeOnly:
            def __len__(self):
                return len(shapes)

            def snapped_shape(self, i):
                return shapes[i]

        b = ShardedBatcher(ShapeOnly(), 4, shuffle=False,
                           launch_cost_px=0.05e6)
        assert prefetch_depth_for(b) in (2, 3, 4)


# -- gauges + report row ---------------------------------------------------
class TestSchedObservability:
    def event(self, **payload):
        return {"ts": 0.0, "kind": "serve.batch", "step": 0, "host_id": 0,
                "payload": payload}

    def test_gauge_sink_sched_metrics(self):
        from can_tpu.obs.exporter import GaugeSink

        g = GaugeSink()
        g.emit(self.event(size=2, valid=2, fill_pct=100.0, padded_slots=0,
                          predicted_cost_px=100.0, realized_cost_px=100.0))
        g.emit(self.event(size=4, valid=1, fill_pct=25.0, padded_slots=3,
                          predicted_cost_px=50.0, realized_cost_px=75.0))
        text = g.render()
        assert "can_tpu_sched_fill_pct 25.0" in text
        assert "can_tpu_sched_padded_slots_total 3" in text
        assert "can_tpu_sched_batches_total 2" in text
        assert "can_tpu_sched_cost_mismatch_total 1" in text

    def test_report_scheduler_row(self):
        from can_tpu.obs.report import format_report, summarize

        events = [
            {"ts": 0.0, "kind": "serve.batch", "step": 0, "host_id": 0,
             "payload": {"size": 2, "valid": 2, "fill_pct": 100.0,
                         "padded_slots": 0, "predicted_cost_px": 100.0,
                         "realized_cost_px": 100.0}},
            {"ts": 0.1, "kind": "serve.request", "step": 0, "host_id": 0,
             "payload": {"latency_s": 0.01}},
        ]
        s = summarize(events)
        assert s["sched_fill_pct"] == 100.0
        assert s["sched_padded_slots"] == 0
        assert s["sched_cost_mismatches"] == 0
        text = format_report(s)
        assert "scheduler" in text and "predicted==realized" in text
