"""Streaming sessions (can_tpu/serve/streams.py): sticky host-side
state, frame-skip admission, and session survival across every fleet
fault.

The contract under test (ISSUE 15 acceptance):

* per-stream session state — count/density EWMA, trend, monotonic frame
  sequence, TTL eviction — lives on the SERVICE host, so quarantine,
  wedge, resurrection, rollout, and autoscale transitions cannot lose
  it (the chaos test drives all of them under sustained streams);
* sticky stream→replica routing is a pick_work PREFERENCE, validated
  against live (index, incarnation) tokens: a pin into a dead replica —
  or an abandoned incarnation of a resurrected one — is re-pinned to a
  live replica and can never starve a stream;
* the degradation ladder (full → frame-skip → reject) is priced by the
  sched core's cost model with hysteresis + a flap-bounding cooldown,
  and every degraded answer is labelled (degraded + staleness);
* requests WITHOUT a stream_id take the exact pre-stream path (HTTP
  body pinned);
* the HTTP body-size cap 413s oversized POSTs on both endpoints;
* the stream fault grammar (stream_burst / frame_gap), the stream.*
  gauges/report rows, the stream_staleness SLO objective, and the
  committed BENCH_STREAM artifact's receipts.
"""

import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from can_tpu import obs
from can_tpu.models import cannet_init
from can_tpu.sched import ServeSched, pick_work
from can_tpu.serve import (
    REJECT_STALE_FRAME,
    STREAM_RUNG_FULL,
    STREAM_RUNG_REJECT,
    STREAM_RUNG_SKIP,
    CountService,
    FleetEngine,
    RejectedError,
    ServeEngine,
    StreamSessionRegistry,
    prepare_image,
    repin_target,
    serve_http,
)
from can_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def params():
    return cannet_init(jax.random.key(0))


@pytest.fixture(scope="module")
def params2():
    return cannet_init(jax.random.key(1))


@pytest.fixture(scope="module")
def engine(params):
    return ServeEngine(params, name="stream_test_predict")


def make_image(h=64, w=64, seed=0):
    rng = np.random.default_rng(seed)
    return prepare_image((rng.uniform(0, 1, (h, w, 3)) * 255)
                         .astype(np.uint8))


def collecting_telemetry():
    events = []
    sink = type("S", (), {"emit": lambda self, e: events.append(e),
                          "close": lambda self: None})()
    return obs.Telemetry(sinks=[sink]), events


def make_registry(clock, *, sched=None, policy="priced", **kw):
    return StreamSessionRegistry(clock=clock, sched=sched, policy=policy,
                                 **kw)


# --- session state unit layer --------------------------------------------
class TestSessionState:
    def test_open_serve_ewma_trend(self):
        clk = FakeClock()
        reg = make_registry(clk)
        assert reg.admit("cam", 1, bucket_hw=(64, 64)).kind == "serve"
        reg.note_completed("cam", 10.0, None, (64, 64), now=0.5)
        clk.t = 1.0
        assert reg.admit("cam", 2, bucket_hw=(64, 64)).kind == "serve"
        reg.note_completed("cam", 20.0, None, (64, 64), now=1.5)
        sess = reg.get("cam")
        # EWMA blends toward the new count, trend is positive
        assert 10.0 < sess.count_ewma < 20.0
        assert sess.trend_per_s > 0
        assert sess.served == 2 and sess.seq == 2

    def test_monotonic_sequence_rejects_dup_and_out_of_order(self):
        clk = FakeClock()
        reg = make_registry(clk)
        assert reg.admit("cam", 5).kind == "serve"
        dup = reg.admit("cam", 5)
        assert dup.kind == "stale" and "5" in dup.detail
        assert reg.admit("cam", 3).kind == "stale"  # out of order
        assert reg.admit("cam", 6).kind == "serve"
        assert reg.get("cam").stale_rejects == 2
        assert reg.get("cam").seq == 6

    def test_no_frame_seq_streams_still_session(self):
        reg = make_registry(FakeClock())
        assert reg.admit("cam", None).kind == "serve"
        assert reg.admit("cam", None).kind == "serve"
        assert reg.get("cam").seq is None

    def test_ttl_eviction_emits_and_drops(self):
        clk = FakeClock()
        tel, events = collecting_telemetry()
        reg = StreamSessionRegistry(ttl_s=10.0, clock=clk, telemetry=tel)
        reg.admit("cam", 1)
        clk.t = 11.0
        assert reg.evict_idle() == 1
        assert reg.active_count() == 0
        ev = [e for e in events if e["kind"] == "stream.session"]
        assert ev[0]["payload"]["state"] == "open"
        assert ev[-1]["payload"]["state"] == "evicted"
        assert ev[-1]["payload"]["active"] == 0
        # a fresh admit opens a NEW session: the old state is gone
        reg.admit("cam", 1)
        assert reg.get("cam").seq == 1

    def test_outstanding_tracks_done_hooks(self):
        from can_tpu.serve.queue import ServeRequest

        clk = FakeClock()
        reg = make_registry(clk)
        reg.admit("cam", 1)
        req = ServeRequest(np.zeros((64, 64, 3), np.float32),
                           deadline_s=None, clock=clk, stream_id="cam",
                           frame_seq=1)
        reg.note_admitted(req)
        assert reg.get("cam").outstanding == 1
        req.reject("deadline", "test")  # rejection ALSO drains
        assert reg.get("cam").outstanding == 0

    def test_density_ewma_follows_fetched_maps(self):
        reg = make_registry(FakeClock())
        reg.admit("cam", 1)
        d1 = np.ones((8, 8, 1), np.float32)
        reg.note_completed("cam", 1.0, d1, (64, 64), now=0.1)
        reg.note_completed("cam", 1.0, 3 * d1, (64, 64), now=0.2)
        sess = reg.get("cam")
        assert sess.density_ewma.shape == (8, 8, 1)
        assert 1.0 < float(sess.density_ewma[0, 0, 0]) < 3.0


# --- the degradation ladder ----------------------------------------------
class TestDegradeLadder:
    def primed(self, clk, *, s_slot=0.025, policy="priced", **kw):
        """Registry with warm drain pricing: sched menu (4,2,1) at the
        default 0.25 launch-cost slots -> one-frame cost =
        s_slot * 1.25 seconds."""
        sched = ServeSched(4, max_wait_s=0.005)
        reg = make_registry(clk, sched=sched, policy=policy, **kw)
        reg.observe_batch((64, 64), s_slot * 4, 4)
        return reg

    def drive(self, reg, clk, gap, n, seq0=0):
        dec = None
        for i in range(n):
            clk.t += gap
            dec = reg.admit("cam", seq0 + i + 1, bucket_hw=(64, 64))
        return dec

    def test_cost_is_the_sched_cores_model(self):
        clk = FakeClock()
        reg = self.primed(clk, s_slot=0.02)
        # cover_one(1)=1 slot + 0.25 launch-cost slots at 20 ms/slot
        assert reg.expected_cost_s((64, 64)) == pytest.approx(0.025)
        # no evidence for an unseen bucket: no pricing, no skipping
        assert reg.expected_cost_s((96, 96)) is None

    def test_sustained_overrun_enters_skip_and_serves_ewma(self):
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0)  # isolate the pricing
        # frame cost 31.25 ms, arrivals every 20 ms: pressure ~1.56 >= 1
        self.drive(reg, clk, 0.020, 4)
        reg.note_completed("cam", 42.0, None, (64, 64))
        dec = self.drive(reg, clk, 0.020, 3, seq0=4)
        assert reg.get("cam").rung == STREAM_RUNG_SKIP
        assert dec.kind == "degrade"
        assert dec.count == pytest.approx(42.0)
        assert dec.staleness_s is not None and dec.staleness_s > 0

    def test_cold_stream_never_skips(self):
        """The skip rung needs an EWMA: a brand-new overloaded stream
        still gets real answers (the only honest ones)."""
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0)
        dec = self.drive(reg, clk, 0.020, 8)
        assert reg.get("cam").rung == STREAM_RUNG_SKIP
        assert dec.kind == "serve"  # no EWMA yet -> full inference

    def test_extreme_overrun_reaches_reject_rung(self):
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0)
        # frame cost 31.25 ms, arrivals every 5 ms: pressure ~6 >= 3
        dec = self.drive(reg, clk, 0.005, 8)
        assert reg.get("cam").rung == STREAM_RUNG_REJECT
        assert dec.kind == "overload"
        assert reg.get("cam").overload_rejects >= 1

    def test_hysteresis_exit_needs_half_the_entry_load(self):
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0)
        self.drive(reg, clk, 0.020, 6)  # pressure ~1.56: skip
        assert reg.get("cam").rung == STREAM_RUNG_SKIP
        # pressure ~0.78 — below entry (1.0) but above exit (0.5):
        # the band holds the rung (no flap at the edge)
        self.drive(reg, clk, 0.040, 8, seq0=6)
        assert reg.get("cam").rung == STREAM_RUNG_SKIP
        # pressure ~0.31 — below exit: back to full
        self.drive(reg, clk, 0.100, 8, seq0=14)
        assert reg.get("cam").rung == STREAM_RUNG_FULL

    def test_flap_bounded_to_one_transition_per_cooldown(self):
        clk = FakeClock()
        tel, events = collecting_telemetry()
        sched = ServeSched(4, max_wait_s=0.005)
        reg = StreamSessionRegistry(clock=clk, sched=sched,
                                    telemetry=tel, cooldown_s=1.0)
        reg.observe_batch((64, 64), 0.1, 4)
        # oscillate hard around the band edges for one second: fast
        # burst (enter pressure) then a long gap (exit pressure), many
        # times — the rung may change AT MOST once per cooldown
        seq = 0
        for _ in range(10):
            for gap in (0.004, 0.004, 0.004, 0.2):
                clk.t += gap
                seq += 1
                reg.admit("cam", seq, bucket_hw=(64, 64))
        transitions = [e for e in events if e["kind"] == "stream.degrade"]
        span = clk.t  # total driven time
        assert len(transitions) <= span / 1.0 + 1
        assert reg.stats()["degrade_transitions"] == len(transitions)

    def test_backlog_pressure_alone_triggers_skip(self):
        """No arrival-rate evidence (gap untrusted) but a deep
        per-stream backlog: outstanding/allowance carries the ladder."""
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0, outstanding_high=4)
        reg.admit("cam", 1, bucket_hw=(64, 64))
        reg.note_completed("cam", 7.0, None, (64, 64))
        sess = reg.get("cam")
        sess.outstanding = 4  # at the allowance: load 1.0 -> skip
        clk.t += 10.0
        dec = reg.admit("cam", 2, bucket_hw=(64, 64))
        assert dec.kind == "degrade"
        assert sess.rung == STREAM_RUNG_SKIP

    def test_overload_reject_does_not_burn_the_frame_seq(self):
        """A load-based reject is 'retry later': the refused frame was
        never answered, so its sequence must NOT be committed — the
        retry passes the gate instead of bouncing 409 forever (review
        r15)."""
        clk = FakeClock()
        reg = self.primed(clk, cooldown_s=0.0)
        self.drive(reg, clk, 0.005, 8)  # pressure ~6: reject rung
        sess = reg.get("cam")
        assert sess.rung == STREAM_RUNG_REJECT
        accepted = sess.seq
        assert accepted < 8  # the refused tail never committed
        clk.t += 0.005
        dec = reg.admit("cam", accepted + 1, bucket_hw=(64, 64))
        assert dec.kind == "overload"
        assert sess.seq == accepted  # still not burned
        # the retry of the same frame is NOT stale — it re-enters the
        # ladder rather than bouncing off the sequence gate
        clk.t += 0.005
        retry = reg.admit("cam", accepted + 1, bucket_hw=(64, 64))
        assert retry.kind != "stale"
        # and once the camera slows below the exit band, the same
        # frame numbers are finally accepted
        self.drive(reg, clk, 0.2, 30, seq0=accepted)
        assert sess.rung == STREAM_RUNG_FULL
        assert sess.seq == accepted + 30

    def test_rollback_seq_uncommits_refused_frame(self):
        clk = FakeClock()
        reg = make_registry(clk)
        dec = reg.admit("cam", 5)
        assert dec.kind == "serve" and reg.get("cam").seq == 5
        # the queue refused frame 5 with nothing to degrade to
        reg.rollback_seq("cam", 5, dec.prior_seq)
        assert reg.get("cam").seq is None
        assert reg.admit("cam", 5).kind == "serve"  # retry passes
        # rollback is a no-op once a later frame advanced the seq
        dec6 = reg.admit("cam", 6)
        reg.rollback_seq("cam", 5, None)
        assert reg.get("cam").seq == 6
        reg.rollback_seq("cam", 6, dec6.prior_seq)
        assert reg.get("cam").seq == 5

    def test_policy_off_never_degrades(self):
        clk = FakeClock()
        reg = self.primed(clk, policy="off", cooldown_s=0.0)
        self.drive(reg, clk, 0.004, 4)
        reg.note_completed("cam", 1.0, None, (64, 64))
        dec = self.drive(reg, clk, 0.004, 8, seq0=4)
        assert dec.kind == "serve"
        assert reg.get("cam").rung == STREAM_RUNG_FULL
        # sequence hygiene still applies with the ladder off
        assert reg.admit("cam", 1).kind == "stale"

    def test_bad_bands_and_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            make_registry(FakeClock(), policy="maybe")
        with pytest.raises(ValueError, match="hysteresis"):
            StreamSessionRegistry(skip_enter=1.0, skip_exit=1.5)


# --- sticky routing ------------------------------------------------------
class _Item:
    _seq = 0

    def __init__(self, *, pin=None, cost=1.0, deadline=None, age=0.0,
                 redispatches=0, now=100.0):
        _Item._seq += 1
        self.seq = _Item._seq
        self.pin = pin
        self.cost_px = cost
        self.min_deadline = deadline
        self.t_enqueue = now - age
        self.redispatches = redispatches


class TestStickyRouting:
    def test_pick_work_prefers_own_pin_in_relaxed_tier(self):
        now = 100.0
        items = [_Item(pin=1, cost=1.0, now=now),
                 _Item(pin=0, cost=5.0, now=now),
                 _Item(pin=None, cost=2.0, now=now)]
        # replica 0 prefers its pin even though it costs more
        assert pick_work(items, now, prefer=0) == 1
        # replica 1 prefers ITS pin; replica 2 (no pins match) takes the
        # unpinned item before items pinned elsewhere
        assert pick_work(items, now, prefer=1) == 0
        assert pick_work(items, now, prefer=2) == 2
        # no preference (single-engine / fifo callers): cheapest wins,
        # exactly the pre-stream ordering
        assert pick_work(items, now) == 0

    def test_pin_never_outranks_deadline_or_starvation(self):
        now = 100.0
        items = [_Item(pin=0, cost=1.0, now=now),
                 _Item(pin=1, deadline=now + 0.1, cost=9.0, now=now)]
        # the expiring item wins even though the puller is replica 0
        assert pick_work(items, now, prefer=0) == 1
        items = [_Item(pin=0, cost=1.0, now=now),
                 _Item(pin=1, cost=9.0, age=5.0, now=now)]
        # the age-promoted item wins over the cheap pinned one
        assert pick_work(items, now, prefer=0) == 1

    def test_repin_target_is_deterministic_and_spread(self):
        live = [0, 1, 2]
        a = repin_target("cam-a", live)
        assert a == repin_target("cam-a", live)  # stable
        targets = {repin_target(f"cam-{i}", live) for i in range(32)}
        assert targets == {0, 1, 2}  # spreads over the live set

    def test_pin_for_validates_and_repins_dead_replica(self):
        from can_tpu.serve.queue import ServeRequest

        clk = FakeClock()
        tel, events = collecting_telemetry()
        reg = StreamSessionRegistry(clock=clk, telemetry=tel)
        reg.admit("cam", 1)
        reg.note_completed("cam", 1.0, None, (64, 64), replica=0,
                           token="pred_r0")
        req = ServeRequest(np.zeros((64, 64, 3), np.float32),
                           deadline_s=None, clock=clk, stream_id="cam")
        # replica 0 alive at its original incarnation: pin holds
        assert reg.pin_for([req], {0: "pred_r0", 1: "pred_r1"}) == 0
        assert not [e for e in events if e["kind"] == "stream.repin"]
        # replica 0 gone (quarantined/wedged/removed): re-pin to a live
        # one — the stream must never wait behind a corpse
        got = reg.pin_for([req], {1: "pred_r1"})
        assert got == 1
        repins = [e for e in events if e["kind"] == "stream.repin"]
        assert len(repins) == 1
        assert repins[0]["payload"]["from_replica"] == 0
        assert repins[0]["payload"]["to_replica"] == 1
        assert reg.get("cam").pin == (1, "pred_r1")

    def test_pin_for_rejects_abandoned_incarnation(self):
        """The repin-vs-resurrection interplay (white-box): a pin into
        replica 0's OLD incarnation must re-pin to the fresh incarnation
        serving under the same index — never match the abandoned
        engine."""
        from can_tpu.serve.queue import ServeRequest

        clk = FakeClock()
        tel, events = collecting_telemetry()
        reg = StreamSessionRegistry(clock=clk, telemetry=tel)
        reg.admit("cam", 1)
        reg.note_completed("cam", 1.0, None, (64, 64), replica=0,
                           token="pred_r0")
        req = ServeRequest(np.zeros((64, 64, 3), np.float32),
                           deadline_s=None, clock=clk, stream_id="cam")
        # replica 0 resurrected under a NEW incarnation name: the stale
        # token fails the match even though the index is live again
        assert reg.pin_for([req], {0: "pred_r0i1"}) == 0
        assert reg.get("cam").pin == (0, "pred_r0i1")
        assert [e for e in events if e["kind"] == "stream.repin"]

    def test_pin_for_majority_vote_and_no_streams(self):
        from can_tpu.serve.queue import ServeRequest

        clk = FakeClock()
        reg = StreamSessionRegistry(clock=clk)
        for sid, rep in (("a", 0), ("b", 1), ("c", 1)):
            reg.admit(sid, 1)
            reg.note_completed(sid, 1.0, None, (64, 64), replica=rep,
                               token=f"pred_r{rep}")
        live = {0: "pred_r0", 1: "pred_r1"}
        reqs = [ServeRequest(np.zeros((4, 4, 3), np.float32),
                             deadline_s=None, clock=clk, stream_id=s)
                for s in ("a", "b", "c")]
        assert reg.pin_for(reqs, live) == 1  # majority
        plain = [ServeRequest(np.zeros((4, 4, 3), np.float32),
                              deadline_s=None, clock=clk)]
        assert reg.pin_for(plain, live) is None
        assert reg.pin_for(reqs, {}) is None  # empty live set


# --- service integration (single engine) ---------------------------------
class TestServiceStreams:
    def make_service(self, engine, **kw):
        tel, events = collecting_telemetry()
        kw.setdefault("queue_capacity", 64)
        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)),
                           telemetry=tel, **kw)
        return svc, events

    def test_stream_round_trip_builds_session(self, engine):
        svc, events = self.make_service(engine)
        svc.warmup([(64, 64)])
        img = make_image()
        with svc:
            r1 = svc.predict(img, stream_id="cam", frame_seq=1,
                             deadline_ms=60_000, timeout=60.0)
            r2 = svc.predict(img, stream_id="cam", frame_seq=2,
                             deadline_ms=60_000, timeout=60.0)
        assert not r1.degraded and not r2.degraded
        assert r1.stream_id == "cam"
        sess = svc.streams.get("cam")
        assert sess.served == 2 and sess.seq == 2
        assert sess.count_ewma == pytest.approx(r1.count, rel=0.5)
        st = svc.stats()["streams"]
        assert st["sessions"] == 1 and st["served_total"] == 2

    def test_duplicate_frame_rejected_typed(self, engine):
        svc, events = self.make_service(engine)
        svc.warmup([(64, 64)])
        img = make_image()
        with svc:
            svc.predict(img, stream_id="cam", frame_seq=3,
                        deadline_ms=60_000, timeout=60.0)
            with pytest.raises(RejectedError) as e:
                svc.predict(img, stream_id="cam", frame_seq=3,
                            deadline_ms=60_000, timeout=60.0)
        assert e.value.reason == REJECT_STALE_FRAME
        assert svc.stats()["rejected"] == 1
        rejects = [e for e in events if e["kind"] == "serve.reject"]
        assert rejects[-1]["payload"]["reason"] == REJECT_STALE_FRAME

    def test_skip_rung_serves_labelled_ewma_without_launch(self, engine):
        svc, events = self.make_service(engine)
        svc.warmup([(64, 64)])
        img = make_image()
        with svc:
            fresh = svc.predict(img, stream_id="cam", frame_seq=1,
                                deadline_ms=60_000, timeout=60.0)
            # force the skip rung (the ladder units prove the pricing;
            # here we prove the SERVICE path: no launch, labelled
            # degraded, staleness measured, batches unchanged)
            sess = svc.streams.get("cam")
            sess.rung = STREAM_RUNG_SKIP
            sess.rung_since = svc._clock()  # cooldown holds the rung
            batches_before = svc.stats()["batches"]
            deg = svc.predict(img, stream_id="cam", frame_seq=2,
                              deadline_ms=60_000, timeout=60.0)
        assert deg.degraded and not fresh.degraded
        assert deg.count == pytest.approx(sess.count_ewma)
        assert deg.staleness_s is not None and deg.staleness_s >= 0
        assert svc.stats()["batches"] == batches_before  # no launch
        assert svc.stats()["degraded"] == 1
        ev = [e for e in events if e["kind"] == "serve.request"
              and e["payload"].get("degraded")]
        assert len(ev) == 1
        assert ev[0]["payload"]["stream"] == "cam"
        assert "staleness_s" in ev[0]["payload"]

    def test_queue_refusal_degrades_instead_of_rejecting(self, engine):
        """The headline behaviour: a stream with an EWMA falls back to
        it when the queue says queue_full/backpressure — where a
        stateless client gets the undifferentiated reject."""
        svc, events = self.make_service(engine, queue_capacity=2)
        # prime the session EWMA without running the batcher
        svc.streams.admit("cam", 1, bucket_hw=(64, 64))
        svc.streams.note_completed("cam", 33.0, None, (64, 64))
        img = make_image()
        # batcher NOT started: the queue fills and stays full
        t1 = svc.submit(img, stream_id="cam", frame_seq=2)
        t2 = svc.submit(img, stream_id="cam", frame_seq=3)
        assert not t1.done and not t2.done  # queued
        t3 = svc.submit(img, stream_id="cam", frame_seq=4)
        res = t3.result(timeout=1.0)
        assert res.degraded and res.count == pytest.approx(33.0)
        ev = [e for e in events if e["kind"] == "serve.request"
              and e["payload"].get("degraded")]
        assert ev and ev[0]["payload"]["fallback"] == "queue_full"
        # a stateless request at the same door still gets the reject
        with pytest.raises(RejectedError):
            svc.submit(img).result(timeout=1.0)
        svc.queue.close()

    def test_queue_reject_without_ewma_releases_the_seq(self, engine):
        """A cold stream's frame refused by the full queue (no EWMA to
        degrade to) gets the typed reject AND its retry passes the
        sequence gate — the 503'd frame was never answered (review
        r15)."""
        svc, _ = self.make_service(engine, queue_capacity=1)
        img = make_image()
        # batcher not started: the queue stays full
        svc.submit(img, stream_id="cam", frame_seq=1)
        t = svc.submit(img, stream_id="cam", frame_seq=2)
        with pytest.raises(RejectedError) as e:
            t.result(timeout=1.0)
        assert e.value.reason == "queue_full"
        # frame 2 un-committed: the seq rolled back to frame 1's
        assert svc.streams.get("cam").seq == 1
        retry = svc.submit(img, stream_id="cam", frame_seq=2)
        assert retry._request._reject is None or \
            retry._request._reject.reason != REJECT_STALE_FRAME
        svc.queue.close()

    def test_frame_seq_without_stream_id_raises(self, engine):
        svc, _ = self.make_service(engine)
        with pytest.raises(ValueError, match="stream_id"):
            svc.submit(make_image(), frame_seq=3)

    def test_degrade_policy_off_keeps_rejects(self, engine):
        svc, _ = self.make_service(engine, queue_capacity=2,
                                   degrade_policy="off")
        svc.streams.admit("cam", 1, bucket_hw=(64, 64))
        svc.streams.note_completed("cam", 33.0, None, (64, 64))
        img = make_image()
        svc.submit(img, stream_id="cam", frame_seq=2)
        svc.submit(img, stream_id="cam", frame_seq=3)
        t = svc.submit(img, stream_id="cam", frame_seq=4)
        with pytest.raises(RejectedError) as e:
            t.result(timeout=1.0)
        assert e.value.reason == "queue_full"
        svc.queue.close()


# --- bit-compatibility of the no-stream path -----------------------------
class TestNoStreamBitCompat:
    def test_stateless_submit_touches_no_session_state(self, engine):
        tel, events = collecting_telemetry()
        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        with svc:
            res = svc.predict(make_image(), deadline_ms=60_000,
                              timeout=60.0)
        assert res.degraded is False
        assert res.staleness_s is None and res.stream_id is None
        assert svc.streams.active_count() == 0
        assert svc.stats()["streams"]["sessions"] == 0
        assert not [e for e in events
                    if e["kind"].startswith("stream.")]

    def test_http_body_without_stream_id_is_exactly_pre_stream(
            self, engine):
        """The wire contract pin: a no-stream POST /predict response
        carries EXACTLY the pre-PR keys — no degraded/staleness leak —
        while a stream request adds the labelled fields."""
        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)])
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            try:
                buf = io.BytesIO()
                np.save(buf, np.zeros((64, 64, 3), np.uint8))
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?deadline_ms=60000",
                    data=buf.getvalue(), method="POST")
                plain = json.loads(urllib.request.urlopen(r).read())
                assert set(plain) == {"count", "latency_ms", "bucket",
                                      "batch_fill", "trace_id",
                                      "queue_wait_ms"}
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?deadline_ms=60000"
                    f"&stream_id=cam&frame_seq=1",
                    data=buf.getvalue(), method="POST")
                stream = json.loads(urllib.request.urlopen(r).read())
                assert stream["degraded"] is False
                assert set(stream) == set(plain) | {"degraded"}
                # duplicate frame over HTTP: 409, reason named
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?deadline_ms=60000"
                    f"&stream_id=cam&frame_seq=1",
                    data=buf.getvalue(), method="POST")
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(r)
                assert e.value.code == 409
                body = json.loads(e.value.read())
                assert body["reason"] == REJECT_STALE_FRAME
                # frame_seq without stream_id is a client error
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?frame_seq=2",
                    data=buf.getvalue(), method="POST")
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(r)
                assert e.value.code == 400
            finally:
                httpd.shutdown()
                httpd.server_close()


# --- HTTP body-size cap (the DoS satellite) ------------------------------
class TestBodyCap:
    def test_413_on_both_endpoints_at_the_boundary(self, engine):
        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)),
                           max_body_mb=0.02)  # ~20 KiB cap
        svc.warmup([(64, 64)])
        cap = svc.max_body_bytes
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            try:
                # one byte OVER the cap: refused with the limit named,
                # on /predict AND /rollout, before the body is read
                for path in ("/predict", "/rollout"):
                    r = urllib.request.Request(
                        f"http://127.0.0.1:{port}{path}",
                        data=b"x" * (cap + 1), method="POST")
                    with pytest.raises(urllib.error.HTTPError) as e:
                        urllib.request.urlopen(r)
                    assert e.value.code == 413, path
                    assert "max-body-mb" in json.loads(
                        e.value.read())["error"]
                # exactly AT the cap: not a 413 (the small valid image
                # round-trips; /rollout then fails on wiring, not size)
                buf = io.BytesIO()
                np.save(buf, np.zeros((64, 64, 3), np.uint8))
                body = buf.getvalue()
                assert len(body) <= cap
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?deadline_ms=60000",
                    data=body, method="POST")
                assert "count" in json.loads(
                    urllib.request.urlopen(r).read())
            finally:
                httpd.shutdown()
                httpd.server_close()

    def test_bad_cap_rejected(self, engine):
        with pytest.raises(ValueError, match="max_body_mb"):
            CountService(engine, max_body_mb=0)

    def test_negative_and_malformed_content_length_are_400(self, engine):
        """A negative Content-Length would make ``rfile.read(-1)`` wait
        for EOF on a keep-alive socket — a handler thread hang per
        request, the DoS the cap exists to close (review r15); a
        malformed one must be a 400, not a dropped connection."""
        import http.client

        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)])
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            try:
                for path in ("/predict", "/rollout"):
                    for bogus in ("-1", "abc"):
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=5.0)
                        conn.putrequest("POST", path)
                        conn.putheader("Content-Length", bogus)
                        conn.endheaders()
                        # the server must ANSWER (no read-until-EOF
                        # hang) with a client error
                        resp = conn.getresponse()
                        assert resp.status == 400, (path, bogus)
                        resp.read()
                        conn.close()
            finally:
                httpd.shutdown()
                httpd.server_close()


# --- fault grammar (stream_burst / frame_gap) ----------------------------
class TestStreamFaults:
    def test_directives_fire_once_and_validate(self):
        inj = faults.FaultInjector({"faults": [
            {"kind": "stream_burst", "stream": "cam0", "frame": 3,
             "burst": 5},
            {"kind": "frame_gap", "stream": "cam1", "frame": 2,
             "mode": "reorder"}]})
        assert inj.on_stream_frame(stream="cam0", frame=1) is None
        d = inj.on_stream_frame(stream="cam0", frame=3)
        assert d == {"kind": "stream_burst", "burst": 5}
        assert inj.on_stream_frame(stream="cam0", frame=3) is None  # once
        d = inj.on_stream_frame(stream="cam1", frame=2)
        assert d == {"kind": "frame_gap", "mode": "reorder"}
        assert len(inj.fired) == 2
        with pytest.raises(ValueError, match="dup|reorder"):
            faults.FaultInjector({"faults": [
                {"kind": "frame_gap", "mode": "sideways"}]})

    def test_env_gated(self, monkeypatch):
        monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
        assert faults.active_injector() is None

    def test_frame_gap_through_the_service_gate(self, engine,
                                                monkeypatch):
        """The grammar composes with the session's sequence gate: a
        frame_gap dup delivery is REJECTED stale, the stream never
        double-serves, and the driver-side burst grammar parses from
        the env trigger like every other fault kind."""
        monkeypatch.setenv(faults.FAULTS_ENV, json.dumps({"faults": [
            {"kind": "frame_gap", "stream": "cam", "frame": 2,
             "mode": "dup"}]}))
        monkeypatch.setattr(faults, "_CACHED", None)
        monkeypatch.setattr(faults, "_CACHED_SPEC", None)
        svc = CountService(engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)])
        img = make_image()
        seqs = {0: 0}
        served = stale = 0
        with svc:
            for f in range(4):
                d = faults.active_injector().on_stream_frame(
                    stream="cam", frame=f + 1)
                sends = []
                if d is not None and d["kind"] == "frame_gap":
                    sends.append(seqs[0])  # dup: re-send the last seq
                seqs[0] += 1
                sends.append(seqs[0])
                for fs in sends:
                    try:
                        svc.predict(img, stream_id="cam", frame_seq=fs,
                                    deadline_ms=60_000, timeout=60.0)
                        served += 1
                    except RejectedError as e:
                        assert e.reason == REJECT_STALE_FRAME
                        stale += 1
        assert served == 4 and stale == 1
        assert svc.streams.get("cam").seq == 4  # monotonic throughout


# --- gauges + report + SLO ------------------------------------------------
class TestStreamObservability:
    def test_event_kinds_declared(self):
        from can_tpu.obs.bus import EVENT_KINDS

        for k in ("stream.session", "stream.degrade", "stream.repin"):
            assert k in EVENT_KINDS

    def test_gauge_sink_stream_kinds(self):
        sink = obs.GaugeSink()
        sink.emit({"kind": "stream.session",
                   "payload": {"state": "open", "active": 3}})
        sink.emit({"kind": "stream.session",
                   "payload": {"state": "evicted", "active": 2}})
        sink.emit({"kind": "stream.degrade",
                   "payload": {"rung": "skip", "from_rung": "full"}})
        sink.emit({"kind": "stream.repin",
                   "payload": {"stream": "cam", "from_replica": 0,
                               "to_replica": 1}})
        sink.emit({"kind": "serve.request",
                   "payload": {"degraded": True, "staleness_s": 0.4}})
        sink.emit({"kind": "serve.request",
                   "payload": {"latency_s": 0.1}})  # fresh: no count
        text = sink.render()
        assert "can_tpu_stream_sessions 2" in text
        assert "can_tpu_stream_evictions_total 1" in text
        assert 'can_tpu_stream_degrade_total{rung="skip"} 1' in text
        assert "can_tpu_stream_repins_total 1" in text
        assert "can_tpu_stream_degraded_total 1" in text
        assert "can_tpu_stream_staleness_s 0.4" in text

    def test_report_streams_row(self):
        from can_tpu.obs.report import format_report, summarize

        events = [
            {"ts": 1.0, "kind": "stream.session",
             "payload": {"state": "open", "active": 2}},
            {"ts": 2.0, "kind": "serve.request",
             "payload": {"latency_s": 0.1}},
            {"ts": 3.0, "kind": "serve.request",
             "payload": {"degraded": True, "staleness_s": 0.7,
                         "latency_s": 0.001}},
            {"ts": 4.0, "kind": "stream.degrade",
             "payload": {"rung": "skip", "from_rung": "full"}},
            {"ts": 5.0, "kind": "stream.repin",
             "payload": {"stream": "cam", "from_replica": 0,
                         "to_replica": 1}},
            {"ts": 6.0, "kind": "stream.session",
             "payload": {"state": "evicted", "active": 1}},
        ]
        s = summarize(events)
        assert s["stream_sessions"] == 1
        assert s["stream_degraded"] == 1
        assert s["stream_staleness_p95_s"] == pytest.approx(0.7)
        assert s["stream_repins"] == 1 and s["stream_evictions"] == 1
        assert s["stream_degrade_transitions"] == {"skip": 1}
        text = format_report(s)
        assert "streams" in text and "repins=1" in text

    def test_slo_stream_staleness_objective(self):
        """The committed spec's stream_staleness objective grades a
        bundle ring: fresh requests (no staleness_s) are not sampled,
        a stale-EWMA run burns through the budget and pages."""
        from can_tpu.obs.slo import grade_events, load_slo_spec

        spec = load_slo_spec(os.path.join(REPO, "slo_spec.json"))
        names = [o.name for o in spec.objectives]
        assert "stream_staleness" in names
        obj = next(o for o in spec.objectives
                   if o.name == "stream_staleness")

        def ring(staleness):
            evs = []
            for i in range(400):
                p = {"latency_s": 0.05}
                if i % 2:  # half the answers are degraded
                    p = {"degraded": True, "staleness_s": staleness,
                         "latency_s": 0.001}
                evs.append({"ts": float(i), "kind": "serve.request",
                            "step": i, "host_id": 0, "payload": p})
            return evs

        ok = grade_events(ring(obj.threshold / 2), spec)
        assert not [v for v in ok["violations"]
                    if v["objective"] == "stream_staleness"]
        # fresh answers were never sampled into the objective
        assert ok["objectives"]["stream_staleness"]["samples"] == 200
        bad = grade_events(ring(obj.threshold * 2), spec)
        viol = [v for v in bad["violations"]
                if v["objective"] == "stream_staleness"]
        assert viol and viol[0]["kind"] == "fast_burn"

    def test_slo_report_cli_grades_staleness_ring(self, tmp_path):
        """tools/slo_report.py end to end on a ring JSONL (the bundle
        layout): exit 1 naming stream_staleness on a stale run."""
        ring = tmp_path / "ring.jsonl"
        with open(ring, "w") as f:
            for i in range(400):
                p = ({"degraded": True, "staleness_s": 99.0,
                      "latency_s": 0.001} if i % 2
                     else {"latency_s": 0.05})
                f.write(json.dumps({"ts": float(i),
                                    "kind": "serve.request", "step": i,
                                    "host_id": 0, "payload": p}) + "\n")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools/slo_report.py"),
             str(ring), "--spec", os.path.join(REPO, "slo_spec.json")],
            capture_output=True, text=True)
        assert proc.returncode == 1
        assert "stream_staleness" in proc.stdout


# --- chaos acceptance -----------------------------------------------------
class TestStreamChaos:
    def _with_faults(self, monkeypatch, schedule):
        monkeypatch.setenv(faults.FAULTS_ENV, json.dumps(schedule))
        monkeypatch.setattr(faults, "_CACHED", None)
        monkeypatch.setattr(faults, "_CACHED_SPEC", None)

    def test_sessions_survive_crash_resurrect_rollout_and_scale(
            self, params, params2, monkeypatch):
        """ISSUE 15 acceptance: N sustained synthetic streams through a
        seeded replica crash -> probation -> resurrection, a blue/green
        rollout, and an autoscale down/up cycle — zero session-state
        loss, zero stuck streams, monotonic per-stream sequences, and
        bounded staleness on every degraded answer."""
        self._with_faults(monkeypatch, {"faults": [
            {"kind": "replica_crash", "replica": 0, "batch": 2}]})
        tel, events = collecting_telemetry()
        fleet = FleetEngine(params, replicas=2, telemetry=tel,
                            name="stream_chaos", self_heal=False,
                            probe_cooldown_s=0.05, probe_jitter=0.0)
        svc = CountService(fleet, max_batch=2, max_wait_ms=1.0,
                           queue_capacity=256,
                           bucket_ladder=((64,), (64,)), telemetry=tel,
                           menu_budget=1, flush_policy="timer")
        svc.warmup([(64, 64)])
        img = make_image()
        streams = [f"cam{k}" for k in range(4)]
        seqs = {s: 0 for s in streams}
        staleness_seen = []

        def send_round(rounds=2):
            tickets = []
            for _ in range(rounds):
                for s in streams:
                    seqs[s] += 1
                    tickets.append((s, svc.submit(
                        img, stream_id=s, frame_seq=seqs[s],
                        deadline_ms=120_000)))
            for s, t in tickets:
                res = t.result(timeout=120.0)  # zero stuck streams
                if res.degraded:
                    assert res.staleness_s is not None
                    assert res.staleness_s < 60.0  # bounded
                    staleness_seen.append(res.staleness_s)

        with svc:
            # phase 1: establish all four sessions, then the seeded
            # crash fires on replica 0's 2nd batch -> quarantine, the
            # in-flight batch redispatches, nothing is lost
            send_round(3)
            t0 = time.time()
            while fleet.live_replicas() > 1 and time.time() - t0 < 30:
                send_round(1)
            assert fleet.live_replicas() == 1  # quarantined
            created = {s: svc.streams.get(s).created_ts for s in streams}
            # phase 2: streams continue on the survivor (any pin into
            # the dead replica re-pins live)
            send_round(2)
            # phase 3: resurrection at a fresh incarnation
            t0 = time.time()
            while fleet.live_replicas() < 2 and time.time() - t0 < 60:
                fleet.maintenance_tick()
                fleet.join_probes(timeout_s=60.0)
                time.sleep(0.02)
            assert fleet.live_replicas() == 2
            send_round(2)
            # phase 4: blue/green rollout under the same streams
            report = svc.rollout(params2)
            assert report["generation"] == 1
            send_round(2)
            # phase 5: autoscale down then up
            fleet.remove_replica(reason="chaos")
            send_round(2)
            fleet.add_replica(reason="chaos")
            send_round(2)
            # zero session-state loss: the SAME session objects carried
            # through every fault (creation timestamps unchanged), and
            # every accepted frame is accounted for
            for s in streams:
                sess = svc.streams.get(s)
                assert sess.created_ts == created[s]
                assert sess.seq == seqs[s]  # monotonic, nothing skipped
                assert sess.served + sess.degraded == seqs[s]
            # monotonic sequence: a duplicate is refused even now
            with pytest.raises(RejectedError) as e:
                svc.predict(img, stream_id="cam0", frame_seq=seqs["cam0"],
                            deadline_ms=60_000, timeout=60.0)
            assert e.value.reason == REJECT_STALE_FRAME
        # the fault fired exactly once; the fleet healed; sessions all
        # live; no admitted request was ever lost
        st = svc.stats()
        assert st["streams"]["sessions"] == 4
        assert st["streams"]["stale_rejects_total"] == 1
        kinds = [e["kind"] for e in events]
        assert kinds.count("fleet.resurrect") == 1
        assert kinds.count("fleet.rollout") == 1
        assert "fleet.scale" in kinds
        inj = faults.active_injector()
        assert inj is not None and len(inj.fired) == 1

    def test_pinned_stream_never_starves_behind_dead_replica(
            self, params, monkeypatch):
        """The routing acceptance pin: pin a stream to replica 0, kill
        replica 0, keep streaming — every frame still resolves (repin
        fired, preference never excluded the survivor)."""
        self._with_faults(monkeypatch, {"faults": [
            {"kind": "replica_crash", "replica": 0, "batch": 1}]})
        tel, events = collecting_telemetry()
        fleet = FleetEngine(params, replicas=2, telemetry=tel,
                            name="stream_pin", self_heal=False)
        svc = CountService(fleet, max_batch=2, max_wait_ms=1.0,
                           queue_capacity=256,
                           bucket_ladder=((64,), (64,)), telemetry=tel,
                           menu_budget=1, flush_policy="timer")
        svc.warmup([(64, 64)])
        img = make_image()
        with svc:
            # force the pin onto replica 0's CURRENT incarnation, then
            # stream until the seeded crash takes replica 0 down
            svc.predict(img, stream_id="cam", frame_seq=1,
                        deadline_ms=120_000, timeout=120.0)
            sess = svc.streams.get("cam")
            sess.pin = (0, fleet.replicas[0].engine.name)
            n = 1
            t0 = time.time()
            while fleet.live_replicas() > 1 and time.time() - t0 < 30:
                n += 1
                svc.predict(img, stream_id="cam", frame_seq=n,
                            deadline_ms=120_000, timeout=120.0)
            assert fleet.live_replicas() == 1
            # the stream keeps flowing through the survivor: no starve
            for _ in range(4):
                n += 1
                res = svc.predict(img, stream_id="cam", frame_seq=n,
                                  deadline_ms=120_000, timeout=120.0)
                assert res.degraded is False
        repins = [e for e in events if e["kind"] == "stream.repin"]
        assert repins and repins[0]["payload"]["from_replica"] == 0
        live_after = {i for i, _ in fleet.live_tokens().items()}
        assert svc.streams.get("cam").pin[0] in live_after
        assert svc.stats()["rejected"] == 0
