"""Online serving subsystem (can_tpu/serve): queue, batcher, engine,
service, HTTP, telemetry.

The contract under test (ISSUE 2 acceptance):

* every submitted request RESOLVES or is REJECTED with a typed reason —
  never hangs;
* XLA compile count == distinct (bucket, dtype) programs, all paid in
  warmup, none during traffic;
* a served count is bit-for-bit what ``evaluate()`` computes offline for
  the same image and params (offline/online parity);
* flush policy: full batch flushes immediately, partial batches flush at
  max_wait, buckets never mix shapes or dtypes;
* backpressure sheds load with hysteresis; deadlines reject, not zombify.
"""

import io
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu import obs
from can_tpu.data import (
    CrowdDataset,
    ShardedBatcher,
    make_synthetic_dataset,
    snap_to_bucket,
)
from can_tpu.models import cannet_init
from can_tpu.serve import (
    REJECT_BACKPRESSURE,
    REJECT_DEADLINE,
    REJECT_ERROR,
    REJECT_QUEUE_FULL,
    REJECT_SHUTDOWN,
    BoundedRequestQueue,
    CountService,
    MicroBatcher,
    RejectedError,
    ServeEngine,
    ServeRequest,
    prepare_image,
    serve_http,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def req(h=64, w=64, deadline_s=None, clock=None, dtype=np.float32):
    img = np.zeros((h, w, 3), dtype)
    return ServeRequest(img, deadline_s=deadline_s,
                        clock=clock or (lambda: 0.0))


class TestQueue:
    def test_fifo_admit_and_drain(self):
        q = BoundedRequestQueue(4)
        rs = [req(), req()]
        assert all(q.offer(r) is None for r in rs)
        assert q.depth() == 2
        live, expired = q.drain()
        assert live == rs and expired == []
        assert q.depth() == 0

    def test_capacity_rejects_queue_full(self):
        q = BoundedRequestQueue(2)
        assert q.offer(req()) is None
        assert q.offer(req()) is None
        r = req()
        assert q.offer(r) == REJECT_QUEUE_FULL
        assert r.done
        with pytest.raises(RejectedError) as e:
            r.wait(0)
        assert e.value.reason == REJECT_QUEUE_FULL

    def test_backpressure_hysteresis_on_outstanding(self):
        """Shedding keys on OUTSTANDING (admitted, unresolved) requests —
        draining the waiting queue into the batcher must NOT end it; only
        resolutions drain load, and shedding persists until the low_water
        band (no admit/timeout oscillation at the mark)."""
        from can_tpu.serve import ServeResult

        q = BoundedRequestQueue(16, high_water=4, low_water=2)
        admitted = [req() for _ in range(4)]
        for r in admitted:
            assert q.offer(r) is None
        assert q.outstanding() == 4
        assert q.offer(req()) == REJECT_BACKPRESSURE
        assert q.shedding
        # the batcher empties the queue — load is unchanged, still shed
        live, _ = q.drain()
        assert len(live) == 4 and q.depth() == 0
        assert q.shedding
        assert q.offer(req()) == REJECT_BACKPRESSURE
        # one resolution: outstanding 3 > low_water 2 — still shedding
        res = ServeResult(count=0.0, density=None, bucket_hw=(64, 64),
                          batch_fill=1.0, latency_s=0.0)
        admitted[0].resolve(res)
        assert q.outstanding() == 3
        assert q.offer(req()) == REJECT_BACKPRESSURE
        # down to the band: recovered
        admitted[1].resolve(res)
        assert q.outstanding() == 2
        assert not q.shedding
        assert q.offer(req()) is None

    def test_drain_splits_expired(self):
        clock = FakeClock()
        q = BoundedRequestQueue(8, clock=clock)
        fresh = req(deadline_s=10.0, clock=clock)
        stale = req(deadline_s=0.5, clock=clock)
        q.offer(fresh)
        q.offer(stale)
        clock.t = 1.0
        live, expired = q.drain()
        assert live == [fresh] and expired == [stale]

    def test_close_stops_admission(self):
        q = BoundedRequestQueue(4)
        q.offer(req())
        leftovers = q.close()
        assert len(leftovers) == 1
        r = req()
        assert q.offer(r) == REJECT_SHUTDOWN

    def test_wait_timeout_is_typed_not_hang(self):
        r = req()
        with pytest.raises(RejectedError):
            r.wait(0.01)


class CollectDispatch:
    """Records flushed (bucket, batch, requests) and resolves requests."""

    def __init__(self, fail=False):
        self.calls = []
        self.fail = fail

    def __call__(self, bucket_hw, batch, requests):
        if self.fail:
            raise RuntimeError("boom")
        self.calls.append((bucket_hw, batch, requests))
        from can_tpu.serve import ServeResult

        for r in requests:
            r.resolve(ServeResult(count=0.0, density=None,
                                  bucket_hw=bucket_hw, batch_fill=0.0,
                                  latency_s=0.0))


class TestBatcherFlush:
    """Flush-trigger matrix with a fake clock and no device work."""

    def make(self, dispatch, *, max_batch=4, max_wait_ms=100.0, ladder=None):
        clock = FakeClock()
        q = BoundedRequestQueue(64, clock=clock)
        b = MicroBatcher(q, dispatch, max_batch=max_batch,
                         max_wait_ms=max_wait_ms, bucket_ladder=ladder,
                         clock=clock)
        return q, b, clock

    def test_flush_on_max_batch_is_immediate(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=3)
        for _ in range(3):
            q.offer(req(64, 64, clock=clock))
        assert b.intake() == 1  # no clock advance needed
        (bucket, batch, requests), = d.calls
        assert bucket == (64, 64)
        assert batch.image.shape == (3, 64, 64, 3)
        assert batch.sample_mask.tolist() == [1.0, 1.0, 1.0]

    def test_partial_batch_waits_then_flushes_on_max_wait(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=4, max_wait_ms=100.0)
        q.offer(req(64, 64, clock=clock))
        q.offer(req(64, 64, clock=clock))
        b.intake()
        assert b.poll(clock.t) == 0 and not d.calls  # not due yet
        clock.t = 0.099
        assert b.poll(clock.t) == 0
        clock.t = 0.1
        assert b.poll(clock.t) == 1
        (_, batch, requests), = d.calls
        # static shape: padded to max_batch with dead fill slots
        assert batch.image.shape == (4, 64, 64, 3)
        assert batch.sample_mask.tolist() == [1.0, 1.0, 0.0, 0.0]
        assert len(requests) == 2

    def test_mixed_buckets_group_independently(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=2,
                                ladder=((64, 96), (64, 96)))
        q.offer(req(64, 64, clock=clock))
        q.offer(req(96, 96, clock=clock))
        q.offer(req(60, 60, clock=clock))  # snaps up into (64, 64)
        assert b.intake() == 1  # the (64,64) pair filled; (96,96) waits
        assert d.calls[0][0] == (64, 64)
        assert b.pending_count() == 1
        clock.t = 1.0
        assert b.poll(clock.t) == 1
        assert d.calls[1][0] == (96, 96)

    def test_dtype_never_mixes_in_one_batch(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=2)
        q.offer(req(64, 64, clock=clock, dtype=np.float32))
        q.offer(req(64, 64, clock=clock, dtype=np.uint8))
        b.intake()
        assert not d.calls  # same bucket shape, but two dtype groups of 1
        clock.t = 1.0
        assert b.poll(clock.t) == 2
        dtypes = {c[1].image.dtype for c in d.calls}
        assert dtypes == {np.dtype(np.float32), np.dtype(np.uint8)}

    def test_expired_request_rejected_never_dispatched(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=2, max_wait_ms=50.0)
        doomed = req(64, 64, deadline_s=0.01, clock=clock)
        q.offer(doomed)
        b.intake()
        clock.t = 0.02  # past deadline, before max_wait
        assert b.poll(clock.t) == 0
        assert doomed.done and not d.calls
        with pytest.raises(RejectedError) as e:
            doomed.wait(0)
        assert e.value.reason == REJECT_DEADLINE

    def test_dispatch_error_rejects_requests_keeps_batcher(self):
        d = CollectDispatch(fail=True)
        q, b, clock = self.make(d, max_batch=1)
        r = req(64, 64, clock=clock)
        q.offer(r)
        b.intake()  # dispatch raises inside; batcher survives
        with pytest.raises(RejectedError) as e:
            r.wait(0)
        assert e.value.reason == REJECT_ERROR
        d.fail = False
        d2 = req(64, 64, clock=clock)
        q.offer(d2)
        b.intake()
        assert d2.done and not isinstance(d2._reject, RejectedError)

    def test_bucket_mapping_matches_offline_batcher(self):
        """The serve bucket function IS the offline one (snap_to_bucket):
        same ladder -> same cell for every shape."""
        ladder = ((64, 128), (96, 160))
        b = MicroBatcher(BoundedRequestQueue(4), lambda *a: None,
                         bucket_ladder=ladder)
        for hw in [(64, 96), (65, 96), (128, 160), (200, 300), (8, 8)]:
            assert b.bucket_of(hw) == snap_to_bucket(hw, ladder=ladder)

    def test_cost_planner_ladder_shared_with_serving(self):
        """Serving inherits the r8 cost-model planner's boundaries
        without a fork: hand a cost-mode auto ladder to MicroBatcher and
        every dataset shape maps to the EXACT cell the offline batcher
        uses (snap_to_bucket is the single source of the mapping — the
        r8 _resolve_auto_buckets changes moved boundary placement, not
        the shape->cell function)."""
        import numpy as np

        from can_tpu.data import ShardedBatcher

        rng = np.random.default_rng(5)
        shapes = [(int(rng.integers(8, 40)) * 8, int(rng.integers(8, 40)) * 8)
                  for _ in range(60)]

        class ShapeOnly:
            def __len__(self):
                return len(shapes)

            def snapped_shape(self, i):
                return shapes[i]

        off = ShardedBatcher(ShapeOnly(), 8, shuffle=True, seed=0,
                             pad_multiple="auto", max_buckets=8,
                             remnant_sizes=True, batch_quantum=1,
                             launch_cost_px=0.05e6)
        assert off.plan_mode == "cost" and off.bucket_ladder is not None
        online = MicroBatcher(BoundedRequestQueue(4), lambda *a: None,
                              bucket_ladder=off.bucket_ladder)
        for hw in shapes + [(1, 1), (4096, 4096)]:
            assert online.bucket_of(hw) == off._bucket_key(hw)

    def test_flush_all_drains_pending(self):
        d = CollectDispatch()
        q, b, clock = self.make(d, max_batch=8)
        q.offer(req(64, 64, clock=clock))
        q.offer(req(96, 96, clock=clock))
        b.intake()
        assert b.flush_all() == 2
        assert b.pending_count() == 0


@pytest.fixture(scope="module")
def small_engine():
    params = cannet_init(jax.random.key(0))
    tel = obs.Telemetry()
    return ServeEngine(params, telemetry=tel)


class TestEngineAndService:
    def test_warmup_compiles_once_per_bucket(self, small_engine):
        before = small_engine.compile_count
        rep = small_engine.warmup([(64, 64), (64, 96)], max_batch=2)
        assert small_engine.compile_count - before == rep["compiles"]
        # idempotent: a second warmup compiles nothing new
        rep2 = small_engine.warmup([(64, 64), (64, 96)], max_batch=2)
        assert rep2["compiles"] == 0

    def test_smoke_64_mixed_requests_bounded_compiles(self, small_engine):
        """Acceptance: >= 64 mixed-resolution requests, zero hangs, compile
        count bounded by the distinct bucket shapes, fill/latency stats."""
        ladder = ((64, 96), (64, 96))
        svc = CountService(small_engine, max_batch=4, max_wait_ms=2.0,
                           queue_capacity=256,
                           bucket_ladder=ladder)
        rep = svc.warmup([(h, w) for h in ladder[0] for w in ladder[1]])
        # compile bound: one program per (bucket shape, menu size) — the
        # r14 sub-batch menu rides the warmup (engine is module-scoped,
        # so compare this warmup's DELTA, not the total)
        assert rep["compiles"] <= 4 * len(svc.sched.menu)
        compiles_before_traffic = small_engine.compile_count
        sizes = [(64, 64), (96, 96), (64, 96), (96, 64), (60, 60), (90, 90)]
        rng = np.random.default_rng(0)
        with svc:
            tickets = [
                svc.submit(prepare_image(
                    (rng.uniform(0, 1, s + (3,)) * 255).astype(np.uint8)),
                    deadline_ms=60_000)
                for s in (sizes[i % len(sizes)] for i in range(64))]
            results = [t.result(timeout=120.0) for t in tickets]
        assert len(results) == 64  # every request resolved — no hangs
        # no NEW compiles during traffic: warmup paid them all
        assert small_engine.compile_count == compiles_before_traffic
        buckets = {r.bucket_hw for r in results}
        assert buckets <= {(64, 64), (64, 96), (96, 64), (96, 96)}
        st = svc.stats()
        assert st["completed"] == 64 and st["rejected"] == 0
        assert 0 < st["mean_batch_fill"] <= 1.0
        assert st["latency_p50_s"] > 0

    def test_deadline_zero_is_rejected_not_hung(self, small_engine):
        svc = CountService(small_engine, max_batch=2, max_wait_ms=5.0,
                           bucket_ladder=((64,), (64,)))
        with svc:
            t = svc.submit(np.zeros((64, 64, 3), np.float32),
                           deadline_ms=0.0)
            with pytest.raises(RejectedError) as e:
                t.result(timeout=10.0)
        assert e.value.reason == REJECT_DEADLINE
        # batcher-side rejections count in stats() too (review r6): the
        # operator-facing reject counter must agree with what clients saw
        assert svc.stats()["rejected"] == 1

    def test_submit_after_close_rejects_shutdown(self, small_engine):
        svc = CountService(small_engine, max_batch=1,
                           bucket_ladder=((64,), (64,)))
        svc.start()
        svc.close()
        t = svc.submit(np.zeros((64, 64, 3), np.float32))
        with pytest.raises(RejectedError) as e:
            t.result(timeout=1.0)
        assert e.value.reason == REJECT_SHUTDOWN

    def test_unsnapped_image_rejected_at_submit(self, small_engine):
        svc = CountService(small_engine, max_batch=1,
                           bucket_ladder=((64,), (64,)))
        with pytest.raises(ValueError):
            svc.submit(np.zeros((60, 60, 3), np.float32))

    def test_oversized_image_rejected_at_submit_not_poisoning(
            self, small_engine):
        """Above the top ladder bound the snap goes DOWN; without the
        door check the batch assembly would raise and error-reject every
        co-batched request (review r6)."""
        svc = CountService(small_engine, max_batch=1,
                           bucket_ladder=((64,), (64,)))
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            svc.submit(np.zeros((128, 128, 3), np.float32))
        # and over HTTP it's a 400 client error, not a 503
        svc2 = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                            bucket_ladder=((64,), (64,)))
        with svc2:
            httpd = serve_http(svc2, port=0)
            port = httpd.server_address[1]
            thread = threading.Thread(target=httpd.serve_forever,
                                      daemon=True)
            thread.start()
            try:
                buf = io.BytesIO()
                np.save(buf, np.zeros((128, 128, 3), np.uint8))
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict",
                    data=buf.getvalue(), method="POST")
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(r)
                assert e.value.code == 400
            finally:
                httpd.shutdown()
                httpd.server_close()

    def test_want_density_returns_item_sized_map(self, small_engine):
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((96,), (96,)))
        svc.warmup([(96, 96)])
        with svc:
            res = svc.predict(np.zeros((64, 72, 3), np.float32),
                              want_density=True, timeout=60.0)
        assert res.bucket_hw == (96, 96)
        assert res.density.shape == (8, 9, 1)  # item's grid, crop of bucket

    def test_http_raw_without_u8_warmup_is_400(self, small_engine):
        """raw=1 on a server that never warmed uint8 programs must be
        refused at the door — an unwarmed dtype would compile mid-traffic
        on the batcher thread, stalling every bucket (review r6)."""
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)])  # float32 only
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            try:
                buf = io.BytesIO()
                np.save(buf, np.zeros((64, 64, 3), np.uint8))
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?raw=1",
                    data=buf.getvalue(), method="POST")
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(r)
                assert e.value.code == 400
                assert "u8-warmup" in json.loads(e.value.read())["error"]
            finally:
                httpd.shutdown()
                httpd.server_close()

    def test_http_round_trip(self, small_engine):
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)], dtypes=(np.float32, np.uint8))
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            thread = threading.Thread(target=httpd.serve_forever,
                                      daemon=True)
            thread.start()
            try:
                img = np.zeros((60, 60, 3), np.uint8)
                buf = io.BytesIO()
                np.save(buf, img)
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?deadline_ms=60000",
                    data=buf.getvalue(), method="POST")
                payload = json.loads(urllib.request.urlopen(r).read())
                assert payload["bucket"] == [64, 64]
                assert "count" in payload and "latency_ms" in payload
                health = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz").read())
                assert health == {"ok": True}
                stats = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/stats").read())
                assert stats["completed"] >= 1
                # raw=1: uint8 stays uint8 on the wire and into the
                # engine (device normalisation) — must hit the u8 program
                # warmed above, not compile a new one
                compiles = small_engine.compile_count
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?raw=1"
                    f"&deadline_ms=60000",
                    data=buf.getvalue(), method="POST")
                payload = json.loads(urllib.request.urlopen(r).read())
                assert payload["bucket"] == [64, 64]
                assert small_engine.compile_count == compiles
                # raw=1 with non-u8 payload is a client error, not a 500
                fbuf = io.BytesIO()
                np.save(fbuf, np.zeros((60, 60, 3), np.float32))
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}/predict?raw=1",
                    data=fbuf.getvalue(), method="POST")
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(r)
                assert e.value.code == 400
            finally:
                httpd.shutdown()
                httpd.server_close()


class StubFleet:
    """The least of a fleet engine the service needs: ``submit_work``
    marks the path on which the batch outlives ``dispatch``."""

    ds, compile_count = 8, 0

    def __init__(self):
        self.telemetry = obs.Telemetry()
        self.work = []

    def bind(self, *, on_complete, **_):
        self.on_complete = on_complete

    def start(self):
        pass

    def close(self):
        pass

    def healthz(self):
        return {"ok": True, "replicas": [], "live": 0, "generation": 0}

    def submit_work(self, bucket_hw, batch, requests, *, pin=None):
        self.work.append((bucket_hw, batch, requests))

    def complete_all(self):
        for bucket_hw, batch, requests in self.work:
            b, h, w, _ = batch.image.shape
            self.on_complete(bucket_hw, batch, requests,
                             np.zeros((b,), np.float32),
                             np.zeros((b, h // 8, w // 8, 1), np.float32),
                             0.0, False, 0, "stub")


class TestStagingReuse:
    """The in-process service assembles each launch into the batcher's
    staging buffer; what the engine is handed, and what the clients get,
    is what fresh ``pad_batch`` batches give, bit for bit."""

    LADDER = ((96,), (96,))
    KEY = (96, 96, "float32")

    def service(self, engine, **kw):
        kw.setdefault("max_batch", 4)
        svc = CountService(engine, max_wait_ms=2.0, bucket_ladder=self.LADDER,
                           queue_capacity=64, **kw)
        svc.warmup([(96, 96)])
        return svc

    def checked_dispatch(self, svc, reference):
        """Wrap the service's dispatch: the batch it is handed equals a
        fresh ``pad_batch`` of the same requests, whose answers (computed
        here, from the fresh batch) go to ``reference`` by request id."""
        from can_tpu.data import pad_batch

        inner = svc.batcher.dispatch

        def dispatch(bucket_hw, batch, requests):
            items = [(r.image, np.zeros((r.shape[0] // 8, r.shape[1] // 8, 1),
                                        np.float32)) for r in requests]
            fresh = pad_batch(items, bucket_hw, batch.image.shape[0],
                              [True] * len(items), 8)
            for name in ("image", "dmap", "pixel_mask", "sample_mask"):
                got, want = getattr(batch, name), getattr(fresh, name)
                assert got.dtype == want.dtype, name
                np.testing.assert_array_equal(got, want, err_msg=name)
            counts, density = svc.engine.predict_batch(fresh,
                                                       want_density=True)
            for slot, r in enumerate(requests):
                h, w = r.shape
                reference[r.id] = (float(counts[slot]),
                                   density[slot, :h // 8, :w // 8].copy())
            inner(bucket_hw, batch, requests)

        svc.batcher.dispatch = dispatch

    def rounds(self, rng):
        """Launches of 4, 2 + 1, 1 and 4 slots, the items first large and
        then small (margins to zero), then large again."""
        sides = [[(96, 96)] * 4, [(32, 40), (96, 64), (8, 8)], [(64, 96)],
                 [(48, 48), (96, 96), (16, 88), (72, 24)]]
        return [[rng.standard_normal((h, w, 3)).astype(np.float32)
                 for h, w in group] for group in sides]

    def test_served_answers_equal_fresh_batches_and_outlive_the_buffer(
            self, small_engine):
        svc = self.service(small_engine)
        reference = {}
        self.checked_dispatch(svc, reference)
        tickets = []
        for group in self.rounds(np.random.default_rng(11)):
            tickets += [svc.submit(img, want_density=True) for img in group]
            svc.batcher.intake()
            svc.batcher.flush_all()  # hand-driven: one group, known launches
        results = [(t._request.id, t.result(0)) for t in tickets]
        assert len(results) == 12 and len(reference) == 12
        for rid, res in results:
            count, dens = reference[rid]
            assert res.count == count  # bit for bit
            np.testing.assert_array_equal(res.density, dens)
        # launches of 4, 2, 1, 1, 4 slots: the first made the buffer, and
        # a hand-driven batcher (no lanes: each launch runs to its end
        # before the next is assembled) never needs the ring's second
        staging = svc.stats()["staging"]
        buf, = svc.batcher._staging_pool[self.KEY]
        assert svc.batcher._staging_free[self.KEY] == [buf]
        assert staging == {"reused": 4, "fresh": 1, "bytes_held": buf.nbytes}
        assert buf.image.shape == (4, 96, 96, 3)
        # the buffer is the batcher's again: scribbling on it reaches no
        # answer a client already holds
        for a in (buf.image, buf.dmap, buf.pixel_mask, buf.sample_mask):
            a[...] = np.nan
        for rid, res in results:
            count, dens = reference[rid]
            assert res.count == count
            np.testing.assert_array_equal(res.density, dens)
        svc.close()
        assert svc.stats()["staging"] == {"reused": 4, "fresh": 1,
                                          "bytes_held": 0}

    def test_a_dispatch_that_raises_leaves_the_buffer_usable(
            self, small_engine, monkeypatch):
        svc = self.service(small_engine)
        reference = {}
        self.checked_dispatch(svc, reference)
        rng = np.random.default_rng(12)
        big, small, mid = self.rounds(rng)[0], self.rounds(rng)[1], \
            self.rounds(rng)[3]

        def launch(group):
            tickets = [svc.submit(img, want_density=True) for img in group]
            svc.batcher.intake()
            svc.batcher.flush_all()
            return tickets

        for t in launch(big):
            t.result(0)
        real = small_engine.predict_batch
        calls = {"n": 0}

        def boom(batch, *, want_density=False):
            calls["n"] += 1
            if calls["n"] % 2 == 0:  # the service's call, not the check's
                raise RuntimeError("device fell over")
            return real(batch, want_density=want_density)

        monkeypatch.setattr(small_engine, "predict_batch", boom)
        failed = launch(small[:1])
        with pytest.raises(RejectedError) as e:
            failed[0].result(0)
        assert e.value.reason == REJECT_ERROR
        monkeypatch.setattr(small_engine, "predict_batch", real)
        for t in launch(mid):  # checked against fresh inside the dispatch
            res = t.result(0)
            count, dens = reference[t._request.id]
            assert res.count == count
            np.testing.assert_array_equal(res.density, dens)
        assert svc.stats()["staging"]["reused"] == 2
        # the launch that raised gave its buffer back like the others
        assert (svc.batcher._staging_free[self.KEY]
                == svc.batcher._staging_pool[self.KEY])
        svc.close()

    def test_uint8_and_float32_stage_apart(self, small_engine):
        svc = self.service(small_engine)
        svc.warmup([(96, 96)], dtypes=(np.uint8,))
        reference = {}
        self.checked_dispatch(svc, reference)
        rng = np.random.default_rng(13)
        for _ in range(2):
            ts = [svc.submit(rng.integers(0, 255, (40, 96, 3)).astype(np.uint8)),
                  svc.submit(rng.standard_normal((96, 40, 3))
                             .astype(np.float32))]
            svc.batcher.intake()
            svc.batcher.flush_all()
            for t in ts:
                assert t.result(0).count == reference[t._request.id][0]
        pool = svc.batcher._staging_pool
        assert sorted(pool) == [(96, 96, "float32"), (96, 96, "uint8")]
        assert [len(ring) for ring in pool.values()] == [1, 1]
        assert pool[(96, 96, "uint8")][0].image.dtype == np.uint8
        assert svc.stats()["staging"] == {
            "reused": 2, "fresh": 2,
            "bytes_held": sum(b.nbytes for ring in pool.values()
                              for b in ring)}
        svc.close()

    def test_the_fleet_path_assembles_every_launch_fresh(self):
        fleet = StubFleet()
        svc = CountService(fleet, max_batch=4, max_wait_ms=2.0,
                           bucket_ladder=self.LADDER)
        assert svc.batcher._staging_pool is None
        # how many launches a fleet holds in flight is its replicas'
        # business: the batcher runs no lanes for it, whatever it states
        fleet.launches_in_flight = 2
        assert CountService(fleet, max_batch=4).batcher.launches_in_flight == 1
        tickets = []
        for group in self.rounds(np.random.default_rng(14)):
            tickets += [svc.submit(img) for img in group]
            svc.batcher.intake()
            svc.batcher.flush_all()
        assert len(fleet.work) == 5
        for _, batch, _ in fleet.work:  # each batch owns its arrays
            assert batch.image.base is None and batch.image.flags.owndata
        images = [b.image for _, b, _ in fleet.work]
        assert not any(np.shares_memory(a, b) for i, a in enumerate(images)
                       for b in images[i + 1:])
        assert svc.stats()["staging"] == {"reused": 0, "fresh": 5,
                                          "bytes_held": 0}
        fleet.complete_all()
        assert all(t.result(0).count == 0.0 for t in tickets)
        svc.close()

    def test_staging_reaches_the_scrape(self):
        from can_tpu.obs.exporter import render_stats

        text = render_stats({"batches": 3, "staging": {
            "reused": 2, "fresh": 1, "bytes_held": 4096}})
        assert ('can_tpu_serve_staging_launches_total{assembled="reused"} 2'
                in text)
        assert ('can_tpu_serve_staging_launches_total{assembled="fresh"} 1'
                in text)
        assert "can_tpu_serve_staging_bytes_held 4096" in text


class GatedEngine:
    """As much of an engine as ``CountService`` needs, with a
    ``predict_batch`` that waits: call ``i`` blocks until ``gates[i]`` is
    set, so a test decides which launches are in flight and nothing depends
    on timing.  A request's "count" is its image's first pixel."""

    ds, compile_count, last_batch_compiled = 8, 0, False

    def __init__(self, launches_in_flight, *, raises=()):
        if launches_in_flight is not None:
            self.launches_in_flight = launches_in_flight
        self.telemetry = obs.Telemetry()
        self.gates = [threading.Event() for _ in range(8)]
        self.raises = set(raises)
        self.entered = threading.Semaphore(0)
        self.calls = []   # per call: thread, the batch, its bytes on entry
        self.running = self.most_running = 0
        self._lock = threading.Lock()

    def wait_entered(self, n=1):
        for _ in range(n):
            assert self.entered.acquire(timeout=30), "a launch never started"

    def predict_batch(self, batch, *, want_density=False):
        with self._lock:
            i = len(self.calls)
            self.calls.append({"thread": threading.current_thread().name,
                               "batch": batch, "bytes": batch.image.copy()})
            self.running += 1
            self.most_running = max(self.most_running, self.running)
        self.entered.release()
        try:
            assert self.gates[i].wait(30), f"gate {i} never opened"
            if i in self.raises:
                raise RuntimeError(f"launch {i} fell over")
            return batch.image[:, 0, 0, 0].copy(), None
        finally:
            with self._lock:
                self.running -= 1


def marked(value, side=16):
    """An image whose first pixel (its "count" on a GatedEngine) is
    ``value``."""
    return np.full((side, side, 3), float(value), np.float32)


class TestLaunchLanes:
    """Two launches in flight (PR 27): the batcher thread assembles and a
    lane dispatches batch n+1 while batch n's ``predict_batch`` is still in
    progress on the other lane; never three; a staging buffer is nobody
    else's while its launch is in flight."""

    def service(self, engine, **kw):
        return CountService(engine, max_batch=2, max_wait_ms=2.0,
                            bucket_ladder=((16,), (16,)), queue_capacity=64,
                            **kw)

    def submit(self, svc, values):
        return [svc.submit(marked(v)) for v in values]

    @staticmethod
    def buffer_of(call):
        return call["batch"].image.base

    def test_depth_is_two_never_three(self):
        eng = GatedEngine(2)
        svc = self.service(eng)
        tickets = self.submit(svc, range(1, 7))  # three launches' worth
        svc.start()
        eng.wait_entered(2)
        # two launches in progress, on the two lanes; the third is not
        # assembled (there is no buffer for it) until one returns
        assert sorted(c["thread"] for c in eng.calls) == [
            "can-tpu-serve-lane_0", "can-tpu-serve-lane_1"]
        assert svc.batcher._lanes.in_flight() == 2
        assert not any(t.done for t in tickets)
        eng.gates[0].set()
        eng.wait_entered(1)
        assert len(eng.calls) == 3
        for g in eng.gates:
            g.set()
        assert [t.result(30).count for t in tickets] == [1, 2, 3, 4, 5, 6]
        assert eng.most_running == 2
        stats = svc.stats()
        assert stats["batches"] == 3 and stats["launches_overlapped"] == 2
        svc.close()

    @pytest.mark.parametrize("first_raises", [False, True],
                             ids=["returns", "raises"])
    def test_a_buffer_is_not_written_while_its_launch_is_in_flight(
            self, first_raises):
        eng = GatedEngine(2, raises={0} if first_raises else ())
        svc = self.service(eng)
        tickets = self.submit(svc, range(1, 7))
        svc.start()
        eng.wait_entered(2)
        first, second = eng.calls
        assert self.buffer_of(first) is not self.buffer_of(second)
        ring = svc.batcher._staging_pool[(16, 16, "float32")]
        assert {id(b.image) for b in ring} == {id(self.buffer_of(first)),
                                               id(self.buffer_of(second))}
        assert svc.stats()["staging"] == {
            "reused": 0, "fresh": 2, "bytes_held": sum(b.nbytes for b in ring)}
        # both launches are blocked and the third group waits: neither
        # buffer has been touched since its launch was handed over
        for call in (first, second):
            np.testing.assert_array_equal(call["batch"].image, call["bytes"])
        eng.gates[0].set()
        eng.wait_entered(1)
        third = eng.calls[2]
        # the first launch returned (or raised): ITS buffer was reused
        assert self.buffer_of(third) is self.buffer_of(first)
        np.testing.assert_array_equal(second["batch"].image, second["bytes"])
        assert third["bytes"][:, 0, 0, 0].tolist() == [5.0, 6.0]
        for g in eng.gates:
            g.set()
        if first_raises:
            # a poison batch on a lane: its own requests only
            for t in tickets[:2]:
                with pytest.raises(RejectedError) as e:
                    t.result(30)
                assert e.value.reason == REJECT_ERROR
            assert svc.stats()["rejected"] == 2
        else:
            assert [t.result(30).count for t in tickets[:2]] == [1, 2]
        assert [t.result(30).count for t in tickets[2:]] == [3, 4, 5, 6]
        assert svc.stats()["staging"]["reused"] == 1
        svc.close()
        assert svc.stats()["staging"]["bytes_held"] == 0

    def test_overlapping_launches_keep_each_request_with_its_own_count(self):
        eng = GatedEngine(2)
        svc = self.service(eng)
        tickets = self.submit(svc, [10, 20, 30, 40])
        svc.start()
        eng.wait_entered(2)
        eng.gates[1].set()   # the second launch completes first
        assert [t.result(30).count for t in tickets[2:]] == [30, 40]
        assert not tickets[0].done and not tickets[1].done
        eng.gates[0].set()
        assert [t.result(30).count for t in tickets[:2]] == [10, 20]
        svc.close()

    def test_close_resolves_everything_with_launches_in_flight(self):
        eng = GatedEngine(2)
        svc = self.service(eng)
        tickets = self.submit(svc, range(1, 8))  # 3 launches and a rest of 1
        svc.start()
        eng.wait_entered(2)
        closer = threading.Thread(target=svc.close)
        closer.start()
        closer.join(0.05)
        assert closer.is_alive()  # close() waits for the launches in flight
        for g in eng.gates:
            g.set()
        closer.join(30)
        assert not closer.is_alive()
        assert [t.result(0).count for t in tickets] == [1, 2, 3, 4, 5, 6, 7]
        assert svc.batcher._lanes is None and eng.running == 0
        late = svc.submit(marked(9))
        with pytest.raises(RejectedError) as e:
            late.result(0)
        assert e.value.reason == REJECT_SHUTDOWN

    @pytest.mark.parametrize("stated, lanes", [(1, 0), (2, 2), (None, 0)],
                             ids=["one", "two", "unstated"])
    def test_the_engine_states_how_many_launches_fly(self, stated, lanes):
        eng = GatedEngine(stated)
        for g in eng.gates:
            g.set()
        svc = self.service(eng)
        assert svc.batcher.launches_in_flight == (stated or 1)
        tickets = self.submit(svc, range(1, 5))
        svc.start()
        assert [t.result(30).count for t in tickets] == [1, 2, 3, 4]
        threads = {c["thread"] for c in eng.calls}
        if lanes:
            assert threads <= {"can-tpu-serve-lane_0", "can-tpu-serve-lane_1"}
            assert len(svc.batcher._lanes._threads) == lanes
        else:
            # depth 1 is the in-line path: dispatch on the batcher thread,
            # no lane, one buffer, never a launch beside another
            assert threads == {"can-tpu-serve-batcher"}
            assert svc.batcher._lanes is None
            assert eng.most_running == 1
            assert len(svc.batcher._staging_pool[(16, 16, "float32")]) == 1
            assert svc.stats()["launches_overlapped"] == 0
        svc.close()

    def test_more_than_one_launch_needs_a_dispatch_that_frees_its_batch(self):
        q = BoundedRequestQueue(4)
        with pytest.raises(ValueError, match="batch_free_on_return"):
            MicroBatcher(q, CollectDispatch(), launches_in_flight=2)
        with pytest.raises(ValueError, match="at least 1"):
            MicroBatcher(q, CollectDispatch(), launches_in_flight=0,
                         batch_free_on_return=True)

    def test_span_tree_with_two_launches_in_flight(self, small_engine,
                                                   monkeypatch):
        """What the benchmark's readers read stays a tree: ``serve.batch``
        under the batcher thread's cycle span, pad / dispatch / fetch /
        complete its direct children, though the batch begins on the
        batcher thread and ends on a lane."""
        tel = obs.Telemetry()
        tel.spans = obs.SpanTracer(tel, prefix="l")
        monkeypatch.setattr(small_engine, "telemetry", tel)
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        real = small_engine.predict_batch
        gate, entered = threading.Event(), threading.Semaphore(0)

        def gated(batch, *, want_density=False):
            entered.release()
            assert gate.wait(30)
            return real(batch, want_density=want_density)

        monkeypatch.setattr(small_engine, "predict_batch", gated)
        tickets = [svc.submit(np.zeros((64, 64, 3), np.float32))
                   for _ in range(4)]
        svc.start()
        for _ in range(2):   # both launches in progress before either runs
            assert entered.acquire(timeout=30)
        gate.set()
        for t in tickets:
            t.result(60)
        assert svc.stats()["launches_overlapped"] == 1
        svc.close()   # every serve.batch span is recorded by now
        ring = tel.spans.snapshot()
        by_id = {s["span_id"]: s for s in ring}
        requests = [s for s in ring if s["name"] == "request"]
        assert len(requests) == 4
        batches = sorted({s["batch"] for s in requests},
                         key=lambda i: by_id[i]["start_s"])
        assert len(batches) == 2
        assert [by_id[i]["in_flight"] for i in batches] == [0, 1]
        lanes = set()
        for i in batches:
            batch = by_id[i]
            assert batch["name"] == "serve.batch" and batch["valid"] == 2
            assert batch["thread"] == "can-tpu-serve-batcher"
            cycle = by_id[batch["parent_id"]]
            assert cycle["name"] in ("serve.intake", "serve.poll")
            assert cycle["thread"] == "can-tpu-serve-batcher"
            assert cycle["trace_id"].startswith("batcher-")
            kids = {s["name"]: s for s in ring if s.get("parent_id") == i}
            assert sorted(kids) == ["serve.complete", "serve.dispatch",
                                    "serve.fetch", "serve.pad"]
            assert all(k["trace_id"] == batch["trace_id"]
                       for k in kids.values())
            assert kids["serve.dispatch"]["compiled"] is False
            assert kids["serve.pad"]["thread"] == "can-tpu-serve-batcher"
            lane = {kids[n]["thread"] for n in ("serve.dispatch",
                                                "serve.fetch",
                                                "serve.complete")}
            assert len(lane) == 1
            lanes |= lane
            # the phases follow one another inside the batch, and the
            # batch ends with the last of them
            order = [kids[n] for n in ("serve.pad", "serve.dispatch",
                                       "serve.fetch", "serve.complete")]
            for a, b in zip(order, order[1:]):
                assert b["start_s"] >= a["start_s"] + a["duration_s"] - 1e-6
            end = batch["start_s"] + batch["duration_s"]
            done = order[-1]["start_s"] + order[-1]["duration_s"]
            assert done - 1e-6 <= end
        assert lanes == {"can-tpu-serve-lane_0", "can-tpu-serve-lane_1"}
        first, second = (by_id[i] for i in batches)
        assert second["start_s"] < first["start_s"] + first["duration_s"]
        for s in requests:   # a request's wait ends where its batch begins
            wait = next(w for w in ring if w["name"] == "queue_wait"
                        and w["parent_id"] == s["span_id"])
            assert wait["start_s"] + wait["duration_s"] == pytest.approx(
                by_id[s["batch"]]["start_s"], abs=2e-6)

    def test_overlap_reaches_the_scrape(self):
        from can_tpu.obs.exporter import render_stats

        text = render_stats({"batches": 5, "launches_overlapped": 4})
        assert "can_tpu_serve_launches_overlapped_total 4" in text


class TestOfflineOnlineParity:
    """Acceptance: a served count is bit-for-bit evaluate()'s per-image
    output for the same image and params."""

    @pytest.fixture(scope="class")
    def setup(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("serve_parity")
        img_root, gt_root = make_synthetic_dataset(
            str(root), 5, sizes=((64, 64), (64, 96), (96, 64)), seed=3,
            max_people=12)
        ds = CrowdDataset(img_root, gt_root, gt_downsample=8, phase="test")
        params = cannet_init(jax.random.key(1))
        # nonzero biases make the forward padding-sensitive — the regime
        # where a parity bug would actually show (test_bucketed_eval.py)
        params = jax.tree_util.tree_map(
            lambda x: x + 0.05 if x.ndim == 1 else x, params)
        return ds, params

    def test_counts_bit_for_bit(self, setup):
        ds, params = setup
        from can_tpu.models import cannet_apply
        from can_tpu.train import evaluate, make_eval_step
        from can_tpu.train.loss import density_counts

        # offline: the eval CLI's single-host path (batch 1, exact shapes)
        ev = jax.jit(make_eval_step(cannet_apply))

        def put(b):
            return {"image": jnp.asarray(b.image),
                    "dmap": jnp.asarray(b.dmap),
                    "pixel_mask": jnp.asarray(b.pixel_mask),
                    "sample_mask": jnp.asarray(b.sample_mask)}

        batcher = ShardedBatcher(ds, 1, shuffle=False)
        offline = evaluate(ev, params, batcher.epoch(0), put_fn=put,
                           dataset_size=batcher.dataset_size)

        # per-image offline counts from the same masked-reduction program
        @jax.jit
        def off_counts(params, batch):
            return density_counts(cannet_apply(params, batch["image"]),
                                  batch)

        engine = ServeEngine(params)
        # exact buckets + max_batch 1: the online tensor IS the offline one
        svc = CountService(engine, max_batch=1, max_wait_ms=1.0)
        abs_sum = 0.0
        with svc:
            for i in range(len(ds)):
                img, dm = ds[i]
                h, w = img.shape[:2]
                served = svc.predict(img, timeout=120.0)
                batch = put(type("B", (), dict(
                    image=img[None], dmap=dm[None],
                    pixel_mask=np.ones((1, h // 8, w // 8, 1), np.float32),
                    sample_mask=np.ones((1,), np.float32)))())
                et, gt = off_counts(params, batch)
                assert served.count == float(et[0])  # BIT-for-bit
                abs_sum += abs(served.count - float(gt[0]))
        # and the dataset metric reconstructed from served counts matches
        # evaluate()'s exactly
        assert abs_sum / len(ds) == offline["mae"]


class TestServeTelemetryReport:
    def test_serve_events_summarized(self, tmp_path):
        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        tel.emit("serve.request", latency_s=0.010, bucket=[64, 64], ok=True)
        tel.emit("serve.request", latency_s=0.030, bucket=[64, 64], ok=True)
        tel.emit("serve.batch", bucket=[64, 64], size=4, valid=3, fill=0.75,
                 execute_s=0.008, queue_depth=5)
        tel.emit("serve.batch", bucket=[96, 96], size=4, valid=1, fill=0.25,
                 execute_s=0.009, queue_depth=2)
        tel.emit("serve.reject", reason=REJECT_DEADLINE, count=1)
        tel.emit("serve.reject", reason=REJECT_BACKPRESSURE, count=2)
        tel.close()
        path = os.path.join(str(tmp_path), "telemetry.host0.jsonl")
        s = obs.summarize(obs.read_events(path))
        assert s["serve_requests"] == 2
        assert s["serve_latency_p50_s"] == pytest.approx(0.020)
        assert s["serve_latency_max_s"] == pytest.approx(0.030)
        assert s["serve_batches"] == 2
        assert s["serve_mean_fill"] == pytest.approx(0.5)
        assert s["serve_rejects"] == 3
        assert s["serve_rejects_by_reason"] == {REJECT_BACKPRESSURE: 2,
                                                REJECT_DEADLINE: 1}
        assert s["serve_queue_depth_max"] == 5
        table = obs.format_report(s)
        assert "serve p95" in table and "backpressure=2" in table

    def test_offline_run_summary_has_no_serve_rows(self):
        s = obs.summarize([{"ts": 1.0, "kind": "step_window", "step": 1,
                            "host_id": 0,
                            "payload": {"steps": 1, "samples_s": [0.1]}}])
        assert s["serve_requests"] == 0
        assert "serve p95" not in obs.format_report(s)

    def test_service_emits_request_batch_reject(self, tmp_path,
                                                small_engine):
        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        # rebind the module-scoped engine's bus just for this service:
        # service-level events (request/batch/reject) go to `tel`
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        with svc:
            svc.predict(np.zeros((64, 64, 3), np.float32), timeout=60.0)
            t = svc.submit(np.zeros((64, 64, 3), np.float32),
                           deadline_ms=0.0)
            with pytest.raises(RejectedError):
                t.result(timeout=10.0)
        tel.close()
        events = obs.read_events(
            os.path.join(str(tmp_path), "telemetry.host0.jsonl"))
        kinds = [e["kind"] for e in events]
        assert "serve.request" in kinds
        assert "serve.batch" in kinds
        assert "serve.reject" in kinds
        batch_ev = next(e for e in events if e["kind"] == "serve.batch")
        assert {"bucket", "size", "valid", "fill", "execute_s",
                "queue_depth"} <= set(batch_ev["payload"])


class TestServeSpansAndPerf:
    """Performance-attribution layer on the serve path: the serve.request
    queue-wait/device breakdown, the request's spans and its batch's,
    and the cost ledger's per-bucket MFU/roofline rows (the r9 tentpole's serve
    acceptance)."""

    def test_request_breakdown_span_tree_and_ledger(self, tmp_path,
                                                    small_engine,
                                                    monkeypatch):
        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        tel.spans = obs.SpanTracer(tel, prefix="t")
        # engine and service on one bus, as the CLI builds them: the
        # engine's dispatch / fetch spans land beside the batcher's
        monkeypatch.setattr(small_engine, "telemetry", tel)
        tel.ledger = obs.ProgramCostLedger(compute="f32")
        # the ENGINE's tracker attributes compiles on its own (module
        # fixture) bus, where (64,64) is already warm — register the
        # program with the service's ledger directly, the path a fresh
        # CLI serve run takes through warmup
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel,
                           perf_summary_every=1)
        svc.warmup([(64, 64)])
        from can_tpu.train.steps import batch_signature

        from can_tpu.data.batching import pad_batch

        # one registration per MENU size (the r14 sub-batch menu): a
        # flush may launch any menu-size program, and a fresh CLI's
        # warmup registers them all
        for size in svc.sched.menu:
            warm = pad_batch([(np.zeros((64, 64, 3), np.float32),
                               np.zeros((8, 8, 1), np.float32))],
                             (64, 64), size, [False], 8)
            tel.ledger.register(
                "serve_predict",
                batch_signature({"image": warm.image, "dmap": warm.dmap,
                                 "pixel_mask": warm.pixel_mask,
                                 "sample_mask": warm.sample_mask}),
                cost=(1e9, 1e8))
        with svc:
            tickets = [svc.submit(np.zeros((64, 64, 3), np.float32),
                                  deadline_ms=60_000) for _ in range(4)]
            results = [t.result(timeout=120.0) for t in tickets]
        tel.close()
        # every result carries the breakdown + its trace handle
        for r in results:
            assert r.queue_wait_s is not None and r.queue_wait_s >= 0
            assert r.device_s is not None and r.device_s > 0
            assert r.trace_id
        events = obs.read_events(
            os.path.join(str(tmp_path), "telemetry.host0.jsonl"))
        reqs = [e["payload"] for e in events if e["kind"] == "serve.request"]
        assert len(reqs) == 4
        for p in reqs:
            assert {"queue_wait_s", "assembly_s", "device_s",
                    "trace_id"} <= set(p)
            # the breakdown is consistent: queue wait never exceeds the
            # whole latency
            assert p["queue_wait_s"] <= p["latency_s"] + 1e-6
        # acceptance: the exported trace of one request shows the
        # request and the batch it rode in, phase by phase
        spans = [e["payload"] for e in events if e["kind"] == "trace.span"]
        tree = [s for s in spans if s["trace_id"] == results[0].trace_id]
        assert {s["name"] for s in tree} == {"request", "queue_wait"}
        root = next(s for s in tree if s["name"] == "request")
        assert all(s["parent_id"] == root["span_id"]
                   for s in tree if s["name"] != "request")
        batch = next(s for s in spans if s["span_id"] == root["batch"])
        phases = [s for s in spans if s["parent_id"] == batch["span_id"]]
        assert sorted(s["name"] for s in phases) == [
            "serve.complete", "serve.dispatch", "serve.fetch", "serve.pad"]
        # the phases follow one another inside the batch (dispatch is
        # single-threaded), and the wait ends where the batch begins
        phases.sort(key=lambda s: s["start_s"])
        assert [s["name"] for s in phases] == [
            "serve.pad", "serve.dispatch", "serve.fetch", "serve.complete"]
        for a, b in zip(phases, phases[1:]):
            assert b["start_s"] >= a["start_s"] + a["duration_s"] - 1e-6
        wait = next(s for s in tree if s["name"] == "queue_wait")
        assert wait["start_s"] + wait["duration_s"] == pytest.approx(
            batch["start_s"], abs=2e-6)
        assert len([s for s in spans if s["name"] == "request"]) == 4
        from tools.trace_export import spans_to_trace_events

        doc = spans_to_trace_events(events, trace_id=results[0].trace_id)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {
            "request", "queue_wait", "serve.batch",
            *(s["name"] for s in phases)}
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
        # the request on its trace's lane, its batch on the thread's
        lane = {e["name"]: e["tid"] for e in xs}
        assert lane["request"] == lane["queue_wait"] != lane["serve.batch"]
        assert lane["serve.batch"] == lane["serve.pad"]
        # the ledger priced the warmed bucket: roofline known, and MFU
        # joined in from the (fenced) execute times of real batches
        perf = [e["payload"] for e in events if e["kind"] == "perf.summary"]
        assert perf, "no perf.summary emitted by the serve path"
        rows = [r for r in perf[-1]["detail"] if r["name"] == "serve_predict"]
        assert rows and rows[0]["roofline"] in ("compute", "memory")
        assert any(r["mfu"] is not None for r in rows)

    def test_breakdown_absent_without_tracer_is_still_consistent(
            self, small_engine):
        """No spans armed: serve.request still carries the breakdown (it
        comes from the batcher's stamps, not the tracer), results resolve
        identically."""
        svc = CountService(small_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)))
        svc.warmup([(64, 64)])
        with svc:
            res = svc.predict(np.zeros((64, 64, 3), np.float32),
                              timeout=60.0)
        assert res.queue_wait_s is not None and res.trace_id


class TestStepTimerRecord:
    def test_record_feeds_reservoir_like_stop(self):
        from can_tpu.utils import StepTimer

        t = StepTimer(skip_first=1)
        t.record(10.0)          # skipped (compile-window convention)
        t.record(0.2, shape=(64, 64))
        t.record(0.4, shape=(64, 64))
        p = t.percentiles()
        assert p["n"] == 2 and p["max_s"] == 0.4
        assert t.shape_summary()["(64, 64)"]["n"] == 2


class TestServeCLIValidation:
    """cli/serve.py arg plumbing + the corrected --checkpoint-dir sentinel
    (ADVICE r5) it shares with cli/test.py."""

    def test_bucket_shapes_parse(self):
        from can_tpu.cli.serve import parse_bucket_shapes

        assert parse_bucket_shapes("384x512, 512x768") == [(384, 512),
                                                           (512, 768)]
        with pytest.raises(Exception):
            parse_bucket_shapes("100x100")  # not /8
        with pytest.raises(Exception):
            parse_bucket_shapes("no")

    @pytest.mark.parametrize("flag,value", [("--flush-policy", "timer"),
                                            ("--menu-budget", "1"),
                                            ("--dispatch-order", "fifo")])
    def test_a_retired_selector_is_refused(self, flag, value, capsys):
        """The serving path's A/B arms are chosen by the code (the request
        kinds' ``cost_unit``), not by a user: the parser no longer knows
        the three flags that selected them."""
        from can_tpu.cli.serve import parse_args

        with pytest.raises(SystemExit) as e:
            parse_args([flag, value])
        assert e.value.code == 2
        assert f"unrecognized arguments: {flag}" in capsys.readouterr().err

    def test_checkpoint_dir_sentinel_conflicts(self, tmp_path):
        """An EXPLICIT --checkpoint-dir ./checkpoints alongside --torch-pth
        must now conflict (it used to slip through the literal-string
        check), and the default still resolves when no flag was given."""
        from can_tpu.cli.serve import main as serve_main
        from can_tpu.cli.test import parse_args, validate_params_source

        pth = tmp_path / "w.pth"
        pth.write_bytes(b"x")
        with pytest.raises(SystemExit):
            serve_main(["--torch-pth", str(pth),
                        "--checkpoint-dir", "./checkpoints"])
        with pytest.raises(SystemExit):
            validate_params_source(parse_args(
                ["--torch-pth", str(pth),
                 "--checkpoint-dir", "./checkpoints"]))
        args = parse_args([])
        validate_params_source(args)
        assert args.checkpoint_dir == "./checkpoints"  # default resolves
        args = parse_args(["--torch-pth", str(pth)])
        validate_params_source(args)  # torch-pth alone: fine
