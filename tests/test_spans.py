"""The span recorder (``can_tpu/obs/spans.py``) and its sites: the serve
batcher thread's cycle, the engine's dispatch and fetch, the input
pipeline's load / put / wait, the train loop's dispatch and turnover."""

import collections
import threading
import time

import numpy as np
import pytest

from can_tpu import obs
from can_tpu.obs import spans as spans_mod
from can_tpu.obs.spans import SpanTracer, active, install, self_time, uninstall
from can_tpu.serve import CountService, ServeEngine


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


@pytest.fixture
def installed():
    tr = install(SpanTracer(prefix="t"))
    try:
        yield tr
    finally:
        uninstall()


def by_name(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s["name"]].append(s)
    return out


def union_length(intervals):
    total, edge = 0.0, float("-inf")
    for s, e in sorted(intervals):
        s = max(s, edge)
        if e > s:
            total += e - s
            edge = e
    return total


# --- the recorder -------------------------------------------------------
class TestRecorder:
    def test_ring_is_bounded_and_ordered(self):
        tr = SpanTracer(prefix="t", capacity=4)
        for i in range(10):
            tr.emit(trace_id="x", name=f"s{i}", start=i, end=i + 1)
        assert [s["name"] for s in tr.snapshot()] == ["s6", "s7", "s8", "s9"]

    def test_works_with_no_telemetry_and_emits_with_one(self):
        alone = SpanTracer(prefix="a")
        with alone.span("one"):
            pass
        assert [s["name"] for s in alone.snapshot()] == ["one"]
        sink = ListSink()
        tr = SpanTracer(obs.Telemetry([sink]), prefix="b")
        with tr.span("two", n=3):
            pass
        (event,) = [e for e in sink.events if e["kind"] == "trace.span"]
        assert event["payload"] == tr.snapshot()[0]
        assert event["payload"]["n"] == 3

    def test_parent_and_trace_ids_follow_the_thread(self):
        tr = SpanTracer(prefix="t")
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert tr.current() is inner
            with tr.span("rooted", trace_id="other") as rooted:
                pass
            def elsewhere():
                with tr.span("elsewhere"):
                    pass

            t = threading.Thread(target=elsewhere, name="another")
            t.start()
            t.join(10.0)
            assert not t.is_alive()
        assert tr.current() is None
        s = {x["name"]: x for x in tr.snapshot()}
        assert s["inner"]["parent_id"] == s["outer"]["span_id"]
        assert s["inner"]["trace_id"] == s["outer"]["trace_id"]
        # a new trace rooted under a parent of another trace
        assert s["rooted"]["parent_id"] == s["outer"]["span_id"]
        assert s["rooted"]["trace_id"] == "other"
        # the open-span stack is per thread
        assert s["elsewhere"]["parent_id"] is None
        assert s["elsewhere"]["trace_id"] != s["outer"]["trace_id"]
        assert s["elsewhere"]["thread"] == "another"
        assert s["outer"]["thread"] == threading.current_thread().name
        assert outer.span_id != inner.span_id != rooted.span_id

    def test_a_begun_span_ends_on_another_thread(self):
        """``begin()`` / ``under()`` / ``finish()``: the span of a launch
        that one thread assembles and another runs."""
        tr = SpanTracer(prefix="t")
        with tr.span("cycle") as cycle:
            batch = tr.span("batch", trace_id="its-own", valid=2).begin()
            assert tr.current() is cycle   # begun, not opened here
            with batch.under():
                with tr.span("pad"):
                    pass
            assert tr.current() is cycle

        def lane():
            with batch.under(), tr.span("dispatch"):
                pass
            assert tr.current() is None
            batch.finish()

        t = threading.Thread(target=lane, name="a-lane")
        t.start()
        t.join(10.0)
        s = {x["name"]: x for x in tr.snapshot()}
        assert s["batch"]["parent_id"] == s["cycle"]["span_id"]
        assert s["batch"]["trace_id"] == "its-own" and s["batch"]["valid"] == 2
        for phase in ("pad", "dispatch"):
            assert s[phase]["parent_id"] == s["batch"]["span_id"]
            assert s[phase]["trace_id"] == "its-own"
        # recorded on the lane of the thread that began it; its children
        # on theirs; it ends after the last of them
        assert s["batch"]["thread"] == threading.current_thread().name
        assert s["dispatch"]["thread"] == "a-lane"
        end = lambda x: x["start_s"] + x["duration_s"]  # noqa: E731
        assert s["batch"]["start_s"] <= s["pad"]["start_s"]
        assert end(s["batch"]) >= end(s["dispatch"]) - 1e-6
        assert self_time(s["batch"], [s["pad"], s["dispatch"]]) >= 0.0

    def test_both_ends_are_perf_counter(self):
        tr = SpanTracer(prefix="t")
        t0 = time.perf_counter()
        with tr.span("timed"):
            time.sleep(0.01)
        t1 = time.perf_counter()
        (s,) = tr.snapshot()
        assert t0 <= s["start_s"] <= s["start_s"] + s["duration_s"] <= t1 + 1e-6
        assert s["duration_s"] >= 0.009

    def test_a_span_that_raises_is_recorded_with_its_error(self):
        tr = SpanTracer(prefix="t")
        with pytest.raises(KeyError):
            with tr.span("bad"):
                raise KeyError("x")
        assert tr.snapshot()[0]["error"] == "KeyError"
        assert tr.current() is None

    def test_self_time_is_duration_less_the_children(self):
        span = {"span_id": "p", "start_s": 10.0, "duration_s": 1.0}
        kids = [{"start_s": 10.1, "duration_s": 0.2},
                {"start_s": 10.2, "duration_s": 0.3},   # overlaps the first
                {"start_s": 10.9, "duration_s": 0.5}]   # runs past the end
        assert self_time(span, kids) == pytest.approx(1.0 - 0.4 - 0.1)
        assert self_time(span, []) == 1.0

    def test_active_prefers_the_bus_then_the_installed_tracer(self):
        assert active() is None and active(obs.Telemetry([])) is None
        tel = obs.Telemetry([])
        tel.spans = SpanTracer(tel, prefix="bus")
        tr = install(SpanTracer(prefix="proc"))
        try:
            assert active() is tr and active(obs.Telemetry([])) is tr
            assert active(tel) is tel.spans
        finally:
            uninstall()
        assert active() is None


# --- serving ------------------------------------------------------------
class StubProgram:
    """In the jitted program's place: the engine's own ``predict_batch``
    (and its spans) runs around it."""

    last_first_call = False

    def __call__(self, params, batch, batch_stats):
        time.sleep(0.004)
        b, h, w, _ = batch["image"].shape
        return (np.arange(b, dtype=np.float32),
                np.zeros((b, h // 8, w // 8, 1), np.float32))


def stub_service(telemetry=None, **kw):
    engine = ServeEngine({"w": np.zeros((1,), np.float32)},
                         telemetry=telemetry)
    engine._predict = StubProgram()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 2.0)
    return CountService(engine, bucket_ladder=((64,), (64,)),
                        telemetry=telemetry, **kw)


IMG = np.zeros((64, 64, 3), np.float32)
BATCH_PHASES = ("serve.pad", "serve.dispatch", "serve.fetch",
                "serve.complete")


class TestServeSpans:
    def test_disarmed_sites_record_nothing(self):
        assert active() is None
        sink = ListSink()
        svc = stub_service(obs.Telemetry([sink]))
        with svc:
            svc.predict(IMG, timeout=30.0)
        assert not [e for e in sink.events if e["kind"] == "trace.span"]
        # no stamp, no link: the request carries nothing of the tracer's
        req = svc.submit(IMG)._request
        assert req.t_trace is None and req.batch_span is None

    def test_each_batch_has_its_phases_once_under_the_right_parent(
            self, installed):
        svc = stub_service(flush_policy="timer", menu_budget=1)
        tickets = [svc.submit(IMG) for _ in range(4)]
        assert svc.batcher.run_once(0.0) == 1          # a full group
        tickets += [svc.submit(IMG) for _ in range(2)]
        assert svc.batcher.run_once(0.0) == 0
        time.sleep(0.004)
        assert svc.batcher.run_once(0.0) == 1          # a due group
        tickets.append(svc.submit(IMG))
        assert svc.batcher.intake() == 0               # pending, not due
        svc.close()                                    # drained
        assert all(t.result(1.0).count >= 0 for t in tickets)

        spans = installed.snapshot()
        ids = {s["span_id"]: s for s in spans}
        names = by_name(spans)
        batches = names["serve.batch"]
        assert [b["flush_reason"] for b in batches] == ["full", "due", "drain"]
        assert [ids[b["parent_id"]]["name"] for b in batches] == [
            "serve.intake", "serve.poll", "serve.drain"]
        assert [(b["slots"], b["valid"]) for b in batches] == [
            (4, 4), (4, 2), (4, 1)]
        assert len({b["trace_id"] for b in batches}) == 3
        for b in batches:
            kids = [s for s in spans if s["parent_id"] == b["span_id"]]
            assert sorted(k["name"] for k in kids) == sorted(BATCH_PHASES)
            assert {k["trace_id"] for k in kids} == {b["trace_id"]}
            assert 0.0 <= self_time(b, kids) < b["duration_s"]
            k = {k["name"]: k for k in kids}
            assert k["serve.pad"]["bytes"] == 4 * 64 * 64 * 3 * 4
            assert k["serve.dispatch"]["compiled"] is False
            assert k["serve.dispatch"]["aot"] is False
            assert k["serve.fetch"]["density"] is False
            assert k["serve.complete"]["resolved"] == b["valid"]
            assert k["serve.dispatch"]["duration_s"] >= 0.004
        # the counters at the same boundary, always on
        counted = collections.Counter(b["flush_reason"] for b in batches)
        assert svc.stats()["flush_reasons"] == dict(counted)
        # the first launch of the key made its staging buffer
        assert [s["reused"] for s in names["serve.pad"]] == [False, True, True]
        assert svc.stats()["staging"] == {"reused": 2, "fresh": 1,
                                          "bytes_held": 0}  # closed: released
        # the thread's own cycle lies on one lane
        cycle = names["serve.wait"] + names["serve.intake"] + names["serve.poll"]
        assert len({s["trace_id"] for s in cycle}) == 1
        assert sum(s["taken"] for s in names["serve.intake"]) == 7
        # a request: two spans of its own, linked to its batch
        reqs = names["request"]
        assert len(reqs) == 7 and len(names["queue_wait"]) == 7
        assert collections.Counter(r["batch"] for r in reqs) == {
            b["span_id"]: b["valid"] for b in batches}
        for q in names["queue_wait"]:
            r = ids[q["parent_id"]]
            b = ids[r["batch"]]
            assert q["start_s"] == r["start_s"]
            assert q["start_s"] + q["duration_s"] == pytest.approx(
                b["start_s"], abs=2e-6)
        assert not set(names) & {"batch_assembly", "device", "respond"}

    def test_flush_reasons_are_counted_with_tracing_off(self):
        svc = stub_service(flush_policy="timer", menu_budget=1)
        for _ in range(4):
            svc.submit(IMG)
        svc.batcher.run_once(0.0)
        svc.submit(IMG)
        svc.batcher.intake()
        svc.close()
        assert svc.stats()["flush_reasons"] == {"full": 1, "due": 0,
                                                "drain": 1}

    def test_flush_reasons_reach_the_scrape(self):
        from can_tpu.obs.exporter import render_stats

        text = render_stats({"batches": 3, "flush_reasons": {
            "full": 2, "due": 1, "drain": 0}})
        assert 'can_tpu_serve_flushes_total{reason="full"} 2' in text
        assert 'can_tpu_serve_flushes_total{reason="due"} 1' in text

    def test_named_spans_cover_the_batcher_threads_time(self, installed):
        svc = stub_service(max_batch=4)
        with svc:
            t_end = time.perf_counter() + 0.4
            while time.perf_counter() < t_end:
                tickets = [svc.submit(IMG) for _ in range(8)]
                for t in tickets:
                    t.result(30.0)
        names = by_name(installed.snapshot())
        batches = names["serve.batch"]
        assert len(batches) >= 10
        lo = min(b["start_s"] for b in batches)
        hi = max(b["start_s"] + b["duration_s"] for b in batches)
        cycle = [(max(s["start_s"], lo), min(s["start_s"] + s["duration_s"], hi))
                 for n in ("serve.wait", "serve.intake", "serve.poll")
                 for s in names[n]]
        assert union_length(cycle) >= 0.95 * (hi - lo)
        assert {s["thread"] for s in batches} == {"can-tpu-serve-batcher"}
        counted = collections.Counter(b["flush_reason"] for b in batches)
        assert svc.stats()["flush_reasons"] == {
            "full": counted["full"], "due": counted["due"],
            "drain": counted["drain"]}

    def test_the_bus_tracer_emits_the_same_spans(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])
        tel.spans = SpanTracer(tel, prefix="t")
        svc = stub_service(tel)
        with svc:
            svc.predict(IMG, timeout=30.0)
        emitted = [e["payload"] for e in sink.events
                   if e["kind"] == "trace.span"]
        assert emitted == tel.spans.snapshot()
        assert {"serve.batch", "request", "queue_wait",
                *BATCH_PHASES} <= {s["name"] for s in emitted}


# --- training -----------------------------------------------------------
def fake_step(state, batch):
    time.sleep(0.003)
    return state, {"loss": 1.0, "num_valid": float(batch["image"].shape[0])}


def slow_put(batch):
    time.sleep(0.006)   # slower than the step: the loop waits for input
    return batch


class TestTrainSpans:
    N = 7

    def run_epoch(self, telemetry=None):
        from can_tpu.data.batching import Batch
        from can_tpu.train import train_one_epoch

        batches = [Batch(np.ones((2, 8, 8, 3), np.float32),
                         np.zeros((2, 1, 1, 1), np.float32),
                         np.ones((2, 1, 1, 1), np.float32),
                         np.ones((2,), np.float32)) for _ in range(self.N)]

        def put(b):
            slow_put(b)
            return {"image": b.image, "sample_mask": b.sample_mask}

        return train_one_epoch(fake_step, None, batches, put_fn=put,
                               show_progress=False, check_every=3,
                               telemetry=telemetry, epoch=5)

    def test_one_epoch_with_no_telemetry(self, installed):
        _, stats = self.run_epoch()
        spans = installed.snapshot()
        names = by_name(spans)
        (root,) = names["train_epoch"]
        assert root["epoch"] == 5 and root["steps"] == self.N
        assert root["images"] == stats.images == 2 * self.N
        assert {s["trace_id"] for s in spans} == {root["trace_id"]}
        assert all(s["parent_id"] == root["span_id"]
                   for s in spans if s is not root)
        # one load and one put per batch, on the worker thread (the load
        # that found the iterator exhausted is recorded with its error)
        loads = [s for s in names["input.load"] if "error" not in s]
        assert [s["index"] for s in loads] == list(range(self.N))
        assert [s["index"] for s in names["input.put"]] == list(range(self.N))
        worker = {s["thread"] for s in loads + names["input.put"]}
        assert len(worker) == 1
        assert worker.pop().startswith("can-tpu-prefetch")
        assert all(s["bytes"] == 2 * 8 * 8 * 3 * 4 + 2 * 4 + 2 * 4 + 2 * 4
                   for s in names["input.put"])
        assert all(s["duration_s"] >= 0.006 for s in names["input.put"])
        # one dispatch per step, on the loop's thread
        assert len(names["train.dispatch"]) == self.N
        assert {s["program"] for s in names["train.dispatch"]} == {"2x8x8"}
        assert {s["thread"] for s in names["train.dispatch"]} == {
            threading.current_thread().name}
        # the epoch's start, up to the first batch
        (turn,) = names["train.turnover"]
        assert turn["start_s"] == root["start_s"] and turn["epoch"] == 5
        assert turn["duration_s"] >= 0.006
        assert (turn["start_s"] + turn["duration_s"]
                <= names["train.dispatch"][0]["start_s"] + 1e-6)
        # windows of 3, 3 and the trailing 1
        assert [s["steps"] for s in names["steps"]] == [3, 3, 1]
        assert len(names["metric_flush"]) == 3
        assert "fetch_stall" not in names

    def test_input_wait_is_what_the_stall_clock_sums(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])
        tel.spans = SpanTracer(tel, prefix="t")
        self.run_epoch(tel)
        (stall,) = [e["payload"] for e in sink.events if e["kind"] == "stall"]
        waits = by_name(tel.spans.snapshot())["input.wait"]
        assert waits and len(waits) == stall["count"]
        assert sum(w["duration_s"] for w in waits) == pytest.approx(
            stall["seconds"], abs=1e-5 * len(waits) + 1e-4)
        assert stall["seconds"] > 0.01   # the put is slower than the step

    def test_disarmed_loop_records_nothing(self):
        sink = ListSink()
        self.run_epoch(obs.Telemetry([sink]))
        assert spans_mod.active() is None
        assert not [e for e in sink.events if e["kind"] == "trace.span"]

    def test_a_prefetcher_alone_roots_a_trace_of_its_own(self, installed):
        """``evaluate()``'s case: the input.* spans, under no epoch."""
        from can_tpu.data.prefetch import prefetch_to_device

        out = list(prefetch_to_device(range(3), lambda b: b * 2, depth=2,
                                      tracer=installed))
        assert out == [0, 2, 4]
        names = by_name(installed.snapshot())
        assert len(names["input.put"]) == 3
        assert len({s["trace_id"] for s in installed.snapshot()}) == 1


# --- the operator's profile ---------------------------------------------
class TestOperatorProfile:
    def test_options_trace_the_device_alone(self):
        # the host tracer floods the serving path at level 1 as at 2
        opts = obs.trace.operator_profile_options()
        assert (opts.host_tracer_level, opts.python_tracer_level) == (0, 0)

    def test_serve_cli_takes_a_window_of_launched_batches(self):
        from can_tpu.cli import serve as cli
        from can_tpu.cli.train import validate_trace_args

        args = cli.parse_args(["--checkpoint-dir", "ck", "--profile-dir", "p",
                               "--trace-steps", "2:4"])
        assert validate_trace_args(args) == (2, 4)
        with pytest.raises(SystemExit, match="--profile-dir"):
            validate_trace_args(cli.parse_args(
                ["--checkpoint-dir", "ck", "--trace-steps", "2:4"]))

    def test_the_window_counts_launched_batches(self, tmp_path):
        calls = []

        class FakeProfiler:
            def start_trace(self, d, profiler_options=None):
                calls.append(("start", svc.stats()["batches"]))

            def stop_trace(self):
                calls.append(("stop", svc.stats()["batches"]))

        window = obs.StepTraceWindow(str(tmp_path), 1, 3,
                                     profiler=FakeProfiler())
        tel = obs.Telemetry([], trace=window)
        svc = stub_service(tel)
        for _ in range(5):
            for _ in range(4):
                svc.submit(IMG)
            assert svc.batcher.run_once(0.0) == 1
        svc.close()
        # armed as the second batch completes, stopped as the fourth does
        assert calls == [("start", 1), ("stop", 3)]

    def test_the_window_records_its_own_span(self, tmp_path):
        class FakeProfiler:
            def start_trace(self, d, profiler_options=None):
                pass

            def stop_trace(self):
                pass

        tr = SpanTracer(prefix="t")
        window = obs.StepTraceWindow(str(tmp_path), 0, 2,
                                     profiler=FakeProfiler(), spans=tr)
        t0 = time.perf_counter()
        for step in (1, 2, 3):
            window.on_step(step)
        (w,) = tr.snapshot()
        assert w["name"] == "profile.window" and w["log_dir"] == str(tmp_path)
        assert t0 <= w["start_s"] <= w["start_s"] + w["duration_s"] \
            <= time.perf_counter()

    def test_export_places_the_device_plane_by_the_last_program(self, tmp_path):
        """A profile of the device alone (three launches recorded on a
        v5e, PR 23) beside the run's spans: the last program ends where
        the last fetch inside the profile.window ends."""
        import gzip
        import os

        from tools.trace_export import load_device_planes, spans_to_trace_events

        src = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmark", "tests", "data",
                           "v5e_predict_b2_64x96_x3.xplane.pb.gz")
        pdir = tmp_path / "plugins" / "profile" / "t"
        pdir.mkdir(parents=True)
        (pdir / "x.xplane.pb").write_bytes(gzip.open(src, "rb").read())
        planes = load_device_planes(str(tmp_path))
        assert list(planes) == ["/device:TPU:0"]
        assert len(planes["/device:TPU:0"]["XLA Modules"]) == 3

        def span(name, start, dur, **kw):
            return {"ts": start, "kind": "trace.span", "step": None,
                    "host_id": 0,
                    "payload": {"trace_id": "t", "span_id": name + str(start),
                                "parent_id": None, "name": name,
                                "start_s": start, "duration_s": dur, **kw}}

        events = [span("serve.batch", 99.0, 0.1, thread="batcher"),
                  span("profile.window", 100.0, 1.0),
                  span("serve.fetch", 100.4, 0.1, thread="batcher"),
                  span("serve.fetch", 100.8, 0.1, thread="batcher"),
                  span("serve.fetch", 101.4, 0.1, thread="batcher")]
        doc = spans_to_trace_events(events, device_planes=planes)
        dev = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["cat"] == "device"]
        mods = [e for e in dev if e["tid"] == 1]
        assert len(mods) == 3 and len(dev) > 100
        # the document starts at 99.0; the anchor is the fetch ending at 100.9
        assert max(e["ts"] + e["dur"] for e in mods) == pytest.approx(
            1.9e6, abs=1.0)
        assert all(e["pid"] >= 1000 for e in dev)
        with pytest.raises(ValueError, match="profile.window"):
            spans_to_trace_events(events[:1], device_planes=planes)
