"""Telemetry subsystem tests: bus/sinks, sources, trace window, report.

The tier-1 contract pinned here: a synthetic 5-step run through the JSONL
sink round-trips into tools/telemetry_report.py's summary with every event
kind present and the right aggregates — the same schema the train/test
CLIs and bench entry points write.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from can_tpu import obs


def fake_train_step(state, batch):
    return state, {"loss": 1.0, "num_valid": float(batch["image"].shape[0])}


def make_batches(n=5, tall_from=3):
    """n fake device batches, two distinct shapes (recompile at tall_from)."""
    out = []
    for i in range(n):
        h = 16 if i >= tall_from else 8
        out.append({"image": np.zeros((2, h, 8, 3), np.float32),
                    "sample_mask": np.ones((2,), np.float32)})
    return out


class TestBusAndSinks:
    def test_jsonl_schema_roundtrip(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)], host_id=3)
        tel.emit("compile", step=7, seconds=1.25, signature=[["image", [2, 8]]])
        tel.emit("heartbeat", uptime_s=0.0)
        tel.close()
        events = [json.loads(l) for l in open(path)]
        assert [e["kind"] for e in events] == ["compile", "heartbeat"]
        for e in events:
            assert set(e) == {"ts", "kind", "step", "host_id", "payload"}
            assert e["host_id"] == 3
        assert events[0]["step"] == 7 and events[0]["payload"]["seconds"] == 1.25

    def test_numpy_payloads_are_jsonable(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        tel.emit("epoch", loss=np.float32(2.5), n=np.int64(4),
                 arr=np.arange(3))
        tel.close()
        e = json.loads(open(path).read())
        assert e["payload"] == {"loss": 2.5, "n": 4, "arr": [0, 1, 2]}

    def test_stdout_sink(self, capsys):
        tel = obs.Telemetry([obs.StdoutSink()])
        tel.emit("stall", step=4, seconds=0.5)
        assert "[telemetry] stall step 4" in capsys.readouterr().out
        tel.close()

    def test_metric_logger_sink_forwards_epoch_scalars_only(self, capsys):
        from can_tpu.utils import MetricLogger

        tel = obs.Telemetry([obs.MetricLoggerSink(MetricLogger())])
        tel.emit("epoch", step=2, train_loss=1.5, buckets="8x8",
                 distinct_shapes=2)
        tel.emit("step_window", step=3, samples_s=[0.1])  # filtered kind
        out = capsys.readouterr().out
        assert "step 2" in out and "train_loss=1.5" in out
        assert "distinct_shapes=2" in out
        assert "buckets" not in out  # non-scalar payload never reaches wandb
        assert "step 3" not in out

    def test_broken_sink_is_kept_and_retried_not_fatal(self, tmp_path,
                                                       capsys):
        class Flaky:
            fails = 2  # transient: first two emits raise, then recovers

            def __init__(self):
                self.got = []

            def emit(self, event):
                if len(self.got) == 0 and self.fails > 0:
                    Flaky.fails -= 1
                    raise OSError("transient")
                self.got.append(event)

            def close(self):
                pass

        flaky = Flaky()
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([flaky, obs.JsonlSink(path)])
        tel.emit("heartbeat")
        tel.emit("heartbeat")
        tel.emit("heartbeat")  # sink recovered: must receive this one
        tel.close()
        out = capsys.readouterr().out
        # one warning per failure streak, not per event; sink NOT dropped
        assert out.count("kept — will retry") == 1
        assert len(flaky.got) == 1
        assert len(obs.read_events(path)) == 3  # healthy sink got all

    def test_open_host_telemetry_names_per_host_file(self, tmp_path):
        tel = obs.open_host_telemetry(str(tmp_path), host_id=2)
        tel.emit("run", config={})
        tel.close()
        assert (tmp_path / "telemetry.host2.jsonl").is_file()


class TestRecompileTracker:
    def test_one_compile_event_per_signature(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        step = obs.RecompileTracker(fake_train_step, tel, name="s")
        for b in make_batches(6, tall_from=3):
            step(None, b)
        # re-wrapping (a new epoch) must NOT re-attribute known signatures
        step2 = obs.RecompileTracker(fake_train_step, tel, name="s")
        for b in make_batches(6, tall_from=3):
            step2(None, b)
        tel.close()
        compiles = [e for e in obs.read_events(path) if e["kind"] == "compile"]
        assert len(compiles) == 2  # two shapes, counted once across epochs
        assert compiles[0]["payload"]["n_signatures"] == 1
        assert compiles[1]["payload"]["n_signatures"] == 2
        assert compiles[0]["payload"]["seconds"] >= 0

    def test_last_first_call_is_the_calling_threads_own(self):
        """Two serve launches may be in progress on two threads: each asks
        whether ITS call compiled, whatever the other's did since."""
        import threading

        tel = obs.Telemetry()
        step = obs.RecompileTracker(fake_train_step, tel, name="per-thread")
        small, tall = make_batches(2, tall_from=1)
        step(None, small)
        assert step.last_first_call is True
        seen = {}

        def other():
            seen["before"] = step.last_first_call   # made no call yet
            step(None, small)
            seen["warm"] = step.last_first_call
            step(None, tall)
            seen["new"] = step.last_first_call

        t = threading.Thread(target=other)
        t.start()
        t.join(10.0)
        assert seen == {"before": False, "warm": False, "new": True}
        assert step.last_first_call is True    # this thread's, still

    def test_dtype_change_is_a_new_signature(self):
        from can_tpu.train import batch_signature

        f32 = {"image": np.zeros((2, 8, 8, 3), np.float32)}
        u8 = {"image": np.zeros((2, 8, 8, 3), np.uint8)}
        assert batch_signature(f32) != batch_signature(u8)
        assert batch_signature(f32) == batch_signature(
            {"image": np.ones((2, 8, 8, 3), np.float32)})


class TestStall:
    def test_slow_producer_accumulates_stall(self):
        from can_tpu.data import prefetch_to_device

        clock = obs.StallClock()
        out = list(prefetch_to_device(range(4), lambda x: (time.sleep(0.03), x)[1],
                                      depth=1, stall=clock))
        assert out == [0, 1, 2, 3]
        # consumer is instant, producer sleeps: nearly every wait blocks
        assert clock.seconds > 0.03
        assert clock.count >= 1

    def test_fast_producer_low_stall(self):
        from can_tpu.data import prefetch_to_device

        clock = obs.StallClock()
        gen = prefetch_to_device(range(8), lambda x: x, depth=2, stall=clock)
        for x in gen:
            time.sleep(0.005)  # consumer slower than producer
        # the overlapped loads must not be charged as stall
        assert clock.seconds < 0.02


class TestHeartbeatAndMemory:
    def test_heartbeat_emits_and_stops(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        hb = obs.Heartbeat(tel, interval_s=0.02)
        time.sleep(0.1)
        hb.close()
        n = len([e for e in obs.read_events(path) if e["kind"] == "heartbeat"])
        assert n >= 2  # immediate beat + at least one interval beat
        time.sleep(0.06)
        tel.close()
        assert len(obs.read_events(path)) == n  # closed: no more beats

    def test_heartbeat_nonpositive_interval_disables(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        hb = obs.Heartbeat(tel, interval_s=0)  # 0 = off, NOT a 10ms flood
        time.sleep(0.05)
        hb.close()
        tel.close()
        assert obs.read_events(path) == []

    def test_memory_snapshot_always_has_host_rss(self):
        snap = obs.device_memory_snapshot()
        assert snap["host_rss_mb"] is None or snap["host_rss_mb"] > 0
        assert isinstance(snap["devices"], list)  # CPU: stats-less entries

    def test_emit_memory_event(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        obs.emit_memory(tel, where="unit_test")
        tel.close()
        (e,) = obs.read_events(path)
        assert e["kind"] == "memory"
        assert e["payload"]["where"] == "unit_test"


class TestTraceWindow:
    def test_parse(self):
        assert obs.parse_trace_steps("") is None
        assert obs.parse_trace_steps("10:13") == (10, 13)
        for bad in ("10", "a:b", "5:5", "-1:3", "7:2"):
            with pytest.raises(ValueError):
                obs.parse_trace_steps(bad)

    def test_window_starts_and_stops_on_step_boundaries(self, tmp_path):
        calls = []

        class FakeProfiler:
            def start_trace(self, d, profiler_options=None):
                # the operator's options, never the profiler's defaults
                # (the host tracer's "Transpose" flood)
                assert profiler_options.host_tracer_level == 0
                assert profiler_options.python_tracer_level == 0
                calls.append(("start", d))

            def stop_trace(self):
                calls.append(("stop",))

        w = obs.StepTraceWindow(str(tmp_path), 2, 4, profiler=FakeProfiler())
        for step in range(1, 8):  # step_tick counts from 1
            w.on_step(step)
        w.close()
        assert calls == [("start", str(tmp_path)), ("stop",)]

    def test_close_flushes_open_window(self, tmp_path):
        calls = []

        class FakeProfiler:
            def start_trace(self, d, profiler_options=None):
                calls.append("start")

            def stop_trace(self):
                calls.append("stop")

        w = obs.StepTraceWindow(str(tmp_path), 0, 100, profiler=FakeProfiler())
        w.on_step(1)
        w.close()
        assert calls == ["start", "stop"]

    def test_telemetry_step_tick_drives_window(self, tmp_path):
        calls = []

        class FakeProfiler:
            def start_trace(self, d, profiler_options=None):
                calls.append("start")

            def stop_trace(self):
                calls.append("stop")

        w = obs.StepTraceWindow(str(tmp_path), 1, 2, profiler=FakeProfiler())
        tel = obs.Telemetry([], trace=w)
        for _ in range(4):
            tel.step_tick()
        tel.close()
        assert calls == ["start", "stop"]


class TestReportRoundTrip:
    """Tier-1 acceptance: synthetic 5-step run -> JSONL sink -> report."""

    def _run(self, tmp_path):
        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        hb = obs.Heartbeat(tel, interval_s=30)  # immediate beat only
        from can_tpu.train import train_one_epoch

        state, stats = train_one_epoch(
            fake_train_step, None, make_batches(5, tall_from=3),
            put_fn=lambda b: b, show_progress=False, check_every=2,
            telemetry=tel, epoch=0)
        tel.emit("epoch", step=0, train_loss=stats.loss,
                 img_per_s=stats.img_per_s,
                 distinct_shapes=stats.distinct_shapes)
        hb.close()
        tel.close()
        return os.path.join(str(tmp_path), "telemetry.host0.jsonl"), stats

    def test_all_kinds_present_and_summary_exact(self, tmp_path):
        path, stats = self._run(tmp_path)
        events = obs.read_events(path)
        kinds = {e["kind"] for e in events}
        assert {"compile", "step_window", "stall", "memory", "heartbeat",
                "epoch"} <= kinds
        s = obs.summarize(events)
        assert s["steps"] == 5 == stats.steps
        assert s["images"] == 10.0
        assert s["recompiles"] == 2 == stats.distinct_shapes
        assert s["epochs"] == 1
        assert s["heartbeats"] >= 1
        assert s["step_p50_s"] > 0 and s["step_p95_s"] >= s["step_p50_s"]
        assert s["step_max_s"] >= s["step_p95_s"]
        # compile first-calls are attributed by compile events and kept
        # OUT of the step samples (2 of the 5 steps were first calls)
        pooled = sum(len(e["payload"].get("samples_s", []))
                     for e in events if e["kind"] == "step_window")
        assert pooled == 3
        # the table renders every row without raising
        table = obs.format_report(s)
        assert "recompiles" in table and "input stall" in table

    def test_report_tool_cli(self, tmp_path):
        path, _ = self._run(tmp_path)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tool = os.path.join(repo, "tools", "telemetry_report.py")
        out = subprocess.run([sys.executable, tool, "--json", str(tmp_path)],
                             capture_output=True, text=True, cwd=repo,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        summary = json.loads(out.stdout.strip())
        assert summary["steps"] == 5
        assert summary["by_kind"]["compile"] == 2
        # human table mode too
        out = subprocess.run([sys.executable, tool, path],
                             capture_output=True, text=True, cwd=repo,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        assert "step p95" in out.stdout

    def test_torn_tail_line_is_skipped(self, tmp_path):
        path, _ = self._run(tmp_path)
        with open(path, "a") as f:
            f.write('{"ts": 1, "kind": "memo')  # killed mid-write
        s = obs.summarize(obs.read_events(path))
        assert s["steps"] == 5  # still summarizes

    def test_data_pipeline_kinds_summarized(self, tmp_path):
        """data.prepared (per-split store status) and data.cache
        (cumulative decoded-item counters; the LAST event wins) land in
        the summary and the table — the host-pipeline subsystem's
        telemetry contract."""
        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        tel.emit("data.prepared", split="train", mode="auto", active=True,
                 root="/d/prepared", reason=None)
        tel.emit("data.prepared", split="test", mode="auto", active=False,
                 root="/d2/prepared", reason="no prepared store")
        for epoch, (hits, misses) in enumerate([(0, 10), (8, 12)]):
            tel.emit("data.cache", step=epoch, hits=hits, misses=misses,
                     hit_rate=hits / max(hits + misses, 1), inserts=misses,
                     evictions=0, oversize_skips=0, items=misses,
                     bytes=123456, capacity_bytes=10**9)
        tel.close()
        s = obs.summarize(obs.read_events(
            os.path.join(str(tmp_path), "telemetry.host0.jsonl")))
        assert s["prepared_splits"] == {
            "train": "on", "test": "legacy(no prepared store)"}
        assert s["cache_hits"] == 8 and s["cache_misses"] == 12
        assert s["cache_hit_rate"] == 0.4
        assert s["cache_bytes"] == 123456
        table = obs.format_report(s)
        assert "prepared store" in table and "item cache" in table
        # offline runs: no data.* rows, no Nones rendered
        s0 = obs.summarize([])
        assert s0["cache_hits"] is None and s0["prepared_splits"] == {}
        assert "item cache" not in obs.format_report(s0)


class TestEvaluateTelemetry:
    def test_eval_loop_emits_windows_and_stall(self, tmp_path):
        from can_tpu.train import evaluate

        def fake_eval_step(params, batch, batch_stats=None):
            n = float(batch["image"].shape[0])
            return {"abs_err_sum": 1.0, "sq_err_sum": 1.0, "num_valid": n}

        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        metrics = evaluate(fake_eval_step, None, make_batches(4, tall_from=2),
                           put_fn=lambda b: b, dataset_size=8,
                           check_every=2, telemetry=tel)
        tel.close()
        assert metrics["num_images"] == 8
        events = obs.read_events(
            os.path.join(str(tmp_path), "telemetry.host0.jsonl"))
        kinds = [e["kind"] for e in events]
        assert kinds.count("compile") == 2
        assert kinds.count("stall") == 1
        assert any(e["kind"] == "step_window"
                   and e["payload"].get("phase") == "eval" for e in events)
