"""Performance-attribution layer tests: device peak table, ProgramCostLedger
(cost_analysis registration, MFU/roofline math, launch-cost fit), span
tracing, the Chrome trace export, and the contracts that keep the layer
honest:

* EVENT_KINDS drift: every ``kind=`` literal emitted anywhere in the tree
  is declared in ``obs/bus.py::EVENT_KINDS`` and vice versa (trace.span /
  perf.summary made this a recurring hazard);
* default runs produce a byte-identical lowered train step (no
  instrumentation can leak into the compiled program).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu import obs
from can_tpu.cli.common import (
    DevicePeaks,
    device_peaks_for_kind,
    local_device_peaks,
)
from can_tpu.obs.costs import ProgramCostLedger, extract_image_signature

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


def sig_of(batch):
    from can_tpu.train import batch_signature

    return batch_signature(batch)


# --- device peak table --------------------------------------------------
class TestDevicePeaks:
    def test_known_kinds_and_ordering(self):
        v5e = device_peaks_for_kind("TPU v5 lite")
        assert v5e.flops_bf16 == 197e12 and v5e.hbm_bytes_s == 819e9
        assert v5e.flops_f32 == v5e.flops_bf16 / 2
        assert not v5e.nominal
        # bare "TPU v5" is v5p, exactly like the HBM table's ordering
        assert device_peaks_for_kind("TPU v5").flops_bf16 == 459e12
        assert device_peaks_for_kind("TPU v4i").flops_bf16 == 138e12
        assert device_peaks_for_kind("TPU v4").flops_bf16 == 275e12
        assert device_peaks_for_kind("warp drive") is None

    def test_ridge_is_flops_over_bandwidth(self):
        p = device_peaks_for_kind("TPU v5e")
        assert p.ridge("bf16") == pytest.approx(197e12 / 819e9)
        assert p.ridge("f32") == pytest.approx(p.ridge("bf16") / 2)

    def test_cpu_backend_gets_labelled_nominal_peaks(self):
        p = local_device_peaks()  # tier-1 runs on the CPU backend
        assert p is not None and p.nominal and p.source == "nominal:cpu"


# --- the ledger ---------------------------------------------------------
def make_ledger(**kw):
    peaks = DevicePeaks(flops_bf16=2e12, flops_f32=1e12, hbm_bytes_s=1e11,
                        source="spec:test")
    return ProgramCostLedger(peaks=peaks, **kw)


class TestLedger:
    def test_mfu_roofline_and_rows(self):
        led = make_ledger(compute="f32")  # peak 1e12 FLOP/s, ridge 10
        sig = sig_of({"image": np.zeros((2, 100, 100, 3), np.float32)})
        # compute-bound: intensity 20 > ridge 10
        led.register("train_step", sig, cost=(2e9, 1e8))
        led.observe("train_step", (2, 100, 100, 3), seconds=0.02, n=5)
        (row,) = led.rows()
        assert row["roofline"] == "compute"
        assert row["intensity"] == pytest.approx(20.0)
        # mfu = flops / (mean_s * peak) = 2e9 / (0.004 * 1e12) = 0.5
        assert row["mfu"] == pytest.approx(0.5)
        assert row["launches"] == 5 and row["pixels"] == 2 * 100 * 100
        s = led.summary()
        assert s["mfu_weighted"] == pytest.approx(0.5)
        assert s["roofline_compute_bound"] == 1
        assert s["peak_nominal"] == 0

    def test_memory_bound_and_unknown_classes(self):
        led = make_ledger(compute="f32")
        sig_a = sig_of({"image": np.zeros((1, 64, 64, 3), np.float32)})
        sig_b = sig_of({"image": np.zeros((1, 32, 32, 3), np.float32)})
        led.register("s", sig_a, cost=(1e6, 1e6))   # intensity 1 < ridge
        led.register("s", sig_b, cost=None)          # backend said nothing
        s = led.summary()
        assert s["roofline_memory_bound"] == 1
        assert s["roofline_unknown"] == 1
        assert "mfu_weighted" not in s  # nothing timed yet

    def test_launch_cost_fit_recovers_planted_overhead(self):
        # mean_s = px / 50 Mpx/s + 1 ms  =>  empirical cost = 0.05 Mpx
        led = make_ledger(plan_launch_cost_px=0.05e6)
        a, b = 1.0 / 50e6, 1e-3
        for batch, n in ((1, 10), (4, 10)):
            shape = (batch, 1000, 1000, 3)
            px = batch * 1000 * 1000
            sig = sig_of({"image": np.zeros(shape, np.float32)})
            led.register("train_step", sig, cost=(1.0, 1.0))
            led.observe("train_step", shape, seconds=(a * px + b) * n, n=n)
        fit = led.launch_cost_fit()
        assert fit["rate_mpx_s"] == pytest.approx(50.0, rel=1e-3)
        assert fit["launch_cost_mpx_empirical"] == pytest.approx(0.05,
                                                                 rel=1e-3)
        assert fit["launch_cost_drift"] == pytest.approx(1.0, rel=1e-3)

    def test_summary_fit_is_per_family_not_pooled(self):
        """train_step (fwd+bwd) and eval_step (fwd-only) have ~3x
        different seconds-per-pixel rates; pooling them into one
        regression manufactures drift.  Both families here carry the
        EXACT planned 1 ms overhead — the reported drift must be 1.0."""
        led = make_ledger(plan_launch_cost_px=0.05e6)
        b = 1e-3  # true per-launch overhead; 0.05 Mpx at 50 Mpx/s
        for name, rate in (("train_step", 50e6), ("eval_step", 150e6)):
            for batch in (1, 2, 4):
                shape = (batch, 1000, 1000, 3)
                px = batch * 1000 * 1000
                led.register(name, sig_of(
                    {"image": np.zeros(shape, np.float32)}),
                    cost=(1.0, 1.0))
                led.observe(name, shape, (px / rate + b) * 5, n=5)
        s = led.summary()
        # the drift gauge must come from the family the planner prices
        # (the Mpx unit is family-relative: 1 ms is 0.05 Mpx at train's
        # 50 Mpx/s but 0.15 Mpx at eval's rate)
        assert s["launch_cost_fit_name"] == "train_step"
        assert s["launch_cost_drift"] == pytest.approx(1.0, rel=1e-3)
        assert s["rate_mpx_s"] == pytest.approx(50.0, rel=1e-3)

    def test_partial_cost_analysis_omits_missing_keys(self):
        """A backend reporting only bytes must not put flops=None into
        the compile payload (downstream numeric consumers choke)."""
        led = make_ledger()
        sig = sig_of({"image": np.zeros((1, 8, 8, 3), np.float32)})
        out = led.register("s", sig, cost=(None, 1234.0))
        assert out == {"bytes_accessed": 1234.0}
        assert led.register("s2", sig, cost=(None, None)) is None

    def test_fit_needs_two_distinct_sizes(self):
        led = make_ledger()
        sig = sig_of({"image": np.zeros((1, 10, 10, 3), np.float32)})
        led.register("s", sig, cost=(1.0, 1.0))
        led.observe("s", (1, 10, 10, 3), 0.5, n=2)
        assert led.launch_cost_fit() is None

    def test_observe_disambiguates_dtype(self):
        led = make_ledger()
        f32 = sig_of({"image": np.zeros((1, 8, 8, 3), np.float32)})
        u8 = sig_of({"image": np.zeros((1, 8, 8, 3), np.uint8)})
        led.register("p", f32, cost=(1.0, 1.0))
        led.register("p", u8, cost=(2.0, 2.0))
        led.observe("p", (1, 8, 8, 3), 0.1, dtype="uint8")
        rows = {r["dtype"]: r for r in led.rows()}
        assert rows["uint8"]["launches"] == 1
        assert rows["float32"]["launches"] == 0

    def test_unfenced_timings_need_min_launches(self):
        """Dispatch-biased (train-loop) samples must not synthesize MFU
        at low launch counts — the r9 bring-up's 600x-MFU artifact."""
        from can_tpu.obs.costs import MIN_UNFENCED_LAUNCHES

        led = make_ledger(compute="f32")
        sig = sig_of({"image": np.zeros((1, 100, 100, 3), np.float32)})
        led.register("train_step", sig, cost=(1e9, 1e7))
        led.observe("train_step", (1, 100, 100, 3), 1e-6, n=1,
                    fenced=False)  # absurdly short dispatch interval
        (row,) = led.rows()
        assert not row["timing_reliable"] and row["mfu"] is None
        assert row["mean_s"] is not None  # the raw number still reported
        led.observe("train_step", (1, 100, 100, 3), 0.01,
                    n=MIN_UNFENCED_LAUNCHES - 1, fenced=False)
        (row,) = led.rows()
        assert row["timing_reliable"] and row["mfu"] is not None
        # fenced (serve) timings are honest at n=1
        led2 = make_ledger(compute="f32")
        led2.register("serve_predict", sig, cost=(1e9, 1e7))
        led2.observe("serve_predict", (1, 100, 100, 3), 0.002, n=1)
        assert led2.rows()[0]["mfu"] is not None

    def test_extract_image_signature_fallback(self):
        sig = sig_of({"x": np.zeros((4, 4), np.float32),
                      "big": np.zeros((8, 8, 8), np.float32)})
        shape, dtype = extract_image_signature(sig)
        assert shape == (8, 8, 8) and dtype == "float32"

    def test_recompile_tracker_registers_real_cost_analysis(self):
        """The compile event carries XLA's flops/bytes when a ledger is on
        the bus — the CPU backend reports cost_analysis, so this is the
        real path, not a stub."""
        sink = ListSink()
        tel = obs.Telemetry([sink])
        tel.ledger = led = make_ledger()
        step = obs.RecompileTracker(
            jax.jit(lambda s, b: (s, {"loss": b["image"].sum()})),
            tel, name="train_step")
        batch = {"image": jnp.ones((2, 16, 16, 3), jnp.float32)}
        step(None, batch)
        step(None, batch)  # second call: no new compile event
        compiles = [e for e in sink.events if e["kind"] == "compile"]
        assert len(compiles) == 1
        assert compiles[0]["payload"]["flops"] > 0
        assert compiles[0]["payload"]["bytes_accessed"] > 0
        (row,) = led.rows()
        assert row["flops"] == compiles[0]["payload"]["flops"]

    def test_ledger_off_keeps_compile_payload_unchanged(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])  # no ledger armed
        step = obs.RecompileTracker(
            jax.jit(lambda s, b: (s, b["image"].sum())), tel, name="s")
        step(None, {"image": jnp.ones((1, 8, 8, 3))})
        (e,) = [e for e in sink.events if e["kind"] == "compile"]
        assert set(e["payload"]) == {"name", "signature", "seconds",
                                     "n_signatures"}


# --- spans --------------------------------------------------------------
class TestSpanTracer:
    def test_emit_schema_and_tree(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])
        tr = obs.SpanTracer(tel, prefix="t")
        tid = tr.new_trace_id("req")
        root = tr.new_span_id()
        tr.emit(trace_id=tid, name="queue_wait", start=1.0, end=1.5,
                parent_id=root)
        tr.emit(trace_id=tid, name="request", start=1.0, end=2.0,
                span_id=root, ok=True)
        spans = [e["payload"] for e in sink.events
                 if e["kind"] == "trace.span"]
        assert len(spans) == 2
        child, parent = spans
        assert child["parent_id"] == parent["span_id"] == root
        assert child["trace_id"] == parent["trace_id"] == tid
        assert child["duration_s"] == pytest.approx(0.5)
        assert parent["start_s"] == 1.0 and parent["ok"] is True
        # negative durations (clock skew) clamp to zero, never negative
        sid = tr.emit(trace_id=tid, name="skew", start=2.0, end=1.0)
        assert sink.events[-1]["payload"]["duration_s"] == 0.0
        assert sid != root


# --- Chrome trace export ------------------------------------------------
def _span_event(trace_id, span_id, name, start, dur, parent=None, host=0):
    return {"ts": start, "kind": "trace.span", "step": None,
            "host_id": host,
            "payload": {"trace_id": trace_id, "span_id": span_id,
                        "parent_id": parent, "name": name,
                        "start_s": start, "duration_s": dur}}


class TestTraceExport:
    def make_events(self):
        return [
            _span_event("t1", "r1", "request", 10.0, 1.0),
            _span_event("t1", "c1", "queue_wait", 10.0, 0.25, parent="r1"),
            _span_event("t1", "c2", "serve.fetch", 10.5, 0.5, parent="r1"),
            _span_event("t2", "r2", "request", 10.2, 0.3, host=1),
        ]

    def test_chrome_schema_and_normalisation(self):
        from tools.trace_export import spans_to_trace_events

        doc = spans_to_trace_events(self.make_events())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(xs) == 4 and len(metas) == 2  # one lane per trace_id
        for e in xs:
            assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                    "args"} <= set(e)
            assert e["ts"] >= 0 and e["dur"] >= 0
        # micros, normalised to the earliest span
        root = next(e for e in xs if e["args"]["span_id"] == "r1")
        assert root["ts"] == 0.0 and root["dur"] == 1e6
        child = next(e for e in xs if e["args"]["span_id"] == "c2")
        assert child["ts"] == 0.5e6
        assert child["args"]["parent_id"] == "r1"
        # hosts keep distinct pids, traces distinct tids
        other = next(e for e in xs if e["args"]["span_id"] == "r2")
        assert other["pid"] == 1 and other["tid"] != root["tid"]

    def test_trace_id_filter(self):
        from tools.trace_export import spans_to_trace_events

        doc = spans_to_trace_events(self.make_events(), trace_id="t2")
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["args"]["trace_id"] for e in xs] == ["t2"]

    def test_multi_host_clock_epochs_normalised_per_host(self):
        """start_s is the emitter's process-local monotonic epoch, so a
        2-host export must anchor per host (re-aligned via the bus wall
        ``ts``), not to a global min — else one host's lane lands a
        clock-epoch difference (hours/days) off-screen."""
        from tools.trace_export import spans_to_trace_events

        events = [
            # host 0: monotonic epoch near 10 s, wall clock 1000.0
            dict(_span_event("t1", "r1", "request", 10.0, 1.0), ts=1000.0),
            # host 1: epoch near 7 DAYS, wall clock only 0.5 s later
            dict(_span_event("t2", "r2", "request", 604800.0, 1.0, host=1),
                 ts=1000.5),
        ]
        doc = spans_to_trace_events(events)
        xs = {e["args"]["span_id"]: e for e in doc["traceEvents"]
              if e["ph"] == "X"}
        assert xs["r1"]["ts"] == 0.0
        # host 1 sits at its 0.5 s wall-clock offset, not at 604790 s
        assert xs["r2"]["ts"] == 0.5e6

    def test_cli_round_trip(self, tmp_path):
        """JSONL -> tool -> valid Chrome trace JSON, end to end."""
        path = tmp_path / "telemetry.host0.jsonl"
        with open(path, "w") as f:
            for e in self.make_events():
                f.write(json.dumps(e) + "\n")
        out = tmp_path / "out.trace.json"
        tool = os.path.join(REPO, "tools", "trace_export.py")
        r = subprocess.run([sys.executable, tool, str(path),
                            "--out", str(out)],
                           capture_output=True, text=True, cwd=REPO,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stderr
        doc = json.load(open(out))
        assert sum(e["ph"] == "X" for e in doc["traceEvents"]) == 4
        # a spanless file is an error, not an empty artifact
        empty = tmp_path / "empty.jsonl"
        empty.write_text(json.dumps({"ts": 1, "kind": "heartbeat",
                                     "step": None, "host_id": 0,
                                     "payload": {}}) + "\n")
        r = subprocess.run([sys.executable, tool, str(empty)],
                           capture_output=True, text=True, cwd=REPO,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 1


# --- EVENT_KINDS drift --------------------------------------------------
class TestEventKindsDrift:
    def test_emit_literals_match_declared_kinds_both_ways(self):
        """Every ``.emit("<kind>", ...)`` literal in the library, bench
        entry points, and tools is declared in EVENT_KINDS — and every
        declared kind has at least one emitter.  The scan is the source
        linter's EMITKIND rule (can_tpu/analysis/source_lint.py — the
        grep this test hand-rolled is deleted; one implementation, this
        test is the thin assertion), cross-checked against the imported
        EVENT_KINDS so the linter's AST parse of obs/bus.py can't drift
        from the real tuple either."""
        from can_tpu.analysis import source_lint

        assert len(source_lint.default_paths(REPO)) > 40  # found the tree
        undeclared, unemitted = source_lint.emit_kind_drift(REPO)
        assert undeclared == {}, (
            f"emitted but not in EVENT_KINDS: {undeclared}")
        assert unemitted == [], (
            f"declared but never emitted: {unemitted}")
        kinds, _ = source_lint.declared_event_kinds(REPO)
        assert tuple(kinds) == tuple(obs.EVENT_KINDS)


# --- default-run byte identity ------------------------------------------
def tiny_apply(params, image, compute_dtype=None):
    x = image if compute_dtype is None else image.astype(compute_dtype)
    x = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 8, 8, 1), (1, 8, 8, 1), "VALID")


class TestDefaultLoweredStepByteIdentity:
    def test_default_train_step_lowering_is_byte_identical(self):
        """Acceptance pin: a default run (telemetry=None — no ledger, no
        spans, no health metrics) lowers the EXACT same program text,
        build after build; and the pin has teeth — the one legitimate
        program-changing knob (health_metrics) produces different text."""
        from can_tpu.train import (
            create_train_state,
            make_lr_schedule,
            make_optimizer,
            make_train_step,
        )
        from can_tpu.train.loop import _arm_telemetry

        opt = make_optimizer(make_lr_schedule(1e-3))
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(3, 3, 3, 1)),
                                   jnp.float32)}
        state = create_train_state(params, opt)
        batch = {
            "image": jnp.zeros((2, 16, 16, 3), jnp.float32),
            "dmap": jnp.zeros((2, 2, 2, 1), jnp.float32),
            "pixel_mask": jnp.ones((2, 2, 2, 1), jnp.float32),
            "sample_mask": jnp.ones((2,), jnp.float32),
        }

        def lowered_text(**kw):
            step = jax.jit(make_train_step(tiny_apply, opt, **kw))
            return step.lower(state, batch).as_text()

        base = lowered_text()
        # telemetry=None arms NOTHING: the loop uses the callable as-is
        armed, timer, stall = _arm_telemetry(None, object(), name="t")
        assert timer is None and stall is None
        assert lowered_text() == base  # byte-identical rebuild
        assert lowered_text(health_metrics=True) != base  # pin has teeth


# --- loop integration ---------------------------------------------------
def fake_step(state, batch):
    # step time proportional to pixels (25ms/51ms for the two shapes):
    # the launch-cost fit needs a robustly POSITIVE pixels->seconds slope,
    # and an instant step would leave it to scheduler noise (flaky)
    b, h, w = batch["image"].shape[:3]
    import time as _time

    _time.sleep(b * h * w * 2e-4)  # 25.6ms / 51.2ms: >> scheduler noise
    return state, {"loss": 1.0, "num_valid": float(batch["image"].shape[0])}


class TestLoopPerfTelemetry:
    def run_epoch(self, tel):
        from can_tpu.train import train_one_epoch

        # 6 steps per shape: 1 first-call compile + 5 recorded launches
        # >= MIN_UNFENCED_LAUNCHES, so both programs' (dispatch-biased)
        # means qualify for MFU and the two-point launch-cost fit
        batches = [{"image": np.ones((2, 8 if i < 6 else 16, 8, 3),
                                     np.float32),
                    "sample_mask": np.ones((2,), np.float32)}
                   for i in range(12)]
        return train_one_epoch(fake_step, None, batches,
                               put_fn=lambda b: b, show_progress=False,
                               check_every=2, telemetry=tel, epoch=0)

    def test_epoch_emits_perf_summary_and_span_tree(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])
        tel.ledger = make_ledger(plan_launch_cost_px=0.05e6)
        tel.spans = obs.SpanTracer(tel, prefix="t")
        self.run_epoch(tel)
        kinds = [e["kind"] for e in sink.events]
        assert kinds.count("perf.summary") == 1
        perf = next(e["payload"] for e in sink.events
                    if e["kind"] == "perf.summary")
        assert perf["phase"] == "train" and perf["perf_programs"] == 2
        # two image shapes -> the fit has two points -> empirical launch
        # cost + drift exist (values are host-noise; existence is the pin)
        assert "launch_cost_mpx_empirical" in perf
        assert "launch_cost_drift" in perf
        names = [r["name"] for r in perf["detail"]]
        assert names == ["train_step", "train_step"]
        spans = [e["payload"] for e in sink.events
                 if e["kind"] == "trace.span"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert set(by_name) == {"steps", "metric_flush", "train.dispatch",
                                "train.turnover", "input.load",
                                "input.put", "train_epoch"}
        assert len(by_name["train.dispatch"]) == 12
        root = by_name["train_epoch"][0]
        assert all(s["parent_id"] == root["span_id"]
                   for name, ss in by_name.items() if name != "train_epoch"
                   for s in ss)
        assert len({s["trace_id"] for s in spans}) == 1

    def test_no_ledger_no_new_kinds(self):
        sink = ListSink()
        tel = obs.Telemetry([sink])  # telemetry on, perf layer off
        self.run_epoch(tel)
        kinds = set(e["kind"] for e in sink.events)
        assert "perf.summary" not in kinds and "trace.span" not in kinds


# --- report section -----------------------------------------------------
class TestReportPerfSection:
    def test_summarize_and_table(self):
        events = [
            {"ts": 1, "kind": "perf.summary", "step": 0, "host_id": 0,
             "payload": {"phase": "train", "perf_programs": 3,
                         "mfu_weighted": 0.61, "mfu_best": 0.66,
                         "mfu_worst": 0.4,
                         "roofline_compute_bound": 1,
                         "roofline_memory_bound": 2,
                         "roofline_unknown": 0,
                         "launch_cost_mpx_empirical": 0.07,
                         "launch_cost_drift": 1.4, "peak_nominal": 0,
                         "detail": []}},
            {"ts": 2, "kind": "trace.span", "step": None, "host_id": 0,
             "payload": {"trace_id": "t", "span_id": "a",
                         "parent_id": None, "name": "request",
                         "start_s": 0.0, "duration_s": 0.1}},
            {"ts": 3, "kind": "serve.request", "step": 0, "host_id": 0,
             "payload": {"latency_s": 0.2, "queue_wait_s": 0.05,
                         "device_s": 0.1, "ok": True}},
        ]
        s = obs.summarize(events)
        assert s["perf_mfu_weighted"] == 0.61
        assert s["perf_roofline_memory"] == 2
        assert s["perf_launch_cost_drift"] == 1.4
        assert s["trace_spans"] == 1
        assert s["trace_spans_by_name"] == {"request": 1}
        assert s["serve_queue_wait_p95_s"] == pytest.approx(0.05)
        assert s["serve_device_p95_s"] == pytest.approx(0.1)
        table = obs.format_report(s)
        assert "perf MFU" in table and "perf roofline" in table
        assert "perf launch cost" in table and "trace spans" in table
        assert "serve breakdown" in table
        # offline/default artifacts: no perf rows, no Nones rendered
        s0 = obs.summarize([])
        assert s0["perf_mfu_weighted"] is None and s0["trace_spans"] == 0
        t0 = obs.format_report(s0)
        assert "perf MFU" not in t0 and "trace spans" not in t0
