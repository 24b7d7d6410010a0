"""Incident layer tests: flight recorder, incident bundles, SLO burn
rates, and the tooling over them.

Tier-1 contracts pinned here:

* the ring evicts per kind at its cap, keeps exact counts under
  concurrent emitters, and dumps bus-schema JSONL;
* each trigger — NaN health alert (through the REAL loop abort path),
  stall-budget alert, replica quarantine, injected loop exception,
  simulated SIGTERM delivery — produces exactly ONE schema-valid bundle
  under rate limiting, with retention bounding the directory;
* burn-rate window math matches hand-computed fixtures (multi-window
  AND alerting, min_samples guard, pruning, list-field sampling,
  bad_kinds counting);
* ``tools/slo_report.py`` exits 0/1/2 per its contract, and the
  COMMITTED spec + fixture pair passes (the CI gate's artifact pin);
* ``tools/run_monitor.py`` collects multi-host bundles and correlates
  them into fleet-level incidents; ``tools/trace_export.py`` exports a
  bundle's ring straight to a trace;
* ``shutdown_telemetry`` closes heartbeat -> telemetry -> exporter in
  that order on every path;
* a recorder/manager armed on the bus changes NOTHING about the lowered
  step program (hot-path pin).
"""

import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu import obs
from can_tpu.obs.incidents import (
    BUNDLE_SCHEMA,
    MANIFEST_NAME,
    RING_NAME,
    IncidentManager,
    read_manifest,
)
from can_tpu.obs.slo import SloEngine, grade_events, parse_slo_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass

    def kinds(self):
        return [e["kind"] for e in self.events]


def make_tel(clock=None):
    sink = ListSink()
    kw = {} if clock is None else {"clock": clock}
    return obs.Telemetry([sink], **kw), sink


def armed_stack(tmp_path, *, clock=None, rate_limit_s=60.0,
                max_bundles=16, gauges=False, recorder_kw=None):
    """Telemetry + recorder + manager wired exactly as build_telemetry
    does it (recorder as a sink, manager as a watcher)."""
    tel, sink = make_tel(clock)
    rec = obs.FlightRecorder(**(recorder_kw or {}))
    tel._sinks.append(rec)
    g = None
    if gauges:
        g = obs.GaugeSink()
        tel._sinks.append(g)
    mgr = IncidentManager(tel, rec, incident_dir=str(tmp_path / "inc"),
                          gauges=g, run_config={"lr": 1e-7, "seed": 0},
                          rate_limit_s=rate_limit_s,
                          max_bundles=max_bundles,
                          clock=clock or time.time)
    tel.watchers.append(mgr)
    tel.incidents = mgr
    return tel, sink, rec, mgr


def bundles_of(mgr):
    d = mgr.incident_dir
    return sorted(os.path.join(d, n) for n in os.listdir(d)
                  if n.startswith("incident-"))


# --- flight recorder -----------------------------------------------------
class TestFlightRecorder:
    def test_per_kind_eviction_and_ordering(self):
        rec = obs.FlightRecorder(capacity=4, kind_capacity={"b": 2})
        for i in range(10):
            rec.emit({"ts": float(i), "kind": "a", "payload": {"i": i}})
            rec.emit({"ts": float(i) + 0.5, "kind": "b", "payload": {"i": i}})
        snap = rec.snapshot()
        by_kind = {}
        for e in snap:
            by_kind.setdefault(e["kind"], []).append(e)
        # kind a keeps its last 4, kind b its last 2 (per-kind caps);
        # chatty kind b cannot evict kind a
        assert [e["payload"]["i"] for e in by_kind["a"]] == [6, 7, 8, 9]
        assert [e["payload"]["i"] for e in by_kind["b"]] == [8, 9]
        # merged snapshot is ts-sorted
        assert [e["ts"] for e in snap] == sorted(e["ts"] for e in snap)
        st = rec.stats()
        assert st["a"] == {"kept": 4, "seen": 10, "evicted": 6,
                           "capacity": 4}
        assert st["b"]["evicted"] == 8

    def test_retain_s_bounds_snapshot_age(self):
        rec = obs.FlightRecorder(capacity=100, retain_s=10.0)
        for i in range(20):
            rec.emit({"ts": float(i), "kind": "a", "payload": {}})
        snap = rec.snapshot(now=19.0)
        assert [e["ts"] for e in snap] == [float(i) for i in range(9, 20)]
        # without `now` the age filter is inert (count bound only)
        assert len(rec.snapshot()) == 20

    def test_concurrent_emitters_with_concurrent_snapshots(self):
        """Eviction/ordering under contention: 4 writer threads through
        the BUS (each event fans to the recorder under the bus lock is
        not assumed — writers use distinct Telemetry objects sharing one
        recorder, so recorder-internal locking is what's under test)
        while a reader snapshots continuously."""
        rec = obs.FlightRecorder(capacity=64)
        stop = threading.Event()
        errors = []

        def reader():
            while not stop.is_set():
                try:
                    snap = rec.snapshot()
                    assert [e["ts"] for e in snap] == sorted(
                        e["ts"] for e in snap)
                except Exception as e:  # pragma: no cover - failure path
                    errors.append(e)
                    return

        def writer(k):
            tel = obs.Telemetry([rec], clock=time.time)
            for i in range(500):
                tel.emit(f"kind{k % 2}", i=i, writer=k)

        r = threading.Thread(target=reader)
        r.start()
        ws = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
        for w in ws:
            w.start()
        for w in ws:
            w.join()
        stop.set()
        r.join()
        assert not errors
        st = rec.stats()
        # 4 writers x 500 events over 2 kinds: exact totals, capped rings
        assert st["kind0"]["seen"] == 1000 and st["kind1"]["seen"] == 1000
        assert st["kind0"]["kept"] == 64 and st["kind1"]["kept"] == 64

    def test_dump_is_bus_schema_jsonl(self, tmp_path):
        rec = obs.FlightRecorder()
        tel = obs.Telemetry([rec])
        tel.emit("heartbeat", seq=0)
        tel.emit("step_window", steps=4, samples_s=[0.1])
        path = str(tmp_path / "ring.jsonl")
        assert rec.dump(path) == 2
        events = obs.read_events(path)
        assert [e["kind"] for e in events] == ["heartbeat", "step_window"]
        for e in events:
            assert set(e) == {"ts", "kind", "step", "host_id", "payload"}


# --- incident bundles ----------------------------------------------------
class TestIncidentManager:
    def _assert_valid_bundle(self, path, *, reason, severity="error",
                             want_gauges=False):
        m = read_manifest(path)
        assert m is not None, f"torn/absent manifest in {path}"
        assert m["schema"] == BUNDLE_SCHEMA
        assert m["reason"] == reason
        assert m["severity"] == severity
        assert isinstance(m["ts"], float)
        assert m["run_config"] == {"lr": 1e-7, "seed": 0}
        want = {RING_NAME, "stacks.txt", "memory.json"}
        if want_gauges:
            want.add("gauges.json")
        assert want <= set(m["files"]), m["files"]
        assert m["section_errors"] == {}
        # the ring dump is readable telemetry and the stacks name threads
        assert os.path.getsize(os.path.join(path, RING_NAME)) > 0
        assert "thread" in open(os.path.join(path, "stacks.txt")).read()
        json.load(open(os.path.join(path, "memory.json")))
        return m

    def test_nan_alert_dumps_one_bundle(self, tmp_path):
        tel, sink, rec, mgr = armed_stack(tmp_path, gauges=True)
        tel.emit("step_window", steps=4, samples_s=[0.1], loss=0.5)
        tel.emit("health.alert", signal="loss", alert="nan",
                 value=float("nan"), epoch=0)
        bundles = bundles_of(mgr)
        assert len(bundles) == 1
        m = self._assert_valid_bundle(bundles[0], reason="health_nan",
                                      want_gauges=True)
        # the triggering alert itself is IN the ring (sinks run before
        # watchers), alongside the prior window
        ring = obs.read_events(os.path.join(bundles[0], RING_NAME))
        assert [e["kind"] for e in ring] == ["step_window", "health.alert"]
        assert m["ring_events"] == 2
        # and the bundle is announced on the bus for the artifact/report
        recs = [e for e in sink.events if e["kind"] == "incident.bundle"]
        assert len(recs) == 1 and recs[0]["payload"]["path"] == bundles[0]

    def test_trigger_selectivity(self, tmp_path):
        """stall_budget and quarantine trigger; spikes, plateaus, active
        replicas, and non-alerting burns do not."""
        tel, _, _, mgr = armed_stack(tmp_path)
        tel.emit("health.alert", signal="loss", alert="spike", value=9.0)
        tel.emit("health.alert", signal="loss", alert="plateau", value=1.0)
        tel.emit("fleet.replica", replica=0, state="active")
        tel.emit("slo.burn", objective="x", alerting=False, windows={})
        assert bundles_of(mgr) == []
        tel.emit("health.alert", signal="input", alert="stall_budget",
                 value=0.4)
        tel.emit("fleet.replica", replica=1, state="quarantined",
                 error="boom")
        tel.emit("slo.burn", objective="p99", alerting=True, windows={})
        names = [os.path.basename(b) for b in bundles_of(mgr)]
        assert len(names) == 3
        assert any("health-stall-budget" in n for n in names)
        assert any("fleet-quarantine" in n for n in names)
        assert any("slo-p99" in n for n in names)

    def test_rate_limit_suppresses_and_counts(self, tmp_path):
        clock = [100.0]
        tel, _, _, mgr = armed_stack(tmp_path, clock=lambda: clock[0],
                                     rate_limit_s=30.0)
        for _ in range(5):
            tel.emit("health.alert", signal="loss", alert="nan", value=0.0)
        assert mgr.bundles_written == 1
        # a DIFFERENT reason is not cooled by the first one's limiter
        tel.emit("fleet.replica", replica=0, state="quarantined")
        assert mgr.bundles_written == 2
        clock[0] += 31.0
        tel.emit("health.alert", signal="loss", alert="nan", value=0.0)
        assert mgr.bundles_written == 3
        # the post-cooldown bundle records what the limiter swallowed
        m = read_manifest(bundles_of(mgr)[-1])
        assert m["suppressed"] == {"health_nan": 4}

    def test_retention_bounds_the_directory(self, tmp_path):
        clock = [0.0]
        tel, _, _, mgr = armed_stack(tmp_path, clock=lambda: clock[0],
                                     rate_limit_s=0.0, max_bundles=3)
        for i in range(6):
            clock[0] = float(i + 1)
            mgr.trigger(f"reason{i}")
        bundles = bundles_of(mgr)
        assert len(bundles) == 3
        # newest survive, oldest were pruned
        assert [read_manifest(b)["reason"] for b in bundles] == \
            ["reason3", "reason4", "reason5"]

    def test_exception_bundle_carries_traceback_and_info_sources(
            self, tmp_path):
        tel, _, _, mgr = armed_stack(tmp_path)
        mgr.add_info_source("serve_stats", lambda: {"queue_depth": 7})
        mgr.add_info_source("dead", lambda: 1 / 0)
        try:
            raise RuntimeError("kaboom")
        except RuntimeError as e:
            assert mgr.on_exception(e, phase="train", epoch=3) is not None
        m = read_manifest(bundles_of(mgr)[0])
        assert m["reason"] == "exception"
        assert m["exception"]["type"] == "RuntimeError"
        assert "kaboom" in m["exception"]["message"]
        assert any("kaboom" in ln for ln in m["exception"]["traceback"])
        assert m["detail"] == {"phase": "train", "epoch": 3}
        assert m["info"]["serve_stats"] == {"queue_depth": 7}
        # a dead source is recorded in place, not fatal
        assert "ZeroDivisionError" in m["info"]["dead"]["error"]

    def test_write_failure_warns_not_raises(self, tmp_path, capsys):
        tel, _, _, mgr = armed_stack(tmp_path)
        good_dir = mgr.incident_dir
        mgr.incident_dir = str(tmp_path / "inc" / "missing" / "deep")
        # os.makedirs inside _dump would create it; sabotage with a FILE
        # where the dir should go
        (tmp_path / "inc" / "missing").write_text("not a dir")
        assert mgr.trigger("boom") is None
        assert "bundle write FAILED" in capsys.readouterr().out
        # a FAILED dump must not consume the cooldown: once the disk
        # recovers, the very next same-reason trigger writes the bundle
        # (a transient I/O hiccup must not lose the incident)
        mgr.incident_dir = good_dir
        assert mgr.trigger("boom") is not None
        assert mgr.bundles_written == 1

    def test_signal_reentry_while_holding_the_stack_locks(self, tmp_path):
        """The preemption deadlock regression: signals run on the MAIN
        thread between bytecodes, so the handler can fire while that
        same thread is inside the bus / recorder / gauge / manager
        critical sections.  Every lock on the dump path is re-entrant —
        this trigger must complete, not deadlock."""
        tel, sink, rec, mgr = armed_stack(tmp_path, gauges=True)
        gauges = [s for s in tel._sinks if isinstance(s, obs.GaugeSink)][0]
        with tel._lock, rec._lock, gauges._lock, mgr._lock:
            assert mgr.on_signal(signal.SIGTERM) is not None
        assert len(bundles_of(mgr)) == 1
        assert "incident.bundle" in sink.kinds()


# --- the trigger matrix through real paths -------------------------------
def make_fake_batches(n, b=2):
    return [{"image": np.zeros((b, 8, 8, 3), np.float32),
             "sample_mask": np.ones((b,), np.float32)} for _ in range(n)]


class TestTriggerMatrix:
    def test_nan_abort_through_the_loop_dumps_exactly_one(self, tmp_path):
        """The real abort path: health.alert(nan) fires inside the
        flush, the watcher dumps, NonFiniteLossError unwinds through the
        loop's NEW exception hook — which must NOT double-bundle."""
        from can_tpu.obs.health import HealthMonitor
        from can_tpu.train import NonFiniteLossError, train_one_epoch

        def step(state, batch):
            i = state["i"]
            loss = float("nan") if i == 10 else 1.0
            return {"i": i + 1}, {"loss": loss, "num_valid": 2.0}

        tel, _, _, mgr = armed_stack(tmp_path)
        mon = HealthMonitor(tel)
        with pytest.raises(NonFiniteLossError):
            train_one_epoch(step, {"i": 0}, make_fake_batches(16),
                            put_fn=lambda b: b, show_progress=False,
                            check_every=4, telemetry=tel, health=mon)
        bundles = bundles_of(mgr)
        assert len(bundles) == 1
        assert read_manifest(bundles[0])["reason"] == "health_nan"

    def test_injected_loop_exception_dumps_before_unwinding(
            self, tmp_path):
        from can_tpu.train import train_one_epoch

        def step(state, batch):
            i = state["i"]
            if i == 5:
                raise RuntimeError("injected device error")
            return {"i": i + 1}, {"loss": 1.0, "num_valid": 2.0}

        tel, _, _, mgr = armed_stack(tmp_path)
        with pytest.raises(RuntimeError, match="injected"):
            train_one_epoch(step, {"i": 0}, make_fake_batches(16),
                            put_fn=lambda b: b, show_progress=False,
                            check_every=4, telemetry=tel)
        bundles = bundles_of(mgr)
        assert len(bundles) == 1
        m = read_manifest(bundles[0])
        assert m["reason"] == "exception"
        assert m["exception"]["type"] == "RuntimeError"
        assert m["detail"]["phase"] == "train"

    def test_eval_loop_exception_dumps(self, tmp_path):
        from can_tpu.train import evaluate

        def eval_step(params, batch, batch_stats=None):
            raise ValueError("poisoned batch")

        eval_step.last_first_call = False
        tel, _, _, mgr = armed_stack(tmp_path)
        with pytest.raises(ValueError, match="poisoned"):
            evaluate(eval_step, None, make_fake_batches(4),
                     put_fn=lambda b: b, dataset_size=8, telemetry=tel)
        m = read_manifest(bundles_of(mgr)[0])
        assert m["reason"] == "exception" and m["detail"]["phase"] == "eval"

    def test_default_run_has_no_incident_surface(self):
        """telemetry=None: the loop's hook is one getattr on None — no
        manager, no recorder, nothing to arm (the hot-path contract;
        the lowered-program pin is TestHotPathPin)."""
        from can_tpu.train import train_one_epoch

        def step(state, batch):
            return state, {"loss": 1.0, "num_valid": 2.0}

        _, stats = train_one_epoch(step, {}, make_fake_batches(4),
                                   put_fn=lambda b: b, show_progress=False,
                                   telemetry=None)
        assert stats.steps == 4

    def test_simulated_sigterm_dumps_flushes_and_exits(self, tmp_path):
        """Real signal delivery: install the hook, kill ourselves with
        SIGTERM, and observe bundle + SystemExit(143) + JSONL flush —
        then the restore path puts the old disposition back."""
        tdir = tmp_path / "tel"
        rec = obs.FlightRecorder()
        tel = obs.open_host_telemetry(str(tdir))
        tel._sinks.append(rec)
        mgr = IncidentManager(tel, rec, incident_dir=str(tmp_path / "inc"),
                              run_config={"lr": 1e-7, "seed": 0})
        tel.watchers.append(mgr)
        prev = signal.getsignal(signal.SIGTERM)
        restore = obs.install_sigterm_handler(mgr)
        assert restore is not None
        try:
            tel.emit("heartbeat", seq=0)
            with pytest.raises(SystemExit) as exc:
                os.kill(os.getpid(), signal.SIGTERM)
                # the handler runs between bytecodes on this thread
                for _ in range(100):
                    time.sleep(0.01)
            assert exc.value.code == 128 + signal.SIGTERM
        finally:
            tel.close()  # closes watchers -> mgr.close() -> restore
        assert signal.getsignal(signal.SIGTERM) == prev
        bundles = bundles_of(mgr)
        assert len(bundles) == 1
        m = read_manifest(bundles[0])
        assert m["reason"] == "signal_sigterm"
        assert m["severity"] == "preemption"
        # flushed: the JSONL records both the heartbeat and the bundle
        events = obs.read_events(str(tdir / "telemetry.host0.jsonl"))
        kinds = [e["kind"] for e in events]
        assert "heartbeat" in kinds and "incident.bundle" in kinds


# --- SLO spec + burn math ------------------------------------------------
def make_spec(**over):
    doc = {"version": 1, "eval_interval_s": over.pop("eval_interval_s", 10),
           "objectives": [dict({
               "name": "lat", "event": "serve.request",
               "field": "latency_s", "op": "<=", "threshold": 1.0,
               "target": 0.9, "windows_s": [60, 600],
               "burn_alert": 5.0, "min_samples": 5}, **over)]}
    return parse_slo_spec(doc)


def req(ts, latency):
    return {"ts": ts, "kind": "serve.request", "step": None, "host_id": 0,
            "payload": {"latency_s": latency}}


class TestSloSpec:
    @pytest.mark.parametrize("mutation,msg", [
        ({"version": 2}, "version"),
        ({"objectives": []}, "objectives"),
        ({"objectives": [{"name": "x"}]}, "event"),
        ({"objectives": [{"event": "stall", "target": 0.5}]}, "name"),
        ({"objectives": [{"name": "x", "event": "stall",
                          "target": 1.5}]}, "target"),
        ({"objectives": [{"name": "x", "event": "stall", "target": 0.9,
                          "field": "f", "op": "=="}]}, "op"),
        ({"objectives": [{"name": "x", "event": "stall", "target": 0.9,
                          "field": "f"}]}, "threshold"),
        ({"objectives": [{"name": "x", "event": "stall", "target": 0.9,
                          "windows_s": []}]}, "windows_s"),
        ({"objectives": [{"name": "x", "event": "stall", "target": 0.9},
                         {"name": "x", "event": "stall",
                          "target": 0.9}]}, "duplicate"),
    ])
    def test_bad_specs_name_the_field(self, mutation, msg):
        doc = {"version": 1, "objectives": [
            {"name": "ok", "event": "stall", "target": 0.9}]}
        doc.update(mutation)
        with pytest.raises(ValueError, match=msg):
            parse_slo_spec(doc)

    def test_load_rejects_bad_json(self, tmp_path):
        p = tmp_path / "s.json"
        p.write_text("{nope")
        with pytest.raises(ValueError, match="JSON"):
            obs.load_slo_spec(str(p))

    def test_committed_example_spec_parses(self):
        spec = obs.load_slo_spec(os.path.join(REPO, "slo_spec.json"))
        names = {o.name for o in spec.objectives}
        # the five objective families the ISSUE names
        assert {"serve_p99_deadline", "serve_reject_rate", "mfu_floor",
                "stall_budget", "step_time_ceiling"} <= names


class TestBurnMath:
    def test_burn_is_bad_fraction_over_budget_per_window(self):
        """Hand-computed: target 0.9 => budget 0.1.  Short window holds
        8 good + 2 bad => bad_frac 0.2 => burn 2.0; long window holds
        those plus 20 older good => bad_frac 2/30 => burn 0.667."""
        eng = SloEngine(make_spec())
        for i in range(20):
            eng.on_event(req(1000.0 + i, 0.5))        # old, good
        for i in range(8):
            eng.on_event(req(1500.0 + i, 0.5))        # recent, good
        for i in range(2):
            eng.on_event(req(1550.0 + i, 2.0))        # recent, bad
        (p,) = eng.evaluate(1555.0)
        assert p["windows"]["60"]["good"] == 8
        assert p["windows"]["60"]["bad"] == 2
        assert p["windows"]["60"]["burn"] == pytest.approx(2.0)
        assert p["windows"]["600"]["burn"] == pytest.approx(
            (2 / 30) / 0.1, abs=1e-4)
        assert p["burn_max"] == pytest.approx(2.0)
        assert not p["alerting"]  # 2.0 < burn_alert 5.0

    def test_multiwindow_and_alerting(self):
        """Alert requires EVERY window burning: a burst that saturates
        the short window but not the long one stays quiet; sustained
        badness trips both."""
        eng = SloEngine(make_spec(burn_alert=5.0))
        for i in range(60):
            eng.on_event(req(1000.0 + i * 5, 0.5))    # long history, good
        for i in range(12):
            eng.on_event(req(1300.0 + i, 2.0))        # short burst, bad
        (p,) = eng.evaluate(1312.0)
        # 60 s window: 9 good (1255..1295) + 12 bad -> burn 5.71; 600 s
        # window: 60 good + 12 bad -> burn 1.67 — short alone, no alert
        assert p["windows"]["60"]["burn"] >= 5.0
        assert p["windows"]["600"]["burn"] < 5.0
        assert not p["alerting"]
        # keep burning: the long window crosses too
        for i in range(60):
            eng.on_event(req(1320.0 + i * 4, 2.0))
        (p,) = eng.evaluate(1560.0)
        assert p["alerting"]
        assert p["windows"]["60"]["burn"] >= 5.0
        assert p["windows"]["600"]["burn"] >= 5.0

    def test_min_samples_guard_and_pruning(self):
        eng = SloEngine(make_spec(min_samples=5))
        for i in range(3):
            eng.on_event(req(1000.0 + i, 2.0))
        (p,) = eng.evaluate(1003.0)
        # 3 < 5: no burn, no alert — "not enough data", never "healthy"
        assert p["windows"]["60"]["burn"] is None
        assert not p["alerting"]
        # 700 s later the samples are outside BOTH windows
        (p,) = eng.evaluate(1700.0)
        assert p["windows"]["600"]["samples"] == 0

    def test_list_field_and_bad_kinds(self):
        spec = parse_slo_spec({"version": 1, "objectives": [
            {"name": "steps", "event": "step_window", "field": "samples_s",
             "op": "<=", "threshold": 0.5, "target": 0.9,
             "windows_s": [60], "min_samples": 4},
            {"name": "rejects", "event": "serve.request", "field": None,
             "bad_kinds": ["serve.reject"], "target": 0.9,
             "windows_s": [60], "min_samples": 4}]})
        eng = SloEngine(spec)
        eng.on_event({"ts": 1000.0, "kind": "step_window", "host_id": 0,
                      "payload": {"samples_s": [0.1, 0.2, 0.6, 0.7]}})
        eng.on_event(req(1001.0, 0.1))
        eng.on_event(req(1002.0, 0.1))
        eng.on_event(req(1003.0, 0.1))
        eng.on_event({"ts": 1004.0, "kind": "serve.reject", "host_id": 0,
                      "payload": {"reason": "deadline", "count": 3}})
        out = {p["objective"]: p for p in eng.evaluate(1005.0)}
        # list field: each element is one sample (2 good, 2 bad)
        assert out["steps"]["windows"]["60"] == {
            "good": 2, "bad": 2, "samples": 4,
            "burn": pytest.approx(0.5 / 0.1)}
        # field None: each event good; bad_kinds add payload count
        assert out["rejects"]["windows"]["60"]["good"] == 3
        assert out["rejects"]["windows"]["60"]["bad"] == 3

    def test_engine_emits_and_gauges_export(self, tmp_path):
        """Live wiring: time-gated slo.burn events on the bus, labelled
        can_tpu_slo_* gauges, incident trigger on fast burn."""
        clock = [1000.0]
        tel, sink, _, mgr = armed_stack(tmp_path, clock=lambda: clock[0],
                                        gauges=True)
        gauges = tel._sinks[-1]
        assert isinstance(gauges, obs.GaugeSink)
        eng = SloEngine(make_spec(eval_interval_s=10, min_samples=3,
                                  windows_s=[60, 600]), tel)
        tel.watchers.append(eng)
        for i in range(30):
            clock[0] = 1000.0 + i
            tel.emit("serve.request", latency_s=5.0)  # all bad: burn 10
        burns = [e for e in sink.events if e["kind"] == "slo.burn"]
        assert burns, "time-gated evaluation never fired"
        assert burns[-1]["payload"]["alerting"]
        text = gauges.render()
        assert 'can_tpu_slo_burn{objective="lat",window_s="60"} 10.0' \
            in text
        assert 'can_tpu_slo_alerting{objective="lat"} 1' in text
        assert 'can_tpu_slo_alerts_total{objective="lat"}' in text
        # the fast burn dumped an incident bundle naming the objective
        names = [os.path.basename(b) for b in bundles_of(mgr)]
        assert any("slo-lat" in n for n in names)
        # and the scrape parses: one TYPE line per metric name
        types = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
        assert len(types) == len({t.split()[2] for t in types})

    def test_concurrent_emitters_evaluate_an_interval_once(self):
        """The time-gate claims its interval INSIDE the lock: N threads
        emitting just past the boundary produce exactly one evaluation,
        not N (double slo.burn events would inflate alert counters)."""
        tel, sink = make_tel()
        eng = SloEngine(make_spec(eval_interval_s=10, min_samples=1), tel)
        tel.watchers.append(eng)
        eng.on_event(req(1000.0, 0.1))  # anchors the gate
        threads = [threading.Thread(
            target=lambda: eng.on_event(req(1011.0, 0.1)))
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sink.kinds().count("slo.burn") == 1

    def test_engine_close_runs_tail_evaluation(self):
        tel, sink = make_tel()
        eng = SloEngine(make_spec(min_samples=2), tel)
        tel.watchers.append(eng)
        tel.emit("serve.request", latency_s=0.1)
        tel.emit("serve.request", latency_s=0.1)
        assert "slo.burn" not in sink.kinds()  # under the time gate
        tel.close()  # watcher close -> final evaluate into open sinks
        assert "slo.burn" in sink.kinds()


class TestGradeEvents:
    def test_pass_fast_burn_and_budget_violations(self):
        spec = make_spec(min_samples=5, burn_alert=5.0,
                         eval_interval_s=10)
        good = [req(1000.0 + i, 0.1) for i in range(100)]
        g = grade_events(good, spec)
        assert g["violations"] == []
        assert g["objectives"]["lat"]["bad"] == 0
        # sustained badness: fast-burn violation naming the windows
        bad = [req(1000.0 + i * 5, 5.0) for i in range(100)]
        g = grade_events(bad, spec)
        kinds = {v["kind"] for v in g["violations"]}
        assert kinds == {"fast_burn"}
        v = g["violations"][0]
        assert v["objective"] == "lat" and v["window"] == "60+600"
        assert v["burn"] == pytest.approx(10.0)
        # slow leak: 15% bad spread evenly trips the budget check even
        # when per-window burns stay under the alert threshold
        leak = [req(1000.0 + i * 30, 5.0 if i % 7 == 0 else 0.1)
                for i in range(100)]
        g = grade_events(leak, spec)
        kinds = {v["kind"] for v in g["violations"]}
        assert "budget" in kinds
        v = [v for v in g["violations"] if v["kind"] == "budget"][0]
        assert v["window"] == "run"
        assert v["bad_frac"] == pytest.approx(15 / 100, abs=0.01)

    def test_zero_sample_objective_is_not_graded(self):
        spec = make_spec()
        g = grade_events([{"ts": 1.0, "kind": "heartbeat", "host_id": 0,
                           "payload": {}}], spec)
        assert g["violations"] == []
        assert g["objectives"]["lat"]["samples"] == 0


# --- slo_report CLI ------------------------------------------------------
SLO_FIXTURE = os.path.join("tests", "fixtures", "SLO_FIXTURE_cpu_r15.jsonl")


def run_slo_report(*argv):
    tool = os.path.join(REPO, "tools", "slo_report.py")
    return subprocess.run([sys.executable, tool, *argv],
                          capture_output=True, text=True, cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


class TestSloReportCLI:
    def test_committed_fixture_passes_committed_spec(self):
        """Artifact pin: the committed fixture grades green against the
        committed example spec."""
        r = run_slo_report(SLO_FIXTURE, "--spec", "slo_spec.json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "PASS" in r.stdout
        # every committed objective was exercised by the fixture
        assert "no samples" not in r.stdout

    def test_violation_exits_1_naming_objective_and_window(self, tmp_path):
        spec = json.load(open(os.path.join(REPO, "slo_spec.json")))
        spec["objectives"][0]["threshold"] = 0.3
        spec["objectives"][0]["burn_alert"] = 2.0
        p = tmp_path / "tight.json"
        p.write_text(json.dumps(spec))
        r = run_slo_report(SLO_FIXTURE, "--spec", str(p))
        assert r.returncode == 1
        assert "VIOLATION serve_p99_deadline" in r.stdout
        assert "window 60+300" in r.stdout

    def test_usage_errors_exit_2(self, tmp_path):
        r = run_slo_report(SLO_FIXTURE,
                           "--spec", str(tmp_path / "absent.json"))
        assert r.returncode == 2
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"version": 1, "objectives": [
            {"name": "x", "event": "stall", "target": 2.0}]}))
        r = run_slo_report("SLO_FIXTURE_cpu_r15.jsonl", "--spec", str(bad))
        assert r.returncode == 2 and "target" in r.stderr
        r = run_slo_report(str(tmp_path / "nothing.jsonl"),
                           "--spec", "slo_spec.json")
        assert r.returncode == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        r = run_slo_report(str(empty), "--spec", "slo_spec.json")
        assert r.returncode == 2 and "no telemetry events" in r.stderr

    def test_grades_an_incident_bundle_directory(self, tmp_path):
        tel, _, rec, mgr = armed_stack(tmp_path)
        for i in range(20):
            tel.emit("serve.request", latency_s=0.1)
        mgr.trigger("manual")
        bundle = bundles_of(mgr)[0]
        spec = tmp_path / "s.json"
        spec.write_text(json.dumps({"version": 1, "objectives": [
            {"name": "lat", "event": "serve.request", "field": "latency_s",
             "op": "<=", "threshold": 1.0, "target": 0.9,
             "windows_s": [60], "min_samples": 5}]}))
        r = run_slo_report(bundle, "--spec", str(spec), "--json")
        assert r.returncode == 0, r.stderr
        doc = json.loads(r.stdout)
        assert doc["objectives"]["lat"]["samples"] == 20


# --- run_monitor incident correlation ------------------------------------
def write_host_file(run_dir, hid, t0):
    events = [{"ts": t0 + i, "kind": "heartbeat", "step": None,
               "host_id": hid, "payload": {"seq": i, "start_ts": t0}}
              for i in range(3)]
    path = os.path.join(run_dir, f"telemetry.host{hid}.jsonl")
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def write_bundle(run_dir, *, ts, hid, reason, sub="incidents"):
    d = os.path.join(run_dir, sub,
                     f"incident-{int(ts * 1000):013d}-h{hid}-{reason}")
    os.makedirs(d)
    with open(os.path.join(d, RING_NAME), "w") as f:
        f.write(json.dumps({"ts": ts, "kind": "heartbeat", "step": None,
                            "host_id": hid, "payload": {}}) + "\n")
    with open(os.path.join(d, MANIFEST_NAME), "w") as f:
        json.dump({"schema": BUNDLE_SCHEMA, "reason": reason,
                   "severity": "error", "ts": ts, "host_id": hid,
                   "ring_events": 1, "files": [RING_NAME]}, f)
    return d


class TestRunMonitorIncidents:
    def test_multi_host_bundles_correlate_into_fleet_incidents(
            self, tmp_path):
        from tools import run_monitor

        run_dir = str(tmp_path)
        t0 = 1000.0
        write_host_file(run_dir, 0, t0)
        write_host_file(run_dir, 1, t0)
        # two bundles 5 s apart (one incident: nan on host 0 cascades to
        # a quarantine on host 1), a third 500 s later (separate)
        write_bundle(run_dir, ts=t0 + 10, hid=0, reason="health-nan")
        write_bundle(run_dir, ts=t0 + 15, hid=1, reason="fleet-quarantine")
        write_bundle(run_dir, ts=t0 + 515, hid=0, reason="signal-sigterm")
        # a torn dump (no manifest) is skipped, never trusted
        os.makedirs(os.path.join(run_dir, "incidents",
                                 "incident-9999999999999-h0-torn"))
        run = run_monitor.analyze_dir(run_dir, stale_after_s=1e12)
        assert len(run["incidents"]) == 3
        assert not run["ok"]
        clusters = run["incident_clusters"]
        assert len(clusters) == 2
        assert clusters[0]["hosts"] == [0, 1]
        assert clusters[0]["reasons"] == {"fleet-quarantine": 1,
                                          "health-nan": 1}
        assert clusters[0]["t1"] - clusters[0]["t0"] == pytest.approx(5.0)
        assert clusters[1]["hosts"] == [0]
        # the report renders the timeline; the CLI pages (exit 1)
        text = run_monitor.format_report(run)
        assert "incident timeline" in text
        assert "health-nan" in text and "fleet-quarantine" in text
        tool = os.path.join(REPO, "tools", "run_monitor.py")
        r = subprocess.run([sys.executable, tool, run_dir,
                            "--stale-after-s", "1e12", "--json"],
                           capture_output=True, text=True, cwd=REPO)
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert len(doc["incidents"]) == 3
        assert len(doc["incident_clusters"]) == 2

    def test_bundles_beside_the_telemetry_also_found(self, tmp_path):
        from tools import run_monitor

        write_host_file(str(tmp_path), 0, 1000.0)
        write_bundle(str(tmp_path), ts=1010.0, hid=0, reason="x", sub=".")
        run = run_monitor.analyze_dir(str(tmp_path), stale_after_s=1e12)
        assert len(run["incidents"]) == 1

    def test_healthy_run_without_bundles_stays_ok(self, tmp_path):
        from tools import run_monitor

        write_host_file(str(tmp_path), 0, 1000.0)
        run = run_monitor.analyze_dir(str(tmp_path), stale_after_s=1e12)
        assert run["ok"] and run["incidents"] == []


# --- trace_export on a bundle --------------------------------------------
class TestTraceExportBundle:
    def test_bundle_ring_exports_to_trace_events(self, tmp_path):
        tel, _, rec, mgr = armed_stack(tmp_path)
        spans = obs.SpanTracer(tel)
        tel.spans = spans
        root = spans.new_span_id()
        spans.emit(trace_id="t1", name="request", start=1.0, end=2.0,
                   span_id=root)
        spans.emit(trace_id="t1", name="queue_wait", start=1.2, end=1.8,
                   parent_id=root)
        tel.emit("fleet.replica", replica=0, state="quarantined")
        bundle = bundles_of(mgr)[0]
        out = tmp_path / "b.trace.json"
        tool = os.path.join(REPO, "tools", "trace_export.py")
        r = subprocess.run([sys.executable, tool, bundle, "--out",
                            str(out)], capture_output=True, text=True,
                           cwd=REPO)
        assert r.returncode == 0, r.stderr
        doc = json.load(open(out))
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert sorted(names) == ["queue_wait", "request"]

    def test_bundle_without_ring_is_an_error(self, tmp_path):
        d = tmp_path / "incident-1-h0-x"
        d.mkdir()
        (d / MANIFEST_NAME).write_text(json.dumps(
            {"schema": BUNDLE_SCHEMA, "reason": "x", "ts": 1.0,
             "host_id": 0}))
        tool = os.path.join(REPO, "tools", "trace_export.py")
        r = subprocess.run([sys.executable, tool, str(d)],
                           capture_output=True, text=True, cwd=REPO)
        assert r.returncode != 0
        assert RING_NAME in r.stderr


# --- deterministic teardown ----------------------------------------------
class TestShutdownOrdering:
    def test_heartbeat_then_telemetry_then_exporter(self):
        order = []

        class Rec:
            def __init__(self, name):
                self.name = name

            def close(self):
                order.append(self.name)

        obs.shutdown_telemetry(Rec("telemetry"), heartbeat=Rec("heartbeat"),
                               exporter=Rec("exporter"))
        assert order == ["heartbeat", "telemetry", "exporter"]

    def test_none_members_and_failures_do_not_stop_the_order(self, capsys):
        order = []

        class Boom:
            def close(self):
                order.append("boom")
                raise RuntimeError("nope")

        class Rec:
            def close(self):
                order.append("exporter")

        obs.shutdown_telemetry(Boom(), heartbeat=None, exporter=Rec())
        assert order == ["boom", "exporter"]
        assert "teardown step failed" in capsys.readouterr().out

    def test_telemetry_close_flushes_watchers_before_sinks(self):
        """The real ordering contract: a watcher's close() may emit, and
        those events must still reach the sinks (bus.close closes
        watchers first, sinks after)."""
        tel, sink = make_tel()

        class FlushWatcher:
            def on_event(self, event):
                pass

            def close(self):
                tel.emit("slo.burn", objective="final", alerting=False,
                         windows={})

        tel.watchers.append(FlushWatcher())
        tel.close()
        assert sink.kinds() == ["slo.burn"]
        # idempotent: a second close (signal racing teardown) is a no-op
        tel.close()
        assert len(sink.events) == 1

    def test_double_shutdown_is_idempotent(self, tmp_path):
        tel, _, _, _ = armed_stack(tmp_path)
        hb = obs.Heartbeat(tel, 0.0, start=False)
        obs.shutdown_telemetry(tel, heartbeat=hb)
        obs.shutdown_telemetry(tel, heartbeat=hb)  # must not raise


# --- build_telemetry wiring ----------------------------------------------
class TestBuildTelemetryWiring:
    def _args(self, tmp_path, **over):
        import argparse

        ns = argparse.Namespace(
            telemetry_dir="", telemetry_heartbeat_s=0.0, profile_dir="",
            metrics_port=None, metrics_host="127.0.0.1", bf16=False,
            incident_dir="", slo_spec="")
        for k, v in over.items():
            setattr(ns, k, v)
        return ns

    def test_incident_and_slo_flags_arm_the_stack(self, tmp_path):
        from can_tpu.cli.train import build_telemetry

        spec = tmp_path / "spec.json"
        spec.write_text(json.dumps(
            {"version": 1, "objectives": [
                {"name": "x", "event": "stall", "field": "frac_of_epoch",
                 "op": "<=", "threshold": 0.15, "target": 0.9}]}))
        args = self._args(tmp_path, incident_dir=str(tmp_path / "inc"),
                          slo_spec=str(spec))
        prev = signal.getsignal(signal.SIGTERM)
        tel, hb, exporter = build_telemetry(
            args, host_id=0, trace_window=None)
        try:
            assert exporter is None
            assert hb is not None  # incident-dir arms liveness
            assert tel.incidents is not None
            assert tel.ledger is not None and tel.spans is not None
            kinds = {type(w).__name__ for w in tel.watchers}
            assert kinds == {"SloEngine", "IncidentManager"}
            assert any(isinstance(s, obs.FlightRecorder)
                       for s in tel._sinks)
            assert any(isinstance(s, obs.GaugeSink) for s in tel._sinks)
            # the signal hook was installed and will be restored on close
            assert signal.getsignal(signal.SIGTERM) != prev
        finally:
            obs.shutdown_telemetry(tel, heartbeat=hb, exporter=exporter)
        assert signal.getsignal(signal.SIGTERM) == prev

    def test_install_signals_false_leaves_the_table_alone(self, tmp_path):
        from can_tpu.cli.train import build_telemetry

        args = self._args(tmp_path, incident_dir=str(tmp_path / "inc"))
        prev = signal.getsignal(signal.SIGTERM)
        tel, hb, exporter = build_telemetry(
            args, host_id=0, trace_window=None, install_signals=False)
        try:
            assert signal.getsignal(signal.SIGTERM) == prev
        finally:
            obs.shutdown_telemetry(tel, heartbeat=hb, exporter=exporter)

    def test_default_args_arm_nothing_new(self, tmp_path):
        from can_tpu.cli.train import build_telemetry

        tel, hb, exporter = build_telemetry(
            self._args(tmp_path), host_id=0, trace_window=None)
        try:
            assert tel.watchers == [] and tel.incidents is None
            assert hb is None and exporter is None
            assert not any(isinstance(s, (obs.FlightRecorder,
                                          obs.GaugeSink))
                           for s in tel._sinks)
        finally:
            obs.shutdown_telemetry(tel, heartbeat=hb, exporter=exporter)


# --- report section ------------------------------------------------------
class TestReportSection:
    def test_incidents_and_slo_in_summary_and_table(self, tmp_path):
        tel, sink, _, mgr = armed_stack(tmp_path)
        tel.emit("health.alert", signal="loss", alert="nan", value=0.0)
        tel.emit("slo.burn", objective="lat", alerting=True,
                 burn_min=12.0, burn_max=12.0,
                 windows={"60": {"burn": 12.0, "good": 0, "bad": 9,
                                 "samples": 9}},
                 run_good=0, run_bad=9)
        summary = obs.summarize(sink.events)
        # the hand-emitted alerting burn itself triggered a second
        # bundle through the live watcher — both are in the summary
        assert summary["incidents"] == 2
        assert summary["incidents_by_reason"] == {"health_nan": 1,
                                                  "slo_lat": 1}
        assert summary["incident_last_path"] == bundles_of(mgr)[-1]
        assert summary["slo_objectives"]["lat"]["alerting"]
        assert summary["slo_alert_events"] == 1
        text = obs.format_report(summary)
        assert "incidents" in text and "health_nan=1" in text
        assert "SLO burn" in text and "lat=12(ALERT)" in text

    def test_gauge_sink_counts_incident_bundles(self, tmp_path):
        tel, _, _, _ = armed_stack(tmp_path, gauges=True)
        gauges = [s for s in tel._sinks
                  if isinstance(s, obs.GaugeSink)][0]
        tel.emit("health.alert", signal="loss", alert="nan", value=0.0)
        assert 'can_tpu_incidents_total{reason="health_nan"} 1' \
            in gauges.render()
        snap = gauges.snapshot()
        assert any(c["name"] == "can_tpu_incidents_total"
                   for c in snap["counters"])


# --- hot-path pin --------------------------------------------------------
def tiny_apply(params, image, compute_dtype=None):
    x = image if compute_dtype is None else image.astype(compute_dtype)
    x = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 8, 8, 1), (1, 8, 8, 1), "VALID")


class TestHotPathPin:
    def test_lowered_step_identical_with_recorder_armed(self, tmp_path):
        """Acceptance pin: arming the WHOLE incident stack (recorder
        sink, incident watcher, SLO engine, gauges) changes nothing
        about the lowered default train-step program — the incident
        layer is host-side observation, byte-for-byte."""
        from can_tpu.train import (
            create_train_state,
            make_lr_schedule,
            make_optimizer,
            make_train_step,
        )

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

        def lowered_text():
            step = jax.jit(make_train_step(tiny_apply, opt))
            return step.lower(state, batch).as_text()

        base = lowered_text()
        tel, _, _, _ = armed_stack(tmp_path, gauges=True)
        eng = SloEngine(make_spec(), tel)
        tel.watchers.append(eng)
        try:
            assert lowered_text() == base
        finally:
            tel.close()
        assert lowered_text() == base


# --- CLI e2e -------------------------------------------------------------
class TestCliE2E:
    def test_train_cli_with_incident_and_slo_flags(self, tmp_path):
        """One real (tiny) training run with the full incident/SLO stack
        armed: clean exit, zero bundles, slo.burn events in the JSONL,
        and the SIGTERM disposition restored."""
        from can_tpu.cli.train import main as train_main
        from can_tpu.data import make_synthetic_dataset

        root = str(tmp_path / "data")
        for split, n, seed in (("train", 8, 0), ("test", 8, 1)):
            make_synthetic_dataset(os.path.join(root, f"{split}_data"), n,
                                   sizes=((64, 64),), seed=seed)
        spec = tmp_path / "spec.json"
        # sub-second eval interval + min_samples 1: the few-second run
        # still produces evaluations on the event clock.  The objective
        # samples the per-epoch stall accounting with a can't-fail
        # threshold (frac <= 1.0): the wiring is under test, not the
        # box's I/O weather.
        spec.write_text(json.dumps({"version": 1, "eval_interval_s": 0.01,
                                    "objectives": [
            {"name": "stall_ok", "event": "stall",
             "field": "frac_of_epoch", "op": "<=", "threshold": 1.0,
             "target": 0.5, "windows_s": [60], "min_samples": 1,
             "burn_alert": 1e9}]}))
        tdir = str(tmp_path / "tel")
        inc_dir = str(tmp_path / "inc")
        prev = signal.getsignal(signal.SIGTERM)
        rc = train_main(["--data_root", root, "--epochs", "1",
                         "--batch-size", "1", "--lr", "1e-7",
                         "--checkpoint-dir", str(tmp_path / "ck"),
                         "--seed", "0", "--telemetry-dir", tdir,
                         "--incident-dir", inc_dir,
                         "--slo-spec", str(spec)])
        assert rc == 0
        assert signal.getsignal(signal.SIGTERM) == prev
        events = obs.read_events(os.path.join(tdir,
                                              "telemetry.host0.jsonl"))
        kinds = {e["kind"] for e in events}
        assert "slo.burn" in kinds
        burns = [e["payload"] for e in events if e["kind"] == "slo.burn"]
        assert all(not b["alerting"] for b in burns)
        # any bundle a stall-budget alert may have dumped on a slow CI
        # box must be VALID (manifest-last) — and nothing else triggers
        for n in os.listdir(inc_dir):
            m = read_manifest(os.path.join(inc_dir, n))
            assert m is not None and m["reason"] == "health_stall_budget"

    def test_bad_slo_spec_fails_before_runtime_init(self, tmp_path):
        from can_tpu.cli.train import main as train_main

        # real-looking dataset dirs so path validation passes and the
        # spec check is what fires (it must run BEFORE init_runtime)
        for split in ("train", "test"):
            for sub in ("images", "ground_truth"):
                os.makedirs(tmp_path / "data" / f"{split}_data" / sub)
        spec = tmp_path / "bad.json"
        spec.write_text("{broken")
        with pytest.raises(SystemExit, match="slo-spec"):
            train_main(["--data_root", str(tmp_path / "data"),
                        "--slo-spec", str(spec)])
