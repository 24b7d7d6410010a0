"""Run-health layer tests: detectors, monitor wiring in the train loop,
cross-host run_monitor, and the /metrics exporter.

Tier-1 contracts pinned here:

* detectors fire on injected anomalies and stay silent on steady streams;
* the NaN-abort path emits ``health.alert`` (alert=nan) on the bus BEFORE
  ``NonFiniteLossError`` propagates;
* ``make_train_step(health_metrics=...)`` defaults to the EXACT pre-PR
  metrics tree (hot-path identity) and adds finite grad/update norms when
  asked;
* a synthesized 2-host run with one straggler and one dead host is
  flagged by ``tools/run_monitor.py``;
* a live /metrics scrape parses as Prometheus text and carries the
  step/loss/grad-norm gauges plus serve counters.
"""

import json
import math
import os
import re
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu import obs
from can_tpu.obs.health import (
    EwmaMadDetector,
    HealthMonitor,
    PlateauDetector,
    ThroughputDetector,
)


class ListSink:
    """Collects events in memory (test double for the JSONL sink)."""

    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass

    def kinds(self):
        return [e["kind"] for e in self.events]

    def alerts(self):
        return [e["payload"] for e in self.events
                if e["kind"] == "health.alert"]


def make_tel():
    sink = ListSink()
    return obs.Telemetry([sink]), sink


# --- detectors ----------------------------------------------------------
class TestDetectors:
    def test_spike_fires_and_steady_stream_is_silent(self):
        det = EwmaMadDetector(warmup=8)
        rng = np.random.default_rng(0)
        verdicts = [det.update(1.0 + 0.01 * rng.standard_normal())
                    for _ in range(100)]
        assert all(v is None for v in verdicts), "steady stream alerted"
        v = det.update(2.0)  # 100-sigma-ish jump on a 0.01-jitter stream
        assert v is not None and v["alert"] == "spike"
        assert v["value"] == 2.0 and v["deviation"] > 8

    def test_constant_stream_needs_relative_jump(self):
        # MAD == 0 on a constant stream: the rel_floor must keep femto
        # jitter quiet while a real relative jump still fires
        det = EwmaMadDetector(warmup=8)
        for _ in range(50):
            assert det.update(5.0) is None
        assert det.update(5.0 + 1e-9) is None  # numeric dust
        assert det.update(5.5) is not None     # 10% jump

    def test_warmup_never_alerts(self):
        det = EwmaMadDetector(warmup=8)
        assert det.update(1.0) is None
        assert det.update(100.0) is None  # inside warmup

    def test_plateau_fires_once_and_rearms(self):
        # alpha=0.5 keeps the EWMA close to the series so the test's flat
        # stretches converge fast; production uses a slower baseline
        det = PlateauDetector(alpha=0.5, patience=10, warmup=5, tol=1e-3)
        # improving: no alert
        assert all(det.update(1.0 - 0.01 * i) is None for i in range(30))
        # stuck: exactly one alert once the EWMA settles on the flat value
        hits = [v for v in (det.update(0.71) for _ in range(60))
                if v is not None]
        assert len(hits) == 1 and hits[0]["alert"] == "plateau"
        # un-stick (a real improvement re-arms), then stick again: fires
        # exactly once more
        hits2 = [v for v in (det.update(0.3) for _ in range(60))
                 if v is not None]
        assert len(hits2) == 1 and hits2[0]["alert"] == "plateau"

    def test_throughput_regression_needs_consecutive_slow_windows(self):
        det = ThroughputDetector(frac=0.25, consec=3, warmup=3)
        for _ in range(6):
            assert det.update(0.1) is None
        # one slow window is noise
        assert det.update(0.2) is None
        assert det.update(0.1) is None  # recovery resets the streak
        assert det.update(0.2) is None
        assert det.update(0.2) is None
        v = det.update(0.2)  # third consecutive
        assert v is not None and v["alert"] == "throughput_regression"
        assert v["slowdown"] == pytest.approx(2.0)
        # the slow windows never entered the baseline
        assert det.baseline() == pytest.approx(0.1)


class TestHealthMonitor:
    def feed_steady(self, mon, n=30, loss=1.0, grad=2.0):
        rng = np.random.default_rng(1)
        for i in range(n):
            mon.on_step_metrics(
                loss_per_img=loss * (1 + 0.005 * rng.standard_normal()),
                grad_norm=grad * (1 + 0.005 * rng.standard_normal()),
                update_norm=0.1, epoch=0, step=i)

    def test_loss_spike_emits_alert(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        self.feed_steady(mon)
        mon.on_step_metrics(loss_per_img=1.5, grad_norm=2.0,
                            update_norm=0.1, epoch=0, step=31)
        alerts = sink.alerts()
        assert len(alerts) == 1
        a = alerts[0]
        assert a["signal"] == "loss" and a["alert"] == "spike"
        assert a["epoch"] == 0

    def test_grad_explosion_is_nan_precursor(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        self.feed_steady(mon)
        # 4 orders of magnitude: the about-to-overflow regime
        mon.on_step_metrics(loss_per_img=1.0, grad_norm=2e4,
                            update_norm=0.1, epoch=0, step=31)
        kinds = {(a["signal"], a["alert"]) for a in sink.alerts()}
        assert ("grad_norm", "nan_precursor") in kinds

    def test_nonfinite_grad_norm_alerts_immediately(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        mon.on_step_metrics(loss_per_img=1.0, grad_norm=float("inf"),
                            update_norm=0.1, epoch=0, step=0)
        a = sink.alerts()
        assert len(a) == 1 and a[0]["alert"] == "nan_precursor"
        assert a[0]["signal"] == "grad_norm"

    def test_cooldown_suppresses_repeats_and_summary_counts_them(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel, cooldown=100)
        self.feed_steady(mon)
        for i in range(5):  # storm: same anomaly 5x inside the cooldown
            mon.on_step_metrics(loss_per_img=3.0 + i, grad_norm=2.0,
                                update_norm=0.1, epoch=0, step=40 + i)
        assert len(sink.alerts()) == 1  # one emitted...
        mon.epoch_summary(0)
        summary = [e["payload"] for e in sink.events
                   if e["kind"] == "health.summary"][-1]
        assert summary["suppressed"] >= 1  # ...the rest counted
        assert summary["counts"]["loss/spike"] >= 2
        assert summary["loss_ewma"] is not None

    def test_stall_budget_escalation(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel, stall_budget_frac=0.15)
        mon.on_stall(seconds=1.0, frac=0.05, epoch=0)  # within budget
        assert sink.alerts() == []
        mon.on_stall(seconds=9.0, frac=0.30, epoch=1)
        a = sink.alerts()
        assert len(a) == 1
        assert a[0]["signal"] == "input" and a[0]["alert"] == "stall_budget"
        assert a[0]["value"] == 0.3 and a[0]["epoch"] == 1

    def test_stall_alert_is_not_step_cooled_across_short_epochs(self):
        # 20-step epochs vs a 50-update cooldown: persistent starvation
        # must alert every epoch, not once per cooldown window
        tel, sink = make_tel()
        mon = HealthMonitor(tel, stall_budget_frac=0.15, cooldown=50)
        for epoch in range(3):
            for i in range(20):
                mon.on_step_metrics(loss_per_img=1.0, grad_norm=2.0,
                                    update_norm=0.1, epoch=epoch, step=i)
            mon.on_stall(seconds=9.0, frac=0.30, epoch=epoch)
        stalls = [a for a in sink.alerts() if a["alert"] == "stall_budget"]
        assert [a["epoch"] for a in stalls] == [0, 1, 2]

    def test_nonfinite_loss_alert_is_never_rate_limited(self):
        tel, sink = make_tel()
        mon = HealthMonitor(tel, cooldown=10**6)
        self.feed_steady(mon)
        mon.on_step_metrics(loss_per_img=5.0, grad_norm=2.0,
                            update_norm=0.1, epoch=0, step=31)  # uses cooldown
        mon.on_nonfinite(float("nan"), epoch=0, step=32)
        kinds = [a["alert"] for a in sink.alerts()]
        assert "nan" in kinds  # the dying breath always lands


# --- train-step aux scalars (hot-path identity + health metrics) --------
def tiny_init(key):
    return {"w": jax.random.normal(key, (3, 3, 3, 1)) * 0.1,
            "b": jnp.zeros((1,))}


def tiny_apply(params, image, compute_dtype=None):
    x = image if compute_dtype is None else image.astype(compute_dtype)
    x = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["b"].astype(x.dtype)
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 8, 8, 1), (1, 8, 8, 1), "VALID")


def tiny_batch(rng, b=4, h=16, w=16):
    return {
        "image": jnp.asarray(rng.normal(size=(b, h, w, 3)), jnp.float32),
        "dmap": jnp.asarray(rng.uniform(size=(b, h // 8, w // 8, 1)),
                            jnp.float32),
        "pixel_mask": jnp.ones((b, h // 8, w // 8, 1), jnp.float32),
        "sample_mask": jnp.ones((b,), jnp.float32),
    }


class TestTrainStepHealthMetrics:
    def test_default_metrics_tree_is_unchanged(self):
        """The hot-path contract: without health_metrics the metrics dict
        (and therefore the compiled program) has exactly the pre-PR keys."""
        from can_tpu.train import create_train_state, make_lr_schedule, \
            make_optimizer, make_train_step

        opt = make_optimizer(make_lr_schedule(1e-3))
        state = create_train_state(tiny_init(jax.random.key(0)), opt)
        step = jax.jit(make_train_step(tiny_apply, opt))
        _, metrics = step(state, tiny_batch(np.random.default_rng(0)))
        assert set(metrics) == {"loss", "num_valid"}

    def test_health_metrics_adds_finite_global_norms(self):
        from can_tpu.train import create_train_state, make_lr_schedule, \
            make_optimizer, make_train_step
        from can_tpu.train.steps import global_norm

        opt = make_optimizer(make_lr_schedule(1e-3))
        state = create_train_state(tiny_init(jax.random.key(0)), opt)
        step = jax.jit(make_train_step(tiny_apply, opt, health_metrics=True))
        _, metrics = step(state, tiny_batch(np.random.default_rng(0)))
        assert set(metrics) == {"loss", "num_valid", "grad_norm",
                                "update_norm"}
        gn = float(metrics["grad_norm"])
        un = float(metrics["update_norm"])
        assert math.isfinite(gn) and gn > 0
        assert math.isfinite(un) and un > 0
        # global_norm is the plain L2 over leaves
        tree = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.zeros((2, 2))}
        assert float(global_norm(tree)) == pytest.approx(5.0)

    def test_sp_train_step_carries_the_same_scalars(self):
        from can_tpu.parallel import make_mesh
        from can_tpu.parallel.spatial import make_sp_train_step
        from can_tpu.train import create_train_state, make_lr_schedule, \
            make_optimizer
        from can_tpu.models import cannet_init

        from can_tpu.data import Batch

        mesh = make_mesh(jax.devices()[:2], dp=1, sp=2)
        opt = make_optimizer(make_lr_schedule(1e-8))
        state = create_train_state(cannet_init(jax.random.key(0)), opt)
        rng = np.random.default_rng(0)
        h, w = 32, 32
        batch = Batch(
            image=rng.normal(size=(1, h, w, 3)).astype(np.float32),
            dmap=rng.uniform(size=(1, h // 8, w // 8, 1)).astype(np.float32),
            pixel_mask=np.ones((1, h // 8, w // 8, 1), np.float32),
            sample_mask=np.ones((1,), np.float32),
        )
        from can_tpu.parallel import make_global_batch

        step = make_sp_train_step(opt, mesh, (h, w), health_metrics=True,
                                  donate=False)
        _, metrics = step(state, make_global_batch(batch, mesh, spatial=True))
        assert math.isfinite(float(metrics["grad_norm"]))
        assert math.isfinite(float(metrics["update_norm"]))


# --- loop integration ---------------------------------------------------
def make_fake_batches(n, b=2):
    return [{"image": np.zeros((b, 8, 8, 3), np.float32),
             "sample_mask": np.ones((b,), np.float32)} for _ in range(n)]


class TestLoopHealth:
    def test_spike_mid_epoch_lands_on_the_bus(self):
        from can_tpu.train import train_one_epoch

        def step(state, batch):
            i = state["i"]
            loss = 8.0 if i == 20 else 1.0 + 0.001 * (i % 5)
            return {"i": i + 1}, {"loss": loss * 2, "num_valid": 2.0,
                                  "grad_norm": 2.0, "update_norm": 0.1}

        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        train_one_epoch(step, {"i": 0}, make_fake_batches(32),
                        put_fn=lambda b: b, show_progress=False,
                        check_every=4, telemetry=tel, health=mon)
        alerts = sink.alerts()
        assert any(a["signal"] == "loss" for a in alerts)
        # the window means ride the step_window payload (the /metrics
        # gauges' feed): loss is per image, norms pass through
        sw = [e["payload"] for e in sink.events
              if e["kind"] == "step_window" and e["payload"].get("steps")]
        assert sw and sw[0]["loss"] == pytest.approx(1.0, rel=0.01)
        assert sw[0]["grad_norm"] == pytest.approx(2.0)
        assert sw[0]["update_norm"] == pytest.approx(0.1)
        # exactly one health.summary per epoch
        assert sink.kinds().count("health.summary") == 1

    def test_nan_abort_emits_alert_before_raising(self):
        from can_tpu.train import NonFiniteLossError, train_one_epoch

        def step(state, batch):
            i = state["i"]
            loss = float("nan") if i == 10 else 1.0
            return {"i": i + 1}, {"loss": loss, "num_valid": 2.0}

        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        with pytest.raises(NonFiniteLossError):
            train_one_epoch(step, {"i": 0}, make_fake_batches(16),
                            put_fn=lambda b: b, show_progress=False,
                            check_every=4, telemetry=tel, health=mon)
        a = [x for x in sink.alerts() if x["alert"] == "nan"]
        assert len(a) == 1
        assert a[0]["signal"] == "loss"
        assert not math.isfinite(a[0]["value"])

    def test_health_without_telemetry_is_ignored(self):
        """health rides telemetry; the telemetry=None hot path must not
        grow detector work (the zero-cost contract)."""
        from can_tpu.train import train_one_epoch

        def step(state, batch):
            return state, {"loss": 1.0, "num_valid": 2.0}

        tel, sink = make_tel()
        mon = HealthMonitor(tel)
        train_one_epoch(step, None, make_fake_batches(8),
                        put_fn=lambda b: b, show_progress=False,
                        telemetry=None, health=mon)
        assert sink.events == []  # monitor never fed, nothing emitted

    def test_stall_escalation_rides_epoch_boundary(self):
        from can_tpu.train import train_one_epoch

        def step(state, batch):
            return state, {"loss": 1.0, "num_valid": 2.0}

        tel, sink = make_tel()
        mon = HealthMonitor(tel, stall_budget_frac=0.0)  # any stall trips
        train_one_epoch(step, None, make_fake_batches(8),
                        put_fn=lambda b: b, show_progress=False,
                        telemetry=tel, health=mon)
        # prefetch always blocks at least once on the first batch
        assert any(a["alert"] == "stall_budget" for a in sink.alerts())


# --- cross-host run monitor ---------------------------------------------
def write_host_file(dirpath, host_id, *, step_s, t_end, hb_every=10.0,
                    start_ts=1000.0, alerts=0, restart_at=None):
    """Synthesize one host's stream with a deterministic clock: heartbeats
    every hb_every until t_end, step_window events of pace ``step_s``."""
    clock = {"t": start_ts}
    tel = obs.Telemetry(
        [obs.JsonlSink(os.path.join(dirpath,
                                    f"telemetry.host{host_id}.jsonl"))],
        host_id=host_id, clock=lambda: clock["t"])
    seq = 0
    proc_start = start_ts
    t = start_ts
    while t <= t_end:
        clock["t"] = t
        if restart_at is not None and t >= restart_at:
            proc_start = restart_at
            restart_at, seq = None, 0
        tel.emit("heartbeat", uptime_s=t - proc_start, seq=seq,
                 start_ts=proc_start)
        seq += 1
        tel.emit("step_window", steps=8, images=16.0, epoch=0,
                 samples_s=[step_s] * 8)
        t += hb_every
    for i in range(alerts):
        tel.emit("health.alert", signal="loss", alert="spike",
                 value=9.0, baseline=1.0)
    tel.close()


class TestRunMonitor:
    def test_flags_straggler_and_dead_host(self, tmp_path):
        from tools.run_monitor import analyze_dir

        d = str(tmp_path)
        # host0 healthy to t=1100; host1 3x slower AND silent from t=1040
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        write_host_file(d, 1, step_s=0.3, t_end=1040.0)
        run = analyze_dir(d, stale_after_s=30.0, skew_factor=1.5)
        assert run["stragglers"] == [1]
        assert run["dead"] == [1]
        assert not run["ok"]
        assert run["hosts"][1]["straggler_skew"] == pytest.approx(3.0)
        assert run["hosts"][1]["staleness_s"] == pytest.approx(60.0)
        assert run["hosts"][0]["staleness_s"] == pytest.approx(0.0)

    def test_healthy_fleet_is_ok(self, tmp_path):
        from tools.run_monitor import analyze_dir, format_report

        d = str(tmp_path)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        write_host_file(d, 1, step_s=0.11, t_end=1100.0)
        run = analyze_dir(d, stale_after_s=30.0)
        assert run["ok"] and run["stragglers"] == [] and run["dead"] == []
        assert "HEALTHY" in format_report(run)

    def test_restart_detected_from_heartbeat_start_ts(self, tmp_path):
        from tools.run_monitor import analyze_dir

        d = str(tmp_path)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0, restart_at=1050.0)
        run = analyze_dir(d, stale_after_s=30.0)
        assert run["hosts"][0]["restarts"] == 1
        assert run["restarts"] == 1

    def test_alert_rollup_and_torn_line(self, tmp_path):
        from tools.run_monitor import analyze_dir

        d = str(tmp_path)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0, alerts=3)
        path = os.path.join(d, "telemetry.host0.jsonl")
        with open(path, "a") as f:
            f.write('{"ts": 1100.5, "kind": "heart')  # killed mid-write
        run = analyze_dir(d, stale_after_s=30.0)
        h = run["hosts"][0]
        assert h["alerts"] == {"loss/spike": 3}
        assert h["skipped_lines"] == 1
        assert run["alerts_total"] == 3 and not run["ok"]

    def test_follow_tail_is_incremental_and_waits_for_files(self, tmp_path):
        """--follow must not die before the run writes its first event,
        must not re-parse the whole file per poll, and must keep an
        in-progress (no newline yet) line buffered instead of counting
        it torn."""
        from tools.run_monitor import HostTail, follow_dir

        d = str(tmp_path)
        kw = dict(stale_after_s=1e12, skew_factor=1.5, recent_windows=8)
        tails = {}
        assert follow_dir(d, tails, **kw) is None  # no files yet: wait
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        run = follow_dir(d, tails, **kw)
        assert run is not None and run["hosts"][0]["steps"] > 0
        path = os.path.join(d, "telemetry.host0.jsonl")
        tail = tails[0]
        offset = tail.offset
        assert offset == os.path.getsize(path)
        # a write in progress: half a line, no newline — buffered, not torn
        with open(path, "a") as f:
            f.write('{"ts": 1200.0, "kind": "heart')
        run = follow_dir(d, tails, **kw)
        assert tail.skipped == 0
        # the write completes: the event is parsed exactly once
        with open(path, "a") as f:
            f.write('beat", "step": 1, "host_id": 0, '
                    '"payload": {"seq": 99, "start_ts": 1000.0}}\n')
        run = follow_dir(d, tails, **kw)
        assert run["hosts"][0]["heartbeat_seq"] == 99
        assert tail.offset > offset  # advanced, not re-read from zero

    def test_cli_one_shot_and_exit_code(self, tmp_path):
        import subprocess
        import sys

        from tools import run_monitor  # noqa: F401 — importable

        d = str(tmp_path)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        write_host_file(d, 1, step_s=0.5, t_end=1030.0)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tool = os.path.join(repo, "tools", "run_monitor.py")
        out = subprocess.run(
            [sys.executable, tool, d, "--stale-after-s", "30", "--json"],
            capture_output=True, text=True, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 1, out.stderr  # unhealthy fleet pages
        run = json.loads(out.stdout)
        assert run["stragglers"] == [1] and run["dead"] == [1]
        out = subprocess.run(
            [sys.executable, tool, d, "--stale-after-s", "30"],
            capture_output=True, text=True, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert "STRAGGLER" in out.stdout and "DEAD" in out.stdout


# --- /metrics exporter ---------------------------------------------------
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.e+E-]+|NaN|[+-]Inf)$")


def scrape(port, path="/metrics"):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


class TestExporter:
    def test_scrape_parses_and_carries_train_and_serve_metrics(self):
        gauges = obs.GaugeSink()
        tel = obs.Telemetry([gauges])
        tel.emit("step_window", step=16, steps=8, images=16.0,
                 samples_s=[0.1, 0.12], loss=0.5, grad_norm=2.5,
                 update_norm=0.01)
        tel.emit("compile", seconds=2.0)
        tel.emit("stall", seconds=0.3)
        tel.emit("epoch", step=1, train_loss=0.4, mae=61.0)
        tel.emit("health.alert", signal="loss", alert="spike", value=9.0)
        tel.emit("memory", devices=[{"id": 0, "platform": "cpu",
                                     "peak_bytes_in_use": 1 << 30}],
                 host_rss_mb=512.0)
        ex = obs.MetricsExporter(gauges, port=0).start()
        ex.add_stats_source("serve", lambda: {
            "submitted": 10, "completed": 9, "rejected": 1,
            "queue_depth": 2, "shedding": False, "latency_p50_s": 0.01,
            "latency_max_s": None})
        try:
            body, ctype = scrape(ex.port)
            assert "text/plain" in ctype and "version=0.0.4" in ctype
            for line in body.splitlines():
                if line and not line.startswith("#"):
                    assert _PROM_LINE.match(line), line
            metrics = {l.split(maxsplit=1)[0].split("{")[0]
                       for l in body.splitlines()
                       if l and not l.startswith("#")}
            # the acceptance trio: step, loss, grad-norm gauges
            assert {"can_tpu_step", "can_tpu_loss",
                    "can_tpu_grad_norm"} <= metrics
            assert {"can_tpu_update_norm", "can_tpu_step_time_p50_s",
                    "can_tpu_mae", "can_tpu_train_loss",
                    "can_tpu_compiles_total", "can_tpu_stall_seconds_total",
                    "can_tpu_peak_hbm_bytes", "can_tpu_health_alerts_total",
                    "can_tpu_events_total"} <= metrics
            # serve's /stats counters, same scrape, same format
            assert {"can_tpu_serve_submitted_total",
                    "can_tpu_serve_queue_depth"} <= metrics
            assert 'can_tpu_health_alerts_total{signal="loss",kind="spike"} 1' \
                in body
            # healthz reports liveness + alert pressure
            hz, _ = scrape(ex.port, "/healthz")
            hz = json.loads(hz)
            assert hz["ok"] is True and hz["alerts_total"] == 1
        finally:
            ex.close()

    def test_dead_stats_source_does_not_kill_the_scrape(self):
        gauges = obs.GaugeSink()
        obs.Telemetry([gauges]).emit("epoch", step=0, train_loss=1.0)
        ex = obs.MetricsExporter(gauges, port=0).start()
        ex.add_stats_source("bad", lambda: 1 / 0)
        try:
            body, _ = scrape(ex.port)
            assert "can_tpu_train_loss" in body  # the rest survives
            assert "# source bad failed" in body
        finally:
            ex.close()

    def test_unknown_path_404s_and_port_zero_resolves(self):
        ex = obs.MetricsExporter(obs.GaugeSink(), port=0).start()
        try:
            assert ex.port > 0
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape(ex.port, "/nope")
            assert e.value.code == 404
        finally:
            ex.close()


# --- live scrape during a real CLI training run --------------------------
class TestMetricsE2E:
    def test_live_scrape_during_training_epoch(self, tmp_path):
        """Acceptance: a train CLI run with --metrics-port answers a LIVE
        /metrics scrape mid-run with step/loss/grad-norm gauges, and the
        same run's JSONL carries health.summary events (detectors armed).
        """
        import socket
        import threading
        import time

        from can_tpu.cli.train import main as train_main
        from can_tpu.data import make_synthetic_dataset

        root = str(tmp_path / "data")
        # 32 train images = 4 steps/epoch on the 8-device test mesh
        # (global batch 8): the train program crosses the ledger's
        # MIN_UNFENCED_LAUNCHES trust threshold during epoch 1, so the
        # MFU gauges the scrape waits for exist well before the run ends
        for split, n, seed in (("train", 32, 0), ("test", 8, 1)):
            make_synthetic_dataset(os.path.join(root, f"{split}_data"), n,
                                   sizes=((64, 64),), seed=seed)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        tdir = str(tmp_path / "tel")
        argv = ["--data_root", root, "--epochs", "3", "--batch-size", "1",
                "--lr", "1e-7", "--checkpoint-dir", str(tmp_path / "ck"),
                "--seed", "0", "--metrics-port", str(port),
                "--telemetry-dir", tdir]
        rc = {}
        t = threading.Thread(target=lambda: rc.update(v=train_main(argv)))
        t.start()
        got = None
        deadline = time.time() + 300
        while t.is_alive() and time.time() < deadline:
            try:
                body, _ = scrape(port)
            except OSError:
                time.sleep(0.05)
                continue
            if ("can_tpu_grad_norm" in body and "can_tpu_loss" in body
                    and "can_tpu_mfu_weighted" in body):
                got = body
                break
            time.sleep(0.05)
        t.join(timeout=300)
        assert rc.get("v") == 0
        assert got is not None, "no successful mid-run scrape"
        metrics = {l.split(maxsplit=1)[0].split("{")[0]
                   for l in got.splitlines()
                   if l and not l.startswith("#")}
        assert {"can_tpu_step", "can_tpu_loss", "can_tpu_grad_norm",
                "can_tpu_update_norm", "can_tpu_steps_total"} <= metrics
        # the perf-attribution gauges (r9): per-program cost analysis
        # joined with step timings — MFU + roofline class live mid-run
        assert {"can_tpu_mfu_weighted", "can_tpu_roofline_compute_bound",
                "can_tpu_roofline_memory_bound",
                "can_tpu_perf_programs"} <= metrics
        # the detectors were armed: one health.summary per epoch in the
        # artifact (quiet run, so alerts_total stays 0)
        events = obs.read_events(
            os.path.join(tdir, "telemetry.host0.jsonl"))
        summaries = [e for e in events if e["kind"] == "health.summary"]
        assert len(summaries) == 3
        assert summaries[-1]["payload"]["alerts_total"] == 0
        # grad-norm gauges rode the step_window payloads
        assert any("grad_norm" in e["payload"] for e in events
                   if e["kind"] == "step_window")
        # the perf-attribution artifact trail (r9): per-epoch
        # perf.summary with a train_step row carrying real
        # cost_analysis flops, and the train loop's span tree
        perfs = [e for e in events if e["kind"] == "perf.summary"]
        assert perfs, "no perf.summary in the artifact"
        detail = perfs[-1]["payload"]["detail"]
        train_rows = [r for r in detail if r["name"] == "train_step"]
        assert train_rows and train_rows[0]["flops"] > 0
        assert train_rows[0]["roofline"] in ("compute", "memory")
        assert any(r["mfu"] is not None for r in train_rows)
        span_names = {e["payload"]["name"] for e in events
                      if e["kind"] == "trace.span"}
        assert {"train_epoch", "steps", "metric_flush"} <= span_names
        # compile events carry the cost analysis when the ledger is armed
        assert any((e["payload"].get("flops") or 0) > 0 for e in events
                   if e["kind"] == "compile")


# --- heartbeat seq/start_ts (restart discrimination) --------------------
class TestHeartbeatIdentity:
    def test_heartbeat_carries_seq_and_start_ts(self):
        tel, sink = make_tel()
        hb = obs.Heartbeat(tel, interval_s=0.05)
        import time

        deadline = time.time() + 5.0
        while sink.kinds().count("heartbeat") < 3 and time.time() < deadline:
            time.sleep(0.02)
        hb.close()
        beats = [e["payload"] for e in sink.events
                 if e["kind"] == "heartbeat"]
        assert len(beats) >= 3
        assert [b["seq"] for b in beats[:3]] == [0, 1, 2]
        assert len({b["start_ts"] for b in beats}) == 1  # one process


# --- torn tail note ------------------------------------------------------
class TestTornLineNote:
    def test_read_events_counted(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        tel.emit("epoch", step=0, train_loss=1.0)
        tel.emit("heartbeat", uptime_s=1.0)
        tel.close()
        with open(path, "a") as f:
            f.write('{"ts": 1, "kind": "memo')  # crashed mid-write
        events, skipped = obs.read_events_counted(path)
        assert len(events) == 2 and skipped == 1
        assert obs.read_events(path) == events  # legacy reader unchanged

    def test_report_tool_prints_the_note(self, tmp_path):
        import subprocess
        import sys

        path = str(tmp_path / "telemetry.host0.jsonl")
        tel = obs.Telemetry([obs.JsonlSink(path)])
        tel.emit("epoch", step=0, train_loss=1.0)
        tel.close()
        with open(path, "a") as f:
            f.write('{"torn')
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tool = os.path.join(repo, "tools", "telemetry_report.py")
        out = subprocess.run([sys.executable, tool, path],
                             capture_output=True, text=True, cwd=repo,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        assert "skipped 1 torn/truncated line" in out.stdout
        out = subprocess.run([sys.executable, tool, "--json", path],
                             capture_output=True, text=True, cwd=repo,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert json.loads(out.stdout)["skipped_lines"] == 1


# --- report renders the alerts section -----------------------------------
class TestReportAlerts:
    def test_alerts_summarized_and_rendered(self):
        tel, sink = make_tel()
        tel.emit("health.alert", signal="loss", alert="spike", value=9.0)
        tel.emit("health.alert", signal="loss", alert="spike", value=8.0)
        tel.emit("health.alert", signal="input", alert="stall_budget",
                 value=0.3)
        tel.emit("health.summary", alerts_total=3, suppressed=5,
                 counts={"loss/spike": 7})
        s = obs.summarize(sink.events)
        assert s["health_alerts"] == 3
        assert s["health_alerts_by_kind"] == {"input/stall_budget": 1,
                                              "loss/spike": 2}
        assert s["health_suppressed"] == 5
        table = obs.format_report(s)
        assert "health alerts" in table and "loss/spike=2" in table
        assert "alerts suppressed" in table
        # quiet runs render no alert rows
        s0 = obs.summarize([])
        assert s0["health_alerts"] == 0
        assert "health alerts" not in obs.format_report(s0)


# --- bench regression gate ----------------------------------------------
def suite(path, entries):
    doc = {"round": 1, "results": entries}
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)
