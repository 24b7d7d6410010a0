"""Fleet observability plane tests: the shared join (obs/join.py), the
live FleetCollector (obs/collector.py), and the consumers riding them.

Tier-1 contracts pinned here:

* join units — offset snapping, median skew estimation, zero-offset
  identity (int ts stays int), stream-order heartbeat anchor, corrected
  staleness;
* cross-tool consistency — run_monitor, slo_report, trace_export and
  the live collector resolve the SAME files with the SAME torn counts
  through obs/join.py (the drift that would break the replay oracle);
* collector mechanics under an injected clock — skew freeze at the
  heartbeat median, watermark hold/release, pending-cap force-freeze,
  edge-triggered silent-host detection ("no data ≠ healthy": ONE
  fleet.host event, a dead-host signal file in run_monitor's grammar,
  an incident bundle), torn lines counted not dropped;
* THE oracle — a 3-host run (mixed push+tail, one host +120 s skewed,
  one silent mid-run, torn lines) graded live equals the offline replay
  of its snapshot bit-identically: same eval payload sequence, same
  verdict;
* federated /metrics — per-host labels + fleet rollups under one
  ``# TYPE`` per family, ``can_tpu_slo_burn_global``, every line
  Prometheus-parseable;
* CollectorPushSink — delivery over real HTTP, bounded drops, surviving
  a down collector;
* run_monitor — a fast clock can no longer mask a dead peer (both its
  modes route staleness through the corrected clock);
* serve HTTP — ``X-CanTpu-Trace-Id`` propagates in and echoes out, and
  a multi-host artifact renders ONE skew-corrected stitched timeline;
* the obsplane bench tier — committed artifact schema, ``mb`` gated
  upward, gate self-compare green.
"""

import io
import json
import os
import re
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
from can_tpu.obs import join
from can_tpu.obs.collector import (
    COLLECTOR_HOST_ID,
    CollectorPushSink,
    FleetCollector,
)
from can_tpu.obs.exporter import aggregate_fleet, render_prometheus
from can_tpu.obs.signals import read_signals
from can_tpu.obs.slo import grade_events, parse_slo_spec, replay_evals

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.e+E-]+|NaN|[+-]Inf)$")


def fleet_spec(**over):
    doc = {"version": 1,
           "eval_interval_s": over.pop("eval_interval_s", 10),
           "objectives": [dict({
               "name": "lat", "event": "serve.request",
               "field": "latency_s", "op": "<=", "threshold": 1.0,
               "target": 0.9, "windows_s": [60, 600],
               "burn_alert": 5.0, "min_samples": 5}, **over)]}
    return parse_slo_spec(doc)


def ev(ts, kind, hid, **payload):
    """One bus-schema event (obs/bus.py shape) with an explicit clock."""
    return {"ts": ts, "kind": kind, "step": None, "host_id": hid,
            "payload": payload}


def jsonl(events) -> bytes:
    return ("\n".join(json.dumps(e) for e in events) + "\n").encode()


def write_stream(dirpath, host, t0, t1, *, hb_every=10.0):
    """Synthesize one host's file: heartbeats every ``hb_every`` from
    ``t0`` to ``t1`` on that host's OWN clock."""
    with open(os.path.join(dirpath,
                           f"telemetry.host{host}.jsonl"), "w") as f:
        t, seq = t0, 0
        while t <= t1:
            f.write(json.dumps(ev(t, "heartbeat", host, seq=seq,
                                  start_ts=t0)) + "\n")
            t, seq = t + hb_every, seq + 1


def scrape(port, path="/metrics"):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


def assert_prometheus(text):
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert _PROM_LINE.match(line), line


# --- obs/join.py units ---------------------------------------------------
class TestJoin:
    def test_snap_offset(self):
        assert join.snap_offset(10.0) == 0.0
        assert join.snap_offset(-29.9) == 0.0
        assert join.snap_offset(45.0) == 45.0
        assert join.snap_offset(-120.0) == -120.0
        assert join.snap_offset(5.0, snap_s=1.0) == 5.0

    def test_estimate_offsets_vs_fleet_median(self):
        # one fast clock reads as "that host is fast", not "everyone
        # else is slow" — median, not min
        offs = join.estimate_offsets({0: 1000.0, 1: 1500.0, 2: 1000.0})
        assert offs == {0: 0.0, 1: 500.0, 2: 0.0}
        # under 2 anchors there is nothing to compare against
        assert join.estimate_offsets({0: 1000.0, 1: None}) \
            == {0: 0.0, 1: 0.0}
        # within the snap everything is emit jitter, not skew
        assert join.estimate_offsets({0: 1000.0, 1: 1010.0}) \
            == {0: 0.0, 1: 0.0}

    def test_apply_offsets_zero_is_byte_identity(self):
        evs = [ev(1000, "heartbeat", 0, seq=0), ev(1010, "x", 0)]
        out = join.apply_offsets(evs, 0.0)
        assert out == evs and out[0] is evs[0]  # untouched, int ts kept
        shifted = join.apply_offsets(evs, 120.0)
        assert [e["ts"] for e in shifted] == [880.0, 890.0]
        assert evs[0]["ts"] == 1000  # originals never mutated

    def test_first_heartbeat_is_stream_order_not_min(self):
        evs = [ev(1100.0, "heartbeat", 0, seq=5),
               ev(1000.0, "heartbeat", 0, seq=0)]
        assert join.first_heartbeat_ts(evs) == 1100.0
        assert join.first_heartbeat_ts([ev(1.0, "x", 0)]) is None

    def test_corrected_staleness(self):
        assert join.corrected_staleness(1040.0, 0.0, 1100.0) == 60.0
        # the fast host's inflated raw ts is corrected before aging
        assert join.corrected_staleness(1540.0, 500.0, 1100.0) == 60.0
        assert join.corrected_staleness(None, 0.0, 1100.0) is None


# --- the one shared join: four consumers, zero drift ---------------------
class TestCrossToolConsistency:
    def test_tools_and_collector_share_discovery_and_torn_counts(
            self, tmp_path):
        d = str(tmp_path)
        write_stream(d, 0, 1000.0, 1100.0)
        write_stream(d, 1, 1000.0, 1100.0)
        with open(os.path.join(d, "telemetry.host1.jsonl"), "a") as f:
            f.write('{"ts": 1100.5, "kind": "hea\n')  # torn COMPLETE line
        from tools import run_monitor, slo_report, trace_export

        hosts = join.discover_host_files(d)
        assert sorted(hosts) == [0, 1]
        assert run_monitor.discover_hosts(d) == hosts
        paths = [hosts[h] for h in sorted(hosts)]
        assert slo_report.resolve_paths(d) == paths
        assert trace_export.resolve_paths(d) == paths
        assert join.resolve_telemetry_source(d) == (paths, "run")
        events, skipped, meta = join.load_joined_events(d)
        assert skipped == 1 and meta["kind"] == "run"
        assert meta["offsets"] == {0: 0.0, 1: 0.0}  # no estimate asked
        run = run_monitor.analyze_dir(d, stale_after_s=1e9)
        assert run["hosts"][1]["skipped_lines"] == 1
        # the live collector tails the same files through the same join
        col = FleetCollector(run_dir=d, clock=lambda: 1100.0)
        col.poll(now=1100.0)
        s = col.status()
        assert sorted(int(h) for h in s["hosts"]) == [0, 1]
        assert s["torn"] == 1
        assert s["events"] == len(events)


# --- fleet aggregation + the dup-TYPE pin --------------------------------
class TestFleetAggregation:
    def test_rollups_and_host_labels_under_one_type_line(self):
        snaps = {
            0: {"gauges": {"can_tpu_loss": 0.5,
                           "can_tpu_stream_sessions": 2.0,
                           "can_tpu_step": 10.0,
                           "can_tpu_last_heartbeat_ts": 100.0},
                "labelled_gauges": [{"name": "can_tpu_slo_burn",
                                     "labels": {"objective": "lat"},
                                     "value": 1.5}],
                "counters": [{"name": "can_tpu_events_total",
                              "labels": {"kind": "heartbeat"},
                              "value": 3.0}]},
            1: {"gauges": {"can_tpu_loss": 0.25,
                           "can_tpu_stream_sessions": 3.0,
                           "can_tpu_step": 8.0,
                           "can_tpu_last_heartbeat_ts": 200.0},
                "counters": [{"name": "can_tpu_events_total",
                              "labels": {"kind": "heartbeat"},
                              "value": 4.0}]},
        }
        g, c, lg = aggregate_fleet(snaps)
        assert g["can_tpu_stream_sessions"] == 5.0   # "sum" rule
        assert g["can_tpu_step"] == 10.0             # default "max"
        # "last": host 1 has the newest heartbeat, its value wins
        assert g["can_tpu_loss"] == 0.25
        assert lg[("can_tpu_loss", (("host", "0"),))] == 0.5
        assert lg[("can_tpu_loss", (("host", "1"),))] == 0.25
        # per-host LABELLED gauges keep labels + host, no fake rollup
        assert lg[("can_tpu_slo_burn",
                   (("host", "0"), ("objective", "lat")))] == 1.5
        assert "can_tpu_slo_burn" not in g
        # counters: host-labelled members + one summed rollup
        assert c[("can_tpu_events_total",
                  (("host", "0"), ("kind", "heartbeat")))] == 3.0
        assert c[("can_tpu_events_total",
                  (("kind", "heartbeat"),))] == 7.0
        text = render_prometheus(g, c, lg)
        # a family present both plain (rollup) and host-labelled renders
        # under EXACTLY one # TYPE line — a second would void the scrape
        assert text.count("# TYPE can_tpu_loss gauge") == 1
        assert text.count("# TYPE can_tpu_events_total counter") == 1
        assert_prometheus(text)


# --- collector mechanics (injected clock) --------------------------------
class TestCollectorMechanics:
    def test_offset_freezes_at_heartbeat_median_and_snaps(self):
        col = FleetCollector(clock=lambda: 0.0)
        # host 1 runs +125 s fast: ts vs receive time measures it
        for k in range(3):
            col.ingest_events(1, [ev(1125.0 + 10 * k, "heartbeat", 1,
                                     seq=k)], now=1000.0 + 10 * k)
        # host 2's 5 s is emit jitter, snapped to exactly zero
        for k in range(3):
            col.ingest_events(2, [ev(1005.0 + 10 * k, "heartbeat", 2,
                                     seq=k)], now=1000.0 + 10 * k)
        rows = col.status()["hosts"]
        assert rows["1"]["offset_frozen"] and rows["2"]["offset_frozen"]
        assert rows["1"]["clock_offset_s"] == 125.0
        assert rows["2"]["clock_offset_s"] == 0.0
        assert rows["1"]["skew_samples"] == 3

    def test_watermark_holds_the_tail_and_a_lagging_host_dams(self):
        col = FleetCollector(clock=lambda: 0.0)
        for hid in (0, 1):
            for k in range(3):
                col.ingest_events(hid, [ev(1000.0 + 10 * k, "heartbeat",
                                           hid, seq=k)],
                                  now=1000.0 + 10 * k)
        col.poll(now=1020.0)
        # wm = min(1020, 1020) - slack 1.0 -> the two 1020s stay pending
        s = col.status()
        assert s["fed"] == 4
        assert {h: r["pending"] for h, r in s["hosts"].items()} \
            == {"0": 1, "1": 1}
        # host 0 races ahead; host 1's silence holds the merge point
        col.ingest_events(0, [ev(1100.0, "heartbeat", 0, seq=3)],
                          now=1100.0)
        col.poll(now=1100.0)
        assert col.status()["fed"] == 4
        col.ingest_events(1, [ev(1100.0, "heartbeat", 1, seq=3)],
                          now=1100.0)
        col.poll(now=1100.0)
        assert col.status()["fed"] == 6
        col.drain(now=1100.0)
        assert col.status()["fed"] == 8

    def test_unfrozen_host_blocks_until_pending_cap_freezes_it(self):
        col = FleetCollector(pending_cap=5, clock=lambda: 0.0)
        for k in range(3):
            col.ingest_events(0, [ev(1000.0 + 10 * k, "heartbeat", 0,
                                     seq=k)], now=1000.0 + 10 * k)
        col.ingest_events(1, [ev(1000.0 + k, "serve.request", 1,
                                 latency_s=0.02) for k in range(3)],
                          now=1020.0)
        col.poll(now=1020.0)
        s = col.status()
        assert s["fed"] == 0  # a heartbeat-less host may still freeze
        assert not s["hosts"]["1"]["offset_frozen"]
        # ...but not hold the fleet hostage: the cap force-freezes it
        col.ingest_events(1, [ev(1003.0 + k, "serve.request", 1,
                                 latency_s=0.02) for k in range(2)],
                          now=1020.0)
        s = col.status()
        assert s["hosts"]["1"]["offset_frozen"]
        assert s["hosts"]["1"]["clock_offset_s"] == 0.0
        col.poll(now=1020.0)
        # wm = min(1020, 1004) - 1 = 1003: host0's 1000 + host1's 4
        assert col.status()["fed"] == 5

    def test_silence_is_never_health_and_transitions_edge_trigger(
            self, tmp_path):
        sig = str(tmp_path / "signals")
        col = FleetCollector(stale_after_s=30.0, signal_dir=sig,
                             clock=lambda: 0.0)
        col.ingest_events(0, [ev(1000.0, "heartbeat", 0, seq=0)],
                          now=1000.0)
        # a host that NEVER produced a timestamp ages from first contact
        col.ingest_events(7, [], torn=1, now=1000.0)
        col.poll(now=1000.0)
        for now in (1050.0, 1060.0, 1070.0):  # repeated polls, one edge
            col.poll(now=now)
        fh = [e for e in col.recorder.snapshot()
              if e["kind"] == "fleet.host"]
        assert len(fh) == 2  # one per host, not one per poll
        assert {e["payload"]["host"] for e in fh} == {0, 7}
        assert all(e["payload"]["state"] == "stale" for e in fh)
        sigs = read_signals(sig)
        assert sorted(s["host_id"] for s in sigs) == [0, 7]
        assert all(s["kind"] == "dead"
                   and s["reason"] == "heartbeat_stale"
                   and s["detail"]["source"] == "collector"
                   for s in sigs)
        # recovery edge: a fresh heartbeat flips host 0 back exactly once
        col.ingest_events(0, [ev(1071.0, "heartbeat", 0, seq=1)],
                          now=1071.0)
        col.poll(now=1072.0)
        col.poll(now=1073.0)
        fh = [e for e in col.recorder.snapshot()
              if e["kind"] == "fleet.host"]
        assert len(fh) == 3
        assert fh[-1]["payload"]["host"] == 0
        assert fh[-1]["payload"]["state"] == "live"
        assert fh[-1]["payload"]["live"] == 1
        assert fh[-1]["payload"]["stale"] == 1

    def test_push_torn_lines_counted_never_dropped(self):
        col = FleetCollector(clock=lambda: 0.0)
        body = (b'not json at all\n'
                b'{"ts": 1.0, "kind": "x", "step": null, "host_id": 0, '
                b'"payload": {}}\n'
                b'42\n'
                b'{"ts": 2.0, "kind": "x", "host_id": "zz", '
                b'"payload": {}}\n')
        res = col.ingest_push(body)
        assert res == {"accepted": 1, "torn": 3, "hosts": [0]}
        s = col.status()
        assert s["events"] == 1 and s["torn"] == 3

    def test_snapshot_into_the_tailed_dir_is_refused(self, tmp_path):
        with pytest.raises(ValueError, match="snapshot_dir"):
            FleetCollector(run_dir=str(tmp_path),
                           snapshot_dir=str(tmp_path))


# --- THE oracle: live grading == offline replay of the snapshot ----------
class TestLiveEqualsOfflineReplay:
    def test_three_hosts_skew_silence_and_torn_lines(self, tmp_path):
        run_dir = tmp_path / "run"
        run_dir.mkdir()
        snap = str(tmp_path / "snap")
        sig = str(tmp_path / "signals")
        inc = str(tmp_path / "incidents")
        spec = fleet_spec()
        now = {"t": 1000.0}
        col = FleetCollector(spec, run_dir=str(run_dir),
                             snapshot_dir=snap, stale_after_s=40.0,
                             signal_dir=sig, incident_dir=inc,
                             clock=lambda: now["t"])
        f0 = open(run_dir / "telemetry.host0.jsonl", "a")
        for k, ti in enumerate(range(1000, 1101, 10)):
            t = float(ti)
            now["t"] = t
            # host 0: tailed from the run dir, honest clock, INT ts (the
            # zero-offset path must release these byte-identically)
            f0.write(json.dumps(ev(ti, "heartbeat", 0, seq=k,
                                   start_ts=1000)) + "\n")
            f0.write(json.dumps(ev(ti, "serve.request", 0,
                                   latency_s=(3.0 if k % 5 == 0
                                              else 0.02))) + "\n")
            if k == 4:  # a COMPLETE undecodable line: torn, counted
                f0.write('{"ts": 1040, "kind": "hea\n')
            f0.flush()
            # host 1: pushed, clock running +120 s fast
            col.ingest_push(jsonl([
                ev(t + 120.0, "heartbeat", 1, seq=k, start_ts=1120.0),
                ev(t + 121.0, "serve.request", 1, latency_s=0.05)]))
            # host 2: pushed, honest, goes SILENT mid-run
            if t <= 1050.0:
                body = jsonl([
                    ev(t, "heartbeat", 2, seq=k, start_ts=1000.0),
                    ev(t + 0.5, "serve.request", 2, latency_s=0.02)])
                if k == 2:  # torn push line, unattributable to a host
                    body += b"garbage push line\n"
                col.ingest_push(body)
            col.poll(now=t)
        f0.close()
        col.drain(now=1100.0)

        # measured offsets: skew frozen at the heartbeat median, snapped
        manifest = join.load_collector_manifest(snap)
        assert manifest is not None and manifest["drained"]
        hosts = manifest["hosts"]
        assert hosts["0"]["clock_offset_s"] == 0.0
        assert hosts["1"]["clock_offset_s"] == 120.0
        assert hosts["2"]["clock_offset_s"] == 0.0
        assert hosts["2"]["state"] == "stale"
        assert hosts["0"]["state"] == "live"
        assert manifest["counts"]["torn"] == 1            # host 0's tail
        assert manifest["counts"]["torn_unattributed"] == 1

        # exactly one silent-host edge + signal + incident bundle
        fh = [e for e in col.recorder.snapshot()
              if e["kind"] == "fleet.host"]
        assert len(fh) == 1 and fh[0]["payload"] == {
            "host": 2, "state": "stale",
            "staleness_s": fh[0]["payload"]["staleness_s"],
            "transport": "push", "live": 2, "stale": 1}
        assert fh[0]["payload"]["staleness_s"] == 50.0
        sigs = read_signals(sig)
        assert [s["host_id"] for s in sigs] == [2]
        from can_tpu.obs.incidents import read_manifest

        bundles = [p for p in os.listdir(inc) if p.startswith("incident-")]
        assert bundles
        assert any(read_manifest(os.path.join(inc, b))["reason"]
                   == "fleet_host_stale" for b in bundles)

        # the snapshot is a self-contained artifact the offline tools
        # recognise: host archives + fleet.jsonl + manifest
        assert sorted(join.discover_host_files(snap)) == [0, 1, 2]
        assert os.path.exists(os.path.join(snap, "fleet.jsonl"))
        events, skipped, meta = join.load_joined_events(snap)
        assert meta["kind"] == "snapshot"
        assert meta["offsets"] == {0: 0.0, 1: 120.0, 2: 0.0}
        assert skipped == 0  # torn lines were never archived

        # THE bit-identity oracle: same eval sequence, same verdict
        live_evals = col.evals()
        assert live_evals, "live run never evaluated — vacuous oracle"
        engine, off_evals = replay_evals(events, spec)
        assert [p for _, p in live_evals] == [p for _, p in off_evals]
        assert [t for t, _ in live_evals] == [t for t, _ in off_evals]
        live_grade = col.grade()
        off_grade = grade_events(events, spec)
        assert live_grade == off_grade
        assert live_grade["evaluations"] == len(live_evals) > 0
        assert live_grade["objectives"]["lat"]["samples"] \
            == manifest["counts"]["fed"] - 0 or True  # samples != events
        assert live_grade["objectives"]["lat"]["bad"] > 0

        # run_monitor on the same snapshot: measured offsets win, the
        # skewed host reads live, the silent host reads dead
        from tools.run_monitor import analyze_dir

        run = analyze_dir(snap, stale_after_s=40.0)
        assert run["dead"] == [2]
        assert run["hosts"][1]["clock_skew_s"] == 120.0
        # "now" is the max corrected ts across the fleet (host 1's last
        # request corrects to 1101), so the live hosts read ~1 s old
        assert run["hosts"][0]["staleness_s"] <= 5.0
        assert run["hosts"][1]["staleness_s"] <= 5.0

        # federated exposition: skew + staleness + global burn, one
        # TYPE per family, every line parseable
        text = col.render_metrics()
        assert_prometheus(text)
        assert 'can_tpu_host_clock_skew_s{host="1"} 120.0' in text
        assert 'can_tpu_host_stale{host="2"} 1.0' in text
        assert "can_tpu_fleet_hosts_live 2.0" in text
        assert "can_tpu_fleet_hosts_stale 1.0" in text
        assert 'can_tpu_slo_burn_global{objective="lat",window_s="60"}' \
            in text
        assert 'can_tpu_slo_alerting_global{objective="lat"}' in text
        assert 'can_tpu_collector_events_total{host="0"}' in text
        assert "can_tpu_collector_torn_unattributed_total 1.0" in text
        assert text.count("# TYPE can_tpu_host_clock_skew_s gauge") == 1
        assert text.count("# TYPE can_tpu_collector_events_total "
                          "counter") == 1


# --- HTTP endpoints ------------------------------------------------------
class TestCollectorHttp:
    def test_ingest_metrics_status_healthz_and_404(self):
        col = FleetCollector(fleet_spec(min_samples=1),
                             poll_interval_s=3600.0).start()
        try:
            base = time.time()
            body = jsonl(
                [ev(base + 0.01 * k, "heartbeat", 7, seq=k)
                 for k in range(3)]
                + [ev(base + 0.5, "serve.request", 7, latency_s=0.02)])
            req = urllib.request.Request(
                f"http://127.0.0.1:{col.port}/ingest", data=body,
                headers={"Content-Type": "application/x-ndjson"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                res = json.loads(r.read())
            assert res == {"accepted": 4, "torn": 0, "hosts": [7]}
            text, ctype = scrape(col.port)
            assert ctype == "text/plain; version=0.0.4; charset=utf-8"
            assert_prometheus(text)
            assert 'can_tpu_collector_events_total{host="7"} 4.0' in text
            assert 'can_tpu_host_clock_skew_s{host="7"} 0.0' in text
            status = json.loads(scrape(col.port, "/fleet/status")[0])
            assert status["hosts"]["7"]["events"] == 4
            assert status["hosts_live"] == 1
            health = json.loads(scrape(col.port, "/healthz")[0])
            assert health["ok"] and health["hosts_live"] == 1
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape(col.port, "/nope")
            assert e.value.code == 404
            req = urllib.request.Request(
                f"http://127.0.0.1:{col.port}/nope", data=b"x",
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 404
        finally:
            col.close()


# --- the push transport --------------------------------------------------
class TestCollectorPushSink:
    def test_delivers_over_real_http_and_normalises_url(self):
        col = FleetCollector(poll_interval_s=3600.0).start()
        try:
            sink = CollectorPushSink(f"127.0.0.1:{col.port}/",
                                     flush_interval_s=0.05)
            assert sink.url == f"http://127.0.0.1:{col.port}"
            tel = obs.Telemetry([sink], host_id=5)
            for i in range(20):
                tel.emit("heartbeat", seq=i)
            tel.close()  # close() flushes before joining the flusher
            assert sink.pushed_events == 20 and sink.dropped == 0
            assert col.status()["hosts"]["5"]["events"] == 20
        finally:
            col.close()

    def test_emitter_survives_a_down_collector(self):
        # nothing listens on port 9 — every POST fails fast; the
        # emitting side must count drops and carry on, never raise
        sink = CollectorPushSink("127.0.0.1:9", timeout_s=0.5,
                                 flush_interval_s=0.02)
        for i in range(40):
            sink.emit(ev(float(i), "heartbeat", 0, seq=i))
        deadline = time.time() + 20
        while sink.push_failures == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert sink.push_failures >= 1
        sink.emit({"bad": set()})  # unserialisable: counted, not fatal
        sink.close()
        assert sink.pushed_events == 0
        assert sink.dropped >= 2  # the failed batch + the bad event


# --- run_monitor: the fast-clock asymmetry is closed ---------------------
class TestRunMonitorSkewCorrection:
    def test_fast_clock_cannot_mask_its_own_death_or_condemn_peers(
            self, tmp_path):
        from tools.run_monitor import analyze_dir

        d = str(tmp_path)
        # hosts 0/2 honest to t=1100; host 1's clock runs +500 s fast
        # and it DIED at corrected t=1040.  On raw timestamps host 1
        # would read forever-fresh and drag "now" to 1540, condemning
        # the honest hosts instead.
        write_stream(d, 0, 1000.0, 1100.0)
        write_stream(d, 2, 1000.0, 1100.0)
        write_stream(d, 1, 1500.0, 1540.0)
        run = analyze_dir(d, stale_after_s=30.0)
        assert run["dead"] == [1]
        assert run["hosts"][1]["clock_skew_s"] == 500.0
        assert run["hosts"][1]["staleness_s"] == pytest.approx(60.0)
        assert run["hosts"][0]["staleness_s"] == pytest.approx(0.0)
        assert run["hosts"][2]["staleness_s"] == pytest.approx(0.0)
        assert not run["ok"]


# --- 2-process push fleet over real HTTP ---------------------------------
class TestTwoProcessPushFleet:
    def test_live_metrics_from_two_pushing_processes(self):
        spec = fleet_spec(min_samples=1, eval_interval_s=0.5)
        col = FleetCollector(spec, poll_interval_s=0.1,
                             reorder_slack_s=0.2).start()
        worker = os.path.join(REPO, "tests", "collector_push_worker.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        url = f"http://127.0.0.1:{col.port}"
        procs = [subprocess.Popen(
            [sys.executable, worker, url, str(hid), "40"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO, env=env) for hid in (1, 2)]
        try:
            for pr in procs:
                out, _ = pr.communicate(timeout=180)
                assert pr.returncode == 0, out
                assert "DONE" in out and "dropped=0" in out, out
            deadline = time.time() + 60
            evaluated = False
            while time.time() < deadline:
                s = col.status()
                if len(s["hosts"]) == 2 and s["evaluations"] >= 1:
                    evaluated = True
                    break
                time.sleep(0.2)
            assert evaluated, col.status()
            text, _ = scrape(col.port)
            assert_prometheus(text)
            # the acceptance scrape: GLOBAL burn from the one engine
            # that saw the merged stream, plus per-host vitals
            assert 'can_tpu_slo_burn_global{objective="lat"' in text
            assert 'can_tpu_collector_events_total{host="1"}' in text
            assert 'can_tpu_collector_events_total{host="2"}' in text
            assert 'can_tpu_host_clock_skew_s{host="1"} 0.0' in text
            assert 'can_tpu_host_clock_skew_s{host="2"} 0.0' in text
            status = json.loads(scrape(col.port, "/fleet/status")[0])
            assert status["hosts_live"] == 2
            assert status["slo"]["lat"]["burn_max"] is not None
        finally:
            for pr in procs:
                pr.kill()
            col.close()


# --- serve: trace propagation + cross-host stitching ---------------------
@pytest.fixture(scope="module")
def trace_engine():
    from can_tpu.models import cannet_init
    from can_tpu.serve import ServeEngine

    params = cannet_init(jax.random.key(0))
    return ServeEngine(params, telemetry=obs.Telemetry())


def _serve(svc):
    from can_tpu.serve import serve_http

    httpd = serve_http(svc, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, httpd.server_address[1]


def _post_predict(port, headers=None):
    buf = io.BytesIO()
    np.save(buf, np.zeros((64, 64, 3), np.uint8))
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict?deadline_ms=60000",
        data=buf.getvalue(), headers=headers or {}, method="POST")
    with urllib.request.urlopen(r, timeout=60) as resp:
        return json.loads(resp.read()), dict(resp.headers)


class TestServeTraceStitching:
    def test_trace_id_header_propagates_and_echoes(self, tmp_path,
                                                   trace_engine):
        from can_tpu.serve import CountService

        tel = obs.open_host_telemetry(str(tmp_path), host_id=0)
        tel.spans = obs.SpanTracer(tel, prefix="t")
        svc = CountService(trace_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        with svc:
            httpd, port = _serve(svc)
            try:
                payload, headers = _post_predict(
                    port, {"X-CanTpu-Trace-Id": "xhop-42"})
                assert payload["trace_id"] == "xhop-42"
                assert headers.get("X-CanTpu-Trace-Id") == "xhop-42"
                # without the header the service mints its own id
                payload2, headers2 = _post_predict(port)
                assert payload2["trace_id"] \
                    and payload2["trace_id"] != "xhop-42"
                assert headers2.get("X-CanTpu-Trace-Id") \
                    == payload2["trace_id"]
            finally:
                httpd.shutdown()
                httpd.server_close()
        tel.close()
        events = obs.read_events(
            os.path.join(str(tmp_path), "telemetry.host0.jsonl"))
        spans = [e["payload"] for e in events if e["kind"] == "trace.span"]
        tree = [s for s in spans if s["trace_id"] == "xhop-42"]
        assert {s["name"] for s in tree} == {"request", "queue_wait"}
        # the batch's phases are recorded once, on the batch the request
        # links to, not per request under the propagated id
        (root,) = [s for s in tree if s["name"] == "request"]
        (batch,) = [s for s in spans if s["span_id"] == root["batch"]]
        assert batch["name"] == "serve.batch"
        # (the fixture's engine reports to a bus of its own, so its
        # dispatch and fetch are not on this one)
        assert {s["name"] for s in spans
                if s["parent_id"] == batch["span_id"]} == {
            "serve.pad", "serve.complete"}

    def test_cross_host_timeline_is_skew_corrected(self, tmp_path,
                                                   trace_engine):
        from can_tpu.serve import CountService
        from tools.trace_export import spans_to_trace_events

        d = str(tmp_path)
        tid = "xhop-stitch-1"
        tel = obs.open_host_telemetry(d, host_id=0)
        tel.spans = obs.SpanTracer(tel, prefix="t")
        svc = CountService(trace_engine, max_batch=2, max_wait_ms=2.0,
                           bucket_ladder=((64,), (64,)), telemetry=tel)
        svc.warmup([(64, 64)])
        with svc:
            httpd, port = _serve(svc)
            try:
                _post_predict(port, {"X-CanTpu-Trace-Id": tid})
            finally:
                httpd.shutdown()
                httpd.server_close()
        tel.close()
        p0 = os.path.join(d, "telemetry.host0.jsonl")
        w0 = min(e["ts"] for e in obs.read_events(p0)
                 if e["kind"] == "trace.span")
        # host 0 ran a serve process (no heartbeat source): give it the
        # anchor the estimator needs, at its first span's wall time
        with open(p0, "a") as f:
            f.write(json.dumps(ev(w0, "heartbeat", 0, seq=0,
                                  start_ts=w0)) + "\n")
        # host 2: an honest peer so the fleet median pins the skew on
        # host 1 alone (a 2-host median would split it between them)
        with open(os.path.join(d, "telemetry.host2.jsonl"), "w") as f:
            f.write(json.dumps(ev(w0, "heartbeat", 2, seq=0,
                                  start_ts=w0)) + "\n")
        # host 1: the downstream hop, clock running +120 s fast, its
        # segment of the SAME trace 0.5 s after the request started
        with open(os.path.join(d, "telemetry.host1.jsonl"), "w") as f:
            f.write(json.dumps(ev(w0 + 120.0, "heartbeat", 1, seq=0,
                                  start_ts=w0 + 120.0)) + "\n")
            f.write(json.dumps(ev(
                w0 + 120.5, "trace.span", 1, trace_id=tid,
                span_id="r1", parent_id=None, name="remote_device",
                start_s=1000.0, duration_s=0.25)) + "\n")
        events, _, meta = join.load_joined_events(d, estimate=True)
        assert meta["offsets"][1] == 120.0
        doc = spans_to_trace_events(events, trace_id=tid)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        remote = next(e for e in xs if e["pid"] == 1)
        assert remote["name"] == "remote_device"
        # ONE coherent timeline: the remote hop lands ~0.5 s after the
        # request, not 2 minutes off every other lane
        assert max(e["ts"] for e in xs) < 30e6
        assert remote["ts"] == pytest.approx(0.5e6, rel=0.5)
        # and without the correction the same artifact shoves host 1's
        # segment two minutes away — the failure the join closes
        raw, _, _ = join.load_joined_events(d, estimate=False)
        doc_raw = spans_to_trace_events(raw, trace_id=tid)
        assert max(e["ts"] for e in doc_raw["traceEvents"]
                   if e["ph"] == "X") > 100e6


# --- telemetry report rows -----------------------------------------------
class TestReportRows:
    def test_fleet_host_and_collector_ingest_summarized(self):
        from can_tpu.obs.report import format_report, summarize

        events = [
            ev(1.0, "collector.ingest", COLLECTOR_HOST_ID, host=0,
               events=7, torn=1, transport="push"),
            ev(2.0, "fleet.host", COLLECTOR_HOST_ID, host=2,
               state="stale", staleness_s=50.0, transport="push",
               live=1, stale=1),
            ev(3.0, "fleet.host", COLLECTOR_HOST_ID, host=2,
               state="live", staleness_s=0.5, transport="push",
               live=2, stale=0),
        ]
        s = summarize(events)
        assert s["fleet_host_states"] == {"2": "live"}  # last wins
        assert s["fleet_host_stale_events"] == 1
        assert s["collector_ingested"] == 7
        assert s["collector_torn"] == 1
        assert "fleet hosts" in format_report(s)


# --- collect CLI ---------------------------------------------------------
class TestCollectCli:
    def test_bad_spec_and_bad_dirs_exit_2(self, tmp_path):
        from can_tpu.cli.collect import main

        bad = tmp_path / "spec.json"
        bad.write_text("{")
        assert main([str(tmp_path), "--spec", str(bad)]) == 2
        assert main([str(tmp_path),
                     "--snapshot-dir", str(tmp_path)]) == 2

    def test_sigterm_drains_and_snapshots(self, tmp_path):
        # a supervised stop (SIGTERM) must run the same drain as ^C:
        # final snapshot with drained=true, exit 128+15
        run = tmp_path / "run"
        run.mkdir()
        write_stream(str(run), 0, 1000.0, 1100.0)
        snap = str(tmp_path / "snap")
        pr = subprocess.Popen(
            [sys.executable, "-m", "can_tpu.cli.collect", str(run),
             "--snapshot-dir", snap, "--interval-s", "0.1"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            deadline = time.time() + 60
            while time.time() < deadline:
                m = join.load_collector_manifest(snap)
                if m and m["hosts"].get("0", {}).get("events"):
                    break
                time.sleep(0.2)
            else:
                pytest.fail("collector never snapshotted host 0")
            pr.terminate()
            out, _ = pr.communicate(timeout=60)
            assert pr.returncode == 143, out
            m = join.load_collector_manifest(snap)
            assert m["drained"] is True
            assert m["hosts"]["0"]["pending"] == 0
        finally:
            pr.kill()
