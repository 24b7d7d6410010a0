"""Serving fleet (can_tpu/serve/fleet.py + quant.py): replicated engines,
work-stealing dispatch, failure quarantine, blue/green rollout, quantized
predict programs.

The contract under test (ISSUE 8 acceptance):

* a 2+ replica fleet on the test mesh sustains mixed-resolution traffic
  with ZERO new compiles after warmup;
* a replica whose predict raises is quarantined, its in-flight batch
  re-dispatched exactly once, and no admitted request is lost — the
  quarantine is visible on /healthz and in per-replica stats;
* ``rollout()`` under live load completes with zero rejected requests
  and flips every live replica to the new generation;
* int8/bf16 predict programs grade on the f32 count-delta parity ladder;
* work stealing: no replica starves under a skewed bucket mix.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from can_tpu import obs
from can_tpu.models import cannet_init
from can_tpu.serve import (
    REJECT_ERROR,
    CountService,
    FleetEngine,
    RejectedError,
    ServeEngine,
    parity_report,
    prepare_image,
    quantize_tree,
    serve_http,
    tree_signature,
)
from can_tpu.serve.quant import (
    dequantize_tree,
    grade_parity,
    is_quantized_leaf,
    param_bytes,
    quantize_int8,
)


@pytest.fixture(scope="module")
def params():
    return cannet_init(jax.random.key(0))


@pytest.fixture(scope="module")
def params2():
    return cannet_init(jax.random.key(1))


def make_image(h=64, w=64, seed=0):
    rng = np.random.default_rng(seed)
    return prepare_image((rng.uniform(0, 1, (h, w, 3)) * 255)
                         .astype(np.uint8))


def make_fleet_service(params, *, replicas=2, serve_dtype="f32",
                       ladder=((64,), (64,)), max_batch=2,
                       run_config=None, telemetry=None, **kw):
    tel = telemetry if telemetry is not None else obs.Telemetry()
    fleet = FleetEngine(params, replicas=replicas, serve_dtype=serve_dtype,
                        telemetry=tel, run_config=run_config)
    svc = CountService(fleet, max_batch=max_batch, max_wait_ms=1.0,
                       queue_capacity=256, bucket_ladder=ladder,
                       telemetry=tel, **kw)
    svc.warmup([(h, w) for h in ladder[0] for w in ladder[1]])
    return fleet, svc


# --- quantization unit layer --------------------------------------------
class TestQuant:
    def test_int8_per_channel_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        # channels with wildly different magnitude: per-channel scales
        # must keep the quiet channel's relative error at int8 grain
        w = rng.normal(size=(3, 3, 8, 4)).astype(np.float32)
        w[..., 0] *= 100.0
        w[..., 1] *= 0.001
        q = quantize_int8(w)
        assert q["q"].dtype == np.int8 and q["scale"].shape == (4,)
        back = np.asarray(q["q"], np.float32) * q["scale"]
        for c in range(4):
            denom = np.abs(w[..., c]).max()
            assert np.abs(back[..., c] - w[..., c]).max() / denom < 1 / 127

    def test_quantize_tree_modes(self, params):
        assert quantize_tree(params, "f32") is params
        b16 = quantize_tree(params, "bf16")
        assert str(jax.tree.leaves(b16)[0].dtype) == "bfloat16"
        i8 = quantize_tree(params, "int8")
        qleaves = [x for x in jax.tree.leaves(
            i8, is_leaf=is_quantized_leaf) if is_quantized_leaf(x)]
        # 10 frontend + 8 context + 6 backend kernels; output conv stays f32
        assert len(qleaves) == 24
        f32_b, i8_b, b16_b = (param_bytes(params), param_bytes(i8),
                              param_bytes(b16))
        assert i8_b < f32_b / 3.5 and b16_b < f32_b / 1.9
        # dequant restores the f32 tree signature cannet_apply expects
        d = dequantize_tree(i8, "int8")
        assert tree_signature(d)[0] == tree_signature(params)[0]
        with pytest.raises(ValueError, match="serve_dtype"):
            quantize_tree(params, "fp4")

    def test_grade_ladder(self):
        assert grade_parity(0.0) == "exact"
        assert grade_parity(5e-4) == "tight"
        assert grade_parity(1e-2) == "serve"
        assert grade_parity(5e-2) == "loose"
        assert grade_parity(0.5) == "fail"


# --- parity ladder vs f32 -----------------------------------------------
class TestParityLadder:
    def test_quantized_modes_grade_on_ladder(self, params):
        tel = obs.Telemetry()
        ref = ServeEngine(params, telemetry=tel, name="pl_f32")
        images = [make_image(64, 64, s) for s in range(3)]
        for mode, worst_ok in (("bf16", "serve"), ("int8", "serve")):
            eng = ServeEngine(params, serve_dtype=mode, telemetry=tel,
                              name=f"pl_{mode}")
            rep = parity_report(eng, ref, images)
            assert rep["images"] == 3
            assert rep["grade"] != "fail", rep
            # the ladder itself is recorded with the artifact
            assert [r["rung"] for r in rep["ladder"]] == [
                "exact", "tight", "serve", "loose"]
            order = [r["rung"] for r in rep["ladder"]]
            assert order.index(rep["grade"]) <= order.index(worst_ok), rep

    def test_f32_vs_itself_is_exact(self, params):
        tel = obs.Telemetry()
        a = ServeEngine(params, telemetry=tel, name="px_a")
        b = ServeEngine(params, telemetry=tel, name="px_b")
        rep = parity_report(a, b, [make_image(64, 64, 9)])
        assert rep["grade"] == "exact"
        assert rep["worst_rel_count_delta"] == 0.0


# --- fleet serving ------------------------------------------------------
class TestFleetServing:
    def test_replica_count_validation(self, params):
        with pytest.raises(ValueError, match="exceeds"):
            FleetEngine(params, replicas=len(jax.devices()) + 1)
        with pytest.raises(ValueError, match="replicas"):
            FleetEngine(params, replicas=0)

    def test_mixed_traffic_zero_new_compiles_and_no_starvation(self,
                                                               params):
        """Acceptance: 2 replicas, mixed resolutions, every request
        resolves, compile count frozen after warmup, and BOTH replicas
        execute batches even under a skewed bucket mix (work stealing:
        an idle replica pulls whatever is next, so no replica starves)."""
        fleet, svc = make_fleet_service(
            params, ladder=((64, 96), (64,)), max_batch=2)
        compiles_after_warmup = fleet.compile_count
        # skewed mix: ~90% of traffic in one bucket
        sizes = [(64, 64)] * 9 + [(96, 64)]
        imgs = {s: make_image(*s, seed=s[0]) for s in set(sizes)}
        with svc:
            tickets = [svc.submit(imgs[sizes[i % len(sizes)]],
                                  deadline_ms=60_000) for i in range(40)]
            results = [t.result(timeout=120.0) for t in tickets]
        assert len(results) == 40
        assert fleet.compile_count == compiles_after_warmup
        st = svc.stats()
        assert st["completed"] == 40 and st["rejected"] == 0
        per_replica = {k: v["batches"] for k, v in st["replicas"].items()}
        assert set(per_replica) == {"0", "1"}
        assert all(b > 0 for b in per_replica.values()), per_replica
        assert st["live_replicas"] == 2 and st["generation"] == 0

    def test_replica_death_redispatches_once_and_quarantines(self, params):
        """An induced predict failure mid-traffic: the in-flight batch is
        re-dispatched (exactly once — the saboteur is called exactly
        once), every admitted request still resolves, and the quarantine
        is visible in healthz, per-replica stats, and fleet.replica
        telemetry."""
        events = []
        sink = type("S", (), {"emit": lambda self, e: events.append(e),
                              "close": lambda self: None})()
        tel = obs.Telemetry(sinks=[sink])
        fleet, svc = make_fleet_service(params, telemetry=tel)
        calls = [0]

        def boom(batch, want_density=False):
            calls[0] += 1
            raise RuntimeError("induced replica death")

        fleet.replicas[0].engine.predict_batch = boom
        img = make_image()
        with svc:
            tickets = [svc.submit(img, deadline_ms=60_000)
                       for _ in range(12)]
            results = [t.result(timeout=60.0) for t in tickets]
        assert len(results) == 12  # zero lost admitted requests
        assert calls[0] == 1      # the batch was NOT retried on the corpse
        assert svc.stats()["rejected"] == 0
        h = fleet.healthz()
        assert h["ok"] and h["live"] == 1
        states = {r["replica"]: r for r in h["replicas"]}
        assert states[0]["state"] == "quarantined"
        assert "induced replica death" in states[0]["error"]
        assert states[1]["state"] == "active"
        st = svc.stats()
        assert st["replicas"]["0"]["quarantined"] == 1
        assert st["replicas"]["0"]["failures"] == 1
        kinds = [e["kind"] for e in events]
        assert "fleet.replica" in kinds
        fr = [e for e in events if e["kind"] == "fleet.replica"][0]
        assert fr["payload"]["state"] == "quarantined"

    def test_batch_failing_on_two_replicas_is_rejected_error(self, params):
        """Both replicas raise: the batch is the poison, not the fleet —
        its requests reject with ``error`` after exactly one re-dispatch,
        nothing hangs, and the SECOND replica it failed on stays in
        service (one bad input must not take the whole fleet down)."""
        fleet, svc = make_fleet_service(params)

        def boom(batch, want_density=False):
            raise RuntimeError("poison batch")

        for r in fleet.replicas:
            r.engine.predict_batch = boom
        img = make_image()
        with svc:
            t = svc.submit(img, deadline_ms=60_000)
            with pytest.raises(RejectedError) as ei:
                t.result(timeout=60.0)
        assert ei.value.reason == REJECT_ERROR
        # poison containment: only the FIRST replica (failure attributed
        # to the replica) is quarantined; the second failure is
        # attributed to the batch, so that replica keeps serving
        assert fleet.live_replicas() == 1
        assert fleet.healthz()["ok"]
        states = sorted(r["state"] for r in fleet.healthz()["replicas"])
        assert states == ["active", "quarantined"]
        assert sum(r.failures for r in fleet.replicas) == 2

    def test_last_replica_death_fails_queued_work(self, params):
        """When the LAST live replica quarantines, batches still queued
        behind its in-flight one are failed too — no worker remains to
        drain them, and a deadline-less request must reject, not hang."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.fleet import _WorkItem
        from can_tpu.serve.queue import ServeRequest

        fleet = FleetEngine(params, replicas=2, telemetry=obs.Telemetry())
        img = make_image()
        dm = np.zeros((8, 8, 1), np.float32)

        def mk():
            r = ServeRequest(img, deadline_s=None)
            return r, pad_batch([(img, dm)], (64, 64), 1, [True], 8)

        queued = []
        for _ in range(3):  # workers never started: items stay queued
            r, b = mk()
            fleet.submit_work((64, 64), b, [r])
            queued.append(r)
        fleet.replicas[1].state = "quarantined"
        inflight, b = mk()
        fleet._quarantine(fleet.replicas[0], _WorkItem((64, 64), b,
                                                       [inflight]),
                          RuntimeError("last replica down"))
        assert fleet.live_replicas() == 0
        for r in [inflight] + queued:
            with pytest.raises(RejectedError):
                r.wait(timeout=5.0)

    def test_first_failure_during_close_still_redispatches(self, params):
        """A transient replica failure while close() drains must still
        re-dispatch the batch — the remaining live workers are draining,
        and close()'s leftover sweep (not _quarantine) decides what gets
        failed.  After the sweep, a straggler requeue would strand, so
        it fails instead."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.fleet import _WorkItem
        from can_tpu.serve.queue import ServeRequest

        fleet = FleetEngine(params, replicas=2, telemetry=obs.Telemetry())
        fleet._closed = True  # mid-close: live workers still draining

        def mk():
            r = ServeRequest(img, deadline_s=None)
            return r, pad_batch([(img, dm)], (64, 64), 1, [True], 8)

        img = make_image()
        dm = np.zeros((8, 8, 1), np.float32)
        r, b = mk()
        fleet._quarantine(fleet.replicas[0], _WorkItem((64, 64), b, [r]),
                          RuntimeError("transient"))
        assert not r.done and len(fleet._queue) == 1  # re-dispatched
        assert fleet.live_replicas() == 1
        # post-sweep (timed-out drain straggler): fail, never strand
        fleet._swept = True
        r2, b2 = mk()
        fleet.replicas[0].state = "active"  # fresh first failure
        fleet._quarantine(fleet.replicas[0], _WorkItem((64, 64), b2,
                                                       [r2]),
                          RuntimeError("transient"))
        with pytest.raises(RejectedError):
            r2.wait(timeout=5.0)

    def test_rollout_loader_imported_source_not_poisoned_by_base_dir(
            self, tmp_path):
        """POST /rollout {"torch_pth": ...} must not inherit the serving
        --checkpoint-dir (validate_params_source rejects the combination,
        which used to 409 EVERY imported-checkpoint rollout)."""
        from can_tpu.cli.serve import make_rollout_loader, parse_args

        loader = make_rollout_loader(
            parse_args(["--checkpoint-dir", str(tmp_path)]))
        with pytest.raises((ValueError, FileNotFoundError)) as ei:
            loader({"torch_pth": str(tmp_path / "nope.pth")})
        # the failure is the missing FILE, not the dir/source conflict
        assert "ignored" not in str(ei.value)

    def test_zombie_batch_shed_behind_work_queue(self, params):
        """A batch whose EVERY request expired while queued behind the
        fleet is rejected with ``deadline`` — no device launch — and the
        rejects land in the service's /stats counter; one still-live
        request keeps the whole batch running (padded slots are cheap,
        the live result is the point)."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.queue import REJECT_DEADLINE, ServeRequest

        fleet, svc = make_fleet_service(params)
        img = make_image()
        dm = np.zeros((8, 8, 1), np.float32)

        def batch_for(reqs):
            return pad_batch([(r.image, dm) for r in reqs], (64, 64),
                             len(reqs), [True] * len(reqs), 8)

        with svc:
            # all slots expired: shed without executing
            dead = [ServeRequest(img, deadline_s=-1.0) for _ in range(2)]
            fleet.submit_work((64, 64), batch_for(dead), dead)
            for r in dead:
                with pytest.raises(RejectedError) as ei:
                    r.wait(timeout=30.0)
                assert ei.value.reason == REJECT_DEADLINE
            assert sum(r.batches for r in fleet.replicas) == 0
            assert svc.stats()["rejected"] == 2
            # one live request: the batch runs whole
            live = ServeRequest(img, deadline_s=None)
            mixed = [ServeRequest(img, deadline_s=-1.0), live]
            fleet.submit_work((64, 64), batch_for(mixed), mixed)
            assert live.wait(timeout=60.0).count is not None
            assert sum(r.batches for r in fleet.replicas) == 1

    def test_submit_with_no_live_replicas_rejects_not_hangs(self, params):
        fleet, svc = make_fleet_service(params)
        for r in fleet.replicas:
            r.state = "quarantined"
        img = make_image()
        with svc:
            t = svc.submit(img, deadline_ms=5_000)
            with pytest.raises(RejectedError):
                t.result(timeout=30.0)


class TestRollout:
    def test_rollout_under_load_zero_rejects(self, params, params2):
        """The blue/green pin: a rollout completing under live traffic
        rejects NOTHING, flips every replica, serves the new weights
        after (counts equal a fresh engine on the new params), and pays
        its compiles on the staging engine only."""
        fleet, svc = make_fleet_service(
            params, run_config={"syncBN": False, "bf16": False})
        img = make_image()
        with svc:
            before = svc.predict(img, timeout=60.0).count
            stop = threading.Event()
            rejected = []

            def load():
                while not stop.is_set():
                    try:
                        svc.predict(img, timeout=60.0)
                    except RejectedError as e:  # pragma: no cover
                        rejected.append(e)

            threads = [threading.Thread(target=load) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            traffic_compiles = fleet.compile_count
            report = fleet.rollout(
                params2, run_config={"syncBN": False, "bf16": False})
            time.sleep(0.2)
            stop.set()
            for t in threads:
                t.join()
            after = svc.predict(img, timeout=60.0).count
        assert rejected == [] and svc.stats()["rejected"] == 0
        assert report["generation"] == 1
        assert report["flipped"] == [0, 1] and report["skipped"] == []
        assert report["staging_compiles"] >= 1
        # live replicas compiled NOTHING for the flip (same signature)
        assert fleet.compile_count == traffic_compiles
        assert all(r.generation == 1 for r in fleet.replicas)
        # the fleet now serves the NEW checkpoint bit-for-bit
        tel = obs.Telemetry()
        oracle = ServeEngine(params2, telemetry=tel, name="oracle2")
        from can_tpu.data.batching import pad_batch

        dm = np.zeros((8, 8, 1), np.float32)
        # a lone request launches the 1-slot MENU program (r14): the
        # bit-for-bit oracle must run the same program shape
        want, _ = oracle.predict_batch(
            pad_batch([(img, dm)], (64, 64), 1, [True], 8))
        assert after == float(want[0])
        assert after != before  # it actually changed weights

    def test_rollout_drift_guard_and_structure_guard(self, params,
                                                     params2):
        from can_tpu.utils import ConfigDriftError

        fleet, svc = make_fleet_service(
            params, run_config={"syncBN": False, "bf16": False})
        # serve-relevant drift (model variant) refused...
        with pytest.raises(ConfigDriftError, match="syncBN"):
            fleet.rollout(params2, run_config={"syncBN": True,
                                               "bf16": False})
        # ...but training-schedule drift is NOT serve-relevant
        rep = fleet.rollout(params2, run_config={"syncBN": False,
                                                 "bf16": False,
                                                 "lr": 123.0})
        assert rep["generation"] == 1
        # allow= overrides, recording the drifted keys
        rep2 = fleet.rollout(params, run_config={"syncBN": False,
                                                 "bf16": True},
                             allow_config_change=True)
        assert rep2["config_drift"] == ["bf16"]
        # structural mismatch (BN variant tree) is refused outright
        bn_params = cannet_init(jax.random.key(2), batch_norm=True)
        with pytest.raises(ValueError, match="structure"):
            fleet.rollout(bn_params)

    def test_rollout_before_warmup_raises(self, params, params2):
        fleet = FleetEngine(params, replicas=2, telemetry=obs.Telemetry())
        with pytest.raises(RuntimeError, match="warmup"):
            fleet.rollout(params2)

    def test_rollout_skips_quarantined_replica(self, params, params2):
        fleet, svc = make_fleet_service(params)
        fleet.replicas[0].state = "quarantined"
        rep = fleet.rollout(params2)
        assert rep["flipped"] == [1] and rep["skipped"] == [0]
        assert fleet.replicas[0].generation == 0
        assert fleet.replicas[1].generation == 1


# --- observability ------------------------------------------------------
class TestFleetObservability:
    def test_per_replica_prometheus_labels(self, params):
        from can_tpu.obs.exporter import render_stats

        fleet, svc = make_fleet_service(params)
        img = make_image()
        with svc:
            for _ in range(6):
                svc.predict(img, timeout=60.0)
        text = render_stats(svc.stats())
        assert 'can_tpu_serve_batches_total{replica="0"}' in text
        assert 'can_tpu_serve_batches_total{replica="1"}' in text
        assert 'can_tpu_serve_quarantined{replica="0"}' in text
        assert 'can_tpu_serve_generation{replica="1"}' in text
        # unlabelled service-wide counters still present
        assert "can_tpu_serve_completed_total 6" in text
        # valid exposition: a name that appears both plain (fleet-wide
        # generation) and labelled (per-replica) must render as ONE group
        # under ONE TYPE line — a second TYPE line for the same metric
        # voids the whole Prometheus scrape
        type_lines = [ln for ln in text.splitlines()
                      if ln.startswith("# TYPE ")]
        assert len(type_lines) == len(set(type_lines)), type_lines
        assert text.count("# TYPE can_tpu_serve_generation gauge") == 1

    def test_gauge_sink_fleet_kinds(self):
        from can_tpu.obs.exporter import GaugeSink

        sink = GaugeSink()
        sink.emit({"kind": "fleet.rollout", "payload": {"generation": 3}})
        sink.emit({"kind": "fleet.replica",
                   "payload": {"replica": 1, "state": "quarantined"}})
        sink.emit({"kind": "fleet.replica",
                   "payload": {"replica": 0, "state": "active"}})
        text = sink.render()
        assert "can_tpu_fleet_generation 3" in text
        assert 'can_tpu_fleet_quarantines_total{replica="1"} 1' in text
        assert 'replica="0"' not in text  # active transition != failure

    def test_report_summarizes_fleet_events(self, tmp_path):
        path = tmp_path / "telemetry.jsonl"
        events = [
            {"ts": 1.0, "kind": "fleet.replica", "step": None, "host_id": 0,
             "payload": {"replica": 0, "state": "quarantined"}},
            {"ts": 2.0, "kind": "fleet.rollout", "step": None, "host_id": 0,
             "payload": {"generation": 2, "flipped": [1]}},
            {"ts": 3.0, "kind": "fleet.replica", "step": None, "host_id": 0,
             "payload": {"replica": 1, "state": "active"}},
        ]
        path.write_text("".join(json.dumps(e) + "\n" for e in events))
        from can_tpu.obs.report import format_report, read_events, summarize

        s = summarize(read_events(str(path)))
        assert s["fleet_rollouts"] == 1
        assert s["fleet_generation"] == 2
        assert s["fleet_quarantines"] == 1
        assert s["fleet_replica_states"] == {"0": "quarantined",
                                             "1": "active"}
        text = format_report(s)
        assert "serving fleet" in text and "rollouts=1" in text

    def test_offline_summary_has_no_fleet_row(self):
        from can_tpu.obs.report import format_report, summarize

        text = format_report(summarize([]))
        assert "serving fleet" not in text


# --- HTTP front end -----------------------------------------------------
class TestFleetHTTP:
    def test_healthz_and_rollout_endpoint(self, params, params2):
        fleet, svc = make_fleet_service(
            params, run_config={"syncBN": False, "bf16": False})
        calls = []

        def loader(spec):
            calls.append(spec)
            return params2, None, {"syncBN": False, "bf16": False}

        svc.rollout_loader = loader
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            try:
                base = f"http://127.0.0.1:{port}"
                with urllib.request.urlopen(f"{base}/healthz") as r:
                    health = json.loads(r.read())
                assert health["ok"] and health["live"] == 2
                assert [x["state"] for x in health["replicas"]] == [
                    "active", "active"]
                req = urllib.request.Request(
                    f"{base}/rollout", method="POST",
                    data=json.dumps({"checkpoint_dir": "ignored"}).encode())
                with urllib.request.urlopen(req) as r:
                    report = json.loads(r.read())
                assert report["generation"] == 1
                assert calls == [{"checkpoint_dir": "ignored"}]
                # quarantined state surfaces on /healthz with ok still true
                fleet.replicas[0].state = "quarantined"
                with urllib.request.urlopen(f"{base}/healthz") as r:
                    health = json.loads(r.read())
                assert health["ok"] and health["live"] == 1
                assert health["replicas"][0]["state"] == "quarantined"
            finally:
                httpd.shutdown()
                httpd.server_close()

    def test_rollout_bad_spec_is_409_not_dead_socket(self, params,
                                                     tmp_path):
        """The real loader path speaks CLI (SystemExit from
        validate_params_source); over HTTP a bad checkpoint spec must
        come back as a 409 — and an unexpected loader crash (corrupt
        .npz) as a 500 — never a reset connection."""
        from can_tpu.cli.serve import make_rollout_loader, parse_args

        fleet, svc = make_fleet_service(params)
        svc.rollout_loader = make_rollout_loader(parse_args([]))
        corrupt = tmp_path / "corrupt.npz"
        corrupt.write_bytes(b"not an npz archive")
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            try:
                base = f"http://127.0.0.1:{port}/rollout"
                for body, code in (
                        ({"torch_pth": "a.pth", "params_npz": "b.npz"},
                         409),
                        # corrupt archive: np.load raises ValueError ->
                        # still the client's fault, still a 409
                        ({"params_npz": str(corrupt)}, 409)):
                    req = urllib.request.Request(
                        base, method="POST",
                        data=json.dumps(body).encode())
                    try:
                        urllib.request.urlopen(req)
                        assert False, f"expected {code}"
                    except urllib.error.HTTPError as e:
                        assert e.code == code, (body, e.code)
                        assert "error" in json.loads(e.read())
                # an UNEXPECTED loader crash answers 500, never a
                # dropped socket with a handler-thread traceback
                def crash(spec):
                    raise KeyError("unexpected loader bug")

                svc.rollout_loader = crash
                req = urllib.request.Request(base, method="POST",
                                             data=b"{}")
                try:
                    urllib.request.urlopen(req)
                    assert False, "expected 500"
                except urllib.error.HTTPError as e:
                    assert e.code == 500
                    assert "KeyError" in json.loads(e.read())["error"]
            finally:
                httpd.shutdown()
                httpd.server_close()

    def test_rollout_without_loader_is_501(self, params):
        fleet, svc = make_fleet_service(params)
        with svc:
            httpd = serve_http(svc, port=0)
            port = httpd.server_address[1]
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/rollout", method="POST",
                    data=b"{}")
                try:
                    urllib.request.urlopen(req)
                    assert False, "expected 501"
                except urllib.error.HTTPError as e:
                    assert e.code == 501
            finally:
                httpd.shutdown()
                httpd.server_close()


import urllib.error  # noqa: E402  (used in the 501 test)


# --- CLI flags ----------------------------------------------------------
class TestFleetCLI:
    def test_parse_fleet_flags(self):
        from can_tpu.cli.serve import parse_args

        args = parse_args(["--replicas", "4", "--serve-dtype", "int8"])
        assert args.replicas == 4 and args.serve_dtype == "int8"
        assert parse_args([]).replicas == 1
        assert parse_args([]).serve_dtype == "f32"

    def test_legacy_bf16_conflicts_with_serve_dtype(self):
        from can_tpu.cli.serve import build_service, parse_args

        args = parse_args(["--bf16", "--serve-dtype", "bf16"])
        with pytest.raises(SystemExit, match="legacy"):
            build_service(args)

    def test_replicas_validated(self):
        from can_tpu.cli.serve import build_service, parse_args

        args = parse_args(["--replicas", "0"])
        with pytest.raises(SystemExit, match="replicas"):
            build_service(args)

