"""Self-healing autoscaling fleet (ISSUE 13): replica resurrection, hang
watchdog, SLO-driven scale-up/down, AOT warm starts.

The contract under test (acceptance criteria):

* a seeded ``replica_crash`` on 1 of 2 replicas under sustained load
  yields quarantine -> probe -> resurrection at the CURRENT generation
  with zero lost admitted requests and live_replicas back to 2;
* a seeded ``replica_hang`` is detected by the watchdog within the
  priced deadline and its batch completes on the surviving replica;
* a quarantined replica's device-resident buffers are released
  immediately (zero HBM for a dead replica), verified by live-array
  accounting on its device;
* an AOT-warm-started replica (resurrected, scaled-up, or a whole fresh
  fleet) reaches ready with ZERO new compiles — loaded executables,
  pinned via ``compile_count`` — and bit-parity counts;
* autoscaler transitions drop zero requests and respect hysteresis (one
  transition per step load change, never a limit cycle);
* generation skew is visible on /healthz and per-replica /stats rows.
"""

import gc
import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from can_tpu import obs
from can_tpu.models import cannet_init
from can_tpu.obs.report import format_report, summarize
from can_tpu.serve import (
    AotStaleError,
    Autoscaler,
    AutoscalePolicy,
    CountService,
    FleetEngine,
    ServeEngine,
    load_aot_bundle,
    prepare_image,
    priced_deadline_s,
)
from can_tpu.serve.autoscale import decide
from can_tpu.testing import faults


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


def collecting_telemetry():
    events = []
    sink = type("S", (), {"emit": lambda self, e: events.append(e),
                          "close": lambda self: None})()
    return obs.Telemetry(sinks=[sink]), events


def make_fleet_service(params, *, replicas=2, ladder=((64,), (64,)),
                       max_batch=2, telemetry=None, warm=True, **kw):
    tel = telemetry if telemetry is not None else obs.Telemetry()
    kw.setdefault("self_heal", False)  # tests drive maintenance_tick
    fleet = FleetEngine(params, replicas=replicas, telemetry=tel, **kw)
    svc = CountService(fleet, max_batch=max_batch, max_wait_ms=1.0,
                       queue_capacity=256, bucket_ladder=ladder,
                       telemetry=tel)
    if warm:
        svc.warmup([(h, w) for h in ladder[0] for w in ladder[1]])
    return fleet, svc


def dev_live_bytes(dev) -> int:
    gc.collect()
    return sum(x.nbytes for x in jax.live_arrays() if dev in x.devices())


# --- AOT bundles ---------------------------------------------------------
class TestAotBundle:
    def test_bake_load_zero_compiles_bit_parity(self, params, tmp_path):
        """A fleet warm-started from a bundle compiles NOTHING (pinned
        via compile_count, the acceptance receipt) and serves counts
        bit-identical to the compiled fleet's."""
        tel = obs.Telemetry()
        fleet, svc = make_fleet_service(params, telemetry=tel)
        d = str(tmp_path / "aot")
        manifest = fleet.bake_aot(d, devices=jax.devices()[:3])
        # 1 bucket x menu sizes x 3 devices (the r14 sub-batch menu is
        # a bake axis: every size the batcher may dispatch is baked)
        assert len(manifest["programs"]) == 3 * len(svc.sched.menu)
        assert manifest["signature_sha"] == fleet._sig_sha

        tel2 = obs.Telemetry()
        fleet2 = FleetEngine(params, replicas=2, telemetry=tel2,
                             aot_bundle=d, self_heal=False)
        svc2 = CountService(fleet2, max_batch=2, max_wait_ms=1.0,
                            bucket_ladder=((64,), (64,)), telemetry=tel2)
        rep = svc2.warmup([(64, 64)])
        assert rep["compiles"] == 0
        assert fleet2.compile_count == 0
        img = make_image()
        with svc2:
            r_aot = svc2.predict(img, deadline_ms=60_000)
        assert fleet2.compile_count == 0  # traffic stayed compile-free
        assert sum(r.engine.aot_hits for r in fleet2.replicas) > 0
        with svc:
            r_jit = svc.predict(img, deadline_ms=60_000)
        assert r_aot.count == r_jit.count  # loaded binary == compiled

    def test_manifest_last_torn_bake_reads_absent(self, params, tmp_path):
        fleet, _ = make_fleet_service(params)
        d = str(tmp_path / "aot")
        fleet.bake_aot(d, devices=jax.devices()[:2])
        os.remove(os.path.join(d, "aot_manifest.json"))  # torn bake
        with pytest.raises(AotStaleError) as ei:
            load_aot_bundle(d)
        assert ei.value.axis == "manifest"

    def test_staleness_axes_refused(self, params, params2, tmp_path):
        fleet, _ = make_fleet_service(params)
        d = str(tmp_path / "aot")
        fleet.bake_aot(d, devices=jax.devices()[:2])
        # different checkpoint variant: signature mismatch... params2 is
        # the SAME architecture, so reuse IS valid; fake a different sig
        with pytest.raises(AotStaleError) as ei:
            b = load_aot_bundle(d)
            b.check(sig_sha="deadbeef", serve_dtype="f32", ds=8)
        assert ei.value.axis == "signature"
        # wrong serve mode bakes a different program family
        with pytest.raises(AotStaleError) as ei:
            FleetEngine(params, replicas=2, serve_dtype="bf16",
                        telemetry=obs.Telemetry(), aot_bundle=d,
                        self_heal=False)
        assert ei.value.axis == "serve_dtype"
        # batch geometry is part of the executable's signature
        fleet3 = FleetEngine(params, replicas=2,
                             telemetry=obs.Telemetry(), aot_bundle=d,
                             self_heal=False)
        with pytest.raises(AotStaleError) as ei:
            fleet3.warmup([(64, 64)], max_batch=4)  # baked at 2
        assert ei.value.axis == "max_batch"
        # a bucket the bake never saw
        with pytest.raises(AotStaleError) as ei:
            fleet3.warmup([(64, 64), (96, 64)], max_batch=2)
        assert ei.value.axis == "bucket_shapes"

    def test_same_signature_rollout_keeps_bundle_valid(self, params,
                                                       params2, tmp_path):
        """Params are jit ARGUMENTS: a same-architecture checkpoint (the
        rollout case) hashes to the same signature, so the bundle
        survives rollouts without a re-bake."""
        from can_tpu.serve.aot import signature_sha

        assert signature_sha(params) == signature_sha(params2)

    def test_programs_for_uncovered_device_is_empty(self, params,
                                                    tmp_path):
        fleet, _ = make_fleet_service(params)
        d = str(tmp_path / "aot")
        fleet.bake_aot(d, devices=jax.devices()[:2])
        bundle = load_aot_bundle(d)
        assert bundle.programs_for(jax.devices()[7]) == {}
        assert bundle.device_ids() == {0, 1}


# --- the HBM leak fix ----------------------------------------------------
class TestBufferRelease:
    def test_quarantine_releases_device_bytes(self, params):
        """Satellite: a quarantined replica costs ZERO HBM.  Replica 1's
        device holds exactly its tree (the test process's own params
        live on device 0), so the release must take it to zero live
        bytes — and the survivor keeps serving."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.fleet import _WorkItem
        from can_tpu.serve.queue import ServeRequest

        fleet, _ = make_fleet_service(params, warm=False)
        fleet.warmup([(64, 64)], 2)
        d1 = fleet.replicas[1].device
        before = dev_live_bytes(d1)
        assert before > 50 * 1024 * 1024  # the ~79 MB f32 tree
        img = np.zeros((64, 64, 3), np.float32)
        dm = np.zeros((8, 8, 1), np.float32)
        r = ServeRequest(img, deadline_s=None)
        batch = pad_batch([(img, dm)], (64, 64), 1, [True], 8)
        fleet._quarantine(fleet.replicas[1],
                          _WorkItem((64, 64), batch, [r]),
                          RuntimeError("induced"))
        assert fleet.replicas[1].state == "quarantined"
        assert fleet.replicas[1].engine.released
        after = dev_live_bytes(d1)
        assert after < before / 50, (before, after)
        # probation is scheduled, the survivor is intact
        assert fleet.replicas[1].probe_at is not None
        c, _ = fleet.replicas[0].engine.predict_batch(
            pad_batch([(img, dm)], (64, 64), 2, [True], 8))
        assert c.shape == (2,)

    def test_released_engine_refuses_predict(self, params):
        from can_tpu.data.batching import pad_batch

        eng = ServeEngine(params, telemetry=obs.Telemetry(),
                          name="release_refuse")
        eng.release_buffers()
        eng.release_buffers()  # idempotent
        img = np.zeros((64, 64, 3), np.float32)
        dm = np.zeros((8, 8, 1), np.float32)
        with pytest.raises(RuntimeError, match="released"):
            eng.predict_batch(pad_batch([(img, dm)], (64, 64), 1,
                                        [True], 8))


# --- watchdog deadline math ---------------------------------------------
class FakeLedger:
    def __init__(self, rows):
        self._rows = rows

    def rows(self):
        return self._rows


def row(name, shape, mean_s, reliable=True):
    return {"name": name, "shape": list(shape), "mean_s": mean_s,
            "timing_reliable": reliable}


class TestWatchdogMath:
    SHAPE = (2, 64, 64, 3)

    def test_no_ledger_falls_back_to_default(self):
        assert priced_deadline_s(None, "f", self.SHAPE, slack=10,
                                 floor_s=1, default_s=30) == 30

    def test_priced_from_reliable_mean_times_slack(self):
        led = FakeLedger([row("f_r0", self.SHAPE, 0.5)])
        assert priced_deadline_s(led, "f", self.SHAPE, slack=10,
                                 floor_s=1, default_s=30) == 5.0

    def test_max_over_replica_programs(self):
        led = FakeLedger([row("f_r0", self.SHAPE, 0.5),
                          row("f_r1", self.SHAPE, 0.9),
                          row("other", self.SHAPE, 99.0)])
        assert priced_deadline_s(led, "f", self.SHAPE, slack=10,
                                 floor_s=1, default_s=30) == 9.0

    def test_floor_binds_tiny_programs(self):
        led = FakeLedger([row("f_r0", self.SHAPE, 0.001)])
        assert priced_deadline_s(led, "f", self.SHAPE, slack=10,
                                 floor_s=1, default_s=30) == 1.0

    def test_dtype_mismatch_falls_back(self):
        """A u8 batch is a different program than the same-shape f32
        one: f32 rows must not price its deadline (rows with unknown
        dtype still match)."""
        led = FakeLedger([{**row("f_r0", self.SHAPE, 0.5),
                           "dtype": "float32"}])
        assert priced_deadline_s(led, "f", self.SHAPE, dtype="uint8",
                                 slack=10, floor_s=1, default_s=30) == 30
        assert priced_deadline_s(led, "f", self.SHAPE, dtype="float32",
                                 slack=10, floor_s=1, default_s=30) == 5.0
        led_unknown = FakeLedger([{**row("f_r0", self.SHAPE, 0.5),
                                   "dtype": "?"}])
        assert priced_deadline_s(led_unknown, "f", self.SHAPE,
                                 dtype="uint8", slack=10, floor_s=1,
                                 default_s=30) == 5.0

    def test_unwarmed_batch_gets_compile_allowance(self, params):
        """Review finding: a legitimate first-compile launch (e.g. the
        first unwarmed raw-u8 request) takes minutes, not the steady-
        state deadline — pricing it normally would wedge a healthy
        replica and cascade-quarantine the fleet."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.fleet import _WorkItem

        fleet, _ = make_fleet_service(params)  # warmed f32 64x64
        img_f32 = np.zeros((64, 64, 3), np.float32)
        img_u8 = np.zeros((64, 64, 3), np.uint8)
        dm = np.zeros((8, 8, 1), np.float32)

        def item_for(img):
            return _WorkItem((64, 64),
                             pad_batch([(img, dm)], (64, 64), 2,
                                       [True], 8), [])

        r = fleet.replicas[0]
        warm = fleet._deadline_for(item_for(img_f32), r)
        cold = fleet._deadline_for(item_for(img_u8), r)
        assert warm == fleet.watchdog_default_s  # warmed: normal path
        assert cold == fleet.watchdog_compile_s  # unwarmed: allowance
        assert cold > warm

    def test_unreliable_or_unmatched_rows_fall_back(self):
        """No cost/timing attribution yet (cost_analysis absent, or a
        1-launch unfenced mean): the fixed default bounds the hang."""
        led = FakeLedger([row("f_r0", self.SHAPE, 0.5, reliable=False),
                          row("f_r0", (2, 96, 64, 3), 0.5)])
        assert priced_deadline_s(led, "f", self.SHAPE, slack=10,
                                 floor_s=1, default_s=30) == 30
        assert priced_deadline_s(FakeLedger([]), "f", self.SHAPE,
                                 slack=10, floor_s=1, default_s=30) == 30


# --- watchdog behaviour --------------------------------------------------
class TestWatchdog:
    def test_hung_launch_wedged_and_batch_completes_on_survivor(
            self, params):
        """Acceptance: a hang is detected within the priced deadline,
        the in-flight batch re-dispatches under the redispatch-once rule
        and completes on the surviving replica; the wedged worker's late
        results are discarded."""
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(params, telemetry=tel)
        origs = {r.index: r.engine.predict_batch for r in fleet.replicas}
        hung = []

        def make_hang(idx):
            def predict(batch, want_density=False):
                if not hung:
                    hung.append(idx)
                    time.sleep(1.5)  # "device execute" that wedges
                return origs[idx](batch, want_density=want_density)
            return predict

        for r in fleet.replicas:
            r.engine.predict_batch = make_hang(r.index)
        img = make_image()
        with svc:
            t = svc.submit(img, deadline_ms=60_000)
            # wait for a worker to enter the hung execute
            deadline = time.time() + 10
            while not hung and time.time() < deadline:
                time.sleep(0.01)
            assert hung
            # one far-future tick: deterministic wedge without waiting
            # out the real 30 s default deadline
            fleet.maintenance_tick(now=fleet._clock() + 1000.0)
            res = t.result(timeout=30.0)
        assert res.count is not None  # zero lost admitted requests
        wedged_idx = hung[0]
        states = {r["replica"]: r for r in fleet.healthz()["replicas"]}
        assert states[wedged_idx]["state"] == "wedged"
        assert "watchdog" in states[wedged_idx]["error"]
        assert states[1 - wedged_idx]["state"] == "active"
        # probation scheduled; the survivor executed the batch
        assert svc.stats()["rejected"] == 0
        wedge_events = [e for e in events if e["kind"] == "fleet.replica"
                        and e["payload"]["state"] == "wedged"]
        assert len(wedge_events) == 1

    def test_completed_launch_never_wedges(self, params):
        """A launch that finished before the sweep is invisible to the
        watchdog (inflight cleared first-wins under _cond)."""
        fleet, svc = make_fleet_service(params)
        img = make_image()
        with svc:
            assert svc.predict(img, deadline_ms=60_000).count is not None
            fleet.maintenance_tick(now=fleet._clock() + 1000.0)
        assert all(r.state == "active" for r in fleet.replicas)


# --- resurrection --------------------------------------------------------
class TestResurrection:
    def test_crash_probe_resurrect_zero_lost(self, params):
        """Quarantine -> cooldown -> probe -> back in dispatch, all
        requests resolved throughout, live back to 2, fleet.probe and
        fleet.resurrect on the bus."""
        tel, events = collecting_telemetry()
        # a LONG cooldown: real wall time elapses while the 10 tickets
        # resolve on a loaded box, and the "no probe yet" assert below
        # must not be outrunnable — the ticks use explicit fake nows
        fleet, svc = make_fleet_service(params, telemetry=tel,
                                        probe_cooldown_s=60.0)

        def boom(batch, want_density=False):
            raise RuntimeError("induced death")

        fleet.replicas[0].engine.predict_batch = boom
        img = make_image()
        with svc:
            tickets = [svc.submit(img, deadline_ms=60_000)
                       for _ in range(10)]
            results = [t.result(timeout=60.0) for t in tickets]
            assert len(results) == 10
            assert fleet.live_replicas() == 1
            # before the cooldown: no probe
            fleet.maintenance_tick(now=fleet._clock())
            assert fleet.live_replicas() == 1
            # past the cooldown (+ max jitter): probe + resurrect (the
            # probe runs on its own thread; join makes the test
            # deterministic)
            fleet.maintenance_tick(now=fleet._clock() + 120.0)
            fleet.join_probes(60.0)
            assert fleet.live_replicas() == 2
            # the resurrected replica serves real traffic
            tickets = [svc.submit(img, deadline_ms=60_000)
                       for _ in range(8)]
            for t in tickets:
                t.result(timeout=60.0)
        kinds = [e["kind"] for e in events]
        assert kinds.count("fleet.resurrect") == 1
        probe_ok = [e for e in events if e["kind"] == "fleet.probe"]
        assert len(probe_ok) == 1 and probe_ok[0]["payload"]["ok"]
        st = svc.stats()
        assert st["rejected"] == 0
        assert st["replicas"]["0"]["quarantined"] == 0  # active again

    def test_resurrection_joins_current_generation(self, params,
                                                   params2):
        """THE staleness acceptance: quarantine r0, roll the fleet to a
        new checkpoint (r0 is skipped — fleet.py's documented skew),
        then resurrect — r0 must come back at the NEW generation serving
        the NEW weights, bit-identical to a params2 engine."""
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(params, telemetry=tel,
                                        probe_cooldown_s=0.1)

        def boom(batch, want_density=False):
            raise RuntimeError("induced death")

        fleet.replicas[0].engine.predict_batch = boom
        img = make_image()
        with svc:
            svc.submit(img, deadline_ms=60_000).result(timeout=60.0)
            assert fleet.replicas[0].state == "quarantined"
            fleet.rollout(params2)
            h = fleet.healthz()
            rows = {r["replica"]: r for r in h["replicas"]}
            assert rows[1]["generation"] == 1  # flipped
            assert rows[0]["generation"] == 0  # quarantined: skipped
            assert not h["mixed_generations"]  # r0 isn't SERVING stale
            fleet.maintenance_tick(now=fleet._clock() + 1.0)
            fleet.join_probes(60.0)
            assert fleet.live_replicas() == 2
            rows = {r["replica"]: r
                    for r in fleet.healthz()["replicas"]}
            assert rows[0]["generation"] == 1  # resurrected at CURRENT
            # pin the weights, not just the label: quarantine r1 so r0
            # must serve, and compare against a fresh params2 engine
            fleet.replicas[1].state = "quarantined"
            got = svc.predict(img, deadline_ms=60_000).count
        ref = ServeEngine(params2, telemetry=obs.Telemetry(),
                          name="gen_ref")
        from can_tpu.data.batching import pad_batch

        h_, w_ = img.shape[:2]
        dm = np.zeros((h_ // 8, w_ // 8, 1), np.float32)
        # a lone request launches the 1-slot MENU program (r14): the
        # bit-for-bit oracle must run the same program shape
        want, _ = ref.predict_batch(
            pad_batch([(img, dm)], (64, 64), 1, [True], 8))
        assert got == float(want[0])

    def test_resurrection_with_aot_is_zero_compile(self, params,
                                                   tmp_path):
        """Acceptance: a resurrected replica loads executables — the
        fleet.resurrect event carries warmup_compiles == 0 and aot
        hits, and the fresh incarnation's registry stays empty."""
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(params, telemetry=tel,
                                        probe_cooldown_s=0.1)
        d = str(tmp_path / "aot")
        fleet.bake_aot(d, devices=jax.devices()[:2])
        fleet.load_aot(d)

        def boom(batch, want_density=False):
            raise RuntimeError("induced death")

        fleet.replicas[0].engine.predict_batch = boom
        img = make_image()
        with svc:
            svc.submit(img, deadline_ms=60_000).result(timeout=60.0)
            fleet.maintenance_tick(now=fleet._clock() + 1.0)
            fleet.join_probes(60.0)
            assert fleet.live_replicas() == 2
        res = [e for e in events if e["kind"] == "fleet.resurrect"]
        assert len(res) == 1
        assert res[0]["payload"]["warmup_compiles"] == 0
        assert res[0]["payload"]["aot_hits"] > 0
        # the fresh incarnation billed zero signatures of its own
        assert fleet.replicas[0].engine.compile_count == 0


# --- probe backoff + paging ---------------------------------------------
def quarantine_directly(fleet):
    """Drive the quarantine path without service threads (the probe
    ticks that follow must run against an OPEN fleet — closing the
    service would, correctly, disable probing)."""
    from can_tpu.data.batching import pad_batch
    from can_tpu.serve.fleet import _WorkItem
    from can_tpu.serve.queue import ServeRequest

    img = np.zeros((64, 64, 3), np.float32)
    dm = np.zeros((8, 8, 1), np.float32)
    r = ServeRequest(img, deadline_s=None)
    batch = pad_batch([(img, dm)], (64, 64), 1, [True], 8)
    fleet._quarantine(fleet.replicas[0],
                      _WorkItem((64, 64), batch, [r]),
                      RuntimeError("induced death"))
    assert fleet.replicas[0].state == "quarantined"


class TestProbeBackoff:
    def _quarantined_fleet(self, params, **kw):
        fleet, _ = make_fleet_service(params, probe_cooldown_s=1.0,
                                      probe_jitter=0.0, **kw)
        quarantine_directly(fleet)
        return fleet

    def test_backoff_escalates_and_caps(self, params):
        fleet = self._quarantined_fleet(params,
                                        probe_backoff_max_s=3.0)
        r = fleet.replicas[0]
        assert r.backoff_s == 1.0  # fresh quarantine: the cooldown

        def sick(index, device):
            raise RuntimeError("device still sick")

        fleet._build_replica_engine = sick
        for want in (2.0, 3.0, 3.0):  # x2, then capped
            now = r.probe_at
            fleet.maintenance_tick(now=now)
            fleet.join_probes(30.0)  # probes run on their own threads
            assert r.state == "quarantined"
            assert r.backoff_s == want
            assert r.probe_at == now + want  # jitter=0: exact

    def test_transient_failure_absorbed(self, params):
        """One failed probe, then the device heals: the next probe
        resurrects, nothing pages (below page_after_probes)."""
        tel = obs.Telemetry()
        pages = []
        tel.incidents = type("I", (), {
            "trigger": lambda self, reason, **kw: pages.append(reason)})()
        fleet, _ = make_fleet_service(params, telemetry=tel,
                                      probe_cooldown_s=0.1,
                                      probe_jitter=0.0,
                                      page_after_probes=3)
        quarantine_directly(fleet)
        build = fleet._build_replica_engine
        calls = [0]

        def flaky(index, device):
            calls[0] += 1
            if calls[0] == 1:
                raise RuntimeError("transient")
            return build(index, device)

        fleet._build_replica_engine = flaky
        r = fleet.replicas[0]
        fleet.maintenance_tick(now=r.probe_at)
        fleet.join_probes(30.0)
        assert fleet.live_replicas() == 1  # transient absorbed
        fleet.maintenance_tick(now=r.probe_at)
        fleet.join_probes(60.0)
        assert fleet.live_replicas() == 2  # healed
        assert pages == []  # transient never paged

    def test_persistent_failure_pages_once_per_cooldown(self, params,
                                                        tmp_path):
        """Past page_after_probes the fleet triggers the incident layer
        every failed probe — and the manager's per-reason cooldown turns
        that into exactly ONE bundle per cooldown window."""
        from can_tpu.obs import FlightRecorder, IncidentManager

        fake_now = [1000.0]
        tel = obs.Telemetry()
        rec = FlightRecorder()
        mgr = IncidentManager(tel, rec,
                              incident_dir=str(tmp_path / "inc"),
                              rate_limit_s=3600.0,
                              clock=lambda: fake_now[0])
        tel.incidents = mgr
        fleet, _ = make_fleet_service(params, telemetry=tel,
                                      probe_cooldown_s=0.1,
                                      probe_jitter=0.0,
                                      page_after_probes=2)
        quarantine_directly(fleet)
        fleet._build_replica_engine = \
            lambda i, d: (_ for _ in ()).throw(RuntimeError("sick"))
        r = fleet.replicas[0]
        for _ in range(4):  # 4 failed probes, threshold at 2
            fleet.maintenance_tick(now=r.probe_at)
            fleet.join_probes(30.0)
        assert r.probe_failures == 4
        bundles = [p for p in os.listdir(str(tmp_path / "inc"))
                   if p.startswith("incident-")]
        assert len(bundles) == 1  # exactly once per cooldown
        manifest = json.load(open(os.path.join(
            str(tmp_path / "inc"), bundles[0], "incident.json")))
        assert manifest["reason"] == "fleet_probe_failed"
        # a second cooldown window pages again
        fake_now[0] += 7200.0
        fleet.maintenance_tick(now=r.probe_at)
        fleet.join_probes(30.0)
        bundles = [p for p in os.listdir(str(tmp_path / "inc"))
                   if p.startswith("incident-")]
        assert len(bundles) == 2


class TestProbeIsolation:
    def test_hung_probe_never_blocks_maintenance(self, params):
        """Review finding: a probe predict on a still-sick device can
        hang exactly like the launch that wedged it — it must cost one
        abandoned thread, not the watchdog/rollout/autoscaler.  The
        maintenance tick returns immediately, the timed-out probe is
        declared failed with escalated backoff, and the late thread's
        result can never swap in (token invalidation)."""
        fleet, _ = make_fleet_service(params, probe_cooldown_s=1.0,
                                      probe_jitter=0.0)
        fleet.probe_timeout_s = 5.0
        quarantine_directly(fleet)
        r = fleet.replicas[0]
        release = threading.Event()
        build = fleet._build_replica_engine

        def hung_build(index, device):
            release.wait(30.0)  # "device execute that never returns"
            return build(index, device)

        fleet._build_replica_engine = hung_build
        t0 = time.perf_counter()
        fleet.maintenance_tick(now=r.probe_at)  # spawns the probe
        assert time.perf_counter() - t0 < 1.0  # tick did NOT block
        assert r.probing is not None
        token_before = r.probe_token
        # rollout/scale surface stays usable while the probe hangs
        assert fleet.healthz()["live"] == 1
        # past the probe timeout: declared failed, backoff escalated
        fleet.maintenance_tick(now=r.probe_at + 10.0)
        assert r.probing is None
        assert r.probe_failures == 1
        assert r.backoff_s == 2.0
        assert r.probe_token == token_before + 1
        # the abandoned thread finishing late must NOT swap in
        release.set()
        fleet.join_probes(30.0)
        assert fleet.live_replicas() == 1
        assert fleet.replicas[0] is r  # never replaced by a stale probe

    def test_mid_probe_rollout_discards_stale_staging(self, params,
                                                      params2):
        """A rollout landing between a probe's staging and its swap-in
        must not let generation-N-1 weights rejoin dispatch: the probe
        discards and reschedules promptly."""
        fleet, _ = make_fleet_service(params, probe_cooldown_s=0.1,
                                      probe_jitter=0.0)
        quarantine_directly(fleet)
        r = fleet.replicas[0]
        build = fleet._build_replica_engine
        gate = threading.Event()

        def slow_build(index, device):
            eng = build(index, device)
            gate.wait(30.0)  # hold the probe while the rollout lands
            return eng

        fleet._build_replica_engine = slow_build
        fleet.maintenance_tick(now=r.probe_at)  # probe staging begins
        fleet.rollout(params2)                  # generation 0 -> 1
        gate.set()
        fleet.join_probes(60.0)
        assert fleet.live_replicas() == 1  # stale staging discarded
        assert r.probe_at is not None      # rescheduled promptly
        # the retry (new generation) succeeds
        fleet._build_replica_engine = build
        fleet.maintenance_tick(now=fleet._clock() + 1.0)
        fleet.join_probes(60.0)
        assert fleet.live_replicas() == 2
        assert fleet.replicas[0].generation == 1


class TestDrainingWatchdog:
    def test_hang_during_scale_down_is_wedged_not_stranded(self, params):
        """Review finding: a launch that hangs while its replica drains
        for scale-down must still be wedged and re-dispatched — the
        batch completes on a survivor instead of stranding behind
        remove_replica's bounded join.  No probe is scheduled: the
        victim was leaving anyway and remove_replica owns its
        teardown."""
        from can_tpu.data.batching import pad_batch
        from can_tpu.serve.fleet import REPLICA_DRAINING, _WorkItem
        from can_tpu.serve.queue import ServeRequest

        fleet, svc = make_fleet_service(params)
        img = np.zeros((64, 64, 3), np.float32)
        dm = np.zeros((8, 8, 1), np.float32)
        req = ServeRequest(img, deadline_s=None)
        item = _WorkItem((64, 64),
                         pad_batch([(img, dm)], (64, 64), 2, [True], 8),
                         [req])
        r = fleet.replicas[0]
        r.state = REPLICA_DRAINING
        with fleet._cond:
            r.inflight = (item, fleet._clock(), 0.5)
        fleet.maintenance_tick(now=fleet._clock() + 100.0)
        assert r.state == "wedged"
        assert r.probe_at is None  # remove_replica owns the teardown
        with fleet._cond:
            assert len(fleet._queue) == 1  # batch re-dispatched
            assert item.redispatches == 1


# --- autoscaler ----------------------------------------------------------
class FakeFleet:
    def __init__(self, live=2):
        self.live = live
        self._queue = []
        self.actions = []

    def live_replicas(self):
        return self.live

    def add_replica(self, *, reason):
        self.live += 1
        self.actions.append(("up", reason))
        return {"direction": "up"}

    def remove_replica(self, *, reason):
        self.live -= 1
        self.actions.append(("down", reason))
        return {"direction": "down"}


class FakeScaleService:
    def __init__(self, fleet):
        self._fleet = fleet
        self.signals = {"outstanding": 0, "p99_s": None}

    @property
    def queue(self):
        svc = self

        class Q:
            def outstanding(self_q):
                return svc.signals["outstanding"]
        return Q()

    def latency_percentile(self, q):
        return self.signals["p99_s"]


def make_autoscaler(live=2, **policy_kw):
    policy_kw.setdefault("min_replicas", 1)
    policy_kw.setdefault("max_replicas", 4)
    policy_kw.setdefault("up_consecutive", 2)
    policy_kw.setdefault("down_consecutive", 3)
    policy_kw.setdefault("cooldown_s", 10.0)
    fleet = FakeFleet(live)
    svc = FakeScaleService(fleet)
    clock = [0.0]
    auto = Autoscaler(svc, AutoscalePolicy(**policy_kw),
                      clock=lambda: clock[0])
    return auto, fleet, svc, clock


class TestAutoscalerUnit:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="hysteresis"):
            AutoscalePolicy(queue_high=2.0, queue_low=2.0)
        with pytest.raises(ValueError, match="max_replicas"):
            AutoscalePolicy(min_replicas=3, max_replicas=2)

    def test_decide_thresholds(self):
        pol = AutoscalePolicy(queue_high=4.0, queue_low=1.0,
                              p99_high_s=2.0)
        up = {"live": 2, "outstanding": 10, "queue_depth": 3,
              "p99_s": 0.1, "slo_alerting": False}
        assert decide(up, pol) == "up"
        # latency-up needs actual load: with zero outstanding the p99
        # is history (see test_idle_overrides_stale_p99)
        lat = {"live": 2, "outstanding": 1, "queue_depth": 0,
               "p99_s": 3.0, "slo_alerting": False}
        assert decide(lat, pol) == "up"
        slo = {"live": 2, "outstanding": 0, "queue_depth": 0,
               "p99_s": None, "slo_alerting": True}
        assert decide(slo, pol) == "up"
        idle = {"live": 2, "outstanding": 0, "queue_depth": 0,
                "p99_s": 0.1, "slo_alerting": False}
        assert decide(idle, pol) == "down"
        hold = {"live": 2, "outstanding": 4, "queue_depth": 1,
                "p99_s": 0.1, "slo_alerting": False}  # inside the band
        assert decide(hold, pol) is None

    def test_idle_overrides_stale_p99(self):
        """Review finding: the latency reservoir is all-time and only
        decays with new traffic — after a burst stops, the stale high
        p99 must neither block scale-down nor keep voting up."""
        pol = AutoscalePolicy(queue_high=4.0, queue_low=1.0,
                              p99_high_s=2.0)
        stale_idle = {"live": 3, "outstanding": 0, "queue_depth": 0,
                      "p99_s": 30.0, "slo_alerting": False}
        assert decide(stale_idle, pol) == "down"
        # the same p99 WITH load still scales up
        stale_loaded = {"live": 3, "outstanding": 1, "queue_depth": 0,
                        "p99_s": 30.0, "slo_alerting": False}
        assert decide(stale_loaded, pol) == "up"

    def test_add_replica_refuses_stale_staging_after_rollout(
            self, params, params2):
        """A rollout landing while a scale-up warms its new engine
        means the staged weights are one generation old — the call
        raises for the autoscaler to retry, never admits them."""
        fleet, _ = make_fleet_service(params)
        build = fleet._build_replica_engine

        def build_and_roll(index, device):
            eng = build(index, device)
            fleet.rollout(params2)  # lands mid-staging
            return eng

        fleet._build_replica_engine = build_and_roll
        with pytest.raises(RuntimeError, match="rolled out during"):
            fleet.add_replica(reason="test")
        assert fleet.live_replicas() == 2  # nothing stale admitted
        fleet._build_replica_engine = build
        rep = fleet.add_replica(reason="retry")
        assert rep["generation"] == 1  # the retry stages gen-1 weights

    def test_up_needs_consecutive_evals(self):
        auto, fleet, svc, clock = make_autoscaler()
        svc.signals["outstanding"] = 100
        assert auto.tick() is None  # streak 1 < 2
        assert auto.tick() == "up"
        assert fleet.live == 3

    def test_spike_does_not_scale(self):
        auto, fleet, svc, clock = make_autoscaler()
        svc.signals["outstanding"] = 100
        assert auto.tick() is None
        svc.signals["outstanding"] = 0
        svc.signals["p99_s"] = 0.0
        auto.tick()  # streak broken
        svc.signals["outstanding"] = 100
        assert auto.tick() is None  # must re-earn the streak
        assert fleet.actions == []

    def test_cooldown_blocks_flapping_on_step_change(self):
        """A step load change produces ONE transition: after the up,
        the cooldown holds even though the signal persists; when it
        expires, the still-sustained signal earns the next step."""
        auto, fleet, svc, clock = make_autoscaler(cooldown_s=100.0)
        svc.signals["outstanding"] = 100
        auto.tick(); auto.tick()
        assert fleet.live == 3
        for _ in range(10):
            clock[0] += 1.0
            assert auto.tick() is None  # in cooldown
        clock[0] += 200.0
        assert auto.tick() == "up"  # cooldown over, signal sustained
        assert fleet.live == 4

    def test_down_requires_sustained_idle_and_floor(self):
        auto, fleet, svc, clock = make_autoscaler(
            live=2, min_replicas=2, down_consecutive=2)
        svc.signals["outstanding"] = 0
        svc.signals["p99_s"] = 0.0
        for _ in range(5):
            assert auto.tick() is None  # at the floor: never below min
        auto2, fleet2, svc2, clock2 = make_autoscaler(
            live=3, min_replicas=2, down_consecutive=2)
        svc2.signals["outstanding"] = 0
        svc2.signals["p99_s"] = 0.0
        assert auto2.tick() is None
        assert auto2.tick() == "down"
        assert fleet2.live == 2

    def test_max_bound_holds(self):
        auto, fleet, svc, clock = make_autoscaler(
            live=4, max_replicas=4, up_consecutive=1)
        svc.signals["outstanding"] = 1000
        assert auto.tick() is None
        assert fleet.live == 4

    def test_needs_fleet_service(self):
        with pytest.raises(ValueError, match="fleet"):
            Autoscaler(object(), AutoscalePolicy())


class TestAutoscalerLive:
    def test_scale_transitions_drop_zero_requests(self, params):
        """Rollout-style pin: requests flow continuously while the fleet
        scales 2 -> 3 -> 2; every admitted request resolves, zero
        rejects, and the scale events land on the bus."""
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(params, telemetry=tel)
        auto = Autoscaler(
            svc, AutoscalePolicy(min_replicas=2, max_replicas=3,
                                 up_consecutive=1, down_consecutive=1,
                                 cooldown_s=0.0),
            clock=lambda: 0.0)
        img = make_image()
        stop = threading.Event()
        resolved, errors = [], []

        def client():
            while not stop.is_set():
                try:
                    resolved.append(
                        svc.predict(img, deadline_ms=60_000,
                                    timeout=60.0).count)
                except Exception as e:  # noqa: BLE001 — the assert
                    errors.append(e)

        with svc:
            threads = [threading.Thread(target=client)
                       for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            auto.observe = lambda: {"live": fleet.live_replicas(),
                                    "outstanding": 1000,
                                    "queue_depth": 5, "p99_s": None,
                                    "slo_alerting": False}
            assert auto.tick() == "up"
            assert fleet.live_replicas() == 3
            time.sleep(0.3)  # traffic through the grown fleet
            auto.observe = lambda: {"live": fleet.live_replicas(),
                                    "outstanding": 0,
                                    "queue_depth": 0, "p99_s": 0.0,
                                    "slo_alerting": False}
            assert auto.tick() == "down"
            assert fleet.live_replicas() == 2
            time.sleep(0.3)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
        assert not errors, errors[:3]
        assert len(resolved) > 0
        assert svc.stats()["rejected"] == 0
        scale = [e["payload"]["direction"] for e in events
                 if e["kind"] == "fleet.scale"]
        assert scale == ["up", "down"]
        up = [e for e in events if e["kind"] == "fleet.scale"
              and e["payload"]["direction"] == "up"][0]
        assert up["payload"]["time_to_first_ready_s"] > 0

    def test_remove_replica_refuses_last_live(self, params):
        fleet, svc = make_fleet_service(params)
        fleet.replicas[0].state = "quarantined"
        with pytest.raises(RuntimeError, match="below 1"):
            fleet.remove_replica(reason="test")


# --- serve-side fault injection -----------------------------------------
class TestServeFaults:
    def test_on_serve_batch_crash_and_hang(self):
        inj = faults.FaultInjector({"faults": [
            {"kind": "replica_crash", "replica": 0, "batch": 2},
            {"kind": "replica_hang", "replica": 1, "batch": 1,
             "delay_s": 0.05}]})
        inj.on_serve_batch(replica=0, batch_index=1)  # no match
        with pytest.raises(faults.InjectedFault):
            inj.on_serve_batch(replica=0, batch_index=2)
        inj.on_serve_batch(replica=0, batch_index=2)  # fires ONCE
        t0 = time.perf_counter()
        inj.on_serve_batch(replica=1, batch_index=1)  # sleeps
        assert time.perf_counter() - t0 >= 0.05
        assert len(inj.fired) == 2

    def test_unknown_kind_rejected_known_kinds_accepted(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.FaultInjector({"faults": [{"kind": "replica_oops"}]})
        faults.FaultInjector({"faults": [{"kind": "replica_crash"},
                                         {"kind": "replica_hang"}]})

    def test_trigger_grammar_documented(self):
        doc = faults.__doc__
        assert "replica_crash" in doc and "replica_hang" in doc

    def test_env_gated_zero_cost(self, monkeypatch):
        monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
        assert faults.active_injector() is None


# --- chaos (the acceptance run) -----------------------------------------
class TestChaos:
    def _with_faults(self, monkeypatch, schedule):
        monkeypatch.setenv(faults.FAULTS_ENV, json.dumps(schedule))
        # the injector caches per spec value; force a fresh parse
        monkeypatch.setattr(faults, "_CACHED", None)
        monkeypatch.setattr(faults, "_CACHED_SPEC", None)

    def test_seeded_crash_quarantine_probe_resurrect_zero_lost(
            self, params, monkeypatch):
        """ISSUE 13 acceptance: sustained load, seeded replica_crash on
        1 of 2 replicas -> quarantine -> probe -> resurrection at the
        current generation, ZERO lost admitted requests, live back to 2.
        Real maintenance thread, real worker threads, env trigger."""
        self._with_faults(monkeypatch, {"faults": [
            {"kind": "replica_crash", "replica": 0, "batch": 1}]})
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(
            params, telemetry=tel, self_heal=True,
            probe_cooldown_s=0.3, maintain_interval_s=0.05)
        img = make_image()
        with svc:
            tickets = [svc.submit(img, deadline_ms=120_000)
                       for _ in range(16)]
            results = [t.result(timeout=120.0) for t in tickets]
            assert len(results) == 16  # zero lost admitted requests
            t0 = time.time()
            while fleet.live_replicas() < 2 and time.time() - t0 < 30:
                time.sleep(0.05)
            assert fleet.live_replicas() == 2  # healed
            # sustained load THROUGH the healed fleet
            tickets = [svc.submit(img, deadline_ms=120_000)
                       for _ in range(8)]
            for t in tickets:
                t.result(timeout=120.0)
        assert svc.stats()["rejected"] == 0
        crash = [f for f in (faults.active_injector() or
                             faults.FaultInjector({"faults": []})).fired]
        assert len(crash) == 1  # the seeded fault fired exactly once
        kinds = [e["kind"] for e in events]
        assert kinds.count("fleet.resurrect") == 1
        res = [e for e in events if e["kind"] == "fleet.resurrect"][0]
        assert res["payload"]["generation"] == fleet.generation
        rows = {r["replica"]: r for r in fleet.healthz()["replicas"]}
        assert all(r["state"] == "active" for r in rows.values())

    def test_seeded_hang_watchdog_within_priced_deadline(
            self, params, monkeypatch):
        """ISSUE 13 acceptance: a seeded replica_hang (replica 0, 5 s —
        TEN times the watchdog deadline) is detected within the priced
        deadline and its batch completes on the SURVIVING replica: the
        whole wave resolves long before the hang would have returned."""
        self._with_faults(monkeypatch, {"faults": [
            {"kind": "replica_hang", "replica": 0, "batch": 1,
             "delay_s": 5.0}]})
        tel, events = collecting_telemetry()
        fleet, svc = make_fleet_service(
            params, telemetry=tel, self_heal=True,
            probe_cooldown_s=0.3, maintain_interval_s=0.05,
            watchdog_default_s=0.5)
        img = make_image()
        inj = faults.active_injector()
        with svc:
            t0 = time.time()
            tickets = []
            # stream requests until replica 0 takes one (work stealing
            # decides who pulls; the seeded fault fires on ITS first)
            while not inj.fired and len(tickets) < 20:
                tickets.append(svc.submit(img, deadline_ms=120_000))
                time.sleep(0.05)
            assert inj.fired, "replica 0 never pulled a batch"
            tickets.append(svc.submit(img, deadline_ms=120_000))
            results = [t.result(timeout=30.0) for t in tickets]
            dt = time.time() - t0
        assert len(results) == len(tickets)  # zero lost, incl. the
        # hung batch — re-dispatched to the survivor by the watchdog
        assert dt < 4.0, dt  # never waited the 5 s hang out
        wedge = [e for e in events if e["kind"] == "fleet.replica"
                 and e["payload"]["state"] == "wedged"]
        assert len(wedge) == 1
        assert wedge[0]["payload"]["replica"] == 0
        assert svc.stats()["rejected"] == 0


# --- events, gauges, report, generation visibility ----------------------
class TestObservability:
    def test_event_kinds_include_healing(self):
        from can_tpu.obs.bus import EVENT_KINDS

        for k in ("fleet.scale", "fleet.resurrect", "fleet.probe"):
            assert k in EVENT_KINDS

    def test_gauge_sink_healing_kinds(self):
        sink = obs.GaugeSink()
        for payload in ({"direction": "up", "live": 3,
                         "time_to_first_ready_s": 0.2},
                        {"direction": "down", "live": 2}):
            sink.emit({"kind": "fleet.scale", "payload": payload})
        sink.emit({"kind": "fleet.resurrect",
                   "payload": {"replica": 1, "live": 2}})
        sink.emit({"kind": "fleet.probe", "payload": {"ok": False}})
        sink.emit({"kind": "fleet.probe", "payload": {"ok": True}})
        sink.emit({"kind": "fleet.replica",
                   "payload": {"replica": 0, "state": "wedged"}})
        text = sink.render()
        assert ('can_tpu_fleet_scale_events_total{direction="up"} 1'
                in text)
        assert ('can_tpu_fleet_scale_events_total{direction="down"} 1'
                in text)
        assert ('can_tpu_fleet_resurrections_total{replica="1"} 1'
                in text)
        assert 'can_tpu_fleet_probes_total{ok="0"} 1' in text
        assert 'can_tpu_fleet_probes_total{ok="1"} 1' in text
        assert "can_tpu_fleet_live_replicas 2" in text
        # a wedge counts with the quarantines (the hang flavour)
        assert ('can_tpu_fleet_quarantines_total{replica="0"} 1'
                in text)

    def test_report_summarizes_healing(self):
        events = [
            {"kind": "fleet.scale", "ts": 1.0,
             "payload": {"direction": "up", "live": 3,
                         "time_to_first_ready_s": 0.21}},
            {"kind": "fleet.scale", "ts": 2.0,
             "payload": {"direction": "down", "live": 2}},
            {"kind": "fleet.probe", "ts": 3.0, "payload": {"ok": False}},
            {"kind": "fleet.probe", "ts": 4.0, "payload": {"ok": True}},
            {"kind": "fleet.resurrect", "ts": 5.0,
             "payload": {"replica": 0, "live": 2}},
        ]
        s = summarize(events)
        assert s["fleet_scale_up"] == 1 and s["fleet_scale_down"] == 1
        assert s["fleet_resurrections"] == 1
        assert s["fleet_probes_ok"] == 1
        assert s["fleet_probes_failed"] == 1
        assert s["fleet_live_replicas"] == 2
        assert s["fleet_ttfr_last_s"] == 0.21
        text = format_report(s)
        assert "fleet healing" in text
        assert "resurrections=1" in text

    def test_offline_summary_has_no_healing_row(self):
        text = format_report(summarize([]))
        assert "fleet healing" not in text

    def test_generation_skew_visible_everywhere(self, params):
        """Satellite: /healthz and per-replica /stats rows carry each
        replica's generation; a mixed-generation serving set is flagged,
        and the scrape renders the per-replica generation lines."""
        from can_tpu.obs.exporter import render_stats

        fleet, svc = make_fleet_service(params)
        fleet.replicas[1].generation = 3  # simulate skew
        h = fleet.healthz()
        assert h["generations"] == [0, 3]
        assert h["mixed_generations"] is True
        rows = {r["replica"]: r["generation"] for r in h["replicas"]}
        assert rows == {0: 0, 1: 3}
        st = svc.stats()
        assert st["mixed_generations"] is True
        assert st["replicas"]["0"]["generation"] == 0
        assert st["replicas"]["1"]["generation"] == 3
        text = render_stats(st)
        assert 'can_tpu_serve_generation{replica="0"} 0' in text
        assert 'can_tpu_serve_generation{replica="1"} 3' in text
        assert "can_tpu_serve_mixed_generations 1" in text


# --- CLI flags -----------------------------------------------------------
class TestCLI:
    def test_parse_healing_flags(self):
        from can_tpu.cli.serve import parse_args

        a = parse_args(["--replicas", "2", "--aot-bundle", "/b",
                        "--aot-bake", "/o", "--autoscale-max", "4",
                        "--autoscale-min", "2",
                        "--probe-cooldown-s", "2.5",
                        "--watchdog-slack", "5",
                        "--watchdog-default-s", "10"])
        assert a.aot_bundle == "/b" and a.aot_bake == "/o"
        assert a.autoscale_max == 4 and a.autoscale_min == 2
        assert a.probe_cooldown_s == 2.5
        assert a.watchdog_slack == 5.0
        assert a.watchdog_default_s == 10.0
        d = parse_args([])
        assert d.autoscale_max == 0 and d.aot_bundle == ""

    def test_fleet_only_flags_refused_single_engine(self):
        from can_tpu.cli.serve import build_service, parse_args

        for flags in (["--aot-bundle", "/b"], ["--aot-bake", "/o"],
                      ["--autoscale-max", "2"]):
            with pytest.raises(SystemExit, match="fleet mode"):
                build_service(parse_args(flags))

    def test_autoscale_max_must_exceed_replicas(self):
        from can_tpu.cli.serve import build_service, parse_args

        with pytest.raises(SystemExit, match="autoscale-max"):
            build_service(parse_args(["--replicas", "2",
                                      "--autoscale-max", "2"]))

    def test_autoscale_min_validated_before_load(self):
        """An out-of-range --autoscale-min is a pre-runtime SystemExit
        like every sibling flag misuse, not an AutoscalePolicy
        ValueError traceback after minutes of load+warmup."""
        from can_tpu.cli.serve import build_service, parse_args

        for bad in ("0", "5"):
            with pytest.raises(SystemExit, match="autoscale-min"):
                build_service(parse_args(["--replicas", "2",
                                          "--autoscale-max", "3",
                                          "--autoscale-min", bad]))
