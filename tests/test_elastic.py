"""Elastic shrink-and-continue: the tier-1 (fast, single-process) layer.

The REAL 2-process choreography — seeded SIGTERM kill, agreement,
shrink checkpoint, re-rendezvous at dp', bit-identical continuation —
lives in tests/test_multiprocess.py::test_elastic_shrink_and_continue
(slow-marked).  Here every
component is pinned in isolation:

* signal files + elastic manifest (atomic, torn-safe, liveness rule);
* generation-counted runtime re-init and the bounded-timeout barrier's
  typed RendezvousTimeoutError;
* the drift guard's elastic allowance (dp-only change OK across a
  transition, real drift still rejected);
* planner replanning of an epoch remainder at a NEW quantum preserving
  exact once-per-epoch coverage;
* the deterministic fault harness (seeded kill schedule, checkpoint-I/O
  error injection, env/file triggers);
* checkpoint save/restore retry/backoff + typed CheckpointIOError;
* the train-loop on_step hook (state attached to ElasticInterrupt, no
  incident bundle for control flow);
* run_monitor --emit-signal -> supervisor polling composition;
* elastic.transition rendering in obs/report;
* the dp'-mesh HLO-audit contracts + the collective-structure mutation.
"""

import json
import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from can_tpu.obs import signals as sig
from can_tpu.parallel import elastic as el
from can_tpu.parallel import runtime as rt
from can_tpu.testing import faults as flt


# -- signal files ---------------------------------------------------------
class TestSignals:
    def test_write_read_roundtrip(self, tmp_path):
        d = str(tmp_path)
        p = sig.write_signal(d, kind="leave", host_id=3, reason="sigterm",
                             detail={"x": 1})
        assert os.path.basename(p) == "signal-leave-h3.json"
        docs = sig.read_signals(d)
        assert len(docs) == 1
        assert docs[0]["kind"] == "leave"
        assert docs[0]["host_id"] == 3
        assert docs[0]["detail"] == {"x": 1}
        assert sig.leaver_hosts(docs) == {3}

    def test_unknown_kind_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown signal kind"):
            sig.write_signal(str(tmp_path), kind="maybe", host_id=0,
                             reason="?")

    def test_torn_and_foreign_files_skipped(self, tmp_path):
        d = str(tmp_path)
        sig.write_signal(d, kind="dead", host_id=1, reason="stale")
        (tmp_path / "signal-dead-h2.json").write_text('{"half')
        (tmp_path / "signal-leave-h9.json").write_text('{"schema": "other"}')
        (tmp_path / "unrelated.json").write_text("{}")
        docs = sig.read_signals(d)
        assert [s["host_id"] for s in docs] == [1]

    def test_stay_signals_are_not_leavers(self, tmp_path):
        d = str(tmp_path)
        sig.write_signal(d, kind="stay", host_id=0, reason="reform",
                         detail={"address": "h0:8576"})
        sig.write_signal(d, kind="leave", host_id=2, reason="sigterm")
        assert sig.leaver_hosts(sig.read_signals(d)) == {2}

    def test_missing_dir_reads_empty(self, tmp_path):
        assert sig.read_signals(str(tmp_path / "nope")) == []


# -- manifest -------------------------------------------------------------
def _manifest(epoch=0, steps=1, consumed=(0, 1), generation=1):
    return {"schema": el.MANIFEST_SCHEMA, "ts": 123.0,
            "generation": generation, "transition_id": generation,
            "epoch": epoch, "steps_done": steps,
            "consumed": list(consumed), "reason": "preemption",
            "leavers": [1], "survivors": [0],
            "world_old": {"processes": 2, "dp": 8, "sp": 1, "devices": 8,
                          "batch_size": 4},
            "world_new": {"processes": 1, "dp": 4, "sp": 1, "devices": 4},
            "lr_scale": 0.5}


class TestManifest:
    def test_save_load_roundtrip(self, tmp_path):
        m = _manifest()
        el.save_manifest(str(tmp_path), m)
        assert el.load_manifest(str(tmp_path)) == m

    def test_absent_torn_wrong_schema_read_as_none(self, tmp_path):
        assert el.load_manifest(str(tmp_path)) is None
        (tmp_path / el.MANIFEST_NAME).write_text("{torn")
        assert el.load_manifest(str(tmp_path)) is None
        (tmp_path / el.MANIFEST_NAME).write_text('{"schema": "v0"}')
        assert el.load_manifest(str(tmp_path)) is None

    def test_liveness_rule(self):
        m = _manifest(epoch=3)
        # live until a COMPLETED-epoch checkpoint reaches the epoch
        assert el.manifest_is_live(m, None)
        assert el.manifest_is_live(m, 2)
        assert not el.manifest_is_live(m, 3)
        assert not el.manifest_is_live(m, 7)
        assert not el.manifest_is_live(None, None)

    def test_consumed_items_from_schedule_prefix(self):
        sched = [((64, 64), [(0, True), (1, True)]),
                 ((64, 64), [(2, True), (2, False)]),  # fill slot dup
                 ((64, 64), [(3, True), (4, True)])]
        assert el.consumed_items(sched, 2) == [0, 1, 2]
        assert el.consumed_items(sched, 0) == []
        assert el.consumed_items(sched, 99) == [0, 1, 2, 3, 4]

    def test_remaining_items_partition(self):
        m = _manifest(consumed=(0, 2, 4))
        assert el.remaining_items(m, 6) == [1, 3, 5]
        with pytest.raises(ValueError, match="outside the dataset"):
            el.remaining_items(m, 3)  # consumed names item 4


# -- re-formation planning ------------------------------------------------
class TestReformation:
    def test_plan_survivor_ranks(self):
        p = el.plan_reformation(n_processes=4, leavers={1, 3},
                                process_index=2)
        assert p["survivors"] == [0, 2]
        assert p["new_num_processes"] == 2
        assert p["new_process_id"] == 1
        assert not p["leaving"]

    def test_plan_for_leaver(self):
        p = el.plan_reformation(n_processes=2, leavers={1},
                                process_index=1)
        assert p["leaving"] and p["new_process_id"] is None
        assert p["survivors"] == [0]

    def test_bad_leavers_rejected(self):
        with pytest.raises(ValueError, match="outside"):
            el.plan_reformation(n_processes=2, leavers={5},
                                process_index=0)
        with pytest.raises(ValueError, match="no leavers"):
            el.plan_reformation(n_processes=2, leavers=set(),
                                process_index=0)

    def test_coordinator_from_stay_file(self, tmp_path):
        d = str(tmp_path)
        assert el.reform_coordinator(d, [0], generation=1) is None
        sig.write_signal(d, kind="stay", host_id=1, reason="reform",
                         detail={"address": "hostb:8577"})
        sig.write_signal(d, kind="stay", host_id=2, reason="reform",
                         detail={"address": "hostc:8577"})
        assert el.reform_coordinator(d, [1, 2], generation=1) == "hostb:8577"
        with pytest.raises(RuntimeError, match="no stay-file"):
            el.reform_coordinator(d, [0, 1], generation=1)


# -- runtime re-init + bounded barrier ------------------------------------
class TestRuntimeReinit:
    def test_generation_counts_across_shutdown_init_cycles(self):
        g0 = rt.generation()
        topo1 = rt.init_runtime()
        assert rt.runtime_active()
        assert topo1["generation"] == rt.generation()
        # repeat call while live: same generation, topology unchanged
        assert rt.init_runtime()["generation"] == topo1["generation"]
        rt.shutdown_runtime()
        assert not rt.runtime_active()
        topo2 = rt.init_runtime()
        assert topo2["generation"] == topo1["generation"] + 1
        assert topo2["process_count"] == 1
        assert topo2["generation"] > g0

    def test_reinit_yields_correct_smaller_mesh(self):
        """shutdown_runtime() -> init_runtime() then a mesh over a
        smaller device subset: process_count and mesh shape are the
        shrunk world's (the single-host analogue of dp' re-formation;
        the 2-process version lives in the chaos test)."""
        import jax

        from can_tpu.parallel import make_mesh

        rt.init_runtime()
        n = len(jax.devices())
        assert n >= 8
        rt.shutdown_runtime()
        topo = rt.init_runtime()
        assert topo["process_count"] == 1
        mesh = make_mesh(jax.devices()[: n // 2])
        assert mesh.devices.shape == (n // 2, 1)

    def test_reinit_without_env_rendezvous_ignores_stale_launcher_env(
            self, monkeypatch):
        """The re-formation bug the live 2-host CLI drive caught: after
        a shrink, the launcher's COORDINATOR_ADDRESS/NUM_PROCESSES env
        still describes the DEAD world — a lone survivor re-initialising
        through env rendezvous would wait forever for the departed rank.
        ``env_rendezvous=False`` (what ElasticSupervisor.reform passes)
        must form a single-process generation without touching them."""
        rt.init_runtime()
        rt.shutdown_runtime()
        monkeypatch.setenv("COORDINATOR_ADDRESS", "localhost:1")
        monkeypatch.setenv("NUM_PROCESSES", "2")
        monkeypatch.setenv("PROCESS_ID", "0")
        topo = rt.init_runtime(env_rendezvous=False)
        assert topo["process_count"] == 1  # never tried the dead world

    def test_barrier_noop_single_process(self):
        rt.init_runtime()
        rt.barrier("anything", timeout_s=0.01)  # must not raise or hang

    def test_barrier_timeout_raises_typed_error(self, monkeypatch):
        from jax.experimental import multihost_utils

        monkeypatch.setattr(rt.jax, "process_count", lambda: 2)
        # no coordination client in a single-process test: force the
        # thread-bounded fallback around a hanging sync
        monkeypatch.setattr(
            "jax._src.distributed.global_state.client", None,
            raising=False)
        monkeypatch.setattr(multihost_utils, "sync_global_devices",
                            lambda name: time.sleep(30))
        t0 = time.monotonic()
        with pytest.raises(rt.RendezvousTimeoutError) as ei:
            rt.barrier("elastic-shrink-g1", timeout_s=0.2)
        assert time.monotonic() - t0 < 5
        err = ei.value
        assert err.barrier == "elastic-shrink-g1"
        assert err.generation == rt.generation()
        assert err.timeout_s == 0.2
        assert err.missing is None
        assert "missing hosts" in str(err)

    def test_barrier_error_names_missing_tasks_when_reported(self):
        msg = ("barrier failed: tasks not at barrier: "
               "/job:jax_worker/replica:0/task:3, "
               "/job:jax_worker/replica:0/task:1")
        assert rt._parse_missing_tasks(msg) == [1, 3]
        assert rt._parse_missing_tasks("nothing here") is None

    def test_barrier_unbounded_mode_preserved(self, monkeypatch):
        from jax.experimental import multihost_utils

        called = []
        monkeypatch.setattr(rt.jax, "process_count", lambda: 2)
        monkeypatch.setattr(multihost_utils, "sync_global_devices",
                            lambda name: called.append(name))
        rt.barrier("old-style", timeout_s=0)  # <= 0: the pre-r13 wait
        assert called == ["old-style"]


# -- drift guard elastic allowance ----------------------------------------
class TestElasticDriftGuard:
    SAVED = {"lr": 1e-7, "epochs": 10, "world_size": 8}

    def test_dp_only_change_allowed_across_transition(self):
        from can_tpu.utils.checkpoint import check_resume_config

        drifted = check_resume_config(
            self.SAVED, {"lr": 1e-7, "epochs": 10, "world_size": 4},
            allow_elastic=True)
        assert drifted == ["world_size"]

    def test_dp_change_rejected_without_transition(self):
        from can_tpu.utils.checkpoint import (
            ConfigDriftError,
            check_resume_config,
        )

        with pytest.raises(ConfigDriftError, match="world_size"):
            check_resume_config(
                self.SAVED, {"lr": 1e-7, "epochs": 10, "world_size": 4})

    def test_real_drift_rejected_even_with_elastic(self):
        from can_tpu.utils.checkpoint import (
            ConfigDriftError,
            check_resume_config,
        )

        with pytest.raises(ConfigDriftError, match="lr"):
            check_resume_config(
                self.SAVED, {"lr": 5e-7, "epochs": 10, "world_size": 4},
                allow_elastic=True)

    def test_explicit_allow_still_wins(self):
        from can_tpu.utils.checkpoint import check_resume_config

        drifted = check_resume_config(
            self.SAVED, {"lr": 5e-7, "epochs": 10, "world_size": 4},
            allow=True)
        assert set(drifted) == {"lr", "world_size"}


# -- planner replanning of an epoch remainder -----------------------------
def _varres_batcher(tmp_path, *, batch, quantum, process_count=1,
                    process_index=0, n=20):
    from can_tpu.data import CrowdDataset, ShardedBatcher, \
        make_synthetic_dataset

    root = tmp_path / "data"
    if not root.exists():
        make_synthetic_dataset(
            str(root), n,
            sizes=((64, 64), (64, 96), (96, 64), (96, 96)), seed=3)
    ds = CrowdDataset(str(root / "images"), str(root / "ground_truth"),
                      gt_downsample=8, phase="train")
    return ShardedBatcher(ds, batch, shuffle=True, seed=3,
                          process_index=process_index,
                          process_count=process_count,
                          pad_multiple="auto", max_buckets=2,
                          remnant_sizes=True, batch_quantum=quantum,
                          launch_cost_px=0)


class TestRemainderReplan:
    def test_subset_schedule_exact_coverage_at_new_quantum(self, tmp_path):
        """The elastic core invariant: items consumed by the old world's
        schedule prefix plus a remainder REPLANNED at a different
        quantum (the shrunk world's) cover the epoch exactly once."""
        from can_tpu.data.planner import schedule_coverage

        old = _varres_batcher(tmp_path, batch=8, quantum=8)   # old world
        sched = old.global_schedule(0)
        consumed = set(el.consumed_items(sched, 2))
        assert consumed  # the prefix consumed something
        remaining = set(range(20)) - consumed
        new = _varres_batcher(tmp_path, batch=4, quantum=4)   # dp' world
        sub = new.global_schedule(0, remaining)
        cov = schedule_coverage(sub)
        assert cov == {i: 1 for i in sorted(remaining)}
        # and the union with consumed is the whole epoch, disjoint
        assert consumed | set(cov) == set(range(20))
        assert not (consumed & set(cov))

    def test_subset_schedule_is_deterministic(self, tmp_path):
        include = set(range(3, 17))
        b1 = _varres_batcher(tmp_path, batch=4, quantum=4)
        b2 = _varres_batcher(tmp_path, batch=4, quantum=4)
        assert b1.global_schedule(0, include) == \
            b2.global_schedule(0, include)

    def test_subset_keeps_epoch_shuffle_order(self, tmp_path):
        b = _varres_batcher(tmp_path, batch=4, quantum=4)
        full = [i for _, g in b.global_schedule(0)
                for i, v in g if v]
        include = set(full[5:])
        sub = [i for _, g in b.global_schedule(0, include)
               for i, v in g if v]
        # per bucket cell, subset items appear in the epoch's order
        assert set(sub) == include

    def test_epoch_yields_only_subset_items(self, tmp_path):
        b = _varres_batcher(tmp_path, batch=4, quantum=4)
        include = set(range(0, 10))
        images = 0.0
        for batch in b.epoch(0, include):
            images += batch.num_valid
        assert images == len(include)

    def test_full_schedule_unchanged_by_feature(self, tmp_path):
        b = _varres_batcher(tmp_path, batch=4, quantum=4)
        assert b.global_schedule(0) == b.global_schedule(0, None)


# -- fault harness --------------------------------------------------------
class TestFaultHarness:
    def test_kill_schedule_seeded_and_bounded(self):
        s1 = flt.make_kill_schedule(7, rank=1, max_step=9, min_step=2)
        s2 = flt.make_kill_schedule(7, rank=1, max_step=9, min_step=2)
        assert s1 == s2  # one seed reproduces exactly
        steps = {flt.make_kill_schedule(s, rank=1, max_step=9,
                                        min_step=2)["faults"][0]["step"]
                 for s in range(40)}
        assert steps <= set(range(2, 10))
        assert len(steps) > 1  # different seeds move the fault around
        with pytest.raises(ValueError):
            flt.make_kill_schedule(0, rank=0, max_step=1, min_step=5)

    def test_env_gating_and_file_trigger(self, tmp_path, monkeypatch):
        monkeypatch.delenv(flt.FAULTS_ENV, raising=False)
        assert flt.active_injector() is None
        spec = {"faults": [{"kind": "ckpt_io", "op": "save", "fails": 1}]}
        f = tmp_path / "faults.json"
        f.write_text(json.dumps(spec))
        monkeypatch.setenv(flt.FAULTS_ENV, str(f))
        inj = flt.active_injector()
        assert inj is not None and len(inj.faults) == 1
        # cached per spec value (attempt counters persist)
        assert flt.active_injector() is inj

    def test_inline_json_trigger(self, monkeypatch):
        monkeypatch.setenv(flt.FAULTS_ENV, '{"faults": []}')
        assert flt.active_injector().faults == []

    def test_malformed_schedule_raises(self):
        with pytest.raises(ValueError, match="fault list"):
            flt.FaultInjector({})
        with pytest.raises(ValueError, match="unknown fault kind"):
            flt.FaultInjector({"faults": [{"kind": "meteor"}]})

    def test_ckpt_io_fires_first_n_attempts(self):
        inj = flt.FaultInjector(
            {"faults": [{"kind": "ckpt_io", "op": "save", "fails": 2}]})
        for _ in range(2):
            with pytest.raises(flt.InjectedFault):
                inj.on_ckpt_io("save")
        inj.on_ckpt_io("save")      # 3rd attempt passes
        inj.on_ckpt_io("restore")   # other op untouched

    def test_kill_delivers_real_signal_once(self):
        got = []
        prev = signal.signal(signal.SIGUSR1,
                             lambda s, f: got.append(s))
        try:
            inj = flt.FaultInjector(
                {"faults": [{"kind": "kill", "rank": 1, "epoch": 0,
                             "step": 3, "signal": "SIGUSR1"}]})
            inj.on_step(3, epoch=0, rank=0)   # wrong rank: nothing
            inj.on_step(2, epoch=0, rank=1)   # wrong step: nothing
            assert got == []
            inj.on_step(3, epoch=0, rank=1)
            assert got == [signal.SIGUSR1]
            inj.on_step(3, epoch=0, rank=1)   # fires ONCE
            assert got == [signal.SIGUSR1]
        finally:
            signal.signal(signal.SIGUSR1, prev)

    def test_barrier_fault_delays_matching_rank(self, monkeypatch):
        inj = flt.FaultInjector(
            {"faults": [{"kind": "rendezvous_timeout",
                         "barrier": "elastic-shrink", "rank": 1,
                         "delay_s": 0.05}]})
        t0 = time.monotonic()
        inj.on_barrier("can_tpu:elastic-shrink-g2:g2", rank=0)
        assert time.monotonic() - t0 < 0.04  # other rank: no delay
        inj.on_barrier("can_tpu:elastic-shrink-g2:g2", rank=1)
        assert time.monotonic() - t0 >= 0.05


# -- checkpoint retry/backoff ---------------------------------------------
def _tiny_state():
    import jax

    from can_tpu.models import cannet_init
    from can_tpu.train import create_train_state, make_lr_schedule, \
        make_optimizer

    opt = make_optimizer(make_lr_schedule(1e-7))
    return create_train_state(cannet_init(jax.random.key(0)), opt)


class TestCheckpointRetries:
    def test_transient_save_failure_retries_then_succeeds(
            self, tmp_path, monkeypatch):
        from can_tpu.utils import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3,
                                backoff_s=0.01)
        real_save = mgr.manager.save
        calls = {"n": 0}

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise OSError("transient FS hiccup")
            return real_save(*a, **kw)

        monkeypatch.setattr(mgr.manager, "save", flaky)
        state = _tiny_state()
        assert mgr.save(0, state, mae=1.0)
        assert calls["n"] == 3
        mgr.wait()
        assert mgr.latest_epoch() == 0
        mgr.close()

    def test_exhausted_retries_raise_typed_error(self, tmp_path,
                                                 monkeypatch):
        from can_tpu.utils import CheckpointIOError, CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=2,
                                backoff_s=0.01)

        def always_fails(*a, **kw):
            raise OSError("disk on fire")

        monkeypatch.setattr(mgr.manager, "save", always_fails)
        with pytest.raises(CheckpointIOError) as ei:
            mgr.save(0, _tiny_state(), mae=1.0)
        assert ei.value.op == "save"
        assert ei.value.attempts == 2
        assert isinstance(ei.value.__cause__, OSError)
        mgr.close()

    def test_non_transient_errors_fail_immediately(self, tmp_path,
                                                   monkeypatch):
        from can_tpu.utils import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3,
                                backoff_s=0.01)
        calls = {"n": 0}

        def wrong_tree(*a, **kw):
            calls["n"] += 1
            raise ValueError("tree structure mismatch")

        monkeypatch.setattr(mgr.manager, "save", wrong_tree)
        with pytest.raises(ValueError, match="tree structure"):
            mgr.save(0, _tiny_state(), mae=1.0)
        assert calls["n"] == 1  # no retry for a non-transient class
        mgr.close()

    def test_injected_ckpt_faults_exercise_retry_path(
            self, tmp_path, monkeypatch):
        """The harness' ckpt_io fault rides INSIDE the retry loop: fails
        below the budget are absorbed; above it the typed give-up."""
        from can_tpu.utils import CheckpointIOError, CheckpointManager

        monkeypatch.setenv(
            flt.FAULTS_ENV,
            json.dumps({"faults": [{"kind": "ckpt_io", "op": "save",
                                    "fails": 2}]}))
        state = _tiny_state()
        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3,
                                backoff_s=0.01)
        assert mgr.save(0, state, mae=1.0)  # 2 injected failures absorbed
        mgr.wait()
        mgr.close()
        monkeypatch.setenv(
            flt.FAULTS_ENV,
            json.dumps({"faults": [{"kind": "ckpt_io", "op": "save",
                                    "fails": 99}]}))
        mgr2 = CheckpointManager(str(tmp_path / "ck2"), retries=2,
                                 backoff_s=0.01)
        with pytest.raises(CheckpointIOError):
            mgr2.save(0, state, mae=1.0)
        mgr2.close()

    def test_restore_retries_transient(self, tmp_path, monkeypatch):
        from can_tpu.utils import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3,
                                backoff_s=0.01)
        state = _tiny_state()
        mgr.save(0, state, mae=1.0)
        mgr.wait()
        real_restore = mgr.manager.restore
        calls = {"n": 0}

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("transient")
            return real_restore(*a, **kw)

        monkeypatch.setattr(mgr.manager, "restore", flaky)
        restored = mgr.restore(_tiny_state())
        assert int(restored.step) == int(state.step)
        assert calls["n"] == 2
        mgr.close()


# -- review-round hardening pins ------------------------------------------
class TestShrinkHardening:
    def test_stale_signal_cannot_cascade_into_new_generation(self, tmp_path):
        """A leave/dead file for an already-shrunk-away host names an
        ORIGINAL host id; after the transition, ranks are re-numbered —
        the stale file must neither re-trigger a shrink nor be
        misattributed to the innocent rank now wearing that number."""
        d = str(tmp_path / "sig")
        sup = el.ElasticSupervisor(d, check_every=1)
        # old world was 2 procs; host 1 left; this generation is the
        # lone survivor (original host 0) — exactly what reform()
        # inherits via adopt_manifest
        sup.adopt_manifest({"survivor_hosts": [0], "leaver_hosts": [1]})
        sig.write_signal(d, kind="leave", host_id=1, reason="sigterm")
        sup.step_hook(0)(1)  # stale file for a handled host: no interrupt
        # a monitor re-emitting 'dead' for the same gone host: still no
        sig.write_signal(d, kind="dead", host_id=1, reason="heartbeat_stale")
        sup.step_hook(0)(2)
        # but a NEW signal for a CURRENT member still shrinks
        sig.write_signal(d, kind="dead", host_id=0, reason="heartbeat_stale")
        with pytest.raises(el.ElasticInterrupt) as ei:
            sup.step_hook(0)(3)
        assert ei.value.leavers == {0}

    def test_shrink_marks_leavers_handled_and_sweeps_files(self, tmp_path):
        """After shrink(), the agreed leavers' signal files are swept and
        their ids marked handled — the manifest carries the original
        host ids the next generation filters on."""
        import jax

        rt.init_runtime()
        d = str(tmp_path / "sig")
        sup = el.ElasticSupervisor(d, check_every=1)
        sig.write_signal(d, kind="leave", host_id=0, reason="sigterm")
        interrupt = el.ElasticInterrupt(steps_done=1, leavers={0})
        state = _tiny_state()
        sched = [((64, 64), [(0, True), (1, True)])]
        m = sup.shrink(interrupt, state=state, epoch=0,
                       checkpoint_dir=str(tmp_path / "ck"),
                       schedule=sched, dp=len(jax.devices()), sp=1,
                       batch_size=2)
        assert m["leaver_hosts"] == [0]
        assert 0 in sup._handled
        assert sig.read_signals(d) == []  # consumed file swept
        assert el.load_manifest(str(tmp_path / "ck")) == m

    def test_agreement_is_bounded(self, monkeypatch):
        """A hard-dead peer (no grace) never joins the agreement
        allgather: the wait must become the typed RendezvousTimeoutError
        (→ incident bundle → restart-resume), never an unbounded hang."""
        monkeypatch.setattr(rt.jax, "process_count", lambda: 2)
        monkeypatch.setattr(rt, "agree_max_value",
                            lambda mask: time.sleep(30))
        t0 = time.monotonic()
        with pytest.raises(rt.RendezvousTimeoutError) as ei:
            el._bounded_agree(np.zeros((2,), np.float32), generation=1,
                              timeout_s=0.2)
        assert time.monotonic() - t0 < 5
        assert ei.value.barrier == "elastic-agreement"
        assert "hard death" in str(ei.value)

    def test_barrier_non_timeout_errors_pass_through(self, monkeypatch):
        """A peer-abort 2s into a barrier must NOT masquerade as a 300s
        timeout; only deadline-class failures become the typed error."""
        class FakeClient:
            def __init__(self, msg):
                self.msg = msg

            def wait_at_barrier(self, barrier_id, timeout_in_ms):
                raise RuntimeError(self.msg)

        class FakeState:
            client = FakeClient("task is set to ERROR: peer aborted "
                                "/job:jax_worker/replica:0/task:1")

        monkeypatch.setattr(rt.jax, "process_count", lambda: 2)
        monkeypatch.setattr("jax._src.distributed.global_state",
                            FakeState, raising=False)
        with pytest.raises(RuntimeError, match="peer aborted"):
            rt.barrier("shrink", timeout_s=5)
        FakeState.client = FakeClient(
            "DEADLINE_EXCEEDED: Barrier timed out. Barrier_id: x. The "
            "following tasks are at the barrier: ... not at the "
            "barrier: /job:jax_worker/replica:0/task:1")
        with pytest.raises(rt.RendezvousTimeoutError) as ei:
            rt.barrier("shrink", timeout_s=5)
        assert ei.value.missing == [1]

    def test_wait_failures_are_typed(self, tmp_path, monkeypatch):
        """Async Orbax write errors surface in wait(): they must arrive
        as CheckpointIOError (→ incident routing), not a raw OSError —
        the shrink save is the one path where losing the checkpoint
        loses the run."""
        from can_tpu.utils import CheckpointIOError, CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=2,
                                backoff_s=0.01)

        def broken_flush():
            raise OSError("async write failed")

        monkeypatch.setattr(mgr.manager, "wait_until_finished",
                            broken_flush)
        with pytest.raises(CheckpointIOError) as ei:
            mgr.wait()
        assert ei.value.op == "wait"
        monkeypatch.undo()  # close() flushes through the real wait
        mgr.close()

    def test_agreement_polls_on_first_step_of_short_epochs(self, tmp_path):
        """step resets per epoch: an epoch shorter than check_every must
        still poll (on step 1), or the layer is silently inert on small
        datasets — the preempted host would train through its grace
        window into the SIGKILL."""
        d = str(tmp_path / "sig")
        sup = el.ElasticSupervisor(d, check_every=4)
        sig.write_signal(d, kind="leave", host_id=0, reason="sigterm")
        with pytest.raises(el.ElasticInterrupt):
            sup.step_hook(0)(1)  # a 3-step epoch's first step polls

    def test_rank_targeted_ckpt_fault_matches_only_its_rank(self):
        inj = flt.FaultInjector(
            {"faults": [{"kind": "ckpt_io", "op": "save", "rank": 1,
                         "fails": 1}]})
        inj.on_ckpt_io("save", rank=0)  # other rank: untouched
        with pytest.raises(flt.InjectedFault):
            inj.on_ckpt_io("save", rank=1)
        # untargeted entries fire on EVERY rank
        inj2 = flt.FaultInjector(
            {"faults": [{"kind": "ckpt_io", "op": "save", "fails": 2}]})
        with pytest.raises(flt.InjectedFault):
            inj2.on_ckpt_io("save", rank=0)
        with pytest.raises(flt.InjectedFault):
            inj2.on_ckpt_io("save", rank=3)

    def test_missing_checkpoint_is_not_retried_as_transient(
            self, tmp_path, monkeypatch):
        """FileNotFoundError is an OSError subclass but never transient:
        a swept/missing step must surface as itself, immediately — not
        as 'failed after 3 attempts' filesystem flakiness."""
        from can_tpu.utils import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ck"), retries=3,
                                backoff_s=0.01)
        mgr.save(0, _tiny_state(), mae=1.0)
        mgr.wait()
        calls = {"n": 0}

        def gone(*a, **kw):
            calls["n"] += 1
            raise FileNotFoundError("step 7 swept by retention")

        monkeypatch.setattr(mgr.manager, "restore", gone)
        with pytest.raises(FileNotFoundError, match="retention"):
            mgr.restore(_tiny_state(), epoch=0)
        assert calls["n"] == 1  # no retry, no re-typing
        monkeypatch.undo()
        mgr.close()

    def test_subset_schedule_is_memoised(self, tmp_path):
        b = _varres_batcher(tmp_path, batch=4, quantum=4)
        inc = set(range(3, 17))
        s1 = b.global_schedule(0, inc)
        s2 = b.global_schedule(0, frozenset(inc))
        assert s1 is s2  # the identical subset plan is not rebuilt
        s3 = b.global_schedule(0, set(range(0, 10)))
        assert s3 is not s1  # a different subset recomputes
        assert b.global_schedule(1, inc) is not s1  # other epoch too


# -- supervisor + loop integration ----------------------------------------
class TestSupervisorHook:
    def test_leave_file_interrupts_at_poll_boundary(self, tmp_path):
        sup = el.ElasticSupervisor(str(tmp_path / "sig"), check_every=2)
        hook = sup.step_hook(0)
        hook(1)  # off the poll cadence: no file read, no interrupt
        sig.write_signal(str(tmp_path / "sig"), kind="leave", host_id=0,
                         reason="sigterm")
        hook(3)  # still off cadence
        with pytest.raises(el.ElasticInterrupt) as ei:
            hook(4)
        assert ei.value.steps_done == 4
        assert ei.value.leavers == {0}

    def test_sigterm_hook_sets_flag_and_writes_leave_file(self, tmp_path):
        rt.init_runtime()
        sup = el.ElasticSupervisor(str(tmp_path / "sig"), check_every=1)
        restore = sup.install_signal_hook()
        assert restore is not None
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            # python delivers on the main thread at the next bytecode
            for _ in range(100):
                if sup._leaving:
                    break
                time.sleep(0.01)
            assert sup._leaving
        finally:
            sup.close()
        docs = sig.read_signals(str(tmp_path / "sig"))
        assert [d["kind"] for d in docs] == ["leave"]
        with pytest.raises(el.ElasticInterrupt):
            sup.step_hook(0)(1)

    def test_loop_attaches_live_state_and_skips_incident(self, tmp_path):
        """ElasticInterrupt out of train_one_epoch carries the POST-step
        state (the exact shrink point) and is control flow: the armed
        IncidentManager writes NO bundle for it."""
        import jax

        from can_tpu import obs
        from can_tpu.data import CrowdDataset, ShardedBatcher, \
            make_synthetic_dataset
        from can_tpu.models import cannet_apply
        from can_tpu.parallel import make_dp_train_step, \
            make_global_batch, make_mesh
        from can_tpu.train import train_one_epoch

        make_synthetic_dataset(str(tmp_path / "data"), 16,
                               sizes=((64, 64),), seed=3)
        ds = CrowdDataset(str(tmp_path / "data" / "images"),
                          str(tmp_path / "data" / "ground_truth"),
                          gt_downsample=8, phase="train")
        mesh = make_mesh(jax.devices()[:8])
        batcher = ShardedBatcher(ds, 8, shuffle=True, seed=3)
        step = make_dp_train_step(cannet_apply, _opt(), mesh)
        state = _tiny_state()
        recorder = obs.FlightRecorder()
        tel = obs.Telemetry([recorder])
        mgr = obs.IncidentManager(tel, recorder,
                                  incident_dir=str(tmp_path / "inc"))
        tel.watchers.append(mgr)
        tel.incidents = mgr

        def on_step(s):
            if s == 1:
                raise el.ElasticInterrupt(steps_done=s, leavers={1})

        with pytest.raises(el.ElasticInterrupt) as ei:
            train_one_epoch(step, state,
                            batcher.epoch(0),
                            put_fn=lambda b: make_global_batch(b, mesh),
                            show_progress=False, telemetry=tel,
                            on_step=on_step)
        assert ei.value.state is not None
        assert int(ei.value.state.step) == 1  # post-step state attached
        assert ei.value.steps_done == 1
        assert mgr.bundles_written == 0  # control flow, not an incident
        # a REAL exception through the same path still bundles
        def boom(s):
            raise RuntimeError("loader exploded")

        with pytest.raises(RuntimeError):
            train_one_epoch(step, _tiny_state(), batcher.epoch(0),
                            put_fn=lambda b: make_global_batch(b, mesh),
                            show_progress=False, telemetry=tel,
                            on_step=boom)
        assert mgr.bundles_written == 1
        tel.close()


def _opt():
    from can_tpu.train import make_lr_schedule, make_optimizer

    return make_optimizer(make_lr_schedule(1e-7, world_size=8))


# -- run_monitor --emit-signal composition --------------------------------
class TestMonitorSignalComposition:
    def test_dead_host_finding_writes_supervisor_readable_signal(
            self, tmp_path):
        from tests.test_health import write_host_file
        from tools.run_monitor import analyze_dir, emit_dead_signals

        d = str(tmp_path / "run")
        os.makedirs(d)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        write_host_file(d, 1, step_s=0.1, t_end=1000.0)  # silent, dead
        run = analyze_dir(d, stale_after_s=30.0)
        assert run["dead"] == [1]
        sigdir = str(tmp_path / "sig")
        paths = emit_dead_signals(run, sigdir)
        assert len(paths) == 1
        docs = sig.read_signals(sigdir)
        assert docs[0]["kind"] == "dead"
        assert docs[0]["host_id"] == 1
        assert docs[0]["reason"] == "heartbeat_stale"
        assert docs[0]["detail"]["staleness_s"] == pytest.approx(100.0)
        # ... and the supervisor's poll sees exactly that host
        assert sig.leaver_hosts(docs) == {1}

    def test_cli_flag_one_shot(self, tmp_path, capsys):
        from tests.test_health import write_host_file
        from tools.run_monitor import main as monitor_main

        d = str(tmp_path / "run")
        os.makedirs(d)
        write_host_file(d, 0, step_s=0.1, t_end=1100.0)
        write_host_file(d, 1, step_s=0.1, t_end=1000.0)
        sigdir = str(tmp_path / "sig")
        rc = monitor_main([d, "--stale-after-s", "30",
                           "--emit-signal", sigdir])
        assert rc == 1  # dead host pages
        assert sig.leaver_hosts(sig.read_signals(sigdir)) == {1}


# -- report rendering -----------------------------------------------------
class TestElasticReport:
    def test_transition_summarized_and_rendered(self):
        from can_tpu.obs.report import format_report, summarize

        ev = {"ts": 1.0, "kind": "elastic.transition", "step": 3,
              "host_id": 0,
              "payload": {"epoch": 2, "steps_done": 5,
                          "processes_old": 2, "processes_new": 1,
                          "dp_old": 8, "dp_new": 4, "lr_scale": 0.5,
                          "remaining_items": 16,
                          "reason": "preemption"}}
        s = summarize([ev])
        assert s["elastic_transitions"] == 1
        assert s["elastic_last"]["dp_new"] == 4
        assert s["elastic_last"]["lr_scale"] == 0.5
        report = format_report(s)
        assert "elastic" in report
        assert "2proc/dp8 -> 1proc/dp4" in report
        assert "lr x0.5" in report

    def test_no_transitions_no_row(self):
        from can_tpu.obs.report import format_report, summarize

        s = summarize([])
        assert s["elastic_transitions"] == 0
        assert s["elastic_last"] is None
        assert "elastic" not in format_report(s)


# -- CLI integration ------------------------------------------------------
class TestElasticCli:
    def test_schedule_drift_guard_covers_elastic_only_checkpoints(
            self, tmp_path):
        """A preemption BEFORE the first epoch save leaves no integer
        step dir — only the elastic manifest + shrink checkpoint.  A
        cold restart with drifted schedule flags must still hit the
        pre-init ConfigDriftError (elastic is a world change, never a
        licence for schedule drift)."""
        from can_tpu.cli.train import main as train_main
        from can_tpu.utils.checkpoint import save_run_config

        ck = tmp_path / "ck"
        save_run_config(str(ck), {"lr": 1e-7, "lrf": 1.0, "epochs": 500,
                                  "batch_size": 1, "seed": 0,
                                  "syncBN": False, "bf16": False,
                                  "world_size": 8})
        el.save_manifest(str(ck), _manifest(epoch=0))
        # a syntactically valid (empty) ShanghaiTech layout: path checks
        # precede the drift guard, and both precede any runtime init
        for split in ("train", "test"):
            for leaf in ("images", "ground_truth"):
                os.makedirs(tmp_path / "d" / f"{split}_data" / leaf)
        with pytest.raises(SystemExit, match="config drift"):
            train_main(["--data_root", str(tmp_path / "d"),
                        "--init_checkpoint", str(ck),
                        "--epochs", "4"])

    def test_flag_validation(self):
        from can_tpu.cli.train import main as train_main

        with pytest.raises(SystemExit, match="elastic-check-every"):
            train_main(["--data_root", "/nonexistent",
                        "--elastic-check-every", "0"])

    def test_elastic_armed_run_trains_and_records_world(self, tmp_path):
        """A signal-free elastic-armed run is one quiet generation: the
        supervisor polls, nothing fires, training completes, and the
        saved run config carries this world's size (the drift guard's
        elastic key)."""
        from can_tpu.cli.train import main as train_main
        from can_tpu.data import make_synthetic_dataset
        from can_tpu.obs.report import read_events
        from can_tpu.utils.checkpoint import load_run_config

        root = tmp_path / "data"
        for split, n in (("train", 16), ("test", 8)):
            make_synthetic_dataset(os.path.join(str(root), f"{split}_data"),
                                   n, sizes=((64, 64),), seed=3)
        ck = str(tmp_path / "ck")
        rc = train_main(["--data_root", str(root), "--epochs", "1",
                         "--batch-size", "1", "--checkpoint-dir", ck,
                         "--platform", "cpu", "--num-workers", "0",
                         "--elastic-dir", str(tmp_path / "sig"),
                         "--elastic-check-every", "1",
                         "--telemetry-dir", str(tmp_path / "tel")])
        assert rc == 0
        cfg = load_run_config(ck)
        assert cfg["world_size"] == 8  # the 8-device test mesh
        # no signal ever fired: zero transitions, the epoch trained whole
        events = read_events(
            str(tmp_path / "tel" / "telemetry.host0.jsonl"))
        kinds = [e["kind"] for e in events]
        assert "elastic.transition" not in kinds
        assert "epoch" in kinds


# -- dp' mesh audit contracts + mutation ----------------------------------
class TestShrunkMeshAudit:
    def test_committed_contract_guards_the_shrunk_mesh(self):
        """The committed PROGRAM_CONTRACTS.json carries entries for the
        re-formed dp'=1 x sp=4 programs with the same packed-moments
        teeth as the full mesh: onepass one (2C+1,) psum per BN layer
        per pass, twopass none."""
        from can_tpu.analysis.hlo_audit import load_contract

        contract = load_contract("PROGRAM_CONTRACTS.json")
        one = contract["programs"]["train_step_syncbn_onepass_dp1"]
        two = contract["programs"]["train_step_syncbn_twopass_dp1"]
        assert one["packed_bn_reduces"] == 32  # 16 BN layers x 2 passes
        assert two.get("packed_bn_reduces", 0) == 0
        assert one["collectives"]["all_reduce"] < \
            two["collectives"]["all_reduce"]
        assert one["forbid_f64"] and one["forbid_host_calls"]

    def test_shrunk_programs_match_committed_contract(self):
        from can_tpu.analysis.hlo_audit import audit_programs, load_contract

        contract = load_contract("PROGRAM_CONTRACTS.json")
        violations = audit_programs(
            contract, ["train_step_syncbn_onepass_dp1",
                       "train_step_syncbn_twopass_dp1"])
        assert violations == []

    def test_transition_that_changes_collective_structure_goes_red(self):
        """The mutation: an elastic transition that re-forms the dp'
        step with a DIFFERENT collective structure (here: the twopass
        moments path where the contract pins onepass packing) must turn
        the audit red naming the invariant."""
        from can_tpu.analysis.hlo_audit import (
            check_facts,
            load_contract,
            program_facts,
        )

        contract = load_contract("PROGRAM_CONTRACTS.json")
        entry = contract["programs"]["train_step_syncbn_onepass_dp1"]
        mutated = program_facts("train_step_syncbn_twopass_dp1")
        mutated.name = "train_step_syncbn_onepass_dp1"
        violations = check_facts(entry, mutated)
        names = {v.invariant for v in violations}
        assert "packed_bn_reduces" in names
        assert any(v.invariant.startswith("collectives") for v in violations)
