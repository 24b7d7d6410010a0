"""End-to-end CLI + checkpoint tests: train a couple of epochs on synthetic
data on the 8-device CPU mesh, resume, then evaluate with the test CLI."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu.data import make_synthetic_dataset
from can_tpu.models import cannet_init
from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer
from can_tpu.utils import CheckpointManager, StepTimer


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("cli_data")
    for split, n, seed in (("train", 8, 0), ("test", 4, 1)):
        make_synthetic_dataset(os.path.join(str(root), f"{split}_data"), n,
                               sizes=((64, 64), (64, 96)), seed=seed)
    return str(root)


class TestCheckpointManager:
    def test_save_restore_roundtrip(self, tmp_path):
        params = cannet_init(jax.random.key(0))
        opt = make_optimizer(make_lr_schedule(1e-7))
        state = create_train_state(params, opt)
        state = state.replace(step=state.step + 5)

        mgr = CheckpointManager(str(tmp_path / "ck"))
        assert mgr.save(0, state, mae=50.0)
        mgr.wait()

        fresh = create_train_state(cannet_init(jax.random.key(1)), opt)
        restored = mgr.restore(fresh)
        assert int(restored.step) == 5
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            restored.params, state.params)
        mgr.close()

    def test_best_policy_keeps_lowest_mae(self, tmp_path):
        params = cannet_init(jax.random.key(0))
        opt = make_optimizer(make_lr_schedule(1e-7))
        state = create_train_state(params, opt)
        mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=1)
        mgr.save(0, state, mae=60.0)
        mgr.save(1, state, mae=40.0)  # best
        mgr.save(2, state, mae=55.0)
        mgr.wait()
        assert mgr.best_epoch() == 1
        mgr.close()

    def test_latest_survives_best_retention(self, tmp_path):
        # code-review r5: BestN-only retention deleted the LATEST save
        # whenever its MAE wasn't top-N, so a crash-resume on a plateaued
        # run rolled training back to an old epoch.  The joint policy
        # must keep the newest checkpoint alongside the N best, and it
        # must be restorable.
        # The joint policy needs orbax's preservation_policy API; on older
        # orbax CheckpointManager degrades to best-N retention (documented
        # in utils/checkpoint.py) and this guarantee doesn't hold.
        pytest.importorskip("orbax.checkpoint.checkpoint_managers",
                            reason="orbax too old for preservation_policy")
        params = cannet_init(jax.random.key(0))
        opt = make_optimizer(make_lr_schedule(1e-7))
        state = create_train_state(params, opt)
        mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=2)
        for ep, mae in enumerate([50.0, 30.0, 20.0, 40.0, 60.0, 70.0]):
            mgr.save(ep, state.replace(step=state.step + ep), mae=mae)
        mgr.wait()
        assert mgr.latest_epoch() == 5          # survived retention
        assert mgr.best_epoch() == 2
        assert mgr.best_metric() == 20.0        # resume carries this forward
        restored = mgr.restore(state)           # latest by default
        assert int(restored.step) == 5
        mgr.close()

    def test_eval_interval_validated_at_parse_time(self):
        from can_tpu.cli.train import main

        with pytest.raises(SystemExit, match="eval-interval"):
            main(["--data_root", "/nonexistent", "--eval-interval", "0"])


class TestResumeConfigGuard:
    """VERDICT weak #4: resuming with drifted schedule-bearing flags used
    to silently reshape the cosine schedule the restored optimizer state
    was built for.  The run config is persisted beside the checkpoints and
    checked BEFORE any runtime work on warm-start."""

    def test_round_trip_and_drift_check(self, tmp_path):
        from can_tpu.utils import (
            ConfigDriftError,
            check_resume_config,
            load_run_config,
            save_run_config,
        )

        cfg = {"lr": 1e-7, "lrf": 1.0, "epochs": 500, "batch_size": 4,
               "seed": 0, "syncBN": False, "bf16": True}
        save_run_config(str(tmp_path), cfg)
        assert load_run_config(str(tmp_path)) == cfg
        # identical config: no drift, continues
        assert check_resume_config(cfg, dict(cfg)) == []
        # a changed --epochs is rejected, naming the key and both values
        changed = dict(cfg, epochs=600)
        with pytest.raises(ConfigDriftError, match="epochs: 500 -> 600"):
            check_resume_config(cfg, changed)
        # ... unless explicitly allowed, in which case the drifted keys
        # come back for the CLI to announce
        assert check_resume_config(cfg, changed, allow=True) == ["epochs"]
        # pre-guard checkpoint dirs resume unchecked (None, not an error)
        assert load_run_config(str(tmp_path / "nope")) is None

    def test_cli_rejects_changed_epochs_and_continues_identical(
            self, data_root, tmp_path):
        from can_tpu.cli.train import main as train_main
        from can_tpu.utils import load_run_config
        from can_tpu.utils.checkpoint import has_checkpoint

        ckdir = str(tmp_path / "ck_guard")
        base = ["--data_root", data_root, "--batch-size", "1",
                "--lr", "1e-7", "--seed", "0",
                "--checkpoint-dir", ckdir,
                "--max-steps-per-epoch", "1"]
        # leg 1: a real run leaves a checkpoint AND its run config
        assert train_main(base + ["--epochs", "1"]) == 0
        assert has_checkpoint(ckdir)
        resume = base + ["--init_checkpoint", ckdir]
        # changed --epochs vs the checkpoint's run: rejected
        with pytest.raises(SystemExit, match="epochs"):
            train_main(resume + ["--epochs", "3"])
        # identical config: the resume proceeds
        assert train_main(resume + ["--epochs", "1"]) == 0
        assert load_run_config(ckdir)["epochs"] == 1

    def test_guard_skips_configs_with_no_checkpoint(self, tmp_path,
                                                    data_root):
        # a run that wrote its config then crashed before the first save
        # has no restored schedule to protect: its cold restart must NOT
        # demand --allow-config-change
        from can_tpu.cli.train import main as train_main
        from can_tpu.utils import save_run_config
        from can_tpu.utils.checkpoint import has_checkpoint

        ckdir = str(tmp_path / "ck_crashed")
        save_run_config(ckdir, {"lr": 1e-7, "lrf": 1.0, "epochs": 2,
                                "batch_size": 1, "seed": 0,
                                "syncBN": False, "bf16": False})
        assert not has_checkpoint(ckdir)
        assert train_main(["--data_root", data_root, "--batch-size", "1",
                           "--lr", "1e-7", "--seed", "0",
                           "--checkpoint-dir", ckdir,
                           "--init_checkpoint", ckdir,
                           "--max-steps-per-epoch", "1",
                           "--epochs", "1"]) == 0


class TestTrainCLI:
    def test_train_eval_resume(self, data_root, tmp_path):
        from can_tpu.cli.train import main as train_main
        from can_tpu.cli.test import main as test_main

        ckdir = str(tmp_path / "ckpt")
        argv = ["--data_root", data_root, "--epochs", "2",
                "--batch-size", "1", "--lr", "1e-7",
                "--checkpoint-dir", ckdir, "--seed", "0"]
        assert train_main(argv) == 0
        assert os.path.isdir(ckdir)
        ck = CheckpointManager(ckdir)
        assert ck.latest_epoch() == 1
        ck.close()

        # resume for one more epoch from the saved state; the longer
        # --epochs is schedule drift vs the checkpoint's run config, so
        # it must be explicitly allowed (the guard's rejection path is
        # pinned in TestResumeConfigGuard)
        argv_resume = ["--data_root", data_root, "--epochs", "3",
                       "--batch-size", "1", "--lr", "1e-7",
                       "--checkpoint-dir", ckdir,
                       "--init_checkpoint", ckdir, "--seed", "0",
                       "--allow-config-change"]
        assert train_main(argv_resume) == 0
        ck = CheckpointManager(ckdir)
        assert ck.latest_epoch() == 2
        ck.close()

        # evaluation CLI reads the same checkpoints
        assert test_main(["--data_root", data_root,
                          "--checkpoint-dir", ckdir,
                          "--show-index", "0",
                          "--out-dir", str(tmp_path / "viz")]) == 0
        assert any(f.endswith(".png") for f in os.listdir(tmp_path / "viz"))

    def test_telemetry_dir_records_every_event_kind(self, data_root,
                                                    tmp_path):
        """Acceptance (this PR): a 2-epoch synthetic run with
        --telemetry-dir writes a parseable per-host JSONL containing >=1
        event of each kind — compile, step_window, stall, memory,
        heartbeat, epoch — and tools/telemetry_report.py summarizes it."""
        import json

        from can_tpu import obs
        from can_tpu.cli.test import main as test_main
        from can_tpu.cli.train import main as train_main

        tdir = str(tmp_path / "telemetry")
        ckdir = str(tmp_path / "ckpt_tel")
        argv = ["--data_root", data_root, "--epochs", "2",
                "--batch-size", "1", "--lr", "1e-7",
                "--checkpoint-dir", ckdir, "--seed", "0",
                "--telemetry-dir", tdir,
                "--telemetry-heartbeat-s", "0.2"]
        assert train_main(argv) == 0
        path = os.path.join(tdir, "telemetry.host0.jsonl")
        events = [json.loads(l) for l in open(path)]  # every line parses
        kinds = {e["kind"] for e in events}
        assert {"compile", "step_window", "stall", "memory", "heartbeat",
                "epoch", "data.planner"} <= kinds, kinds
        for e in events:
            assert set(e) == {"ts", "kind", "step", "host_id", "payload"}
        # planner gauges ride the bus once per epoch, with the realized
        # program count cross-checking the plan (r8)
        pl = [e for e in events if e["kind"] == "data.planner"]
        assert len(pl) == 2
        assert pl[0]["payload"]["program_count"] >= 1
        assert pl[0]["payload"]["realized_programs"] >= 1
        # epoch events carry the wandb-bound scalars (the MetricLogger
        # adapter forwards exactly these)
        ep = [e for e in events if e["kind"] == "epoch"]
        assert len(ep) == 2 and "train_loss" in ep[0]["payload"]
        assert "mae" in ep[-1]["payload"]
        # the report summarizes without error and sees real steps
        summary = obs.summarize(events)
        assert summary["steps"] > 0
        assert summary["recompiles"] >= 1
        assert summary["step_p95_s"] is not None

        # the eval CLI writes the same schema to the same layout
        tdir2 = str(tmp_path / "telemetry_eval")
        assert test_main(["--data_root", data_root,
                          "--checkpoint-dir", ckdir,
                          "--telemetry-dir", tdir2,
                          "--telemetry-heartbeat-s", "0.2"]) == 0
        ev = obs.read_events(os.path.join(tdir2, "telemetry.host0.jsonl"))
        ekinds = {e["kind"] for e in ev}
        assert {"compile", "step_window", "stall", "memory", "heartbeat",
                "epoch"} <= ekinds, ekinds
        assert any(e["kind"] == "epoch" and "mae" in e["payload"]
                   for e in ev)

    def test_trace_steps_flag_validation(self, data_root):
        from can_tpu.cli.train import main as train_main

        with pytest.raises(SystemExit, match="START:STOP"):
            train_main(["--data_root", data_root, "--epochs", "1",
                        "--trace-steps", "nope"])
        with pytest.raises(SystemExit, match="profile-dir"):
            train_main(["--data_root", data_root, "--epochs", "1",
                        "--trace-steps", "0:2"])

    def test_syncbn_train_then_eval(self, data_root, tmp_path):
        """BN-variant end to end through both CLIs: --syncBN trains the
        real BatchNorm model (running stats checkpointed with the state),
        and the eval CLI restores the same variant. The reference's flag is
        a no-op (its model has no BN layers, SURVEY §2); a break anywhere
        in the batch_stats -> Orbax -> restore chain fails here."""
        from can_tpu.cli.test import main as test_main
        from can_tpu.cli.train import main as train_main

        ckdir = str(tmp_path / "ck_bn")
        argv = ["--data_root", data_root, "--epochs", "1",
                "--batch-size", "1", "--syncBN",
                "--checkpoint-dir", ckdir, "--seed", "0"]
        assert train_main(argv) == 0
        assert test_main(["--data_root", data_root, "--checkpoint-dir",
                          ckdir, "--syncBN"]) == 0
        # the case --sp exists for: a BN checkpoint visualized H-sharded
        # (used to silently fall back to a single-device forward)
        viz = tmp_path / "viz_bn_sp"
        assert test_main(["--data_root", data_root, "--checkpoint-dir",
                          ckdir, "--syncBN", "--sp", "2",
                          "--show-index", "0", "--out-dir", str(viz)]) == 0
        assert any(f.endswith(".png") for f in os.listdir(viz))

    def test_bn_impl_flag(self, data_root, tmp_path):
        """--bn-impl (r10): default is the one-pass moments path; twopass
        stays selectable end to end (the bit-compatible A/B anchor); the
        pallas variant is rejected on the multi-device GSPMD dp step
        (no partitioning rule — it needs --sp or a single device)."""
        from can_tpu.cli.train import main as train_main, parse_args

        assert parse_args(["--data_root", "x"]).bn_impl == "onepass"
        ckdir = str(tmp_path / "ck_bn_twopass")
        argv = ["--data_root", data_root, "--epochs", "1",
                "--batch-size", "1", "--syncBN", "--bn-impl", "twopass",
                "--checkpoint-dir", ckdir, "--seed", "0",
                "--max-steps-per-epoch", "2"]
        assert train_main(argv) == 0
        # the conftest mesh is dp=8: pallas on the GSPMD dp path must be
        # refused with the actionable message, BEFORE any training
        with pytest.raises(SystemExit, match="pallas"):
            train_main(["--data_root", data_root, "--epochs", "1",
                        "--batch-size", "1", "--syncBN",
                        "--bn-impl", "pallas",
                        "--checkpoint-dir", str(tmp_path / "ck_bn_pl")])

    def test_explicit_split_roots(self, data_root, tmp_path):
        """VisDrone-style layouts: images and density maps in unrelated
        trees via explicit per-split roots (reference hardcodes such a
        pair, train.py:54-57)."""
        from can_tpu.cli.test import main as test_main
        from can_tpu.cli.train import main as train_main

        ckdir = str(tmp_path / "ck_roots")
        argv = ["--train-image-root", os.path.join(data_root, "train_data", "images"),
                "--train-gt-root", os.path.join(data_root, "train_data", "ground_truth"),
                "--test-image-root", os.path.join(data_root, "test_data", "images"),
                "--test-gt-root", os.path.join(data_root, "test_data", "ground_truth"),
                "--epochs", "1", "--batch-size", "1",
                "--max-steps-per-epoch", "1",
                "--checkpoint-dir", ckdir, "--seed", "0"]
        assert train_main(argv) == 0
        assert test_main(["--image-root",
                          os.path.join(data_root, "test_data", "images"),
                          "--gt-root",
                          os.path.join(data_root, "test_data", "ground_truth"),
                          "--checkpoint-dir", ckdir]) == 0
        # half-specified roots and missing data_root fail fast
        with pytest.raises(SystemExit, match="both"):
            train_main(["--train-image-root", "/tmp/x", "--epochs", "1"])
        with pytest.raises(SystemExit, match="data_root"):
            train_main(["--epochs", "1"])

    def test_spatial_mode_smoke(self, data_root, tmp_path):
        """Maximal flag composition: spatial parallelism x remat x bf16 x
        u8 transfer, through BOTH CLIs (every advertised capability in one
        program — no pairwise guards, unlike round 1)."""
        from can_tpu.cli.train import main as train_main
        from can_tpu.cli.test import main as test_main

        ckdir = str(tmp_path / "ck_sp")
        argv = ["--data_root", data_root, "--epochs", "1",
                "--batch-size", "2", "--sp", "4", "--remat", "--bf16",
                "--u8-input", "--checkpoint-dir", ckdir,
                "--max-steps-per-epoch", "1", "--seed", "0"]
        assert train_main(argv) == 0
        # spatial-parallel EVAL through the test CLI (UCF-QNRF config):
        # same checkpoint, H sharded 4-ways per replica
        assert test_main(["--data_root", data_root, "--checkpoint-dir", ckdir,
                          "--sp", "4", "--batch-size", "2", "--bf16",
                          "--u8-input"]) == 0


def test_step_timer_fences():
    t = StepTimer(skip_first=1)
    for _ in range(3):
        t.start()
        x = jnp.ones((100, 100)) @ jnp.ones((100, 100))
        t.stop(x)
    assert t.mean > 0


class TestDeterminism:
    def test_resume_equals_straight_run(self, tmp_path):
        """checkpoint -> restore -> continue == training straight through
        (full-state checkpoints; the reference loses optimizer momentum and
        the epoch counter, SURVEY §5)."""
        import jax
        from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
        from can_tpu.train import (create_train_state, make_lr_schedule,
                                   make_optimizer, train_one_epoch)
        from tests.test_train import random_batch, tiny_apply, tiny_init

        mesh = make_mesh(jax.devices()[:8])
        opt = make_optimizer(make_lr_schedule(1e-8, world_size=8))
        params = tiny_init(jax.random.key(3))
        rng = np.random.default_rng(11)
        batches = [random_batch(rng) for _ in range(4)]
        step = make_dp_train_step(tiny_apply, opt, mesh, donate=False)
        put = lambda b: make_global_batch(b, mesh)

        s_straight = create_train_state(jax.tree.map(jnp.array, params), opt)
        for ep in range(2):
            s_straight, _ = train_one_epoch(step, s_straight, batches,
                                            put_fn=put, epoch=ep,
                                            show_progress=False)

        s_a = create_train_state(jax.tree.map(jnp.array, params), opt)
        s_a, _ = train_one_epoch(step, s_a, batches, put_fn=put, epoch=0,
                                 show_progress=False)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(0, s_a, mae=1.0)
        mgr.wait()
        s_b = mgr.restore(create_train_state(
            jax.tree.map(jnp.array, params), opt))
        mgr.close()
        s_b, _ = train_one_epoch(step, s_b, batches, put_fn=put, epoch=1,
                                 show_progress=False)

        assert int(s_b.step) == int(s_straight.step)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_b.params, s_straight.params)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_b.opt_state, s_straight.opt_state)

    def test_same_seed_reproduces_cli_run(self, data_root, tmp_path):
        """Two CLI runs with the same seed produce identical checkpoints
        (the reference seeds with time.time(), train.py:66)."""
        import jax
        from can_tpu.cli.train import main as train_main
        from can_tpu.models import cannet_init
        from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer

        outs = []
        for tag in ("a", "b"):
            ck = str(tmp_path / f"ck_{tag}")
            assert train_main(["--data_root", data_root, "--epochs", "1",
                               "--batch-size", "1", "--checkpoint-dir", ck,
                               "--seed", "42"]) == 0
            opt = make_optimizer(make_lr_schedule(1e-7))
            state = create_train_state(cannet_init(jax.random.key(42)), opt)
            mgr = CheckpointManager(ck)
            outs.append(mgr.restore(state))
            mgr.close()
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), outs[0].params, outs[1].params)


class TestRematPolicy:
    """VERDICT r3 item 3: per-bucket remat — jax.checkpoint only where the
    activation estimate would overflow HBM, so small buckets keep the
    full-speed backward while huge ones fit at all."""

    # the v5e the calibration points were measured on (bytes_limit from
    # its OOM dump: "Used 16.97G of 15.75G hbm") — PINNED so these tests
    # don't flip on hosts with different device memory (advisor r4)
    V5E_HBM = int(15.75 * 2 ** 30)

    def test_estimator_matches_measured_fit_boundary(self):
        from can_tpu.cli.common import activation_bytes

        hbm = self.V5E_HBM
        # measured on the ~16 GiB v5e: these trained fine (r3/r4) ...
        assert activation_bytes(16, 576, 768, bf16=True) < 0.80 * hbm
        assert activation_bytes(8, 1016, 1024, bf16=True) < 0.80 * hbm
        # ... and this OOM'd with AND without remat (r4 dump: 16.97 GiB)
        assert activation_bytes(16, 1016, 1024, bf16=True) > 0.92 * hbm
        # f32 doubles the footprint
        assert (activation_bytes(4, 256, 256, bf16=False)
                == 2 * activation_bytes(4, 256, 256, bf16=True))

    def test_pixel_cap_admits_known_fits_rejects_known_oom(self):
        from can_tpu.cli.common import max_launch_pixels

        cap = max_launch_pixels(bf16=True, hbm_bytes=self.V5E_HBM)
        assert 16 * 576 * 768 <= cap      # headline config
        assert 8 * 1016 * 1024 <= cap     # biggest bucket at b8 (fits)
        assert 16 * 768 * 1024 <= cap     # dominant bench cell at b16
        assert 16 * 1016 * 1024 > cap     # the measured OOM

    def test_hbm_spec_fallback_by_device_kind(self, monkeypatch):
        # regression: a v5e PJRT client that returned no memory_stats
        # silently disabled the pixel cap AND auto-remat -> the b16 x
        # 1016x1024 launch compiled at 16.97 GiB and OOM'd the chip.
        # The spec table keeps the fits-in-HBM machinery alive on such
        # clients.
        import can_tpu.cli.common as common
        from can_tpu.cli.common import (
            _PJRT_SPEC_DERATE,
            UnknownDeviceKindError,
            hbm_bytes_for_device_kind,
            max_launch_pixels,
        )

        # ADVICE r5: spec values are derated by the typical PJRT
        # reservation (the r5 v5e OOM dump showed 15.75 GiB usable of the
        # 16 GiB spec) — spec > bytes_limit always, so handing the planner
        # raw spec bytes overpromises

        def spec(gib):
            return int((gib << 30) * _PJRT_SPEC_DERATE)

        assert hbm_bytes_for_device_kind("TPU v5 lite") == spec(16)
        assert hbm_bytes_for_device_kind("TPU v5litepod-16") == spec(16)
        assert hbm_bytes_for_device_kind("TPU v5e") == spec(16)
        assert hbm_bytes_for_device_kind("TPU v5p") == spec(95)
        # real v5p clients report bare "TPU v5" (v5e always says lite/e)
        assert hbm_bytes_for_device_kind("TPU v5") == spec(95)
        assert hbm_bytes_for_device_kind("TPU v4") == spec(32)
        # lite/inference variants must NOT inherit the full part's HBM
        assert hbm_bytes_for_device_kind("TPU v4i") == spec(8)
        assert hbm_bytes_for_device_kind("TPU v4 lite") == spec(8)
        assert hbm_bytes_for_device_kind("TPU v3") == spec(16)
        assert hbm_bytes_for_device_kind("cpu") is None
        assert hbm_bytes_for_device_kind("Fancy NPU 9000") is None
        # the derate stays under every real bytes_limit seen (15.75/16 =
        # 0.984 on v5e) without rejecting configurations that fit
        assert 0.9 < _PJRT_SPEC_DERATE < 0.984
        # the spec-derived cap must reject the measured OOM launch and
        # admit the known fits, same as the bytes_limit-derived one
        cap = max_launch_pixels(
            bf16=True, hbm_bytes=hbm_bytes_for_device_kind("TPU v5 lite"))
        assert 16 * 1016 * 1024 > cap
        assert 8 * 1016 * 1024 <= cap
        assert 16 * 768 * 1024 <= cap

        # the pure kind->bytes maps answer None for a kind they do not
        # know; on platform "tpu" that is an ERROR in both device
        # lookups, never "no HBM cap" / "no peaks" — even when the
        # client does report a bytes_limit
        class UnknownTpu:
            platform = "tpu"
            device_kind = "TPU v99 quantum"

            def memory_stats(self):
                return {"bytes_limit": 123}

        monkeypatch.setattr(common.jax, "local_devices",
                            lambda: [UnknownTpu()])
        with pytest.raises(UnknownDeviceKindError, match="v99 quantum"):
            common.device_memory_bytes()
        with pytest.raises(UnknownDeviceKindError, match="v99 quantum"):
            common.local_device_peaks()

    def test_device_memory_bytes_spec_fallback_branch(self, monkeypatch):
        # drive device_memory_bytes() itself through the stats-less-TPU
        # branch (the pure kind->bytes map is covered above): a device
        # that reports no memory_stats but is a known TPU kind must get
        # the spec size; an unknown TPU kind raises, never a guess
        import can_tpu.cli.common as common

        class FakeDev:
            platform = "tpu"

            def __init__(self, kind, stats=None):
                self.device_kind = kind
                self._stats = stats

            def memory_stats(self):
                return self._stats

        monkeypatch.setattr(common.jax, "local_devices",
                            lambda: [FakeDev("TPU v5 lite")])
        assert common.device_memory_bytes() == int(
            (16 << 30) * common._PJRT_SPEC_DERATE)
        # a reported bytes_limit always wins over the spec table
        monkeypatch.setattr(
            common.jax, "local_devices",
            lambda: [FakeDev("TPU v5 lite", {"bytes_limit": 123})])
        assert common.device_memory_bytes() == 123
        assert common.device_memory_sources() == (
            123, int((16 << 30) * common._PJRT_SPEC_DERATE))
        monkeypatch.setattr(common.jax, "local_devices",
                            lambda: [FakeDev("TPU v99 quantum")])
        with pytest.raises(common.UnknownDeviceKindError):
            common.device_memory_bytes()
        # a backend that fails to come up is a failure, not "no ceiling"
        def boom():
            raise RuntimeError("backend init failed")

        monkeypatch.setattr(common.jax, "local_devices", boom)
        with pytest.raises(RuntimeError, match="backend init failed"):
            common.device_memory_bytes()

    def test_no_fictitious_memory_on_cpu(self):
        # CPU backends report no bytes_limit: the cap and auto-remat must
        # disable rather than run off an invented 16 GiB (code-review r4)
        from can_tpu.cli.common import (
            device_memory_bytes,
            make_remat_policy,
            max_launch_pixels,
        )

        if device_memory_bytes() is None:
            assert max_launch_pixels(bf16=True) is None
            auto = make_remat_policy("auto", global_batch=64, bf16=True)
            assert not auto((4096, 4096))

    def test_policy_modes(self):
        from can_tpu.cli.common import make_remat_policy

        on = make_remat_policy("on", global_batch=1, bf16=True)
        off = make_remat_policy("off", global_batch=16, bf16=True)
        assert on((64, 64)) and not off((2048, 2048))
        auto = make_remat_policy("auto", global_batch=16, bf16=True,
                                 hbm_bytes=self.V5E_HBM)
        assert not auto((576, 768))
        assert auto((1016, 1024))
        # the remat band sits just under the pixel cap: the dominant bench
        # cell at b16 (12.6 Mpx, known fit) keeps the fast backward
        assert not auto((768, 1024))
        # remnant sub-batches pass their smaller actual size: a big-shape
        # straggler at batch 2 fits without remat
        assert not auto((1016, 1024), batch=2)

    def test_per_device_scaling_with_shards(self):
        # ADVICE r4 (medium): the footprint is per-DEVICE — a launch
        # sharded over dp*sp devices puts 1/shards of its pixels on each.
        # The global-pixel cap must scale by shards, and the remat policy
        # must divide its estimate by shards, or dp>1 meshes cap launches
        # dp x too small and over-remat.
        from can_tpu.cli.common import make_remat_policy, max_launch_pixels

        cap1 = max_launch_pixels(bf16=True, hbm_bytes=self.V5E_HBM)
        cap4 = max_launch_pixels(bf16=True, hbm_bytes=self.V5E_HBM,
                                 shards=4)
        assert cap4 == 4 * cap1
        # b64 x 1016x1024 on a dp=4 pod = the known per-device fit (b16
        # OOMs single-chip, b8 fits; 64/4 = 16 per device is the OOM, so
        # use b32 -> 8 per device: fits)
        assert 32 * 1016 * 1024 <= cap4
        assert 64 * 1016 * 1024 > cap4
        auto1 = make_remat_policy("auto", global_batch=16, bf16=True,
                                  hbm_bytes=self.V5E_HBM)
        auto4 = make_remat_policy("auto", global_batch=64, bf16=True,
                                  hbm_bytes=self.V5E_HBM, shards=4)
        # same per-device work as the single-chip remat trigger: global
        # b64 over 4 devices = b16 per device -> still remats ...
        assert auto1((1016, 1024)) and auto4((1016, 1024))
        # ... but global b16 over 4 devices = b4 per device -> must NOT
        # (the old global-vs-one-device compare over-triggered here)
        auto4b = make_remat_policy("auto", global_batch=16, bf16=True,
                                   hbm_bytes=self.V5E_HBM, shards=4)
        assert not auto4b((1016, 1024))

    def test_agreed_hbm_single_process(self):
        # ws=1 path: agreement is a no-op and must equal local detection
        from can_tpu.cli.common import (
            agreed_device_memory_bytes,
            device_memory_bytes,
        )

        assert agreed_device_memory_bytes() == device_memory_bytes()

    def test_flag_parsing(self):
        from can_tpu.cli.train import parse_args

        assert parse_args([]).remat == "auto"
        assert parse_args(["--remat"]).remat == "on"
        assert parse_args(["--remat", "off"]).remat == "off"
        # bare --remat followed by another flag (the maximal-composition
        # smoke invocation) still means "on"
        args = parse_args(["--remat", "--bf16"])
        assert args.remat == "on" and args.bf16


class TestDeviceWatchdog:
    """utils.device_watchdog: the unreachable-backend fail-fast (r4
    incident — jax.devices() can block forever when the accelerator link
    dies)."""

    def test_disarm_path(self):
        from can_tpu.utils import await_devices

        assert len(await_devices(30)) >= 1  # CPU backend answers fast

    def test_bench_device_refuses_a_backend_that_is_not_a_tpu(
            self, monkeypatch, capsys):
        """The bench entry points' gate: a first device that is not a TPU
        is an exit (2), not a fallback — unless the CPU was requested
        explicitly; and every result gets the device triple."""
        import can_tpu.utils.profiling as prof

        # tier-1 requests the CPU (conftest): the smoke mode passes
        assert prof.requested_platform() == "cpu"
        triple = prof.bench_device()
        assert triple["platform"] == "cpu" and triple["device_count"] >= 1
        assert set(triple) == {"platform", "device_kind", "device_count"}
        # nothing requested, and what came up is the CPU: a machine whose
        # TPU failed to initialise — refuse to time it
        monkeypatch.setattr(prof, "requested_platform", lambda: "")
        with pytest.raises(SystemExit) as exc:
            prof.bench_device()
        assert exc.value.code == 2
        assert "not a TPU" in capsys.readouterr().err

    def test_pallas_interpret_follows_the_requested_platform(
            self, monkeypatch):
        """A TPU request can never end up interpreted, whatever backend
        is live; the CPU request interprets; no request -> what came up."""
        import can_tpu.utils.profiling as prof

        monkeypatch.setattr(prof, "requested_platform", lambda: "tpu")
        assert prof.pallas_interpret() is False  # live backend: the CPU
        monkeypatch.setattr(prof, "requested_platform", lambda: "cpu")
        assert prof.pallas_interpret() is True
        monkeypatch.setattr(prof, "requested_platform", lambda: "")
        assert prof.pallas_interpret() is True   # the CPU is what came up

    def test_fires_and_exits_3(self):
        # firing path needs its own process (the watchdog os._exit's)
        import subprocess
        import sys

        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import time\n"
            "from can_tpu.utils import device_watchdog\n"
            "device_watchdog(1.0)\n"
            "time.sleep(30)\n"  # simulate a hung backend acquisition
            "print('should never get here')\n"
        )
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=25)
        assert proc.returncode == 3, (proc.returncode, proc.stderr)
        assert "watchdog" in proc.stderr
        assert "should never" not in proc.stdout

    def test_on_timeout_emits_before_exit(self):
        # the timing tools (tools/launch_cost_probe.py, ablate_mfu.py) use
        # this to leave a machine-readable null result instead of a bare
        # rc=3
        import subprocess
        import sys

        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import time\n"
            "from can_tpu.utils import device_watchdog\n"
            "device_watchdog(1.0, on_timeout=lambda: "
            "print('{\"value\": null}', flush=True))\n"
            "time.sleep(30)\n"
        )
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=25)
        assert proc.returncode == 3, (proc.returncode, proc.stderr)
        assert '"value": null' in proc.stdout
        # a broken callback must not mask the exit
        code_bad = code.replace("print('{\"value\": null}', flush=True)",
                                "1 / 0")
        proc = subprocess.run([sys.executable, "-c", code_bad],
                              capture_output=True, text=True, timeout=25)
        assert proc.returncode == 3, (proc.returncode, proc.stderr)

    def test_disarms_on_exception(self):
        # a backend that RAISES (refused connection) must not leave the
        # timer to kill the caller's fallback path later (code-review
        # r4).  Run in a subprocess and drive await_devices itself with
        # jax.devices monkeypatched to raise: if the finally-disarm
        # regresses, the timer os._exit(3)s the child (not pytest) and
        # the 'survived' marker never prints.
        import subprocess
        import sys

        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import time\n"
            "import can_tpu.utils.profiling as prof\n"
            "prof.jax.devices = lambda: (_ for _ in ()).throw("
            "RuntimeError('refused'))\n"
            "try:\n"
            "    prof.await_devices(1.0)\n"
            "except RuntimeError as e:\n"
            "    assert 'refused' in str(e)\n"
            "time.sleep(1.5)\n"  # a still-armed timer would exit 3 here
            "print('survived')\n"
        )
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=25)
        assert proc.returncode == 0, (proc.returncode, proc.stderr)
        assert "survived" in proc.stdout


class TestLaunchCostAuto:
    def test_resolve_fixed_and_auto(self):
        from can_tpu.cli.common import resolve_launch_cost_px

        assert resolve_launch_cost_px("2.0") == pytest.approx(2e6)
        assert resolve_launch_cost_px("0.05") == pytest.approx(5e4)
        # auto measures this host's dispatch overhead: non-negative, and
        # on a local CPU backend far below the 2 Mpx CLI default
        v = resolve_launch_cost_px("auto")
        assert 0 <= v < 2e6

    def test_cli_accepts_auto_and_validates_at_parse_time(self):
        from can_tpu.cli.test import parse_args as eval_parse
        from can_tpu.cli.train import parse_args

        assert parse_args([]).launch_cost_mpx == 2.0
        assert parse_args(["--launch-cost-mpx", "auto"]).launch_cost_mpx == "auto"
        assert eval_parse(["--data_root", "/tmp",
                           "--launch-cost-mpx", "auto"]).launch_cost_mpx == "auto"
        # a typo'd value fails AT PARSE TIME (before any multi-host
        # rendezvous), not as a raw ValueError mid-run
        with pytest.raises(SystemExit):
            parse_args(["--launch-cost-mpx", "2.o"])
