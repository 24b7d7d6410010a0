"""Unit tests for the observability utilities (viz, logging).

These are exercised indirectly through the CLI drives (--show /
--show-index, MetricLogger lines); here their contracts are pinned
directly: inverse-normalisation round-trips (the reference's 0.255-vs-0.225
std typo, utils/train_eval_utils.py:92-95, is exactly the bug this would
catch), file outputs, and logger gating.
"""

import os

import numpy as np
import pytest

from can_tpu.data import normalize_host
from can_tpu.utils import MetricLogger, save_density_visualization


class TestViz:
    def test_writes_three_pngs(self, tmp_path):
        rng = np.random.default_rng(0)
        raw = (rng.random((32, 48, 3)) * 255).astype(np.uint8)
        img = normalize_host(raw)
        dmap = rng.random((4, 6, 1)).astype(np.float32)
        paths = save_density_visualization(img, dmap, dmap,
                                           str(tmp_path), tag="t")
        assert [p.split("_")[-1] for p in paths] == ["img.png", "gt.png",
                                                     "et.png"]
        for p in paths:
            assert (tmp_path / p.split("/")[-1]).stat().st_size > 0

    def test_normalisation_constants_and_inverse(self):
        """Pin the ImageNet constants (the reference's viz typo is std
        0.255 where blue is 0.225, utils/train_eval_utils.py:92-95) and
        check viz.py's inverse undoes the LIBRARY forward transform."""
        from can_tpu.data import IMAGENET_MEAN, IMAGENET_STD

        np.testing.assert_allclose(IMAGENET_MEAN, [0.485, 0.456, 0.406])
        np.testing.assert_allclose(IMAGENET_STD, [0.229, 0.224, 0.225])

        rng = np.random.default_rng(1)
        raw = (rng.random((8, 8, 3)) * 255).astype(np.uint8)
        normed = normalize_host(raw)  # the library forward
        # the exact inverse viz.py applies before rendering
        back = normed * IMAGENET_STD + IMAGENET_MEAN
        np.testing.assert_allclose(back, raw.astype(np.float32) / 255.0,
                                   atol=1e-6)


class TestMetricLogger:
    def test_stdout_lines_and_gating(self, capsys):
        log = MetricLogger(enabled=True)
        log.log({"loss": 1.5, "mae": 2.0}, step=3)
        out = capsys.readouterr().out
        assert "step 3" in out and "loss=1.5" in out and "mae=2" in out
        log.finish()

        quiet = MetricLogger(enabled=False)  # non-main processes
        quiet.log({"loss": 1.0}, step=0)
        assert capsys.readouterr().out == ""
        quiet.finish()

    def test_numpy_scalars_format_like_floats(self, capsys):
        """Fetched metrics arrive as np.float32/np.float64 scalars; they
        must hit the %.6g float path, not raw repr (satellite, this PR:
        np.float32(1/3) used to print as 0.33333334 or worse)."""
        log = MetricLogger(enabled=True)
        log.log({"a": np.float32(1.0) / 3, "b": np.float64(2.5),
                 "n": np.int64(7)}, step=0)
        out = capsys.readouterr().out
        assert "a=0.333333 " in out  # %.6g, not float32 repr
        assert "b=2.5" in out and "n=7" in out
        log.finish()

    def test_wandb_absent_degrades(self, capsys, monkeypatch):
        # force the absent-wandb path regardless of the environment:
        # requesting wandb must fall back to stdout, not crash (the
        # reference hard-requires wandb)
        import sys

        monkeypatch.setitem(sys.modules, "wandb", None)  # import -> ImportError
        log = MetricLogger(enabled=True, use_wandb=True)
        log.log({"x": 1.0})
        assert "x=1" in capsys.readouterr().out
        log.finish()


class TestCompileCache:
    """Where the cache lives is decided outside the program:
    JAX_COMPILATION_CACHE_DIR when set (and then no code sets a
    directory), else one fixed path inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        import jax

        names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        prev = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in prev.items():
            jax.config.update(n, v)

    def test_enable_creates_dir_and_sets_config(self, tmp_path, monkeypatch):
        import jax

        from can_tpu.utils import enable_compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = tmp_path / "xla_cache"
        got = enable_compilation_cache(str(d))
        assert got == str(d)
        assert d.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(d)

    def test_off_disables(self, monkeypatch):
        from can_tpu.utils import enable_compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compilation_cache("off") is None
        assert enable_compilation_cache("none") is None

    def test_default_dir_is_fixed_inside_checkout(self, monkeypatch,
                                                  tmp_path):
        """Unset: <repo>/.jax_cache, derived from the package location —
        not from ~, the cwd, a pid or the time (the path is part of the
        cache key)."""
        import can_tpu
        from can_tpu.utils import default_cache_dir

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(can_tpu.__file__)))
        monkeypatch.setenv("HOME", str(tmp_path))
        monkeypatch.chdir(tmp_path)
        assert default_cache_dir() == os.path.join(repo, ".jax_cache")
        assert default_cache_dir() == default_cache_dir()

    def test_env_var_set_means_code_sets_no_dir(self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: the directory is JAX's to read;
        every jax.config.update the call makes is recorded, and none of
        them may name a cache dir.  An explicit directory is refused;
        "off" still means cold."""
        import jax

        from can_tpu.utils import enable_compilation_cache

        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        # JAX snapshots the variable at import; stand in for that here
        jax.config.update("jax_compilation_cache_dir", env_dir)
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, val: (updates.append(name), real_update(name, val)))
        assert enable_compilation_cache() == env_dir
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            enable_compilation_cache(str(tmp_path / "elsewhere"))
        assert enable_compilation_cache("off") is None
        assert jax.config.jax_enable_compilation_cache is False
        assert "jax_compilation_cache_dir" not in updates


class TestStepTimer:
    """Edge cases load-bearing in bench entry points (satellite, this PR):
    the NaN-before-warmup contract and the misuse guard."""

    def test_mean_is_nan_before_skip_first(self):
        import math

        from can_tpu.utils import StepTimer

        t = StepTimer(skip_first=2)
        for _ in range(2):
            t.start()
            t.stop()
        assert math.isnan(t.mean)  # still inside the skip window
        t.start()
        t.stop()
        assert t.mean >= 0 and not math.isnan(t.mean)

    def test_stop_without_start_raises(self):
        import pytest

        from can_tpu.utils import StepTimer

        t = StepTimer()
        with pytest.raises(RuntimeError, match="before start"):
            t.stop()
        t.start()
        t.stop()
        with pytest.raises(RuntimeError, match="before start"):
            t.stop()  # double-stop is the same misuse

    def test_percentiles_and_shape_buckets(self):
        from can_tpu.utils import StepTimer

        t = StepTimer(skip_first=0)
        assert t.percentiles()["n"] == 0
        for i in range(10):
            t.start()
            t.stop(shape=(2, 8, 8, 3) if i % 2 else (2, 16, 8, 3))
        p = t.percentiles()
        assert p["n"] == 10
        assert 0 < p["p50_s"] <= p["p95_s"] <= p["max_s"]
        shapes = t.shape_summary()
        assert set(shapes) == {"(2, 8, 8, 3)", "(2, 16, 8, 3)"}
        assert all(rec["n"] == 5 for rec in shapes.values())

    def test_drain_window_resets(self):
        from can_tpu.utils import StepTimer

        t = StepTimer(skip_first=0)
        t.start()
        t.stop()
        assert len(t.drain_window()) == 1
        assert t.drain_window() == []  # drained
        assert t.percentiles()["n"] == 1  # reservoir keeps the sample


class TestEmitNullResult:
    def test_emits_valid_json_line(self, capsys):
        """The watchdog null-result line is parsed by the driver — it must
        be one json.loads-able line (satellite, this PR)."""
        import json

        from can_tpu.utils import emit_null_result

        emit_null_result("bench_img_per_s", unit="images/sec",
                         vs_baseline=None)()
        out = capsys.readouterr().out.strip()
        rec = json.loads(out)
        assert rec["metric"] == "bench_img_per_s"
        assert rec["value"] is None
        assert "unreachable" in rec["error"]
        assert rec["unit"] == "images/sec"

    def test_extra_kwargs_ride_along(self, capsys):
        import json

        from can_tpu.utils import emit_null_result

        emit_null_result("m", config={"batch": 16})()
        assert json.loads(capsys.readouterr().out)["config"] == {"batch": 16}


class TestStableRunId:
    def test_minted_then_reused(self, tmp_path):
        from can_tpu.utils.logging import _stable_run_id

        f = str(tmp_path / "ck" / "wandb_run_id.txt")
        rid = _stable_run_id(f)
        assert rid and len(rid) == 12
        # a resumed run reads the same id back (same wandb run continues)
        assert _stable_run_id(f) == rid

    def test_empty_file_remints(self, tmp_path):
        from can_tpu.utils.logging import _stable_run_id

        f = tmp_path / "id.txt"
        f.write_text("")
        assert _stable_run_id(str(f))


class TestMultihostMetadataGate:
    """parallel/runtime.py::_multihost_metadata_present (ADVICE r5): a bare
    coordinator var inherited from a stale pod session must NOT route a
    single-worker machine into the fatal split-brain branch."""

    def _present(self, monkeypatch, env):
        from can_tpu.parallel.runtime import _multihost_metadata_present

        for var in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
                    "TPU_WORKER_HOSTNAMES", "NUM_PROCESSES",
                    "JAX_NUM_PROCESSES", "TPU_WORKER_COUNT",
                    "MEGASCALE_NUM_SLICES"):
            monkeypatch.delenv(var, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        return _multihost_metadata_present()

    def test_clean_env_is_single_host(self, monkeypatch):
        assert not self._present(monkeypatch, {})

    def test_bare_coordinator_var_is_not_a_pod(self, monkeypatch):
        assert not self._present(
            monkeypatch, {"JAX_COORDINATOR_ADDRESS": "10.0.0.1:8476"})
        assert not self._present(
            monkeypatch, {"MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.1:8476"})

    def test_coordinator_plus_worker_count_is_a_pod(self, monkeypatch):
        assert self._present(monkeypatch,
                             {"JAX_COORDINATOR_ADDRESS": "10.0.0.1:8476",
                              "NUM_PROCESSES": "2"})
        assert self._present(monkeypatch,
                             {"JAX_COORDINATOR_ADDRESS": "10.0.0.1:8476",
                              "JAX_NUM_PROCESSES": "4"})
        assert self._present(monkeypatch,
                             {"MEGASCALE_COORDINATOR_ADDRESS": "x:1",
                              "MEGASCALE_NUM_SLICES": "4"})

    def test_coordinator_with_count_one_degrades(self, monkeypatch):
        assert not self._present(monkeypatch,
                                 {"JAX_COORDINATOR_ADDRESS": "x:1",
                                  "NUM_PROCESSES": "1"})

    def test_multi_hostname_list_is_a_pod_without_coordinator(self,
                                                              monkeypatch):
        assert self._present(monkeypatch,
                             {"TPU_WORKER_HOSTNAMES": "host-a,host-b"})
        assert not self._present(monkeypatch,
                                 {"TPU_WORKER_HOSTNAMES": "host-a"})

    def test_garbage_count_var_is_ignored(self, monkeypatch):
        assert not self._present(monkeypatch,
                                 {"JAX_COORDINATOR_ADDRESS": "x:1",
                                  "NUM_PROCESSES": "not-a-number"})


class TestSlurmRendezvous:
    """parallel/runtime.py::_slurm_rendezvous (VERDICT missing #3): derive
    the coordinator from SLURM_NTASKS + the first nodelist host at the
    fixed port; metadata that names a multi-task job but is incomplete is
    FATAL — never a silent single-process fallback.  Pure env-dict calls:
    no monkeypatching, no jax.distributed."""

    def _rv(self, env):
        from can_tpu.parallel.runtime import _slurm_rendezvous

        return _slurm_rendezvous(env)

    def test_full_metadata_derives_triple(self):
        from can_tpu.parallel.runtime import SLURM_COORDINATOR_PORT

        got = self._rv({"SLURM_NTASKS": "4",
                        "SLURM_JOB_NODELIST": "node[001-004]",
                        "SLURM_PROCID": "2"})
        assert got == (f"node001:{SLURM_COORDINATOR_PORT}", 4, 2)

    def test_port_keyed_on_job_id(self):
        # two concurrent jobs whose first node coincides must NOT share a
        # port (they would rendezvous into each other); every task of ONE
        # job derives the same offset without communicating
        from can_tpu.parallel.runtime import SLURM_COORDINATOR_PORT

        env = {"SLURM_NTASKS": "2", "SLURM_JOB_NODELIST": "node001",
               "SLURM_PROCID": "0"}
        a = self._rv(dict(env, SLURM_JOB_ID="123456"))
        b = self._rv(dict(env, SLURM_JOB_ID="123457"))
        assert a[0] == f"node001:{SLURM_COORDINATOR_PORT + 456}"
        assert a[0] != b[0]
        # same job id -> same address on every task
        assert a == self._rv(dict(env, SLURM_JOB_ID="123456",
                                  SLURM_PROCID="0"))

    def test_nodelist_forms(self):
        from can_tpu.parallel.runtime import _first_slurm_host

        assert _first_slurm_host("tpu-host003") == "tpu-host003"
        assert _first_slurm_host("a,b,c") == "a"
        assert _first_slurm_host("node[001-004]") == "node001"
        assert _first_slurm_host("node[7,9-12]") == "node7"
        # bracket group first, plain host after: the comma inside []
        # must not split the first entry
        assert _first_slurm_host("tpu[003-004,007],gpu2") == "tpu003"

    def test_absent_metadata_is_none(self):
        assert self._rv({}) is None
        # salloc shell: nodelist without a launched task — not a job
        assert self._rv({"SLURM_JOB_NODELIST": "node001"}) is None

    def test_single_task_job_degrades(self):
        assert self._rv({"SLURM_NTASKS": "1",
                         "SLURM_JOB_NODELIST": "node001",
                         "SLURM_PROCID": "0"}) is None

    def test_salloc_shell_degrades_with_notice(self, capsys):
        # salloc exports NTASKS/NODELIST but never PROCID (only srun sets
        # it, per task) — a shell inside a multi-task allocation is NOT a
        # launched task and must run single-process, loudly
        assert self._rv({"SLURM_NTASKS": "4",
                         "SLURM_JOB_NODELIST": "node[001-004]"}) is None
        assert "salloc" in capsys.readouterr().out

    def test_partial_metadata_is_fatal(self):
        import pytest

        # a LAUNCHED task (PROCID set) missing its nodelist: incomplete
        with pytest.raises(RuntimeError, match="incomplete"):
            self._rv({"SLURM_NTASKS": "4", "SLURM_PROCID": "0"})
        # a launched task id without a task count: incomplete, not absent
        with pytest.raises(RuntimeError, match="incomplete"):
            self._rv({"SLURM_PROCID": "3"})

    def test_garbage_values_are_fatal_not_silent(self):
        import pytest

        with pytest.raises(RuntimeError, match="SLURM_NTASKS"):
            self._rv({"SLURM_NTASKS": "many"})
        with pytest.raises(RuntimeError, match="SLURM_PROCID"):
            self._rv({"SLURM_NTASKS": "2",
                      "SLURM_JOB_NODELIST": "a,b",
                      "SLURM_PROCID": "zero"})
