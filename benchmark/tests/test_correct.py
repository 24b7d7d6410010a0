"""``correct`` has to come out false when it should: with the control (the
reference in the next lower precision, in the program's place) and with the
timed path broken underneath.  These drive a whole run at a size the CPU can
hold, skipping only the harness's look for a chip.  The serving limits are
the chip cells' own (ratios to bfloat16's own gap carry over); the training
limits on norm gaps are wider at 64x96 on the CPU, where a sound run reads
0.03 (0.0065 on the chip at 768x1024), and were set the same way from
readings at that size; the limit on the per-leaf gap ratio is the chip's
(on the CPU at 64x96: sound 1.01 to 1.11, int8 2.19 to 2.46 over seeds 7, 8,
9; on the chip 0.96 to 1.04 and 3.45 to 4.50)."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.harness import correct

TINY = os.path.join(os.path.dirname(__file__), "tinybench")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _run(name, tmp_path, **kw):
    return run.run_cell(name, 7, 0.5, False, root=str(tmp_path), require_chip=False,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"),
                        data_dir=TINY, **kw)


def _limits(config):
    return json.load(open(os.path.join(REAL, config + ".json")))["limits"]


def _tiny_limits(config):
    return json.load(open(os.path.join(TINY, "configs", config + ".json")))["limits"]


def test_tiny_cells_compare_the_numbers_the_chip_cells_compare():
    assert _tiny_limits("tiny-serve") == _limits("cannet-serve-bf16")
    assert _tiny_limits("tiny-train").keys() == _limits("cannet-train-bf16").keys()
    assert _tiny_limits("tiny-train")["grad1_leaf_gap_ratio"] == \
        _limits("cannet-train-bf16")["grad1_leaf_gap_ratio"]


@pytest.fixture(scope="module")
def sound_train(tmp_path_factory):
    return _run("tiny-train", tmp_path_factory.mktemp("train"),
                control_modes=("int8", "bf16params"))


def test_sound_training_run_is_correct(sound_train):
    assert sound_train["correct"] is True
    assert set(sound_train["numbers"]) >= {"loss_rel_step1", "loss_rel_step3",
                                           "grad1_norm_gap", "grad1_leaf_gap_ratio",
                                           "dparam_norm_gap"}


@pytest.mark.parametrize("mode,number", [("int8", "grad1_leaf_gap_ratio"),
                                         ("bf16params", "dparam_norm_gap")])
def test_training_control_is_not_correct(sound_train, mode, number):
    limits = _tiny_limits("tiny-train")
    assert correct.judge(sound_train["control"][mode], limits) is False
    assert sound_train["control"][mode][number] > limits[number]


def test_training_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    def freeze(probe):
        import jax

        real = probe.step
        # the real step donates its state; hand it a copy so `state` survives

        def step(state, batch):
            _, metrics = real(jax.tree.map(lambda x: x.copy(), state), batch)
            return state, metrics
        probe.step = step

    line = _run("tiny-train", tmp_path, break_path=freeze)
    assert line["correct"] is False
    assert line["numbers"]["dparam_norm_gap"] > _tiny_limits("tiny-train")["dparam_norm_gap"]


def test_training_step_that_leaves_out_part_of_the_batch_is_not_correct(tmp_path):
    def halve(probe):
        real = probe.step

        def step(state, batch):
            n = batch["sample_mask"].shape[0]
            keep = np.arange(n) < max(1, n // 2)
            batch = dict(batch, sample_mask=batch["sample_mask"] * keep)
            return real(state, batch)
        probe.step = step

    line = _run("tiny-train", tmp_path, break_path=halve)
    assert line["correct"] is False
    worst = max(v for k, v in line["numbers"].items() if k.startswith("loss_rel"))
    assert worst > _tiny_limits("tiny-train")["loss_rel"]


def test_sound_serving_run_is_correct_and_an_altered_answer_is_not(tmp_path):
    assert _run("tiny-serve", tmp_path)["correct"] is True

    def alter(engine):
        def corrupt(out):
            counts, density = out
            counts = np.array(counts)
            counts[0] += 1.0 + abs(float(counts[0]))
            return counts, density
        engine.corrupt = corrupt

    line = _run("tiny-serve", tmp_path, break_path=alter)
    assert line["correct"] is False


def test_serving_control_int8_is_not_correct(tmp_path):
    line = _run("tiny-serve", tmp_path, serve_dtype="int8")
    assert line["correct"] is False


def test_a_small_leaf_is_not_hidden_by_a_large_one():
    rng = np.random.default_rng(0)
    ref = {"big": rng.standard_normal(10_000) * 100.0, "small": rng.standard_normal(100),
           "other": rng.standard_normal(100)}
    yard = {k: v * (1 + 1e-3 * rng.standard_normal(v.shape)) for k, v in ref.items()}
    sound = {k: v * (1 + 1e-3 * rng.standard_normal(v.shape)) for k, v in ref.items()}
    # only the small leaf is computed badly: as one vector the trees still agree
    bad = dict(sound, small=ref["small"] * (1 + 2e-2 * rng.standard_normal(100)))
    cat = lambda t: np.concatenate([t["big"], t["small"], t["other"]])
    cos = 1 - cat(bad) @ cat(ref) / np.linalg.norm(cat(bad)) / np.linalg.norm(cat(ref))
    assert cos < 1e-6
    assert correct.leaf_gap_ratio(sound, ref, yard) == pytest.approx(1.0, abs=0.2)
    assert correct.leaf_gap_ratio(bad, ref, yard) > 5


@pytest.mark.parametrize("wrong", [0.0, float("nan"), 9.0])
def test_one_wrong_count_among_all_stands_out(wrong):
    rng = np.random.default_rng(1)
    ref = {"counts": list(rng.uniform(2.0, 3.0, 1000)), "mass": [3.0] * 1000,
           "densities": []}
    sound = [c + 0.02 * rng.standard_normal() for c in ref["counts"]]
    assert correct.serve_numbers(sound, [], ref, ref)["count_gap_mass"] < 0.05
    sound[500] = wrong
    assert correct.serve_numbers(sound, [], ref, ref)["count_gap_mass"] > 0.3
