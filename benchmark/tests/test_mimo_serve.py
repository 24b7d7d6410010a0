"""MiMo-V2-Flash's cell's files on the CPU at the tiny preset
(``tinybench_mimo/``: a ``BENCHMARK.json`` of its own with the real cell's
metric set): the ``lfm2_serve`` driver as it stands, the four new readers, the
work counts, the controls."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import (device, flops_mimo_v2_flash, spec, trace,
                               trace_lm, trace_lm_any)
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_mimo")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "mimo-v2-flash-ep16-serve-bf16.json")
CELL = "serve-mimo-doc8k-closed"
CONTROLS = ("int8", "bf16-1", "variant:no_sink", "variant:sink_value",
            "variant:window_minus_1", "variant:window_plus_1",
            "variant:full_groups_of_window", "variant:rope_whole_head",
            "variant:thetas_swapped", "variant:no_value_scale",
            "variant:unnormalised_topk", "variant:expert_zeroed")
WEAK = ("variant:bias_in_weights",)
SPAN_AND_COUNTER = (
    "seg_median_req_per_s.serve", "batch_fill_pct.serve", "pad_ms_per_img.serve",
    "complete_ms_per_img.serve", "batcher_wait_pct.serve",
    "cycle_unnamed_pct.serve", "expert_load_max_over_mean.lm",
    "expert_local_share_pct.lm", "full_cache_bytes_per_pos.lm",
    "ring_cache_bytes_per_slot.lm", "prefill_pad_token_pct.lm",
    "decode_experts_read_pct.lm")
NEW = ("decode_attn_window_ms_per_step.lm", "prefill_attn_window_ms_per_ktok.lm",
       "full_cache_bytes_per_pos.lm", "ring_cache_bytes_per_slot.lm")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-mimo", 2**31 + 7, 0.5, trace_, root=str(tmp_path),
                        require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs", "tiny-mimo.json")))["limits"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("mimo"),
                control_modes=CONTROLS + WEAK)


def test_tiny_cell_runs_on_the_cpu_and_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"req_per_s", "setup_s"}
    assert sound["metrics"]["req_per_s"]["value"] > 0
    assert set(sound["numbers"]) == {"logit_gap_ratio", "token_miss_share",
                                     "routing_diff_share", "compiles_in_window"}
    assert sound["numbers"]["compiles_in_window"] == 0.0


@pytest.mark.parametrize("mode", CONTROLS)
def test_every_control_breaks_a_limit(sound, mode):
    control = sound["control"][mode]
    assert any(control[k] > _limits()[k] for k in control), control


@pytest.mark.parametrize("mode", WEAK)
def test_the_weak_controls_are_computed(sound, mode):
    """A bias of 0.05 added to normalised weights: small at this size; what
    it reads at the published sizes is the chip's to say (PERF.md section
    2)."""
    assert sound["control"][mode]["logit_gap_ratio"] > sound["numbers"][
        "logit_gap_ratio"] * 0.5


@pytest.mark.parametrize("name", ["ring_rolled", "late_write"])
def test_the_program_broken_underneath_is_not_correct(tmp_path, name):
    from benchmark.tools import calibrate_mimo_v2_flash as cal

    breaker = cal.PROGRAM_BREAKS[name]
    try:
        line = _run(tmp_path, break_path=breaker)
    finally:
        breaker.undo()
    assert line["correct"] is False, line["numbers"]


class CpuEnv(run.Env):
    """No chip and no device trace: the program's spans and counters alone."""

    def start_trace(self):
        return None

    def stop_trace(self):
        pass


def test_a_traced_run_reports_every_span_and_counter_metric(tmp_path, monkeypatch):
    recorder.uninstall()
    monkeypatch.setattr(run, "Env", CpuEnv)
    try:
        line = _run(tmp_path, True)
    finally:
        recorder.uninstall()
    assert line["correct"] is True
    for m in SPAN_AND_COUNTER:
        assert line["metrics"][m]["value"] > 0.0, m
    # the full layers' prefill has ONE form (the scanned one): the span
    # notes none and the fused kernel's share is not this cell's to report
    assert "prefill_fused_attention_pct.lm" not in line["metrics"]
    # no device trace here: nothing that reads one, the two window readers
    # among them
    assert not any(n.startswith(("decode_attn", "decode_device", "prefill_device",
                                 "prefill_attn", "prefill_roofline"))
                   for n in line["metrics"])
    # 2 full layers x 2 heads x (24 + 16) x 2 bytes; 5 window layers x 4 heads
    # x 40 x 2 bytes x 8 slots
    assert line["metrics"]["full_cache_bytes_per_pos.lm"]["value"] == 2 * 2 * 40 * 2
    assert line["metrics"]["ring_cache_bytes_per_slot.lm"]["value"] == 5 * 4 * 40 * 2 * 8
    # rank 1 holds 8 of 32 experts: about a quarter of the choices land here
    assert 10 < line["metrics"]["expert_local_share_pct.lm"]["value"] < 45
    # the batched form reads every held expert (a CPU never skips)
    assert line["metrics"]["decode_experts_read_pct.lm"]["value"] == 100.0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program that records no ``program.scopes`` span and reports no
    cache (the parent's would not even build the model) and without a
    trace: None, not an error."""
    cell = spec.load_cell("tiny-mimo", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    ctx = {"cell": cell, "counters": {}, "trace": {}, "end_to_end": {}}
    try:
        for name in NEW:
            assert spec.load_metric_reader(name)(ctx) is None
        # a cache of other kinds (GLM's latent): still nothing
        ctx["counters"] = {"lm": {"cache_bytes": {"latent": 1}}}
        for name in NEW[2:]:
            assert spec.load_metric_reader(name)(ctx) is None
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "mimo_v2_flash")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_mimo_v2_flash.params_by_part(cfg)
    left_out = 7 * 2 * 4096 + 4096 + 6 * 256          # norms, the bias
    assert sum(p.values()) == 3_429_955_392 - left_out
    assert p["attention_full"] == 2 * 89_128_960
    assert p["attention_window"] == 5 * 94_371_904
    assert flops_mimo_v2_flash.full_bytes_per_position(cfg) == 5_120
    assert flops_mimo_v2_flash.ring_bytes_per_slot(cfg) == 3_276_800
    step = flops_mimo_v2_flash.decode_step(cfg, [5823 + 128] * 16)
    by = step["bytes"]
    # attention's weights 1.30 GB + the full layers' keys and values up to the
    # context + five rings; 39.8% of the held experts' 4.83 GB
    assert by["attention"] == pytest.approx(
        1.30e9 + 16 * 5951 * 5120 + 16 * 3_276_800, rel=0.01)
    assert by["experts"] == pytest.approx(0.398 * 4.83e9 + 12.6e6, rel=0.01)
    assert by["dense_mlp"] == pytest.approx(0.403e9, rel=0.01)
    assert step["bytes_total"] == pytest.approx(4.33e9, rel=0.01)
    lengths = [5823] * 16
    pre = flops_mimo_v2_flash.prefill(cfg, lengths, 16 * 5823 * 6 * 8 / 16)
    per_token = pre["ops_total"] / (16 * 5823)
    assert per_token == pytest.approx(2.13e9, rel=0.01)
    assert pre["ops"]["attention"] > pre["ops"]["dense_mlp"] > pre["ops"]["experts"]
    # a window layer's scores stop at 128 keys: a prompt of 5,823 costs a
    # window layer 1 / 23 of what a full layer's causal half costs
    one = flops_mimo_v2_flash.dims(cfg)
    assert (one["windows"], one["fulls"], one["window"]) == (5, 2, 128)


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_mimo_v2_flash.decode_step(cfg, [5951] * 16)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_mimo_v2_flash.least_seconds(step, peaks) == pytest.approx(5.29e-3,
                                                                          rel=0.02)
    pre = flops_mimo_v2_flash.prefill(cfg, [5823] * 16, 16 * 5823 * 3.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s


# -- the reduction ----------------------------------------------------------
def _events(launches, *, decode_ms=9.0, prefill_ms=1000.0, gap_ms=1.0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[4,8192]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"]):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%fusion.2 = bf16[16,4096]{1,0} fusion()", t, decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    return trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                        marks=[("lm.prefill.dispatch", 0.0, 1e12, {})])


def test_reduction_with_this_model_s_work_functions():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launch = {"slots": 16, "bucket": 8192, "valid": 16, "lengths": [5823] * 16,
              "slices": 4, "steps": 4, "held_prefill": 16 * 5823 * 3.0}
    launches = [launch] * 3
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_mimo_v2_flash)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(9.0)
    assert 55 < out["decode_step_roofline_pct"] < 60
    assert 23 < out["prefill_roofline_pct"] < 27
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*decode step"):
        trace_lm_any.reduce(_events(launches, decode_ms=5.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_mimo_v2_flash)


def test_real_cell_is_declared_and_its_files_are_found():
    import importlib

    cell = spec.load_cell(CELL)
    assert cell.config["driver"] == "lfm2_serve" and cell.chips == 1
    assert cell.traffic_name == "doc-8k-256-probe8-closed"
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW) | {"decode_step_roofline", "prefill_roofline",
                       "expert_local_share_pct.lm", "decode_experts_read_pct.lm",
                       "decode_attn_cache_ms_per_step.lm",
                       "prefill_dispatch_ms_per_ktok.lm",
                       "decode_unscoped_pct.lm",
                       "prefill_unscoped_pct.lm"} <= set(names)
    assert not any(n.startswith(("latent_", "state_", "decode_ssm", "prefill_ssm",
                                 "decode_conv", "prefill_conv",
                                 "prefill_fused_attention"))
                   for n in names)
    for n in names:
        assert callable(spec.load_metric_reader(n))
    # the tiny benchmark reports the real cell's metric set
    tiny = spec.load_cell("tiny-mimo", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    assert [m["name"] for m in tiny.per_layer] == names
    t = cell.traffic
    assert (t["clients"], t["max_new_tokens"], t["prompt_tokens"], t["length_seed"],
            t["distinct_prompts"], t["segment_requests"]) == (
        32, 256, [4096, 8192], 20261001, 16, 16)
    assert (t["logits_every"], t["logits_slot_step"], t["logits_compared"],
            t["tokens_compared"], t["traced_launches"]) == (4, 5, 8, 8, 3)
    # who asks for logits (``drive_glm_serve._Door``), as ISSUE 40 named the
    # cell: slots 0, 5, 10, 15 of every launch, one in each prefill slice of
    # four, each at another place in its slice.  The prompts move on by one a
    # launch, so the second launch's slot 15 carries the first one's slot 0's
    # prompt again: the eight probes of a kind are SEVEN independent readings
    # (the traffic's note reckons a sound run's refusal odds for seven)
    from benchmark.harness.drive_lm_serve import prompt_of
    e, s = t["logits_every"], t["logits_slot_step"]
    asks = [i for i in range(32) if i % e == (s * (i // e)) % e]
    assert asks == [0, 5, 10, 15, 16, 21, 26, 31]
    assert sorted(i // 4 for i in asks[:4]) == sorted(i % 4 for i in asks[:4]) == [
        0, 1, 2, 3]
    prompts = list(range(16))
    assert len({prompt_of(prompts, i) for i in asks}) == 7
    assert prompt_of(prompts, 0) == prompt_of(prompts, 31)
    c = cell.config
    assert (c["max_batch"], c["queue_capacity"], c["length_ladder"],
            c["max_new_tokens"], c["max_wait_ms"], c["prefill_slice"]) == (
        16, 64, [8192], 256, 100.0, 4)
    for key in ("reference", "weights", "work"):
        importlib.import_module(c[key])
