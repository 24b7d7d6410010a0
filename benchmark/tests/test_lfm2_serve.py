"""The LFM2-MoE cell's files on the CPU at the tiny preset
(``tinybench_lfm2/``: a ``BENCHMARK.json`` of its own with the real cell's
metric set): the ``lfm2_serve`` driver (``glm_serve`` and the one counter it
leaves out), the two ``conv.`` readers, the work counts, the controls."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, flops_lfm2_moe, spec, trace, trace_lm, trace_lm_any
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_lfm2")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "lfm2-24b-a2b-ep8-serve-bf16.json")
CELL = "serve-lfm2-chat-closed"
CONTROLS = ("int8", "bf16-1", "variant:no_gate_b", "variant:no_gate_c",
            "variant:no_qk_norm", "variant:unnormalised_topk")
WEAK = ("variant:bias_in_weights", "variant:expert_zeroed")
SPAN_AND_COUNTER = (
    "seg_median_req_per_s.serve", "batch_fill_pct.serve", "pad_ms_per_img.serve",
    "complete_ms_per_img.serve", "batcher_wait_pct.serve",
    "cycle_unnamed_pct.serve", "expert_load_max_over_mean.lm",
    "expert_local_share_pct.lm", "state_cache_bytes_per_slot.lm",
    "prefill_pad_token_pct.lm")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-lfm2", 2**31 + 7, 0.5, trace_, root=str(tmp_path),
                        require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs", "tiny-lfm2.json")))["limits"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("lfm2"),
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
    """A bias of 0.05 added to normalised weights, and one of rank 1's four
    experts zeroed: small at this size; what they read at the published
    sizes is the chip's to say (PERF.md section 2)."""
    assert sound["control"][mode]["logit_gap_ratio"] > sound["numbers"][
        "logit_gap_ratio"] * 0.5


@pytest.mark.parametrize("name", ["tail_late", "tail_after_padding",
                                  "late_write"])
def test_the_program_broken_underneath_is_not_correct(tmp_path, name):
    from benchmark.tools import calibrate_lfm2_moe as cal

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
    # no device trace here: nothing that reads one, the conv. readers among them
    assert not any(n.startswith(("decode_", "prefill_device", "prefill_conv",
                                 "prefill_roofline")) for n in line["metrics"])
    # 6 conv layers x 64 channels x 2 inputs x 2 bytes
    assert line["metrics"]["state_cache_bytes_per_slot.lm"]["value"] == 6 * 256
    # rank 1 holds 4 of 16 experts: about a quarter of the choices land here
    assert 10 < line["metrics"]["expert_local_share_pct.lm"]["value"] < 45


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program that records no ``program.scopes`` span (the parent's
    would not even build the model) and without a trace: None, not an error."""
    cell = spec.load_cell("tiny-lfm2", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    ctx = {"cell": cell, "counters": {}, "trace": {}, "end_to_end": {}}
    try:
        for name in ("decode_conv_ms_per_step.lm", "prefill_conv_ms_per_ktok.lm"):
            assert spec.load_metric_reader(name)(ctx) is None
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "lfm2_moe")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_lfm2_moe.params_by_part(cfg)
    left_out = 40 * 2 * 2048 + 2048 + 10 * 128 + 38 * 64   # norms, the bias
    assert sum(p.values()) == 3_761_333_888 - left_out
    assert flops_lfm2_moe.state_bytes_per_slot(cfg) == 245_760
    assert flops_lfm2_moe.kv_bytes_per_position(cfg) == 20_480
    step = flops_lfm2_moe.decode_step(cfg, [832 + 128] * 64)
    by = step["bytes"]
    # experts 5.65 GB of a step's 8.8; the 30 mixers 1.04; keys and values 1.26
    assert by["experts"] == pytest.approx(5.66e9, rel=0.01)
    assert by["conv"] == pytest.approx(1.04e9, rel=0.01)
    assert by["attention"] == pytest.approx(0.21e9 + 64 * 960 * 20_480, rel=0.01)
    assert step["bytes_total"] == pytest.approx(8.76e9, rel=0.01)
    pre = flops_lfm2_moe.prefill(cfg, [832] * 64, 64 * 832 * 38 * 4 / 8)
    per_token = pre["ops_total"] / (64 * 832)
    assert per_token == pytest.approx(1.91e9, rel=0.01)
    assert pre["ops"]["conv"] > pre["ops"]["experts"] > pre["ops"]["dense_mlp"]


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_lfm2_moe.decode_step(cfg, [960] * 64)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_lfm2_moe.least_seconds(step, peaks) == pytest.approx(10.7e-3,
                                                                     rel=0.02)
    pre = flops_lfm2_moe.prefill(cfg, [832] * 64, 64 * 832 * 19.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s


# -- the reduction ----------------------------------------------------------
def _events(launches, *, decode_ms=20.0, prefill_ms=200.0, gap_ms=1.0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[8,1024]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"]):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%fusion.2 = bf16[64,2048]{1,0} fusion()", t, decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    return trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                        marks=[("lm.prefill.dispatch", 0.0, 1e12, {})])


def test_reduction_with_this_model_s_work_functions():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launch = {"slots": 64, "bucket": 1024, "valid": 64, "lengths": [832] * 64,
              "slices": 8, "steps": 4, "held_prefill": 64 * 832 * 19.0}
    launches = [launch] * 3
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_lfm2_moe)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(20.0)
    assert 50 < out["decode_step_roofline_pct"] < 56
    assert 28 < out["prefill_roofline_pct"] < 34
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*decode step"):
        trace_lm_any.reduce(_events(launches, decode_ms=9.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_lfm2_moe)


def test_real_cell_is_declared_and_its_files_are_found():
    import importlib

    cell = spec.load_cell(CELL)
    assert cell.config["driver"] == "lfm2_serve" and cell.chips == 1
    assert cell.traffic_name == "chat-1k-256-probe16-closed"
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert {"decode_conv_ms_per_step.lm", "prefill_conv_ms_per_ktok.lm",
            "decode_step_roofline", "prefill_roofline",
            "expert_local_share_pct.lm", "state_cache_bytes_per_slot.lm",
            "decode_attn_cache_ms_per_step.lm", "prefill_dispatch_ms_per_ktok.lm",
            "decode_unscoped_pct.lm", "prefill_unscoped_pct.lm"} <= set(names)
    assert not any(n.startswith(("latent_", "prefill_fused_", "decode_ssm",
                                 "prefill_ssm", "decode_experts_read"))
                   for n in names)
    for n in names:
        assert callable(spec.load_metric_reader(n))
    t = cell.traffic
    assert (t["clients"], t["max_new_tokens"], t["prompt_tokens"],
            t["length_seed"]) == (128, 256, [640, 1024], 20260928)
    assert (t["logits_every"], t["logits_compared"], t["tokens_compared"]) == (
        8, 16, 32)
    # the schedule of lengths is the other two chat cells'
    chat = spec.load_cell("serve-exaone-chat-closed").traffic
    assert all(chat[k] == t[k] for k in ("prompt_tokens", "length_seed",
                                         "distinct_prompts", "clients"))
    # who asks for logits (``drive_glm_serve._Door``): one slot of every
    # prefill slice of 8, each at another place within its slice, the same
    # slots in every launch; the prompts move on by one a launch, and no
    # probe of the second launch carries a prompt the first one's did (with
    # ``drive_lm_serve.wants_logits``' 17, slots 0, 9, ..., 63, the second
    # launch's slot 63 would carry the first one's slot 0's prompt again)
    from benchmark.harness.drive_lm_serve import prompt_of
    e, s = t["logits_every"], t["logits_slot_step"]
    asks = [i for i in range(128) if i % e == (s * (i // e)) % e]
    assert asks[:8] == [0, 11, 22, 25, 36, 47, 50, 61]
    assert [i - 64 for i in asks[8:]] == asks[:8]
    assert sorted(i // 8 for i in asks[:8]) == list(range(8))
    assert sorted(i % 8 for i in asks[:8]) == list(range(8))
    prompts = list(range(64))
    assert len({prompt_of(prompts, i) for i in asks}) == 16
    c = cell.config
    assert (c["max_batch"], c["queue_capacity"], c["length_ladder"],
            c["max_new_tokens"], c["max_wait_ms"]) == (64, 256, [1024], 256, 100.0)
    for key in ("reference", "weights", "work"):
        importlib.import_module(c[key])
