"""The ``glm_serve`` driver (a language-model cell whose reference, weights
and work counts are found by name), its readers and its reduction, at the
tiny GLM preset on the CPU (``tinybench_glm/``: a ``BENCHMARK.json`` of its
own; ``tinybench_lm/`` stays K-EXAONE's)."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, flops_glm, spec, trace, trace_lm, trace_lm_any
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_glm")
HARNESS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "harness")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "glm-4.7-flash-pp8-serve-bf16.json")
CELL = "serve-glm-agent16k-closed"
CONTROLS = ("int8", "variant:scale_nope", "variant:no_kv_norm",
            "variant:rope_on_nope", "variant:unnormalised_topk",
            "variant:expert_zeroed")
SHARED = ("seg_median_req_per_s.serve", "batch_fill_pct.serve",
          "pad_ms_per_img.serve", "complete_ms_per_img.serve",
          "batcher_wait_pct.serve", "cycle_unnamed_pct.serve")
SPAN_AND_COUNTER = SHARED + ("expert_load_max_over_mean.lm",
                             "latent_cache_bytes_per_pos.lm",
                             "prefill_pad_token_pct.lm")
FROM_TRACE = ("prefill_device_ms_per_ktok.lm", "decode_device_ms_per_step.lm",
              "decode_step_roofline", "prefill_roofline")


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Prefill attention in blocks of 8: the bucket of 32 is four of them."""
    from can_tpu.models import glm_moe_lite

    monkeypatch.setattr(glm_moe_lite, "PREFILL_BLOCK", 8)


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-glm", 2**31 + 7, 0.5, trace_, root=str(tmp_path),
                        require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs", "tiny-glm.json")))["limits"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    from can_tpu.models import glm_moe_lite

    block, glm_moe_lite.PREFILL_BLOCK = glm_moe_lite.PREFILL_BLOCK, 8
    try:
        return _run(tmp_path_factory.mktemp("glm"), control_modes=CONTROLS)
    finally:
        glm_moe_lite.PREFILL_BLOCK = block


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


def test_decode_writing_a_position_late_is_not_correct(tmp_path):
    """The timed path broken underneath: every decode step writes its latent
    one position after the token's own."""
    from benchmark.tools import calibrate_glm

    try:
        line = _run(tmp_path, break_path=calibrate_glm.late_write)
    finally:
        calibrate_glm.late_write.undo()
    assert line["correct"] is False


def test_a_prefill_slice_written_at_the_wrong_slots_is_not_correct(tmp_path):
    from benchmark.tools import calibrate_lm

    line = _run(tmp_path, break_path=calibrate_lm.slice_offset(1))
    assert line["correct"] is False
    assert line["numbers"]["token_miss_share"] > _limits()["token_miss_share"]


def test_who_asks_for_logits_moves_on_by_the_traffic_s_slot_step():
    from benchmark.harness import drive_glm_serve, drive_lm_serve

    class Service:
        def __init__(self):
            self.asked = []

        def submit(self, tokens, *, max_new_tokens, want_logits):
            self.asked.append(want_logits)

    def asked(every, step, n):
        svc = Service()
        door = drive_glm_serve._Door(svc, every, step)
        for i in range(n):
            door.submit(None, max_new_tokens=1, want_logits=(i % 2 == 0))
        return [i for i, w in enumerate(svc.asked) if w]

    # the cell's (ISSUE 30): one a launch of 16, its slot moving on by 5
    traffic = json.load(open(os.path.join(
        os.path.dirname(TINY), os.pardir, "traffic", "agent-16k-128-closed.json")))
    assert (traffic["logits_every"], traffic["logits_slot_step"],
            traffic["logits_compared"], traffic["tokens_compared"]) == (16, 5, 4, 8)
    assert asked(16, 5, 80) == [0, 16 + 5, 32 + 10, 48 + 15, 64 + 4]
    # with K-EXAONE's step it is K-EXAONE's rule
    assert asked(64, 17, 512) == [i for i in range(512)
                                  if drive_lm_serve.wants_logits(i, 64)]
    # calibration: one launch, its four probes at slots 0, 5, 10, 15
    assert asked(4, 5, 16) == [0, 5, 10, 15]
    assert asked(0, 5, 16) == []


def test_the_benchmarks_own_weights_are_the_tree_the_program_reads():
    import inspect

    from benchmark.harness import weights_glm
    from can_tpu.models import glm_moe_lite as gm

    assert "can_tpu" not in inspect.getsource(weights_glm)
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-glm.json")))
    for cfg in (json.load(open(REAL)), tiny, dict(tiny, num_nextn_predict_layers=1)):
        assert weights_glm.shapes(cfg) == gm.param_shapes(
            gm.Glm4MoeLiteConfig.from_dict(cfg))
    import jax.numpy as jnp

    params = weights_glm.make_params(tiny, 2**31 + 3)
    assert params["layers"][1]["moe"]["bias"].dtype == jnp.float32
    assert params["layers"][0]["attn"]["wkv_b"].dtype == jnp.bfloat16
    assert abs(float(params["layers"][0]["attn"]["kv_norm"].astype(jnp.float32)
                     .mean()) - 1.0) < 0.2


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
    assert not set(FROM_TRACE) & set(line["metrics"])   # no device trace here
    # 3 layers x (16 + 8) numbers x 2 bytes: bfloat16 as served
    assert line["metrics"]["latent_cache_bytes_per_pos.lm"]["value"] == 144.0
    assert 0.0 < line["metrics"]["prefill_pad_token_pct.lm"]["value"] < 60.0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    cell = spec.load_cell("tiny-glm", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    ctx = {"cell": cell, "counters": {}, "trace": {}, "end_to_end": {}}
    assert spec.load_metric_reader("latent_cache_bytes_per_pos.lm")(ctx) is None
    ctx["counters"] = {"lm": {"cache_bytes": {"full": 4 * 44 * 960}}}
    assert spec.load_metric_reader("latent_cache_bytes_per_pos.lm")(ctx) == 960.0
    try:
        assert spec.load_metric_reader("prefill_pad_token_pct.lm")(ctx) is None
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "glm4_moe_lite")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_glm.params_by_part(cfg)
    norms = 6 * (2 * 2048 + 768 + 512) + 2048 + 5 * 64   # and the router's bias
    assert sum(p.values()) == 3_895_625_536 - norms
    assert flops_glm.cache_bytes_per_position(cfg) == 6912
    lengths = [12288] * 16
    pre = flops_glm.prefill(cfg, lengths, 16 * 12288 * 4 * 5.0)
    # ISSUE 30: 169 TFLOP in matrix products and 154 in causal scores and values
    assert pre["ops_total"] == pytest.approx(323e12, rel=0.03)
    step = flops_glm.decode_step(cfg, [12288 + 64] * 16)
    assert step["bytes_total"] == pytest.approx(6.4e9, rel=0.03)
    assert step["bytes"]["attention"] == pytest.approx(1.36e9 + 0.26e9, rel=0.05)


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_glm.decode_step(cfg, [12000] * 16)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_glm.least_seconds(step, peaks) == pytest.approx(7.8e-3, rel=0.05)
    pre = flops_glm.prefill(cfg, [12288] * 16, 16 * 12288 * 20.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s
    assert pre["ops_total"] / peaks.flops == pytest.approx(1.64, rel=0.03)


# -- the reduction ----------------------------------------------------------
def test_the_reduction_is_trace_lm_s_with_the_work_functions_handed_in():
    def body(name):
        text = open(os.path.join(HARNESS, name)).read()
        return text[text.index('"""', 3) + 3:]

    theirs = (body("trace_lm.py").replace("flops_lm.", "work_of.")
              .replace("from benchmark.harness import flops_lm\n", "")
              .replace("cfg: dict, peaks) -> dict:", "cfg: dict, peaks, work_of) -> dict:"))
    assert theirs == body("trace_lm_any.py")


def _events(launches, *, decode_ms=12.0, prefill_ms=800.0, gap_ms=1.0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[2,16384]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"]):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%fusion.2 = bf16[16,2048]{1,0} fusion()", t, decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    return trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                        marks=[("lm.prefill.dispatch", 0.0, 1e12, {})])


def _launch(steps=4):
    return {"slots": 16, "bucket": 16384, "valid": 16, "lengths": [12288] * 16,
            "slices": 8, "steps": steps, "held_prefill": 16 * 12288 * 20.0}


def test_reduction_with_this_model_s_work_functions():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launches = [_launch(), _launch(), _launch()]
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_glm)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(12.0)
    assert out["prefill_device_ms_per_ktok"] == pytest.approx(8 * 800.0 / (16 * 12.288))
    assert out["prefill_roofline_pct"] == pytest.approx(100 * 1.64 / 6.4, rel=0.03)
    assert 60 < out["decode_step_roofline_pct"] < 70
    assert out["idle_gaps"][0][0] == "lm.prefill.dispatch"
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*prefill"):
        trace_lm_any.reduce(_events(launches, prefill_ms=100.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_glm)


def test_real_cell_is_declared_and_its_files_are_found():
    cell = spec.load_cell(CELL)
    assert cell.config["driver"] == "glm_serve" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(SPAN_AND_COUNTER) | set(FROM_TRACE)
    for n in names:
        assert callable(spec.load_metric_reader(n))
    t = cell.traffic
    assert (t["clients"], t["max_new_tokens"], t["prompt_tokens"]) == (32, 128, [8192, 16384])
    # the published widths, all 64 experts, the whole vocabulary
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["moe_intermediate_size"], c["intermediate_size"],
            c["num_experts_per_tok"], c["n_routed_experts"], c["vocab_size"]) == (
        2048, 20, 768, 512, 192, 64, 256, 1536, 10240, 4, 64, 154880)
    assert c["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    import importlib

    for key in ("reference", "weights", "work"):
        importlib.import_module(c[key])
