"""The ``hybrid_serve`` driver (a language-model cell whose model has no
expert layer and keeps a recurrent state), its readers and its work counts,
at the tiny Falcon-H1 preset on the CPU (``tinybench_falcon_h1/``: a
``BENCHMARK.json`` of its own)."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, flops_falcon_h1, spec, trace, trace_lm, trace_lm_any
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_falcon_h1")
HARNESS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "harness")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "falcon-h1-34b-pp12-serve-bf16.json")
CELL = "serve-falconh1-chat-closed"
CONTROLS = ("int8", "variant:norm_all_channels", "variant:no_key_multiplier",
            "variant:no_ssm_multipliers")
SHARED = ("seg_median_req_per_s.serve", "batch_fill_pct.serve",
          "pad_ms_per_img.serve", "complete_ms_per_img.serve",
          "batcher_wait_pct.serve", "cycle_unnamed_pct.serve")
SPAN_AND_COUNTER = SHARED + ("prefill_pad_token_pct.lm",
                             "state_cache_bytes_per_slot.lm",
                             "prefill_ssm_chunked_pct.lm")
FROM_TRACE = ("prefill_device_ms_per_ktok.lm", "decode_device_ms_per_step.lm",
              "decode_step_roofline", "prefill_roofline")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-falcon-h1", 2**31 + 7, 0.5, trace_,
                        root=str(tmp_path), require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs",
                                       "tiny-falcon-h1.json")))["limits"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("falcon"),
                control_modes=CONTROLS + ("variant:state_bf16",))


def test_tiny_cell_runs_on_the_cpu_and_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"req_per_s", "setup_s"}
    assert sound["metrics"]["req_per_s"]["value"] > 0
    assert set(sound["numbers"]) == {"logit_gap_ratio", "token_miss_share",
                                     "routing_diff_share", "compiles_in_window"}
    assert sound["numbers"]["compiles_in_window"] == 0.0
    assert sound["numbers"]["routing_diff_share"] == 0.0   # no expert layer


@pytest.mark.parametrize("mode", CONTROLS)
def test_every_control_breaks_a_limit(sound, mode):
    control = sound["control"][mode]
    assert any(control[k] > _limits()[k] for k in control), control


def test_the_state_rounded_at_every_position_is_computed_as_a_control(sound):
    """At 12 new tokens the rounding of the state is under bfloat16's own
    noise: the control is computed and reported, and what it reads at 256
    steps and the published sizes is the chip's to say (PERF.md section 2)."""
    assert "logit_gap_ratio" in sound["control"]["variant:state_bf16"]


@pytest.mark.parametrize("name", ["state_after_padding", "tail_late",
                                  "late_write"])
def test_the_program_broken_underneath_is_not_correct(tmp_path, name):
    from benchmark.tools import calibrate_falcon_h1 as cal

    breaker = cal.PROGRAM_BREAKS[name]
    try:
        line = _run(tmp_path, break_path=breaker)
    finally:
        breaker.undo()
    assert line["correct"] is False, line["numbers"]


def test_the_state_kept_in_bfloat16_halves_what_the_counter_reads(tmp_path,
                                                                 monkeypatch):
    from benchmark.tools import calibrate_falcon_h1 as cal

    recorder.uninstall()
    monkeypatch.setattr(run, "Env", CpuEnv)
    try:
        line = _run(tmp_path, True, break_path=cal.state_bf16)
    finally:
        recorder.uninstall()
    # 2 layers x (6 x 8 x 16 x 2 + 112 x 3 x 2) bytes
    assert line["metrics"]["state_cache_bytes_per_slot.lm"]["value"] == 2 * (
        1536 + 672)


def test_a_prefill_slice_written_at_the_wrong_slots_is_not_correct(tmp_path):
    from benchmark.tools import calibrate_lm

    line = _run(tmp_path, break_path=calibrate_lm.slice_offset(1))
    assert line["correct"] is False


def test_the_benchmarks_own_weights_are_the_tree_the_program_reads():
    import inspect

    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import weights_falcon_h1 as w
    from can_tpu.models import falcon_h1 as fh

    assert "can_tpu" not in inspect.getsource(w).split('"""', 2)[2]
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-falcon-h1.json")))
    for cfg in (json.load(open(REAL)), tiny):
        assert w.shapes(cfg) == fh.param_shapes(fh.FalconH1Config.from_dict(cfg))
    params = w.make_params(tiny, 2**31 + 3)
    mixer = params["layers"][1]["mixer"]
    assert mixer["in_proj"].dtype == jnp.bfloat16 == mixer["A_log"].dtype
    a = np.exp(np.asarray(mixer["A_log"], np.float32))
    assert ((a >= 0.99) & (a <= 16.1)).all()
    # each projection over the multiplier that follows it: keys of order one
    std = float(np.std(np.asarray(params["layers"][0]["attn"]["wk"], np.float32)))
    assert std == pytest.approx(64 ** -0.5 / (0.9 * 0.011), rel=0.1)
    # in_proj by column group: dt's columns over ssm_in x ssm_multipliers[4]
    dt_cols = np.asarray(mixer["in_proj"], np.float32)[:, -6:]
    assert float(np.std(dt_cols)) == pytest.approx(64 ** -0.5 / (0.25 * 0.35),
                                                   rel=0.2)


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
    # 2 layers x (6 x 8 x 16 float32 + 112 x 3 bfloat16)
    assert line["metrics"]["state_cache_bytes_per_slot.lm"]["value"] == 2 * (
        3072 + 672)
    assert line["metrics"]["prefill_ssm_chunked_pct.lm"]["value"] == 100.0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent's program (no ``state`` kind, no ``ssm`` on a span) and
    in a cell of another model: None, not an error."""
    cell = spec.load_cell("tiny-falcon-h1", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    ctx = {"cell": cell, "counters": {}, "trace": {}, "end_to_end": {}}
    read = spec.load_metric_reader("state_cache_bytes_per_slot.lm")
    assert read(ctx) is None
    ctx["counters"] = {"lm": {"cache_bytes": {"full": 4 * 44 * 960}}}
    assert read(ctx) is None
    ctx["counters"] = {"lm": {"cache_bytes": {"full": 1, "state": 4 * 100}}}
    assert read(ctx) == 100.0
    try:
        assert spec.load_metric_reader("prefill_ssm_chunked_pct.lm")(ctx) is None
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "falcon_h1")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_falcon_h1.params_by_part(cfg)
    left_out = 6 * (2 * 5120 + 4096 + 3 * 32 + 5120) + 5120   # norms, vectors, conv bias
    assert sum(p.values()) == 5_254_594_112 - left_out
    assert flops_falcon_h1.state_bytes_per_slot(cfg) == 25_350_144
    assert flops_falcon_h1.kv_bytes_per_position(cfg) == 12_288
    step = flops_falcon_h1.decode_step(cfg, [832 + 128] * 64)
    # ISSUE 32: 11.8 GB a step; the mixer 4.0 of them, the MLP 3.96
    assert step["bytes_total"] == pytest.approx(11.8e9, rel=0.01)
    assert step["bytes"]["mixer"] == pytest.approx(4.06e9, rel=0.01)
    assert step["bytes"]["mlp"] == pytest.approx(3.96e9, rel=0.01)
    assert step["bytes"]["mixer"] > step["bytes"]["mlp"] > step["bytes"]["head"]
    pre = flops_falcon_h1.prefill(cfg, [832] * 64)
    per_token_layer = pre["ops"]["mixer"] / (64 * 832 * 6)
    # the chunked recurrence about 5 MFLOP beside the mixer's projections
    assert per_token_layer - 2 * p["mixer"] / 6 == pytest.approx(4.8e6, rel=0.02)
    assert pre["ops_total"] / 197e12 == pytest.approx(1.41, rel=0.03)


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_falcon_h1.decode_step(cfg, [960] * 64)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_falcon_h1.least_seconds(step, peaks) == pytest.approx(14.4e-3,
                                                                      rel=0.02)
    pre = flops_falcon_h1.prefill(cfg, [832] * 64, 0.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s


# -- the reduction ----------------------------------------------------------
def _events(launches, *, decode_ms=24.0, prefill_ms=400.0, gap_ms=1.0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[8,1024]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"]):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%fusion.2 = bf16[64,5120]{1,0} fusion()", t, decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    return trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                        marks=[("lm.prefill.dispatch", 0.0, 1e12, {})])


def _launch(steps=4):
    # what EngineProbe records of an expert-less model: held_prefill 0.0
    return {"slots": 64, "bucket": 1024, "valid": 64, "lengths": [832] * 64,
            "slices": 8, "steps": steps, "held_prefill": 0.0}


def test_reduction_with_this_model_s_work_functions():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launches = [_launch(), _launch(), _launch()]
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_falcon_h1)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(24.0)
    assert out["prefill_device_ms_per_ktok"] == pytest.approx(
        8 * 400.0 / (64 * 0.832))
    assert out["prefill_roofline_pct"] == pytest.approx(100 * 1.41 / 3.2, rel=0.03)
    assert 55 < out["decode_step_roofline_pct"] < 62
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*decode step"):
        trace_lm_any.reduce(_events(launches, decode_ms=10.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_falcon_h1)


def test_real_cell_is_declared_and_its_files_are_found():
    cell = spec.load_cell(CELL)
    assert cell.config["driver"] == "hybrid_serve" and cell.chips == 1
    assert cell.traffic_name == "chat-1k-256-closed"
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(SPAN_AND_COUNTER) | set(FROM_TRACE)
    assert not any(n.startswith(("expert_", "latent_", "prefill_fused_"))
                   for n in names)
    for n in names:
        assert callable(spec.load_metric_reader(n))
    t = cell.traffic
    assert (t["clients"], t["max_new_tokens"], t["prompt_tokens"]) == (
        128, 256, [640, 1024])
    # the traffic file is K-EXAONE's cell's, byte for byte
    assert spec.load_cell("serve-exaone-chat-closed").traffic == t
    c = cell.config
    assert (c["max_batch"], c["queue_capacity"], c["length_ladder"],
            c["max_new_tokens"], c["max_wait_ms"]) == (64, 256, [1024], 256, 100.0)
    import importlib

    for key in ("reference", "weights", "work"):
        importlib.import_module(c[key])


def test_the_driver_reads_no_expert_counter():
    text = open(os.path.join(HARNESS, "drive_hybrid_serve.py")).read()
    code = text[text.index('"""', 3) + 3:]
    assert "expert" not in code.replace("# the control", "")
