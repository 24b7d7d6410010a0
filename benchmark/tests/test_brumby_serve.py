"""The ``hybrid_serve`` driver as it stands, given the power-retention model
(no expert layer, a cache of ``state`` alone), its new readers and its work
counts, at the tiny Brumby preset on the CPU (``tinybench_brumby/``: a
``BENCHMARK.json`` of its own)."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, flops_brumby, spec, trace, trace_lm, trace_lm_any
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_brumby")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "brumby-14b-pp5-serve-bf16.json")
CELL = "serve-brumby-gen512-closed"
CONTROLS = ("int8", "variant:no_gate", "variant:no_normaliser",
            "variant:no_head_norm", "variant:no_rope")
NEW = ("decode_ret_ms_per_step.lm", "prefill_ret_ms_per_ktok.lm",
       "decode_ret_state_ms_per_step.lm", "decode_ret_roofline")
SPAN_AND_COUNTER = ("seg_median_req_per_s.serve", "batch_fill_pct.serve",
                    "prefill_pad_token_pct.lm", "state_cache_bytes_per_slot.lm")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-brumby", 2**31 + 7, 0.5, trace_,
                        root=str(tmp_path), require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs", "tiny-brumby.json")))["limits"]


class CpuEnv(run.Env):
    """No chip and no device trace: the program's spans and counters alone."""

    def start_trace(self):
        return None

    def stop_trace(self):
        pass


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("brumby"),
                control_modes=CONTROLS + ("variant:no_scale",))


def test_tiny_cell_runs_on_the_cpu_and_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"req_per_s", "setup_s"}
    assert sound["numbers"]["compiles_in_window"] == 0.0
    assert sound["numbers"]["routing_diff_share"] == 0.0   # no expert layer


@pytest.mark.parametrize("mode", CONTROLS)
def test_every_control_breaks_a_limit(sound, mode):
    control = sound["control"][mode]
    assert any(control[k] > _limits()[k] for k in control), control


def test_the_scale_left_out_of_the_power_reads_like_the_yardstick(sound):
    """The normaliser divides the scale out again: this control is the
    reference in bfloat16 itself (PERF.md section 7)."""
    control = sound["control"]["variant:no_scale"]
    assert all(control[k] <= _limits()[k] for k in control), control


@pytest.mark.parametrize("name", ["state_after_padding", "update_late"])
def test_the_program_broken_underneath_is_not_correct(tmp_path, name):
    from benchmark.tools import calibrate_brumby as cal

    breaker = cal.PROGRAM_BREAKS[name]
    try:
        line = _run(tmp_path, break_path=breaker)
    finally:
        breaker.undo()
    assert line["correct"] is False, line["numbers"]


def test_a_traced_run_reports_the_span_and_counter_metrics(tmp_path, monkeypatch):
    recorder.uninstall()
    monkeypatch.setattr(run, "Env", CpuEnv)
    try:
        line = _run(tmp_path, True)
    finally:
        recorder.uninstall()
    assert line["correct"] is True
    for m in SPAN_AND_COUNTER:
        assert line["metrics"][m]["value"] > 0.0, m
    assert not set(NEW) & set(line["metrics"])     # no device trace here
    # 3 layers x 2 key heads x 40 rows x (8 + 1) float32
    assert line["metrics"]["state_cache_bytes_per_slot.lm"]["value"] == 3 * 2 * 40 * 9 * 4


def test_the_state_kept_in_bfloat16_halves_what_the_counter_reads(tmp_path,
                                                                 monkeypatch):
    from benchmark.tools import calibrate_brumby as cal

    recorder.uninstall()
    monkeypatch.setattr(run, "Env", CpuEnv)
    try:
        line = _run(tmp_path, True, break_path=cal.state_bf16)
    finally:
        recorder.uninstall()
    assert line["metrics"]["state_cache_bytes_per_slot.lm"]["value"] == 3 * 2 * 40 * 9 * 2


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    cell = spec.load_cell("tiny-brumby", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    ctx = {"cell": cell, "counters": {}, "trace": {}, "end_to_end": {}}
    try:
        for name in NEW:
            assert spec.load_metric_reader(name)(ctx) is None, name
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "brumby")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


def test_the_benchmarks_own_weights_are_the_tree_the_program_reads():
    import inspect

    import numpy as np

    from benchmark.harness import weights_brumby as w
    from can_tpu.models import brumby as bm

    assert "can_tpu" not in inspect.getsource(w).split('"""', 2)[2]
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-brumby.json")))
    for cfg in (json.load(open(REAL)), tiny):
        assert w.shapes(cfg) == bm.param_shapes(bm.BrumbyConfig.from_dict(cfg))
    params = w.make_params(tiny, 2**31 + 3)
    assert (np.asarray(params["embed"], np.float32)[:, 0] == w.ANCHOR).all()
    for i, layer in enumerate(params["layers"]):
        anchor = np.asarray(layer["ret"]["wg"], np.float32)[0] * w.ANCHOR
        aim = anchor / (1.0 + w.GROWTH * i) ** 0.5
        assert ((aim > 2.1) & (aim < 7.0)).all(), aim     # sigmoid: 0.9 .. 0.999


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_brumby.params_by_part(cfg)
    norms = 8 * (2 * 5120 + 2 * 128) + 5120
    assert sum(p.values()) == 4_198_652_928 - norms
    assert flops_brumby.state_bytes_per_slot(cfg) == 272_646_144    # ISSUE 47
    step = flops_brumby.decode_step(cfg, [1200] * 16)
    # ISSUE 47: 15.56 GB a step, of which the state 8.72 and the weights 6.84
    assert step["bytes_total"] == pytest.approx(15.56e9, rel=0.002)
    assert step["bytes"]["retention"] == pytest.approx(8.72e9 + 1.01e9, rel=0.002)
    assert flops_brumby.retention_bytes_per_step(cfg) == step["bytes"]["retention"]
    assert step["bytes"]["retention"] > 0.56 * step["bytes_total"] * 1.1
    # the contexts change nothing: that is the model
    assert flops_brumby.decode_step(cfg, [32000] * 16) == step


def test_the_prefill_s_count_is_the_lesser_form_s():
    cfg = json.load(open(REAL))
    short, long_ = [1024] * 4, [32768]
    # at the cell's bucket the quadratic form is the lesser: the pairs of one
    # chunk and one state built
    m = flops_brumby.dims(cfg)
    build = 2.0 * m["rows"] * 128 * 8 * 4096
    pairs = 4 * 1024 * 1025 // 2
    assert flops_brumby.retention_prefill_ops(cfg, short) == pytest.approx(
        8 * (4.0 * 128 * 40 * pairs + build))
    # at 32k the chunked form is: far under the quadratic count
    quadratic = 8 * 4.0 * 128 * 40 * (32768 * 32769 // 2)
    assert flops_brumby.retention_prefill_ops(cfg, long_) < 0.35 * quadratic
    pre = flops_brumby.prefill(cfg, short)
    assert pre["ops"]["mlp"] > pre["ops"]["retention"] > pre["ops"]["head"]


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_brumby.decode_step(cfg, [1200] * 16)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_brumby.least_seconds(step, peaks) == pytest.approx(19.0e-3, rel=0.01)
    pre = flops_brumby.prefill(cfg, [800] * 16, 0.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s


# -- the reduction ----------------------------------------------------------
def _events(launches, *, decode_ms=28.0, prefill_ms=120.0, gap_ms=1.0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[4,1024]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"]):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%fusion.2 = bf16[16,5120]{1,0} fusion()", t, decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    return trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                        marks=[("lm.prefill.dispatch", 0.0, 1e12, {})])


def test_reduction_with_this_model_s_work_functions():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launch = {"slots": 16, "bucket": 1024, "valid": 16, "lengths": [800] * 16,
              "slices": 4, "steps": 4, "held_prefill": 0.0}
    launches = [launch] * 3
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_brumby)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(28.0)
    assert out["decode_step_roofline_pct"] == pytest.approx(100 * 19.0 / 28.0, rel=0.01)
    assert 50 < out["prefill_roofline_pct"] < 100
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*decode step"):
        trace_lm_any.reduce(_events(launches, decode_ms=15.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_brumby)


def test_real_cell_is_declared_and_its_files_are_found():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "hybrid_serve"
    assert cell.traffic["max_new_tokens"] == cell.config["max_new_tokens"] == 512
    assert cell.traffic["clients"] == 2 * cell.config["max_batch"] == 32
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and {"decode_step_roofline", "prefill_roofline",
                                  "state_cache_bytes_per_slot.lm"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"req_per_s", "setup_s"}
    for m in cell.per_layer:
        spec.load_metric_reader(m["name"])
    assert set(cell.config["limits"]) == {"logit_gap_ratio", "token_miss_share",
                                          "routing_diff_share", "compiles_in_window"}
