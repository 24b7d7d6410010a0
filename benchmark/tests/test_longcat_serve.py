"""LongCat-Flash's cell's files on the CPU at the tiny preset
(``tinybench_longcat/``: a ``BENCHMARK.json`` of its own with the real cell's
metric set): the ``lfm2_serve`` driver as it stands, the two new readers, the
work counts, the controls."""
import json
import os

import pytest

from benchmark import run
from benchmark.harness import device, flops_longcat_flash, spec, trace, trace_lm, trace_lm_any
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_longcat")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "longcat-flash-omni-ep32-serve-bf16.json")
CELL = "serve-longcat-reason1k-closed"
CONTROLS = ("int8", "variant:no_zero_term", "variant:normalised_topk",
            "variant:sigmoid_scoring", "variant:no_scale_factor",
            "variant:no_kv_scale", "variant:sequential_block")
SPAN_AND_COUNTER = (
    "seg_median_req_per_s.serve", "batch_fill_pct.serve", "pad_ms_per_img.serve",
    "complete_ms_per_img.serve", "expert_load_max_over_mean.lm",
    "expert_local_share_pct.lm", "latent_cache_bytes_per_pos.lm",
    "prefill_pad_token_pct.lm", "zero_expert_choice_pct.lm")
NEW = ("zero_expert_choice_pct.lm", "decode_experts_roofline")
# the readers over ``program_spans.batcher_interval``, which wants a
# ``serve.wait`` and a ``serve.poll`` between the window's first batch and its
# last: the real cell's window is two launches that one ``serve.intake`` may
# hold (the test below), so the cell declares neither
BATCHER_CYCLE = ("batcher_wait_pct.serve", "cycle_unnamed_pct.serve")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-longcat", 2**31 + 7, 0.5, trace_,
                        root=str(tmp_path), require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


def _limits():
    return json.load(open(os.path.join(TINY, "configs", "tiny-longcat.json")))["limits"]


class CpuEnv(run.Env):
    """No chip and no device trace: the program's spans and counters alone."""

    def start_trace(self):
        return None

    def stop_trace(self):
        pass


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("longcat"), control_modes=CONTROLS)


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


@pytest.mark.parametrize("name", ["leaves_swapped", "late_write"])
def test_the_program_broken_underneath_is_not_correct(tmp_path, name):
    from benchmark.tools import calibrate_longcat_flash as cal

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
    assert "decode_experts_roofline" not in line["metrics"]   # no device trace here
    # 2 layers x 2 sublayers x (16 + 8) numbers x 2 bytes
    assert line["metrics"]["latent_cache_bytes_per_pos.lm"]["value"] == 2 * 2 * 24 * 2
    # 4 of the router's 12 outputs are identity experts
    assert 15 < line["metrics"]["zero_expert_choice_pct.lm"]["value"] < 55
    # 2 of 12 outputs are held here
    assert 5 < line["metrics"]["expert_local_share_pct.lm"]["value"] < 35


def test_a_traced_window_of_two_launches_in_one_intake_still_reports(tmp_path, monkeypatch):
    """The real cell's window: 2 x ``max_batch`` requests in flight and two
    launches, no third (``--seconds`` 0 ends the tiny cell's window at its
    first segment boundary, as 30 s end the real one's after its first launch
    of 30.1 s).  Where the batcher's first full intake holds both groups, it
    launches them one after the other inside one ``serve.intake`` span and no
    ``serve.wait`` or ``serve.poll`` lies between them: the readers of
    ``BATCHER_CYCLE`` raise ``MissingSpan`` there (exit code 1 on the chip, the
    driver's refusal of PR 50's first hand-in), every declared reader reads."""
    from benchmark.harness import program_spans

    recorder.uninstall()
    monkeypatch.setattr(run, "Env", CpuEnv)
    cell = spec.load_cell("tiny-longcat", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    try:
        line = run.run_cell("tiny-longcat", 2**31 + 11, 0.0, True, root=str(tmp_path),
                            require_chip=False, data_dir=TINY,
                            spec_path=os.path.join(TINY, "BENCHMARK.json"))
        ring = program_spans.read()
        batches = ring.steady_batches(line["attempted"])
        one_intake = len({b["parent_id"] for b in batches}) == 1
        raised = []
        for name in BATCHER_CYCLE:
            ctx = {"cell": cell, "counters": {"rate": {"rate": line["attempted"],
                                                       "window_s": 1.0}}}
            try:
                spec.load_metric_reader(name)(ctx)
            except program_spans.MissingSpan:
                raised.append(name)
    finally:
        recorder.uninstall()
    assert line["correct"] is True and line["attempted"] == 16 and len(batches) == 2
    for m in SPAN_AND_COUNTER:
        assert line["metrics"][m]["value"] > 0.0, m
    if not one_intake:
        pytest.skip("the batcher woke between the two groups' submissions: a "
                    "wait and a poll lie between the launches in this run")
    assert raised == list(BATCHER_CYCLE)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    cell = spec.load_cell("tiny-longcat", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    recorder.uninstall()
    try:
        for counters in ({}, {"lm": {"assignments_all": 10}}):
            ctx = {"cell": cell, "counters": counters, "trace": {}, "end_to_end": {}}
            for name in NEW:
                assert spec.load_metric_reader(name)(ctx) is None, name
    finally:
        recorder.uninstall()


def test_a_program_without_the_model_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), before a
    device is opened or a weight is made."""
    from can_tpu.serve import programs

    monkeypatch.delitem(programs.MODEL_TYPES, "longcat_flash")
    monkeypatch.setattr(run.Env, "open_devices", lambda *a: pytest.fail("opened"))
    with pytest.raises(spec.SpecError, match="no serving programs for model_type"):
        _run(tmp_path)


def test_the_benchmarks_own_weights_are_the_tree_the_program_reads():
    import inspect

    import numpy as np

    from benchmark.harness import weights_longcat_flash as w
    from can_tpu.models import longcat_flash as lf

    assert "can_tpu" not in inspect.getsource(w).split('"""', 2)[2]
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-longcat.json")))
    for cfg in (json.load(open(REAL)), tiny):
        assert w.shapes(cfg) == lf.param_shapes(lf.LongcatFlashConfig.from_dict(cfg))
    params = w.make_params(tiny, 2**31 + 3)
    router = np.asarray(params["layers"][0]["moe"]["router"], np.float32)
    assert 1.5 < router.std() * 64 ** 0.5 < 2.5          # ROUTER_GAIN 2
    assert params["layers"][0]["moe"]["bias"].dtype == np.float32


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_work():
    cfg = json.load(open(REAL))
    p = flops_longcat_flash.params_by_part(cfg)
    norms_and_bias = 4 * (4 * 6144 + 2 * (1536 + 512) + 768) + 6144
    assert sum(p.values()) == 5_172_749_312 - norms_and_bias
    assert flops_longcat_flash.cache_bytes_per_position(cfg) == 9216
    assert flops_longcat_flash.experts_bytes_per_step(cfg) == 64 * 75_497_472
    assert flops_longcat_flash.experts_bytes_per_step(cfg, 0.5) == 32 * 75_497_472
    step = flops_longcat_flash.decode_step(cfg, [700] * 256)
    # the held experts are about half of a step's weight bytes
    weights = sum(step["bytes"].values()) - 256 * 1024 * 9216
    assert 0.45 < step["bytes"]["experts"] / weights < 0.5
    # a context of 700 reads one block of 1,024; of 1,100 the allocation's 1,280
    assert step["bytes"]["attention"] == 2 * p["attention"] + 256 * 1024 * 9216
    late = flops_longcat_flash.decode_step(cfg, [1100] * 256)
    assert late["bytes"]["attention"] - step["bytes"]["attention"] == 256 * 256 * 9216


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    step = flops_longcat_flash.decode_step(cfg, [700] * 256)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert 0.014 < flops_longcat_flash.least_seconds(step, peaks) < 0.017
    pre = flops_longcat_flash.prefill(cfg, [190] * 256, 256 * 190 * 4 * 0.25)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s
    # a prefill token: 634 M dense parameters a layer, a quarter of a held expert
    assert pre["ops"]["experts"] < 0.03 * pre["ops_total"]


# -- the reduction ----------------------------------------------------------
def _events(launches, *, decode_ms=20.0, prefill_ms=400.0, gap_ms=1.0):
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
    launch = {"slots": 256, "bucket": 256, "valid": 256, "lengths": [190] * 256,
              "slices": 8, "steps": 4, "held_prefill": 256 * 190.0}
    launches = [launch] * 3
    out = trace_lm_any.reduce(_events(launches), launches, cfg=cfg, peaks=peaks,
                              work_of=flops_longcat_flash)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(20.0)
    assert 60 < out["decode_step_roofline_pct"] < 100
    assert 20 < out["prefill_roofline_pct"] < 100
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*decode step"):
        trace_lm_any.reduce(_events(launches, decode_ms=10.0), launches, cfg=cfg,
                            peaks=peaks, work_of=flops_longcat_flash)


def test_real_cell_is_declared_and_its_files_are_found():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "lfm2_serve"
    assert cell.traffic["max_new_tokens"] == cell.config["max_new_tokens"] == 1024
    assert cell.traffic["clients"] == 2 * cell.config["max_batch"] == 512
    assert cell.traffic["prompt_tokens"] == [128, 256]
    assert cell.config["length_ladder"] == [256]
    assert (cell.traffic["logits_every"], cell.traffic["logits_slot_step"]) == (64, 85)
    assert cell.traffic["segment_requests"] == 256
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and {"decode_step_roofline", "prefill_roofline",
                                  "latent_cache_bytes_per_pos.lm",
                                  "expert_local_share_pct.lm"} <= names
    assert not {"prefill_fused_attention_pct.lm", "decode_experts_read_pct.lm"} & names
    assert not set(BATCHER_CYCLE) & names
    tiny = spec.load_cell("tiny-longcat", data_dir=TINY,
                          spec_path=os.path.join(TINY, "BENCHMARK.json"))
    assert {m["name"] for m in tiny.per_layer} == names
    assert {m["name"] for m in cell.end_to_end} == {"req_per_s", "setup_s"}
    for m in cell.per_layer:
        spec.load_metric_reader(m["name"])
    assert set(cell.config["limits"]) == {"logit_gap_ratio", "token_miss_share",
                                          "routing_diff_share", "compiles_in_window"}


def test_the_door_asks_at_the_slots_the_issue_names():
    """``drive_glm_serve._Door`` with ``logits_every`` 64 and ``logits_slot_
    step`` 85: slots 0, 85, 170, 255 of the first launch and 20, 105, 190, 211
    of the second, eight different prompts of the 256."""
    from benchmark.harness.drive_glm_serve import _Door
    from benchmark.harness.drive_lm_serve import prompt_of

    class Service:
        asked = []

        def submit(self, tokens, *, max_new_tokens, want_logits=False):
            self.asked.append(want_logits)

    door = _Door(Service(), 64, 85)
    for _ in range(512):
        door.submit(None, max_new_tokens=1)
    at = [i for i, want in enumerate(Service.asked) if want]
    assert [i % 256 for i in at] == [0, 85, 170, 255, 20, 105, 190, 211]
    prompts = list(range(256))
    assert len({prompt_of(prompts, i) for i in at}) == 8
