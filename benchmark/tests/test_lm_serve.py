"""The ``lm_serve`` driver, its comparison, its reduction and its readers,
at the tiny preset on the CPU (``tinybench_lm/``: a ``BENCHMARK.json`` of
its own; ``tinybench/`` stays CANNet's)."""
import json
import os

import jax.numpy as jnp
import pytest

from benchmark import run
from benchmark.harness import (correct_lm, device, flops_lm, spec, trace,
                               trace_lm)
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench_lm")
REAL = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                    "k-exaone-ep8-serve-bf16.json")
CONTROLS = ("int8", "variant:window+1", "variant:rope_on_full",
            "variant:unnormalised_topk", "variant:expert_zeroed")
# the service's and the batcher's own, through the readers CANNet's serving
# cell reports them with (the code is one), then the model's
SHARED = ("seg_median_req_per_s.serve", "batch_fill_pct.serve",
          "pad_ms_per_img.serve", "complete_ms_per_img.serve",
          "batcher_wait_pct.serve", "cycle_unnamed_pct.serve")
SPAN_AND_COUNTER = SHARED + ("expert_load_max_over_mean.lm",
                             "expert_local_share_pct.lm")
FROM_TRACE = ("prefill_device_ms_per_ktok.lm", "decode_device_ms_per_step.lm",
              "decode_step_roofline", "prefill_roofline")


def _run(tmp_path, trace_=False, **kw):
    return run.run_cell("tiny-lm", 7, 0.5, trace_, root=str(tmp_path),
                        require_chip=False, data_dir=TINY,
                        spec_path=os.path.join(TINY, "BENCHMARK.json"), **kw)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("lm"), control_modes=CONTROLS)


def test_tiny_cell_runs_on_the_cpu_and_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert set(sound["metrics"]) == {"req_per_s", "setup_s"}
    assert sound["metrics"]["req_per_s"]["value"] > 0
    assert set(sound["numbers"]) == {"logit_gap_ratio", "token_miss_share",
                                     "routing_diff_share", "compiles_in_window"}
    assert sound["numbers"]["compiles_in_window"] == 0.0


@pytest.mark.parametrize("mode", CONTROLS)
def test_every_control_breaks_a_limit(sound, mode):
    """The reference in 8 bits, or with one piece of the mathematics broken,
    in the program's place: not correct by at least one limit."""
    limits = json.load(open(os.path.join(TINY, "configs", "tiny-lm.json")))["limits"]
    control = sound["control"][mode]
    assert any(control[k] > limits[k] for k in control), control


def test_tiny_cell_compares_the_numbers_the_chip_cell_compares():
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-lm.json")))
    real = json.load(open(REAL))
    assert tiny["limits"].keys() == real["limits"].keys()
    assert tiny["correct"].keys() <= real["correct"].keys()


def test_a_window_off_by_one_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: decode sees one position fewer of
    its ring than the window holds."""
    from can_tpu.ops import attention

    sound_ring = attention.ring_positions

    def short_ring(pos, window):
        held = sound_ring(pos, window)
        return jnp.where(held == pos[:, None] - (window - 1), -1, held)

    monkeypatch.setattr(attention, "ring_positions", short_ring)
    line = _run(tmp_path)
    assert line["correct"] is False


def test_a_prefill_slice_written_at_the_wrong_slots_is_not_correct(tmp_path):
    """The timed path broken where no compared prefill logit can see it:
    the launch's second prefill slice writes its cache rows a slot early, so
    three of four slots decode from another prompt's keys or from none."""
    from benchmark.tools import calibrate_lm

    line = _run(tmp_path, break_path=calibrate_lm.slice_offset(1))
    assert line["correct"] is False
    limits = json.load(open(os.path.join(TINY, "configs", "tiny-lm.json")))["limits"]
    assert line["numbers"]["token_miss_share"] > limits["token_miss_share"]


def test_the_logit_gap_is_judged_per_probe_kind():
    """Every prefill probe wrong and every other probe sound: not hidden by
    the other kinds' probes (one median over all 16 would read about 1)."""
    import numpy as np

    rng = np.random.default_rng(0)
    names = ["prefill", "step1", "step6", "step12"]
    compared, ref, yard = [], [], []
    for _ in range(4):
        n, v = 10, 32
        rows = rng.standard_normal((n + 12, v))
        ref.append({"logits": rows, "chosen": []})
        yard.append({"logits": rows + 0.01 * rng.standard_normal(rows.shape),
                     "chosen": []})
        at = correct_lm.probe_positions(n, names)
        logits = {k: rows[r] + 0.01 * rng.standard_normal(v) for k, r in at.items()}
        compared.append({"prompt": np.zeros(n, np.int32),
                         "tokens": rows[n - 1:n + 11].argmax(-1),
                         "logits": logits, "routing": {k: [] for k in names}})
    sound = correct_lm.lm_numbers(compared, ref, yard, token_margin_rms=6.0)
    assert sound["logit_gap_ratio"] < 1.5
    for c in compared:
        c["logits"]["prefill"] = c["logits"]["prefill"] + 1.0
    broken = correct_lm.lm_numbers(compared, ref, yard, token_margin_rms=6.0)
    assert broken["logit_gap_ratio"] > 50
    # one flip in a kind is rounding's (a routing choice changed sides)
    for c in compared[1:]:
        c["logits"]["prefill"] = c["logits"]["prefill"] - 1.0
    assert correct_lm.lm_numbers(compared, ref, yard, token_margin_rms=6.0)[
        "logit_gap_ratio"] < 1.5


def test_the_benchmarks_own_weights_are_the_tree_the_program_reads():
    """``weights_lm.py`` writes its shapes from the configuration file and
    imports nothing of the program; the program's own shapes for the same
    file are the same tree (else it could not be served at all)."""
    import inspect

    import jax

    from benchmark.harness import weights_lm
    from can_tpu.models import exaone_moe

    assert "can_tpu" not in inspect.getsource(weights_lm)
    for path in (REAL, os.path.join(TINY, "configs", "tiny-lm.json")):
        cfg = json.load(open(path))
        assert weights_lm.shapes(cfg) == exaone_moe.param_shapes(
            exaone_moe.ExaoneMoeConfig.from_dict(cfg))
    tiny = json.load(open(os.path.join(TINY, "configs", "tiny-lm.json")))
    tiny["num_nextn_predict_layers"] = 1
    params = weights_lm.make_params(tiny, 3)
    again = weights_lm.make_params(tiny, 3)
    other = weights_lm.make_params(tiny, 4)
    flat = jax.tree_util.tree_leaves_with_path(params)
    assert "mtp" in params and len(flat) > 60
    for (path, a), b, c in zip(flat, jax.tree.leaves(again), jax.tree.leaves(other)):
        name = path[-1].key
        assert a.dtype == (jnp.float32 if name == "bias" else jnp.bfloat16), path
        assert bool((a == b).all()) and not bool((a == c).all()), path
        a = a.astype(jnp.float32)
        if name in weights_lm.NORMS:
            assert abs(float(a.mean()) - 1.0) < 0.1
        elif a.size >= 1024:
            want = {"embed": 1.0, "bias": 0.05}.get(name, a.shape[-2] ** -0.5)
            assert float(a.std()) == pytest.approx(want, rel=0.15), path


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
        assert recorder.active() is not None
    finally:
        recorder.uninstall()
    assert line["correct"] is True
    for m in SPAN_AND_COUNTER:
        assert line["metrics"][m]["value"] > 0.0, m
    assert not set(FROM_TRACE) & set(line["metrics"])   # no device trace here
    assert line["metrics"]["batch_fill_pct.serve"]["value"] == 100.0
    # the tiny preset holds every expert
    assert line["metrics"]["expert_local_share_pct.lm"]["value"] == 100.0
    assert line["metrics"]["cycle_unnamed_pct.serve"]["value"] < 20.0


def test_a_missing_span_raises(tmp_path, monkeypatch):
    """A reader that finds no launch among the spans of a run that completed
    work raises; it does not print a null.  A launch is a ``serve.batch``
    with the engine's ``serve.dispatch`` under it, for every model."""
    from benchmark.harness import program_spans

    recorder.uninstall()
    tracer = recorder.install(recorder.SpanTracer())
    try:
        with tracer.span("serve.batch", valid=4):
            with tracer.span("lm.prefill"):
                pass
        ctx = {"counters": {"rate": {"rate": 8.0, "window_s": 1.0}}}
        with pytest.raises(program_spans.MissingSpan):
            program_spans.serve_window(ctx)
    finally:
        recorder.uninstall()


def test_a_program_without_the_serving_path_fails_cleanly(tmp_path, monkeypatch):
    """The parent commit, asked for the cell: SpecError (exit 2), at once."""
    import can_tpu.serve

    monkeypatch.delattr(can_tpu.serve, "build_model_service")
    with pytest.raises(spec.SpecError, match="no language-model serving path"):
        _run(tmp_path)


# -- operations and bytes ---------------------------------------------------
def test_published_cut_parameters_and_decode_bytes():
    cfg = json.load(open(REAL))
    p = flops_lm.params_by_part(cfg)
    assert sum(p.values()) == 3_712_028_416 - 5 * 2 * 6144 - 6144 - 5 * 2 * 128 - 4 * 128
    step = flops_lm.decode_step(cfg, [1000] * 64)
    read = (sum(v for k, v in p.items() if k != "embedding")) * 2
    assert read == pytest.approx(7.19e9, rel=2e-3)        # ISSUE: 7.19 GB
    assert step["bytes_total"] == pytest.approx(read + 0.32e9, rel=0.05)
    assert step["bytes"]["experts"] / step["bytes_total"] == pytest.approx(0.68, abs=0.03)
    pre = flops_lm.prefill(cfg, [1024] * 64, 64 * 1024 * 4.0)
    assert pre["ops_total"] / (64 * 1024) == pytest.approx(2.45e9, rel=0.03)


def test_decode_floor_is_memory_and_prefill_floor_compute():
    cfg = json.load(open(REAL))
    peaks = device.PEAKS["v5lite"]
    step = flops_lm.decode_step(cfg, [900] * 64)
    assert step["bytes_total"] / peaks.hbm_bytes_s > step["ops_total"] / peaks.flops
    assert flops_lm.least_seconds(step, peaks) == pytest.approx(9.1e-3, rel=0.05)
    pre = flops_lm.prefill(cfg, [832] * 64, 64 * 832 * 4.0)
    assert pre["ops_total"] / peaks.flops > pre["bytes_total"] / peaks.hbm_bytes_s


# -- the reduction, on a trace made by hand ------------------------------------
def _events(launches, *, decode_ms=12.0, prefill_ms=400.0, gap_ms=1.0, drop_decode=0):
    mods, ops, t = [], [], 1e6
    for l in launches:
        for _ in range(l["slices"]):
            mods.append((f"{trace_lm.PREFILL}(1)", t, prefill_ms * 1e6))
            ops.append(("%fusion.1 = bf16[8,1024]{1,0} fusion()", t, prefill_ms * 1e6))
            t += prefill_ms * 1e6 + gap_ms * 1e6
        for _ in range(l["steps"] - drop_decode):
            mods.append((f"{trace_lm.DECODE}(2)", t, decode_ms * 1e6))
            ops.append(("%ragged-dot = bf16[512,2048]{1,0} custom-call()", t,
                        decode_ms * 1e6))
            t += decode_ms * 1e6 + gap_ms * 1e6
    ev = trace.Events(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                      marks=[])
    return ev


def _launch(steps=4):
    return {"slots": 64, "bucket": 1024, "valid": 64, "lengths": [832] * 64,
            "slices": 8, "steps": steps, "held_prefill": 64 * 832 * 4.0}


def test_reduction_reads_all_but_the_last_launch():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launches = [_launch(), _launch(), _launch()]
    ev = _events(launches)
    ev.marks = [("lm.decode.dispatch", 0.0, 1e12, {})]
    out = trace_lm.reduce(ev, launches, cfg=cfg, peaks=peaks)
    assert out["launches"] == 2
    assert out["decode_device_ms_per_step"] == pytest.approx(12.0)
    assert out["prefill_device_ms_per_ktok"] == pytest.approx(8 * 400.0 / (64 * 0.832))
    assert 50 < out["decode_step_roofline_pct"] < 100
    assert 0 < out["prefill_roofline_pct"] < 100
    assert out["busy_s"] < out["window_s"]
    assert out["idle_gaps"][0][0] == "lm.decode.dispatch"
    assert out["device_ops"][0][0].startswith("fusion.1")


def test_reduction_refuses_executions_the_host_did_not_count():
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launches = [_launch(), _launch()]
    with pytest.raises(trace.ImpossibleReading, match=r"\(a\)"):
        trace_lm.reduce(_events(launches, drop_decode=1), launches, cfg=cfg,
                        peaks=peaks)


@pytest.mark.parametrize("kw,what", [({"decode_ms": 5.0}, "decode step"),
                                     ({"prefill_ms": 50.0}, "prefill")])
def test_reduction_refuses_an_execution_under_its_floor(kw, what):
    """No share can read over 100%: a program faster than its roofline floor
    is an error, not a metric."""
    cfg, peaks = json.load(open(REAL)), device.PEAKS["v5lite"]
    launches = [_launch(), _launch()]
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*" + what):
        trace_lm.reduce(_events(launches, **kw), launches, cfg=cfg, peaks=peaks)


def test_probe_positions():
    rows = correct_lm.probe_positions(700, ["prefill", "step1", "step128", "step256"])
    assert rows == {"prefill": 699, "step1": 700, "step128": 827, "step256": 955}


def test_real_cell_is_declared_and_its_files_are_found():
    cell = spec.load_cell("serve-exaone-chat-closed")
    assert cell.config["driver"] == "lm_serve" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(SPAN_AND_COUNTER) | set(FROM_TRACE)
    for n in names:
        assert callable(spec.load_metric_reader(n))
    assert cell.traffic["clients"] == 128 and cell.traffic["max_new_tokens"] == 256
    # the published widths, uncut
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["moe_intermediate_size"], c["intermediate_size"],
            c["num_experts_per_tok"], c["sliding_window"]) == (
        6144, 64, 8, 128, 2048, 18432, 8, 128)
