"""``harness/program_scopes.py`` and the readers over it, on a recorded
window: three launches of two programs whose op events are written out by
hand, the spans as ``LMEngine`` records them."""
import json
import os

import pytest

from benchmark.harness import program_scopes, program_spans, spec, trace
from can_tpu.obs import spans as recorder

SPEC = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
LM3 = ["serve-exaone-chat-closed", "serve-glm-agent16k-closed",
       "serve-falconh1-chat-closed"]
NEW = {m["name"]: m for m in SPEC["per_layer"]
       if m["name"].endswith((".lm",)) and ("_ms_per_" in m["name"]
                                            or "_unscoped_pct" in m["name"])
       and m["source"] == "program_span"}

# instruction -> (part or None, nanoseconds an execution spends in it)
DECODE = {"fusion.1": ("attn.proj", 100.0), "fusion.2": ("attn.core", 300.0),
          "copy-done.3": ("attn.core", 50.0),        # inherited
          "skipping_experts.7": ("moe.experts", 400.0),
          "fusion.4": ("moe.router", 30.0), "fusion.5": ("moe.shared", 60.0),
          "convolution.6": ("head", 200.0), "fusion.8": ("sample", 10.0),
          "copy.9": (None, 20.0)}
PREFILL = {"fused_causal_attention.6": ("attn.core", 5000.0),
           "fusion.10": ("attn.proj", 2000.0),
           "ragged-dot-none.1": ("moe.experts", 3000.0),
           "fusion.11": ("moe.dispatch", 1500.0),
           "fusion.12": ("dense_mlp", 500.0), "fusion.13": ("ssm.scan", 700.0)}
LAUNCHES = [(2, 3, 1000), (2, 3, 3000), (2, 3, 2000)]  # slices, steps, tokens


@pytest.fixture
def tracer():
    recorder.uninstall()
    program_scopes._loaded.clear()
    tr = program_spans.arm()
    yield tr
    recorder.uninstall()
    program_scopes._loaded.clear()


def record(tr, *, decode=DECODE, prefill=PREFILL, scopes=True, stray=0.0):
    """A warm-up launch with the two ``program.scopes`` spans, then
    ``LAUNCHES``; -> the ``Events`` a trace of those three would hold.
    ``stray``: nanoseconds an execution spends in an instruction no map
    knows."""
    def put(name, t0, t1, **attrs):
        tr.emit(trace_id="lane", name=name, start=t0, end=t1, **attrs)

    put("lm.prefill", -9.0, -8.0, slices=2, valid_tokens=4, compiled=True)
    put("lm.decode", -8.0, -7.0, steps=1, compiled=True)
    if scopes:
        for program, ops in (("jit_prefill_slice", prefill), ("jit_decode", decode)):
            put("program.scopes", -8.5, -8.4, program=program, key=[2, 32],
                parts={k: p for k, (p, _) in ops.items()},
                inherited=[k for k in ops if k.startswith("copy-done")],
                instructions=len(ops),
                unscoped=sum(p is None for p, _ in ops.values()))
    modules, events, t = [], [], 1000.0
    for i, (slices, steps, tokens) in enumerate(LAUNCHES):
        put("lm.prefill", float(i), i + 0.5, slices=slices, valid_tokens=tokens,
            compiled=False)
        put("lm.decode", i + 0.5, i + 0.9, steps=steps, compiled=False)
        modules.append(("jit_new_cache(1)", t, 5.0))
        t += 10.0
        for name, ops, n in (("jit_prefill_slice(2)", prefill, slices),
                             ("jit_decode(3)", decode, steps)):
            for _ in range(n):
                start = t
                for inst, (_, ns) in ops.items():
                    events.append((f"%{inst} = bf16[4,8]{{1,0}} fusion(%p)", t, ns))
                    t += ns
                if stray:
                    events.append(("%fusion.999 = f32[] fusion()", t, stray))
                    t += stray
                modules.append((name, start, t - start))
                t += 100.0    # the gap between two executions
    return trace.Events(devices={"/device:TPU:0": {"modules": modules,
                                                   "ops": events}}, marks=[])


def test_parts_sum_over_the_launches_that_are_read(tracer, capsys):
    program_scopes._loaded[:] = [record(tracer)]
    found = program_scopes.read()
    # two launches are read: 6 decode steps, 4 slices, 4,000 tokens
    assert found["decode"]["executions"] == 6 and found["decode"]["per"] == 6
    assert found["prefill"]["executions"] == 4 and found["prefill"]["per"] == 4.0
    want = {"attn.proj": 100, "attn.core": 350, "moe.experts": 400,
            "moe.router": 30, "moe.shared": 60, "head": 200, "sample": 10}
    assert found["decode"]["parts"] == pytest.approx(
        {p: 6 * ns * 1e-9 for p, ns in want.items()})
    assert found["decode"]["unscoped_s"] == pytest.approx(6 * 20e-9)
    assert found["decode"]["inherited_s"] == pytest.approx(6 * 50e-9)
    assert found["decode"]["unknown_s"] == 0
    assert found["decode"]["total_s"] == pytest.approx(found["decode"]["module_s"])
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[scopes] decode")]
    assert len(line) == 1 and "moe.experts 0.0004" in line[0]
    assert program_scopes.read() is found       # once a run


@pytest.mark.parametrize("name,want", [
    ("decode_attn_ms_per_step.lm", 450e-6),
    ("decode_attn_core_ms_per_step.lm", 350e-6),
    ("decode_experts_ms_per_step.lm", 430e-6),
    ("decode_dense_ms_per_step.lm", 60e-6),
    ("decode_ssm_ms_per_step.lm", 0.0),
    ("decode_head_ms_per_step.lm", 210e-6),
    ("decode_unscoped_pct.lm", 100 * 20 / 1170),
    ("prefill_attn_ms_per_ktok.lm", 7000e-6),
    ("prefill_experts_ms_per_ktok.lm", 4500e-6),
    ("prefill_dispatch_ms_per_ktok.lm", 1500e-6),
    ("prefill_dense_ms_per_ktok.lm", 500e-6),
    ("prefill_ssm_ms_per_ktok.lm", 700e-6),
    ("prefill_unscoped_pct.lm", 0.0),
])
def test_reader_on_a_recorded_window(tracer, name, want):
    """Per decode step / per 1,000 valid prompt tokens (4 slices for 4,000
    tokens: a slice per 1,000)."""
    read = spec.load_metric_reader(name)
    program_scopes._loaded[:] = [record(tracer)]
    assert read({}) == pytest.approx(want)


def test_the_decode_parts_are_a_partition_of_the_step(tracer):
    program_scopes._loaded[:] = [record(tracer)]
    parts = sum(spec.load_metric_reader(f"decode_{p}_ms_per_step.lm")({})
                for p in ("attn", "experts", "dense", "ssm", "head"))
    found = program_scopes.read()
    unscoped = spec.load_metric_reader("decode_unscoped_pct.lm")({}) / 100
    step_ms = 1e3 * found["decode"]["module_s"] / 6
    assert parts + unscoped * step_ms == pytest.approx(step_ms)


def test_the_thirteen_metrics_are_declared_with_their_cells():
    assert len(NEW) == 13
    for name, m in NEW.items():
        assert m["moves"] == "req_per_s" and m["better"] == "lower"
        if "ssm" in name:
            assert m["workloads"] == LM3[2:]
        elif "experts" in name or "dispatch" in name:
            assert m["workloads"] == LM3[:2]
        else:
            assert m["workloads"] == LM3
        assert callable(spec.load_metric_reader(name))


def test_a_program_without_the_span_reads_nothing(tracer):
    program_scopes._loaded[:] = [record(tracer, scopes=False)]
    assert program_scopes.read() is None
    for name in NEW:
        assert spec.load_metric_reader(name)({}) is None


def test_no_trace_loaded_reads_nothing(tracer):
    record(tracer)
    assert program_scopes.read() is None


def test_a_map_of_another_compile_raises(tracer):
    program_scopes._loaded[:] = [record(tracer, stray=40.0)]   # 3% of a step
    with pytest.raises(trace.ImpossibleReading, match=r"\(a\).*another compile"):
        program_scopes.read()


def test_ops_that_do_not_fill_their_executions_raise(tracer):
    events = record(tracer)
    lines = events.devices["/device:TPU:0"]
    lines["modules"] = [(n, s, d * 1.05) if n.startswith("jit_decode") else (n, s, d)
                        for n, s, d in lines["modules"]]
    program_scopes._loaded[:] = [events]
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\)"):
        program_scopes.read()


def test_a_family_the_map_holds_may_not_read_zero(tracer):
    decode = dict(DECODE, **{"fusion.20": ("ssm.scan", 0.0)})
    program_scopes._loaded[:] = [record(tracer, decode=decode)]
    with pytest.raises(trace.ImpossibleReading, match=r"\(c\).*'ssm'"):
        program_scopes.read()


def test_launches_that_do_not_add_up_raise(tracer):
    events = record(tracer)
    lines = events.devices["/device:TPU:0"]
    lines["modules"] = lines["modules"][:-1]        # a decode step went missing
    program_scopes._loaded[:] = [events]
    with pytest.raises(trace.ImpossibleReading, match="add up"):
        program_scopes.read()


def test_overlapping_ops_are_counted_once():
    """A copy that runs beside two fusions: every instant goes to the op
    started last."""
    ops = [("copy-start.1", 0.0, 100.0), ("fusion.1", 10.0, 30.0),
           ("fusion.2", 50.0, 70.0)]
    got = program_scopes.exclusive_ns(ops, 0.0, 110.0)
    assert got == {"copy-start.1": 10 + 10, "fusion.1": 30, "fusion.2": 60}
    assert sum(got.values()) == 110


def test_arm_wraps_load_once_and_returns_what_load_returns(monkeypatch, tracer):
    calls = []
    monkeypatch.setattr(trace, "load", lambda path: calls.append(path) or "events")
    program_scopes.arm()
    wrapped = trace.load
    program_scopes.arm()
    assert trace.load is wrapped
    assert trace.load("x.pb") == "events" and calls == ["x.pb"]
    assert program_scopes._loaded == ["events"]
