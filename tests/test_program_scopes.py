"""Device time by model part, the program's side: the models' scopes are one
vocabulary and change no program, ``obs/trace.py`` reads them out of a
compiled program's text, and ``LMEngine`` records them when a tracer is
active, and only then."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import lm_tiny
from can_tpu.models import (brumby, exaone_moe, falcon_h1, glm_moe_lite, lfm2_moe,
                            longcat_flash,
                            lm_blocks, mimo_v2_flash)
from can_tpu.obs import spans as recorder
from can_tpu.obs.trace import (cache_copies, hlo_type, part_of,
                               program_scopes, scope_map)
from can_tpu.serve.programs import LMPrograms

PARTS = lm_blocks.PARTS

# what a compiled module's text looks like (XLA:TPU's, cut down by hand)
HLO = r"""HloModule jit_decode, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[4,8]) -> bf16[4,8] {
  %param_0.1 = bf16[4,8]{1,0} parameter(0)
  ROOT %multiply.9 = bf16[4,8]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(decode)/attn.proj/mul"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%a, %b), metadata={op_name="jit(decode)/head/reduce_sum"}
}

%body.7 (carry: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %carry = (s32[], bf16[4,8]{1,0}) parameter(0)
  %get-tuple-element.5 = bf16[4,8]{1,0} get-tuple-element(%carry), index=1
  %fusion.12 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/attn.core/while/body/jit(_where)/select_n" stack_frame_id=7}
  %constant.2 = s32[] constant(1)
  ROOT %tuple.4 = (s32[], bf16[4,8]{1,0}) tuple(%constant.2, %fusion.12)
}

%cond.8 (carry.1: (s32[], bf16[4,8])) -> pred[] {
  %carry.1 = (s32[], bf16[4,8]{1,0}) parameter(0)
  %get-tuple-element.6 = s32[] get-tuple-element(%carry.1), index=0
  %constant.3 = s32[] constant(4)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.6, %constant.3), direction=LT, metadata={op_name="jit(decode)/attn.core/while/cond/lt"}
}

ENTRY %main.20 (x: bf16[4,8], w: bf16[64,8,8], cache: bf16[4,8]) -> (bf16[4,8], f32[], bf16[4,8]) {
  %x = bf16[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %w = bf16[64,8,8]{2,1,0} parameter(1), metadata={op_name="params['w']"}
  %cache = bf16[4,8]{1,0} parameter(2), metadata={op_name="cache['k']"}
  %copy-start.1 = (bf16[4,8]{1,0:S(1)}, bf16[4,8]{1,0}, u32[]{:S(2)}) copy-start(%x)
  %copy-done.1 = bf16[4,8]{1,0:S(1)} copy-done(%copy-start.1)
  %copy.30 = bf16[4,8]{0,1} copy(%cache), metadata={op_name="cache['k']"}
  %fusion.1 = bf16[4,8]{1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/attn.proj/mul" stack_frame_id=3}
  %tuple.1 = (s32[], bf16[4,8]{1,0}) tuple(%constant.9, %fusion.1)
  %while.1 = (s32[], bf16[4,8]{1,0}) while(%tuple.1), condition=%cond.8, body=%body.7, metadata={op_name="jit(decode)/attn.core/while"}
  %get-tuple-element.9 = bf16[4,8]{1,0} get-tuple-element(%while.1), index=1
  %skipping_experts.3 = bf16[4,8]{1,0} custom-call(%get-tuple-element.9, /*index=1*/%w), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/moe.shared/moe.experts/jit(skipping_experts)/pallas_call"}
  %ragged-dot-none.2 = bf16[4,8]{1,0} custom-call(%skipping_experts.3, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %reduce.5 = f32[] reduce(%ragged-dot-none.2, %constant.10), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(decode)/head/reduce_sum"}
  %add.40 = bf16[4,8]{1,0} add(%copy.30, %skipping_experts.3), metadata={op_name="jit(decode)/add"}
  %copy.31 = bf16[4,8]{0,1} copy(%add.40)
  %bitcast.2 = bf16[4,8]{1,0} bitcast(%copy.31)
  %copy.40 = bf16[4,8]{0,1} copy(%x)
  ROOT %tuple.9 = (bf16[4,8]{1,0}, f32[], bf16[4,8]{0,1}) tuple(%bitcast.2, %reduce.5, %copy.40)
}
"""


# a decode step that writes its cache in place (XLA:TPU's text for
# ``write_slot`` since PR 37, cut down by hand): the donated leaf is bitcast
# to the merged (slot x head) axis, scattered into and bitcast back; a
# ``copy`` INSIDE the fusion is no op of its own, one of another shape is
# not the cache's
HLO_IN_PLACE = r"""HloModule jit_decode, is_scheduled=true

%fused_computation.2 (param_0.2: bf16[8,16,8], param_1.2: bf16[8,8]) -> bf16[8,16,8] {
  %param_0.2 = bf16[8,16,8]{2,1,0} parameter(0)
  %param_1.2 = bf16[8,8]{1,0} parameter(1)
  %copy.7 = bf16[4,2,16,8]{3,2,1,0} copy(%param_0.2)
  ROOT %scatter.1 = bf16[8,16,8]{2,1,0} scatter(%param_0.2, %param_1.2), metadata={op_name="jit(decode)/attn.cache/scatter"}
}

ENTRY %main.9 (cache: bf16[4,2,16,8], new: bf16[8,8]) -> (bf16[4,2,16,8], bf16[8,8]) {
  %cache = bf16[4,2,16,8]{3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="cache['layers'][0]['k']"}
  %new = bf16[8,8]{1,0} parameter(1)
  %bitcast.1 = bf16[8,16,8]{2,1,0:T(8,128)(2,1)} bitcast(%cache)
  %fusion.2 = bf16[8,16,8]{2,1,0:T(8,128)(2,1)} fusion(%bitcast.1, %new), kind=kInput, calls=%fused_computation.2, metadata={op_name="jit(decode)/attn.cache/scatter"}
  %bitcast.3 = bf16[4,2,16,8]{3,2,1,0:T(8,128)(2,1)} bitcast(%fusion.2)
  %copy.8 = bf16[8,8]{0,1} copy(%new)
  ROOT %tuple.2 = (bf16[4,2,16,8]{3,2,1,0}, bf16[8,8]{0,1}) tuple(%bitcast.3, %copy.8)
}
"""


# a loop whose body the compiler gave copies of its carried state (XLA:TPU's
# text for the sorted expert form's passes, cut down by hand): they carry no
# metadata, nothing but the body's own result uses them and an argument made
# them, so neither neighbour has a part to hand on
HLO_LOOP = r"""HloModule jit_prefill_slice, is_scheduled=true

%inner_body.3 (c.2: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %c.2 = (s32[], bf16[4,8]{1,0}) parameter(0)
  %get-tuple-element.21 = bf16[4,8]{1,0} get-tuple-element(%c.2), index=1
  %copy.22 = bf16[4,8]{0,1} copy(%get-tuple-element.21)
  %constant.23 = s32[] constant(1)
  ROOT %tuple.24 = (s32[], bf16[4,8]{0,1}) tuple(%constant.23, %copy.22)
}

%inner_cond.4 (c.3: (s32[], bf16[4,8])) -> pred[] {
  %c.3 = (s32[], bf16[4,8]{1,0}) parameter(0)
  ROOT %constant.25 = pred[] constant(false)
}

%body.1 (c: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %c = (s32[], bf16[4,8]{1,0}) parameter(0)
  %get-tuple-element.11 = bf16[4,8]{1,0} get-tuple-element(%c), index=1
  %copy-start.12 = (bf16[4,8]{1,0:S(1)}, bf16[4,8]{1,0}, u32[]{:S(2)}) copy-start(%get-tuple-element.11)
  %copy-done.12 = bf16[4,8]{1,0:S(1)} copy-done(%copy-start.12)
  %while.13 = (s32[], bf16[4,8]{1,0}) while(%c), condition=%inner_cond.4, body=%inner_body.3
  %add.14 = s32[] add(%constant.15, %constant.15), metadata={op_name="jit(prefill_slice)/jit(_sorted_in_passes)/moe.dispatch/while/body/add"}
  ROOT %tuple.16 = (s32[], bf16[4,8]{1,0:S(1)}) tuple(%add.14, %copy-done.12)
}

%cond.2 (c.1: (s32[], bf16[4,8])) -> pred[] {
  %c.1 = (s32[], bf16[4,8]{1,0}) parameter(0)
  %get-tuple-element.17 = s32[] get-tuple-element(%c.1), index=0
  ROOT %compare.18 = pred[] compare(%get-tuple-element.17, %get-tuple-element.17), direction=LT
}

ENTRY %main.5 (x: bf16[4,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %tuple.6 = (s32[], bf16[4,8]{1,0}) tuple(%constant.7, %x)
  %while.8 = (s32[], bf16[4,8]{1,0}) while(%tuple.6), condition=%cond.2, body=%body.1, metadata={op_name="jit(prefill_slice)/jit(_sorted_in_passes)/moe.dispatch/while"}
  %copy.9 = bf16[4,8]{0,1} copy(%x)
  ROOT %get-tuple-element.10 = bf16[4,8]{1,0} get-tuple-element(%while.8), index=1
}
"""


def test_what_runs_in_a_loop_and_names_no_part_is_the_loop_s():
    """The compiler's copies of a loop's carried state, a condition that
    kept no metadata and a loop inside the loop that has none belong to the
    ``while`` that runs them (through the inner loop, to its body too);
    outside a loop nothing changes."""
    got = program_scopes(HLO_LOOP, {p: p for p in PARTS})
    p = got["parts"]
    assert p["while.8"] == p["add.14"] == "moe.dispatch"
    for inst in ("copy-start.12", "copy-done.12", "compare.18", "while.13",
                 "copy.22"):
        assert p[inst] == "moe.dispatch", inst
        assert inst in got["inherited"]
    assert p["copy.9"] is None and got["unscoped"] == 1
    assert got["instructions"] == 8


@pytest.mark.parametrize("text,leaves,want", [
    # ``HLO`` above: its cache is copied in (copy.30), a result of its shape
    # out (copy.31) and an argument of its shape out (copy.40)
    (HLO, [((4, 8), "bfloat16")], 3),
    (HLO, [((64, 8, 8), "bfloat16")], 0),    # the weights: never copied
    (HLO, [((4, 8), "bfloat16"), ((), "float32"), ((4, 8), "bfloat16")], 3),
    (HLO_IN_PLACE, [((4, 2, 16, 8), "bfloat16")], 0),
    (HLO_IN_PLACE, [((8, 8), "bfloat16")], 1),   # counted where asked for
    (HLO_IN_PLACE, [], 0),
], ids=["copied-in-and-out", "another-shape", "two-leaves", "in-place",
        "not-the-cache", "no-leaves"])
def test_cache_copies_counts_the_copies_that_run_with_a_leaf_s_type(
        text, leaves, want):
    assert cache_copies(text, [jax.ShapeDtypeStruct(s, jnp.dtype(d))
                               for s, d in leaves]) == want


@pytest.mark.parametrize("shape,dtype,want", [
    ((64, 4, 1280, 128), jnp.bfloat16, "bf16[64,4,1280,128]"),
    ((16, 16512, 64), jnp.zeros((), jnp.bfloat16).dtype, "bf16[16,16512,64]"),
    ((64, 32, 128, 256), jnp.float32, "f32[64,32,128,256]"),
    ((64,), "int32", "s32[64]"), ((), jnp.uint8, "u8[]"),
    ((2, 3), bool, "pred[2,3]"),
])
def test_hlo_type_writes_an_array_as_the_compiled_text_does(shape, dtype, want):
    assert hlo_type(shape, dtype) == want


class TestTheMap:
    def test_scope_map_covers_every_computation_that_runs(self):
        m = scope_map(HLO)
        # the while's body and condition are events of their own
        assert m["fusion.12"].endswith("attn.core/while/body/jit(_where)/select_n")
        assert m["compare.1"].endswith("while/cond/lt")
        assert m["while.1"] == "jit(decode)/attn.core/while"
        assert m["copy-start.1"] == "" and m["copy.31"] == ""   # no metadata
        assert m["copy.30"] == "cache['k']"
        # what runs inside another instruction, or not at all, is left out
        for gone in ("multiply.9", "add.3", "x", "w", "tuple.1", "constant.2",
                     "get-tuple-element.9", "bitcast.2", "tuple.9"):
            assert gone not in m
        assert len(m) == 13

    @pytest.mark.parametrize("op_name,want", [
        ("jit(decode)/attn.proj/mul", "attn.proj"),
        ("jit(decode)/attn.core/while/body/jit(_where)/select_n", "attn.core"),
        # nested scopes: the innermost
        ("jit(decode)/moe.shared/moe.experts/jit(skipping_experts)/pallas_call",
         "moe.experts"),
        ("jit(decode)/add", None), ("", None), ("cache['k']", None),
        ("jit(prefill_slice)/attn/dot_general", None),   # not a name, a prefix
        ("ragged-dot-none", None),
    ])
    def test_part_of_takes_the_innermost_name(self, op_name, want):
        assert part_of(op_name, PARTS) == want

    def test_part_of_reads_the_compiler_s_names_from_a_mapping(self):
        parts = {"moe.experts": "moe.experts", "ragged-dot-none": "moe.experts"}
        assert part_of("ragged-dot-none", parts) == "moe.experts"
        assert part_of("jit(f)/moe.experts/dot_general", parts) == "moe.experts"

    def test_program_scopes_hands_a_part_to_what_has_none_of_its_own(self):
        parts = {**{p: p for p in PARTS}, **lm_blocks.RENAMED_BY_COMPILER}
        got = program_scopes(HLO, parts)
        p = got["parts"]
        assert p["fusion.1"] == "attn.proj" and p["while.1"] == "attn.core"
        assert p["skipping_experts.3"] == "moe.experts"
        assert p["ragged-dot-none.2"] == "moe.experts"
        # a prefetch belongs to the op that needs it
        assert p["copy-start.1"] == p["copy-done.1"] == "attn.proj"
        # their users lead to the output alone: their producer's part
        assert p["add.40"] == "moe.experts" and p["copy.31"] == "moe.experts"
        # nothing passes sideways: an argument copied for an op without a
        # part of its own, and one copied out beside other results
        assert p["copy.30"] is None and p["copy.40"] is None
        assert got["instructions"] == 13 and got["unscoped"] == 2
        assert set(got["inherited"]) == {"copy-start.1", "copy-done.1",
                                         "add.40", "copy.31"}

    def test_the_vocabulary_is_the_issue_s(self):
        # sixteen of ISSUE 35, ISSUE 38's three of the short convolution,
        # ISSUE 40's ``attn.window`` and ISSUE 47's four of power retention
        assert len(PARTS) == len(set(PARTS)) == 24
        assert {p.split(".")[0] for p in PARTS} == {
            "embed", "attn", "moe", "dense_mlp", "ssm", "conv", "ret", "head",
            "sample", "routing"}
        assert {p for p in PARTS if p.startswith("ret.")} == {
            "ret.proj", "ret.core", "ret.state", "ret.out"}
        assert set(lm_blocks.RENAMED_BY_COMPILER.values()) <= set(PARTS)


# -- the seven tiny models -----------------------------------------------------
MODELS = {"k-exaone": (exaone_moe, lambda: lm_tiny.tiny_model(mtp=0)),
          "glm": (glm_moe_lite, lambda: lm_tiny.tiny_glm_model(mtp=0)),
          "falcon-h1": (falcon_h1, lm_tiny.tiny_falcon_model),
          "lfm2": (lfm2_moe, lm_tiny.tiny_lfm2_model),
          "mimo": (mimo_v2_flash, lm_tiny.tiny_mimo_model),
          "brumby": (brumby, lm_tiny.tiny_brumby_model),
          "longcat": (longcat_flash, lm_tiny.tiny_longcat_model)}
SLOTS, PART, BUCKET = 4, 2, 16


def _programs_and_args(name):
    """-> {"prefill_slice" | "decode": (function, arguments)} of a tiny
    model's serving programs."""
    module, make = MODELS[name]
    _, cfg, params = make()
    programs = LMPrograms(module, cfg, max_new_tokens=4)
    cache = programs.new_cache(SLOTS, BUCKET)
    batch = {"tokens": jnp.zeros((PART, BUCKET), jnp.int32),
             "lengths": jnp.full((PART,), 5, jnp.int32),
             "active": jnp.ones((PART,), bool)}
    start = jnp.zeros((), jnp.int32)
    out = jax.eval_shape(lambda *a: programs.prefill_slice(*a)[0], params,
                         batch, cache, start)
    state = jax.eval_shape(
        lambda outs: programs.new_state(outs, jnp.ones((SLOTS,), jnp.int32),
                                        jnp.ones((SLOTS,), bool))[0],
        [out] * (SLOTS // PART))
    return programs, {"prefill_slice": (programs.prefill_slice,
                                        (params, batch, cache, start)),
                      "decode": (programs.decode, (params, state, cache))}


@pytest.fixture(scope="module", params=sorted(MODELS))
def tiny(request):
    programs, progs = _programs_and_args(request.param)
    return request.param, programs, progs


@pytest.mark.parametrize("program", ["prefill_slice", "decode"])
def test_every_traced_instruction_of_a_tiny_model_has_a_part(tiny, program):
    """Every instruction of the compiled program that the model's trace made
    (its ``op_name`` is a path from ``jit(...)``: it does arithmetic) maps
    into the vocabulary, the families a model lacks are absent, and next to
    nothing is left without a part once the copies took their users'."""
    name, programs, progs = tiny
    fn, args = progs[program]
    text = jax.jit(fn).lower(*args).compile().as_text()
    traced = {i: n for i, n in scope_map(text).items() if n.startswith("jit(")}
    assert len(traced) > 50
    lost = {i: n for i, n in traced.items() if part_of(n, PARTS) is None}
    assert not lost, lost
    got = program_scopes(text, programs.parts)
    families = {p.split(".")[0] for p in got["parts"].values() if p}
    assert {"head", "sample", "embed", "dense_mlp"} <= families
    # retention INSTEAD of attention: its model has no ``attn.`` part at all,
    # and its slices' states are placed into the cache as ``ret.state``
    assert ("ret" in families) == (name == "brumby") == ("attn" not in families)
    if name == "brumby":
        want = {"ret.proj", "ret.state", "ret.out"} | (
            {"ret.core"} if program == "prefill_slice" else set())
        assert {p for p in got["parts"].values()
                if p and p.startswith("ret.")} == want
        return
    assert ("ssm" in families) == (name == "falcon-h1")
    assert ("conv" in families) == (name == "lfm2")
    # a window layer's core is a part of its own in the one model that opens
    # it (inside ``attn.core``, which is then the full layers' alone);
    # K-EXAONE's window layers stay ``attn.core``
    assert ("attn.window" in got["parts"].values()) == (name == "mimo")
    assert "attn.core" in got["parts"].values()
    assert ("moe" in families) == ("routing" in families) == (name != "falcon-h1")
    assert got["unscoped"] <= 3 and got["unscoped"] < 0.03 * got["instructions"]
    assert set(got["parts"].values()) <= set(PARTS) | {None}


@pytest.mark.parametrize("program", ["prefill_slice", "decode"])
def test_the_scopes_change_no_program(tiny, program, monkeypatch):
    """A scope is metadata: the program lowered with ``jax.named_scope``
    patched to a null context is the same StableHLO text (locations are
    not printed)."""
    _, _, progs = tiny
    fn, args = progs[program]
    with_scopes = jax.jit(fn).lower(*args).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = jax.jit(lambda *a: fn(*a)).lower(*args).as_text()
    assert "loc(" not in with_scopes
    module = re.compile(r"module @jit_\w+")      # named after the function
    assert module.sub("", with_scopes) == module.sub("", without)
    # the patch took: traced again, the locations name no scope
    bare = jax.jit(lambda *a: fn(*a)).lower(*args).as_text(debug_info=True)
    assert "attn.core" not in bare and "ret.state" not in bare


def test_the_scopes_are_in_the_lowering_s_locations(tiny):
    _, _, progs = tiny
    fn, args = progs["decode"]
    named = jax.jit(fn).lower(*args).as_text(debug_info=True)
    core = "ret.state" if tiny[0] == "brumby" else "attn.core"
    assert core in named and "sample" in named


# -- the engine ----------------------------------------------------------
def _service(config_path):
    import json

    from can_tpu.serve import build_model_service

    with open(config_path) as f:
        return build_model_service(json.load(f), seed=0)


@pytest.fixture
def count_lowerings(monkeypatch):
    from can_tpu.obs import costs

    calls = []
    inner = costs.resolve_jit
    monkeypatch.setattr(costs, "resolve_jit",
                        lambda fn, args: calls.append(fn) or inner(fn, args))
    return calls


TINY_GLM = "benchmark/tests/tinybench_glm/configs/tiny-glm.json"


def test_without_a_tracer_nothing_is_lowered_twice(count_lowerings):
    import os

    recorder.uninstall()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    service = _service(os.path.join(root, TINY_GLM))
    assert service.warmup()["compiles"] == 2
    assert count_lowerings == []


def test_with_a_tracer_the_engine_records_one_span_a_program(count_lowerings):
    import os

    from can_tpu.serve.kinds import TokenBatch
    import numpy as np

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    recorder.uninstall()
    tr = recorder.install(recorder.SpanTracer())
    try:
        service = _service(os.path.join(root, TINY_GLM))
        service.warmup()
        engine = service.engine
        slots, bucket = sorted(engine._warm)[0]
        batch = TokenBatch(np.zeros((slots, bucket), np.int32),
                           np.ones((slots,), np.int32),
                           np.ones((slots,), np.float32))
        engine.generate_batch(batch, steps=2)        # a second launch: warm
    finally:
        recorder.uninstall()
    ring = tr.snapshot()
    scopes = [s for s in ring if s["name"] == "program.scopes"]
    assert sorted(s["program"] for s in scopes) == ["jit_decode",
                                                    "jit_prefill_slice"]
    assert len(count_lowerings) == 2
    by_id = {s["span_id"]: s for s in ring}
    for s in scopes:
        parent = by_id[s["parent_id"]]
        assert parent["name"] == "serve.dispatch" and parent["compiled"]
        assert s["key"][1] == bucket
        assert s["instructions"] == len(s["parts"]) > 50
        assert s["unscoped"] == sum(p is None for p in s["parts"].values()) <= 3
        assert set(s["inherited"]) <= set(s["parts"])
        assert {"attn.core", "moe.experts", "head"} <= set(s["parts"].values())
        # the CPU's compiler copies what it likes: an integer, whatever it is
        assert type(s["cache_copies"]) is int and s["cache_copies"] >= 0
    assert {s["program"]: s["key"][0] for s in scopes} == {
        "jit_decode": slots, "jit_prefill_slice": engine._slices(slots)[0][1]}


# -- the operator's view -----------------------------------------------------
def test_trace_export_writes_each_device_op_with_its_part(tmp_path, capsys):
    """``--profile`` beside spans that hold ``program.scopes``: an op event
    of that program carries its part as its category and in ``args``, and
    ``--scopes`` prints the seconds by program and part (a profile of three
    launches recorded on a v5e, PR 23; the map is made up)."""
    import gzip
    import json
    import os

    from tools import trace_export

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "benchmark", "tests", "data",
                       "v5e_predict_b2_64x96_x3.xplane.pb.gz")
    pdir = tmp_path / "prof" / "plugins" / "profile" / "t"
    pdir.mkdir(parents=True)
    (pdir / "x.xplane.pb").write_bytes(gzip.open(src, "rb").read())

    def span(name, start, dur, **kw):
        return {"ts": start, "kind": "trace.span", "step": None, "host_id": 0,
                "payload": {"trace_id": "t", "span_id": name + str(start),
                            "parent_id": None, "name": name,
                            "start_s": start, "duration_s": dur, **kw}}

    events = [span("profile.window", 100.0, 1.0),
              span("serve.fetch", 100.4, 0.1, thread="batcher"),
              span("program.scopes", 90.0, 0.5, program="jit_predict",
                   key=[2, 64], instructions=3, unscoped=1, inherited=[],
                   cache_copies=4,
                   parts={"convert_bitcast_fusion": "embed",
                          "copy-done.14": "attn.cache", "copy.1": None})]
    tel = tmp_path / "telemetry.host0.jsonl"
    tel.write_text("".join(json.dumps(e) + "\n" for e in events))
    out = tmp_path / "doc.json"
    assert trace_export.main([str(tel), "--profile", str(tmp_path / "prof"),
                              "--scopes", "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    ops = [e for e in doc["traceEvents"] if e["ph"] == "X" and e["pid"] >= 1000
           and e["tid"] == 2]
    embed = [e for e in ops if e["cat"] == "embed"]
    assert len(embed) == 3                      # one a launch
    assert all(e["name"].startswith("%convert_bitcast_fusion = ")
               and e["args"] == {"part": "embed", "program": "jit_predict"}
               for e in embed)
    assert len([e for e in ops if e["cat"] == "attn.cache"]) == 3
    rest = [e for e in ops if e["cat"] == "device"]
    assert len(rest) == len(ops) - 6 and all(e["args"] == {} for e in rest)
    table = capsys.readouterr().out
    head, = [l for l in table.splitlines() if l.startswith("[scopes]")]
    assert head.startswith("[scopes] jit_predict: ")
    assert head.endswith(" s of ops, cache_copies 4")
    rows = {l.split()[0]: float(l.split()[1]) for l in table.splitlines()
            if l.startswith("  ")}
    assert set(rows) == {"embed", "attn.cache", "(none)"}
    assert rows["(none)"] > rows["embed"] > 0
    # --scopes without a profile, and a profile no span names a program of
    with pytest.raises(SystemExit):
        trace_export.main([str(tel), "--scopes"])
    tel.write_text("".join(json.dumps(e) + "\n" for e in events[:2]))
    assert trace_export.main([str(tel), "--profile", str(tmp_path / "prof"),
                              "--scopes", "--out", str(out)]) == 1
