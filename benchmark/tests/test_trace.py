"""The reduction on a small trace recorded on a v5e (three launches of a
b2 x 64x96 bf16 predict program, PR 23), and doctored copies that trip each
of its three checks."""
import copy
import os

import pytest

from benchmark.harness import device, trace

PATH = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_predict_b2_64x96_x3.xplane.pb.gz")
PEAKS = device.peaks_for_kind("TPU v5 lite")
LAUNCHES = [{"key": "2x64x96", "batch": 2, "images": 2, "h": 64, "w": 96}] * 3
KW = dict(program_prefix="jit_predict", peaks=PEAKS, n_devices=1, train=False)


@pytest.fixture(scope="module")
def events():
    return trace.load(PATH)


def test_what_the_trace_holds(events):
    assert list(events.devices) == ["/device:TPU:0"]
    lines = events.devices["/device:TPU:0"]
    assert len(lines["modules"]) == 3
    assert all(m[0].startswith("jit_predict(") for m in lines["modules"])
    assert len(lines["ops"]) > 100
    assert [m[0] for m in events.marks] == [trace.LAUNCH] * 3
    assert events.marks[0][3] == {"shape": "2x64x96"}


def test_reduction_reads_what_a_chip_can_do(events):
    r = trace.reduce(copy.deepcopy(events), LAUNCHES, **KW)
    assert r["launches"] == 1 and r["images"] == 2
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["contraction_roofline_pct"] <= 100
    assert r["device_ms_per_img"] > 0
    assert len(r["device_ops"]) == 10 and r["idle_gaps"]
    assert {g[0] for g in r["idle_gaps"]} <= {trace.LAUNCH, "no_bench_span"}


def test_check_a_a_launch_missing_on_the_device(events):
    bad = copy.deepcopy(events)
    bad.devices["/device:TPU:0"]["modules"].pop()
    with pytest.raises(trace.ImpossibleReading, match=r"\(a\).*2 executions.*3 launches"):
        trace.reduce(bad, LAUNCHES, **KW)


def test_check_a_a_launch_the_host_did_not_count(events):
    with pytest.raises(trace.ImpossibleReading, match=r"\(a\) the host annotated 3"):
        trace.reduce(copy.deepcopy(events), LAUNCHES[:2], **KW)


def test_check_a_dropped_device_ops(events):
    bad = copy.deepcopy(events)
    ops = bad.devices["/device:TPU:0"]["ops"]
    bad.devices["/device:TPU:0"]["ops"] = ops[: len(ops) // 3]
    with pytest.raises(trace.ImpossibleReading, match="dropped device events"):
        trace.reduce(bad, LAUNCHES, **KW)


def test_check_b_device_time_under_the_roofline(events):
    bad = copy.deepcopy(events)
    for key in ("modules", "ops"):
        bad.devices["/device:TPU:0"][key] = [
            (n, s, d / 100.0) for n, s, d in bad.devices["/device:TPU:0"][key]]
    with pytest.raises(trace.ImpossibleReading, match=r"\(b\).*floor"):
        trace.reduce(bad, LAUNCHES, **KW)


def test_check_c_busy_share_under_what_the_images_need(events):
    crowded = [dict(l, images=400) for l in LAUNCHES]
    with pytest.raises(trace.ImpossibleReading, match=r"\(c\).*busy"):
        trace.reduce(copy.deepcopy(events), crowded, **KW)


@pytest.mark.parametrize("name,want", [
    ("%fusion.4 = bf16[16,768,1024,64]{3,0,2,1:T(8,128)(2,1)} fusion(bf16[3,3,64,64]{3,2,1,0} %copy-done.10, bf16[16,768,1024,64] %x), kind=kOutput, calls=%fused_computation.4", True),
    ("%multiply_add_fusion.42 = (f32[3,3,64,64]{3,2,1,0}, f32[3,3,64,64]{3,2,1,0}) fusion(f32[3,3,64,64] %p)", True),
    ("%broadcast_maximum_fusion = (bf16[8,576,768,64]{3,0,2,1}) fusion(bf16[64]{0} %b)", False),
    ("%copy.60 = bf16[3,3,64,64]{0,3,2,1} copy(bf16[3,3,64,64]{2,1,3,0} %c)", False),
])
def test_contraction_classifier(name, want):
    assert trace.is_contraction(name) is want


def test_host_spans_are_placed_on_the_device_clock_by_the_last_program(events):
    ev = copy.deepcopy(events)
    mods = ev.devices["/device:TPU:0"]["modules"]
    last_end = mods[-1][1] + mods[-1][2]
    # three launches on a host clock that started elsewhere; the last one
    # returned when the last program ended
    spans = [("bench:launch", 100.0 + 0.004 * i, 100.0 + 0.004 * i + 0.0035)
             for i in range(3)]
    trace.place_spans(ev, spans, anchor_host_s=spans[-1][2], program_prefix="jit_predict")
    assert [m[0] for m in ev.marks] == ["bench:launch"] * 3
    assert ev.marks[-1][1] + ev.marks[-1][2] == pytest.approx(last_end)
    assert ev.marks[0][1] == pytest.approx(last_end - (0.008 + 0.0035) * 1e9)
    r = trace.reduce(ev, LAUNCHES, **KW)
    assert r["idle_gaps"]
