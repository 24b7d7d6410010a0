"""The readers of source ``program_span``: each on a recorded list of spans,
what they do where there is nothing to read, and a whole traced run of the
tiny cells on the CPU."""
import json
import os
import types

import pytest

from benchmark import run
from benchmark.harness import program_spans, spec
from can_tpu.obs import spans as recorder

TINY = os.path.join(os.path.dirname(__file__), "tinybench")
SPEC = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NEW = [m for m in SPEC["per_layer"] if m["source"] == "program_span"
       and m["name"] != "exec_ms_per_img.serve"]
SERVE = [m["name"] for m in NEW if m["name"].endswith(".serve")]
TRAIN = [m["name"] for m in NEW if m["name"].endswith(".train")]


@pytest.fixture
def tracer():
    recorder.uninstall()
    tr = program_spans.arm()
    yield tr
    recorder.uninstall()


def record_serving(tr, *, leave_out=()):
    """Warm-up, three batches of the window and one traced launch, 0.2 s
    apart, every time written out by hand."""
    def put(name, t0, t1, sid=None, parent=None, trace="lane", **attrs):
        if name not in leave_out:
            tr.emit(trace_id=trace, name=name, start=t0, end=t1, span_id=sid,
                    parent_id=parent, **attrs)

    put("serve.dispatch", -5.0, -1.0, trace="warm", compiled=True, aot=False)
    for i in range(4):
        t, b = 0.2 * i, f"batch{i}"
        put("serve.wait", t, t + 0.05)
        put("serve.intake", t + 0.05, t + 0.19, sid=f"intake{i}", taken=4)
        put("serve.batch", t + 0.06, t + 0.18, sid=b, parent=f"intake{i}",
            trace=b, valid=4, slots=4, flush_reason="full")
        put("serve.pad", t + 0.06, t + 0.08, parent=b, trace=b)
        put("serve.dispatch", t + 0.08, t + 0.09, parent=b, trace=b,
            compiled=False, aot=False)
        put("serve.fetch", t + 0.09, t + 0.15, parent=b, trace=b)
        put("serve.complete", t + 0.15, t + 0.17, parent=b, trace=b)
        put("serve.poll", t + 0.19, t + 0.195)


SERVE_CTX = {"counters": {"rate": {"rate": 24.0, "window_s": 0.5}},
             "trace": {}, "end_to_end": {"req_per_s": 24.0}, "cell": None}
SERVE_WANT = {
    "pad_ms_per_img.serve": 1e3 * 0.06 / 12,
    "dispatch_ms_per_img.serve": 1e3 * 0.03 / 12,
    "fetch_ms_per_img.serve": 1e3 * 0.18 / 12,
    "complete_ms_per_img.serve": 1e3 * 0.06 / 12,
    # from the first batch's start (0.06) to the third's end (0.58): the
    # second and third cycles' waits
    "batcher_wait_pct.serve": 100 * 0.10 / 0.52,
    # two gaps of 5 ms between poll and wait, and 10 ms of each batch that
    # is neither pad, dispatch, fetch nor complete
    "cycle_unnamed_pct.serve": 100 * (0.01 + 0.03) / 0.52,
}


def record_training(tr, *, leave_out=()):
    """Set-up's three images, then two whole epochs of ten."""
    def put(name, t0, t1, trace, sid=None, **attrs):
        if name not in leave_out:
            tr.emit(trace_id=trace, name=name, start=t0, end=t1, span_id=sid,
                    parent_id=None if sid else "root-" + trace, **attrs)

    put("train_epoch", 50.0, 52.0, "setup", sid="root-setup", images=3.0)
    put("input.load", 50.0, 51.0, "setup")
    for k, t in enumerate((100.0, 110.5)):
        e = f"e{k}"
        put("train_epoch", t, t + 10.0, e, sid="root-" + e, images=10.0)
        put("train.turnover", t, t + 0.3, e)
        for j in range(5):
            put("input.load", t + j, t + j + 0.1, e, index=j)
            put("input.put", t + j + 0.1, t + j + 0.12, e, index=j)
        tr.emit(trace_id=e, name="input.load", start=t + 6, end=t + 9,
                parent_id="root-" + e, error="StopIteration")
        for d in (0.001, 0.002, 0.009):
            put("train.dispatch", t + 1, t + 1 + d, e)
        put("metric_flush", t + 4.0, t + 4.1, e)
        put("metric_flush", t + 9.5, t + 9.9, e)


TRAIN_CTX = {"counters": {"rate": {"rate": 1.0, "window_s": 20.0}}, "trace": {},
             "end_to_end": {"img_per_s": 1.0},
             "cell": types.SimpleNamespace(traffic={"n_images": 10})}
TRAIN_WANT = {
    "load_ms_per_img.train": 1e3 * 1.0 / 20,
    "put_ms_per_img.train": 1e3 * 0.2 / 20,
    # each epoch is fed from its first batch (0.3 s in) to the end of its
    # last metric_flush (9.9 s in); the rest of the 20.5 s is boundary
    "epoch_turnover_pct.train": 100 * (20.5 - 2 * 9.6) / 20.5,
    "dispatch_ms_per_step.train": 2.0,
}


def test_the_issue_s_ten_metrics_are_declared_with_their_cells():
    assert len(NEW) == 10 and set(SERVE_WANT) | set(TRAIN_WANT) == {
        m["name"] for m in NEW}
    for m in NEW:
        cell = "serve-shb-closed" if m["name"] in SERVE else "train-sha-varres"
        assert m["workloads"] == [cell]


@pytest.mark.parametrize("metric", SERVE)
def test_serve_reader_on_a_recorded_window(tracer, metric):
    record_serving(tracer)
    assert spec.load_metric_reader(metric)(SERVE_CTX) == pytest.approx(
        SERVE_WANT[metric], rel=1e-6)


@pytest.mark.parametrize("metric", TRAIN)
def test_train_reader_on_recorded_epochs(tracer, metric):
    record_training(tracer)
    assert spec.load_metric_reader(metric)(TRAIN_CTX) == pytest.approx(
        TRAIN_WANT[metric], rel=1e-6)


@pytest.mark.parametrize("metric,span", [
    ("pad_ms_per_img.serve", "serve.pad"),
    ("complete_ms_per_img.serve", "serve.complete"),
    ("dispatch_ms_per_img.serve", "serve.batch"),   # no steady batch is told
    ("fetch_ms_per_img.serve", "serve.fetch"),
    ("batcher_wait_pct.serve", "serve.wait"),
    ("cycle_unnamed_pct.serve", "serve.poll"),
    ("load_ms_per_img.train", "input.load"),
    ("put_ms_per_img.train", "input.put"),
    ("epoch_turnover_pct.train", "train.turnover"),
    ("dispatch_ms_per_step.train", "train.dispatch"),
])
def test_a_missing_span_raises_and_is_named(tracer, metric, span):
    serve = metric.endswith(".serve")
    gone = "serve.dispatch" if span == "serve.batch" else span
    (record_serving if serve else record_training)(tracer, leave_out=(gone,))
    with pytest.raises(program_spans.MissingSpan, match=repr(span)):
        spec.load_metric_reader(metric)(SERVE_CTX if serve else TRAIN_CTX)


def test_a_program_without_the_recorder_leaves_the_metrics_out(monkeypatch):
    monkeypatch.setattr(program_spans, "_recorder", lambda: None)
    assert program_spans.arm() is None and program_spans.read() is None
    for m in NEW:
        ctx = SERVE_CTX if m["name"] in SERVE else TRAIN_CTX
        assert spec.load_metric_reader(m["name"])(ctx) is None


def test_arm_twice_installs_one_tracer():
    recorder.uninstall()
    try:
        first = program_spans.arm()
        assert program_spans.arm() is first is recorder.active()
        spec.load_metric_reader("pad_ms_per_img.serve")   # arms as it loads
        assert recorder.active() is first
    finally:
        recorder.uninstall()


def test_marks_are_disjoint_and_innermost_first(tracer):
    record_serving(tracer)
    for s in tracer._ring:
        s["thread"] = "batcher"
    marks = program_spans.read().as_marks(0.0, 0.2)
    assert [m[0] for m in marks] == [
        "serve.wait", "serve.intake", "serve.pad", "serve.dispatch",
        "serve.fetch", "serve.complete", "serve.batch", "serve.intake",
        "serve.poll"]
    for (_, _, end), (_, start, _) in zip(marks, marks[1:]):
        assert start >= end - 1e-9
    assert sum(t1 - t0 for _, t0, t1 in marks) == pytest.approx(0.195)


def test_idle_gaps_are_named_by_the_program_s_spans(tracer, tmp_path):
    """tools/idle_by_span.py's Env on the trace recorded on a v5e (three
    launches; test_trace.py): the benchmark's launches keep their count and
    cover nothing, the program's spans name the gaps."""
    import gzip

    from benchmark.harness import device
    from benchmark.tools import idle_by_span

    src = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_predict_b2_64x96_x3.xplane.pb.gz")
    tdir = tmp_path / "plugins" / "profile" / "t"
    tdir.mkdir(parents=True)
    (tdir / "x.xplane.pb").write_bytes(gzip.open(src, "rb").read())
    launches = [{"key": "2x64x96", "batch": 2, "images": 2, "h": 64, "w": 96}] * 3
    bench = [("bench:launch", 100.0 + 0.004 * i, 100.0035 + 0.004 * i)
             for i in range(3)]
    for _, t0, t1 in bench:
        tracer.emit(trace_id="lane", name="serve.intake", start=t0 - 0.0004,
                    end=t1 + 0.0001, span_id=f"i{t0}", thread="batcher")
        tracer.emit(trace_id="b", name="serve.dispatch", start=t0, end=t0 + 0.001,
                    parent_id=f"i{t0}", thread="batcher", compiled=False)
        tracer.emit(trace_id="b", name="serve.fetch", start=t0 + 0.001, end=t1,
                    parent_id=f"i{t0}", thread="batcher")
    env = idle_by_span.SpanEnv(str(tmp_path), require_chip=False)
    env.peaks = device.peaks_for_kind("TPU v5 lite")
    reduced = env.reduce_trace(str(tmp_path), launches, spans=bench,
                               anchor=bench[-1][2], program_prefix="jit_predict",
                               n_devices=1, train=False)
    names = {n for n, _ in reduced["idle_gaps"]}
    assert names and names <= {"serve.intake", "serve.dispatch", "serve.fetch",
                               "no_program_span"}
    assert names & {"serve.dispatch", "serve.fetch"}
    assert reduced["idle_gaps"] == idle_by_span.SpanEnv.by_span


class CpuEnv(run.Env):
    """No chip to trace here: the traced launches run, the reduction is
    skipped (the readers of source device_trace then report nothing)."""

    def start_trace(self):
        return None

    def stop_trace(self):
        pass

    def reduce_trace(self, *a, **kw):
        return None


def _run(name, trace, tmp_path, monkeypatch):
    """The tiny benchmark with the new entries beside its own, each on the
    tiny cell of its kind."""
    tiny = json.load(open(os.path.join(TINY, "BENCHMARK.json")))
    tiny["per_layer"] += [
        dict(m, workloads=["tiny-serve" if m["name"] in SERVE else "tiny-train"])
        for m in NEW]
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps(tiny))
    monkeypatch.setattr(run, "Env", CpuEnv)
    return run.run_cell(name, 7, 0.5, trace, root=str(tmp_path),
                        require_chip=False, data_dir=TINY,
                        spec_path=str(spec_path))


@pytest.mark.parametrize("name,metrics", [("tiny-serve", SERVE),
                                          ("tiny-train", TRAIN)])
def test_a_traced_run_reports_every_new_metric(name, metrics, tmp_path,
                                               monkeypatch):
    recorder.uninstall()
    try:
        line = _run(name, True, tmp_path, monkeypatch)
        assert recorder.active() is not None
    finally:
        recorder.uninstall()
    assert line["correct"] is True
    for m in metrics:
        assert line["metrics"][m]["value"] >= 0.0, m
    if name == "tiny-serve":
        assert line["metrics"]["cycle_unnamed_pct.serve"]["value"] < 25.0
        assert line["metrics"]["exec_ms_per_img.serve"]["value"] > 0.0


def test_an_untraced_run_installs_no_tracer(tmp_path, monkeypatch):
    recorder.uninstall()
    line = _run("tiny-serve", False, tmp_path, monkeypatch)
    assert recorder.active() is None
    assert set(line["metrics"]) == {"req_per_s", "setup_s"}
