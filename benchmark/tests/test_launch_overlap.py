"""The reader of ``launch_overlap_pct.serve`` on spans written out by hand:
what it counts, and what it does where no batch says how many launches were
in flight (a program without launch lanes: the parent of the PR that added
it)."""
import pytest

from benchmark.harness import program_spans, spec
from can_tpu.obs import spans as recorder

CTX = {"counters": {"rate": {"rate": 24.0, "window_s": 0.5}}, "trace": {},
       "end_to_end": {"req_per_s": 24.0}, "cell": None}


@pytest.fixture
def tracer():
    recorder.uninstall()
    tr = program_spans.arm()
    yield tr
    recorder.uninstall()


def record(tr, in_flight):
    """Warm-up, then one batch of 4 per entry of ``in_flight`` (None: the
    batch does not carry the attribute), 0.2 s apart; the window's rate
    says its first three answered the window's 12 requests."""
    tr.emit(trace_id="warm", name="serve.dispatch", start=-5.0, end=-1.0,
            compiled=True)
    for i, n in enumerate(in_flight):
        t, b = 0.2 * i, f"batch{i}"
        attrs = {} if n is None else {"in_flight": n}
        tr.emit(trace_id=b, name="serve.batch", start=t, end=t + 0.3,
                span_id=b, parent_id=f"intake{i}", valid=4, slots=4, **attrs)
        tr.emit(trace_id=b, name="serve.dispatch", start=t + 0.02,
                end=t + 0.03, parent_id=b, compiled=False)


@pytest.mark.parametrize("in_flight, want", [
    ([0, 1, 1, 0], 100 * 2 / 3),     # the fourth batch is past the window
    ([1, 1, 2, 0], 100.0),
    ([0, 0, 0, 1], 0.0),
    ([None, None, None, None], None),  # no lanes in the program: left out
])
def test_share_of_the_windows_batches_with_a_launch_in_flight(
        tracer, in_flight, want):
    record(tracer, in_flight)
    got = spec.load_metric_reader("launch_overlap_pct.serve")(CTX)
    assert got == (want if want is None else pytest.approx(want))


def test_nothing_to_read_without_a_recorder_or_a_window(tracer, monkeypatch):
    read = spec.load_metric_reader("launch_overlap_pct.serve")
    record(tracer, [1, 1, 1])
    assert read(dict(CTX, counters={})) is None
    monkeypatch.setattr(program_spans, "_recorder", lambda: None)
    assert read(CTX) is None
