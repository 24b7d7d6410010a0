"""The rate estimator on a synthetic completion log with a stall: the rate
pays the stall, the segments show where it fell, their median leaves it out."""
import pytest

from benchmark.harness import estimator


def _log(rate, n, stall_at=None, stall_s=0.0):
    t, out = 0.0, []
    for i in range(n):
        t += 1.0 / rate
        if i == stall_at:
            t += stall_s
        out.append(t)
    return out


def test_the_rate_pays_a_stall_and_the_segment_median_leaves_it_out():
    log = _log(50.0, 640, stall_at=200, stall_s=0.5)
    est = estimator.summarise(0.0, estimator.boundaries_from_log(log, 64), 64.0)
    assert len(est["segments"]) == 10
    # all the work over all the time: 640 / 13.3 s
    assert est["rate"] == pytest.approx(640 / (12.8 + 0.5), rel=1e-9)
    assert est["window_s"] == pytest.approx(13.3)
    assert est["segment_median"] == pytest.approx(50.0, rel=1e-9)
    slow = [r for r in est["segments"] if r < 49.9]
    assert len(slow) == 1 and slow[0] == pytest.approx(64 / (1.28 + 0.5))


def test_partial_trailing_segment_is_dropped_and_order_does_not_matter():
    log = _log(10.0, 70)
    b = estimator.boundaries_from_log(list(reversed(log)), 32)
    assert b == [log[31], log[63]]


def test_no_whole_segment_is_an_error():
    with pytest.raises(ValueError):
        estimator.summarise(0.0, [], 64.0)
    with pytest.raises(ValueError):
        estimator.segment_rates(1.0, [0.5], 64.0)


@pytest.mark.parametrize("stall_s", [0.1, 0.5, 2.0])
def test_the_rate_is_the_harmonic_mean_of_its_segments(stall_s):
    log = _log(40.0, 320, stall_at=100, stall_s=stall_s)
    est = estimator.summarise(0.0, estimator.boundaries_from_log(log, 64), 64.0)
    assert est["rate"] == pytest.approx(5 / sum(1 / r for r in est["segments"]))
