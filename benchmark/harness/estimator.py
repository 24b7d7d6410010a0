"""The rate of a window: all the work over all the time, from the start of
the window to the completion of its last unit.  The window is cut into
consecutive segments of equal work, each timed from completion to completion,
and every segment's rate is printed beside the result, so that a stall is
seen where it fell; the median of the segments is a per-layer diagnostic
(``seg_median_*``), never the rate: a change that adds stalls has to show."""

from __future__ import annotations

import statistics


def segment_rates(t_start: float, boundaries, work_per_segment: float):
    """``boundaries``: completion time of each segment's last unit of work."""
    rates, prev = [], t_start
    for t in boundaries:
        if t <= prev:
            raise ValueError(f"segment boundary {t} not after {prev}")
        rates.append(work_per_segment / (t - prev))
        prev = t
    return rates


def boundaries_from_log(completions, segment: int):
    """Completion times (any order) of single units -> the time of every
    ``segment``-th completion.  A trailing partial segment is dropped."""
    done = sorted(completions)
    return [done[i] for i in range(segment - 1, len(done), segment)]


def summarise(t_start: float, boundaries, work_per_segment: float) -> dict:
    rates = segment_rates(t_start, boundaries, work_per_segment)
    if not rates:
        raise ValueError("no whole segment completed in the window")
    window = boundaries[-1] - t_start
    return {"rate": work_per_segment * len(rates) / window,
            "segment_median": statistics.median(rates),
            "segments": rates, "window_s": window}
