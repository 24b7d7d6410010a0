"""Median of the rates of the window's segments of 64 completed requests: req_per_s without its stalls.  The two drifting apart says stalls were added."""


def read(ctx):
    return (ctx["counters"].get("rate") or {}).get("segment_median")
