"""Median of the rates of the window's whole epochs: img_per_s without its slowest epochs.  The two drifting apart says stalls were added."""


def read(ctx):
    return (ctx["counters"].get("rate") or {}).get("segment_median")
