"""Pixels launched beyond the images' own, as a share of the images' pixels: ShardedBatcher.schedule_overhead (exact, host)."""


def read(ctx):
    return ctx["counters"].get("plan_pixel_overhead_pct")
