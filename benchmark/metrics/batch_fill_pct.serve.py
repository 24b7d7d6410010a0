"""Requests per launched slot over the window: CountService.stats() batch_valid / batch_slots."""


def read(ctx):
    return ctx["counters"].get("batch_fill_pct")
