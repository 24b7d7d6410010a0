"""Share of the window the loop spent waiting for a batch that was not ready: the program's stall telemetry (StallClock) over the window."""


def read(ctx):
    return ctx["counters"].get("input_stall_pct")
