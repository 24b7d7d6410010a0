"""The busiest held expert's decode tokens over the mean held expert's, per launch (all layers), averaged over the window's launches: the engine's per-launch counters.  1 is even routing."""


def read(ctx):
    return ctx["counters"].get("expert_load_max_over_mean")
