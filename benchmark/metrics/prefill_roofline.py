"""The prefill's operations for its valid tokens (harness/flops_lm.py) over peak FLOP/s, over the device time of the prefill programs.  Compute-bound; of the whole program, the trace carries no scope."""


def read(ctx):
    return ctx["trace"].get("prefill_roofline_pct")
