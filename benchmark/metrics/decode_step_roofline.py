"""A decode step's bytes (harness/flops_lm.py: every weight once, the cache up to each context) over peak bandwidth, over the device time of the decode programs.  Memory-bound at 64 tokens a step; of the whole program, the trace carries no scope."""


def read(ctx):
    return ctx["trace"].get("decode_step_roofline_pct")
