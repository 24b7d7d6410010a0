"""Host-timed predict_batch (to the fetched counts) per answered request: the service's serve.batch execute_s over the window."""


def read(ctx):
    return ctx["counters"].get("exec_ms_per_img")
