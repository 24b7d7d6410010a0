"""Milliseconds per answered request from the call of the predict program to its return (the runtime's host-side layout change, the H2D enqueue, the launch): the program's serve.dispatch spans over the window's batches."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    return ring.phase_ms_per_img(batches, "serve.dispatch")
