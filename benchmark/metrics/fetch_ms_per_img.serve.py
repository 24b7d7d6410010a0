"""Milliseconds per answered request the engine waits for the device and copies the answer back: the program's serve.fetch spans over the window's batches."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    return ring.phase_ms_per_img(batches, "serve.fetch")
