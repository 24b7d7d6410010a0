"""Milliseconds CountService._complete takes per answered request (resolve, telemetry, stats): the program's serve.complete spans over the window's batches, by their valid count."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    return ring.phase_ms_per_img(batches, "serve.complete")
