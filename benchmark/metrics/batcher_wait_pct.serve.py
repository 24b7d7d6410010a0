"""Share of the batcher thread's time, from the window's first batch to its last, spent waiting for the queue (serve.wait): what the load generator leaves idle."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    lo, hi, cycle = ring.batcher_interval(batches)
    waited = sum(min(s["start_s"] + s["duration_s"], hi) - max(s["start_s"], lo)
                 for s in cycle if s["name"] == "serve.wait")
    return 100.0 * waited / (hi - lo)
