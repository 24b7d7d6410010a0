"""Share of the batcher thread's time, from the window's first batch to its last, that no phase names: what lies outside serve.wait / serve.intake / serve.poll, plus what of each serve.batch is neither pad, dispatch, fetch nor complete.  The instrumentation's own check."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    lo, hi, cycle = ring.batcher_interval(batches)
    named = program_spans.union_length(
        (max(s["start_s"], lo), min(s["start_s"] + s["duration_s"], hi))
        for s in cycle)
    for b in batches:
        for phase in program_spans.BATCH_PHASES:
            ring.phase(b, phase)
    unnamed = (hi - lo) - named + sum(ring.self_time(b) for b in batches)
    return 100.0 * unnamed / (hi - lo)
