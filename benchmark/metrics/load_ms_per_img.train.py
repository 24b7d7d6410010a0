"""Milliseconds per image the prefetch worker spends in next(it) (the planner's schedule, decode, collate): the program's input.load spans over the window's whole epochs.  With put_ms_per_img it is the single worker's ceiling: 1000 / (load + put) img/s."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.train_window(ctx)
    if found is None:
        return None
    ring, epochs = found
    loads = ring.in_epochs(epochs, "input.load")
    return 1e3 * sum(s["duration_s"] for s in loads) / sum(e["images"] for e in epochs)
