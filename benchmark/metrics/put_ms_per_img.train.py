"""Milliseconds per image the prefetch worker spends in put_fn (make_global_batch: the H2D): the program's input.put spans over the window's whole epochs."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.train_window(ctx)
    if found is None:
        return None
    ring, epochs = found
    puts = ring.in_epochs(epochs, "input.put")
    return 1e3 * sum(s["duration_s"] for s in puts) / sum(e["images"] for e in epochs)
