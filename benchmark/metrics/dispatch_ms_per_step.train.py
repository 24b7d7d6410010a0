"""Median milliseconds the loop thread is held in the call of the train step (train.dispatch) over the window's whole epochs: whether the loop is held in dispatch or on input."""

import statistics

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.train_window(ctx)
    if found is None:
        return None
    ring, epochs = found
    return 1e3 * statistics.median(
        s["duration_s"] for s in ring.in_epochs(epochs, "train.dispatch"))
