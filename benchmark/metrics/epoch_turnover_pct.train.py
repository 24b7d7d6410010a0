"""Share of the window's wall time an epoch boundary costs: from the end of an epoch's last metric_flush (the device has drained) to the end of the next epoch's train.turnover (the first batch of a new prefetcher), over the time from the first whole epoch's start to the last one's end."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.train_window(ctx)
    if found is None:
        return None
    ring, epochs = found
    wall = epochs[-1]["start_s"] + epochs[-1]["duration_s"] - epochs[0]["start_s"]
    fed = 0.0   # from an epoch's first batch to its last fetch of metrics
    for e in epochs:
        (turn,) = ring.in_epochs([e], "train.turnover")
        last = max(ring.in_epochs([e], "metric_flush"), key=lambda s: s["start_s"])
        fed += (last["start_s"] + last["duration_s"]
                - turn["start_s"] - turn["duration_s"])
    return 100.0 * (wall - fed) / wall
