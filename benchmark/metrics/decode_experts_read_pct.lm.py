"""Share of the held experts' weights that the window's decode steps read: the program's serve.fetch spans (of the window's batches) carry `experts_read`, the held experts whose weights the launch's decode steps read summed over expert layers and steps, and `experts_held`, those they could have read; 100 x read / held over the window.  About 64 where 16 tokens' top-4 of 64 experts meet the skipping kernel (ops/pallas_experts.py), 100 where the batched form reads every held expert; nothing where no span carries the attributes (a program from before PR 33)."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    read_ = held = 0
    for b in batches:
        fetch = ring.phase(b, "serve.fetch")
        if "experts_held" in fetch:
            read_ += fetch["experts_read"]
            held += fetch["experts_held"]
    return 100.0 * read_ / held if held else None
