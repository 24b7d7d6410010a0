"""Share of the window's steady batches that were handed to a launch lane while another launch was in flight: the program's serve.batch spans whose in_flight is at least 1.  Left out where no batch carries the attribute (a program without launch lanes)."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    _, batches = found
    counted = [b["in_flight"] for b in batches if "in_flight" in b]
    if not counted:
        return None
    return 100.0 * sum(n >= 1 for n in counted) / len(counted)
