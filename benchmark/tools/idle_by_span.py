"""The device's idle seconds in a cell's traced launches, by the PROGRAM's
spans, and where the window's host time went, span by span.

``breakdown.idle_gaps`` names an idle gap after the benchmark's own spans
(``bench:launch``, ``bench:put``), because ``harness/trace.py`` computes it
from the spans the drivers hand it.  This tool runs one cell traced with an
``Env`` whose ``reduce_trace`` hands the same reduction the program's spans
instead (``program_spans.as_marks``: what the launching thread was doing,
innermost span first) and keeps the benchmark's launches only as marks of
no width, for the reduction's count of them.  Nothing of the harness is
edited; what a later benchmark change has to do is one line in each driver.

    chiprun --chips 1 -- python3 -m benchmark.tools.idle_by_span \\
        --workload serve-shb-closed --seed 2200000001 --seconds 30

Prints ``[spans]`` (per span name over the measured window: count, total
seconds, and the launching thread's own seconds under that name),
``[idle by span]`` (seconds of the traced window's idle time per span name,
``no_program_span`` for the rest) and the cell's result line.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys

from benchmark import run
from benchmark.harness import program_spans


class SpanEnv(run.Env):
    """``Env`` whose idle gaps are named by the program's spans."""

    by_span = None      # the last reduction's table, for main() to print

    def reduce_trace(self, tdir, launches, *, spans, anchor, **kw):
        ring = program_spans.read()
        lo = min(t0 for _, t0, _ in spans)
        marks = ring.as_marks(lo - 1.0, anchor + 1.0)
        zero_width = [(name, t0, t0) for name, t0, _ in spans]
        reduced = super().reduce_trace(tdir, launches, spans=zero_width + marks,
                                       anchor=anchor, **kw)
        SpanEnv.by_span = [["no_program_span" if n == "no_bench_span" else n, s]
                           for n, s in reduced["idle_gaps"]]
        reduced["idle_gaps"] = SpanEnv.by_span
        return reduced


def window_table(ring, cell):
    """Per span name over the measured window: how many, their total
    seconds, and the seconds of the launching thread that lie under that
    name and no deeper one."""
    if cell.config["driver"] == "serve":
        # the steady batches less the traced launches at their end
        batches = ring.steady_batches()[:-run.Env.traced_launches]
        lo, hi, _ = ring.batcher_interval(batches)
        images = sum(b["valid"] for b in batches)
    else:
        epochs = ring.whole_epochs(cell)
        lo = epochs[0]["start_s"]
        hi = epochs[-1]["start_s"] + epochs[-1]["duration_s"]
        images = sum(e["images"] for e in epochs)
    rows = collections.defaultdict(lambda: [0, 0.0, 0.0])
    for s in ring.spans:
        if lo <= s["start_s"] < hi and "error" not in s:
            rows[s["name"]][0] += 1
            rows[s["name"]][1] += s["duration_s"]
    for name, t0, t1 in ring.as_marks(lo, hi):
        rows[name][2] += t1 - t0
    return rows, images, hi - lo


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    if program_spans.arm() is None:
        print("idle_by_span: this program has no span recorder "
              "(can_tpu.obs.spans.install)", file=sys.stderr)
        return 2
    from benchmark.harness import spec

    run.Env = SpanEnv   # run_cell builds its Env by this name
    line = run.run_cell(args.workload, args.seed, args.seconds, True)
    rows, images, wall = window_table(program_spans.read(),
                                      spec.load_cell(args.workload))
    print(f"[spans] over the measured window: {images} images, {wall:.3f} s; "
          f"self_s is the launching thread's time under the name and no "
          f"deeper one (unnamed {wall - sum(r[2] for r in rows.values()):.4f} s)",
          flush=True)
    for name, (n, total, own) in sorted(rows.items()):
        print(f"[spans] {name}: n {n} total_s {total:.4f} self_s {own:.4f} "
              f"self_ms_per_img {1e3 * own / images:.4f} "
              f"self_pct {100.0 * own / wall:.2f}", flush=True)
    idle = sum(s for _, s in SpanEnv.by_span)
    for name, s in SpanEnv.by_span:
        print(f"[idle by span] {name}: {s:.6f} s "
              f"({100.0 * s / idle:.1f}% of {idle:.4f} s idle in the traced "
              f"window of {line['device']['window_s']:.4f} s)", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
