"""The program's own spans (``can_tpu/obs/spans.py``), read from its
in-memory ring: what the per-layer readers of source ``program_span`` read.

The drivers hand a reader no way to the program's spans, so a reader
fetches them from the program itself: its module calls ``arm()`` when it is
loaded, which installs a ring-only tracer for the process.  ``run.py`` loads
the readers before the driver runs and only under ``--trace 1``, so a
``--trace 0`` run installs nothing and is the tracing-off run.

A program without the recorder (a parent commit before it) has nothing to
arm: ``arm()`` and ``read()`` return None there, and a reader leaves its
metric out.  With the recorder, a reader that finds none of its spans in a
run that completed work raises ``MissingSpan``: a silent null would hide a
span that a later change removed.
"""

from __future__ import annotations

import collections
import heapq

CYCLE = ("serve.wait", "serve.intake", "serve.poll")
BATCH_PHASES = ("serve.pad", "serve.dispatch", "serve.fetch", "serve.complete")
LAUNCH = ("serve.dispatch", "train.dispatch")


class MissingSpan(RuntimeError):
    """The run completed work and the ring holds no span of this name."""

    def __init__(self, name: str, where: str):
        super().__init__(f"no {name!r} span {where}: the program no longer "
                         f"records it, or the reader looks in the wrong place")


def _recorder():
    try:
        from can_tpu.obs import spans
    except ImportError:
        return None
    return spans if hasattr(spans, "install") else None


def arm():
    """Install a ring-only tracer for the process unless one is installed.
    Returns it, or None where the program has no recorder."""
    mod = _recorder()
    if mod is None:
        return None
    return mod.active() or mod.install(mod.SpanTracer())


def read():
    """The ring as it stands, indexed; None where the program has no
    recorder or none is installed."""
    mod = _recorder()
    tracer = mod.active() if mod is not None else None
    return None if tracer is None else Ring(tracer.snapshot(), mod.self_time)


def window_requests(ctx):
    """How many requests (images) the measured window completed, from the
    driver's own rate; None when the run measured no window."""
    rate = ctx["counters"].get("rate")
    return None if not rate else int(round(rate["rate"] * rate["window_s"]))


def serve_window(ctx):
    """``(ring, the measured window's steady batches)``; None where there is
    nothing to read (no recorder, or the run measured no window)."""
    ring, n = read(), window_requests(ctx)
    if ring is None or n is None:
        return None
    batches = ring.steady_batches(n)
    if not batches:
        raise MissingSpan("serve.batch", "in the window")
    return ring, batches


def train_window(ctx):
    """``(ring, the measured window's whole epochs)``; None as above."""
    ring = read()
    if ring is None or window_requests(ctx) is None:
        return None
    epochs = ring.whole_epochs(ctx["cell"])
    if not epochs:
        raise MissingSpan("train_epoch", "of a whole epoch")
    return ring, epochs


def _end(span):
    return span["start_s"] + span["duration_s"]


def union_length(intervals):
    total, edge = 0.0, float("-inf")
    for s, e in sorted(intervals):
        s = max(s, edge)
        if e > s:
            total += e - s
            edge = e
    return total


def innermost(spans):
    """Spans of one thread, which may nest -> disjoint ``(name, t0, t1)``:
    every instant goes to the span opened last among those open at it (of
    two opened together, the shorter).  Ends that miss each other by the
    rounding of a microsecond do no harm."""
    todo = sorted(spans, key=lambda s: (s["start_s"], -s["duration_s"]))
    points = sorted({t for s in todo for t in (s["start_s"], _end(s))})
    out, open_, i = [], [], 0   # open_: heap, the span opened last on top
    for a, b in zip(points, points[1:]):
        while i < len(todo) and todo[i]["start_s"] <= a:
            s = todo[i]
            heapq.heappush(open_, (-s["start_s"], s["duration_s"], i, s))
            i += 1
        while open_ and _end(open_[0][3]) <= a:
            heapq.heappop(open_)
        if open_:
            name = open_[0][3]["name"]
            if out and out[-1][0] == name and out[-1][2] == a:
                out[-1] = (name, out[-1][1], b)
            else:
                out.append((name, a, b))
    return out


class Ring:
    """One snapshot of the program's spans."""

    def __init__(self, spans, self_time_of):
        self.spans = spans
        self._self_time = self_time_of
        self.by_id = {s["span_id"]: s for s in spans}
        self.children = collections.defaultdict(list)
        self.by_trace = collections.defaultdict(list)
        for s in spans:
            self.children[s.get("parent_id")].append(s)
            self.by_trace[s["trace_id"]].append(s)

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def self_time(self, span) -> float:
        """The span's duration less what its children cover."""
        return self._self_time(span, self.children[span["span_id"]])

    # -- serving ---------------------------------------------------------
    def steady_batches(self, requests=None):
        """The ``serve.batch`` spans whose ``serve.dispatch`` compiled
        nothing, oldest first: the window's and the traced launches', not
        warm-up's.  ``requests``: only the batches that answered the first
        so many requests (the measured window's)."""
        out, answered = [], 0
        for b in sorted(self.named("serve.batch"), key=lambda s: s["start_s"]):
            kids = {k["name"]: k for k in self.children[b["span_id"]]}
            launch = kids.get("serve.dispatch")
            if launch is None or launch.get("compiled"):
                continue
            if requests is not None and answered >= requests:
                break
            answered += b["valid"]
            out.append(b)
        return out

    def phase(self, batch, name):
        for k in self.children[batch["span_id"]]:
            if k["name"] == name:
                return k
        raise MissingSpan(name, f"under the serve.batch {batch['span_id']}")

    def phase_ms_per_img(self, batches, name) -> float:
        total = sum(self.phase(b, name)["duration_s"] for b in batches)
        return 1e3 * total / sum(b["valid"] for b in batches)

    def batcher_interval(self, batches):
        """``(t_lo, t_hi, cycle spans clipped to it)``: the batcher thread's
        time from the first of ``batches`` to the end of the last, and its
        wait / intake / poll spans inside."""
        lo, hi = batches[0]["start_s"], max(_end(b) for b in batches)
        lane = self.by_id[batches[0]["parent_id"]]["trace_id"]
        cycle = [s for s in self.by_trace[lane]
                 if s["name"] in CYCLE and _end(s) > lo and s["start_s"] < hi]
        for name in CYCLE:
            if not any(s["name"] == name for s in cycle):
                raise MissingSpan(name, "on the batcher thread's lane")
        return lo, hi, cycle

    # -- training --------------------------------------------------------
    def whole_epochs(self, cell):
        """The ``train_epoch`` roots whose ``images`` is the traffic's image
        count: the window's epochs, not set-up's first steps nor the traced
        launches."""
        n = cell.traffic["n_images"]
        return sorted((s for s in self.named("train_epoch") if s["images"] == n),
                      key=lambda s: s["start_s"])

    def in_epochs(self, epochs, name):
        """The spans called ``name`` of the epochs' traces."""
        found = [s for e in epochs for s in self.by_trace[e["trace_id"]]
                 if s["name"] == name and "error" not in s]
        if not found:
            raise MissingSpan(name, "in the window's train_epoch traces")
        return found

    # -- for harness/trace.py::place_spans -------------------------------
    def as_marks(self, t_lo: float, t_hi: float):
        """``(name, t0, t1)`` on ``perf_counter``, disjoint: what the thread
        that launches the programs was doing between ``t_lo`` and ``t_hi``,
        each instant under the innermost span open on that thread."""
        inside = [s for s in self.spans
                  if s.get("thread") and _end(s) > t_lo and s["start_s"] < t_hi]
        launches = collections.Counter(s["thread"] for s in inside
                                       if s["name"] in LAUNCH)
        if not launches:
            raise MissingSpan(" / ".join(LAUNCH), "in the traced interval")
        thread = launches.most_common(1)[0][0]
        return [(n, max(a, t_lo), min(b, t_hi))
                for n, a, b in innermost([s for s in inside
                                          if s["thread"] == thread])
                if b > t_lo and a < t_hi]
