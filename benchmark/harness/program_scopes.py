"""Device time by model part: the traced launches' op events summed by the
part of the model each belongs to, for the two programs of a language-model
cell.

The program says which part each instruction of a compiled program belongs
to: with a tracer active, ``LMEngine`` records at a program's first launch
one span ``program.scopes`` (``program``: the name its executions carry on
the trace's ``XLA Modules`` line, ``parts``: {instruction name: part or
null}, ``inherited``: the instructions that took their part from the op
that uses their result; ``can_tpu/obs/trace.py``).  The device trace names
each op event by its HLO text, whose head is that instruction name.  This
module joins the two.

The drivers load the device events, reduce them and delete the trace
directory before any reader runs, and hand a reader only the reduced dict.
So, as ``program_spans.arm()`` fetches the spans from the program, ``arm()``
here (called when a reader's module is loaded: before the driver runs, under
``--trace 1`` only) makes ``benchmark.harness.trace.load`` REMEMBER the
``Events`` it returns, by a wrapper that returns exactly what ``load``
returns; the three language-model drivers call it as ``trace.load(...)``,
through the module.  Nothing an existing metric reads changes.  **The next
``benchmark`` issue's fold of the three drivers (PERF.md section 7) should
hand the readers the events and delete the wrapper.**

``read(ctx)`` -> for ``prefill`` (``jit_prefill_slice``) and ``decode``
(``jit_decode``), over the executions that ``trace_lm*.reduce`` reads (all
traced launches but the last): seconds by part.  Every instant of an
execution goes to ONE op, the one started last among those running (ops
overlap little on one core; what they do overlap is printed), so the parts
with the unscoped rest are a partition of the programs' busy time.  None
where the ring holds no ``program.scopes`` span (a program from before the
span: every metric that reads this leaves itself out) or no trace was
loaded.  Its checks raise like its neighbours':

 (a) op events whose instruction the map does not know hold under 1% of the
     programs' op time (else the map is of another compile);
 (b) the parts with the unscoped rest sum to the executions' ``XLA Modules``
     time within ``SUM_TOLERANCE``;
 (c) a family of parts that the program's map holds (``attn.``, ``moe.``,
     ``ssm.``, ...) is not zero.

It prints one line a program, ``[scopes] decode (ms a step): attn.proj ...``,
with every part the program's map holds (the vocabulary itself is the
program's), so that PERF.md can quote parts that have no metric.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import re

from benchmark.harness import program_spans, trace
from benchmark.harness.program_spans import MissingSpan
from benchmark.harness.trace import ImpossibleReading

SPAN = "program.scopes"
PROGRAMS = {"prefill": "jit_prefill_slice", "decode": "jit_decode"}
UNKNOWN_LIMIT = 0.01     # (a)
SUM_TOLERANCE = 0.02     # (b)
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = ")

_loaded: list = []       # the Events of trace.load's last call
_reduced: dict = {}      # id(those Events) -> what read() returned for them


def arm():
    """Arm the program's recorder (``program_spans.arm``) and make
    ``trace.load`` remember what it returns."""
    program_spans.arm()
    inner = trace.load
    if getattr(inner, "remembers", False):
        return

    def load(path):
        events = inner(path)
        _loaded[:] = [events]
        return events

    load.remembers = True
    trace.load = load


def instruction_of(event_name: str) -> str:
    """``%fusion.4 = bf16[16,64]{...} fusion(...)`` -> ``fusion.4``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def exclusive_ns(ops, lo: float, hi: float) -> collections.Counter:
    """``ops`` (name, start, duration), sorted by start, clipped to
    ``lo..hi`` -> {name: nanoseconds}, every instant given to the op started
    last among those running at it."""
    out, running, now = collections.Counter(), [], lo   # heap: (-start, end, name)

    def advance(to):
        nonlocal now
        while now < to:
            while running and running[0][1] <= now:
                heapq.heappop(running)
            if not running:
                now = to
                return
            _, end, name = running[0]
            upto = min(end, to)
            out[name] += upto - now
            now = upto

    for name, start, dur in ops:
        advance(min(max(start, lo), hi))
        heapq.heappush(running, (-start, min(start + dur, hi), name))
    advance(hi)
    return out


def traced_launches(ring, n_prefill: int, n_decode: int):
    """The launches the trace holds, oldest first, as (slices, steps, valid
    prompt tokens): the ring's newest ``lm.prefill`` / ``lm.decode`` spans
    whose slices and steps add up to the executions on the device."""
    pre = sorted(ring.named("lm.prefill"), key=lambda s: s["start_s"])
    dec = sorted(ring.named("lm.decode"), key=lambda s: s["start_s"])
    if not pre or len(pre) != len(dec):
        raise MissingSpan("lm.prefill / lm.decode", "one of each a launch")
    out, slices, steps = [], 0, 0
    for p, d in zip(reversed(pre), reversed(dec)):
        if (slices, steps) == (n_prefill, n_decode):
            break
        out.append((int(p["slices"]), int(d["steps"]), int(p["valid_tokens"])))
        slices, steps = slices + out[-1][0], steps + out[-1][1]
    if (slices, steps) != (n_prefill, n_decode):
        raise ImpossibleReading(
            f"(a) the device ran {n_prefill} prefill slices and {n_decode} "
            f"decode steps, the ring's newest launches add up to {slices} "
            f"and {steps}")
    return out[::-1]


def _map_of(scopes, program: str) -> dict:
    """The one ``program.scopes`` span of ``program``."""
    mine = [s for s in scopes if s["program"] == program]
    if not mine:
        raise MissingSpan(f"{SPAN} of {program}", "in the ring")
    if any(s["parts"] != mine[0]["parts"] for s in mine[1:]):
        raise ImpossibleReading(
            f"{len(mine)} programs were compiled as {program} "
            f"(keys {[s['key'] for s in mine]}): an execution does not say "
            f"which one it is")
    return mine[0]


def reduce(events, ring) -> dict:
    """See the module's docstring."""
    scopes = ring.named(SPAN)
    (plane, lines), = events.devices.items()
    mods = {k: [m for m in lines["modules"] if m[0].startswith(name)]
            for k, name in PROGRAMS.items()}
    launches = traced_launches(ring, len(mods["prefill"]), len(mods["decode"]))
    read = launches[:-1]
    if not read:
        raise ImpossibleReading(f"only {len(launches)} launches traced")
    counts = {"prefill": sum(l[0] for l in read), "decode": sum(l[1] for l in read)}
    ops = lines["ops"]
    starts = [o[1] for o in ops]
    out = {}
    for kind, program in PROGRAMS.items():
        span = _map_of(scopes, program)
        parts, inherited = span["parts"], set(span.get("inherited", ()))
        by_inst, plain, module_ns = collections.Counter(), 0.0, 0.0
        for _, ms, md in mods[kind][:counts[kind]]:
            inside = ops[bisect.bisect_left(starts, ms):
                         bisect.bisect_left(starts, ms + md)]
            by_inst.update(exclusive_ns(inside, ms, ms + md))
            plain += sum(min(s + d, ms + md) - s for _, s, d in inside)
            module_ns += md
        seconds = collections.Counter()
        unknown = unscoped = inherited_ns = 0.0
        for name, ns in by_inst.items():
            inst = instruction_of(name)
            if inst not in parts:
                unknown += ns
            elif parts[inst] is None:
                unscoped += ns
            else:
                seconds[parts[inst]] += ns * 1e-9
                if inst in inherited:
                    inherited_ns += ns
        total = sum(by_inst.values())
        if unknown > UNKNOWN_LIMIT * total:
            raise ImpossibleReading(
                f"(a) {plane}: op events of {program} whose instruction its "
                f"{SPAN} span does not know hold {unknown * 1e-6:.2f} ms of "
                f"{total * 1e-6:.2f}: the map is of another compile")
        if abs(total - module_ns) > SUM_TOLERANCE * module_ns:
            raise ImpossibleReading(
                f"(b) {plane}: the parts of {program} sum to "
                f"{total * 1e-6:.2f} ms, its executions took "
                f"{module_ns * 1e-6:.2f} ms")
        for family in sorted({p.split(".")[0] for p in parts.values() if p}):
            if not any(s > 0 for p, s in seconds.items()
                       if p.split(".")[0] == family):
                raise ImpossibleReading(
                    f"(c) {plane}: {program} holds instructions of "
                    f"{family!r} and none of them took any time")
        for held in set(parts.values()) - {None}:
            seconds.setdefault(held, 0.0)     # every part the map holds is printed
        out[kind] = {"parts": dict(seconds), "unscoped_s": unscoped * 1e-9,
                     "unknown_s": unknown * 1e-9, "total_s": total * 1e-9,
                     "inherited_s": inherited_ns * 1e-9,
                     "overlap_s": (plain - total) * 1e-9,
                     "module_s": module_ns * 1e-9,
                     "executions": counts[kind]}
    out["prefill"]["per"] = sum(l[2] for l in read) / 1e3    # 1,000 valid tokens
    out["decode"]["per"] = counts["decode"]                  # steps
    for kind, unit in (("prefill", "ms per 1k tokens"), ("decode", "ms a step")):
        r = out[kind]
        ms = lambda s: f"{1e3 * s / r['per']:.4f}"   # noqa: E731
        print(f"[scopes] {kind} ({unit}): "
              + " ".join(f"{p} {ms(s)}" for p, s in sorted(r["parts"].items()))
              + f" | unscoped {ms(r['unscoped_s'])} unknown {ms(r['unknown_s'])}"
              f" | sum {ms(r['total_s'])} of the executions' {ms(r['module_s'])}"
              f" | of the sum, from ops that took their user's part "
              f"{ms(r['inherited_s'])}, overlapped and counted once "
              f"{ms(r['overlap_s'])}", flush=True)
    return out


def read(ctx=None):
    """``reduce`` of the remembered events and the ring as it stands, once a
    run; None where there is nothing to read (module docstring)."""
    ring = program_spans.read()
    if ring is None or not _loaded or not ring.named(SPAN):
        return None
    key = id(_loaded[0])
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = reduce(_loaded[0], ring)
    return _reduced[key]


def _selected(parts: dict, names) -> float:
    """Seconds of the parts ``names`` picks: a name that ends in ``.`` picks
    every part that starts with it."""
    return sum(s for p, s in parts.items()
               if any(p == n or (n.endswith(".") and p.startswith(n))
                      for n in names))


def ms_per(ctx, kind: str, *names):
    """Milliseconds of the parts ``names`` per decode step (``kind``
    ``"decode"``) or per 1,000 valid prompt tokens (``"prefill"``)."""
    found = read(ctx)
    if found is None or not found[kind]["per"]:
        return None
    return 1e3 * _selected(found[kind]["parts"], names) / found[kind]["per"]


def unscoped_pct(ctx, kind: str):
    """100 x (ops with no part + ops the map does not know) / the program."""
    found = read(ctx)
    if found is None or not found[kind]["total_s"]:
        return None
    r = found[kind]
    return 100.0 * (r["unscoped_s"] + r["unknown_s"]) / r["total_s"]
