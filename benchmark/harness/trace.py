"""From the profiler's ``.xplane.pb`` to per-layer numbers, with three checks
that turn an impossible reading into an error instead of a metric.

What the v5e trace holds (looked at by hand, PERF.md section 6, PR 23): one
plane ``/device:TPU:<n>`` per chip with the line ``XLA Modules`` (one event
per execution of a whole program, named ``jit_<fn>(<id>)``), the line
``XLA Ops`` (one event per HLO instruction, named by its HLO text); one plane
``/host:CPU`` whose lines are threads, where ``jax.profiler.TraceAnnotation``
spans appear under their own names with their keyword arguments as stats.
Host and device events share one clock.  But the host tracer also records
one event per chunk of the runtime's host-side layout change ("Transpose",
~225,000 events per b16 768x1024 float32 batch), which slows the host two to
four times and would make the traced window's idle share a property of the
tracer.  So the benchmark traces the device alone (``host_tracer_level`` 0),
times its own spans (``bench:launch``, ``bench:put``) on ``perf_counter``, and
puts them on the device's clock through one anchor: the host time at which
the last traced launch was seen complete against the end of the last program
on the device (a few milliseconds of error; gaps are tens of milliseconds).

A program's device time is taken from ``XLA Modules``.  The benchmark traces
a fixed number of launches, drained on both sides, and reads busy and idle
over the span from the start of one launch to the start of a later one.

The checks (each raises ``ImpossibleReading`` with the numbers):
 (a) the executions of each program found on every device equal the launches
     the host annotated, and the ops recorded inside them cover them;
 (b) every execution lasts at least its operations / peak and its
     bytes / peak bandwidth, so no roofline share passes 100%;
 (c) each device is busy at least images x operations per image / peak.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re

LAUNCH = "bench:launch"
_KERNEL_SHAPE = re.compile(r"\[(?:3,3,\d+,\d+|1,1,\d+,\d+|512,512)\]")


class ImpossibleReading(RuntimeError):
    """The trace says something no chip can do, or disagrees with the host."""


@dataclasses.dataclass
class Events:
    devices: dict      # plane name -> {"modules": [...], "ops": [...]}
    marks: list        # host annotations named bench:*: (name, start, dur, stats)
    # every event is (name, start_ns, dur_ns)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, marks = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    lines[key] = sorted(
                        ((e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        marks.append((e.name, float(e.start_ns), float(e.duration_ns),
                                      {k: str(v) for k, v in e.stats}))
    marks.sort(key=lambda m: m[1])
    return Events(devices=devices, marks=marks)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short_op(name: str) -> str:
    """``%fusion.4 = bf16[16,768,1024,64]{...} fusion(...)`` -> ``fusion.4 bf16[16,768,1024,64]``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:60]


def is_contraction(name: str) -> bool:
    """A device op that touches a convolution kernel or a 1x1 matrix of the
    model: forward convolutions, input- and weight-gradient convolutions and
    the optimiser updates XLA fuses into the latter.  Over-inclusive on
    purpose: what it adds is time, so the roofline share it feeds reads low,
    never high."""
    head = name.split("calls=")[0]
    return bool(_KERNEL_SHAPE.search(head)) and not head.lstrip("%").startswith("copy")


def place_spans(events: Events, spans, anchor_host_s: float, program_prefix: str):
    """Host spans ``(name, t0, t1)`` on ``perf_counter`` -> ``events.marks`` on
    the device's clock, anchored at the end of the last program."""
    last_end = max(s + d for lines in events.devices.values()
                   for n, s, d in lines["modules"] if n.startswith(program_prefix))
    offset = last_end - anchor_host_s * 1e9
    events.marks = sorted(((name, t0 * 1e9 + offset, (t1 - t0) * 1e9, {})
                           for name, t0, t1 in spans), key=lambda m: m[1])


def reduce(events: Events, launches, *, program_prefix: str, peaks, n_devices: int,
           train: bool, image_bytes: int = 4, skip: int = 2, steady: int = 8) -> dict:
    """``launches``: what the host launched while the trace ran, in order, as
    dicts ``{"key", "batch", "images", "h", "w"}`` (batch and images are
    global; each device runs batch / n_devices of them)."""
    from benchmark.harness import flops

    n_marks = sum(1 for m in events.marks if m[0] == LAUNCH)
    if n_marks != len(launches):
        raise ImpossibleReading(
            f"(a) the host annotated {n_marks} launches in the trace and "
            f"counted {len(launches)} itself")
    if len(events.devices) != n_devices:
        raise ImpossibleReading(f"(a) trace has {len(events.devices)} device "
                                f"planes, the cell runs on {n_devices}")
    if len(launches) < skip + 1:
        raise ImpossibleReading(f"only {len(launches)} launches traced")
    lo_i = min(skip, len(launches) - 2)
    hi_i = min(lo_i + steady, len(launches) - 1)

    busy_total, window = 0.0, None
    agg_ops = collections.Counter()
    gaps_named = collections.Counter()
    contraction_s = module_s = 0.0
    for plane, lines in sorted(events.devices.items()):
        mods = [m for m in lines["modules"] if m[0].startswith(program_prefix)]
        if len(mods) != len(launches):
            raise ImpossibleReading(
                f"(a) {plane}: {len(mods)} executions of {program_prefix}* on "
                f"the device, {len(launches)} launches on the host")
        by_key = {}
        for launch, (name, start, dur) in zip(launches, mods):
            if by_key.setdefault(launch["key"], name) != name:
                raise ImpossibleReading(
                    f"(a) {plane}: program {launch['key']} ran as {name} and "
                    f"as {by_key[launch['key']]}")
            per = launch["batch"] // n_devices
            least, bound = flops.least_seconds(launch["h"], launch["w"], per, peaks,
                                               train=train, image_bytes=image_bytes)
            if dur * 1e-9 < least:
                raise ImpossibleReading(
                    f"(b) {plane}: {launch['key']} ran {dur * 1e-6:.3f} ms, under "
                    f"the {bound} floor {least * 1e3:.3f} ms: the device time "
                    f"is under-counted or the operations over-counted")
        if len(set(by_key.values())) != len(by_key):
            raise ImpossibleReading(f"(a) {plane}: two programs share one module: {by_key}")
        covered = _length(_union(
            (s, s + d) for _, s, d in lines["ops"]
            if any(ms <= s < ms + md for _, ms, md in mods)))
        inside = sum(d for _, _, d in mods)
        if covered < 0.8 * inside:
            raise ImpossibleReading(
                f"(a) {plane}: ops recorded inside the programs cover "
                f"{covered * 1e-6:.1f} ms of their {inside * 1e-6:.1f} ms: the "
                f"trace dropped device events")
        w_lo, w_hi = mods[lo_i][1], mods[hi_i][1]
        window = (w_hi - w_lo) * 1e-9
        ops_in = [(n, max(s, w_lo), min(s + d, w_hi)) for n, s, d in lines["ops"]
                  if s + d > w_lo and s < w_hi]
        busy = _union((s, e) for _, s, e in ops_in)
        busy_s = _length(busy) * 1e-9
        need = sum(flops.forward_flops(l["h"], l["w"]) * (3 if train else 1)
                   * l["images"] / n_devices
                   for l in launches[lo_i:hi_i]) / peaks.flops
        if busy_s < need:
            raise ImpossibleReading(
                f"(c) {plane}: busy {busy_s:.4f} s of a {window:.4f} s window, "
                f"but its images need {need:.4f} s at peak")
        busy_total += busy_s
        module_s += sum(d for _, _, d in mods[lo_i:hi_i]) * 1e-9
        for n, s, e in ops_in:
            agg_ops[short_op(n)] += (e - s) * 1e-9
            if is_contraction(n):
                contraction_s += (e - s) * 1e-9
        # each idle gap is shared out among the host spans that cover it; what
        # no span of the benchmark covers is the program's own time
        for gs, ge in _subtract([(w_lo, w_hi)], busy):
            if ge - gs < 1e3:
                continue
            left = [(gs, ge)]
            for name in sorted({m[0] for m in events.marks}):
                cover = _union(_clip(((ms, ms + md) for n, ms, md, _ in events.marks
                                      if n == name), gs, ge))
                rest = _subtract(left, cover)
                gaps_named[name] += (_length(left) - _length(rest)) * 1e-9
                left = rest
            gaps_named["no_bench_span"] += _length(left) * 1e-9

    nd = float(n_devices)
    images = sum(l["images"] for l in launches[lo_i:hi_i])
    model_flops = sum(flops.forward_flops(l["h"], l["w"]) * (3 if train else 1)
                      * l["images"] for l in launches[lo_i:hi_i])
    contraction_s /= nd
    floor = model_flops / nd / peaks.flops
    if contraction_s and floor > contraction_s:
        raise ImpossibleReading(
            f"(b) contraction ops took {contraction_s:.4f} s, their operations "
            f"need {floor:.4f} s at peak")
    return {
        "busy_s": busy_total / nd, "window_s": window, "images": images,
        "launches": hi_i - lo_i,
        "device_ms_per_img": module_s * 1e3 / images if images else None,
        "contraction_roofline_pct": (100.0 * floor / contraction_s
                                     if contraction_s else None),
        "device_ops": [[n, s / nd] for n, s in agg_ops.most_common(10)],
        "idle_gaps": [[n, s / nd] for n, s in gaps_named.most_common(10) if s > 0],
    }
