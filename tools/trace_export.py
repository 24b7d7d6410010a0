#!/usr/bin/env python
"""Convert ``trace.span`` telemetry JSONL into Chrome trace-event JSON.

The spans a run emits (``can_tpu/obs/spans.py`` — the serve batcher
thread's wait/intake/poll cycle and each batch's pad/dispatch/fetch/
complete, every request's request/queue_wait, the train loop's
steps/metric_flush/train.dispatch and the prefetch worker's
input.load/input.put) are viewable in ``chrome://tracing`` or Perfetto
once converted to the trace-event format::

    python tools/trace_export.py runs/exp1/telemetry.host0.jsonl
    python tools/trace_export.py runs/exp1/ --out run.trace.json
    python tools/trace_export.py tel.jsonl --trace-id req-1f03-7
    python tools/trace_export.py runs/exp1/incidents/incident-...-h0-.../
        # an incident bundle's ring dump (obs/incidents.py) exports the
        # same way — quarantine to flame view, one artifact

Mapping: every span becomes one complete event (``ph: "X"``) with
microsecond ``ts``/``dur`` normalised to each HOST's earliest span (spans
carry ``perf_counter`` stamps, whose epoch is process-local, so a
cross-host export re-anchors hosts against each other via the bus
wall-clock ``ts``); ``pid`` is the telemetry ``host_id``.  Lanes
(``tid``, with a ``thread_name`` metadata event): a span opened on a
thread, or stamped as that thread's time (it carries ``thread``: the
batcher thread, the prefetch worker, the train loop), lies on that
thread's lane, where nesting reads as a flame; a request's own spans
(request, queue_wait) lie on its trace's lane, so one request reads as
one horizontal track.  ``--trace-id`` of a
request draws it with its batch: the ``serve.batch`` trace its
``batch`` attribute names comes along.  Span/parent ids ride in
``args`` for tooling that wants to rebuild the tree.

Cross-host stitching: serve hops propagate one trace_id over HTTP
(``X-CanTpu-Trace-Id`` — can_tpu/serve/service.py), so ``--trace-id``
over a multi-host artifact renders one request's journey across hosts
as one timeline.  The re-anchoring wall clocks are SKEW-CORRECTED first
(obs/join.py): a FleetCollector snapshot's measured per-host offsets
when the target is one, else the first-heartbeat estimate — without
this, a host running 2 minutes fast would shove its segment of the
request 2 minutes off every other host's.

Beside the device: ``--profile DIR`` (the ``--profile-dir`` of the same
run) adds the profiler's device planes — ``XLA Modules`` and ``XLA Ops`` —
as one more process in the document.  An operator's profile holds the
device alone (the host tracer floods the serving path; PERF.md section
6), so the host's spans reach the device's clock through one anchor, the
benchmark's: the end of the last ``serve.fetch`` / ``metric_flush`` inside
the run's ``profile.window`` span — the host has just seen a program
complete — against the end of the last program on the device (a few
milliseconds of error; gaps are tens of milliseconds).

By model part: where the spans hold ``program.scopes`` (a language-model
engine records one per compiled program when a tracer was active at its
first launch: ``serve/engine.py``, ``obs/trace.py``), every ``XLA Ops``
event of that program is written with the part of the model its
instruction belongs to, in ``args`` and as the event's category (Perfetto
colours by it), and ``--scopes`` prints the seconds by program and part
(plain sums of the ops' durations; the benchmark's
``harness/program_scopes.py`` counts overlapping ops once) and, beside a
program's name, the span's ``cache_copies``: how many times the program
copies an array of its decoding cache whole (0 where a step writes its
position in place).

Pure host-side file reading — no JAX import unless ``--profile`` is given
(reading ``.xplane.pb`` takes ``jax.profiler.ProfileData``), safe anywhere
the artifact was copied to (same contract as tools/telemetry_report.py).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from can_tpu.obs.join import (  # noqa: E402
    load_joined_events,
    resolve_telemetry_source,
)
from can_tpu.obs.trace import instruction_of  # noqa: E402

# spans at whose end the host has just seen a device program complete
_SEEN_COMPLETE = ("serve.fetch", "metric_flush")
_DEVICE_LINES = ("XLA Modules", "XLA Ops")
_DEVICE_PID = 1000  # + the plane's index: clear of the host ids


def load_device_planes(profile_dir: str) -> dict:
    """The newest ``.xplane.pb`` under a ``--profile-dir`` ->
    ``{plane: {line: [(name, start_ns, duration_ns)]}}`` for the device
    planes' ``XLA Modules`` / ``XLA Ops`` lines."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    planes: dict = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events]
                 for line in plane.lines if line.name in _DEVICE_LINES}
        if any(lines.values()):
            planes[plane.name] = lines
    return planes


def program_parts(events) -> dict:
    """``program.scopes`` spans -> {program (``jit_decode``): {instruction
    name: part or None}}; programs compiled under one name share a map."""
    out: dict = {}
    for e in events:
        p = e.get("payload", {})
        if e.get("kind") == "trace.span" and p.get("name") == "program.scopes":
            out.setdefault(p["program"], {}).update(p["parts"])
    return out


def program_cache_copies(events) -> dict:
    """``program.scopes`` spans -> {program: ``cache_copies``}, how many
    times the compiled program copies an array of its decoding cache whole
    (0: every position is written in place); of programs compiled under one
    name the most; nothing for a span from before the attribute."""
    out: dict = {}
    for e in events:
        p = e.get("payload", {})
        if (e.get("kind") == "trace.span" and p.get("name") == "program.scopes"
                and "cache_copies" in p):
            out[p["program"]] = max(out.get(p["program"], 0),
                                    int(p["cache_copies"]))
    return out


def _ops_with_parts(lines: dict, parts: dict):
    """A device plane's ``XLA Ops`` events as (name, start, duration,
    program, part): the program is the ``XLA Modules`` execution the op
    starts in, the part what that program's map says of the instruction
    the event's HLO text begins with (None: the map has none, or there is
    no map)."""
    mods = sorted((s, s + d, n.split("(")[0])
                  for n, s, d in lines.get("XLA Modules", []))
    starts = [m[0] for m in mods]
    for name, start, dur in lines.get("XLA Ops", []):
        i = bisect.bisect_right(starts, start) - 1
        program = mods[i][2] if i >= 0 and start < mods[i][1] else None
        yield (name, start, dur, program,
               parts.get(program, {}).get(instruction_of(name)))


def seconds_by_part(planes: dict, parts: dict) -> dict:
    """{program: {part: seconds}} over the programs that have a map; ops
    without a part under ``"(none)"``."""
    out: dict = {}
    for lines in planes.values():
        for _, _, dur, program, part in _ops_with_parts(lines, parts):
            if program in parts:
                by = out.setdefault(program, {})
                by[part or "(none)"] = by.get(part or "(none)", 0.0) + dur * 1e-9
    return out


def _device_events(planes: dict, spans, to_ts) -> list:
    """The device planes as trace events, anchored (module docstring).
    ``to_ts(host, seconds)``: the document's microseconds for a host
    ``perf_counter`` time."""
    windows = [e for e in spans
               if e["payload"].get("name") == "profile.window"]
    if not windows:
        raise ValueError("no profile.window span: the run recorded no "
                         "profile beside these spans (--trace-steps with "
                         "--telemetry-dir records one)")
    host = int(windows[-1].get("host_id", 0))
    w = windows[-1]["payload"]
    lo, hi = float(w["start_s"]), float(w["start_s"]) + float(w["duration_s"])
    seen = [float(p["start_s"]) + float(p["duration_s"])
            for p in (e["payload"] for e in spans
                      if int(e.get("host_id", 0)) == host)
            if p.get("name") in _SEEN_COMPLETE]
    seen = [t for t in seen if lo <= t <= hi]
    last_end = max((s + d for lines in planes.values()
                    for _, s, d in lines.get("XLA Modules", [])), default=None)
    if not seen or last_end is None:
        raise ValueError("nothing to anchor the device plane by: no "
                         "serve.fetch / metric_flush span ends inside the "
                         "profile.window, or the profile holds no program")
    anchor = max(seen)
    parts = program_parts(spans)
    out = []
    for i, (plane, lines) in enumerate(sorted(planes.items())):
        pid = _DEVICE_PID + i
        out.append({"ph": "M", "name": "process_name", "pid": pid,
                    "args": {"name": plane + " (anchored to the host by "
                                     "its last program's end)"}})
        for tid, line in enumerate(_DEVICE_LINES, start=1):
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": line}})
            rows = (_ops_with_parts(lines, parts) if line == "XLA Ops" else
                    ((n, s, d, None, None) for n, s, d in lines.get(line, [])))
            for name, start, dur, program, part in rows:
                out.append({
                    "name": name[:120], "cat": part or "device", "ph": "X",
                    "ts": round(to_ts(host, anchor + (start - last_end) * 1e-9),
                                3),
                    "dur": round(dur * 1e-3, 3), "pid": pid, "tid": tid,
                    "args": ({"part": part, "program": program}
                             if part else {})})
    return out


def spans_to_trace_events(events, *, trace_id: Optional[str] = None,
                          offsets: Optional[dict] = None,
                          device_planes: Optional[dict] = None) -> dict:
    """``trace.span`` events -> a Chrome trace-event document
    (``{"traceEvents": [...], "displayTimeUnit": "ms"}``).

    Lanes (``tid``) are assigned per thread (spans that carry one) or
    trace_id, in order of first appearance — deterministic for a given
    artifact.  ``trace_id`` filters to one request/epoch tree, and the
    batch a request rode in.  ``offsets`` (host_id -> seconds
    fast, obs/join.py convention) skew-corrects the per-host wall
    anchors for RAW event streams; events already corrected upstream
    (``load_joined_events``) must not pass it again.  ``device_planes``
    (``load_device_planes``) adds the profiler's device planes."""
    spans = every = [e for e in events if e.get("kind") == "trace.span"]
    if trace_id is not None:
        mine = [e["payload"] for e in spans
                if e.get("payload", {}).get("trace_id") == trace_id]
        batches = {p["batch"] for p in mine if p.get("batch")}
        keep = {trace_id} | {e["payload"].get("trace_id") for e in spans
                             if e.get("payload", {}).get("span_id") in batches}
        spans = [e for e in spans
                 if e.get("payload", {}).get("trace_id") in keep]
    out: List[dict] = []
    lanes: dict = {}
    # span start_s is the emitter's perf_counter, whose epoch is
    # process-local — a global min across
    # hosts would offset lanes by arbitrary inter-host clock deltas.
    # Normalise per host, then re-anchor hosts against each other with
    # the bus wall-clock ``ts`` each event also carries (cross-host skew
    # is then bounded by emit latency, not clock-epoch differences).
    base: dict = {}       # host_id -> min start_s (that host's clock)
    wall0: dict = {}      # host_id -> min bus ts (skew-corrected wall)
    offsets = offsets or {}
    for e in spans:
        p = e.get("payload", {})
        if "start_s" not in p:
            continue
        h = int(e.get("host_id", 0))
        base[h] = min(base.get(h, float("inf")), float(p["start_s"]))
        wall0[h] = min(wall0.get(h, float("inf")),
                       float(e.get("ts", 0.0))
                       - float(offsets.get(h, 0.0)))
    global_wall0 = min(wall0.values(), default=0.0)
    for e in spans:
        p = e.get("payload", {})
        if "start_s" not in p or "duration_s" not in p:
            continue  # malformed span: skip, exactly like a torn line
        tid_key = str(p.get("thread") or p.get("trace_id", "?"))
        pid = int(e.get("host_id", 0))
        if (pid, tid_key) not in lanes:
            lanes[(pid, tid_key)] = len(lanes) + 1
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": lanes[(pid, tid_key)],
                        "args": {"name": tid_key}})
        args = {k: v for k, v in p.items()
                if k not in ("name", "start_s", "duration_s")}
        out.append({
            "name": str(p.get("name", "?")),
            "cat": "can_tpu",
            "ph": "X",
            "ts": round(((float(p["start_s"]) - base[pid])
                         + (wall0[pid] - global_wall0)) * 1e6, 3),
            "dur": round(float(p["duration_s"]) * 1e6, 3),
            "pid": pid,
            "tid": lanes[(pid, tid_key)],
            "args": args,
        })
    if device_planes:
        out.extend(_device_events(
            device_planes, every,
            lambda h, t: ((t - base[h]) + (wall0[h] - global_wall0)) * 1e6))
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def resolve_paths(target: str) -> list:
    """Telemetry file / run dir / collector snapshot / incident bundle
    -> the JSONL files to read.  Thin alias of the shared
    ``obs/join.py`` resolution, kept for the tool's public surface."""
    return resolve_telemetry_source(target)[0]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("target", help="telemetry JSONL file, or a directory "
                                  "holding telemetry.host*.jsonl")
    p.add_argument("--out", default="",
                   help="output path (default <target>.trace.json; '-' "
                        "writes the JSON to stdout)")
    p.add_argument("--trace-id", default=None,
                   help="export only this trace's span tree (the id a "
                        "serve response returns)")
    p.add_argument("--profile", default="",
                   help="the run's --profile-dir: draw the device's XLA "
                        "Modules / XLA Ops beside the spans")
    p.add_argument("--scopes", action="store_true",
                   help="with --profile: print the device seconds by "
                        "program and model part (needs program.scopes "
                        "spans: a language-model engine warmed up with a "
                        "tracer active)")
    args = p.parse_args(argv)
    if args.scopes and not args.profile:
        p.error("--scopes reads the device plane: give --profile DIR")
    # estimate=True: a flame view exists to compare timing across hosts,
    # so skew correction is always on (measured snapshot offsets win;
    # plain run dirs get the first-heartbeat estimate).  The events come
    # back already corrected — no offsets passed below.
    events, _, _ = load_joined_events(args.target, estimate=True)
    try:
        planes = load_device_planes(args.profile) if args.profile else None
        doc = spans_to_trace_events(events, trace_id=args.trace_id,
                                    device_planes=planes)
    except (FileNotFoundError, ValueError) as e:
        print(f"trace_export: {e}", file=sys.stderr)
        return 1
    if args.scopes:
        table = seconds_by_part(planes, program_parts(events))
        if not table:
            print("trace_export: no program.scopes span names a program of "
                  "this profile", file=sys.stderr)
            return 1
        copies = program_cache_copies(events)
        for program, by in sorted(table.items()):
            total = sum(by.values())
            print(f"[scopes] {program}: {total:.6f} s of ops"
                  + (f", cache_copies {copies[program]}"
                     if program in copies else ""))
            for part, sec in sorted(by.items(), key=lambda kv: -kv[1]):
                print(f"  {part:<14}{sec:12.6f} s {100 * sec / total:6.2f}%")
    n = sum(1 for e in doc["traceEvents"]
            if e["ph"] == "X" and e["cat"] == "can_tpu")
    if not n:
        print("no trace.span events found"
              + (f" for trace_id {args.trace_id}" if args.trace_id else "")
              + " (run with --telemetry-dir to record spans)",
              file=sys.stderr)
        return 1
    if args.out == "-":
        json.dump(doc, sys.stdout)
        return 0
    out = args.out or (args.target.rstrip("/") + ".trace.json")
    with open(out, "w") as f:
        json.dump(doc, f)
    print(f"[trace_export] wrote {n} spans to {out} "
          f"(open in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
