"""``trace_lm.reduce`` with the work functions as an argument: from the
profiler's ``.xplane.pb`` to the per-layer numbers of a language-model cell
of ANY model.  ``trace_lm`` binds K-EXAONE's work counts (``flops_lm``) when
it is imported and may not be edited by the PR that adds a second model, so
this is its text with ``flops_lm`` replaced by ``work_of``: a module (or any
object) with ``prefill(cfg, lengths, held_assignments)``, ``decode_step(cfg,
contexts)`` and ``least_seconds(work, peaks)``.  The three checks are
``trace_lm``'s, word for word:

 (a) the executions of each program on the device equal what the host
     counted (slices of prefill, steps of decode, per launch), and the ops
     recorded inside them cover them;
 (b) no execution is shorter than its operations / peak or its bytes /
     bandwidth, so no roofline share passes 100%;
 (c) the device is busy at least as long as the window's launches need at
     the roofline.

The next ``benchmark`` issue should fold the two into one (PERF.md section
7).  ``tests/test_glm_serve.py`` holds this text against ``trace_lm``'s.
"""

from __future__ import annotations

import bisect
import collections

from benchmark.harness.trace import (ImpossibleReading, _clip, _length,
                                     _subtract, _union, short_op)

PREFILL, DECODE = "jit_prefill_slice", "jit_decode"


def reduce(events, launches, *, cfg: dict, peaks, work_of) -> dict:
    """``launches``: what the host launched while the trace ran, in order:
    {"slots", "bucket", "valid", "lengths" (of the valid prompts),
    "slices", "steps", "held_prefill" (assignments on held experts)}."""
    if len(events.devices) != 1:
        raise ImpossibleReading(f"(a) trace has {len(events.devices)} device "
                                f"planes, the cell runs on 1")
    if len(launches) < 2:
        raise ImpossibleReading(f"only {len(launches)} launches traced")
    (plane, lines), = events.devices.items()
    pre = [m for m in lines["modules"] if m[0].startswith(PREFILL)]
    dec = [m for m in lines["modules"] if m[0].startswith(DECODE)]
    want_pre = sum(l["slices"] for l in launches)
    want_dec = sum(l["steps"] for l in launches)
    if (len(pre), len(dec)) != (want_pre, want_dec):
        raise ImpossibleReading(
            f"(a) {plane}: {len(pre)} executions of {PREFILL}* and {len(dec)} "
            f"of {DECODE}* on the device, the host counted {want_pre} and "
            f"{want_dec} in {len(launches)} launches")
    ours = sorted(pre + dec, key=lambda m: m[1])
    starts = [m[1] for m in ours]

    def in_ours(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < ours[i][1] + ours[i][2]

    covered = _length(_union((s, s + d) for _, s, d in lines["ops"]
                             if in_ours(s)))
    inside = sum(d for _, _, d in ours)
    if covered < 0.8 * inside:
        raise ImpossibleReading(
            f"(a) {plane}: ops recorded inside the programs cover "
            f"{covered * 1e-6:.1f} ms of their {inside * 1e-6:.1f} ms: the "
            f"trace dropped device events")

    # -- per launch: its executions, their floors (b) ----------------------
    per, pi, di = [], 0, 0
    for l in launches:
        mods_p, mods_d = pre[pi:pi + l["slices"]], dec[di:di + l["steps"]]
        pi, di = pi + l["slices"], di + l["steps"]
        if mods_d and mods_p and mods_d[0][1] < mods_p[-1][1]:
            raise ImpossibleReading(
                f"(a) {plane}: a launch's first decode step started before "
                f"its last prefill slice: the host's order is not the device's")
        work_p = work_of.prefill(cfg, l["lengths"], l["held_prefill"])
        floor_p = work_p["ops_total"] / peaks.flops
        took_p = sum(d for _, _, d in mods_p) * 1e-9
        if l["valid"] and took_p < floor_p:
            raise ImpossibleReading(
                f"(b) {plane}: a prefill of {sum(l['lengths'])} tokens ran "
                f"{took_p * 1e3:.2f} ms, under its compute floor "
                f"{floor_p * 1e3:.2f} ms")
        floor_d = 0.0
        for step, (_, _, d) in enumerate(mods_d, start=1):
            work = work_of.decode_step(cfg, [n + step for n in l["lengths"]])
            least = work_of.least_seconds(work, peaks)
            floor_d += least
            if l["valid"] and d * 1e-9 < least:
                raise ImpossibleReading(
                    f"(b) {plane}: decode step {step} ran {d * 1e-6:.3f} ms, "
                    f"under its floor {least * 1e3:.3f} ms: the device time "
                    f"is under-counted or the bytes over-counted")
        per.append({"start": min(m[1] for m in mods_p + mods_d),
                    "prefill_s": took_p, "prefill_floor_s": floor_p,
                    "decode_s": sum(d for _, _, d in mods_d) * 1e-9,
                    "decode_floor_s": floor_d, "launch": l})

    # -- the window: from the first launch's start to the last one's -------
    read = per[:-1]
    w_lo, w_hi = per[0]["start"], per[-1]["start"]
    window = (w_hi - w_lo) * 1e-9
    ops_in = [(n, max(s, w_lo), min(s + d, w_hi)) for n, s, d in lines["ops"]
              if s + d > w_lo and s < w_hi]
    busy = _union((s, e) for _, s, e in ops_in)
    busy_s = _length(busy) * 1e-9
    need = sum(p["prefill_floor_s"] + p["decode_floor_s"] for p in read)
    if busy_s < need:
        raise ImpossibleReading(
            f"(c) {plane}: busy {busy_s:.4f} s of a {window:.4f} s window, but "
            f"its launches need {need:.4f} s at the roofline")
    agg = collections.Counter()
    for n, s, e in ops_in:
        agg[short_op(n)] += (e - s) * 1e-9
    gaps = collections.Counter()
    for gs, ge in _subtract([(w_lo, w_hi)], busy):
        if ge - gs < 1e3:
            continue
        left = [(gs, ge)]
        for name in sorted({m[0] for m in events.marks}):
            cover = _union(_clip(((ms, ms + md) for n, ms, md, _ in events.marks
                                  if n == name), gs, ge))
            rest = _subtract(left, cover)
            gaps[name] += (_length(left) - _length(rest)) * 1e-9
            left = rest
        gaps["no_program_span"] += _length(left) * 1e-9

    tokens = sum(sum(p["launch"]["lengths"]) for p in read)
    steps = sum(p["launch"]["steps"] for p in read)
    prefill_s = sum(p["prefill_s"] for p in read)
    decode_s = sum(p["decode_s"] for p in read)
    return {
        "busy_s": busy_s, "window_s": window, "launches": len(read),
        "prefill_device_ms_per_ktok": (1e6 * prefill_s / tokens) if tokens else None,
        "decode_device_ms_per_step": (1e3 * decode_s / steps) if steps else None,
        "prefill_roofline_pct": (100.0 * sum(p["prefill_floor_s"] for p in read)
                                 / prefill_s) if prefill_s else None,
        "decode_step_roofline_pct": (100.0 * sum(p["decode_floor_s"] for p in read)
                                     / decode_s) if decode_s else None,
        "device_ops": [[n, s] for n, s in agg.most_common(10)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(10) if s > 0],
    }
