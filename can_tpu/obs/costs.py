"""ProgramCostLedger: per-program XLA cost attribution — MFU, roofline
class, and the empirical launch-cost fit.

Until this module, the MFU plateau (~60%, VERDICT r5) and the planner's
``DEVICE_LAUNCH_COST_MPX`` constant were argued from one-off hand math in
``tools/ablate_mfu.py`` — no running system could say, per compiled
program, how many FLOPs it executes, how many HBM bytes it moves, or
whether it is compute- or bandwidth-bound.  The ledger closes that gap by
joining three data sources the stack already has:

* **compile time** — ``obs.RecompileTracker`` fires once per new
  ``(shape, dtype)`` signature; when a ledger is attached to the telemetry
  bus (``Telemetry.ledger``), the tracker calls :meth:`register`, which
  AOT-lowers the SAME jitted callable and reads
  ``compiled.cost_analysis()`` flops / "bytes accessed".  Backends that
  don't report cost analysis degrade to ``None`` rows — the ledger never
  raises into the step path.  The extra ``lower().compile()`` rides the
  compile event (already the slow path) and is a persistent-cache hit on
  backends with the XLA compilation cache armed.
* **steady state** — ``StepTimer`` per-shape wall totals (train/eval) and
  serve per-batch execute times (``CountService``) land via
  :meth:`observe` / :meth:`observe_timer`, giving each program a measured
  seconds-per-launch with first-call compiles already excluded upstream.
* **the device peak table** — ``cli.common.local_device_peaks`` (spec
  FLOP/s + HBM GB/s per device kind; a labelled-NOMINAL entry on CPU so
  the plumbing stays testable) turns flops/seconds into MFU and
  flops/bytes into a roofline class against the ridge intensity.

The launch-cost fit closes the loop with the PR-5 planner: the
``PlanCostModel`` prices a launch as ``area * slots + launch_cost_px``;
in time units that is ``seconds = px / rate + launch_overhead_s``.  A
weighted least-squares line through the measured (pixels, mean seconds)
points recovers both terms, and the intercept re-expressed in the
planner's unit is the EMPIRICAL ``DEVICE_LAUNCH_COST_MPX`` —
``launch_cost_drift`` (empirical / planned) is the model-drift gauge that
says when the constant in ``cli/common.py`` has gone stale.

Everything surfaces as ``perf.summary`` events (per-epoch in the loops,
periodic in serve): numeric payload keys become ``can_tpu_mfu_*`` /
``can_tpu_roofline_*`` / ``can_tpu_launch_cost_*`` gauges via the
exporter's ``GaugeSink``, and the ``detail`` rows feed
``tools/telemetry_report.py``.  A run without telemetry constructs no
ledger — the default hot path is untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

ROOFLINE_COMPUTE = "compute"
ROOFLINE_MEMORY = "memory"
ROOFLINE_UNKNOWN = "unknown"

# Timing-trust rule: serve execute times are FENCED (a device->host fetch
# closes every measured window), so one launch is already honest.  The
# train loop's per-shape samples are host-side dispatch intervals (the
# window-flush step absorbs the device sync — loop.py's documented
# bias): an individual sample can be wildly short, but the pipeline is
# rate-limited, so the MEAN converges on the true step time as launches
# accumulate.  Unfenced programs therefore need this many launches
# before their mean feeds MFU / the launch-cost fit; below it the row
# reports mean_s but refuses to synthesize utilisation from it (the r9
# bring-up saw a 1-launch program "achieve" 600x MFU this way).
MIN_UNFENCED_LAUNCHES = 4


def extract_image_signature(signature) -> Tuple[tuple, str]:
    """``train.steps.batch_signature`` triples -> (image shape, dtype).

    The image tensor carries the pixels every cost in this module is
    normalised by; batches without an ``image`` entry fall back to the
    largest-shape tensor (so the ledger still keys sanely on exotic
    batch dicts)."""
    best = None
    for name, shape, dtype in signature:
        if name == "image":
            return tuple(shape), str(dtype)
        size = 1
        for d in shape:
            size *= int(d)
        if best is None or size > best[0]:
            best = (size, tuple(shape), str(dtype))
    if best is None:
        return (), "?"
    return best[1], best[2]


def resolve_jit(fn, args):
    """The lowerable jitted callable behind ``fn`` for these ``args``:
    ``jax.jit`` objects pass through, wrapped dispatchers (the bucketed/
    spatial step closures, ``obs.RecompileTracker``) expose ``jit_for``
    returning the underlying jit.  Shared by the cost ledger and the HLO
    auditor (``can_tpu.analysis.hlo_audit``) so both reach the SAME
    program an operator's step actually runs."""
    picker = getattr(fn, "jit_for", None)
    return picker(*args) if picker is not None else fn


def cost_analysis_of(fn, args) -> Optional[Tuple[Optional[float],
                                                 Optional[float]]]:
    """(flops, bytes accessed) for the program ``fn(*args)`` compiles to,
    or None when the backend/callable can't say.

    ``fn`` is usually a ``jax.jit`` object (``.lower`` exists); wrapped
    dispatchers (the bucketed/spatial step closures) expose ``jit_for``
    returning the underlying jitted callable for these args.  The
    ``lower().compile()`` here is a SECOND compile of a program jit just
    built — acceptable because it happens once per signature on the
    already-slow compile path, and the persistent compilation cache (CLI
    default) turns it into a deserialise.  Never raises."""
    try:
        target = resolve_jit(fn, args)
        lower = getattr(target, "lower", None)
        if lower is None:
            return None
        ca = lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            return None
        flops = ca.get("flops")
        byts = ca.get("bytes accessed")
        flops = float(flops) if flops is not None and flops > 0 else None
        byts = float(byts) if byts is not None and byts > 0 else None
        if flops is None and byts is None:
            return None
        return flops, byts
    # can-tpu-lint: disable=SWALLOW(attribution must never kill a run; None row is the degrade)
    except Exception:  # noqa: BLE001 — attribution must never kill a run
        return None


@dataclasses.dataclass
class ProgramCost:
    """One compiled program's ledger row (mutable: timings accumulate)."""

    name: str                 # step name ("train_step", "serve_predict", …)
    shape: tuple              # image shape (B, H, W, C)
    dtype: str                # image dtype string
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    launches: int = 0
    seconds: float = 0.0
    fenced: bool = True  # ANDed over observations; see MIN_UNFENCED_LAUNCHES

    @property
    def timing_reliable(self) -> bool:
        return bool(self.launches) and (self.fenced or
                                        self.launches >=
                                        MIN_UNFENCED_LAUNCHES)

    @property
    def pixels(self) -> Optional[int]:
        if len(self.shape) < 3:
            return None
        return int(self.shape[0]) * int(self.shape[1]) * int(self.shape[2])

    @property
    def mean_s(self) -> Optional[float]:
        return self.seconds / self.launches if self.launches else None

    @property
    def intensity(self) -> Optional[float]:
        """Arithmetic intensity, FLOP per HBM byte."""
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed


class ProgramCostLedger:
    """The join: per-program cost analysis x timings x device peaks.

    compute: "bf16" or "f32" — selects the peak-FLOP/s ceiling MFU is
      quoted against (the run's compute dtype, not the transfer dtype).
    peaks: a ``cli.common.DevicePeaks``; default autodetects the local
      device (None on unknown backends — MFU rows go None, flops/bytes
      and the launch-cost fit still work).
    plan_launch_cost_px: the planner's configured launch cost (pixel
      units) — the denominator of the ``launch_cost_drift`` gauge; the
      train CLI sets it to the resolved ``--launch-cost-mpx``.

    Thread-safety: ``register`` runs on whatever thread hits the compile
    (train loop / serve batcher), ``observe`` on loop or batcher threads,
    snapshots on scrape threads — one lock covers the record table.
    """

    def __init__(self, *, compute: str = "f32", peaks=None,
                 plan_launch_cost_px: Optional[float] = None):
        if peaks is None:
            from can_tpu.cli.common import local_device_peaks

            peaks = local_device_peaks()
        self.peaks = peaks
        self.compute = compute if compute in ("bf16", "f32") else "f32"
        self.plan_launch_cost_px = plan_launch_cost_px
        import threading

        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, tuple, str], ProgramCost] = {}

    # -- compile-time registration (RecompileTracker hook) ---------------
    def register(self, name: str, signature, *, fn=None, args=(),
                 cost=None) -> Optional[dict]:
        """Record a newly compiled signature; returns ``{"flops",
        "bytes_accessed"}`` when the backend reported them (the tracker
        folds these into the ``compile`` event payload).  ``cost`` is a
        (flops, bytes) override — the test seam and the path for callers
        that already hold a compiled object."""
        shape, dtype = extract_image_signature(signature)
        if cost is None and fn is not None:
            cost = cost_analysis_of(fn, args)
        with self._lock:
            rec = self._programs.setdefault(
                (name, shape, dtype), ProgramCost(name, shape, dtype))
            if cost is not None and rec.flops is None:
                rec.flops, rec.bytes_accessed = cost
        if cost is None:
            return None
        # only the keys the backend actually reported: a half-reporting
        # client must not put literal Nones into the compile payload
        out = {}
        if cost[0] is not None:
            out["flops"] = cost[0]
        if cost[1] is not None:
            out["bytes_accessed"] = cost[1]
        return out or None

    # -- steady-state timing ---------------------------------------------
    def observe(self, name: str, shape, seconds: float, n: int = 1,
                *, dtype: Optional[str] = None,
                fenced: bool = True) -> None:
        """Add ``n`` launches totalling ``seconds`` for the program with
        this image ``shape`` (compile first-calls excluded by the caller,
        exactly as for the step reservoirs).  ``dtype`` disambiguates when
        one shape was compiled at several image dtypes (serve passes it;
        the train loop runs one dtype per run, so shape alone resolves —
        ties go to the most recently registered record).  ``fenced=False``
        marks dispatch-biased samples (the train loop's async intervals):
        those only feed MFU once MIN_UNFENCED_LAUNCHES accumulate."""
        shape = tuple(shape)
        with self._lock:
            rec = None
            if dtype is not None:
                rec = self._programs.get((name, shape, dtype))
            if rec is None:
                matches = [r for (n_, s_, _), r in self._programs.items()
                           if n_ == name and s_ == shape]
                rec = matches[-1] if matches else None
            if rec is None:
                rec = self._programs[(name, shape, dtype or "?")] = \
                    ProgramCost(name, shape, dtype or "?")
            rec.launches += int(n)
            rec.seconds += float(seconds)
            rec.fenced = rec.fenced and bool(fenced)

    def observe_timer(self, name: str, timer) -> None:
        """Fold a ``StepTimer``'s per-shape totals in (the loops call this
        at epoch boundaries with their per-epoch timers).  Loop samples
        are host-side dispatch intervals — unfenced by construction."""
        for shape, (n, total) in timer.shape_totals().items():
            self.observe(name, shape, total, n, fenced=False)

    # -- snapshots --------------------------------------------------------
    def _peak_flops(self) -> Optional[float]:
        return self.peaks.flops(self.compute) if self.peaks else None

    def roofline_of(self, rec: ProgramCost) -> str:
        inten = rec.intensity
        if inten is None or self.peaks is None:
            return ROOFLINE_UNKNOWN
        return (ROOFLINE_COMPUTE
                if inten >= self.peaks.ridge(self.compute)
                else ROOFLINE_MEMORY)

    def _snapshot(self) -> List[ProgramCost]:
        """Consistent point-in-time copy of every registered program —
        the unit rows(), launch_cost_fit() and the summary share so one
        emitted event can never disagree with itself."""
        with self._lock:
            recs = sorted(self._programs.values(),
                          key=lambda r: (r.name, r.shape, r.dtype))
            return [dataclasses.replace(r) for r in recs]

    def rows(self, _snapshot: Optional[List[ProgramCost]] = None
             ) -> List[dict]:
        """Per-program dicts, sorted by (name, shape): flops/bytes,
        intensity, roofline class, launches, mean seconds, MFU and
        bandwidth utilisation against the peak table."""
        peak_f = self._peak_flops()
        peak_bw = self.peaks.hbm_bytes_s if self.peaks else None
        recs = self._snapshot() if _snapshot is None else _snapshot
        out = []
        for r in recs:
            mean_s = r.mean_s
            trust = r.timing_reliable
            mfu = (r.flops / (mean_s * peak_f)
                   if trust and r.flops and mean_s and peak_f else None)
            bw_util = (r.bytes_accessed / (mean_s * peak_bw)
                       if trust and r.bytes_accessed and mean_s and peak_bw
                       else None)
            out.append({
                "name": r.name, "shape": list(r.shape), "dtype": r.dtype,
                "flops": r.flops, "bytes_accessed": r.bytes_accessed,
                "pixels": r.pixels,
                "intensity": (round(r.intensity, 4)
                              if r.intensity is not None else None),
                "roofline": self.roofline_of(r),
                "launches": r.launches,
                "mean_s": round(mean_s, 6) if mean_s is not None else None,
                "total_s": round(r.seconds, 4),
                "timing_reliable": trust,
                "mfu": round(mfu, 4) if mfu is not None else None,
                "bw_util": round(bw_util, 4) if bw_util is not None else None,
            })
        return out

    def launch_cost_fit(self, name: Optional[str] = None, *,
                        _snapshot: Optional[List[ProgramCost]] = None
                        ) -> Optional[dict]:
        """Weighted least-squares of mean seconds-per-launch against
        pixels-per-launch over the timed programs (optionally one step
        ``name``): ``seconds = px / rate + overhead``.  Needs >= 2
        distinct pixel sizes and a positive slope; returns the realized
        device rate, the fixed per-launch overhead, and that overhead in
        the planner's Mpx unit (clamped at 0 — a negative intercept is
        measurement noise, reported raw in ``intercept_s``).  Only
        timing-reliable programs contribute (see MIN_UNFENCED_LAUNCHES):
        one dispatch-biased point would swing the intercept wildly."""
        if _snapshot is None:
            _snapshot = self._snapshot()
        pts = [(r.pixels, r.mean_s, r.launches)
               for r in _snapshot
               if (name is None or r.name == name)
               and r.pixels and r.mean_s and r.timing_reliable]
        if len({px for px, _, _ in pts}) < 2:
            return None
        sw = sum(n for _, _, n in pts)
        mx = sum(n * px for px, _, n in pts) / sw
        my = sum(n * s for _, s, n in pts) / sw
        sxx = sum(n * (px - mx) ** 2 for px, _, n in pts)
        sxy = sum(n * (px - mx) * (s - my) for px, s, n in pts)
        if sxx <= 0 or sxy <= 0:
            return None
        slope = sxy / sxx            # seconds per pixel
        intercept = my - slope * mx  # fixed seconds per launch
        mpx = max(intercept / slope, 0.0) / 1e6
        out = {
            "rate_mpx_s": round(1.0 / slope / 1e6, 4),
            "intercept_s": round(intercept, 6),
            "launch_cost_mpx_empirical": round(mpx, 4),
            "fit_points": len(pts),
        }
        if self.plan_launch_cost_px:
            out["launch_cost_drift"] = round(
                mpx / (self.plan_launch_cost_px / 1e6), 4)
        return out

    def _aggregate(self, rows: List[dict],
                   snapshot: Optional[List[ProgramCost]] = None) -> dict:
        """Aggregate payload derived from ONE rows() snapshot (so an
        emitted summary always agrees with its own detail): weighted MFU
        over timed programs, roofline class counts over all registered
        programs, the launch-cost fit, and the peak-table provenance.
        Keys are named for the exporter: numeric entries become
        ``can_tpu_<key>`` gauges verbatim."""
        out: dict = {"perf_programs": len(rows)}
        for cls in (ROOFLINE_COMPUTE, ROOFLINE_MEMORY, ROOFLINE_UNKNOWN):
            out[f"roofline_{cls}_bound" if cls != ROOFLINE_UNKNOWN
                else "roofline_unknown"] = sum(
                    1 for r in rows if r["roofline"] == cls)
        timed = [r for r in rows if r["mfu"] is not None and r["total_s"]]
        if timed:
            wsum = sum(r["total_s"] for r in timed)
            out["mfu_weighted"] = round(
                sum(r["mfu"] * r["total_s"] for r in timed) / wsum, 4)
            out["mfu_best"] = max(r["mfu"] for r in timed)
            out["mfu_worst"] = min(r["mfu"] for r in timed)
        # launch-cost fit PER step family, never pooled: train_step is
        # fwd+bwd+optimizer while eval/serve are fwd-only, so their
        # seconds-per-pixel slopes differ ~3x and a pooled regression
        # reports a bogus intercept (hence bogus drift) even when every
        # family matches the planner constant exactly.  The Mpx unit is
        # itself family-relative (overhead seconds x that family's own
        # rate), and the planner prices TRAIN launches — so the drift
        # gauge comes from "train_step" whenever it has a fit, with the
        # best-constrained other family as the fallback (serve-only
        # deployments still get an empirical rate/overhead, labelled).
        best_name = best_fit = None
        for n in sorted({r["name"] for r in rows}):
            f = self.launch_cost_fit(n, _snapshot=snapshot)
            if f is None:
                continue
            if n == "train_step":
                best_name, best_fit = n, f
                break
            if best_fit is None or f["fit_points"] > best_fit["fit_points"]:
                best_name, best_fit = n, f
        if best_fit is not None:
            out.update(best_fit)
            out["launch_cost_fit_name"] = best_name
        if self.peaks is not None:
            out["peak_flops"] = self._peak_flops()
            out["peak_hbm_bytes_s"] = self.peaks.hbm_bytes_s
            out["peak_nominal"] = int(self.peaks.nominal)
            out["peak_source"] = self.peaks.source
        return out

    def summary(self) -> dict:
        snap = self._snapshot()
        return self._aggregate(self.rows(snap), snap)

    def emit_summary(self, telemetry, *, step: Optional[int] = None,
                     phase: str = "") -> dict:
        """One ``perf.summary`` event: the aggregate payload (gauge feed)
        plus the per-program ``detail`` rows (the report's feed), both —
        including the launch-cost fit — from the same snapshot."""
        snap = self._snapshot()
        rows = self.rows(snap)
        payload = self._aggregate(rows, snap)
        telemetry.emit("perf.summary", step=step, phase=phase,
                       detail=rows, **payload)
        return payload
