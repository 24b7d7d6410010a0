"""CountService: the serving front door (programmatic API + HTTP).

Wires the pieces into one lifecycle::

    client -> submit() -> BoundedRequestQueue -> MicroBatcher(thread)
                                                   -> (launch lane ->)
                                                      ServeEngine.predict_batch
                                                   -> resolve ServeRequests

``submit()/result()`` is the primary API — tests and the benchmark drive
the full stack through it with zero networking.  The HTTP front end
(``serve_http``) is a thin stdlib adapter over the same calls: one process,
one device owner, many client connections.

The engine may be a single engine in process or a ``FleetEngine``.  In
process, dispatch executes the batch through to the resolved requests: on
the batcher thread (the original topology) where the engine holds one
launch in flight (``LMEngine``), on one of the batcher's launch lanes
where it states more (``ServeEngine``: 2, so that batch n+1 is assembled
and transferred while batch n's program runs; serve/batcher.py).  Behind a
``FleetEngine`` (serve/fleet.py) dispatch ENQUEUES the assembled batch and
returns, replica worker threads execute on their own devices and call back
into ``_complete`` — same resolution/telemetry code every way, so every
guarantee (typed rejection, parity, bounded compiles) holds per replica.

Telemetry (same bus/schema as train/eval, summarised by
``tools/telemetry_report.py``):

* ``serve.request``  — per completed request: latency_s, bucket, ok
* ``serve.batch``    — per flush: bucket, size/valid/fill, execute_s,
                       queue_depth (the depth gauge rides the batch event:
                       sampled exactly when it changes, no extra thread);
                       fleet batches add ``replica``
* ``serve.reject``   — per rejection: reason (queue_full / backpressure /
                       deadline / shutdown / error)
* ``serve.warmup``   — pre-traffic compile pass summary
* ``fleet.replica`` / ``fleet.rollout`` — emitted by serve/fleet.py:
                       replica state transitions and rollout reports
"""

from __future__ import annotations

import io
import json
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from can_tpu.data.dataset import normalize_host
from can_tpu.obs.spans import active
from can_tpu.serve.batcher import MicroBatcher
from can_tpu.serve.engine import ServeEngine
from can_tpu.serve.queue import (
    REJECT_ERROR,
    REJECT_SHUTDOWN,
    REJECT_STALE_FRAME,
    REJECT_STREAM_OVERLOAD,
    BoundedRequestQueue,
    GenerateResult,
    RejectedError,
    ServeRequest,
    ServeResult,
    TokenRequest,
)
from can_tpu.serve.streams import StreamSessionRegistry
from can_tpu.utils.profiling import StepTimer


def prepare_image(image: np.ndarray, *, ds: int = 8,
                  normalize: bool = True) -> np.ndarray:
    """Snap an arbitrary HWC image to the density grid, exactly as the
    offline ``CrowdDataset.__getitem__`` does: cv2 bilinear resize down to
    the nearest /ds multiple (half-pixel centers — bit-exact with the
    reference), then ImageNet-normalise (u8 input + normalize=False keeps
    bytes for the device-normalised transfer mode)."""
    if image.ndim != 3 or image.shape[2] != 3:
        raise ValueError(f"expected HWC RGB image, got shape {image.shape}")
    h, w = image.shape[:2]
    rows, cols = h // ds, w // ds
    if rows == 0 or cols == 0:
        raise ValueError(f"image {h}x{w} is smaller than one {ds}px "
                         f"density cell")
    if (rows * ds, cols * ds) != (h, w):
        import cv2

        image = cv2.resize(np.ascontiguousarray(image), (cols * ds, rows * ds))
    if normalize:
        image = normalize_host(np.asarray(image))
        if image.dtype != np.float32:
            raise ValueError("normalize=True needs uint8 or already "
                             f"normalised float32 pixels, got {image.dtype}")
    return image


class ServeTicket:
    """Handle returned by ``submit()``; ``result()`` blocks for the
    outcome (raising ``RejectedError`` on any rejection — never hangs:
    the wait is bounded by the request deadline plus a grace window for
    the in-flight batch)."""

    def __init__(self, request: ServeRequest, service: "CountService"):
        self._request = request
        self._service = service
        self.id = request.id

    @property
    def done(self) -> bool:
        return self._request.done

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if timeout is None:
            if self._request.deadline_ts is not None:
                # deadline + a grace window: an expired request is rejected
                # at the next batcher pump, and a dispatched one resolves
                # within the batch execute — either way well under this.
                # "now" comes from the SERVICE clock (deadline_ts does too;
                # mixing in time.monotonic breaks fake-clock tests)
                timeout = (self._request.deadline_ts
                           - self._service._clock()
                           + self._service.grace_s)
            else:
                timeout = self._service.default_result_timeout_s
        return self._request.wait(max(timeout, 0.0))


class CountService:
    """Owns the queue, the batcher thread (with its launch lanes), and the
    engine.

    bucket_ladder / pad_multiple: the bucket policy (same semantics as the
    offline batcher; pick the ladder from the deployment's expected shape
    distribution).  ``warmup()`` should be called before traffic.
    """

    def __init__(self, engine: ServeEngine, *, max_batch: int = 8,
                 max_wait_ms: float = 5.0, queue_capacity: int = 64,
                 high_water: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 bucket_ladder=None, pad_multiple=None,
                 min_bucket_h: Optional[int] = None,
                 telemetry=None, clock=time.monotonic,
                 perf_summary_every: int = 32,
                 menu_budget: Optional[int] = None,
                 flush_policy: Optional[str] = None,
                 stream_ttl_s: float = 300.0,
                 degrade_policy: str = "priced",
                 max_body_mb: float = 64.0, kinds=None):
        if flush_policy not in (None, "priced", "timer"):
            raise ValueError(f"unknown flush_policy {flush_policy!r} "
                             f"(priced | timer)")
        self.engine = engine
        # the scheduling core (can_tpu/sched): priced sub-batch menu +
        # priced flush deadlines.  Which side runs follows from the
        # request kinds the service serves: the core where it can price
        # every one of them (images), else no core at all (sched=None):
        # one launch size of max_batch slots, flushed by the max_wait_ms
        # timer (a language model; serve-exaone-chat-closed).  The two
        # keywords are for tests that want a core-less service or one
        # menu size; no CLI reaches them.  Asked for a kind it cannot
        # price, the core refuses (ValueError).
        from can_tpu.sched import COST_UNIT, DEFAULT_MENU_BUDGET, ServeSched
        from can_tpu.serve.kinds import ImageKind

        served = tuple(kinds.values()) if kinds else (ImageKind,)
        priced = all(k.cost_unit == COST_UNIT for k in served)
        if flush_policy is None:
            flush_policy = "priced" if priced else "timer"
        budget = ((DEFAULT_MENU_BUDGET if priced else 1)
                  if menu_budget is None else int(menu_budget))
        if budget == 1 and flush_policy == "timer":
            self.sched = None
        else:
            self.sched = ServeSched(int(max_batch), kinds=served,
                                    max_wait_s=float(max_wait_ms) / 1e3,
                                    menu_budget=budget,
                                    priced_flush=flush_policy == "priced")
        # fleet mode: dispatch enqueues instead of executing inline, and
        # replica workers call _complete/_fail_batch back on this service
        self._fleet = engine if hasattr(engine, "submit_work") else None
        if self._fleet is not None:
            self._fleet.bind(on_complete=self._complete,
                             on_fail=self._fail_batch,
                             on_reject=self._note_reject, clock=clock)
        self._replica_stats: dict = {}
        self.telemetry = telemetry if telemetry is not None else engine.telemetry
        self.max_batch = int(max_batch)
        self.default_deadline_s = (None if default_deadline_ms is None
                                   else float(default_deadline_ms) / 1e3)
        # result() safety margins (see ServeTicket)
        self.grace_s = max(1.0, 4 * float(max_wait_ms) / 1e3)
        self.default_result_timeout_s = 120.0
        self._clock = clock
        self.queue = BoundedRequestQueue(queue_capacity,
                                         high_water=high_water, clock=clock)
        self.batcher = MicroBatcher(self.queue, self._dispatch,
                                    max_batch=max_batch,
                                    max_wait_ms=max_wait_ms,
                                    bucket_ladder=bucket_ladder,
                                    pad_multiple=pad_multiple,
                                    min_bucket_h=min_bucket_h,
                                    ds=engine.ds, telemetry=self.telemetry,
                                    clock=clock,
                                    on_reject=self._note_reject,
                                    sched=self.sched,
                                    # in process, _dispatch returns with
                                    # the answers fetched and the requests
                                    # resolved; the fleet's only enqueues
                                    batch_free_on_return=self._fleet is None,
                                    # ... and may run as many launches at
                                    # once as the engine says it can hold
                                    # in flight (launch lanes); the fleet
                                    # has its replicas' workers for that
                                    launches_in_flight=(
                                        1 if self._fleet is not None else
                                        getattr(engine, "launches_in_flight",
                                                1)),
                                    # further request kinds by name
                                    # (serve/kinds.py); images always
                                    kinds=kinds)
        # request latency reservoir: p50/p95/max over recent requests,
        # tagged by bucket shape (skip_first=0 — warmup() already keeps
        # compiles off the request path, so every sample is steady-state).
        # Guarded by _lock: the batcher thread records while HTTP threads
        # read percentiles, and a deque mutated mid-iteration raises.
        self.latency = StepTimer(skip_first=0)
        self._lock = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "rejected": 0,
                       "degraded": 0,
                       "batches": 0, "batch_slots": 0, "batch_valid": 0}
        # stream sessions (serve/streams.py): HOST-side per-stream state
        # — count/density EWMAs, sequence hygiene, the degradation
        # ladder, sticky replica pins.  Living here (never on a replica)
        # is what makes sessions survive quarantine, wedge,
        # resurrection, rollout, and scale events by construction.
        # Requests without a stream_id never touch it.
        if max_body_mb <= 0:
            raise ValueError(f"max_body_mb must be positive, got "
                             f"{max_body_mb}")
        self.max_body_bytes = int(float(max_body_mb) * 2 ** 20)
        self.streams = StreamSessionRegistry(
            ttl_s=stream_ttl_s, clock=clock, telemetry=self.telemetry,
            sched=self.sched, policy=degrade_policy)
        self._started = False
        self._closed = False
        # image dtypes warmup() has compiled — the HTTP raw=1 gate: an
        # unwarmed dtype would compile for seconds ON the batcher thread,
        # stalling every bucket's flushes mid-traffic
        self.warmed_dtypes: set = set()
        # perf-attribution cadence: with a cost ledger on the bus
        # (Telemetry.ledger), one perf.summary event per this many
        # batches keeps the can_tpu_mfu_* gauges live without one event
        # per request (0/negative disables the periodic emit; warmup and
        # close still emit one each)
        self.perf_summary_every = int(perf_summary_every)
        self._perf_batches = 0
        import os as _os

        # pid + random tag: pid alone collides across containerised
        # replicas (both typically pid 1), which would merge two
        # unrelated requests' span trees in a joined artifact
        self._trace_prefix = f"req-{_os.getpid():x}{_os.urandom(2).hex()}"

    # -- lifecycle -------------------------------------------------------
    def warmup(self, bucket_shapes: Sequence[Tuple[int, int]],
               dtypes=(np.float32,)) -> dict:
        # the menu rides the warmup: every size the core may dispatch is
        # compiled here, so traffic never mints a program (the zero-new-
        # compiles pin holds per menu size, not just per bucket)
        report = self.engine.warmup(
            bucket_shapes, self.max_batch, dtypes=dtypes,
            sizes=self.sched.menu if self.sched is not None else None)
        self.warmed_dtypes.update(np.dtype(dt) for dt in dtypes)
        ledger = getattr(self.telemetry, "ledger", None)
        if ledger is not None:
            # every warmed bucket's flops/bytes (hence roofline class) is
            # known the moment warmup returns — publish before traffic;
            # MFU joins in once real batches provide timings
            ledger.emit_summary(self.telemetry, phase="serve_warmup")
        return report

    def start(self) -> "CountService":
        if not self._started:
            if self._fleet is not None:
                self._fleet.start()
            self.batcher.start()
            auto = getattr(self, "autoscaler", None)
            if auto is not None:
                # wired by cli/serve.py (or tests): the SLO/queue-driven
                # scale loop lives and dies with the service
                auto.start()
            inc = getattr(self.telemetry, "incidents", None)
            if inc is not None:
                # an incident bundle dumped while this service is alive
                # (replica quarantine, SLO burn, SIGTERM) carries the
                # live serving stats — queue depth, rejects, per-replica
                # health/generation — in its manifest (obs/incidents.py)
                inc.add_info_source("serve_stats", self.stats)
            # can-tpu-lint: disable=LOCKHELD(idempotent lifecycle flag; start/close run on the owner thread)
            self._started = True
        return self

    def close(self) -> None:
        """Stop admissions, drain in-flight work, reject the rest."""
        if self._closed:
            return
        # can-tpu-lint: disable=LOCKHELD(monotonic flag; a submit racing the flip is rejected by queue.close below)
        self._closed = True
        auto = getattr(self, "autoscaler", None)
        if auto is not None:
            # BEFORE the drain: a scale decision mid-teardown would race
            # the fleet's close choreography
            auto.close()
        for r in self.queue.close():
            r.reject(REJECT_SHUTDOWN, "service closing")
            self._count_reject(REJECT_SHUTDOWN)
        self.batcher.close()  # flushes pending groups through the engine
        if self._fleet is not None:
            # after the batcher: its shutdown flush enqueues final work,
            # which the replicas drain before their threads stop
            self._fleet.close()
        ledger = getattr(self.telemetry, "ledger", None)
        if ledger is not None:
            ledger.emit_summary(self.telemetry, phase="serve_close")
        # can-tpu-lint: disable=LOCKHELD(idempotent lifecycle flag; start/close run on the owner thread)
        self._started = False

    def __enter__(self) -> "CountService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- the programmatic API --------------------------------------------
    def submit(self, image: np.ndarray, *,
               deadline_ms: Optional[float] = None,
               want_density: bool = False,
               stream_id: Optional[str] = None,
               frame_seq: Optional[int] = None,
               trace_id: Optional[str] = None) -> ServeTicket:
        """Enqueue one prepared image (see ``prepare_image``).  Returns a
        ticket whose ``result()`` either yields a ``ServeResult`` or raises
        ``RejectedError`` — immediate rejection (full queue, shedding,
        shutdown) still returns a ticket, with the rejection stored.

        ``stream_id`` opts the request into a per-stream session
        (serve/streams.py): sequence hygiene on ``frame_seq``, sticky
        replica routing, and the degradation ladder — under overload the
        frame may be answered from the stream's EWMA (``degraded: true``
        + staleness on the result) instead of launched or rejected.
        Without a stream_id the request takes the EXACT stateless path
        (pinned by test)."""
        if frame_seq is not None and stream_id is None:
            # same validation as the HTTP layer: silently dropping the
            # seq would leave a caller believing the sequence gate is
            # on while duplicates sail through
            raise ValueError("frame_seq needs a stream_id (the sequence "
                             "gate is per-stream)")
        deadline_s = (float(deadline_ms) / 1e3 if deadline_ms is not None
                      else self.default_deadline_s)
        req = ServeRequest(np.asarray(image), deadline_s=deadline_s,
                           want_density=want_density, clock=self._clock,
                           stream_id=stream_id, frame_seq=frame_seq)
        # the trace is born at the front door: the request's spans
        # (request, queue_wait; its batch's phases link by ``batch``)
        # key on this id, and HTTP clients get it back in the response.
        # A caller-provided id (the X-CanTpu-Trace-Id request header, or
        # an upstream service propagating its own) wins over minting —
        # that is what stitches one trace ACROSS hosts: every hop's
        # spans key on the same id, and the fleet collector's snapshot
        # exports them as one skew-corrected timeline
        self._stamp(req, trace_id)
        if req.shape[0] % self.engine.ds or req.shape[1] % self.engine.ds:
            raise ValueError(
                f"image shape {req.shape} is not snapped to the /"
                f"{self.engine.ds} density grid — call prepare_image first")
        bucket = self.batcher.bucket_of(req.shape)
        if bucket[0] < req.shape[0] or bucket[1] < req.shape[1]:
            # above the top ladder bound the snap goes DOWN, and the batch
            # assembly would raise — poisoning every co-batched request.
            # Reject the oversized image at the door instead (client error)
            raise ValueError(
                f"image {req.shape[0]}x{req.shape[1]} exceeds the largest "
                f"bucket {bucket[0]}x{bucket[1]} — resize it or serve with "
                f"a bigger bucket ladder")
        if not self._count_in(req):
            return ServeTicket(req, self)
        if stream_id is None:
            return self._offer(req)
        return self._submit_stream(req, bucket)

    def _stamp(self, req: ServeRequest, trace_id: Optional[str]) -> None:
        """The request's trace id (the caller's, else minted) and, with a
        tracer active, its submit stamp on the spans' clock (not the
        service's injectable one: that stays for deadlines and fake-clock
        tests)."""
        req.trace_id = trace_id or f"{self._trace_prefix}-{req.id}"
        if active(self.telemetry) is not None:
            req.t_trace = time.perf_counter()

    def _count_in(self, req: ServeRequest) -> bool:
        """Count the submission; False (the request rejected) when the
        service has closed."""
        with self._lock:
            self._stats["submitted"] += 1
        if self._closed:
            req.reject(REJECT_SHUTDOWN, "service closed")
            self._count_reject(REJECT_SHUTDOWN)
            return False
        return True

    def _offer(self, req: ServeRequest) -> ServeTicket:
        """The stateless admission: the queue admits or rejects."""
        reason = self.queue.offer(req)
        if reason is not None:
            self._count_reject(reason)
        return ServeTicket(req, self)

    def _submit_stream(self, req: ServeRequest,
                       bucket) -> ServeTicket:
        """The stream admission path: registry decision first (sequence
        gate + degradation ladder), then the queue — and a queue refusal
        degrades to the EWMA when one exists instead of rejecting (the
        "degrade instead of drown" rung the ladder's pricing may not
        have caught yet)."""
        now = self._clock()
        dec = self.streams.admit(req.stream_id, req.frame_seq, now,
                                 bucket)
        if dec.kind == "stale":
            req.reject(REJECT_STALE_FRAME, dec.detail)
            self._count_reject(REJECT_STALE_FRAME)
            return ServeTicket(req, self)
        if dec.kind == "overload":
            req.reject(REJECT_STREAM_OVERLOAD, dec.detail)
            self._count_reject(REJECT_STREAM_OVERLOAD)
            return ServeTicket(req, self)
        if dec.kind == "degrade":
            self._resolve_degraded(req, bucket, dec)
            return ServeTicket(req, self)
        self.streams.note_admitted(req)
        reason = self.queue.offer(req, reject=False)
        if reason is not None:
            fb = self.streams.degrade_fallback(req.stream_id, now)
            if fb is not None:
                self._resolve_degraded(req, bucket, fb,
                                       fallback=reason)
            else:
                # refused with nothing to degrade to: un-commit the
                # frame's sequence so the camera's RETRY of this
                # never-answered frame passes the gate instead of
                # bouncing off it as stale_frame forever
                self.streams.rollback_seq(req.stream_id, req.frame_seq,
                                          dec.prior_seq)
                req.reject(reason,
                           f"outstanding {self.queue.outstanding()}")
                self._count_reject(reason)
        return ServeTicket(req, self)

    def _resolve_degraded(self, req: ServeRequest, bucket, dec,
                          fallback: Optional[str] = None) -> None:
        """Answer a stream frame from its session EWMA — no queue, no
        batch, no launch: a degraded answer must be CHEAP.  Labelled
        ``degraded: true`` with staleness seconds on both the result
        and the ``serve.request`` event; deliberately kept OUT of the
        device-latency reservoir (an instant EWMA answer in the p99
        would make overload look like a latency win)."""
        now = self._clock()
        dens = None
        if req.want_density and dec.density is not None:
            h, w = req.shape
            d = dec.density
            if d.shape[:2] == (h // self.engine.ds, w // self.engine.ds):
                dens = d
        res = ServeResult(count=float(dec.count), density=dens,
                          bucket_hw=tuple(bucket), batch_fill=0.0,
                          latency_s=now - req.t_submit,
                          queue_wait_s=0.0, device_s=0.0,
                          trace_id=req.trace_id, degraded=True,
                          staleness_s=dec.staleness_s,
                          stream_id=req.stream_id)
        req.resolve(res)
        with self._lock:
            self._stats["completed"] += 1
            self._stats["degraded"] += 1
        payload = {"request_id": req.id,
                   "latency_s": round(res.latency_s, 6),
                   "bucket": list(bucket), "ok": True,
                   "trace_id": req.trace_id, "degraded": True,
                   "stream": req.stream_id}
        if dec.staleness_s is not None:
            payload["staleness_s"] = dec.staleness_s
        if fallback is not None:
            # the queue refused this frame (queue_full/backpressure);
            # the session EWMA absorbed it instead of a reject
            payload["fallback"] = fallback
        self.telemetry.emit("serve.request", **payload)

    def predict(self, image: np.ndarray, *,
                deadline_ms: Optional[float] = None,
                want_density: bool = False,
                timeout: Optional[float] = None,
                stream_id: Optional[str] = None,
                frame_seq: Optional[int] = None,
                trace_id: Optional[str] = None) -> ServeResult:
        """submit + result in one call (the closed-loop client pattern)."""
        return self.submit(image, deadline_ms=deadline_ms,
                           want_density=want_density, stream_id=stream_id,
                           frame_seq=frame_seq,
                           trace_id=trace_id).result(timeout)

    def stats(self) -> dict:
        with self._lock:
            s = dict(self._stats)
            lat = self.latency.percentiles()
            rep_counts = {k: dict(v) for k, v in self._replica_stats.items()}
        slots = max(s["batch_slots"], 1)
        out = {
            **s,
            "queue_depth": self.queue.depth(),
            "shedding": self.queue.shedding,
            "mean_batch_fill": round(s["batch_valid"] / slots, 4),
            "latency_p50_s": lat["p50_s"],
            "latency_p95_s": lat["p95_s"],
            "latency_max_s": lat["max_s"],
            "compile_count": self.engine.compile_count,
            # per-stream sessions (serve/streams.py): the operator's
            # view of the degradation ladder and sticky routing
            "streams": self.streams.stats(),
            # launched batches by why their group was flushed (full /
            # due / drain; serve/batcher.py)
            "flush_reasons": dict(self.batcher.flush_reasons),
            # launches by how their batch was assembled (into a staging
            # buffer that already existed / fresh) and the bytes the
            # batcher's staging pool holds now
            "staging": dict(self.batcher.staging),
            # launches handed to a launch lane while another was in flight
            # (of ``batches``): how often the overlap engages
            "launches_overlapped": self.batcher.launches_overlapped,
        }
        stage1 = getattr(self.engine, "stage1", None)
        if stage1 is not None:
            # per compiled program ("BxHxW:dtype"), how it carries the
            # network's first stage: "folded" (W-pairs of 128 channels)
            # or "plain" (models/cannet.py::stage1_layout); an engine of
            # another model has none
            out["stage1"] = dict(stage1)
        if self._fleet is not None:
            # per-replica rows: service-side work counters joined with the
            # fleet's health snapshot — obs/exporter.py renders these as
            # can_tpu_serve_*{replica="k"} labelled lines
            fh = self._fleet.healthz()
            health = {r["replica"]: r for r in fh["replicas"]}
            out["replicas"] = {
                str(k): {**rep_counts.get(k, {"batches": 0,
                                              "completed": 0}),
                         "quarantined": int(h["state"] != "active"),
                         "failures": h["failures"],
                         "generation": h["generation"]}
                for k, h in health.items()}
            out["live_replicas"] = fh["live"]
            out["generation"] = fh["generation"]
            # generation skew is an operator-visible fact, not something
            # to diff out of the per-replica rows by hand: a fleet
            # serving two checkpoints at once shows mixed_generations=1
            # on /stats and the scrape
            out["mixed_generations"] = bool(fh.get("mixed_generations"))
        return out

    def latency_percentile(self, q: float):
        """One request-latency percentile under the service lock (the
        reservoir is a deque the batcher thread appends to; an unlocked
        read can see it mutate mid-iteration) — the autoscaler's p99
        signal."""
        with self._lock:
            return self.latency.percentile(q)

    # -- batcher dispatch (the batcher thread, or one of its launch lanes) -
    def _dispatch(self, bucket_hw, batch, requests) -> None:
        if self._fleet is not None:
            # hand the assembled batch to whichever replica frees up
            # first; the worker thread calls _complete (or _fail_batch).
            # Stream batches carry their sticky pin (validated against
            # the LIVE replica set right here — a pin to a quarantined/
            # wedged/replaced incarnation is re-pinned before it can
            # queue behind a corpse)
            pin = None
            if (self.streams.active_count()
                    and hasattr(self._fleet, "live_tokens")):
                pin = self.streams.pin_for(requests,
                                           self._fleet.live_tokens())
            self._fleet.submit_work(bucket_hw, batch, requests, pin=pin)
            return
        t_exec0 = self._clock()
        t0 = time.perf_counter()
        counts, density = self.engine.predict_batch(
            batch, want_density=any(r.want_density for r in requests))
        # execute_s stays on perf_counter (honest wall time even under
        # the fake clocks the tests drive)
        execute_s = time.perf_counter() - t0
        compiled = self.engine.last_batch_compiled
        self._complete(bucket_hw, batch, requests, counts, density,
                       execute_s, compiled, t_exec0=t_exec0)

    # -- batch completion (whoever dispatched, or a fleet replica worker) -
    def _complete(self, bucket_hw, batch, requests, counts, density,
                  execute_s, compiled, replica=None,
                  program: str = "serve_predict", t_exec0=None) -> None:
        if getattr(self.telemetry, "trace", None) is not None:
            # an operator's --trace-steps window counts launched batches
            self.telemetry.step_tick()
        done = (bucket_hw, batch, requests, counts, density, execute_s,
                compiled, replica, program, t_exec0)
        tr = active(self.telemetry)
        if tr is None:
            self._resolve_batch(*done, None)
        else:
            with tr.span("serve.complete", resolved=len(requests)):
                self._resolve_batch(*done, tr)

    def _resolve_batch(self, bucket_hw, batch, requests, counts, density,
                       execute_s, compiled, replica, program, t_exec0,
                       tr) -> None:
        if t_exec0 is None:
            # fleet path: the worker measured execute_s on perf_counter;
            # place the execute window by subtracting it on the service
            # clock (exact for the default monotonic clock)
            t_exec0 = self._clock() - execute_s
        slots = batch.sample_mask.shape[0]
        fill = len(requests) / slots
        now = self._clock()
        for slot, req in enumerate(requests):
            latency = now - req.t_submit
            # assembly stamps come from the batcher; a request dispatched
            # through a path that skipped them (flush_all on a hand-driven
            # batcher) degrades to a zero-width assembly window
            t_asm = req.t_assembly if req.t_assembly is not None else t_exec0
            t_ready = req.t_ready if req.t_ready is not None else t_exec0
            queue_wait = max(t_asm - req.t_submit, 0.0)
            res = self._result_for(slot, req, counts, density, dict(
                bucket_hw=tuple(bucket_hw), batch_fill=fill,
                latency_s=latency, queue_wait_s=round(queue_wait, 6),
                device_s=round(execute_s, 6), trace_id=req.trace_id))
            if req.stream_id is not None:
                # fold the fresh count (and density, when fetched) into
                # the stream's session BEFORE resolving: a degraded
                # answer racing this completion serves the newest EWMA.
                # The serving replica becomes the stream's sticky pin
                # (first completion only; pins move via re-pin, not
                # work stealing).
                self.streams.note_completed(
                    req.stream_id, res.count, res.density, bucket_hw,
                    now=now, replica=replica,
                    token=None if replica is None else program)
            req.resolve(res)
            with self._lock:
                self.latency.record(latency, shape=tuple(bucket_hw))
            self.telemetry.emit("serve.request", request_id=req.id,
                               latency_s=round(latency, 6),
                               bucket=list(bucket_hw), ok=True,
                               trace_id=req.trace_id,
                               queue_wait_s=round(queue_wait, 6),
                               assembly_s=round(max(t_ready - t_asm, 0.0), 6),
                               device_s=round(execute_s, 6))
            if tr is not None and req.t_trace is not None:
                # the request's own two spans, from the perf_counter
                # stamp it took at submit; the phases of its batch are
                # recorded once, on the batch (``batch`` links to it)
                sp = req.batch_span
                t_done = time.perf_counter()
                root = tr.emit(trace_id=req.trace_id, name="request",
                               start=req.t_trace, end=t_done,
                               bucket=list(bucket_hw), ok=True,
                               batch=None if sp is None else sp.span_id)
                if sp is not None:
                    tr.emit(trace_id=req.trace_id, name="queue_wait",
                            start=req.t_trace, end=sp.start,
                            parent_id=root)
        with self._lock:
            self._stats["completed"] += len(requests)
            self._stats["batches"] += 1
            self._stats["batch_slots"] += slots
            self._stats["batch_valid"] += len(requests)
            if replica is not None:
                rs = self._replica_stats.setdefault(
                    replica, {"batches": 0, "completed": 0})
                rs["batches"] += 1
                rs["completed"] += len(requests)
        extra = {} if replica is None else {"replica": replica}
        # scheduler economics on every flush: dead slots, fill %, and the
        # core's predicted vs realized launch cost (pixel units, the
        # offline planner's).  predicted is recomputed INDEPENDENTLY from
        # the valid count (ServeSched.cover_one) — the batcher chose the
        # size through the same core, so any divergence is a scheduling
        # bug the can_tpu_sched_* gauges must surface, not noise.  The
        # legacy no-core service predicts its own contract: every launch
        # pads to max_batch.
        # drain pricing for the stream degradation ladder: every
        # completed batch (stream or not) feeds the bucket's measured
        # seconds-per-slot, so the pricing is warm before the first
        # stream needs a skip decision
        self.streams.observe_batch(bucket_hw, execute_s, slots)
        area = float(bucket_hw[0] * bucket_hw[1])
        if self.sched is not None:
            predicted = self.sched.predicted_cost_px(area, len(requests))
            realized = self.sched.realized_cost_px(area, slots)
        else:
            predicted = area * self.max_batch
            realized = area * slots
        self.telemetry.emit("serve.batch", bucket=list(bucket_hw),
                           size=slots, valid=len(requests),
                           fill=round(fill, 4),
                           fill_pct=round(100.0 * fill, 2),
                           padded_slots=slots - len(requests),
                           predicted_cost_px=round(predicted, 1),
                           realized_cost_px=round(realized, 1),
                           execute_s=round(execute_s, 6),
                           compiled=compiled,
                           queue_depth=self.queue.depth(), **extra)
        ledger = getattr(self.telemetry, "ledger", None)
        if ledger is not None:
            if not compiled:
                # steady-state launch time for this program (first-call
                # compiles are the compile event's bill, same exclusion
                # rule as the step reservoirs); fleet batches bill their
                # replica's own program name
                payload = self.batcher.kinds[requests[0].kind].payload(batch)
                ledger.observe(program, tuple(payload.shape),
                               execute_s, dtype=str(payload.dtype))
            # under _lock: fleet replica workers call _complete
            # concurrently, and an unlocked += here can lose counts or
            # double-emit the periodic summary (lint: LOCKHELD)
            with self._lock:
                self._perf_batches += 1
                due = 0 < self.perf_summary_every <= self._perf_batches
                if due:
                    self._perf_batches = 0
            if due:
                ledger.emit_summary(self.telemetry, phase="serve")

    def _result_for(self, slot: int, req, counts, density, common: dict):
        """What slot ``slot`` of a completed launch answers its request
        with; ``common``: the fields every kind of result carries."""
        h, w = req.shape
        dens = (np.asarray(density[slot, : h // self.engine.ds,
                                   : w // self.engine.ds])
                if req.want_density else None)
        return ServeResult(count=float(counts[slot]), density=dens,
                           stream_id=req.stream_id, **common)

    def _note_reject(self, reason: str, count: int = 1) -> None:
        """Count a rejection that already emitted its own telemetry
        (batcher-side deadline/error paths) — stats() must agree with the
        RejectedErrors clients actually saw."""
        with self._lock:
            self._stats["rejected"] += count

    def _count_reject(self, reason: str) -> None:
        self._note_reject(reason)
        self.telemetry.emit("serve.reject", reason=reason, count=1,
                           queue_depth=self.queue.depth())

    def _fail_batch(self, requests, exc) -> None:
        """Fleet failure sink: a batch that failed on two replicas (or
        outlived every replica) rejects its requests with ``error`` —
        mirror of the batcher's poison-batch containment."""
        n = 0
        for r in requests:
            if not r.done:
                r.reject(REJECT_ERROR, f"{type(exc).__name__}: {exc}")
                n += 1
        if n:
            self._note_reject(REJECT_ERROR, n)
            self.telemetry.emit("serve.reject", reason=REJECT_ERROR,
                                count=n,
                                detail=f"{type(exc).__name__}: {exc}")

    # -- fleet health / rollout ------------------------------------------
    def healthz(self) -> dict:
        """Liveness + (for a fleet) per-replica state: the /healthz body.
        A fleet with zero live replicas reports ok=False — the probe that
        tells an orchestrator to restart or reroute."""
        if self._fleet is None:
            return {"ok": True}
        return self._fleet.healthz()

    def rollout(self, params, batch_stats=None, *, run_config=None,
                allow_config_change: bool = False) -> dict:
        """Blue/green checkpoint flip (fleet engines only): see
        ``FleetEngine.rollout``.  Single-engine services must restart —
        there is no second engine to stage on."""
        if self._fleet is None:
            raise RuntimeError("rollout needs a FleetEngine "
                               "(serve with --replicas >= 2 fleet mode)")
        return self._fleet.rollout(params, batch_stats,
                                   run_config=run_config,
                                   allow_config_change=allow_config_change)


class GenerateService(CountService):
    """The same front door for a language model: prompts of token ids in,
    generated ids out.  Queue, batcher, scheduler, spans, staging,
    lifecycle, rejection and statistics are ``CountService``'s; what
    differs is the request kind (``serve.kinds.TokenKind``: prompts bucket
    on a length ladder), the engine's call (one flush is a prefill and then
    decode steps, ``LMEngine.generate_batch``) and the result.  Static
    batches: a slot that has finished waits for its launch."""

    def __init__(self, engine, *, length_ladder, **kw):
        from can_tpu.serve.kinds import TOKENS, TokenKind

        self.token_kind = TokenKind(length_ladder)
        super().__init__(engine, kinds={TOKENS: self.token_kind}, **kw)
        if self._fleet is not None:
            raise ValueError("GenerateService runs one engine in process")

    def warmup(self, buckets=None) -> dict:
        """Compile every (bucket, menu size) launch before traffic."""
        return self.engine.warmup(
            self.token_kind.ladder if buckets is None else buckets,
            self.max_batch,
            sizes=self.sched.menu if self.sched is not None else None)

    def submit(self, tokens, *, max_new_tokens: Optional[int] = None,
               want_logits: bool = False,
               deadline_ms: Optional[float] = None,
               trace_id: Optional[str] = None) -> ServeTicket:
        """Enqueue one prompt; the ticket's ``result()`` is a
        ``GenerateResult`` with ``max_new_tokens`` generated ids (and the
        probe logits, if asked for).  A prompt past the length ladder or
        ids outside the vocabulary held here are refused at the door."""
        cap = self.engine.programs.max_new_tokens
        n_new = cap if max_new_tokens is None else int(max_new_tokens)
        if not 1 <= n_new <= cap:
            raise ValueError(f"max_new_tokens {n_new} outside 1..{cap}")
        deadline_s = (float(deadline_ms) / 1e3 if deadline_ms is not None
                      else self.default_deadline_s)
        req = TokenRequest(tokens, max_new_tokens=n_new,
                           want_logits=want_logits, deadline_s=deadline_s,
                           clock=self._clock)
        self.token_kind.bucket_of(req.shape[1])  # ValueError past the ladder
        held = self.engine.programs.vocab_size
        if req.tokens.min() < 0 or req.tokens.max() >= held:
            raise ValueError(f"token ids must lie in 0..{held - 1}, the "
                             f"vocabulary slice held here")
        self._stamp(req, trace_id)
        if not self._count_in(req):
            return ServeTicket(req, self)
        return self._offer(req)

    def generate(self, tokens, *, timeout: Optional[float] = None, **kw):
        """submit + result in one call."""
        return self.submit(tokens, **kw).result(timeout)

    def stats(self) -> dict:
        out = super().stats()
        # the engine's counters (the batcher thread writes them between
        # launches; a copy of plain numbers)
        out["lm"] = dict(self.engine.counters)
        return out

    def _dispatch(self, bucket_hw, batch, requests) -> None:
        t_exec0 = self._clock()
        t0 = time.perf_counter()
        ids, probes = self.engine.generate_batch(
            batch, steps=max(r.max_new_tokens for r in requests),
            want_logits=any(r.want_logits for r in requests))
        execute_s = time.perf_counter() - t0
        self._complete(bucket_hw, batch, requests, ids, probes, execute_s,
                       self.engine.last_batch_compiled,
                       program=self.engine.name, t_exec0=t_exec0)

    def _result_for(self, slot: int, req, ids, probes, common: dict):
        logits = routing = None
        if req.want_logits:
            logits = {k: v["logits"][slot] for k, v in probes.items()}
            routing = {k: v["choices"][:, slot] for k, v in probes.items()}
        return GenerateResult(tokens=ids[slot, :req.max_new_tokens].copy(),
                              logits=logits, routing=routing, **common)


def build_model_service(config: dict, *, params=None, seed: int = 0,
                        telemetry=None, break_programs=None):
    """Queue, batcher, engine and service for the model a configuration
    file describes (``benchmark/configs/*.json``'s format): the ONE
    construction ``can_tpu.cli.serve --model-config`` and the benchmark's
    driver both use.  What is the model's own (its programs, the engine
    that runs them, the service in front) comes from the table in
    ``serve/programs.py`` under the file's ``model_type``; no network is
    named here.  ``params``: the model's parameter tree; made from ``seed``
    where there is no checkpoint.  ``break_programs``: tests and
    calibration only, ``fn(programs)`` before the engine takes them."""
    from can_tpu.serve.programs import serving_model

    model = serving_model(config.get("model_type"))
    programs, params = model.programs(config, params, seed)
    if break_programs is not None:
        break_programs(programs)
    engine = model.engine(params, programs, config, telemetry)
    capacity = int(config["queue_capacity"])
    return model.service(
        engine, config, max_batch=int(config["max_batch"]),
        max_wait_ms=float(config["max_wait_ms"]), queue_capacity=capacity,
        high_water=max(1, (3 * capacity) // 4), telemetry=telemetry)


# -- HTTP front end -----------------------------------------------------
def make_http_handler(service: CountService):
    """Request handler class bound to ``service``.

    POST /predict    body: .npy bytes (np.save of an HWC uint8/float32
                     image); query: ?deadline_ms=&density=1&raw=1
                     (raw=1 keeps uint8 pixels and normalises ON DEVICE —
                     the u8 transfer mode; needs the u8 programs warmed,
                     cli --u8-warmup); ?stream_id=cam1&frame_seq=17 opts
                     into a per-stream session (serve/streams.py):
                     sticky routing, sequence hygiene, and the
                     degradation ladder — a frame-skipped answer carries
                     "degraded": true + "staleness_s"
                     -> 200 {"count", "latency_ms", "bucket", "batch_fill"
                             [, "density"]}; stream requests add
                             {"degraded"[, "staleness_s"]}
                     -> 408/503 {"error", "reason"} on deadline/shedding;
                        409 on a stale/duplicate frame_seq; 413 when the
                        body exceeds --max-body-mb
    GET  /healthz    -> 200/503 {"ok", ...}; fleet services add per-
                     replica state (quarantine visible here), live count,
                     generation — 503 when zero replicas are live
    GET  /stats      -> 200 stats() JSON
    POST /rollout    (fleet only) body: JSON checkpoint source — the
                     same keys the CLI takes ({"checkpoint_dir", "epoch",
                     "params_npz", "torch_pth", "allow_config_change"}) —
                     loaded via ``service.rollout_loader`` (wired by
                     cli/serve.py), then blue/green-flipped.  Synchronous:
                     replies with the rollout report when the last replica
                     has flipped; live traffic keeps flowing meanwhile.
    """
    from http.server import BaseHTTPRequestHandler
    from urllib.parse import parse_qs, urlparse

    from can_tpu.serve.queue import (
        REJECT_BACKPRESSURE,
        REJECT_DEADLINE,
        REJECT_QUEUE_FULL,
    )

    status_of = {REJECT_DEADLINE: 408, REJECT_QUEUE_FULL: 503,
                 REJECT_BACKPRESSURE: 503, REJECT_SHUTDOWN: 503,
                 # a stale/duplicate stream frame is the client's
                 # ordering problem (409), not server load (503)
                 REJECT_STALE_FRAME: 409, REJECT_STREAM_OVERLOAD: 503}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _send(self, code: int, payload: dict,
                  headers: Optional[dict] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _body_capped(self) -> Optional[int]:
            """Content-Length, or None after answering 413/400: a
            multi-GB POST must be refused BEFORE ``rfile.read``
            materialises it on the serve host (the DoS shape: one
            request, whole-host OOM).  A malformed or NEGATIVE header
            is a 400 — ``rfile.read(-1)`` would read until EOF, which
            on a keep-alive socket is never: the handler thread hangs,
            and a handful of such requests exhaust the thread pool
            (the same DoS through the guard's own gap).  The cap is
            named so the operator knows which knob moves it."""
            try:
                n = int(self.headers.get("Content-Length", "0"))
                if n < 0:
                    raise ValueError(f"negative Content-Length {n}")
            except ValueError as e:
                self._send(400, {"error": f"bad request: {e}"})
                return None
            if n > service.max_body_bytes:
                self._send(413, {
                    "error": f"request body {n} bytes exceeds the "
                             f"{service.max_body_bytes} byte cap "
                             f"(--max-body-mb="
                             f"{service.max_body_bytes / 2 ** 20:g})"})
                return None
            return n

        def log_message(self, fmt, *args):  # quiet: telemetry is the log
            pass

        def do_GET(self):
            path = urlparse(self.path).path
            if path == "/healthz":
                health = service.healthz()
                self._send(200 if health.get("ok") else 503, health)
            elif path == "/stats":
                self._send(200, service.stats())
            else:
                self._send(404, {"error": f"no such path: {path}"})

        def _do_rollout(self):
            # cap FIRST: an oversized body is refused regardless of
            # rollout wiring (the 413 is the DoS guard, not a feature
            # of the endpoint)
            n = self._body_capped()
            if n is None:
                return
            loader = getattr(service, "rollout_loader", None)
            if loader is None:
                self._send(501, {"error": "rollout is not wired on this "
                                          "server (no rollout_loader; "
                                          "fleet CLI serves wire it)"})
                return
            try:
                spec = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(spec, dict):
                    raise ValueError("rollout body must be a JSON object")
            except Exception as e:  # noqa: BLE001 — client error
                self._send(400, {"error": f"bad request: {e}"})
                return
            try:
                allow = bool(spec.pop("allow_config_change", False))
                params, batch_stats, run_config = loader(spec)
                report = service.rollout(params, batch_stats,
                                         run_config=run_config,
                                         allow_config_change=allow)
            except (ValueError, RuntimeError, FileNotFoundError) as e:
                # drift guard / structure guard / bad source: refused,
                # the serving fleet is untouched
                self._send(409, {"error": str(e)})
                return
            except Exception as e:  # noqa: BLE001 — corrupt .npz,
                # IsADirectoryError, ... must answer the client, never
                # drop the socket with a raw handler-thread traceback
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            self._send(200, report)

        def _do_generate(self):
            """POST /generate {"tokens": [...], "max_new_tokens": n}
            -> {"tokens": [...], "latency_ms"} (a GenerateService only)."""
            n = self._body_capped()
            if n is None:
                return
            if not hasattr(service, "generate"):
                self._send(501, {"error": "this server serves images "
                                          "(POST /predict)"})
                return
            try:
                spec = json.loads(self.rfile.read(n) or b"{}")
                res = service.generate(
                    np.asarray(spec["tokens"], np.int32),
                    max_new_tokens=spec.get("max_new_tokens"),
                    deadline_ms=spec.get("deadline_ms"))
            except (KeyError, TypeError, ValueError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            except RejectedError as e:
                self._send(status_of.get(e.reason, 503),
                           {"error": str(e), "reason": e.reason})
                return
            self._send(200, {"tokens": res.tokens.tolist(),
                             "latency_ms": round(res.latency_s * 1e3, 3),
                             "bucket": list(res.bucket_hw),
                             "batch_fill": res.batch_fill})

        def do_POST(self):
            url = urlparse(self.path)
            if url.path == "/rollout":
                self._do_rollout()
                return
            if url.path == "/generate":
                self._do_generate()
                return
            if url.path != "/predict":
                self._send(404, {"error": f"no such path: {url.path}"})
                return
            if hasattr(service, "generate"):
                self._send(501, {"error": "this server serves prompts of "
                                          "token ids (POST /generate)"})
                return
            n = self._body_capped()
            if n is None:
                return
            try:
                arr = np.load(io.BytesIO(self.rfile.read(n)),
                              allow_pickle=False)
                q = parse_qs(url.query)
                deadline_ms = (float(q["deadline_ms"][0])
                               if "deadline_ms" in q else None)
                want_density = q.get("density", ["0"])[0] not in ("0", "")
                raw = q.get("raw", ["0"])[0] not in ("0", "")
                stream_id = q.get("stream_id", [None])[0] or None
                frame_seq = (int(q["frame_seq"][0])
                             if "frame_seq" in q else None)
                # cross-host trace propagation: an upstream hop's id
                # rides in on this header, keys every span this host
                # emits, and is echoed back on the response — one
                # trace_id, one stitched timeline (tools/trace_export.py
                # over a collector snapshot)
                trace_in = self.headers.get("X-CanTpu-Trace-Id") or None
                if frame_seq is not None and stream_id is None:
                    raise ValueError("frame_seq needs a stream_id")
                if raw and arr.dtype != np.uint8:
                    raise ValueError("raw=1 needs uint8 pixels")
                if raw and np.dtype(np.uint8) not in service.warmed_dtypes:
                    # an unwarmed dtype would compile mid-traffic on the
                    # batcher thread, stalling every bucket — refuse at
                    # the door (serve with --u8-warmup to enable)
                    raise ValueError("raw=1 (uint8) programs are not "
                                     "warmed on this server; start it "
                                     "with --u8-warmup")
                image = prepare_image(arr, ds=service.engine.ds,
                                      normalize=not raw)
            except Exception as e:  # noqa: BLE001 — client error, not ours
                self._send(400, {"error": f"bad request: {e}"})
                return
            try:
                res = service.predict(image, deadline_ms=deadline_ms,
                                      want_density=want_density,
                                      stream_id=stream_id,
                                      frame_seq=frame_seq,
                                      trace_id=trace_in)
            except ValueError as e:  # submit-side validation: client error
                self._send(400, {"error": f"bad request: {e}"})
                return
            except RejectedError as e:
                self._send(status_of.get(e.reason, 503),
                           {"error": str(e), "reason": e.reason})
                return
            payload = {"count": res.count,
                       "latency_ms": round(res.latency_s * 1e3, 3),
                       "bucket": list(res.bucket_hw),
                       "batch_fill": res.batch_fill}
            if res.trace_id is not None:
                # the handle into the exported span timeline
                # (tools/trace_export.py --trace-id)
                payload["trace_id"] = res.trace_id
            if res.queue_wait_s is not None:
                payload["queue_wait_ms"] = round(res.queue_wait_s * 1e3, 3)
            if stream_id is not None:
                # stream answers are LABELLED: a client can always tell
                # a fresh inference from a served EWMA.  Non-stream
                # responses keep the exact pre-stream body (pinned)
                payload["degraded"] = bool(res.degraded)
                if res.staleness_s is not None:
                    payload["staleness_s"] = round(res.staleness_s, 6)
            if res.density is not None:
                payload["density"] = res.density[..., 0].tolist()
            self._send(200, payload,
                       headers=({"X-CanTpu-Trace-Id": res.trace_id}
                                if res.trace_id is not None else None))

    return Handler


def serve_http(service: CountService, *, host: str = "127.0.0.1",
               port: int = 8000):
    """Build a ``ThreadingHTTPServer`` for ``service`` (caller runs
    ``serve_forever()``; threads give one blocked client per connection
    while the batcher thread and its launch lanes own the device)."""
    from http.server import ThreadingHTTPServer

    return ThreadingHTTPServer((host, port), make_http_handler(service))
