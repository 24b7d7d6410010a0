"""Host-side epoch loops (the reference's utils/train_eval_utils.py re-done).

Differences from the reference, by design:

* metrics returned by the compiled steps are already global (GSPMD reduces
  across chips in-program) — no per-step ``reduce_value`` collective
  (reference :39) and no end-of-epoch ``cuda.synchronize`` (:55-57); we
  block once per epoch on the last metric fetch.
* non-finite loss raises ``NonFiniteLossError`` on every host
  simultaneously instead of rank-locally ``sys.exit(1)``-ing into a NCCL
  deadlock (reference :48-50; SURVEY §5).  Metric fetches are batched in
  windows of ``check_every`` steps, so the pipeline only drains once per
  window — never per step.
* eval MAE/MSE denominators use the true dataset size, not the
  padding-inflated sampler total (reference train.py:157 bias).
* per-epoch wall time and images/sec are measured and returned (the
  observability the reference's tqdm gave for free, minus the host syncs).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Iterable, NamedTuple, Optional

import jax
import numpy as np

from can_tpu.models.cannet import program_key, stage1_traced
from can_tpu.obs.spans import active
from can_tpu.parallel.elastic import ElasticInterrupt
from can_tpu.train.steps import NonFiniteLossError


def _progress(iterable, *, enabled: bool, desc: str, total: Optional[int]):
    if not enabled:
        return iterable
    try:
        from tqdm import tqdm

        return tqdm(iterable, desc=desc, total=total)
    except ImportError:  # pragma: no cover
        return iterable


class EpochStats(NamedTuple):
    """One epoch's results: mean per-image ``loss`` plus throughput.

    ``images`` counts valid samples (mask-zero fill slots excluded);
    ``distinct_shapes`` counts distinct full batch signatures (batch dim
    included) seen this epoch = executables exercised.  (Until r4 this
    subclassed float so old callers could treat the whole object as the
    loss — a surprise worth breaking: read ``stats.loss`` explicitly,
    VERDICT r4 weak-5.)"""

    loss: float
    seconds: float = 0.0
    images: float = 0.0
    steps: int = 0
    distinct_shapes: int = 0

    @property
    def img_per_s(self) -> float:
        return self.images / self.seconds if self.seconds > 0 else 0.0

    @property
    def programs(self) -> int:
        """Realized program count: with remnant/lowered sub-batches a
        bucket shape runs at several batch sizes, each its own XLA
        program — the (B, H, W) signature count IS that number (the
        batch dim rides the signature), counted here from the batches
        the step actually saw so the planner's predicted
        ``program_count`` can be checked against reality per epoch
        (``data.planner`` telemetry)."""
        return self.distinct_shapes


def _arm_telemetry(telemetry, step_fn, *, name: str):
    """Shared train/eval instrumentation setup.  Returns
    ``(wrapped_step_fn, timer, stall_clock)`` — all pass-throughs /
    None when telemetry is off, so the uninstrumented hot path is
    byte-identical to before (the <2% bench-overhead contract)."""
    if telemetry is None:
        return step_fn, None, None
    from can_tpu.obs import RecompileTracker, StallClock
    from can_tpu.utils.profiling import StepTimer

    # signatures live on the telemetry object, so re-wrapping every epoch
    # re-attributes nothing; first-call-per-signature wall time = compile
    return (RecompileTracker(step_fn, telemetry, name=name),
            StepTimer(skip_first=0), StallClock())


def _emit_epoch_telemetry(telemetry, timer, stall, *, phase: str,
                          epoch: int, seconds: float,
                          health=None) -> None:
    """Epoch-boundary events: stall accounting + device-memory snapshot +
    the step-time reservoir summary (per-shape breakdown included).
    ``health`` escalates over-budget starvation into a ``health.alert``.
    With a cost ledger on the bus, the epoch's per-shape wall totals are
    folded in and one ``perf.summary`` (per-program MFU / roofline /
    launch-cost fit) closes the epoch — the /metrics gauges' feed."""
    from can_tpu.obs import emit_memory

    stall_frac = (round(stall.seconds / seconds, 4) if seconds > 0 else 0.0)
    telemetry.emit("stall", phase=phase, epoch=epoch,
                   seconds=round(stall.seconds, 4), count=stall.count,
                   frac_of_epoch=stall_frac)
    if health is not None:
        health.on_stall(seconds=stall.seconds, frac=stall_frac,
                        epoch=epoch, phase=phase)
    telemetry.emit("step_window", phase=phase, epoch=epoch, steps=0,
                   samples_s=[], closes_epoch=True,
                   **timer.percentiles(), shapes=timer.shape_summary())
    emit_memory(telemetry, where=f"{phase}_epoch_{epoch}_end")
    ledger = getattr(telemetry, "ledger", None)
    if ledger is not None:
        # the timer is per-epoch (fresh in _arm_telemetry), so these
        # totals are this epoch's increment; the ledger accumulates
        # run-wide.  The summary covers ALL programs the ledger knows
        # (train + eval + serve share one ledger), so last-wins gauge
        # semantics stay coherent whichever phase emitted last.
        ledger.observe_timer(f"{phase}_step", timer)
        ledger.emit_summary(telemetry, step=epoch, phase=phase)


def _notify_incident(telemetry, exc, *, phase: str, epoch: int,
                     step: int) -> None:
    """An exception is about to unwind through the loop: give the armed
    IncidentManager (``Telemetry.incidents``, obs/incidents.py) one shot
    at snapshotting the run's context — ring, gauges, stacks — while it
    still exists.  ``NonFiniteLossError`` is deliberately NOT routed
    here: its bundle was already dumped by the ``health.alert`` nan
    trigger inside ``_flush``, and a second one would double-report the
    same death.  ``ElasticInterrupt`` is excluded too: an agreed shrink
    is CONTROL FLOW — the preemption's bundle belongs to the leaver's
    SIGTERM hook, and a per-survivor exception bundle would multiply one
    fleet event into N incidents.  No-op (one getattr) when incidents
    are unarmed."""
    inc = (getattr(telemetry, "incidents", None)
           if telemetry is not None else None)
    if inc is not None and not isinstance(exc, (NonFiniteLossError,
                                                ElasticInterrupt)):
        inc.on_exception(exc, phase=phase, epoch=epoch, step=step)


def _emit_step_window(telemetry, samples, *, steps: int, phase: str,
                      epoch: int, t_window: float, images: float,
                      **scalars) -> float:
    """One ``step_window`` event per metric-flush window.  The samples are
    host-side step intervals (no per-step fence — that would serialise the
    dispatch pipeline); the flush step absorbs the device sync, so the
    window's sample SUM is honest wall time while individual samples are
    dispatch-biased.  ``steps`` counts every step in the window; samples
    exclude first-call compiles (attributed by their own compile events),
    so ``len(samples_s)`` can be smaller.  ``scalars`` carries the
    window's fetched health means (loss / grad_norm / update_norm) so the
    /metrics gauges update mid-epoch without any new event kind.  Returns
    the new window start."""
    now = time.perf_counter()
    telemetry.emit("step_window", phase=phase, epoch=epoch, steps=steps,
                   seconds=round(now - t_window, 4), images=images,
                   samples_s=[round(s, 6) for s in samples], **scalars)
    return now


def train_one_epoch(train_step: Callable, state, batches: Iterable, *,
                    put_fn: Callable, epoch: int = 0, show_progress: bool = True,
                    check_finite: bool = True, total: Optional[int] = None,
                    prefetch: int = 2, check_every: int = 8, telemetry=None,
                    health=None, on_step: Optional[Callable] = None):
    """Run one epoch; returns (state, EpochStats).

    train_step: jitted (state, batch_dict) -> (state, metrics).
    batches: iterable of data.Batch (this host's slices).
    put_fn: Batch -> device batch dict (parallel.make_global_batch partial).
    prefetch: batches loaded+transferred ahead in a background thread.
    check_every: steps per metric flush — each flush is ONE host<->device
      sync covering the whole window (loss accumulation + non-finite abort
      check), so larger windows keep the device queue fuller at the cost of
      later divergence detection.
    telemetry: optional ``obs.Telemetry``; when given the loop emits
      ``compile`` (new batch signature -> first-call time), ``step_window``
      (per metric-flush window), and epoch-boundary ``stall``/``memory``
      events.  None keeps the hot path untouched.
    health: optional ``obs.HealthMonitor``; fed the fetched per-step
      scalars (loss per image + the in-program grad/update norms when the
      step computes them), each window's step-time samples, and the
      epoch's stall fraction — emitting ``health.alert`` events on the
      same bus.  Requires ``telemetry`` (ignored without it): detection
      rides the windowed fetch, never adds a sync.
    on_step: optional callable(step_count) run after each completed step
      — the elastic supervisor's hook (fault delivery + preemption
      agreement, parallel/elastic.py).  An ``ElasticInterrupt`` it
      raises gets the LIVE post-step train state attached
      (``exc.state``/``exc.steps_done``) before unwinding, so the caller
      can checkpoint the exact mid-epoch point; None (the default)
      keeps the hot path untouched.
    """
    from can_tpu.data.prefetch import prefetch_to_device

    if telemetry is None:
        health = None
    train_step, timer, stall = _arm_telemetry(telemetry, train_step,
                                              name="train_step")
    # span tracing (obs/spans.py): one trace per call, rooted at
    # train_epoch; under it a span pair per metric-flush window (steps /
    # metric_flush), one train.dispatch per step, train.turnover up to
    # the first batch, and the prefetcher's input.load / input.put /
    # input.wait.  None on default runs.
    spans = active(telemetry)
    trace_id = root_id = lane = None
    if spans is not None:
        trace_id = spans.new_trace_id(f"train.e{epoch}")
        root_id = spans.new_span_id()  # root emitted at epoch end
        # spans stamped after the fact say whose time they are too
        lane = threading.current_thread().name
    loss_sum = 0.0
    img_sum = 0.0
    flushed_img = 0.0  # img_sum at the last window flush (per-window delta)
    flushed_steps = 0  # steps at the last window flush
    steps = 0
    shapes = set()
    pending = []  # still-async metrics awaiting a windowed flush
    t0 = time.perf_counter()
    t_window = t0
    timed = telemetry is not None or spans is not None

    def _close_window(w0: float, t_flush: float, win: dict) -> float:
        """A metric-flush window has just been fetched: its step_window
        event and its steps / metric_flush span pair.  Returns the next
        window's start."""
        nonlocal flushed_img, flushed_steps
        n = steps - flushed_steps
        if telemetry is not None:
            samples = timer.drain_window()
            if health is not None:
                health.on_window(samples, epoch=epoch, phase="train")
            t_end = _emit_step_window(
                telemetry, samples, steps=n, phase="train", epoch=epoch,
                t_window=w0, images=img_sum - flushed_img, **win)
        else:
            t_end = time.perf_counter()
        if spans is not None:
            spans.emit(trace_id=trace_id, name="steps", start=w0,
                       end=t_flush, parent_id=root_id, step=steps, steps=n,
                       thread=lane)
            spans.emit(trace_id=trace_id, name="metric_flush",
                       start=t_flush, end=t_end, parent_id=root_id,
                       step=steps, thread=lane)
        flushed_img = img_sum
        flushed_steps = steps
        return t_end

    it = _progress(prefetch_to_device(batches, put_fn, depth=prefetch,
                                      stall=stall, tracer=spans,
                                      trace_id=trace_id, parent_id=root_id),
                   enabled=show_progress, desc=f"epoch {epoch}", total=total)
    try:
        for dev_batch in it:
            shape = tuple(dev_batch["image"].shape)
            shapes.add(shape)
            if telemetry is not None:
                telemetry.step_tick()
                timer.start()
            if spans is None:
                state, metrics = train_step(state, dev_batch)
            else:
                if not steps:
                    # a new prefetcher filled from nothing: what an epoch
                    # boundary costs before its first step
                    spans.emit(trace_id=trace_id, name="train.turnover",
                               start=t0, end=time.perf_counter(),
                               parent_id=root_id, epoch=epoch,
                               thread=lane)
                with spans.span("train.dispatch", trace_id=trace_id,
                                parent_id=root_id,
                                program=program_key(shape)) as sp:
                    state, metrics = train_step(state, dev_batch)
                    # as the program's trace noted it (a first call traces
                    # inside the step); None: no such program was traced
                    sp.attrs["stage1"] = stage1_traced(shape)
            if telemetry is not None:
                # a first-call compile is attributed by its own compile
                # event; recording it here too would poison the step
                # p95/max
                timer.stop(shape=shape,
                           record=not train_step.last_first_call)
            pending.append(metrics)
            steps += 1
            if on_step is not None:
                on_step(steps)
            if len(pending) >= max(check_every, 1):
                t_flush = (time.perf_counter() if timed else 0.0)
                loss_sum, img_sum, win = _flush(
                    pending, loss_sum, img_sum, check_finite, epoch, steps,
                    health=health, collect=telemetry is not None)
                pending = []
                if timed:
                    t_window = _close_window(t_window, t_flush, win)
                if show_progress and hasattr(it, "set_postfix") and img_sum:
                    it.set_postfix(loss=f"{loss_sum / img_sum:.4f}")
        t_flush = (time.perf_counter() if timed else 0.0)
        loss_sum, img_sum, win = _flush(pending, loss_sum, img_sum,
                                        check_finite, epoch, steps,
                                        health=health,
                                        collect=telemetry is not None)
    except Exception as e:
        if isinstance(e, ElasticInterrupt):
            # an agreed shrink: hand the caller the LIVE mid-epoch state
            # (post-step) — the shrink checkpoint must save exactly this
            # point or "resume from the exact step" is a lie
            e.state = state
            e.steps_done = steps
        # the incident hook (a crashed loader thread, a poisoned batch,
        # an XLA error): bundle first, THEN unwind — the NaN abort and
        # elastic-shrink paths are excluded inside
        _notify_incident(telemetry, e, phase="train", epoch=epoch,
                         step=steps)
        raise
    seconds = time.perf_counter() - t0
    if timed and steps > flushed_steps:  # partial trailing window
        _close_window(t_window, t_flush, win)
    if telemetry is not None:
        _emit_epoch_telemetry(telemetry, timer, stall, phase="train",
                              epoch=epoch, seconds=seconds, health=health)
        if health is not None:
            health.epoch_summary(epoch)
    if spans is not None:
        spans.emit(trace_id=trace_id, name="train_epoch", start=t0,
                   end=time.perf_counter(), span_id=root_id,
                   epoch=epoch, steps=steps, images=img_sum, thread=lane)
    stats = EpochStats(loss_sum / max(img_sum, 1.0), seconds=seconds,
                       images=img_sum, steps=steps,
                       distinct_shapes=len(shapes))
    return state, stats


def _flush(pending, loss_sum, img_sum, check_finite, epoch, step_count,
           health=None, collect=False):
    """Fetch a window of async step metrics in one device_get.

    Returns ``(loss_sum, img_sum, window_scalars)``; ``window_scalars``
    holds the window's mean loss-per-image (and grad/update norms when
    the step computes them, see ``make_train_step health_metrics``) for
    the ``step_window`` payload — empty unless ``collect`` (telemetry on),
    so the uninstrumented flush does exactly the work it did before.
    ``health`` gets every fetched step's scalars, and — on the abort
    path — the non-finite loss BEFORE ``NonFiniteLossError`` propagates,
    so the run's last bus event says why it died."""
    window = len(pending)
    collect = collect or health is not None
    win: dict = {}
    for i, metrics in enumerate(jax.device_get(pending)):
        # can-tpu-lint: disable=HOSTSYNC(host value: the windowed jax.device_get above is the one sync)
        loss = float(metrics["loss"])
        step_no = step_count - window + i + 1
        if check_finite and not math.isfinite(loss):
            if health is not None:
                health.on_nonfinite(loss, epoch=epoch, step=step_no)
            # every host computes the same replicated loss, so every host
            # raises: a clean global abort, not the reference's one-rank
            # exit + deadlock.  Detection is windowed (one sync per
            # check_every steps), so the divergence happened up to
            # `window` steps before this flush.
            raise NonFiniteLossError(
                f"non-finite loss {loss} in epoch {epoch}, within the last "
                f"{window} steps (<= step {step_count}; metric checks are "
                f"windowed — pass check_every=1 to train_one_epoch to "
                f"pinpoint); aborting all hosts")
        # can-tpu-lint: disable=HOSTSYNC(host value from the windowed device_get)
        n = float(metrics["num_valid"])
        loss_sum += loss
        img_sum += n
        if collect:
            per_img = loss / max(n, 1.0)
            # can-tpu-lint: disable=HOSTSYNC(host value from the windowed device_get)
            gn = (float(metrics["grad_norm"])
                  if "grad_norm" in metrics else None)
            # can-tpu-lint: disable=HOSTSYNC(host value from the windowed device_get)
            un = (float(metrics["update_norm"])
                  if "update_norm" in metrics else None)
            for key, v in (("loss", per_img), ("grad_norm", gn),
                           ("update_norm", un)):
                if v is not None:
                    acc = win.setdefault(key, [0, 0.0])
                    acc[0] += 1
                    acc[1] += v
            if health is not None:
                health.on_step_metrics(loss_per_img=per_img, grad_norm=gn,
                                       update_norm=un, epoch=epoch,
                                       step=step_no)
    return loss_sum, img_sum, {k: round(total / cnt, 8)
                               for k, (cnt, total) in win.items()}


def evaluate(eval_step: Callable, params, batches: Iterable, *,
             put_fn: Callable, dataset_size: int, show_progress: bool = False,
             total: Optional[int] = None, batch_stats=None,
             check_every: int = 4, prefetch: int = 2,
             telemetry=None) -> dict:
    """Dataset MAE and (paper-style) RMSE over the eval set.

    eval_step returns global sums (see train/steps.py), so accumulating on
    one host and dividing by the TRUE dataset size gives the exact
    reference metric ``mae = Σ|et-gt| / N`` (reference
    utils/train_eval_utils.py:83,136, minus its padding bias).

    prefetch: batches loaded+transferred ahead in a background thread,
    exactly as in train_one_epoch (VERDICT r4 weak-1: eval used to call
    put_fn synchronously in the loop, so every batch paid the host
    materialisation + H2D transfer in series with the device).
    """
    from can_tpu.data.prefetch import prefetch_to_device

    eval_step, timer, stall = _arm_telemetry(telemetry, eval_step,
                                             name="eval_step")
    abs_sum = 0.0
    sq_sum = 0.0
    n_seen = 0.0
    pending = []  # async per-batch metric trees, fetched in windows
    t0 = time.perf_counter()
    t_window = t0
    it = _progress(prefetch_to_device(batches, put_fn, depth=prefetch,
                                      stall=stall,
                                      tracer=active(telemetry)),
                   enabled=show_progress, desc="eval", total=total)

    def flush():
        nonlocal abs_sum, sq_sum, n_seen, t_window
        n_before = n_seen
        window = len(pending)
        for m in jax.device_get(pending):
            # can-tpu-lint: disable=HOSTSYNC(host values: the windowed device_get above is the one sync)
            abs_sum += float(m["abs_err_sum"])
            # can-tpu-lint: disable=HOSTSYNC(host value from the windowed device_get)
            sq_sum += float(m["sq_err_sum"])
            # can-tpu-lint: disable=HOSTSYNC(host value from the windowed device_get)
            n_seen += float(m["num_valid"])
        pending.clear()
        if telemetry is not None and window:
            t_window = _emit_step_window(telemetry, timer.drain_window(),
                                         steps=window, phase="eval",
                                         epoch=0, t_window=t_window,
                                         images=n_seen - n_before)

    try:
        for dev_batch in it:
            # don't fetch per step: each device_get is a host<->device
            # round trip (expensive on pods) and drains the
            # dispatch queue.  Windowed instead (like train_one_epoch):
            # one sync per ``check_every`` batches.  The window (plus
            # prefetch depth) also caps how many in-flight INPUT batches
            # the dispatch queue can pin in HBM, so the default stays
            # small (4) — at UCF-QNRF image sizes each staged batch is
            # hundreds of MB; raise it for small-image evals where the
            # round trips dominate.
            shape = tuple(dev_batch["image"].shape)
            if telemetry is not None:
                telemetry.step_tick()
                timer.start()
            pending.append(eval_step(params, dev_batch, batch_stats))
            if telemetry is not None:
                timer.stop(shape=shape,
                           record=not eval_step.last_first_call)
            if len(pending) >= max(check_every, 1):
                flush()
        flush()
    except Exception as e:
        # same incident hook as the train loop (see _notify_incident)
        _notify_incident(telemetry, e, phase="eval", epoch=0,
                         step=len(pending))
        raise
    if telemetry is not None:
        _emit_epoch_telemetry(telemetry, timer, stall, phase="eval",
                              epoch=0, seconds=time.perf_counter() - t0)
    if int(n_seen) != dataset_size:
        raise RuntimeError(
            f"eval saw {int(n_seen)} valid samples, expected {dataset_size}")
    return {
        "mae": abs_sum / dataset_size,
        # can-tpu-lint: disable=HOSTSYNC(host numpy sqrt of epoch sums)
        "mse": float(np.sqrt(sq_sum / dataset_size)),
        "num_images": dataset_size,
    }
