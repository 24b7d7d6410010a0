"""Profiling hooks: jax.profiler traces + wall-clock step timing.

The reference has no profiling at all (SURVEY §5: "Tracing/profiling:
ABSENT" — only tqdm bars).  TPU-first observability:

* ``profile_trace(dir)`` captures an XLA/TPU trace viewable in TensorBoard /
  Perfetto (device timelines, HLO ops, ICI collectives);
* ``StepTimer`` measures steady-state step time with an explicit
  ``block_until_ready`` fence — the JAX analogue of the reference's
  ``cuda.synchronize`` timing hygiene (utils/train_eval_utils.py:55-57);
* ``device_watchdog`` / ``await_devices`` fail fast when backend
  acquisition hangs (an unreachable accelerator blocks ``jax.devices()``
  forever);
* ``bench_device`` is the benchmark entry points' device gate: a
  benchmark times the chip, so a backend that is not a TPU is an exit,
  not a fallback — unless the caller explicitly asked for the CPU;
* ``pallas_interpret`` decides interpreter-vs-compiled for Pallas kernels
  from the REQUESTED platform.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

import jax


def device_watchdog(seconds: float = 300.0, on_timeout=None):
    """Fail FAST if JAX backend/device acquisition hangs.

    An unreachable accelerator makes ``jax.devices()`` block forever
    with no output — a silently hung benchmark/driver process.  Arm this
    BEFORE the first backend touch and ``.set()`` the returned event
    right after ``jax.devices()`` returns; if it isn't set within
    ``seconds`` the process prints one clear stderr line and exits 3.

    ``on_timeout``: optional callback run before the exit — benchmark
    entry points use it to emit a machine-readable null result so the
    driver's artifact records WHY there is no number (r5; the bare rc=3
    of r4 took a human to interpret).  Exceptions in it are swallowed:
    the exit must happen regardless.
    """
    armed = threading.Event()

    def boom():
        if not armed.wait(seconds):
            # Re-check after the wait: jax.devices() may have returned
            # just before the deadline with armed.set() not yet executed
            # — killing a healthy process with a false "unreachable"
            # artifact (code-review r5).  One grace second closes the
            # set-vs-timeout race; a genuinely hung backend cannot set
            # the event at all.
            if armed.wait(1.0):
                return
            import sys

            if on_timeout is not None:
                try:
                    on_timeout()
                # can-tpu-lint: disable=SWALLOW(process is about to _exit(3); the fatal print below is the record)
                except Exception:
                    pass
            print(f"[watchdog] FATAL: no JAX device within {seconds:.0f}s "
                  f"— accelerator backend unreachable", file=sys.stderr,
                  flush=True)
            os._exit(3)

    threading.Thread(target=boom, daemon=True).start()
    return armed


def emit_null_result(metric: str, **extra):
    """on_timeout callback factory for benchmark entry points: print one
    machine-readable null-result line before the watchdog exit, so the
    recorded artifact says WHY there is no number instead of a bare
    rc=3 (r5).  Usage: ``await_devices(on_timeout=emit_null_result(...))``."""

    def emit():
        import json

        print(json.dumps(dict(
            {"metric": metric, "value": None,
             "error": "accelerator backend unreachable (watchdog timeout)"},
            **extra)), flush=True)

    return emit


def await_devices(seconds: float = 300.0, on_timeout=None):
    """Arm the watchdog, force backend init, disarm; returns devices.
    One call at the top of every benchmark entry point.  Disarms in
    ``finally``: a backend that RAISES (refused connection) instead of
    hanging must not leave the timer to kill the caller's fallback path
    minutes later."""
    armed = device_watchdog(seconds, on_timeout=on_timeout)
    try:
        return jax.devices()
    finally:
        armed.set()


def requested_platform() -> str:
    """The platform the caller asked JAX for — first entry of
    ``jax_platforms`` (the ``JAX_PLATFORMS`` env var, or a
    ``--platform`` / ``*_PLATFORM=cpu8`` knob that updated the config);
    "" when nothing was requested and JAX takes whatever comes up."""
    return (jax.config.jax_platforms or "").split(",")[0]


def bench_device(on_timeout=None) -> dict:
    """``await_devices`` + the device triple every benchmark result
    carries (``platform`` / ``device_kind`` / ``device_count``).

    A benchmark times the chip.  On a machine whose TPU failed to come
    up JAX quietly hands back the CPU, and the run would carry on timing
    that — so unless the caller explicitly requested the CPU (the
    scripts' documented smoke modes all do, via ``requested_platform``),
    a first device that is not a TPU prints one stderr line and exits 2.
    """
    dev = await_devices(on_timeout=on_timeout)[0]
    if dev.platform != "tpu" and requested_platform() != "cpu":
        import sys

        print(f"[bench] FATAL: first JAX device is {dev.platform!r} "
              f"({dev.device_kind}), not a TPU, and the CPU was not "
              f"requested — refusing to time it (set JAX_PLATFORMS=cpu "
              f"for the CPU smoke mode)", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter (True) or compiled.

    Decided from the REQUESTED platform, so a TPU request can never end
    up interpreted: "tpu" -> compiled (should the CPU come up instead,
    e.g. under ``JAX_PLATFORMS=tpu,cpu``, the kernel fails to lower —
    loudly), "cpu" -> interpreted.  With nothing requested, the platform
    that came up decides.  Callers print the mode in effect."""
    req = requested_platform()
    if req:
        return req != "tpu"
    return jax.devices()[0].platform != "tpu"


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str], spans=None):
    """Capture a jax.profiler trace into ``log_dir`` (no-op if None), at
    the operator's options (``obs/trace.py``: the device alone).
    ``spans``: the run's ``SpanTracer``, for the ``profile.window`` span
    that lets ``tools/trace_export.py --profile`` place the host's spans
    beside the device plane."""
    if not log_dir:
        yield
        return
    from can_tpu.obs.trace import (
        operator_profile_options,
        record_profile_window,
    )

    t0 = time.perf_counter()
    with jax.profiler.trace(log_dir,
                            profiler_options=operator_profile_options()):
        try:
            yield
        finally:
            record_profile_window(spans, log_dir, t0, time.perf_counter())


class StepTimer:
    """Step wall-time accounting: rolling mean, bounded sample reservoir
    (p50/p95/max), and optional per-shape-bucket breakdown.

    The first ``skip_first`` steps are excluded from every statistic (they
    carry compile time; ``mean`` is NaN until a post-skip step lands).
    The reservoir keeps the most recent ``reservoir`` samples (deque, not
    true reservoir sampling: for telemetry the RECENT distribution is the
    one that predicts the next hour).  ``stop(shape=...)`` tags the sample
    with its batch bucket so a bimodal p95 can be attributed to the bucket
    causing it instead of read as noise."""

    def __init__(self, skip_first: int = 2, reservoir: int = 4096):
        import collections

        self.skip_first = skip_first
        self._count = 0
        self._total = 0.0
        self._last: Optional[float] = None
        self._samples = collections.deque(maxlen=max(int(reservoir), 1))
        self._window: list = []  # samples since the last drain_window()
        self._shapes: dict = {}  # shape -> [count, total_s]

    def start(self) -> None:
        self._last = time.perf_counter()

    def stop(self, result=None, *, shape=None, record: bool = True) -> float:
        """Fence on ``result`` (if given) and record the elapsed time.

        In an async-dispatch loop, call WITHOUT ``result``: the sample is
        then the host-side step interval (the window-flush step absorbs
        the device sync), whose sum over a window is honest wall time.
        ``record=False`` measures but records nothing — for steps whose
        time is accounted elsewhere (a first-call compile, attributed by
        its own ``compile`` event; folding it in here would let one 10 s
        compile masquerade as the steady-state p95/max)."""
        if self._last is None:
            raise RuntimeError("StepTimer.stop() before start()")
        if result is not None:
            jax.block_until_ready(result)
        dt = time.perf_counter() - self._last
        self._last = None
        if not record:
            return dt
        return self.record(dt, shape=shape)

    def record(self, dt: float, *, shape=None) -> float:
        """Record an externally measured sample — for durations that don't
        fit the sequential start/stop pattern (e.g. serve request
        latencies, measured per request across threads).  Same reservoir,
        window, skip_first, and per-shape accounting as ``stop``."""
        self._count += 1
        if self._count > self.skip_first:
            self._total += dt
            self._samples.append(dt)
            self._window.append(dt)
            if shape is not None:
                rec = self._shapes.setdefault(shape, [0, 0.0])
                rec[0] += 1
                rec[1] += dt
        return dt

    @property
    def mean(self) -> float:
        n = self._count - self.skip_first
        return self._total / n if n > 0 else float("nan")

    def percentiles(self) -> dict:
        """``{n, p50_s, p95_s, max_s}`` over the reservoir (post-skip
        samples); Nones when nothing has been recorded yet."""
        if not self._samples:
            return {"n": 0, "p50_s": None, "p95_s": None, "max_s": None}
        import numpy as np

        arr = np.asarray(self._samples, np.float64)
        return {"n": int(arr.size),
                "p50_s": float(np.percentile(arr, 50)),
                "p95_s": float(np.percentile(arr, 95)),
                "max_s": float(arr.max())}

    def percentile(self, q: float) -> Optional[float]:
        """One percentile over the reservoir (None when empty) — the
        autoscaler reads p99 here; ``percentiles()`` stays the fixed
        p50/p95/max report shape."""
        if not self._samples:
            return None
        import numpy as np

        return float(np.percentile(
            np.asarray(self._samples, np.float64), q))

    def shape_totals(self) -> dict:
        """Raw per-shape accounting, ``{shape: (n, total_s)}`` — the
        lossless feed the ProgramCostLedger joins against compiled-program
        flops (``shape_summary`` stringifies keys and rounds, which is
        right for the JSONL payload and wrong for arithmetic)."""
        return {shape: (n, total) for shape, (n, total)
                in self._shapes.items()}

    def shape_summary(self) -> dict:
        """Per-bucket breakdown: ``{shape_str: {n, total_s, mean_s}}``."""
        return {str(shape): {"n": n, "total_s": round(total, 4),
                             "mean_s": round(total / n, 6)}
                for shape, (n, total) in sorted(self._shapes.items(),
                                                key=lambda kv: str(kv[0]))}

    def drain_window(self) -> list:
        """Return (and reset) the samples recorded since the last drain —
        the per-window payload for ``step_window`` telemetry events."""
        window, self._window = self._window, []
        return window
