"""Step-range profiler trigger: trace a WINDOW instead of the whole run.

``profile_trace`` (utils/profiling.py) wraps the entire run — fine for a
smoke run, useless for "steady-state steps 10..12 of a 10-hour job" where
a whole-run trace is gigabytes of mostly-identical timelines.  Here the
``jax.profiler`` trace is armed by the run-local step counter: the CLI's
``--trace-steps A:B`` (python slice semantics: first traced step A,
first untraced step B) starts the trace when step A begins and stops it
when step B begins, so the artifact holds exactly ``B - A`` steps.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple


def operator_profile_options():
    """``ProfileOptions`` for an operator's trace (``--profile-dir``,
    ``--trace-steps``): the device alone, as the benchmark traces.  The
    host tracer records one "Transpose" event per chunk of the runtime's
    host-side layout change of a float32 batch, at its default level 2
    and at level 1 alike: 3,594,316 events and a 119 MB file for 4
    serving launches at level 1, ``predict_batch`` 5.7 times slower, 19 s
    to stop the trace (PERF.md section 6, PR 24).  The program's spans
    (``obs/spans.py``) are placed beside the device plane afterwards, by
    ``tools/trace_export.py --profile``, through the ``profile.window``
    span the trigger records."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts


def record_profile_window(spans, log_dir: str, start: float,
                          end: float) -> None:
    """One ``profile.window`` span (``perf_counter`` ends) for a trace
    that ran from ``start`` to ``end``: what lets the exporter put the
    host's spans on the device trace's clock."""
    if spans is not None:
        spans.emit(trace_id=spans.new_trace_id("profile"),
                   name="profile.window", start=start, end=end,
                   log_dir=str(log_dir))


def parse_trace_steps(spec: str) -> Optional[Tuple[int, int]]:
    """``"10:13"`` -> ``(10, 13)``; empty/None -> None.  Slice semantics:
    steps ``[10, 13)`` are traced.  Raises ValueError on malformed specs
    (argparse ``type=`` surfaces it as a usage error before any work)."""
    if not spec:
        return None
    try:
        lo_s, hi_s = spec.split(":")
        lo, hi = int(lo_s), int(hi_s)
    except ValueError:
        raise ValueError(
            f"--trace-steps wants START:STOP (e.g. 10:13), got {spec!r}")
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"--trace-steps window must satisfy 0 <= START < STOP, "
            f"got {spec!r}")
    return lo, hi


class StepTraceWindow:
    """Start/stop a ``jax.profiler`` trace on run-local step boundaries.

    ``on_step(step)`` is called once per step (step counts from 1, see
    ``Telemetry.step_tick``; the window is interpreted on the 0-based step
    ORDINAL, so ``--trace-steps 0:2`` traces the first two steps).  Safe to
    call after the window has passed — both branches are a pair of integer
    compares.  ``close()`` stops a still-open trace (a window extending
    past the last step must still flush its file).  ``spans``: the run's
    ``SpanTracer`` when it has one (``build_telemetry`` sets it); the
    window then records its own ``profile.window`` span."""

    def __init__(self, log_dir: str, start: int, stop: int,
                 *, profiler=None, spans=None):
        if not log_dir:
            raise ValueError("StepTraceWindow needs a log_dir "
                             "(pass --profile-dir with --trace-steps)")
        self.log_dir = log_dir
        self.start = int(start)
        self.stop = int(stop)
        self._active = False
        self._done = False
        self._profiler = profiler  # test seam; defaults to jax.profiler
        self.spans = spans
        self._t_start = 0.0

    def _jax_profiler(self):
        if self._profiler is None:
            import jax.profiler

            self._profiler = jax.profiler
        return self._profiler

    def on_step(self, step: int) -> None:
        ordinal = step - 1  # step_tick counts from 1
        if (not self._active and not self._done
                and self.start <= ordinal < self.stop):
            self._jax_profiler().start_trace(
                self.log_dir, profiler_options=operator_profile_options())
            self._t_start = time.perf_counter()
            self._active = True
        elif self._active and ordinal >= self.stop:
            self._stop_trace()

    def _stop_trace(self) -> None:
        t_stop = time.perf_counter()
        try:
            self._jax_profiler().stop_trace()
            record_profile_window(self.spans, self.log_dir, self._t_start,
                                  t_stop)
        finally:
            self._active = False
            self._done = True  # one window per run: never re-arm

    def close(self) -> None:
        if self._active:
            self._stop_trace()
