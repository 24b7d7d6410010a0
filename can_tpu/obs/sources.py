"""Instrumentation sources: where the events come from.

Each source measures one TPU-specific failure mode the loop comments used
to only WARN about:

* ``RecompileTracker`` — silent recompiles.  Every new ``(shape, dtype)``
  batch signature hitting a jitted step costs a trace+lower+compile on the
  calling thread; before this, ``EpochStats.distinct_shapes`` was a bare
  count with no timing or attribution.
* ``StallClock`` — input-pipeline starvation: seconds the consumer spent
  blocked waiting for ``prefetch_to_device``'s next batch.
* ``device_memory_snapshot`` / ``emit_memory`` — HBM pressure from
  in-flight staged batches, via PJRT ``memory_stats()`` where the client
  implements it (host RSS as the always-available fallback: the CPU
  backend reports no device stats).
* ``Heartbeat`` — a liveness timestamp every N seconds from a daemon
  thread, so a hung run leaves a last-known-good timestamp in the artifact
  instead of a file that just stops.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional


class RecompileTracker:
    """Wrap a (jitted) step callable; attribute each NEW batch signature.

    The first call carrying an unseen ``(name, shape, dtype)`` signature is
    timed end-to-end and emitted as a ``compile`` event: under jit the
    first call with a new signature blocks on trace + lower + compile
    before dispatching, so its wall time IS the compile bill (plus one
    dispatch — noise next to any real compile).  Signatures live in
    ``telemetry.signature_registry[name]``, not on the wrapper, so
    re-wrapping the step every epoch doesn't re-attribute old shapes.

    ``batch_arg``: positional index of the batch dict in the wrapped
    callable's signature (1 for ``train_step(state, batch)`` and
    ``eval_step(params, batch, ...)``).

    ``last_first_call`` is True right after a call that hit a new
    signature — callers timing steps around this wrapper use it to keep
    compile wall time OUT of their steady-state step distribution (it is
    already fully accounted by the ``compile`` event; recording it twice
    would let one 10 s compile masquerade as the step p95/max).  It is the
    CALLING THREAD's last call: two serve launches in flight on two
    threads each read their own."""

    def __init__(self, fn: Callable, telemetry, *, name: str = "step",
                 batch_arg: int = 1, signature_of: Optional[Callable] = None):
        from can_tpu.train.steps import batch_signature

        self._fn = fn
        self._tel = telemetry
        self._name = name
        # signature_of(args) -> hashable: for a callable whose program
        # depends on more than one dict of arrays (a decode step: its
        # state AND its cache); None keys on args[batch_arg] alone
        self._signature = (signature_of if signature_of is not None else
                           lambda args: batch_signature(args[batch_arg]))
        self._seen = telemetry.signature_registry.setdefault(name, {})
        self._call = threading.local()

    @property
    def last_first_call(self) -> bool:
        return getattr(self._call, "first", False)

    def jit_for(self, *args):
        """The underlying jitted callable for these args — the same hook
        the bucketed/spatial dispatch closures expose, so the cost ledger
        and the HLO auditor (``obs.costs.resolve_jit``) can lower the
        EXACT program this wrapper dispatches.  Chains through a wrapped
        callable that itself exposes ``jit_for``."""
        inner = getattr(self._fn, "jit_for", None)
        return inner(*args) if inner is not None else self._fn

    def __call__(self, *args):
        sig = self._signature(args)
        if sig in self._seen:
            self._call.first = False
            return self._fn(*args)
        self._call.first = True
        t0 = time.perf_counter()
        out = self._fn(*args)
        dt = time.perf_counter() - t0
        self._seen[sig] = dt
        payload = dict(name=self._name, signature=[list(s) for s in sig],
                       seconds=round(dt, 4), n_signatures=len(self._seen))
        # perf-attribution hook: with a ProgramCostLedger on the bus
        # (Telemetry.ledger, armed by the CLIs), the new signature's XLA
        # cost_analysis() flops/bytes are read at compile time and ride
        # this same compile event; backends that report nothing degrade
        # to the bare payload (the ledger never raises into the step)
        ledger = getattr(self._tel, "ledger", None)
        if ledger is not None:
            cost = ledger.register(self._name, sig, fn=self._fn, args=args)
            if cost is not None:
                payload.update(cost)
        self._tel.emit("compile", **payload)
        return out


class StallClock:
    """Accumulates time a consumer spent BLOCKED on its input pipeline.

    ``prefetch_to_device(..., stall=clock)`` adds to it only when the next
    batch's future wasn't already done — i.e. genuine starvation, not the
    cost of the (already overlapped) load itself."""

    __slots__ = ("seconds", "count")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def add(self, dt: float) -> None:
        self.seconds += dt
        self.count += 1


def device_memory_snapshot() -> dict:
    """Best-effort memory accounting: per-local-device PJRT stats where the
    client implements ``memory_stats()`` (real TPUs), host RSS always.

    ``jax.local_devices()``, not ``jax.devices()``: on a pod, non-local
    devices' stats are unreadable off their host (ADVICE r4)."""
    devices = []
    try:
        import jax

        for d in jax.local_devices():
            rec = {"id": d.id, "platform": d.platform}
            try:
                stats = d.memory_stats()
            # can-tpu-lint: disable=SWALLOW(memory_stats is optional per PJRT client; the device row still lands)
            except Exception:
                stats = None
            if stats:
                for key in ("bytes_in_use", "peak_bytes_in_use",
                            "bytes_limit", "largest_alloc_size"):
                    if key in stats:
                        rec[key] = int(stats[key])
            devices.append(rec)
    # can-tpu-lint: disable=SWALLOW(backend not initialised / unreachable: host RSS still lands)
    except Exception:
        pass  # backend not initialised / unreachable: host RSS still lands
    snap = {"devices": devices, "host_rss_mb": _host_rss_mb()}
    return snap


def _host_rss_mb() -> Optional[float]:
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return round(rss_kb / 1024.0, 1)  # linux reports KiB
    # can-tpu-lint: disable=SWALLOW(resource module is unix-only; None row is the degrade)
    except Exception:  # pragma: no cover — non-unix
        return None


def emit_memory(telemetry, *, step: Optional[int] = None,
                where: str = "") -> None:
    """One ``memory`` event: epoch boundaries and on-demand probes."""
    telemetry.emit("memory", step=step, where=where,
                   **device_memory_snapshot())


class Heartbeat:
    """Daemon thread emitting a ``heartbeat`` event every ``interval_s``.

    One event fires immediately at start (the last-known-good baseline a
    short run still records), then every interval until ``close()``.
    Payload carries the run-local step counter, so a wedged run's artifact
    says how far it got, not just when it died — plus a monotonic ``seq``
    and the process-start ``start_ts``, so a reader of an APPENDED file
    (same run dir, new process) can tell a restarted process (``start_ts``
    changes, ``seq`` resets) from a resumed stream (``tools/run_monitor.py``
    counts the restarts).  ``interval_s <= 0`` disables the thread entirely
    (NOT a floor — a 0 interval flooding ~100 fsync'd events/second into
    the file would be worse than none)."""

    def __init__(self, telemetry, interval_s: float = 60.0,
                 *, start: bool = True):
        self._tel = telemetry
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._t0 = time.time()
        self._seq = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="can-tpu-heartbeat")
        if start and self.interval_s > 0:
            self._thread.start()

    def _run(self) -> None:
        while True:
            self._tel.emit("heartbeat",
                           uptime_s=round(time.time() - self._t0, 3),
                           seq=self._seq, start_ts=round(self._t0, 3))
            self._seq += 1
            if self._stop.wait(self.interval_s):
                return

    def close(self) -> None:
        self._stop.set()
        if self._thread.is_alive():  # pragma: no branch
            self._thread.join(timeout=5.0)
