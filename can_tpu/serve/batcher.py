"""Micro-batcher: group queued requests by bucket shape, flush as one
static-shape batch.

The offline ``ShardedBatcher`` solves variable-resolution-under-XLA with
shape buckets + masked padding; online serving has the same constraint at
request granularity, so this batcher reuses the SAME math — for images the
bucket mapping is ``data.batching.snap_to_bucket`` and batch assembly is
``data.batching.pad_batch`` — it only swaps the epoch schedule for an
arrival-driven flush policy.

What a request carries is its KIND's business (``serve/kinds.py``): the
request names its kind, and the batcher asks that kind for the group key
and for the assembly.  Images (``ImageKind``, built from this class's own
bucket arguments) make the two calls above; prompts of token ids
(``TokenKind``) bucket on a length ladder.  Intake, pricing, the menu, the
spans and the staging pool below are one code for every kind.

Where ``dispatch`` is done with a batch when it returns (the in-process
service: ``predict_batch`` has fetched the answers and the requests are
resolved), the batcher assembles into staging buffers it OWNS: one
``data.batching.StagingBatch`` per (bucket, dtype), sized for the top
launch size at the key's first flush and kept until ``close()``; a
smaller menu size is its leading view.  ``pad_batch(out=...)`` then
copies the items in and zeroes only what the previous launch left
stale, instead of mapping and zeroing the whole batch anew per launch
(151 MB at b16 768x1024 f32: 9.2 ms per image on the v5e's host against
1.0 for the copy alone; PERF.md, PR 25).  The batch handed to
``dispatch`` is a view of that buffer, free again when ``dispatch``
returns or raises.  Where ``dispatch`` only enqueues (the fleet: a
``_WorkItem`` keeps the batch until a replica completes it, may be
redispatched, and a wedged replica may still be reading it) every launch
is assembled fresh, and the batch is its receiver's.  ``staging`` counts
launches by which of the two it was.

Since round 14 the flush policy and launch sizes come from the shared
scheduling core (``can_tpu/sched``) when a ``ServeSched`` is given:

* a bucket's group flushes the moment it holds the TOP menu size (the
  batch is full — waiting longer buys nothing);
* otherwise it flushes at the core's PRICED deadline
  (``ServeSched.flush_at``): immediately when coalescing one more
  request cannot beat launch-cost amortization or when the bucket's
  observed arrival rate says no request is expected inside the window;
  at the latency cap (``max_wait_ms``) or the group's deadline slack
  otherwise — with no rate estimate yet the priced deadline IS the old
  timer, so cold behaviour is unchanged;
* a flush is covered by the core's menu parts (the planner's exact
  ``decompose`` DP): a 2-request flush launches a 2-slot program
  instead of padding to ``max_batch`` (fill slots remain
  ``sample_mask=0``, the offline dead-slot convention), and every
  emitted size is a menu size — the XLA compile count is
  ``buckets x dtypes x menu sizes``, static and warmed up front.

Without a ``sched`` the pre-r14 behaviour is preserved exactly: pad
every flush to ``max_batch``, flush on the ``max_wait_ms`` timer (the
bit-compatible baseline the tests and the bench's legacy arm drive).

The pump wakes EXACTLY at the earliest pending flush deadline (or on
arrival, via the queue's condition) — never on a fixed poll grain: with
priced deadlines that can be "now", a 50 ms idle poll would have eaten
the entire low-load latency win, and even under the timer policy a poll
interval above a short ``max_wait_ms`` silently inflated the tail.

Requests whose deadline expires before dispatch are rejected, never
launched: a result the client has already given up on still costs a full
batch slot, and under overload those zombie slots are exactly the capacity
the live requests need.

Single consumer thread; dispatch runs ON that thread — the device executes
serially anyway, and one thread means the pending-group state needs no
locking beyond the queue's own, and that one staging buffer per key is
enough: the thread assembles the next launch only after the previous
``dispatch`` has returned.  A launch therefore blocks the pump for as
long as it runs, and its view of the queue is stale when it returns:
``intake`` sorts what arrived meanwhile into its groups before ``poll``
judges them, so a group's rest is never launched alone while its
companions sit in the queue.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from can_tpu.obs.spans import active
from can_tpu.serve.kinds import IMAGE, GroupKey, ImageKind
from can_tpu.serve.queue import (
    REJECT_DEADLINE,
    REJECT_ERROR,
    BoundedRequestQueue,
    ServeRequest,
)

# why a group was flushed, named where it is decided: ``intake`` saw it
# reach the top launch size, ``poll`` saw its priced deadline (or the
# legacy timer) arrive, ``flush_all`` drained it at shutdown
FLUSH_FULL, FLUSH_DUE, FLUSH_DRAIN = "full", "due", "drain"


class _Group:
    """One pending per-key group: requests + the arrival timestamps the
    priced flush deadline needs."""

    __slots__ = ("requests", "t0", "t_last")

    def __init__(self, t0: float):
        self.requests: List[ServeRequest] = []
        self.t0 = t0      # oldest request's submit (latency cap anchor)
        self.t_last = t0  # newest arrival (the wait-for-next anchor)


class MicroBatcher:
    """Pulls from a ``BoundedRequestQueue``, emits padded ``Batch``es.

    dispatch: ``fn(bucket_hw, batch, requests)`` — executes the batch and
    resolves each request (the service wires this to the engine).  A
    dispatch that raises rejects its requests with ``error`` and the
    batcher keeps running: one poison batch must not kill the service.

    batch_free_on_return: what the owner knows of ITS ``dispatch`` — True
    when nothing reads the batch's arrays once ``dispatch`` has returned
    or raised.  The batcher then assembles every launch into a staging
    buffer it reuses (module docstring); False (a dispatch that hands the
    batch on, or keeps it) assembles each launch fresh.

    sched: optional ``can_tpu.sched.ServeSched`` — the shared scheduling
    core (priced sub-batch menu + priced flush deadlines).  None keeps
    the pre-r14 pad-to-``max_batch`` / fixed-timer behaviour exactly.

    bucket_ladder / pad_multiple / min_bucket_h: the image kind's
    ``snap_to_bucket`` arguments (same semantics as the offline batcher).

    kinds: further request kinds by name (``serve/kinds.py``); the image
    kind is always there, built from the arguments above.
    """

    def __init__(self, queue: BoundedRequestQueue, dispatch: Callable,
                 *, max_batch: int = 8, max_wait_ms: float = 5.0,
                 bucket_ladder=None, pad_multiple=None,
                 min_bucket_h: Optional[int] = None, ds: int = 8,
                 telemetry=None, clock=time.monotonic,
                 idle_wait_s: float = 0.05,
                 on_reject: Optional[Callable] = None,
                 sched=None, batch_free_on_return: bool = False,
                 kinds=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if sched is not None and sched.max_batch != int(max_batch):
            raise ValueError(
                f"sched menu tops out at {sched.max_batch}, batcher "
                f"max_batch is {max_batch} — one core, one top size")
        self.queue = queue
        self.dispatch = dispatch
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.sched = sched
        self.kinds = {IMAGE: ImageKind(bucket_ladder=bucket_ladder,
                                       pad_multiple=pad_multiple,
                                       min_bucket_h=min_bucket_h, ds=ds)}
        self.kinds.update(kinds or {})
        self.telemetry = telemetry
        # on_reject(reason, count): batcher-side rejections (deadline
        # expiry, poison batch) happen past the admission gate, so the
        # owner's reject counters need this hook to stay truthful
        self.on_reject = on_reject
        self._clock = clock
        self._idle_wait_s = float(idle_wait_s)
        self._pending: Dict[GroupKey, _Group] = {}
        # launched batches by flush reason (batcher thread writes; the
        # service's stats() copies)
        self.flush_reasons = {FLUSH_FULL: 0, FLUSH_DUE: 0, FLUSH_DRAIN: 0}
        # the staging pool (None: every launch fresh), and launches by how
        # they were assembled + the bytes the pool holds now (same
        # writer, same reader as flush_reasons)
        self._staging_pool: Optional[Dict[GroupKey, object]] = (
            {} if batch_free_on_return else None)
        self.staging = {"reused": 0, "fresh": 0, "bytes_held": 0}
        # the trace the thread's own cycle (wait / intake / poll) is
        # recorded under; minted on the first traced cycle
        self._lane: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- bucket mapping -------------------------------------------------
    def bucket_of(self, hw: Tuple[int, int]) -> Tuple[int, int]:
        """The image kind's bucket for an (H, W)."""
        return self.kinds[IMAGE].bucket_of(hw)

    # -- flush pricing ---------------------------------------------------
    def _flush_at(self, key: GroupKey, group: _Group, now: float) -> float:
        """Absolute flush deadline for one group — the core's priced
        deadline, or the legacy ``t0 + max_wait`` timer without a core."""
        if self.sched is None:
            return group.t0 + self.max_wait_s
        deadlines = [r.deadline_ts for r in group.requests
                     if r.deadline_ts is not None]
        return self.sched.flush_at(key, len(group.requests), group.t0,
                                   group.t_last, now,
                                   min(deadlines) if deadlines else None)

    def next_wake_s(self, now: Optional[float] = None) -> float:
        """Seconds until the earliest pending flush deadline (the EXACT
        pump wake bound — never a fixed poll grain), or ``idle_wait_s``
        with nothing pending.  >= 0."""
        now = self._clock() if now is None else now
        if not self._pending:
            return self._idle_wait_s
        due = min(self._flush_at(k, g, now)
                  for k, g in self._pending.items())
        return max(0.0, min(self._idle_wait_s, due - now))

    # -- core pump (thread-free, testable with a fake clock) ------------
    def run_once(self, wait_s: Optional[float] = None) -> int:
        """One pump iteration: wait for arrivals (bounded by the earliest
        pending flush deadline), intake, flush what's due.  Returns the
        number of batches dispatched."""
        wait = self.next_wake_s() if wait_s is None else wait_s
        tr = active(self.telemetry)
        if tr is None:
            self.queue.wait_nonempty(wait)
        else:
            with self._cycle_span(tr, "serve.wait"):
                self.queue.wait_nonempty(wait)
        return self.intake() + self.poll(self._clock())

    def _cycle_span(self, tr, name: str):
        """A span of the batcher thread's own cycle, on the thread's
        lane (one trace for the batcher's life)."""
        if self._lane is None:
            self._lane = tr.new_trace_id("batcher")
        return tr.span(name, trace_id=self._lane)

    def intake(self) -> int:
        """Drain the queue into per-bucket pending groups; reject already
        expired requests; launch every group that reached the top launch
        size (the one place that does).  Returns batches dispatched."""
        tr = active(self.telemetry)
        if tr is None:
            return self._intake()[0]
        with self._cycle_span(tr, "serve.intake") as sp:
            flushed, sp.attrs["taken"] = self._intake()
        return flushed

    def _intake(self) -> Tuple[int, int]:
        """-> (batches dispatched, requests taken off the queue)."""
        taken = self._sort_arrivals()
        flushed = 0
        for key in list(self._pending):
            group = self._pending[key]
            while len(group.requests) >= self.max_batch:
                full = group.requests[:self.max_batch]
                group.requests = group.requests[self.max_batch:]
                flushed += self._flush(key, full, FLUSH_FULL)
            if not group.requests:
                del self._pending[key]
            else:
                group.t0 = group.requests[0].t_submit
        if flushed:
            # a launch blocked this thread for as long as it ran: what
            # arrived meanwhile joins its group before ``poll`` judges the
            # groups.  Else the rest of a drain is flushed as "due" while
            # its companions sit in the queue: launches of 60 and 4 for a
            # group of 64
            taken += self._sort_arrivals()
        return flushed, taken

    def _sort_arrivals(self) -> int:
        """The queue's requests into their groups (nothing is launched);
        -> how many were taken off the queue."""
        live, expired = self.queue.drain()
        for r in expired:
            self._reject_expired(r)
        for r in live:
            key = self.kinds[r.kind].group_key(r)
            group = self._pending.get(key)
            if group is None:
                group = self._pending[key] = _Group(r.t_submit)
            group.requests.append(r)
            group.t_last = r.t_submit
            if self.sched is not None:
                self.sched.observe_arrival(key, r.t_submit)
        return len(live) + len(expired)

    def poll(self, now: float) -> int:
        """Reject expired pending requests; flush groups whose priced
        deadline (or legacy timer) has arrived.  Returns batches
        dispatched."""
        tr = active(self.telemetry)
        if tr is None:
            return self._poll(now)
        with self._cycle_span(tr, "serve.poll"):
            return self._poll(now)

    def _poll(self, now: float) -> int:
        flushed = 0
        for key in sorted(self._pending):
            group = self._pending[key]
            kept = []
            for r in group.requests:
                if r.expired(now):
                    self._reject_expired(r)
                else:
                    kept.append(r)
            if not kept:
                del self._pending[key]
                continue
            group.requests = kept
            if len(kept) >= self.max_batch:
                # filled while a launch blocked the thread: a whole launch
                # is the next intake's (``next_wake_s`` is 0 for it), and
                # what it leaves over is judged then, by its own oldest
                continue
            if now >= self._flush_at(key, group, now):
                del self._pending[key]
                flushed += self._flush(key, kept, FLUSH_DUE)
        return flushed

    def flush_all(self) -> int:
        """Dispatch every pending group (shutdown path: an admitted request
        resolves even when the service is closing)."""
        tr = active(self.telemetry)
        if tr is None or not self._pending:
            return self._flush_all()
        with self._cycle_span(tr, "serve.drain"):
            return self._flush_all()

    def _flush_all(self) -> int:
        n = 0
        for key in sorted(self._pending):
            group = self._pending.pop(key)
            n += self._flush(key, group.requests, FLUSH_DRAIN)
        return n

    def pending_count(self) -> int:
        return sum(len(g.requests) for g in self._pending.values())

    # -- assembly + dispatch --------------------------------------------
    def _flush(self, key: GroupKey, group: List[ServeRequest],
               reason: str) -> int:
        """Cover the group with menu-size launches (one launch padded to
        ``max_batch`` without a core) and dispatch each.  Returns the
        number of batches dispatched."""
        if self.sched is None:
            # one padded launch per max_batch-full slice (legacy; a group
            # never exceeds max_batch in practice — intake flushes full)
            parts: Tuple[int, ...] = (self.max_batch,) * max(
                1, -(-len(group) // self.max_batch))
        else:
            parts = self.sched.parts_for(len(group))
        n = 0
        pos = 0
        for size in parts:
            take = group[pos:pos + size]
            pos += size
            if not take:
                break
            self.flush_reasons[reason] += 1
            tr = active(self.telemetry)
            if tr is None:
                self._flush_part(key, take, size)
            else:
                # the root of the batch's own trace; on the thread's lane
                # a child of the cycle span that launched it
                with tr.span("serve.batch", trace_id=tr.new_trace_id("batch"),
                             bucket=[key[0], key[1]], slots=size,
                             valid=len(take), flush_reason=reason) as sp:
                    for r in take:
                        r.batch_span = sp
                    self._flush_part(key, take, size, tr)
            n += 1
        return n

    def _flush_part(self, key: GroupKey, group: List[ServeRequest],
                    size: int, tr=None) -> None:
        bh, bw = key[0], key[1]
        try:
            # assembly window stamped on every request (service clock):
            # queue-wait ends where assembly starts, and the service turns
            # the pair into the serve.request breakdown
            t_asm = self._clock()
            kind = self.kinds[group[0].kind]
            if tr is None:
                batch, _ = self._assemble(kind, key, group, size)
            else:
                with tr.span("serve.pad") as sp:
                    batch, sp.attrs["reused"] = self._assemble(kind, key,
                                                               group, size)
                    sp.attrs["bytes"] = int(kind.payload(batch).nbytes)
            t_ready = self._clock()
            for r in group:
                r.t_assembly = t_asm
                r.t_ready = t_ready
            self.dispatch((bh, bw), batch, group)
        except Exception as e:  # noqa: BLE001 — poison batch, keep serving
            n = 0
            for r in group:
                if not r.done:
                    r.reject(REJECT_ERROR, f"{type(e).__name__}: {e}")
                    n += 1
            if self.on_reject is not None and n:
                self.on_reject(REJECT_ERROR, n)
            if self.telemetry is not None:
                self.telemetry.emit("serve.reject", reason=REJECT_ERROR,
                                    count=n,
                                    detail=f"{type(e).__name__}: {e}")

    def _assemble(self, kind, key: GroupKey, group: List[ServeRequest],
                  size: int):
        """-> (the launch's batch, whether it was assembled into a buffer
        that already existed)."""
        out, reused = None, False
        if self._staging_pool is not None:
            out = self._staging_pool.get(key)
            reused = out is not None
            if out is None:
                out = self._staging_pool[key] = kind.new_staging(
                    key, self.max_batch)
                self.staging["bytes_held"] += out.nbytes
        self.staging["reused" if reused else "fresh"] += 1
        return kind.assemble(key, group, size, out), reused

    def _reject_expired(self, r: ServeRequest) -> None:
        r.reject(REJECT_DEADLINE, "deadline expired before dispatch")
        if self.on_reject is not None:
            self.on_reject(REJECT_DEADLINE, 1)
        if self.telemetry is not None:
            self.telemetry.emit("serve.reject", reason=REJECT_DEADLINE,
                                count=1, request_id=r.id)

    # -- thread lifecycle ------------------------------------------------
    def start(self) -> "MicroBatcher":
        if self._thread is not None:
            raise RuntimeError("batcher already started")
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="can-tpu-serve-batcher")
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            self.run_once()
        # drain-on-stop: admitted requests still resolve (close() has
        # already stopped new admissions)
        self.intake()
        self.flush_all()

    def close(self) -> None:
        """Stop the pump thread and flush everything pending (idempotent)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        else:
            self.intake()
            self.flush_all()
        if self._staging_pool:
            self._staging_pool.clear()
            self.staging["bytes_held"] = 0
