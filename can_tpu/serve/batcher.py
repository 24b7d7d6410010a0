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
resolved), the batcher assembles into staging buffers it OWNS: a ring of
``data.batching.StagingBatch`` per (bucket, dtype), as many as launches
may be in flight (below), each sized for the top launch size when it is
first needed and kept until ``close()``; a smaller menu size is its
leading view.  ``pad_batch(out=...)`` then copies the items in and zeroes
only what the buffer's previous launch left stale, instead of mapping and
zeroing the whole batch anew per launch (151 MB at b16 768x1024 f32: 9.2
ms per image on the v5e's host against 1.0 for the copy alone; PERF.md,
PR 25).  The batch handed to ``dispatch`` is a view of its buffer, and the
buffer goes back to the ring when that ``dispatch`` has returned or
raised, never earlier: the runtime reads its bytes until then.  Where
``dispatch`` only enqueues (the fleet: a ``_WorkItem`` keeps the batch
until a replica completes it, may be redispatched, and a wedged replica
may still be reading it) every launch is assembled fresh, and the batch is
its receiver's.  ``staging`` counts launches by which of the two it was.

The flush policy and launch sizes come from the shared scheduling core
(``can_tpu/sched``) when a ``ServeSched`` is given, which is whenever the
core can price every kind the service serves (``CountService`` decides;
images: the benchmark's ``serve-shb-closed``):

* a bucket's group flushes the moment it holds the TOP menu size (the
  batch is full — waiting longer buys nothing);
* otherwise it flushes at the core's PRICED deadline
  (``ServeSched.flush_at``): immediately when coalescing one more
  request cannot beat launch-cost amortization or when the bucket's
  observed arrival rate says no request is expected inside the window;
  at the latency cap (``max_wait_ms``) or the group's deadline slack
  otherwise — with no rate estimate yet the priced deadline is the
  ``max_wait_ms`` timer;
* a flush is covered by the core's menu parts (the planner's exact
  ``decompose`` DP): a 2-request flush launches a 2-slot program
  instead of padding to ``max_batch`` (fill slots remain
  ``sample_mask=0``, the offline dead-slot convention), and every
  emitted size is a menu size — the XLA compile count is
  ``buckets x dtypes x menu sizes``, static and warmed up front.

Without a ``sched`` (a service with a kind the core cannot price: a
launch of prompts costs decode steps, not slots x pixels) there is one
launch size and the timer: every flush pads to ``max_batch``, and a group
that is not full flushes ``max_wait_ms`` after its first request (the
benchmark's ``serve-exaone-chat-closed`` runs this side).

The pump wakes EXACTLY at the earliest pending flush deadline (or on
arrival, via the queue's condition) — never on a fixed poll grain: with
priced deadlines that can be "now", a 50 ms idle poll would eat the
entire low-load latency win, and under the timer a poll interval above a
short ``max_wait_ms`` would inflate the tail.

Requests whose deadline expires before dispatch are rejected, never
launched: a result the client has already given up on still costs a full
batch slot, and under overload those zombie slots are exactly the capacity
the live requests need.

Single consumer thread: it alone reads the queue, owns the pending-group
state (which therefore needs no locking beyond the queue's own), decides
the flushes and assembles the launches.  WHO RUNS a launch depends on how
many the owner's engine can hold in flight (``launches_in_flight``, which
the service reads off the engine; nobody sets it):

* 1 (a language model's engine, a hand-driven batcher, the fleet, whose
  ``dispatch`` only enqueues): ``dispatch`` runs ON the batcher thread.
  A launch blocks the pump for as long as it runs, and one staging buffer
  per key is enough.
* N > 1 (``ServeEngine``: 2), once ``start()`` has made the threads: the
  batcher thread hands each assembled launch to one of N LAUNCH LANES and
  returns to the queue; the lane runs ``dispatch`` (through the fetched
  answers and the requests' resolution) and gives the buffer back.  While
  lane A waits for program n, the batcher thread assembles batch n+1 into
  the ring's other buffer and lane B dispatches it: its layout change and
  H2D run while n computes, and its program is queued behind n (PERF.md,
  PR 27).  With N launches in flight the batcher thread waits for one to
  return before it assembles the next (``serve.launch_wait``): that is
  the backpressure, and what bounds the buffers.  The flush decision is
  the same in both: a group is judged by its size, its timer and its
  arrival rate, not by what is in flight.

Either way the thread's view of the queue is stale after a launch it had
to wait for: ``intake`` sorts what arrived meanwhile into its groups
before ``poll`` judges them, so a group's rest is never launched alone
while its companions sit in the queue.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

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


class _Launch(NamedTuple):
    """One assembled launch on its way to ``dispatch``: what whoever runs
    it needs to run it, to give its staging buffer back and to close its
    ``serve.batch`` span."""

    key: GroupKey
    batch: object
    requests: List[ServeRequest]
    staging: object            # the ring's buffer it views; None: fresh
    span: object               # its begun serve.batch span; None: untraced


class _LaunchLanes:
    """N threads that run launches, and the count of launches in flight.

    The batcher thread ``acquire()``s a slot BEFORE it assembles (it waits
    while N launches are in flight), ``hand_over()``s the assembled launch,
    and a lane ``run``s it and frees the slot.  ``run`` gives the launch's
    staging buffer back before the slot is freed: whoever holds a slot
    finds a buffer of its key free or not made yet."""

    def __init__(self, depth: int, run: Callable[[_Launch], None]):
        self.depth = int(depth)
        self.lock = threading.Condition()
        self._run = run
        self._in_flight = 0
        self._work: collections.deque = collections.deque()
        self._stop = False
        self._threads = [threading.Thread(target=self._lane, daemon=True,
                                          name=f"can-tpu-serve-lane_{i}")
                         for i in range(self.depth)]
        for t in self._threads:
            t.start()

    def in_flight(self) -> int:
        return self._in_flight

    def acquire(self) -> int:
        """Take a slot, waiting while all are taken; -> launches in flight
        without this one."""
        with self.lock:
            while self._in_flight >= self.depth:
                self.lock.wait()
            self._in_flight += 1
            return self._in_flight - 1

    def release(self) -> None:
        with self.lock:
            self._in_flight -= 1
            self.lock.notify_all()

    def hand_over(self, launch: _Launch) -> None:
        with self.lock:
            self._work.append(launch)
            self.lock.notify_all()

    def _lane(self) -> None:
        while True:
            with self.lock:
                while not self._work and not self._stop:
                    self.lock.wait()
                if not self._work:
                    return
                launch = self._work.popleft()
            try:
                self._run(launch)
            finally:
                self.release()

    def close(self, timeout: float = 30.0) -> None:
        """Wait for every launch in flight (no longer than ``timeout``: a
        hung engine must not hang shutdown), then stop the threads."""
        with self.lock:
            self.lock.wait_for(lambda: not self._in_flight, timeout)
            self._stop = True
            self.lock.notify_all()
        for t in self._threads:
            t.join(timeout=timeout)


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

    launches_in_flight: how many ``dispatch`` calls may be in progress at
    once (what the owner's engine states; needs ``batch_free_on_return``).
    Above 1, ``start()`` makes that many launch lanes and ``dispatch`` runs
    on them (module docstring); it must then be safe to call from several
    threads.  1: ``dispatch`` runs on the batcher thread.

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
                 launches_in_flight: int = 1, kinds=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if launches_in_flight < 1 or (launches_in_flight > 1
                                      and not batch_free_on_return):
            raise ValueError(
                f"launches_in_flight {launches_in_flight}: at least 1, and "
                f"more than 1 only for a dispatch that is done with its "
                f"batch when it returns (batch_free_on_return)")
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
        # the staging pool (None: every launch fresh): per key the ring's
        # buffers, all of them and those no launch holds; and launches by
        # how they were assembled + the bytes the pool holds now (same
        # writer, same reader as flush_reasons).  A free list is appended
        # to by whoever ran the launch and popped by the batcher thread
        # alone (``list.append`` / ``pop`` are atomic under CPython)
        self._staging_pool: Optional[Dict[GroupKey, List[object]]] = (
            {} if batch_free_on_return else None)
        self._staging_free: Dict[GroupKey, List[object]] = {}
        self.staging = {"reused": 0, "fresh": 0, "bytes_held": 0}
        # launch lanes: made by start() where dispatch may run on more than
        # one thread; None runs every launch on the batcher thread
        self.launches_in_flight = int(launches_in_flight)
        self._lanes: Optional[_LaunchLanes] = None
        # launches handed over while another was in flight (batcher thread
        # writes; stats() copies)
        self.launches_overlapped = 0
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
        ``max_batch`` without a core), assemble each and run it: here, or
        on a launch lane.  Returns the number of batches dispatched."""
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
            tr = active(self.telemetry)
            lanes, in_flight = self._lanes, 0
            if lanes is not None:
                in_flight, take = self._launch_slot(lanes, take, tr)
                if not take:
                    lanes.release()
                    continue
            self.flush_reasons[reason] += 1
            if in_flight:
                self.launches_overlapped += 1
            sp = None
            if tr is not None:
                # the root of the batch's own trace; on the thread's lane
                # a child of the cycle span that launched it.  Begun here,
                # finished by whoever runs the launch
                sp = tr.span("serve.batch", trace_id=tr.new_trace_id("batch"),
                             bucket=[key[0], key[1]], slots=size,
                             valid=len(take), flush_reason=reason,
                             in_flight=in_flight).begin()
                for r in take:
                    r.batch_span = sp
            launch = self._stage(key, take, size, tr, sp)
            if launch is None:
                if lanes is not None:
                    lanes.release()
            elif lanes is None:
                self._run_launch(launch)
            else:
                lanes.hand_over(launch)
            n += 1
        return n

    def _launch_slot(self, lanes: _LaunchLanes, take: List[ServeRequest],
                     tr) -> Tuple[int, List[ServeRequest]]:
        """Take a launch slot, waiting for a launch to return while all are
        taken (the backpressure) -> (launches in flight without this one,
        the requests still worth launching: those a wait saw expire are
        rejected here, as ``poll`` would have)."""
        if lanes.in_flight() < lanes.depth:
            return lanes.acquire(), take
        if tr is None:
            in_flight = lanes.acquire()
        else:
            with tr.span("serve.launch_wait"):
                in_flight = lanes.acquire()
        now = self._clock()
        live = []
        for r in take:
            if r.expired(now):
                self._reject_expired(r)
            else:
                live.append(r)
        return in_flight, live

    def _stage(self, key: GroupKey, group: List[ServeRequest], size: int,
               tr, sp) -> Optional[_Launch]:
        """Assemble one launch (``serve.pad``); None when assembly raised
        and the requests were rejected."""
        out = None
        try:
            # assembly window stamped on every request (service clock):
            # queue-wait ends where assembly starts, and the service turns
            # the pair into the serve.request breakdown
            t_asm = self._clock()
            kind = self.kinds[group[0].kind]
            out, reused = self._staging_for(kind, key)
            if tr is None:
                batch = kind.assemble(key, group, size, out)
            else:
                with sp.under(), tr.span("serve.pad", reused=reused) as pad:
                    batch = kind.assemble(key, group, size, out)
                    pad.attrs["bytes"] = int(kind.payload(batch).nbytes)
            t_ready = self._clock()
            for r in group:
                r.t_assembly = t_asm
                r.t_ready = t_ready
            return _Launch(key, batch, group, out, sp)
        except Exception as e:  # noqa: BLE001 — poison batch, keep serving
            self._reject_poison(group, e)
            self._launch_done(key, out, sp)
            return None

    def _run_launch(self, launch: _Launch) -> None:
        """``dispatch`` one assembled launch through to its end: the
        batcher thread, or a launch lane.  Never raises."""
        key, sp = launch.key, launch.span
        try:
            if sp is None:
                self.dispatch(key[:2], launch.batch, launch.requests)
            else:
                with sp.under():
                    self.dispatch(key[:2], launch.batch, launch.requests)
        except Exception as e:  # noqa: BLE001 — poison batch, keep serving
            self._reject_poison(launch.requests, e)
        finally:
            self._launch_done(key, launch.staging, sp)

    def _launch_done(self, key: GroupKey, staging, sp) -> None:
        """``dispatch`` has returned or raised: nothing reads the launch's
        staging buffer any more, and its span ends."""
        if staging is not None:
            self._staging_free[key].append(staging)
        if sp is not None:
            sp.finish()

    def _reject_poison(self, group: List[ServeRequest], e: Exception) -> None:
        n = 0
        for r in group:
            if not r.done:
                r.reject(REJECT_ERROR, f"{type(e).__name__}: {e}")
                n += 1
        if self.on_reject is not None and n:
            self.on_reject(REJECT_ERROR, n)
        if self.telemetry is not None:
            self.telemetry.emit("serve.reject", reason=REJECT_ERROR,
                                count=n, detail=f"{type(e).__name__}: {e}")

    def _staging_for(self, kind, key: GroupKey):
        """-> (a buffer of ``key``'s ring that no launch holds, whether it
        existed already); (None, False) where every launch is assembled
        fresh.  Whoever asks runs launches in line or holds a launch slot,
        so fewer than ``launches_in_flight`` of the ring's buffers are out:
        one is free, or the ring has room for one more."""
        out, reused = None, False
        if self._staging_pool is not None:
            free = self._staging_free.setdefault(key, [])
            reused = bool(free)
            if reused:
                out = free.pop()
            else:
                out = kind.new_staging(key, self.max_batch)
                self._staging_pool.setdefault(key, []).append(out)
                self.staging["bytes_held"] += out.nbytes
        self.staging["reused" if reused else "fresh"] += 1
        return out, reused

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
        if self.launches_in_flight > 1:
            self._lanes = _LaunchLanes(self.launches_in_flight,
                                       self._run_launch)
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
        """Stop the pump thread, flush everything pending and wait for the
        launches in flight (idempotent)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        else:
            self.intake()
            self.flush_all()
        if self._lanes is not None:
            self._lanes.close()
            self._lanes = None
        if self._staging_pool:
            self._staging_pool.clear()
            self._staging_free.clear()
            self.staging["bytes_held"] = 0
