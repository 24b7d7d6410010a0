"""Async host-side prefetch: overlap data loading/transfer with device work.

The reference gets this from torch DataLoader worker processes
(reference: train.py:87-91, num_workers); here a single background thread
runs the (numpy) batch materialisation + host->device transfer while the
device crunches the previous step — with JAX's async dispatch that is enough
to hide the input pipeline entirely.

Observability: pass ``stall=obs.StallClock()`` to account the seconds the
CONSUMER spends blocked waiting for a batch that isn't ready — genuine
input-pipeline starvation, the thing that silently caps throughput when the
host can't keep up with the chip.  Time is added only when the popped
future wasn't already done, so an overlapped (hidden) load costs zero.

Spans: pass ``tracer`` (an ``obs.spans.SpanTracer``) and the worker's
``next(it)`` and ``put_fn`` are recorded as ``input.load`` / ``input.put``
(the host pipeline's own ceiling is one image per load + put, there being
one worker), and each interval the StallClock sums as ``input.wait``.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, Optional


class PrefetchPutError(RuntimeError):
    """``put_fn`` failed inside the prefetch worker thread.

    The worker's exception only surfaces when its future is popped — up to
    ``depth`` batches after the failing one, by which point "which batch?"
    is gone from the traceback (the generator frame swallowed it).  This
    wrapper pins the failing batch index; the original exception rides
    along as ``__cause__`` with its full worker-thread traceback."""

    def __init__(self, batch_index: int):
        super().__init__(f"put_fn failed on batch {batch_index} "
                         f"(prefetched in a worker thread; see the chained "
                         f"cause for the original traceback)")
        self.batch_index = batch_index


def _nbytes(batch) -> int:
    """Bytes ``put_fn`` is handed (a ``data.Batch``; 0 for anything else)."""
    return sum(int(getattr(getattr(batch, k, None), "nbytes", 0))
               for k in ("image", "dmap", "pixel_mask", "sample_mask"))


def prefetch_to_device(batches: Iterable, put_fn: Callable, *,
                       depth: int = 2, stall=None, tracer=None,
                       trace_id: Optional[str] = None,
                       parent_id: Optional[str] = None) -> Iterator:
    """Yield ``put_fn(batch)`` for each batch, computed ``depth`` ahead in a
    background thread.  depth<=0 disables prefetching (synchronous path:
    exceptions propagate untouched, and ``stall`` accounts the full load
    time — nothing overlaps it).  ``tracer`` records the ``input.*``
    spans under ``trace_id`` / ``parent_id`` (a trace of their own when
    the caller names none)."""
    if tracer is not None and trace_id is None:
        trace_id = tracer.new_trace_id("input")
    if depth <= 0:
        for b in batches:
            if stall is not None:
                t0 = time.perf_counter()
                out = put_fn(b)
                stall.add(time.perf_counter() - t0)
                yield out
            else:
                yield put_fn(b)
        return

    it = iter(batches)
    _done = object()
    n_submitted = 0

    def load_next(index: int):
        try:
            if tracer is None:
                batch = next(it)
            else:
                with tracer.span("input.load", trace_id=trace_id,
                                 parent_id=parent_id, index=index):
                    batch = next(it)
        except StopIteration:
            return _done
        try:
            if tracer is None:
                return put_fn(batch)
            with tracer.span("input.put", trace_id=trace_id,
                             parent_id=parent_id, index=index,
                             bytes=_nbytes(batch)):
                return put_fn(batch)
        except Exception as e:
            raise PrefetchPutError(index) from e

    def submit():
        nonlocal n_submitted
        fut = ex.submit(load_next, n_submitted)
        n_submitted += 1
        return fut

    ex = ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="can-tpu-prefetch")
    n_popped = 0
    try:
        queue = collections.deque(submit() for _ in range(depth))
        while queue:
            fut = queue.popleft()
            if (stall is not None or tracer is not None) and not fut.done():
                t0 = time.perf_counter()
                result = fut.result()
                t1 = time.perf_counter()
                if stall is not None:
                    stall.add(t1 - t0)
                if tracer is not None:
                    tracer.emit(trace_id=trace_id, name="input.wait",
                                start=t0, end=t1, parent_id=parent_id,
                                index=n_popped,
                                thread=threading.current_thread().name)
            else:
                result = fut.result()
            n_popped += 1
            if result is _done:
                break
            queue.append(submit())
            yield result
    finally:
        # On consumer abandonment (GeneratorExit: a raised
        # NonFiniteLossError, Ctrl-C, an early break) the queued
        # load_next futures must be CANCELLED, not awaited — each runs a
        # host->device transfer, and `with ThreadPoolExecutor` would
        # block generator close behind up to ``depth`` full loads (or
        # forever on a wedged accelerator; code-review r5).  The one
        # in-flight call still finishes (a worker thread can't be
        # interrupted), but nothing new starts.
        ex.shutdown(wait=False, cancel_futures=True)
