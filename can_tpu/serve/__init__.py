"""can_tpu.serve — online inference: bucketed micro-batching, deadlines,
backpressure.

The training repro already solved variable-resolution-under-XLA once
(``data/batching.py``); this subsystem lifts that solution to request
granularity::

    engine = ServeEngine(params, batch_stats)
    ladder = ((384, 768), (512, 1024))      # per-axis H x W bounds
    svc = CountService(engine, max_batch=8, max_wait_ms=5,
                       queue_capacity=64, high_water=48,
                       bucket_ladder=ladder)
    # compile BEFORE traffic — the ladder's full cross product, because
    # any (H bound, W bound) pairing can occur
    svc.warmup([(h, w) for h in ladder[0] for w in ladder[1]])
    with svc:                               # starts the batcher thread
        res = svc.predict(prepare_image(img), deadline_ms=200)
        print(res.count, res.latency_s)

Guarantees: every submitted request resolves or is rejected with a typed
reason (never hangs); compile count == distinct (bucket, menu size,
dtype) programs — the launch-size menu comes from the shared scheduling
core (``can_tpu/sched``, r14) — all paid in ``warmup``; a served count
is bit-for-bit what ``evaluate()`` computes offline for the same image
and params at the same launch size.
"""

from .aot import AotBundle, AotStaleError, load_aot_bundle
from .autoscale import Autoscaler, AutoscalePolicy
from .batcher import MicroBatcher
from .engine import LMEngine, ServeEngine, lm_probe_steps, tree_signature
from .fleet import (
    REPLICA_ACTIVE,
    REPLICA_DRAINING,
    REPLICA_QUARANTINED,
    REPLICA_WEDGED,
    FleetClosedError,
    FleetEngine,
    ReplicaWedgedError,
    priced_deadline_s,
)
from .quant import (
    PARITY_LADDER,
    SERVE_DTYPES,
    dequantize_tree,
    parity_report,
    quantize_tree,
)
from .queue import (
    REJECT_BACKPRESSURE,
    REJECT_DEADLINE,
    REJECT_ERROR,
    REJECT_QUEUE_FULL,
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
from .service import (
    CountService,
    GenerateService,
    ServeTicket,
    build_model_service,
    make_http_handler,
    prepare_image,
    serve_http,
)
from .streams import (
    STREAM_RUNG_FULL,
    STREAM_RUNG_REJECT,
    STREAM_RUNG_SKIP,
    StreamSession,
    StreamSessionRegistry,
    repin_target,
)

__all__ = [
    "AotBundle",
    "AotStaleError",
    "Autoscaler",
    "AutoscalePolicy",
    "BoundedRequestQueue",
    "CountService",
    "GenerateResult",
    "GenerateService",
    "LMEngine",
    "TokenRequest",
    "build_model_service",
    "lm_probe_steps",
    "FleetClosedError",
    "FleetEngine",
    "MicroBatcher",
    "PARITY_LADDER",
    "REPLICA_ACTIVE",
    "REPLICA_DRAINING",
    "REPLICA_QUARANTINED",
    "REPLICA_WEDGED",
    "ReplicaWedgedError",
    "load_aot_bundle",
    "priced_deadline_s",
    "SERVE_DTYPES",
    "dequantize_tree",
    "parity_report",
    "quantize_tree",
    "tree_signature",
    "REJECT_BACKPRESSURE",
    "REJECT_DEADLINE",
    "REJECT_ERROR",
    "REJECT_QUEUE_FULL",
    "REJECT_SHUTDOWN",
    "REJECT_STALE_FRAME",
    "REJECT_STREAM_OVERLOAD",
    "RejectedError",
    "STREAM_RUNG_FULL",
    "STREAM_RUNG_REJECT",
    "STREAM_RUNG_SKIP",
    "ServeEngine",
    "ServeRequest",
    "ServeResult",
    "ServeTicket",
    "StreamSession",
    "StreamSessionRegistry",
    "make_http_handler",
    "prepare_image",
    "repin_target",
    "serve_http",
]
