"""Multi-host runtime: rendezvous, topology queries, host-level collectives.

TPU-native counterpart of the reference's NCCL bootstrap
(reference: utils/distributed_utils.py:7-70):

* ``init_distributed_mode`` (env-var / SLURM rendezvous + nccl init_process_group)
  → ``init_runtime`` calling ``jax.distributed.initialize`` when a coordinator
  is configured, else single-process no-op (the reference degrades the same
  way, distributed_utils.py:15-18).
* ``get_rank / get_world_size / is_main_process`` → ``process_index /
  process_count / is_main_process`` (JAX process == host, not chip).
* ``dist.barrier`` → ``barrier()`` via multihost sync.
* ``reduce_value`` (dist.all_reduce of a metric tensor, distributed_utils.py:60-70)
  → ``reduce_value`` — but note: in this framework cross-chip reductions of
  loss/metrics happen *inside* compiled programs as ``lax.psum`` / GSPMD
  shardings; this host-level helper exists only for values computed outside
  jit (e.g. host-side counters).

Identical-init protocol: unnecessary here.  The reference makes replicas agree
by rank0-saving random weights to a tempfile + barrier + all-load
(train.py:104-114); with JAX, every process seeds the same PRNG key and gets
bit-identical params by construction.

Elastic re-init (r13): the runtime is GENERATION-COUNTED, not init-once.
``init_runtime`` → ``shutdown_runtime(reset=True)`` →
``init_runtime`` at a different world size is a supported cycle: each
completed init bumps :func:`generation`, and resetting the backends
between generations rebuilds the device topology for the new world (live
``jax.Array``s of the old generation become invalid — the elastic
choreography round-trips state through a checkpoint, parallel/elastic.py).
``barrier`` takes a bounded timeout and raises a typed
:class:`RendezvousTimeoutError` naming the generation instead of hanging
through a preemptor's SIGKILL window.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import jax
import numpy as np


_generation = 0      # completed init_runtime() calls (monotonic, never reset)
_active = False      # a runtime generation is currently live
_distributed = False  # ... and it holds a jax.distributed client

#: default bound on barrier()/re-rendezvous waits, overridable per call or
#: via the environment.  Finite BY DEFAULT: an indefinite wait at a
#: re-formation barrier outlives the preemptor's grace window and turns a
#: recoverable shrink into a SIGKILL with no incident record.
DEFAULT_BARRIER_TIMEOUT_S = float(
    os.environ.get("CAN_TPU_BARRIER_TIMEOUT_S", "300"))


class RendezvousTimeoutError(RuntimeError):
    """A multihost barrier did not complete within its bound.

    Carries the runtime ``generation``, the barrier ``name``, the
    ``timeout_s`` that expired, and ``missing`` — the host/process ids
    that had not arrived, when the coordination service reports them
    (None = unknown: the transport gave no partial-arrival info)."""

    def __init__(self, name: str, *, generation: int, timeout_s: float,
                 missing: Optional[Sequence] = None, detail: str = ""):
        self.barrier = name
        self.generation = generation
        self.timeout_s = timeout_s
        self.missing = list(missing) if missing is not None else None
        miss = ("unknown (no partial-arrival info)" if self.missing is None
                else ", ".join(str(m) for m in self.missing))
        super().__init__(
            f"barrier {name!r} (runtime generation {generation}) timed out "
            f"after {timeout_s:g}s; missing hosts: {miss}"
            + (f" — {detail}" if detail else ""))

# base rendezvous port for SLURM auto-derived coordinators: every task
# must compute the SAME address without communicating, so the port must be
# a pure function of job metadata (the reference hardcodes 29500 via
# torch.distributed.launch; this base is can_tpu's own to avoid colliding
# with a torch job on the same node).  The ACTUAL port offsets by
# SLURM_JOB_ID % 1000 — identical for every task of one job, different
# across concurrent jobs whose first node coincides (two jobs at one
# fixed port would rendezvous into each other: the split-brain class
# this module exists to prevent).
SLURM_COORDINATOR_PORT = 8476


def _slurm_port(env) -> int:
    try:
        return SLURM_COORDINATOR_PORT + int(env.get("SLURM_JOB_ID", "")) % 1000
    except ValueError:
        return SLURM_COORDINATOR_PORT


def _first_slurm_host(nodelist: str) -> str:
    """First hostname of a SLURM_JOB_NODELIST, expanding the compressed
    bracket form: "tpu[003-004,007],gpu2" -> "tpu003" (zero padding kept,
    as sinfo/scontrol print it)."""
    s = nodelist.strip()
    if not s:
        raise RuntimeError("empty SLURM_JOB_NODELIST")
    # cut at the first comma OUTSIDE brackets (commas inside [] separate
    # ranges of the same prefix)
    depth = 0
    first = s
    for i, ch in enumerate(s):
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth -= 1
        elif ch == "," and depth == 0:
            first = s[:i]
            break
    if "[" not in first:
        return first
    prefix, _, rest = first.partition("[")
    body = rest.rstrip("]")
    head = body.split(",")[0].split("-")[0]
    return prefix + head


def _slurm_rendezvous(env=None):
    """(coordinator_address, num_processes, process_id) derived from SLURM
    metadata, None when this is not a multi-task SLURM job.

    Contract (VERDICT missing #3): metadata that identifies a LAUNCHED
    task of a multi-task job (``SLURM_PROCID`` is set — only ``srun``
    sets it, once per task) but lacks what rendezvous needs is FATAL,
    exactly like the TPU-pod guard below — a silent single-process
    fallback would train this task alone on a diverged lockstep schedule
    while its siblings wait at the coordinator.  An salloc SHELL is not a
    launched task: salloc exports ``SLURM_NTASKS``/``SLURM_JOB_NODELIST``
    but never ``SLURM_PROCID``, so NTASKS-without-PROCID degrades to
    single-process (with a notice) — that is someone debugging inside an
    allocation, and srun would have set PROCID.
    """
    env = os.environ if env is None else env
    ntasks_s = env.get("SLURM_NTASKS", "")
    nodelist = env.get("SLURM_JOB_NODELIST", "")
    procid_s = env.get("SLURM_PROCID", "")
    if not ntasks_s:
        if procid_s:
            # a launched task (srun sets both) missing its task count:
            # incomplete metadata, not "no SLURM"
            raise RuntimeError(
                "SLURM_PROCID is set but SLURM_NTASKS is not — SLURM "
                "metadata present but incomplete; refusing to guess "
                "single-process (split-brain risk)")
        return None  # salloc shell / stray vars: not a launched task
    try:
        ntasks = int(ntasks_s)
    except ValueError:
        raise RuntimeError(
            f"unparseable SLURM_NTASKS={ntasks_s!r}; refusing to degrade "
            "to single-process")
    if ntasks <= 1:
        return None  # single-task job: nothing to rendezvous
    if not procid_s:
        # NTASKS > 1 but no task id: an salloc shell inside a multi-task
        # allocation, not an srun-launched task (srun always sets
        # PROCID) — single-process is correct, but say so, since the
        # surrounding allocation LOOKS distributed
        print(f"[runtime] SLURM_NTASKS={ntasks} but SLURM_PROCID is "
              "unset (salloc shell, not an srun task): running "
              "single-process; use srun to launch the distributed job",
              flush=True)
        return None
    if not nodelist:
        raise RuntimeError(
            f"SLURM task {procid_s} of {ntasks} has no "
            "SLURM_JOB_NODELIST — SLURM metadata present but incomplete; "
            "refusing to degrade to single-process (split-brain)")
    try:
        procid = int(procid_s)
    except ValueError:
        raise RuntimeError(
            f"unparseable SLURM_PROCID={procid_s!r} in a "
            f"{ntasks}-task SLURM job")
    host = _first_slurm_host(nodelist)
    return f"{host}:{_slurm_port(env)}", ntasks, procid


def _multihost_metadata_present() -> bool:
    """True only when pod metadata names MORE THAN ONE worker — a single
    hostname (a one-host machine, whatever its chip count) is not a pod.

    A bare coordinator var is NOT such a signal on its own: dev machines
    inherit stale ``JAX_COORDINATOR_ADDRESS`` / ``MEGASCALE_*`` env from
    old pod sessions, and treating it as pod metadata routed them into the
    fatal split-brain branch below (ADVICE r5).  The coordinator var only
    counts when an accompanying worker-count variable says > 1 worker;
    otherwise this host degrades to single-process like any other
    coordinator-less run."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hosts.split(",") if h.strip()]) > 1:
        return True
    if ("JAX_COORDINATOR_ADDRESS" in os.environ
            or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ):
        for var in ("NUM_PROCESSES", "JAX_NUM_PROCESSES",
                    "TPU_WORKER_COUNT", "MEGASCALE_NUM_SLICES"):
            try:
                if int(os.environ.get(var, "")) > 1:
                    return True
            except ValueError:
                continue
    return False


def reset_backends() -> None:
    """Drop every live PJRT client + jit cache so the NEXT device access
    rebuilds the topology for the current world — the bridge between
    runtime generations.  Every ``jax.Array`` of the old generation
    becomes invalid: callers round-trip state through host memory or a
    checkpoint (the elastic choreography does the latter)."""
    jax.clear_caches()
    from jax.extend import backend as _backend

    _backend.clear_backends()


def generation() -> int:
    """Completed ``init_runtime`` calls — the runtime generation.  An
    elastic transition bumps it; barrier names and elastic manifests carry
    it so logs from different world formations can't be conflated."""
    return _generation


def runtime_active() -> bool:
    return _active


def init_runtime(*, coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 env_rendezvous: bool = True) -> dict:
    """Initialise multi-host JAX if a coordinator is configured.

    Rendezvous sources, in priority order (mirroring the reference's env-var /
    SLURM probing, distributed_utils.py:8-14):

    1. explicit arguments;
    2. ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID`` env vars;
    3. SLURM auto-rendezvous: coordinator = first host of
       ``SLURM_JOB_NODELIST`` at the fixed ``SLURM_COORDINATOR_PORT``,
       num_processes = ``SLURM_NTASKS``, process_id = ``SLURM_PROCID`` —
       incomplete multi-task SLURM metadata is FATAL (see
       ``_slurm_rendezvous``), never a silent single-process fallback;
    4. TPU pod metadata (``jax.distributed.initialize()`` with no args
       auto-detects on Cloud TPU when JAX_COORDINATOR_ADDRESS etc. are set);
    5. none found → single-process mode (no distributed client), like the
       reference's "Not using distributed mode" fallback.

    Re-initialisable: after ``shutdown_runtime(reset=True)`` a fresh
    call forms a NEW generation, possibly at a different world size
    (the elastic shrink path).  A call while a generation is live returns
    the current topology unchanged.  ``env_rendezvous=False`` disables
    sources 2-4 entirely — the elastic re-formation MUST pass it: the
    launcher's COORDINATOR_ADDRESS/NUM_PROCESSES/SLURM/pod metadata all
    describe the DEAD generation's world, and re-reading them makes a
    lone survivor re-rendezvous a 2-process world whose other member is
    gone (RegisterTask deadline → coordination-service abort, found by
    the live 2-host CLI drive).  Returns a small topology dict
    (incl. ``generation``) for logging.
    """
    global _generation, _active, _distributed
    if env_rendezvous:
        coordinator_address = (coordinator_address
                               or os.environ.get("COORDINATOR_ADDRESS"))
        if num_processes is None and "NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["NUM_PROCESSES"])
        if process_id is None and "PROCESS_ID" in os.environ:
            process_id = int(os.environ["PROCESS_ID"])
        elif process_id is None and "SLURM_PROCID" in os.environ:
            process_id = int(os.environ["SLURM_PROCID"])
        if coordinator_address is None:
            slurm = _slurm_rendezvous()
            if slurm is not None:
                coordinator_address, slurm_n, slurm_id = slurm
                num_processes = (slurm_n if num_processes is None
                                 else num_processes)
                process_id = slurm_id if process_id is None else process_id

    if not _active:
        if coordinator_address:
            # (a multi-process CPU world needs no extra switch: jax 0.9.0
            # defaults jax_cpu_collectives_implementation to gloo and
            # applies it exactly when a distributed client is live)
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            _distributed = True
        elif env_rendezvous and _multihost_metadata_present():
            # Cloud TPU pod metadata present: no-arg initialize auto-detects
            # topology (rendezvous source 3).
            try:
                jax.distributed.initialize()
                _distributed = True
            except (ValueError, RuntimeError) as e:
                if jax.process_count() > 1:
                    # an external launcher already initialised the
                    # distributed client for this process — use it, but
                    # do NOT own it: _distributed stays False so
                    # shutdown_runtime never tears down a client the
                    # launcher expects to still be alive (double
                    # shutdown)
                    print(f"[runtime] distributed client already up: {e}")
                else:
                    # Metadata NAMES a multi-host job (a one-host
                    # machine never reaches this branch — see
                    # _multihost_metadata_present), so a failed rendezvous
                    # must be FATAL: swallowing it left this host training
                    # alone on a diverged lockstep schedule while its
                    # peers waited at the coordinator — a silent
                    # split-brain (code-review r5).
                    raise RuntimeError(
                        "multi-host metadata present but distributed "
                        "rendezvous failed; refusing to degrade to "
                        f"single-process (split-brain): {e}") from e
        else:
            _distributed = False
        _generation += 1
        _active = True
    return {
        "process_index": process_index(),
        "process_count": process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "generation": _generation,
    }


def shutdown_runtime(*, reset: bool = False) -> None:
    """Tear down the current runtime generation (the reference defines
    ``cleanup()`` but never calls it, train.py — we do, from the CLI's
    finally block).

    ``reset=True`` additionally drops the PJRT backends + caches so a
    following ``init_runtime`` forms a genuinely new world (the elastic
    re-rendezvous path).  The default keeps the old exit-path behaviour:
    live arrays stay valid through interpreter teardown.

    Multihost note: ``jax.distributed.shutdown`` runs a shutdown barrier —
    on an ELASTIC leave, every member of the dying generation (leavers
    included, inside their preemption grace window) must call this, or
    the coordination service aborts the survivors (the fatal the
    coordinated-leave choreography in parallel/elastic.py exists to
    avoid)."""
    global _active, _distributed
    if _active and _distributed:
        jax.distributed.shutdown()
    _active = False
    _distributed = False
    if reset:
        reset_backends()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    return jax.process_index() == 0


_MISSING_RE = None  # compiled lazily (re import below)


def _parse_missing_tasks(message: str) -> Optional[list]:
    """Task ids the coordination service names as not-arrived in a barrier
    error, e.g. ``.../task:3``; None when the message carries none."""
    global _MISSING_RE
    if _MISSING_RE is None:
        import re

        _MISSING_RE = re.compile(r"/task:(\d+)")
    found = sorted({int(m) for m in _MISSING_RE.findall(message)})
    return found or None


def barrier(name: str = "barrier",
            timeout_s: Optional[float] = None) -> None:
    """Block until all processes arrive (reference: dist.barrier) —
    BOUNDED: after ``timeout_s`` (default ``DEFAULT_BARRIER_TIMEOUT_S``,
    env ``CAN_TPU_BARRIER_TIMEOUT_S``) raises
    :class:`RendezvousTimeoutError` naming the runtime generation and —
    when the coordination service reports them — the missing hosts.  A
    barrier during elastic re-formation that hangs instead of raising
    would ride out the preemptor's grace window and die by SIGKILL with
    no incident record; the typed error lets the caller dump a bundle
    and exit (or re-plan around the missing host) first.

    ``timeout_s <= 0`` restores the old unbounded wait."""
    if jax.process_count() <= 1:
        return
    if timeout_s is None:
        timeout_s = DEFAULT_BARRIER_TIMEOUT_S
    from can_tpu.testing.faults import active_injector

    inj = active_injector()
    if inj is not None:
        # deterministic fault harness: a scheduled rendezvous_timeout
        # fault makes THIS barrier behave as if a peer never arrived
        inj.on_barrier(name, rank=process_index())
    gen = _generation
    # jax 0.9.0's public jax.distributed exposes only initialize /
    # is_initialized / shutdown — the coordination client's bounded
    # barrier is reachable through the private module alone.  None when
    # no distributed client is live in this process.
    from jax._src import distributed as _dist

    client = _dist.global_state.client
    if client is not None and timeout_s > 0:
        # the coordination service's own barrier: a REAL server-side
        # timeout whose error names the tasks that never arrived
        try:
            client.wait_at_barrier(f"can_tpu:{name}:g{gen}",
                                   timeout_in_ms=int(timeout_s * 1000))
            return
        except Exception as e:  # jaxlib raises XlaRuntimeError
            msg = str(e)
            low = msg.lower()
            # only a genuine deadline becomes the typed TIMEOUT (its
            # message names the not-arrived tasks); a peer-abort or
            # service error 2s in must not masquerade as "timed out
            # after 300s" — callers and incident bundles would chase a
            # phantom timeout
            if ("deadline" in low or "timed out" in low
                    or "timeout" in low):
                raise RendezvousTimeoutError(
                    name, generation=gen, timeout_s=timeout_s,
                    missing=_parse_missing_tasks(msg),
                    detail=msg.splitlines()[0] if msg else "") from e
            raise
    from jax.experimental import multihost_utils

    if timeout_s <= 0:
        multihost_utils.sync_global_devices(name)
        return
    # no coordination client handle: bound the WAIT around the unbounded
    # sync (the stuck thread is abandoned — the caller is about to tear
    # the process down anyway)
    bounded_wait(lambda: multihost_utils.sync_global_devices(name),
                 name=name, timeout_s=timeout_s, generation=gen)


def bounded_wait(fn, *, name: str, timeout_s: float,
                 generation: Optional[int] = None, detail: str = ""):
    """Run a blocking collective ``fn`` on a daemon thread and bound the
    wait: on expiry raise the typed :class:`RendezvousTimeoutError`
    instead of hanging through a preemptor's SIGKILL window (the stuck
    thread is abandoned — callers are on a teardown/abort path).  Shared
    by the barrier fallback above and the elastic agreement allgather
    (parallel/elastic.py).  Returns ``fn()``'s result."""
    done = threading.Event()
    out: list = []

    def _run():
        try:
            out.append((True, fn()))
        except Exception as e:  # surfaced to the waiting thread
            out.append((False, e))
        finally:
            done.set()

    t = threading.Thread(target=_run, name=f"bounded-{name}", daemon=True)
    t.start()
    if not done.wait(timeout_s):
        raise RendezvousTimeoutError(
            name, generation=_generation if generation is None
            else generation, timeout_s=timeout_s, detail=detail)
    ok, value = out[0]
    if not ok:
        raise value
    return value


def reduce_value(value, average: bool = True):
    """Sum (or average) a host-side scalar/array across processes.

    No-op at world size 1, like the reference (distributed_utils.py:62-63).
    """
    if jax.process_count() < 2:
        return value
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray(value))
    total = gathered.sum(axis=0)
    return total / jax.process_count() if average else total


def agree_max_value(value):
    """Elementwise maximum of a host-side scalar/array across processes
    (no-op at world size 1).  The union-agreement primitive: the elastic
    supervisor allgathers per-host leave/dead bitmasks each poll — max is
    set-union on 0/1 masks — so every host derives the SAME leaver set at
    the same lockstep step boundary (parallel/elastic.py)."""
    if jax.process_count() < 2:
        return value
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(np.asarray(value)).max(axis=0)


def agree_min_value(value):
    """Minimum of a host-side scalar/array across processes (no-op at
    world size 1).  For numbers every host must DERIVE IDENTICALLY from
    per-host measurements — e.g. the HBM launch cap: the lockstep batch
    schedule breaks if hosts disagree, and min is the conservative
    agreement (no host schedules a launch another host can't fit)."""
    if jax.process_count() < 2:
        return value
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(np.asarray(value)).min(axis=0)
