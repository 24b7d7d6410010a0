"""The engines: device-resident params + the jitted programs of one model,
one program per bucket signature.  An engine is GIVEN its programs
(``serve/programs.py``, the model seam); it imports no network.
``ServeEngine`` runs CANNet's one predict program per launch; ``LMEngine``
(below) a language model's prefill and then a loop of decode steps over a
cache that stays on the device.

ServeEngine's prediction math is EXACTLY the offline eval step's (``train/steps.py
make_eval_step``): normalise-on-device for u8 batches, ``cannet_apply``
forward, masked per-image count reduction via ``train.loss.density_counts``
— so a count served online is bit-for-bit the count ``evaluate()`` would
have produced for the same image and params (the offline/online parity the
tests pin).  The engine adds only what serving needs around that math:

* params (and BN ``batch_stats``) are device-resident from construction —
  a host-numpy param tree fed to jit would re-upload ~74 MB per batch —
  and stored in the ``serve_dtype`` format (``serve/quant.py``): f32
  bit-parity, bf16 MXU-rate, or int8 weight-only PTQ with in-program
  dequantization and f32 accumulation;
* ``device=`` pins one engine to one device of the mesh (the fleet's
  replica placement: committed params make jit place the whole program
  on that device) — None keeps the single-device default behaviour;
* ``warmup()`` drives one zero batch through every bucket shape BEFORE
  traffic, so no real request pays the multi-second trace+compile bill,
  and ``utils/compile_cache`` (wired by the CLI) makes warm restarts
  deserialise instead of recompile;
* ``swap_params()`` atomically replaces the device-resident trees with a
  new checkpoint's — same structure means the already-compiled programs
  serve the new weights instantly (params are jit ARGUMENTS, not
  constants), which is what makes the fleet's blue/green flip free;
* every new (shape, dtype) signature is counted and attributed on the
  telemetry bus via ``obs.RecompileTracker`` — a mid-traffic compile is a
  latency cliff an operator must be able to see.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from can_tpu.data.batching import Batch, pad_batch
from can_tpu.obs import RecompileTracker, Telemetry
from can_tpu.obs.spans import active
from can_tpu.serve import cache as kv_cache
from can_tpu.serve.quant import compute_dtype_for, quantize_tree
from can_tpu.train.steps import batch_signature


def _batch_dict(batch: Batch) -> dict:
    return {"image": batch.image, "dmap": batch.dmap,
            "pixel_mask": batch.pixel_mask,
            "sample_mask": batch.sample_mask}


def _program_key(batch: Batch) -> str:
    return ("x".join(map(str, batch.image.shape[:3]))
            + f":{batch.image.dtype}")


def tree_signature(tree) -> tuple:
    """Structure + per-leaf (shape, dtype) of a pytree — the compiled
    predict programs' view of the params.  Two trees with equal
    signatures are interchangeable WITHOUT recompilation; a rollout to a
    differently-shaped checkpoint must be refused, not compiled mid-
    traffic."""
    leaves, treedef = jax.tree.flatten(tree)
    return (str(treedef),
            tuple((tuple(x.shape), str(jnp.asarray(x).dtype))
                  for x in leaves))


class ServeEngine:
    """Executes padded serve batches on one device.

    params / batch_stats: as returned by ``cli.test.load_params`` (host or
    device trees; moved on-device once here).
    serve_dtype: "f32" | "bf16" | "int8" — the storage/compute mode
    (serve/quant.py); "f32" is the bit-parity default.
    compute_dtype: overrides the mode's compute dtype (the legacy --bf16
    path: f32 params, bf16 compute).  None derives it from serve_dtype.
    device: pin params (and hence the compiled programs) to this device.
    quantized: params/batch_stats are ALREADY in serve_dtype storage form
    (the fleet quantizes once and replicates, instead of per replica).
    telemetry: optional bus for ``compile`` events; the engine works (and
    still counts compiles) without one.
    predict: the program, ``fn(params, batch dict, batch_stats) -> (counts,
    masked density)``; None builds CANNet's (``programs.cannet_predict``).
    """

    # How many ``predict_batch`` calls the engine can usefully hold in
    # progress at once, on as many threads (the batcher reads it and runs
    # that many launch lanes): a launch is ONE stateless program, so while
    # program n runs the runtime changes the layout of batch n+1, copies it
    # to the device and queues its program behind n.  The device runs one
    # program at a time: a third launch in flight would only add latency
    # and a third input batch.
    launches_in_flight = 2

    def __init__(self, params, batch_stats=None, *, compute_dtype=None,
                 serve_dtype: str = "f32", ds: int = 8, device=None,
                 quantized: bool = False, telemetry=None,
                 name: str = "serve_predict", aot_programs=None,
                 predict=None):
        self.ds = int(ds)
        self.serve_dtype = serve_dtype
        self.device = device
        self.name = name
        # AOT warm start (serve/aot.py): {(image shape, dtype str):
        # loaded Compiled}.  A matching batch executes the DESERIALIZED
        # binary — no trace, no compile, compile_count untouched; misses
        # fall through to the jit path and are counted like any compile.
        self._aot = dict(aot_programs) if aot_programs else {}
        self.aot_hits = 0
        self.released = False
        if not quantized:
            params = quantize_tree(params, serve_dtype)
        self.params = self._put(params)
        self.batch_stats = (None if batch_stats is None
                            else self._put(batch_stats))
        self._signature = tree_signature((self.params, self.batch_stats))
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        if compute_dtype is None:
            compute_dtype = compute_dtype_for(serve_dtype)

        if predict is None:
            from can_tpu.serve.programs import cannet_predict

            predict = cannet_predict(serve_dtype, compute_dtype)

        # RecompileTracker attributes each new (shape, dtype) signature —
        # bucket warmup and any mid-traffic compile both land as `compile`
        # events, and len(signatures) is the engine's compile count
        self._predict = RecompileTracker(jax.jit(predict), self.telemetry,
                                         name=name, batch_arg=1)
        self._signatures = self.telemetry.signature_registry[name]
        # per program ("BxHxW:dtype" of its image batch): how it carries
        # the network's first stage, "folded" or "plain", as its trace
        # noted it (the program's ``stage1_traced``, where it has one); a
        # binary loaded from an AOT bundle was traced where it was baked
        # and has no entry
        self._stage1_traced = getattr(predict, "stage1_traced", None)
        self.stage1: Dict[str, str] = {}
        # per calling thread: whether its last predict_batch compiled
        self._call = threading.local()

    def _put(self, tree):
        if self.device is None:
            return jax.device_put(tree)
        return jax.device_put(tree, self.device)

    @property
    def compile_count(self) -> int:
        """Distinct predict signatures compiled so far."""
        return len(self._signatures)

    def swap_params(self, params, batch_stats=None, *,
                    quantized: bool = False) -> None:
        """Atomically replace the served weights (the blue/green flip).

        The new trees must match the current param signature exactly —
        same structure, shapes, dtypes — so every already-compiled bucket
        program serves the new weights with ZERO recompilation.  A
        mismatch raises instead of silently queueing a mid-traffic
        compile.  The caller serialises against in-flight ``predict_batch``
        calls (the fleet holds the replica's dispatch lock)."""
        if not quantized:
            params = quantize_tree(params, self.serve_dtype)
        params = self._put(params)
        batch_stats = None if batch_stats is None else self._put(batch_stats)
        sig = tree_signature((params, batch_stats))
        if sig != self._signature:
            raise ValueError(
                "swap_params structure mismatch: the new checkpoint's "
                "param tree differs in structure/shape/dtype from the "
                "serving tree — flipping would recompile every bucket "
                "program mid-traffic; deploy it as a fresh fleet instead")
        self.params = params
        self.batch_stats = batch_stats

    def predict_batch(self, batch: Batch, *, want_density: bool = False
                      ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Run one padded batch; returns host (counts (B,), density
        (B, h, w, 1) or None).  Counts are fetched synchronously (the
        caller resolves waiting requests with them, nothing to overlap
        with); the density tensor — orders of magnitude bigger — is only
        shipped device→host when a request actually asked for it.  The
        compiled program is identical either way: only the host fetch is
        conditional, so the jit signature (and the warmup compile budget)
        doesn't fork on ``want_density``.

        With an AOT table (``aot_programs``), a batch whose exact
        (shape, dtype) was baked executes the loaded binary: no trace, no
        compile, ``last_batch_compiled`` False.  Misses fall through to
        the jit path unchanged."""
        if self.released:
            raise RuntimeError(f"engine {self.name}: buffers released "
                               f"(quarantined/retired replica) — build a "
                               f"fresh engine to serve again")
        prog = (self._aot.get((tuple(batch.image.shape),
                               str(batch.image.dtype)))
                if self._aot else None)
        tr = active(self.telemetry)
        if tr is None:
            counts, density = self._launch(prog, batch)
            return self._fetch(counts, density, want_density)
        # dispatch: the call of the program up to its return, which is
        # the enqueue (the runtime changes the batch's layout and copies
        # it to the device on threads of its own); fetch: the wait for
        # those, for the program (and, with a second call in progress on
        # another thread, for the program queued ahead of it), and the D2H
        with tr.span("serve.dispatch", aot=prog is not None) as sp:
            counts, density = self._launch(prog, batch)
            sp.attrs["compiled"] = self._call.compiled
            sp.attrs["stage1"] = self.stage1.get(_program_key(batch))
        with tr.span("serve.fetch", density=bool(want_density)):
            return self._fetch(counts, density, want_density)

    def _launch(self, prog, batch: Batch):
        if prog is not None:
            counts, density = prog(self.params, _batch_dict(batch),
                                   self.batch_stats)
            self.aot_hits += 1
            self._call.compiled = False
        else:
            counts, density = self._predict(self.params,
                                            _batch_dict(batch),
                                            self.batch_stats)
            self._call.compiled = self._predict.last_first_call
            mode = (self._stage1_traced(batch.image.shape)
                    if self._call.compiled and self._stage1_traced else None)
            if mode is not None:
                self.stage1[_program_key(batch)] = mode
        return counts, density

    @staticmethod
    def _fetch(counts, density, want_density: bool):
        # can-tpu-lint: disable=HOSTSYNC(the fetch IS the product: callers resolve waiting requests with it)
        return (np.asarray(counts),
                # can-tpu-lint: disable=HOSTSYNC(fetched only when a request asked for the density tensor)
                np.asarray(density) if want_density else None)

    def is_warm(self, batch: Batch) -> bool:
        """True when dispatching ``batch`` runs an already-built program
        — an AOT table hit or a jit signature this engine has seen.
        False means the dispatch would pay a live trace+lower+compile
        (the fleet's watchdog prices those launches with the compile
        allowance instead of the steady-state deadline)."""
        if (self._aot and (tuple(batch.image.shape),
                           str(batch.image.dtype)) in self._aot):
            return True
        return batch_signature(_batch_dict(batch)) in self._signatures

    @property
    def last_batch_compiled(self) -> bool:
        """True when the CALLING THREAD's most recent ``predict_batch`` hit
        a new signature (its wall time is compile, not steady-state — keep
        it out of latency reservoirs, exactly like the offline loops do).
        Per thread, because two calls may be in progress at once
        (``launches_in_flight``) and each caller asks about its own.  AOT
        hits are never compiles."""
        return getattr(self._call, "compiled", False)

    def release_buffers(self) -> None:
        """Drop every reference to the device-resident param/batch-stats
        trees (and the loaded AOT executables) so the device's bytes are
        freed by refcount.  Deliberately NOT ``x.delete()``: the fleet's
        batched replication can alias per-device shards across replica
        trees, and a force-delete would invalidate a sibling replica's
        params — refcount release frees exactly this replica's bytes once
        nothing else holds them.  Idempotent; a released engine refuses
        ``predict_batch`` with a typed error instead of tracing None
        params into jit."""
        self.params = None
        self.batch_stats = None
        self._aot = {}
        self.released = True
        import gc

        gc.collect()  # quarantine path, rare: make the free deterministic

    # -- AOT export (serve/aot.py bake path) ------------------------------
    def compile_program(self, batch: Batch):
        """Lower+compile the exact predict program this engine would
        dispatch for ``batch`` (the cost-ledger precedent: a second
        compile on an already-slow path, persistent-cache-deduped)."""
        from can_tpu.obs.costs import resolve_jit

        args = (self.params, _batch_dict(batch), self.batch_stats)
        return resolve_jit(self._predict, args).lower(*args).compile()

    def serialize_program(self, batch: Batch) -> Tuple[bytes, dict]:
        """One bucket program as a self-contained payload: the serialized
        executable plus its pickled arg/result treedefs (device-free —
        devices ride the executable itself, keyed by id at load).  Returns
        ``(payload, meta)`` with the program's cost facts in ``meta`` when
        the backend reports them (the bundle's contract receipt)."""
        import pickle

        from jax.experimental import serialize_executable as se

        compiled = self.compile_program(batch)
        ser, in_tree, out_tree = se.serialize(compiled)
        meta = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                if ca.get("flops"):
                    # can-tpu-lint: disable=HOSTSYNC(bake path, host floats from cost_analysis — no device value involved)
                    meta["flops"] = float(ca["flops"])
                if ca.get("bytes accessed"):
                    # can-tpu-lint: disable=HOSTSYNC(bake path, host floats from cost_analysis — no device value involved)
                    meta["bytes_accessed"] = float(ca["bytes accessed"])
        # can-tpu-lint: disable=SWALLOW(cost facts are receipts, not requirements; a non-reporting backend still bakes)
        except Exception:
            pass
        return pickle.dumps((ser, in_tree, out_tree)), meta

    def warmup(self, bucket_shapes, max_batch: int, *,
               dtypes=(np.float32,), sizes=None) -> dict:
        """Compile every (bucket shape, batch size, dtype) program before
        traffic.

        bucket_shapes: iterable of (H, W); dtypes: the image dtypes traffic
        will carry (float32, and uint8 if the front end admits raw bytes);
        sizes: the launch-size menu (can_tpu/sched) — every size the
        batcher may dispatch must be warmed here or a live request pays a
        mid-traffic compile.  None keeps the single ``max_batch`` program
        (pre-r14 behaviour).  Returns ``{"shapes": n, "compiles": new,
        "seconds": wall}``.
        """
        from can_tpu.sched import normalize_sizes

        t0 = time.perf_counter()
        before = self.compile_count
        shapes = sorted(set(map(tuple, bucket_shapes)))
        sizes = normalize_sizes(max_batch, sizes)
        for bh, bw in shapes:
            if bh % self.ds or bw % self.ds:
                raise ValueError(f"bucket shape {bh}x{bw} is not a multiple "
                                 f"of the density downsample ({self.ds})")
            for size in sizes:
                for dt in dtypes:
                    img = np.zeros((bh, bw, 3), dt)
                    dm = np.zeros((bh // self.ds, bw // self.ds, 1),
                                  np.float32)
                    batch = pad_batch([(img, dm)], (bh, bw), size,
                                      [False], self.ds)
                    self.predict_batch(batch)  # np.asarray fetch = fence
        dt_s = time.perf_counter() - t0
        report = {"shapes": len(shapes), "sizes": len(sizes),
                  "compiles": self.compile_count - before,
                  "seconds": round(dt_s, 3)}
        self.telemetry.emit("serve.warmup", **report)
        return report


# -- the language model's engine ------------------------------------------
def lm_probe_steps(steps: int) -> Tuple[int, ...]:
    """The decode steps whose logits a launch keeps for a request that
    asked for them: the first, the middle one and the last."""
    return tuple(sorted({1, max(1, steps // 2), steps}))


class LMEngine:
    """Executes token launches on one device: one flush is a prefill (in
    slices of the batch, so that its temporaries fit beside the weights)
    and then greedy decode steps over the same slots, dispatched one by one
    (a later change can let slots join and leave between steps).  The cache
    and the step's state are donated to every program and never leave the
    device; the answers are fetched once, at the end.  Whether a request
    asked for its logits changes that fetch only, never a program.

    programs: ``serve.programs.LMPrograms``.  prefill_slice: how many of a
    launch's prompts one prefill program takes.
    """

    ds = 1  # a token batch has no density grid (CountService reads it)

    # One launch is hundreds of executions of two programs over a cache the
    # engine owns: a second ``generate_batch`` in progress would interleave
    # decode steps on a device that is busy already, and hold a second cache.
    launches_in_flight = 1

    def __init__(self, params, programs, *, prefill_slice: int = 8,
                 device=None, telemetry=None, name: str = "lm"):
        self.programs = programs
        self.prefill_slice = int(prefill_slice)
        self.device = device
        self.name = name
        self.released = False
        self.params = (jax.device_put(params) if device is None
                       else jax.device_put(params, device))
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        tel = self.telemetry
        self._prefill = RecompileTracker(
            jax.jit(programs.prefill_slice, donate_argnums=(2,)), tel,
            name=f"{name}_prefill", signature_of=self._with_cache_signature)
        self._decode = RecompileTracker(
            jax.jit(programs.decode, donate_argnums=(1, 2)), tel,
            name=f"{name}_decode", signature_of=self._with_cache_signature)
        self._new_cache = jax.jit(programs.new_cache, static_argnums=(0, 1))
        self._new_state = jax.jit(programs.new_state)
        self._signatures = (tel.signature_registry[f"{name}_prefill"],
                            tel.signature_registry[f"{name}_decode"])
        self._warm: set = set()   # (slots, bucket) launched to the end once
        self._last_compiled = False
        # per prefill program ((prompts in a slice, bucket)): the form its
        # trace ran the attention in, "fused" or "scanned", where the model
        # notes one (``LMPrograms.attention_traced``)
        self.prefill_attention: Dict[tuple, str] = {}
        # per program ((prompts in a slice, bucket); (slots, 1) for decode):
        # the form its trace ran the state-space recurrence in, where the
        # model has one (``LMPrograms.ssm_traced``)
        self.ssm_forms: Dict[tuple, str] = {}
        # the same of the gated short convolution, where the model has one
        # (``LMPrograms.conv_traced``)
        self.conv_forms: Dict[tuple, str] = {}
        # the same of a retention layer (``LMPrograms.retention_traced``)
        self.retention_forms: Dict[tuple, str] = {}
        # per decode program ((slots, 1)): the form its trace read a latent
        # cache in, "fused" or "plain" (``LMPrograms.latent_traced``)
        self.latent_forms: Dict[tuple, str] = {}
        # counters (the batcher thread writes, stats() reads a copy)
        self.counters = {"launches": 0, "generated_tokens": 0,
                         "prompt_tokens": 0, "decode_steps": 0,
                         # assignments_held: tokens over all held experts;
                         # _zero: choices that were identity experts (a
                         # router with none: 0); _all: every choice a token
                         "expert_tokens_max": 0,
                         "assignments_held": 0, "assignments_all": 0,
                         "assignments_zero": 0,
                         # held experts whose weights the decode steps read
                         # (layers x steps), of those they could have
                         "decode_experts_read": 0, "decode_experts_held": 0,
                         # passes the prefill slices' expert layers took
                         # over their sorted buffers, of their calls (one a
                         # layer and slice; a form without a buffer: 0 of 0)
                         "dispatch_passes": 0, "dispatch_calls": 0,
                         "cache_bytes": {},
                         # launches by the form their prefill's attention
                         # ran in (models that note none: empty)
                         "prefill_attention": {}}
        self.last_launch: dict = {}

    @property
    def compile_count(self) -> int:
        """Distinct prefill and decode signatures compiled so far."""
        return sum(len(s) for s in self._signatures)

    @property
    def last_batch_compiled(self) -> bool:
        return self._last_compiled

    def is_warm(self, batch) -> bool:
        return tuple(batch.tokens.shape) in self._warm

    def release_buffers(self) -> None:
        self.params = None
        self.released = True
        import gc

        gc.collect()

    def _slices(self, slots: int):
        s = min(self.prefill_slice, slots)
        return [(lo, min(s, slots - lo)) for lo in range(0, slots, s)]

    def generate_batch(self, batch, *, steps: Optional[int] = None,
                       want_logits: bool = False):
        """Run one padded launch (``serve.kinds.TokenBatch``) to the end:
        -> (ids (slots, steps) int32, probes or None).  ``ids[:, 0]`` is
        prefill's token, ``ids[:, s]`` decode step ``s``'s; all ``steps``
        decode steps run (the last writes the last token's cache row and
        yields the last probe).  ``probes``: {"prefill" | "step<s>":
        {"logits" (slots, V) float32, "choices" (expert layers, slots, k)}}
        for ``lm_probe_steps(steps)``."""
        if self.released:
            raise RuntimeError(f"engine {self.name}: buffers released")
        steps = self.programs.max_new_tokens if steps is None else int(steps)
        if not 1 <= steps <= self.programs.max_new_tokens:
            raise ValueError(f"steps {steps} outside 1..{self.programs.max_new_tokens}")
        slots, bucket = batch.tokens.shape
        tr = active(self.telemetry)
        span = tr.span if tr is not None else _NoSpan
        live = batch.sample_mask > 0
        valid = int(live.sum())
        valid_tokens = int(batch.lengths[live].sum())
        slices = self._slices(slots)
        compiled = False
        # the engine's two phases under ``serve.batch`` carry the names
        # they have for every model (``ServeEngine.predict_batch``):
        # ``serve.dispatch`` from the first program's call to the last
        # one's return, ``serve.fetch`` the wait for the answers and their
        # copy back; the model's own spans nest in the first
        with span("serve.dispatch") as launch:
            with span("lm.prefill", bucket=bucket, slots=slots, valid=valid,
                      tokens=slots * bucket, valid_tokens=valid_tokens,
                      slices=len(slices)) as sp:
                cache = self._new_cache(slots, bucket)
                outs = []
                for i, (lo, n) in enumerate(slices):
                    part = {"tokens": batch.tokens[lo:lo + n],
                            "lengths": batch.lengths[lo:lo + n],
                            "active": live[lo:lo + n]}
                    with span("lm.prefill.dispatch", slice=i, start_slot=lo):
                        out, cache = self._prefill(self.params, part, cache,
                                                   np.int32(lo))
                    compiled |= self._prefill.last_first_call
                    self._note_forms(self._prefill.last_first_call,
                                     (n, bucket))
                    if tr is not None and self._prefill.last_first_call:
                        self._note_scopes(tr, launch, self._prefill,
                                          "jit_prefill_slice", (n, bucket),
                                          (self.params, part, cache,
                                           np.int32(lo)))
                    outs.append(out)
                state, pre = self._new_state(outs, batch.lengths, live)
                sp.attrs["compiled"] = compiled
                ran = [(n, bucket) for _, n in slices]
                attention = _one_form(self.prefill_attention, ran)
                if attention is not None:
                    sp.attrs["attention"] = attention
                ssm = _one_form(self.ssm_forms, ran)
                if ssm is not None:
                    sp.attrs["ssm"] = ssm
                conv = _one_form(self.conv_forms, ran)
                if conv is not None:
                    sp.attrs["conv"] = conv
                retention = _one_form(self.retention_forms, ran)
                if retention is not None:
                    sp.attrs["retention"] = retention
            probes = {"prefill": {"logits": pre["logits"],
                                  "choices": pre["choices"]}}
            keep = set(lm_probe_steps(steps))
            with span("lm.decode", steps=steps, slots=slots) as sp:
                for step in range(1, steps + 1):
                    with span("lm.decode.dispatch", decode_step=step):
                        state, cache, out = self._decode(self.params, state,
                                                         cache)
                    compiled |= self._decode.last_first_call
                    self._note_forms(self._decode.last_first_call, (slots, 1))
                    if tr is not None and self._decode.last_first_call:
                        self._note_scopes(tr, launch, self._decode,
                                          "jit_decode", (slots, bucket),
                                          (self.params, state, cache))
                    if step in keep:
                        probes[f"step{step}"] = out
                sp.attrs["compiled"] = compiled
                if (slots, 1) in self.ssm_forms:
                    sp.attrs["ssm"] = self.ssm_forms[(slots, 1)]
                if (slots, 1) in self.conv_forms:
                    sp.attrs["conv"] = self.conv_forms[(slots, 1)]
                if (slots, 1) in self.retention_forms:
                    sp.attrs["retention"] = self.retention_forms[(slots, 1)]
                if (slots, 1) in self.latent_forms:
                    sp.attrs["latent"] = self.latent_forms[(slots, 1)]
                # (expert layers, held): no rows in a model without experts
                expert_layers, held = pre["counts"].shape
                if expert_layers:
                    sp.attrs["experts"] = self.programs.decode_experts(slots)
            launch.attrs["compiled"] = compiled
        self._last_compiled = compiled
        with span("serve.fetch", logits=bool(want_logits)) as sp:
            # can-tpu-lint: disable=HOSTSYNC(the fetch IS the product: the generated ids resolve the waiting requests)
            ids = np.asarray(state["ids"])[:, :steps]
            # can-tpu-lint: disable=HOSTSYNC(the launch's routing counters, reduced on the device, fetched with the answers)
            pre_counts, dec_counts = np.asarray(pre["counts"]), np.asarray(state["counts"])
            # held experts whose weights the decode steps read, of those
            # they could have: a form that does not count read them all
            experts_held = expert_layers * held * steps
            # can-tpu-lint: disable=HOSTSYNC(one more counter of the launch, fetched with the others)
            experts_read = (int(np.asarray(state["experts_read"]))
                            if "experts_read" in state else experts_held)
            if expert_layers:
                sp.attrs["experts_read"] = experts_read
                sp.attrs["experts_held"] = experts_held
            # the choices that were identity experts, where the router has any
            zero = 0
            if "zero" in pre:
                # can-tpu-lint: disable=HOSTSYNC(one more counter of the launch, fetched with the others)
                zero = int(np.asarray(pre["zero"] + state["zero"]).sum())
            # the prefill's passes are known only now: ``lm.prefill`` closed
            # when its slices were queued, before the device ran them
            passes = calls = 0
            if "dispatch_passes" in pre:
                # can-tpu-lint: disable=HOSTSYNC(one more counter of the launch, fetched with the others)
                passes = int(np.asarray(pre["dispatch_passes"]))
                calls = expert_layers * len(slices)
                sp.attrs["dispatch_passes"] = passes
                sp.attrs["dispatch_calls"] = calls
            fetched = None
            if want_logits:
                # can-tpu-lint: disable=HOSTSYNC(fetched only when a request asked for its logits)
                fetched = jax.tree.map(np.asarray, probes)
        self._warm.add((slots, bucket))
        k = pre["choices"].shape[-1]   # (expert layers, slots, k)
        self._count(cache, valid, valid_tokens, steps, pre_counts, dec_counts,
                    attention, expert_layers * k, experts_read, experts_held,
                    passes, calls, zero)
        return ids, fetched

    def _note_forms(self, traced: bool, program: tuple) -> None:
        """After a program's first launch: the forms its trace noted (a
        program compiled elsewhere has no entry)."""
        if not traced:
            return
        for noted, forms in ((self.programs.attention_traced,
                              self.prefill_attention),
                             (self.programs.ssm_traced, self.ssm_forms),
                             (self.programs.conv_traced, self.conv_forms),
                             (self.programs.retention_traced,
                              self.retention_forms),
                             (self.programs.latent_traced,
                              self.latent_forms)):
            form = noted(program) if noted is not None else None
            if form is not None:
                forms[program] = form

    def _note_scopes(self, tr, launch, program, name: str, key: tuple,
                     args) -> None:
        """After a program's first launch WITH A TRACER ACTIVE: one
        ``program.scopes`` span under the launch's ``serve.dispatch`` that
        says which part of the model (``LMPrograms.parts``) each instruction
        of the compiled program belongs to (``obs.trace.program_scopes``),
        so that a device trace's op events can be summed by part.  The
        compiled text is read from a second ``lower().compile()`` of the
        same signature (``ServeEngine.compile_program``'s precedent: a hit
        in the persistent cache); ``args`` are the launch's own or, where it
        donated them, what it returned in their place (the same shapes);
        ``args[2]`` is the cache in both programs, and ``cache_copies`` says
        how many times the program copies whole one of its arrays that have
        positions (``obs.trace.cache_copies``: none where a position is
        written in place), ``state_copies`` the same of its arrays WITHOUT
        positions, which a step rewrites whole where they lie (a model
        without a ``state`` kind: 0).
        ``name``: the program as the trace's ``XLA Modules`` line names it."""
        from can_tpu.obs.costs import resolve_jit
        from can_tpu.obs.trace import cache_copies, program_scopes
        from can_tpu.ops.cache_layout import positioned_leaves, state_leaves

        with tr.span("program.scopes", parent_id=launch.span_id,
                     program=name, key=list(key)) as sp:
            text = resolve_jit(program, args).lower(*args).compile().as_text()
            sp.attrs.update(program_scopes(text, self.programs.parts))
            sp.attrs["cache_copies"] = cache_copies(text, positioned_leaves(
                self.programs.cache_layout, args[2]))
            sp.attrs["state_copies"] = cache_copies(text, state_leaves(
                self.programs.cache_layout, args[2]))

    def _with_cache_signature(self, args) -> tuple:
        """(params, dict of arrays, cache, ...) -> the signature of the dict
        AND of the cache: the launch size and the bucket both choose the
        program."""
        flat = dict(args[1])
        for i, leaf in enumerate(kv_cache.signature_leaves(
                args[2], self.programs.cache_layout)):
            flat[f"cache{i}"] = leaf
        return batch_signature(flat)

    def _count(self, cache, valid, valid_tokens, steps, pre_counts,
               dec_counts, attention, choices_per_token: int,
               experts_read: int, experts_held: int, passes: int,
               calls: int, zero: int) -> None:
        """``choices_per_token``: routing choices a token makes over all the
        expert layers (0 for a model without one: every expert counter then
        reads zero); ``zero``: those of the launch's valid tokens that were
        identity experts."""
        p = self.programs
        held = int(pre_counts.sum() + dec_counts.sum())
        every = (valid_tokens + valid * steps) * choices_per_token
        launch = {"valid": valid, "steps": steps,
                  "prefill_expert_tokens": pre_counts.tolist(),
                  "decode_expert_tokens": dec_counts.tolist(),
                  "assignments_held": held, "assignments_all": every,
                  "assignments_zero": zero}
        c = self.counters
        c["launches"] += 1
        c["generated_tokens"] += valid * steps
        c["prompt_tokens"] += valid_tokens
        c["decode_steps"] += steps
        c["expert_tokens_max"] = max(c["expert_tokens_max"],
                                     int((pre_counts + dec_counts).max(initial=0)))
        c["assignments_held"] += held
        c["assignments_all"] += every
        c["assignments_zero"] += zero
        c["decode_experts_read"] += experts_read
        c["decode_experts_held"] += experts_held
        c["dispatch_passes"] += passes
        c["dispatch_calls"] += calls
        c["cache_bytes"] = kv_cache.nbytes_by_kind(cache, p.cache_layout)
        if attention is not None:
            by_form = c["prefill_attention"]
            # a new dict: ``warmup`` restores a shallow copy of the counters
            c["prefill_attention"] = {**by_form,
                                      attention: by_form.get(attention, 0) + 1}
        self.last_launch = launch

    def warmup(self, buckets, max_batch: int, *, sizes=None) -> dict:
        """Compile every (bucket, menu size) launch before traffic: one
        launch of one decode step each, through ``generate_batch`` itself,
        so that every program and every small op of the path is built."""
        from can_tpu.sched import normalize_sizes
        from can_tpu.serve.kinds import TokenBatch

        t0 = time.perf_counter()
        before = self.compile_count
        buckets = sorted(set(int(b) for b in buckets))
        sizes = normalize_sizes(max_batch, sizes)
        counters = dict(self.counters)
        for bucket in buckets:
            for size in sizes:
                batch = TokenBatch(np.zeros((size, bucket), np.int32),
                                   np.ones((size,), np.int32),
                                   np.zeros((size,), np.float32))
                self.generate_batch(batch, steps=1, want_logits=True)
        self.counters = counters   # warm-up launches are not traffic
        report = {"shapes": len(buckets), "sizes": len(sizes),
                  "compiles": self.compile_count - before,
                  "seconds": round(time.perf_counter() - t0, 3)}
        self.telemetry.emit("serve.warmup", **report)
        return report


def _one_form(forms: dict, programs) -> Optional[str]:
    """The one form the launch's programs ran in; "mixed" where they
    differ, None where none noted one."""
    found = {forms.get(p) for p in programs}
    return found.pop() if len(found) == 1 else "mixed"


class _NoSpan:
    """``tracer.span(...)`` with tracing off: takes the attributes, keeps
    nothing."""

    def __init__(self, name, **attrs):
        self.attrs = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
