"""AOT predict-program bundles: load executables instead of compiling.

A replica's warmup bill is buckets x dtypes live trace+lower+compile
passes — seconds on CPU, MINUTES on a real chip.  That bill is fine once
at fleet startup; it is exactly wrong for the self-healing paths, where a
resurrected or scaled-up replica must reach ready in seconds while the
queue is deepening (ROADMAP item 2).  This module serializes the compiled
predict executables themselves (``jax.experimental.serialize_executable``,
the compiled-binary layer UNDER the persistent compilation cache) into a
bundle directory written beside the checkpoint at warmup time, so a new
replica's warmup becomes deserialise-and-load: zero new compiles, pinned
via the engine's ``compile_count``.

Bundle layout::

    <dir>/
        prog_d<device_id>_<B>x<H>x<W>x<C>_<dtype>.bin   one per program
        aot_manifest.json                               written LAST

Manifest-last is the prepared-store rule (DESIGN §9): a bake torn by a
crash leaves no manifest and reads as ABSENT, never as a half-bundle.

Compiled executables bake their device assignment in, so the bundle keys
programs by ``device_id`` and a bake covers an explicit device list — the
fleet bakes its whole autoscale range, not just the replicas currently
serving (a scale-up lands on a device that was idle at bake time).

Staleness is checked, never assumed (``AotBundle.check``): an executable
is only valid for the exact param-tree signature (structure, shapes,
dtypes — a rollout to a same-signature checkpoint keeps the bundle valid,
because params are jit ARGUMENTS), serve dtype, density grid, batch
geometry, platform/device kind, and jax version it was compiled under.
Any mismatch raises ``AotStaleError`` naming the axis; callers degrade to
live compiles (visible in ``compile_count``) or refuse, but never run a
stale program.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Dict, List, Optional, Sequence, Tuple

AOT_VERSION = 1
MANIFEST_NAME = "aot_manifest.json"


class AotStaleError(RuntimeError):
    """The bundle does not match the world trying to load it; ``axis``
    names the mismatched invariant (signature, serve_dtype, ...)."""

    def __init__(self, axis: str, detail: str = ""):
        super().__init__(f"AOT bundle stale on {axis}"
                         + (f": {detail}" if detail else ""))
        self.axis = axis


def signature_sha(params, batch_stats=None) -> str:
    """Stable digest of the param tree's compiled-program view (the
    ``tree_signature`` structure+shape+dtype tuple): host and device
    copies of the same tree hash identically, so a bake from committed
    replica params and a load from the checkpoint's host tree agree."""
    from can_tpu.serve.engine import tree_signature

    sig = tree_signature((params, batch_stats))
    return hashlib.sha256(repr(sig).encode()).hexdigest()[:16]


def _program_filename(device_id: int, shape: Tuple[int, ...],
                      dtype: str) -> str:
    dims = "x".join(str(int(d)) for d in shape)
    return f"prog_d{device_id}_{dims}_{dtype}.bin"


def bake_aot_bundle(out_dir: str, *, engines: Sequence, bucket_shapes,
                    max_batch: int, dtypes, ds: int, serve_dtype: str,
                    sig_sha: str, generation: int = 0,
                    telemetry=None, batch_sizes=None) -> dict:
    """Serialize every (bucket, size, dtype) predict executable of every
    engine.

    ``engines``: ``ServeEngine``s, one per target device (their committed
    params pin the compiled device assignment).  ``batch_sizes`` is the
    scheduling core's launch-size menu (None = just ``max_batch``) — the
    menu RIDES the bake axes, so a loaded bundle covers every size the
    batcher may dispatch and a menu change invalidates the bundle
    instead of hiding live compiles.  Each program is lower+compiled
    fresh (``ServeEngine.compile_program`` — the cost-ledger precedent:
    a second compile on the already-slow bake path, deduped by the
    persistent compilation cache where armed) and serialized with its
    arg trees.  Returns the manifest."""
    import jax
    import numpy as np

    from can_tpu.data.batching import pad_batch

    from can_tpu.sched import normalize_sizes

    os.makedirs(out_dir, exist_ok=True)
    shapes = sorted(set(map(tuple, bucket_shapes)))
    sizes = normalize_sizes(max_batch, batch_sizes)
    programs: List[dict] = []
    t0 = time.perf_counter()
    platform = device_kind = None
    for engine in engines:
        dev = engine.device if engine.device is not None else jax.devices()[0]
        platform = dev.platform
        device_kind = dev.device_kind
        for bh, bw in shapes:
            for size in sizes:
                for dt in dtypes:
                    img = np.zeros((bh, bw, 3), dt)
                    dm = np.zeros((bh // ds, bw // ds, 1), np.float32)
                    batch = pad_batch([(img, dm)], (bh, bw), size,
                                      [False], ds)
                    payload, meta = engine.serialize_program(batch)
                    fname = _program_filename(dev.id, batch.image.shape,
                                              str(batch.image.dtype))
                    with open(os.path.join(out_dir, fname), "wb") as f:
                        f.write(payload)
                    programs.append({"device_id": int(dev.id),
                                     "shape": [int(d)
                                               for d in batch.image.shape],
                                     "dtype": str(batch.image.dtype),
                                     "file": fname,
                                     "bytes": len(payload), **meta})
    manifest = {
        "version": AOT_VERSION,
        "jax_version": jax.__version__,
        "platform": platform,
        "device_kind": device_kind,
        "serve_dtype": serve_dtype,
        "ds": int(ds),
        "max_batch": int(max_batch),
        "batch_sizes": [int(s) for s in sizes],
        "bucket_shapes": [list(s) for s in shapes],
        "image_dtypes": sorted(str(np.dtype(dt)) for dt in dtypes),
        "signature_sha": sig_sha,
        "generation": int(generation),
        "created_ts": time.time(),
        "bake_seconds": round(time.perf_counter() - t0, 3),
        "programs": programs,
    }
    # manifest LAST: a torn bake must read as absent, not as a half-bundle
    tmp = os.path.join(out_dir, MANIFEST_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, os.path.join(out_dir, MANIFEST_NAME))
    if telemetry is not None:
        telemetry.emit("serve.warmup", phase="aot_bake", path=out_dir,
                       programs=len(programs),
                       devices=len(set(p["device_id"] for p in programs)),
                       seconds=manifest["bake_seconds"])
    return manifest


class AotBundle:
    """A loaded (or loadable) bundle: manifest + lazily deserialized
    per-device program tables."""

    def __init__(self, path: str, manifest: dict):
        self.path = path
        self.manifest = manifest
        self._loaded: Dict[int, dict] = {}

    @classmethod
    def open(cls, path: str) -> "AotBundle":
        """Open a bundle directory; absent/torn (no manifest) or
        wrong-version bundles raise ``AotStaleError`` — never a silent
        pass."""
        mpath = os.path.join(path, MANIFEST_NAME)
        if not os.path.isfile(mpath):
            raise AotStaleError("manifest",
                                f"no {MANIFEST_NAME} in {path} (absent or "
                                f"torn bake)")
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (json.JSONDecodeError, OSError) as e:
            raise AotStaleError("manifest", f"unreadable: {e}") from e
        if manifest.get("version") != AOT_VERSION:
            raise AotStaleError(
                "version", f"bundle v{manifest.get('version')} != "
                           f"loader v{AOT_VERSION}")
        return cls(path, manifest)

    def check(self, *, sig_sha: str, serve_dtype: str, ds: int,
              max_batch: Optional[int] = None,
              bucket_shapes=None, batch_sizes=None) -> None:
        """Raise ``AotStaleError`` unless the bundle matches the loading
        world on every axis an executable bakes in."""
        import jax

        m = self.manifest
        if m.get("jax_version") != jax.__version__:
            raise AotStaleError("jax_version",
                                f"baked under {m.get('jax_version')}, "
                                f"running {jax.__version__}")
        dev = jax.devices()[0]
        if m.get("platform") != dev.platform:
            raise AotStaleError("platform", f"baked for {m.get('platform')}"
                                            f", running {dev.platform}")
        if m.get("device_kind") != dev.device_kind:
            raise AotStaleError("device_kind",
                                f"baked for {m.get('device_kind')!r}, "
                                f"running {dev.device_kind!r}")
        if m.get("serve_dtype") != serve_dtype:
            raise AotStaleError("serve_dtype",
                                f"baked {m.get('serve_dtype')}, "
                                f"serving {serve_dtype}")
        if int(m.get("ds", -1)) != int(ds):
            raise AotStaleError("ds", f"baked /{m.get('ds')}, "
                                      f"serving /{ds}")
        if m.get("signature_sha") != sig_sha:
            raise AotStaleError(
                "signature",
                "the serving param tree differs in structure/shape/dtype "
                "from the baked one (different checkpoint variant?) — "
                "re-bake with --aot-bake")
        if max_batch is not None and int(m.get("max_batch", -1)) != \
                int(max_batch):
            raise AotStaleError("max_batch",
                                f"baked at {m.get('max_batch')}, "
                                f"serving at {max_batch}")
        if bucket_shapes is not None:
            baked = {tuple(s) for s in m.get("bucket_shapes", ())}
            want = set(map(tuple, bucket_shapes))
            missing = sorted(want - baked)
            if missing:
                raise AotStaleError("bucket_shapes",
                                    f"grid {missing} not in the bundle")
        if batch_sizes is not None:
            # the menu is a bake axis: a size the bundle never baked
            # would compile live on every recovery/scale path — exactly
            # what the bundle exists to prevent (pre-menu bundles baked
            # only max_batch and read as {max_batch})
            baked_sizes = {int(s) for s in
                           m.get("batch_sizes", (m.get("max_batch"),))
                           if s is not None}
            missing_sizes = sorted({int(s) for s in batch_sizes}
                                   - baked_sizes)
            if missing_sizes:
                raise AotStaleError(
                    "batch_sizes",
                    f"menu sizes {missing_sizes} not in the bundle "
                    f"(baked {sorted(baked_sizes)}) — the sub-batch menu "
                    f"changed since the bake; re-bake with --aot-bake")

    def device_ids(self) -> set:
        return {int(p["device_id"]) for p in self.manifest["programs"]}

    def programs_for(self, device) -> dict:
        """``{(image_shape, dtype_str): Compiled}`` for one device —
        empty when the bundle has no coverage for it (the caller falls
        back to live compiles, which stay visible in compile_count)."""
        did = int(device.id)
        cached = self._loaded.get(did)
        if cached is not None:
            return cached
        from jax.experimental import serialize_executable as se

        table: dict = {}
        for p in self.manifest["programs"]:
            if int(p["device_id"]) != did:
                continue
            with open(os.path.join(self.path, p["file"]), "rb") as f:
                ser, in_tree, out_tree = pickle.loads(f.read())
            # execution_devices: the one device the program was compiled
            # for.  Left out, this JAX loads the executable for EVERY
            # device of the backend and then refuses the call ("expected
            # args ... to have 8 shards" on a host with 8 devices)
            table[(tuple(p["shape"]), str(p["dtype"]))] = \
                se.deserialize_and_load(ser, in_tree, out_tree,
                                        execution_devices=[device])
        self._loaded[did] = table
        return table


def load_aot_bundle(path: str) -> AotBundle:
    return AotBundle.open(path)
