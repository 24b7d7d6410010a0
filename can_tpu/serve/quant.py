"""Quantized predict-program parameter storage for serving.

Serving is forward-only, so the training-grade f32 parameter tree is pure
cost: ~74 MB of HBM reads per predict launch that carry 4x (int8) or 2x
(bf16) more bytes than the arithmetic needs.  This module converts a
trained f32 tree into the storage format each ``--serve-dtype`` mode keeps
device-resident, and provides the in-program dequantization the engine's
jitted predict runs before ``cannet_apply``:

* ``f32``  — identity.  The bit-for-bit offline/online parity mode.
* ``bf16`` — every float leaf stored bf16, compute in bf16 (MXU rate),
  f32 accumulation per the TPU conv contract (ops/conv.py).  Counts move
  ~1e-3 relative vs f32.
* ``int8`` — post-training weight-only quantization: conv kernels and the
  context 1x1 matrices stored as int8 with PER-OUTPUT-CHANNEL f32 scales
  (symmetric, scale = max|w| over the input axes / 127 — per-channel
  because conv channels in this model span ~100x dynamic range, and one
  per-tensor scale would crush the quiet channels to zero).  Biases, BN
  affine/stats, and the final 1-channel output conv stay f32 (the output
  conv is 65 weights whose quantization error lands directly on the count;
  keeping it f32 is free).  Dequantization (``w_i8 * scale``) happens
  INSIDE the jitted predict, so HBM holds int8 and the f32 weights exist
  only as fused temporaries; all arithmetic then runs in f32 — "int8
  storage, f32 accumulation", the numerically conservative PTQ point.

Every mode keeps the same pytree STRUCTURE contract at the engine seam:
``quantize_tree`` returns a tree ``dequantize_tree`` restores to the exact
shapes/dtypes ``cannet_apply`` expects, so one predict body serves all
three modes and the jit signature differs only via the stored leaves.

The parity cost of each mode is measured, not assumed: ``parity_report``
runs the same images through a quantized engine and the f32 reference and
grades the worst count delta against ``PARITY_LADDER`` (tests/test_fleet.py
holds every mode to its rung).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

SERVE_DTYPES = ("f32", "bf16", "int8")

# Marker key pair of a quantized leaf: {"q": int8 (..., Cout), "scale":
# f32 (Cout,)}.  A dict is a quantized leaf iff its keys are exactly these.
_QKEYS = frozenset({"q", "scale"})

# The count-delta tolerance ladder parity_report grades against: worst
# relative count delta vs f32 <= bound -> that rung.  Rungs are ordered
# strictest first; "fail" means the mode moved counts more than any rung
# allows and must not ship.  Bounds chosen from the numerics, not wishes:
# bf16 weight rounding is ~2^-8 relative and the count is a large masked
# sum (errors partially cancel), int8 per-channel is ~2^-7 with the same
# cancellation, so each mode should land comfortably inside its rung and
# a regression (e.g. per-tensor scales sneaking in) trips the grade.
PARITY_LADDER = (
    ("exact", 0.0),
    ("tight", 1e-3),
    ("serve", 2e-2),
    ("loose", 1e-1),
)


def is_quantized_leaf(node) -> bool:
    return isinstance(node, dict) and frozenset(node.keys()) == _QKEYS


def quantize_int8(w) -> dict:
    """Symmetric per-output-channel int8: the last axis is Cout (HWIO
    kernels and (Cin, Cout) context matrices both put channels last).
    scale = max|w| over all input axes / 127; all-zero channels get
    scale 1 (q is zero anyway, and 0-scales would NaN the dequant)."""
    w = np.asarray(w, np.float32)
    red = tuple(range(w.ndim - 1))
    amax = np.max(np.abs(w), axis=red)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return {"q": q, "scale": scale}


def dequantize_int8(leaf, dtype=jnp.float32):
    return (leaf["q"].astype(dtype) * leaf["scale"].astype(dtype))


def _is_output_conv(path) -> bool:
    # the 1x1 output conv's 65 weights stay f32: its error lands directly
    # on the density map with nothing downstream to absorb it
    return len(path) > 0 and path[0] == "output"


def quantize_tree(params, serve_dtype: str):
    """f32 params tree -> the storage tree for ``serve_dtype``.

    f32: identity.  bf16: float leaves astype(bf16).  int8: weight
    tensors (ndim >= 2) quantized per-output-channel except the output
    conv; 1-D leaves (biases, BN affine) stay f32.
    """
    if serve_dtype not in SERVE_DTYPES:
        raise ValueError(f"serve_dtype must be one of {SERVE_DTYPES}, "
                         f"got {serve_dtype!r}")
    if serve_dtype == "f32":
        return params
    if serve_dtype == "bf16":
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            params)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return [walk(v, path + (i,)) for i, v in enumerate(node)]
        arr = np.asarray(node)
        if arr.ndim >= 2 and not _is_output_conv(path):
            return quantize_int8(arr)
        return jnp.asarray(arr, jnp.float32)

    return walk(params, ())


def dequantize_tree(qtree, serve_dtype: str):
    """Storage tree -> the f32/bf16 tree ``cannet_apply`` consumes.  Runs
    INSIDE the jitted predict: for int8 the multiply is fused with the
    consumer and HBM only ever holds the int8 bytes."""
    if serve_dtype in ("f32", "bf16"):
        return qtree

    def walk(node):
        if is_quantized_leaf(node):
            return dequantize_int8(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return [walk(v) for v in node]
        return node

    return walk(qtree)


def compute_dtype_for(serve_dtype: str):
    """The ``cannet_apply`` compute dtype per mode: bf16 runs activations
    at MXU rate; f32 and int8 (dequantized to f32) keep f32 end-to-end —
    int8's accumulation is f32 by construction."""
    return jnp.bfloat16 if serve_dtype == "bf16" else None


def host_tree(tree):
    """Device arrays -> host copies, structure/shapes/dtypes preserved
    (int8 leaf dicts and bf16 leaves included, so ``tree_signature`` of
    the host copy equals the device tree's).  The fleet keeps the CURRENT
    generation's quantized tree host-side: resurrection and scale-up can
    stage params onto ANY device from it, without pinning a replicated
    copy in every device's HBM for the life of the process."""
    return jax.device_get(tree)


def param_bytes(tree) -> int:
    """Device-resident parameter bytes of a storage tree (the HBM the
    mode actually holds)."""
    return sum(int(np.prod(x.shape)) * jnp.asarray(x).dtype.itemsize
               for x in jax.tree.leaves(tree))


def grade_parity(worst_rel: float) -> str:
    for name, bound in PARITY_LADDER:
        if worst_rel <= bound:
            return name
    return "fail"


def parity_report(engine_q, engine_ref, images: Sequence[np.ndarray], *,
                  max_batch: int = 1, ds: int = 8,
                  ladder=PARITY_LADDER) -> dict:
    """Run ``images`` (prepared HWC arrays) through both engines one item
    per batch; grade the worst relative count delta on ``ladder``.

    Relative to max(|ref count|, 1): crowd counts are naturally large, and
    a near-zero reference count would otherwise explode the ratio for an
    absolutely-tiny delta.
    """
    from can_tpu.data.batching import pad_batch

    deltas = []
    for img in images:
        h, w = img.shape[:2]
        dm = np.zeros((h // ds, w // ds, 1), np.float32)
        batch = pad_batch([(img, dm)], (h, w), max_batch, [True], ds)
        cq, _ = engine_q.predict_batch(batch)
        cr, _ = engine_ref.predict_batch(batch)
        ref = float(cr[0])
        deltas.append(abs(float(cq[0]) - ref) / max(abs(ref), 1.0))
    worst = max(deltas) if deltas else 0.0
    return {
        "images": len(deltas),
        "worst_rel_count_delta": round(worst, 8),
        "mean_rel_count_delta": round(float(np.mean(deltas)), 8)
        if deltas else 0.0,
        "ladder": [{"rung": n, "bound": b} for n, b in ladder],
        "grade": grade_parity(worst),
    }
