"""BatchNorm batch-moment implementations: the SyncBN hot path, selectable.

The train-mode moments of every BN layer (13 in the VGG frontend+backend of
the ``--syncBN`` model) are the per-layer reduction ``(B, h, w, C) -> (C,)``
— and how that reduction is *shaped* decides the syncBN tax (72.4 img/s vs
94.5 plain-BN on v5e, ROADMAP item 2):

* ``twopass`` — the original formulation (models/cannet.py pre-r10):
  masked mean first (``sum(y*m)``/``sum(m)``), THEN the centered second
  moment ``sum((y-mean)^2 * m)``.  Numerically the most forgiving (the
  square is of centered values), but the feature map streams through HBM
  twice per layer, and under shard_map axes each pass carries its own
  ``psum`` — two collective rounds per BN layer.  Kept BIT-COMPATIBLE as
  the A/B reference (it is the default, mirroring ``plan_mode="legacy"``).
* ``onepass`` — per-channel ``(sum, sumsq, count)`` in f32 accumulators
  from ONE read of the feature map, all three packed into ONE ``(2C+1,)``
  collective, variance as ``E[x^2] - mean^2`` (clamped at 0: the
  subtraction can go negative by rounding).  Halves the activation reads
  and the collective rounds of the moments path.
* ``pallas`` — the same one-pass contract with the local reduction done by
  the TPU kernel in ``ops/pallas_bn.py`` (mask-multiply fused into the
  moment accumulation, tiles resident in VMEM); the packing/psum stays
  out here.  Layers whose shape the compiled kernel cannot tile (C not a
  multiple of 128 — the C=64 stem — or W not a multiple of 8) take the
  jnp onepass twin; ``masked_moments_pallas(routed=[...])`` tallies each
  such decision at trace time, which is how the step builder can SAY how
  many layers took which (``cli.common.bn_kernel_routing``).

The f32 accumulator dtype is pinned across every implementation: callers
hand in ``yf = y.astype(float32)`` and masks are f32, so bf16 compute
changes only the values entering the reduction, never the accumulation.

Selection rides ``LocalOps.bn_ops`` (models/cannet.py) and ``--bn-impl``
on the train CLI.
``None``/default keeps the twopass math bit-for-bit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

BN_IMPLS = ("twopass", "onepass", "pallas")


def _psum(x, axes):
    return jax.lax.psum(x, axes) if axes else x


# -- masked moments: (yf, m f32, axes) -> (mean, biased var, global s0) ---
def masked_moments_twopass(yf, m, axes) -> Tuple:
    """The original two-pass weighted moments (bit-compatible with the
    pre-r10 inline code in models/cannet.py::_batch_norm): mean from the
    first pass over ``yf``, centered second moment from a second pass,
    each with its own psum round over ``axes``."""
    s0 = jnp.sum(m)
    s1 = jnp.sum(yf * m, axis=(0, 1, 2))
    if axes:
        s0 = jax.lax.psum(s0, axes)
        s1 = jax.lax.psum(s1, axes)
    den = jnp.maximum(s0, 1.0)
    mean = s1 / den
    ss = jnp.sum(jnp.square(yf - mean) * m, axis=(0, 1, 2))
    if axes:
        ss = jax.lax.psum(ss, axes)
    var = ss / den
    return mean, var, s0


def masked_moment_sums(yf, m) -> Tuple:
    """The LOCAL one-pass reduction: per-channel ``(sum, sumsq)`` plus the
    valid-pixel count, one read of ``yf``.  The jnp twin of the Pallas
    kernel (ops/pallas_bn.py) — also its VJP reference."""
    s1 = jnp.sum(yf * m, axis=(0, 1, 2))
    s2 = jnp.sum(jnp.square(yf) * m, axis=(0, 1, 2))
    s0 = jnp.sum(m)
    return s1, s2, s0


def _finish_onepass(s1, s2, s0, axes):
    """Pack the three accumulators into ONE collective, then close the
    moments: the batched-collective half of the one-pass contract (a
    twopass layer pays two psum rounds; this pays one, of 2C+1 lanes)."""
    c = s1.shape[-1]
    packed = jnp.concatenate([s1, s2, jnp.reshape(s0, (1,))])
    packed = _psum(packed, axes)
    s1, s2, s0 = packed[:c], packed[c:2 * c], packed[2 * c]
    den = jnp.maximum(s0, 1.0)
    mean = s1 / den
    # E[x^2] - mean^2 in f32: can round a hair negative on near-constant
    # channels; rsqrt(var+eps) downstream needs the clamp
    var = jnp.maximum(s2 / den - jnp.square(mean), 0.0)
    return mean, var, s0


def masked_moments_onepass(yf, m, axes) -> Tuple:
    return _finish_onepass(*masked_moment_sums(yf, m), axes)


def masked_moments_pallas(yf, m, axes, *, interpret: bool = False,
                          routed: Optional[list] = None) -> Tuple:
    """``routed`` (a list, appended to at TRACE time): one
    ``(took_kernel, yf.shape)`` per call — the shape gate made visible."""
    from can_tpu.ops import pallas_bn

    took_kernel = pallas_bn.supports(yf.shape, interpret=interpret)
    if routed is not None:
        routed.append((took_kernel, tuple(yf.shape)))
    if not took_kernel:
        return masked_moments_onepass(yf, m, axes)
    s1, s2, s0 = pallas_bn.moment_sums(yf, m, interpret=interpret)
    return _finish_onepass(s1, s2, s0, axes)


# -- unmasked cross-shard moments: (yf, axes) -> (mean, biased var) -------
def global_moments_twopass(yf, axes) -> Tuple:
    """Two-pass global moments over the mesh (pre-r10 inline code): mean
    first, then the centered second moment (stabler than E[x^2]-E[x]^2),
    one pmean round each."""
    mean = jax.lax.pmean(jnp.mean(yf, axis=(0, 1, 2)), axes)
    var = jax.lax.pmean(
        jnp.mean(jnp.square(yf - mean), axis=(0, 1, 2)), axes)
    return mean, var


def global_moments_onepass(yf, axes) -> Tuple:
    """One read, one pmean of the packed ``(E[x], E[x^2])`` pair (the
    local count is static and equal across shards, so pmean of local
    means IS the global mean — no count lane needed)."""
    c = yf.shape[-1]
    packed = jnp.concatenate([jnp.mean(yf, axis=(0, 1, 2)),
                              jnp.mean(jnp.square(yf), axis=(0, 1, 2))])
    packed = jax.lax.pmean(packed, axes)
    mean = packed[:c]
    var = jnp.maximum(packed[c:] - jnp.square(mean), 0.0)
    return mean, var


@dataclasses.dataclass(frozen=True)
class BNOps:
    """The BN-moments seam on ``LocalOps``.

    ``masked_moments(yf, m, axes) -> (mean, biased_var, global_s0)`` and
    ``global_moments(yf, axes) -> (mean, biased_var)`` — both f32 in/out.
    ``impl`` is the CLI-facing name; ``interpret`` runs the Pallas kernel
    in interpreter mode (CPU tests).
    """

    impl: str = "twopass"
    interpret: bool = False
    masked_moments: Callable = masked_moments_twopass
    global_moments: Callable = global_moments_twopass


def make_bn_ops(impl: Optional[str], *, interpret: bool = False
                ) -> Optional[BNOps]:
    """``--bn-impl`` value -> BNOps (None/'twopass' -> None: the model's
    built-in default path stays bit-identical when no override rides in)."""
    if impl in (None, "twopass"):
        return None
    if impl == "onepass":
        return BNOps(impl="onepass",
                     masked_moments=masked_moments_onepass,
                     global_moments=global_moments_onepass)
    if impl == "pallas":
        import functools

        return BNOps(impl="pallas", interpret=interpret,
                     masked_moments=functools.partial(
                         masked_moments_pallas, interpret=interpret),
                     # the unmasked cross-shard path has no mask multiply
                     # to fuse — the jnp onepass is already a single read
                     global_moments=global_moments_onepass)
    raise ValueError(f"unknown bn impl {impl!r} (one of {BN_IMPLS})")
