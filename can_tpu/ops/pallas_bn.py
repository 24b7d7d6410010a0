"""Pallas TPU kernel: fused masked BN moment sums (one HBM pass).

The masked SyncBN moments path (models/cannet.py::_batch_norm with a
``mask``) is pure HBM traffic: per BN layer the (B, h, w, C) activation is
read, multiplied by the validity mask, and reduced to per-channel sums.
The stock two-pass lowering reads the activation twice (mean pass +
centered-variance pass); the jnp one-pass (ops/bn_moments.py) already
halves that, and this kernel is the remaining step — mask-multiply and
BOTH moment accumulations fused over VMEM-resident tiles, so each
activation element is read from HBM exactly once and never rewritten:

    for each (b, row-tile, col-tile):   ym = y * m          (VPU)
        s1 += sum(ym);  s2 += sum(ym * y);  s0 += sum(m)    (VPU adds)

Outputs the LOCAL ``(s1 (C,), s2 (C,), s0)`` in f32 — the packing into
one cross-shard collective stays in ops/bn_moments.py, so the kernel
composes with shard_map mesh axes unchanged (the shard_map body is
per-device; pallas_call runs on each device's local block).

Normalize-scale-shift(+ReLU) is deliberately NOT in the kernel: it is a
per-element affine of the SAME activation the next conv consumes, and XLA
already fuses that chain into the consumer (verified per-program via the
PR-6 cost ledger: bytes do not move when the affine is pulled in by
hand).  Gradients come from a custom VJP that re-differentiates the
jnp twin (``masked_moment_sums``) — the residuals
are just the kernel inputs, no extra HBM.

Constraints (else callers fall back to the jnp one-pass): C a multiple of
128 lanes (the C=128+ frontend/backend layers; the C=64 stem layers fall
back), W a multiple of 8.  ``interpret=True`` runs anywhere (CPU
parity tests).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 8
MAX_COL_TILE = 128


def supports(y_shape, *, interpret: bool = False) -> bool:
    if len(y_shape) != 4:
        return False
    if interpret:
        return True
    _, h, w, c = y_shape
    return c % 128 == 0 and w % 8 == 0


def _pick_col_tile(w: int, max_tw: int) -> int:
    """Largest multiple-of-8 divisor of w that is <= max_tw (VMEM: a
    (ROW_TILE, tw, C) f32 y-tile at C=512 is 2 MB for the default 128)."""
    for tw in range(min(w, max_tw), 0, -8):
        if w % tw == 0 and tw % 8 == 0:
            return tw
    return w


def _kernel(y_ref, m_ref, out_ref):
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    y = y_ref[0].astype(jnp.float32)   # (th, tw, C)
    m = m_ref[0].astype(jnp.float32)   # (th, tw, 1)
    ym = y * m
    c = y.shape[-1]
    # grid steps run sequentially on TPU: accumulating into the shared
    # (3, C) output block is the standard reduction pattern
    out_ref[0, :] += jnp.sum(ym, axis=(0, 1))
    out_ref[1, :] += jnp.sum(ym * y, axis=(0, 1))
    # s0 broadcast across the lane dim (every lane carries the count —
    # a scalar store to one lane would fight the vector layout)
    out_ref[2, :] += jnp.full((c,), jnp.sum(m), jnp.float32)


def _sums_forward(yf, m, *, interpret=False, row_tile=ROW_TILE,
                  max_col_tile=MAX_COL_TILE):
    b, h, w, c = yf.shape
    while h % row_tile:
        row_tile //= 2
    tw = _pick_col_tile(w, max_col_tile)
    grid = (b, h // row_tile, w // tw)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, row_tile, tw, c),
                         lambda bi, hi, wi: (bi, hi, wi, 0)),
            pl.BlockSpec((1, row_tile, tw, 1),
                         lambda bi, hi, wi: (bi, hi, wi, 0)),
        ],
        # every grid step maps to the SAME output block: the kernel
        # accumulates, so the result is the full reduction
        out_specs=pl.BlockSpec((3, c), lambda bi, hi, wi: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, c), jnp.float32),
        interpret=interpret,
    )(yf, m)
    return out[0], out[1], out[2, 0]


def _reference(yf, m):
    """jnp twin of the kernel math (the VJP source and parity anchor) —
    single-sourced from ops/bn_moments.py."""
    from can_tpu.ops.bn_moments import masked_moment_sums

    return masked_moment_sums(yf.astype(jnp.float32), m.astype(jnp.float32))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _sums(yf, m, interpret=False, row_tile=ROW_TILE,
          max_col_tile=MAX_COL_TILE):
    return _sums_forward(yf, m, interpret=interpret, row_tile=row_tile,
                         max_col_tile=max_col_tile)


def _sums_fwd(yf, m, interpret, row_tile, max_col_tile):
    out = _sums_forward(yf, m, interpret=interpret, row_tile=row_tile,
                        max_col_tile=max_col_tile)
    return out, (yf, m)


def _sums_bwd(interpret, row_tile, max_col_tile, residuals, g):
    yf, m = residuals
    # recompute-in-backward: differentiate the jnp twin (the sums are
    # linear/quadratic in yf, so the cotangent is one fused elementwise
    # pass XLA folds into the backward)
    _, vjp = jax.vjp(_reference, yf, m)
    return vjp(g)


_sums.defvjp(_sums_fwd, _sums_bwd)


def moment_sums(yf, m, *, interpret: bool = False, row_tile: int = ROW_TILE,
                max_col_tile: int = MAX_COL_TILE):
    """Fused masked moment sums: ``(yf (B,h,w,C), m (B,h,w,1)) ->
    (s1 (C,), s2 (C,), s0 scalar)``, all f32.  Callers gate on
    :func:`supports` (ops/bn_moments.py falls back to the jnp one-pass)."""
    return _sums(yf, m, interpret, row_tile, max_col_tile)
