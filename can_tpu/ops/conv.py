"""Convolution wrappers for NHWC / HWIO layouts (TPU-native).

The reference model uses torch Conv2d in NCHW/OIHW (model/CANNet.py:104-121);
on TPU the canonical layout is NHWC activations with HWIO kernels so the
channel dim rides the 128-wide lanes and matmuls hit the MXU.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")


def conv2d(x, w, b=None, *, dilation: int = 1, padding=None, precision=None):
    """3x3 (or any) conv, SAME-style padding = dilation by default.

    x: (N, H, W, Cin);  w: (kh, kw, Cin, Cout);  b: (Cout,) or None.
    ``padding=dilation`` with kernel 3 keeps spatial size, matching the
    reference's ``nn.Conv2d(k=3, padding=d, dilation=d)`` (model/CANNet.py:114).
    """
    if padding is None:
        ph = dilation * (w.shape[0] // 2)
        pw = dilation * (w.shape[1] // 2)
        pad = ((ph, ph), (pw, pw))
    else:
        pad = ((padding, padding), (padding, padding))
    # NOTE: no preferred_element_type here — TPU's MXU already accumulates
    # bf16 convs in f32 internally, and requesting an f32 output + downcast
    # breaks the transpose rule (dtype-mismatched cotangent convs in grad).
    # Backend caveat: that "bf16 compute, f32 accumulation" contract is a
    # TPU hardware property; on the CPU/GPU backends (test suite,
    # --platform cpu) bf16 convs may accumulate at lower precision.  The
    # bf16 parity tests therefore compare against bf16-quantised
    # references, and --bf16 is a TPU-targeted flag.
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding=pad,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=_DIMS,
        precision=precision,
    )
    if b is not None:
        out = out + b
    return out.astype(x.dtype)


def fold_w_pairs(x):
    """(N, H, W, C) -> (N, H, W/2, 2C): columns 2j and 2j+1 become one
    super-pixel whose channels are [column 2j's C, column 2j+1's C].  The
    row-major bytes are the same, so this is a reshape and no transpose."""
    n, h, w, c = x.shape
    return x.reshape(n, h, w // 2, 2 * c)


def fold_w_pairs_kernel(w, b=None):
    """A 3x3 stride-1 SAME kernel for W-pair folded activations:
    ``fold_w_pairs(conv2d(x, w, b)) == conv2d(fold_w_pairs(x), w', b')``.

    w: (3, 3, C, O) -> (3, 3, 2C, 2O); b: (O,) -> (2O,).  Output super-pixel
    j holds original columns 2j + dp (dp in 0, 1); tap v in (-1, 0, 1) of
    that column reads column 2j + dp + v, which is half ``rb`` of
    super-pixel ``j + fb`` with ``fb, rb = divmod(dp + v, 2)``.  The six
    (dp, v) blocks are disjoint and every other entry is an exact zero
    (the taps that would reach columns 2j-2 and 2j+3), so each output is
    the same sum of the same products; SAME padding by one super-pixel
    pads two columns, the outer of which only meets those zeros.  The H
    taps are untouched.  The result is placed, never computed (no product
    that a TPU's default precision would round), and is linear in ``w``:
    gradients reach the original kernel through the slices' transposes.

    Why: a 64-channel activation fills half of a TPU tile's 128 lanes, so
    it is stored and streamed at twice its bytes and its convolution
    drives half of the MXU's columns; the W-pair form of the same array
    has 128 channels (models/cannet.py, stage 1).  The folded kernel does
    twice the nominal multiply-adds (half of them against the zeros).
    """
    assert w.shape[:2] == (3, 3), "fold derived for the 3x3 stride-1 case"
    c, o = w.shape[2], w.shape[3]
    wp = jnp.zeros((3, 3, 2 * c, 2 * o), w.dtype)
    for dp in (0, 1):
        for v in (-1, 0, 1):
            fb, rb = divmod(dp + v, 2)
            wp = wp.at[:, fb + 1, rb * c:(rb + 1) * c,
                       dp * o:(dp + 1) * o].set(w[:, v + 1])
    return wp, None if b is None else jnp.tile(b, 2)


def conv1x1(x, w, b=None, *, precision=None):
    """1x1 conv == channel matmul. w: (Cin, Cout). Accumulates in f32 under
    bf16 compute (like conv2d) before casting back."""
    out = jnp.einsum(
        "...c,cd->...d",
        x,
        w,
        precision=precision,
        preferred_element_type=jnp.float32 if x.dtype == jnp.bfloat16 else None,
    )
    if b is not None:
        out = out + b.astype(out.dtype)
    return out.astype(x.dtype)
