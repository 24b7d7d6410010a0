"""Pooling ops with PyTorch-exact semantics, expressed TPU-first.

Adaptive average pooling is a *linear* map along each spatial axis once the
(static) input size is known, so instead of gathers / dynamic windows we
materialise a tiny ``(out_size, in_size)`` averaging matrix at trace time and
contract with it — two small matmuls that XLA places on the MXU and fuses
freely.  Bin boundaries replicate ``torch.nn.functional.adaptive_avg_pool2d``
(reference use: model/CANNet.py:42,51,60,70): for output index ``i``,
``start = floor(i * in / out)``, ``end = ceil((i + 1) * in / out)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from can_tpu.ops.separable import separable_hw_contract


@functools.lru_cache(maxsize=None)
def _adaptive_pool_matrix_np(in_size: int, out_size: int) -> np.ndarray:
    m = np.zeros((out_size, in_size), dtype=np.float32)
    for i in range(out_size):
        start = (i * in_size) // out_size
        end = -((-(i + 1) * in_size) // out_size)  # ceil((i+1)*in/out)
        m[i, start:end] = 1.0 / (end - start)
    return m


@functools.lru_cache(maxsize=None)
def _adaptive_pool_matrix_jnp(in_size: int, out_size: int, dtype_name: str):
    # first call often lands INSIDE a jit trace: without the eager scope
    # the cache would capture that trace's tracer and poison every later
    # trace (UnexpectedTracerError); with it the cache always holds a
    # concrete device array, closed over as a constant thereafter
    with jax.ensure_compile_time_eval():
        return jnp.asarray(_adaptive_pool_matrix_np(in_size, out_size),
                           dtype=dtype_name)


def adaptive_pool_matrix(in_size: int, out_size: int, dtype=jnp.float32):
    """(out_size, in_size) row-stochastic averaging matrix (PyTorch bins).

    Cached by (in, out, dtype) as a device array, not just the numpy
    build: every trace of every pooling site used to re-upload the same
    tiny constant (13 BN-model conv layers x per-bucket-shape compiles
    add up), and inside a trace the cached array is a plain closed-over
    constant — numerically identical program, one host->device copy ever.
    """
    return _adaptive_pool_matrix_jnp(in_size, out_size,
                                     np.dtype(dtype).name)


def adaptive_avg_pool2d(x, output_size):
    """PyTorch-exact adaptive average pool for NHWC tensors.

    x: (..., H, W, C);  output_size: int or (Sh, Sw).
    Returns (..., Sh, Sw, C).
    """
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    sh, sw = output_size
    h, w = x.shape[-3], x.shape[-2]
    # f32 matrices (bf16 would quantize exact coefficients like 1/3); the
    # contraction is tiny (S <= 6 output bins) but parity critical.
    return separable_hw_contract(x, adaptive_pool_matrix(h, sh),
                                 adaptive_pool_matrix(w, sw))


def max_pool2d(x, window: int = 2, stride: int = 2):
    """Max pool over NHWC, VALID padding (floor division of odd sizes —
    matches torch.nn.MaxPool2d(kernel_size=2, stride=2), reference
    model/CANNet.py:112).

    ABLATION (rounds 2-3, timed through the device path PR 21 retired:
    history, not evidence for the chip as it is reached today; v5e-1,
    576x768 b16 bf16 train step; VERDICT r2 item 5): the
    step profile charges maxpool-backward (``select_and_scatter``) ~5% of
    device time, so two replacements were measured against this stock
    lowering's 95.0-95.2 img/s, interleaved in one process:

    * reshape + ``reduce_max`` (VJP = elementwise compare/scale, no
      select_and_scatter): 88.5 img/s — the forward reshape over
      minor-adjacent dims costs more than the backward saves;
    * ``reduce_window`` forward + custom VJP (repeat-upsample the output,
      equality mask, tie-count division): 77.1 img/s — the backward's
      full-resolution mask/count intermediates are pure HBM traffic,
      ~3x the 5% it tried to reclaim.

    The honest conclusion is that XLA's lowering wins: select_and_scatter overlaps
    with the surrounding conv fusions well enough that removing it from
    the op list does not remove its time from the step.
    """
    return _max_window(x, (window, window), (stride, stride))


def _max_window(x, window_hw, stride_hw):
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, *window_hw, 1),
        window_strides=(1, *stride_hw, 1),
        padding="VALID",
    )


def max_pool2d_w_pairs(x):
    """``max_pool2d`` (2x2, stride 2) of a W-pair folded tensor
    (ops/conv.py::fold_w_pairs): (N, H, W/2, 2C) -> (N, H/2, W/2, C), the
    plain pool's output of the unfolded tensor.  Row pairs first, so the
    full-resolution pass (and its ``select_and_scatter`` in the backward)
    runs on all 2C lanes; then the two columns of a super-pixel, which are
    its two channel halves.  Where both columns hold the maximum the left
    one takes the gradient, as the plain pool's window order has it."""
    c = x.shape[-1] // 2
    y = _max_window(x, (2, 1), (2, 1))
    left, right = y[..., :c], y[..., c:]
    return jnp.where(left >= right, left, right)
