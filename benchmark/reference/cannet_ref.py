"""Plain reference for CANNet (Liu, Salzmann, Fua: "Context-Aware Crowd
Counting", CVPR 2019; reference code ``model/CANNet.py``, ``train.py``).

Written from the paper and the reference's module, in ``jax.numpy`` and
``lax.conv_general_dilated`` alone.  It imports nothing of ``can_tpu`` and
takes nothing the program has made: weights come from the benchmark's own
``harness/weights.py`` as a dict of float32 arrays, inputs from the
benchmark's feed.

    front end   VGG-16 conv 3x3 + ReLU: 64 64 M 128 128 M 256 256 256 M
                512 512 512 (M = 2x2 max pool, stride 2)          -> fv, 1/8
    context     for S in 1 2 3 6:  a_S = conv1x1(adaptive_avg_pool(fv, S))
                s_S = bilinear_align_corners(a_S, size of fv)
                w_S = sigmoid(conv1x1(s_S - fv))        (both 1x1 biasless)
                fi  = sum_S w_S s_S / (sum_S w_S + 1e-12)
    back end    cat(fv, fi) -> conv 3x3 dilation 2 + ReLU: 512 512 512 256
                128 64 -> conv 1x1 -> density map at 1/8 resolution
    loss        sum of squared errors over the valid cells (MSELoss 'sum')
    optimiser   SGD, momentum 0.95, no weight decay, lr 1e-7 per replica's
                summed loss (DDP averages the replicas' gradients and the lr
                is scaled by their number, so the update is 1e-7 times the
                gradient of the global batch's summed loss)

Departures from the reference code: NHWC / HWIO layout; batches are padded
to a bucket shape and the loss, the counts and the density are masked
(the reference runs batch 1 and never pads).

``mode`` is the arithmetic: "f32" is float32 with every matmul at
``highest`` (the reference proper); the others are the CONTROLS of
``correct`` and are never a yardstick: "bf16" rounds weights and
activations to bfloat16 per layer; "int8w" first rounds each weight tensor
to 8-bit integers (symmetric, one scale per output channel) and then
computes as "bf16" (weight-only, like the program's own int8 serving
path); "int8" rounds the weights AND every layer's input to 8-bit integers
(one dynamic scale per tensor for the inputs; gradients pass straight through
the rounding) and computes in bfloat16: the step below bfloat16 that the
v5e's matrix unit offers and that would tempt a later PR; "bf16params" keeps
float32 arithmetic but stores the parameters (and every update) in bfloat16.
(A float8_e4m3 mode written as a convert to float8 and back read exactly as
bfloat16 on the chip, 0.98 to 1.02 where the CPU reads 3.3 to 4.4: XLA:TPU
drops the round trip.  It was taken out with its claims; PERF.md section 6.)

The limits live in the configuration files (``limits``) with the readings
they were set from in PERF.md section 2; ``harness/correct.py`` says why each
number is compared the way it is.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FRONTEND = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512)
BACKEND = (512, 512, 512, 256, 128, 64)
SCALES = (1, 2, 3, 6)
MODES = ("f32", "bf16", "int8w", "int8", "bf16params")
_LOW = ("bf16", "int8w", "int8")
LR = 1e-7
MOMENTUM = 0.95


def _fake_int8(w):
    """Round to int8 and back: symmetric, one scale per output channel."""
    axes = tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w / scale), -127, 127)
    # straight-through: the gradient goes to the unrounded weight
    return w + lax.stop_gradient(q * scale - w)


def _fake_int8_tensor(x):
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale), -127, 127) * scale
    return (xf + lax.stop_gradient(q - xf)).astype(x.dtype)


def _prep(w, mode):
    """A weight tensor as the mode stores it."""
    if mode in ("int8", "int8w"):
        w = _fake_int8(w)
    if mode in _LOW:
        w = w.astype(jnp.bfloat16)
    return w


def _act(x, mode):
    """A layer's input as the mode feeds it to the contraction."""
    if mode == "int8":
        return _fake_int8_tensor(x)
    return x


def _conv(x, w, b, dilation, pad):
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((pad, pad), (pad, pad)),
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y if b is None else y + b.astype(y.dtype)


def _max_pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def _adaptive_avg_pool(x, s):
    """torch adaptive_avg_pool2d: bin i covers floor(i n / s) .. ceil((i+1) n / s)."""
    n, h, w, c = x.shape
    rows = []
    for i in range(s):
        r0, r1 = (i * h) // s, -((-(i + 1) * h) // s)
        cols = []
        for j in range(s):
            c0, c1 = (j * w) // s, -((-(j + 1) * w) // s)
            cols.append(jnp.mean(x[:, r0:r1, c0:c1, :].astype(jnp.float32),
                                 axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)  # (n, s, s, c) float32


def _interp_axis(x, out, axis):
    """Bilinear, align_corners=True, along one axis."""
    n_in = x.shape[axis]
    if n_in == 1:
        return jnp.repeat(x, out, axis=axis)
    pos = np.arange(out) * ((n_in - 1) / max(out - 1, 1))
    lo = np.minimum(np.floor(pos).astype(np.int64), n_in - 2)
    frac = (pos - lo).astype(np.float32)
    shape = [1] * x.ndim
    shape[axis] = out
    frac = jnp.asarray(frac).reshape(shape)
    a = jnp.take(x, jnp.asarray(lo), axis=axis)
    b = jnp.take(x, jnp.asarray(lo + 1), axis=axis)
    return a * (1.0 - frac) + b * frac


def forward(params, x, mode="f32"):
    """(N, H, W, 3) normalised image -> (N, H/8, W/8, 1) float32 density."""
    act = jnp.bfloat16 if mode in _LOW else jnp.float32
    x = x.astype(act)
    i = 0
    for v in FRONTEND:
        if v == "M":
            x = _max_pool(x)
        else:
            p = params["frontend"][i]
            x = jax.nn.relu(_conv(_act(x, mode), _prep(p["w"], mode), p["b"], 1, 1))
            i += 1
    fv = x
    h, w = fv.shape[1], fv.shape[2]
    num = 0.0
    den = 0.0
    for s in SCALES:
        cp = params["context"][f"s{s}"]
        ave = _adaptive_avg_pool(fv, s)
        ave = jnp.einsum("nhwc,cd->nhwd", _act(ave.astype(act), mode), _prep(cp["ave"], mode),
                         preferred_element_type=jnp.float32)
        up = _interp_axis(_interp_axis(ave, h, 1), w, 2).astype(act)
        contrast = up - fv
        wgt = jax.nn.sigmoid(jnp.einsum("nhwc,cd->nhwd", _act(contrast, mode),
                                        _prep(cp["weight"], mode)))
        num = num + wgt * up
        den = den + wgt
    fi = num / (den + 1e-12)
    x = jnp.concatenate([fv, fi.astype(act)], axis=-1)
    for p in params["backend"]:
        x = jax.nn.relu(_conv(_act(x, mode), _prep(p["w"], mode), p["b"], 2, 2))
    p = params["output"]
    return _conv(_act(x, mode), _prep(p["w"], mode), p["b"], 1, 0).astype(jnp.float32)


def _mask(batch):
    return batch["pixel_mask"] * batch["sample_mask"][:, None, None, None]


def sse(params, batch, mode="f32"):
    err = (forward(params, batch["image"], mode) - batch["dmap"]) * _mask(batch)
    return jnp.sum(err * err)


def _precision(mode):
    return jax.default_matmul_precision(
        "highest" if mode in ("f32", "bf16params") else "default")


@functools.partial(jax.jit, static_argnames=("mode",))
def _sse_and_grad(params, batch, mode):
    return jax.value_and_grad(sse)(params, batch, mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _predict(params, batch, mode):
    dens = forward(params, batch["image"], mode) * _mask(batch)
    return jnp.sum(dens, axis=(1, 2, 3)), dens


def _rows(batch, lo, hi):
    return {k: jnp.asarray(np.asarray(v)[lo:hi]) for k, v in batch.items()}


def loss_and_grad(params, batch, mode="f32", block=1):
    """Summed squared error of one padded batch and its gradient, taken
    ``block`` images at a time so that float32 fits beside nothing else."""
    n = int(np.asarray(batch["image"]).shape[0])
    loss, grad = 0.0, None
    with _precision(mode):
        for lo in range(0, n, block):
            part = _rows(batch, lo, min(lo + block, n))
            if not float(np.sum(np.asarray(part["sample_mask"]))):
                continue
            l, g = _sse_and_grad(params, part, mode)
            loss = loss + l
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    if grad is None:
        grad = jax.tree.map(jnp.zeros_like, params)
    return jnp.asarray(loss, jnp.float32), grad


def train_steps(params, batches, mode="f32", block=1):
    """Follow SGD (momentum 0.95, lr 1e-7 on the global summed loss) over
    ``batches``.  Returns the losses, the first gradient and the parameters
    after the last step, all on the host."""
    store = jnp.bfloat16 if mode == "bf16params" else jnp.float32
    params = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32).astype(store), params)
    trace = None
    losses, first_grad = [], None
    for batch in batches:
        loss, grad = loss_and_grad(
            jax.tree.map(lambda p: p.astype(jnp.float32), params), batch, mode, block)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, grad)
        trace = grad if trace is None else jax.tree.map(
            lambda t, g: MOMENTUM * t + g, trace, grad)
        params = jax.tree.map(lambda p, t: (p - (LR * t).astype(p.dtype)).astype(store),
                              params, trace)
    return losses, first_grad, jax.tree.map(lambda p: np.asarray(p, np.float32), params)


def predict(params, batch, mode="f32", block=2):
    """Counts (N,) and masked density (N, h, w, 1) of one padded batch."""
    n = int(np.asarray(batch["image"]).shape[0])
    counts, dens = [], []
    with _precision(mode):
        for lo in range(0, n, block):
            c, d = _predict(params, _rows(batch, lo, min(lo + block, n)), mode)
            counts.append(np.asarray(c))
            dens.append(np.asarray(d))
    return np.concatenate(counts), np.concatenate(dens)


def param_count(params):
    return sum(math.prod(np.shape(p)) for p in jax.tree.leaves(params))
