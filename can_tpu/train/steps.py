"""Pure train/eval step functions (jit-ready, mesh-agnostic).

The reference's hot loop (utils/train_eval_utils.py:28-52) is
forward → MSE-sum → backward → DDP gradient-allreduce(mean) → SGD step.
Here the whole step is ONE compiled XLA program; when the batch is sharded
over the ``data`` mesh axis, GSPMD inserts the gradient all-reduce over ICI
automatically (the DDP bucket machinery has no analogue — XLA schedules and
overlaps the collective itself).

DDP-parity note (SURVEY §7 hard part d): DDP *averages* per-rank gradients of
per-rank MSE-*sum* losses while lr scales by world size.  The global-batch
equivalent is ``loss = sse(global_batch) / grad_divisor`` with
``grad_divisor = dp world size``, which is what ``make_train_step`` computes.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from can_tpu.train.loss import density_counts, masked_mse_sum


def batch_signature(batch) -> tuple:
    """The (shape, dtype) signature jit keys its executable cache on, for a
    batch dict: sorted ``(name, shape, dtype)`` triples.  A new signature
    hitting a jitted step means trace + lower + compile on the calling
    thread — ``obs.RecompileTracker`` uses this to attribute that bill to
    the batch that incurred it (``EpochStats.distinct_shapes`` counts
    image shapes only; masks/dtypes can recompile too, e.g. --u8-input
    flips the image dtype without touching the shape)."""
    return tuple(sorted(
        (k, tuple(v.shape), str(getattr(v, "dtype", type(v).__name__)))
        for k, v in batch.items() if hasattr(v, "shape")))


def normalize_on_device(image, pixel_mask):
    """uint8 pixels -> ImageNet-normalised f32, inside the compiled step.

    The TPU-first transfer mode (data/dataset.py u8_output): the host ships
    bytes (4x less host->device traffic than normalised f32) and XLA fuses
    this arithmetic into the first conv.  Padded pixels are zeroed in
    NORMALISED space (via the upsampled pixel_mask — the downsample factor
    is derived from the image/mask shapes, so any gt_downsample works) so
    the result is identical to the f32 host path, whose zero padding also
    lives in normalised space.  Float images pass through untouched.
    """
    if image.dtype != jnp.uint8:
        return image
    from can_tpu.data.dataset import IMAGENET_MEAN, IMAGENET_STD

    ds = image.shape[-3] // pixel_mask.shape[-3]
    x = image.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)
    m = jnp.repeat(jnp.repeat(pixel_mask, ds, axis=-3), ds, axis=-2)
    return x * m


def _batch_image(batch):
    return normalize_on_device(batch["image"], batch["pixel_mask"])


class NonFiniteLossError(RuntimeError):
    """Raised on NaN/Inf loss.  The reference ``sys.exit(1)``s the observing
    rank while its peers keep waiting in NCCL collectives — a deadlock
    (utils/train_eval_utils.py:48-50, SURVEY §5).  Here the loss is a
    replicated value of one compiled program, so every host observes the same
    non-finite value and every host raises — a clean global abort."""


def global_norm(tree) -> jnp.ndarray:
    """L2 norm over every leaf of a pytree (optax.global_norm without the
    import): the health layer's gradient/update magnitude signal."""
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.vdot(x, x).real for x in leaves))


def make_train_step(apply_fn: Callable, optimizer, *, grad_divisor: int = 1,
                    compute_dtype=None, remat: bool = False,
                    remat_policy=None, health_metrics: bool = False) -> Callable:
    """Returns ``train_step(state, batch) -> (state, metrics)`` (un-jitted).

    batch: dict with image/dmap/pixel_mask/sample_mask (see data/batching.py).
    metrics: dict of scalars (loss = global SSE before divisor, num_valid).
    health_metrics: also return ``grad_norm``/``update_norm`` (global L2,
    computed in-program so they ride the loop's windowed metric fetch with
    no extra device syncs — obs/health.py's divergence signals).  Default
    off: the metrics tree, and therefore the compiled program, stays
    byte-identical to before for uninstrumented runs.
    remat: rematerialise the forward in backward (``jax.checkpoint``) —
    trades ~1/3 more FLOPs for not keeping every VGG activation in HBM,
    enabling much larger batches / resolutions per chip.
    remat_policy: optional jax.checkpoint policy for SELECTIVE remat (only
    meaningful with remat=True) — e.g.
    ``save_anything_except_these_names("frontend0.pre", "frontend0", ...)``
    recomputes just the named full-res activations (models/cannet.py
    checkpoint_name tags) to trade a sliver of FLOPs for HBM bandwidth
    (tools/ablate_mfu.py measures whether that moves the MFU plateau).
    """

    def train_step(state, batch):
        has_bn = state.batch_stats is not None

        def fwd_plain(params, image):
            return apply_fn(params, image, compute_dtype=compute_dtype)

        def fwd_bn(params, image):
            # masks keep bucket padding / fill slots out of the BN batch
            # moments (models/cannet.py::_batch_norm; no-ops for unpadded
            # batches where the masks are all-ones)
            return apply_fn(params, image, compute_dtype=compute_dtype,
                            batch_stats=state.batch_stats, train=True,
                            pixel_mask=batch["pixel_mask"],
                            sample_mask=batch["sample_mask"])

        fwd = fwd_bn if has_bn else fwd_plain
        if remat:
            fwd = (jax.checkpoint(fwd, policy=remat_policy)
                   if remat_policy is not None else jax.checkpoint(fwd))

        image = _batch_image(batch)

        def loss_fn(params):
            if has_bn:
                pred, new_stats = fwd(params, image)
            else:
                pred = fwd(params, image)
                new_stats = None
            sse = masked_mse_sum(pred, batch)
            return sse / grad_divisor, (sse, new_stats)

        grads, (sse, new_stats) = jax.grad(loss_fn, has_aux=True)(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                              state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state,
            batch_stats=jax.lax.stop_gradient(new_stats) if has_bn else None)
        metrics = {
            "loss": sse,
            "num_valid": jnp.sum(batch["sample_mask"]),
        }
        if health_metrics:
            metrics["grad_norm"] = global_norm(grads)
            metrics["update_norm"] = global_norm(updates)
        return new_state, metrics

    return train_step


def make_eval_step(apply_fn: Callable, *, compute_dtype=None) -> Callable:
    """Returns ``eval_step(params, batch) -> metrics`` (un-jitted).

    metrics: abs_err_sum = Σᵢ|etᵢ-gtᵢ|, sq_err_sum = Σᵢ(etᵢ-gtᵢ)²,
    num_valid — enough to compute dataset MAE and (paper-style RMSE) MSE on
    the host without shipping density maps back.
    """

    def eval_step(params, batch, batch_stats=None):
        image = _batch_image(batch)
        if batch_stats is not None:
            pred = apply_fn(params, image, compute_dtype=compute_dtype,
                            batch_stats=batch_stats, train=False)
        else:
            pred = apply_fn(params, image, compute_dtype=compute_dtype)
        et, gt = density_counts(pred, batch)
        err = (et - gt) * batch["sample_mask"]
        return {
            "abs_err_sum": jnp.sum(jnp.abs(err)),
            "sq_err_sum": jnp.sum(err * err),
            "num_valid": jnp.sum(batch["sample_mask"]),
        }

    return eval_step
