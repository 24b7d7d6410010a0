"""Spatial (context) parallelism: image-height sharding with halo exchange.

The CNN analogue of ring attention / sequence parallelism — the framework's
first-class answer to "long context".  The reference handles high-resolution
images (UCF-QNRF scale) only by batch=1 on a single GPU (reference:
train.py:177; SURVEY §5 "long-context: ABSENT"); here one image can span many
chips:

* activations are sharded along H over the ``spatial`` mesh axis;
* every 3x3 (possibly dilated) conv first exchanges ``dilation`` boundary
  rows with its neighbours via ``lax.ppermute`` over ICI (a halo exchange —
  the structural twin of ring attention's block rotation).  Devices at the
  global top/bottom receive zeros, which IS the conv's SAME zero padding, so
  the sharded conv is numerically identical to the unsharded one;
* adaptive average pooling contracts each shard against its column-slice of
  the (out x H_global) pooling matrix and ``lax.psum``s the partials — a
  global pooling tree over ICI;
* align-corners upsampling from the (replicated) S x S context grid needs
  only the row-slice of the interpolation matrix owned by each shard — no
  communication at all;
* max pooling stays local (shard heights are kept divisible by the total
  /8 downsampling, so 2x2 windows never straddle a boundary).

All of this plugs into the SAME model body via the ``LocalOps`` injection
point (models/cannet.py) — the forward pass is written once and runs
unsharded or H-sharded under ``shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from can_tpu.models.cannet import LocalOps, cannet_apply
from can_tpu.ops.pooling import adaptive_pool_matrix, max_pool2d
from can_tpu.ops.resize import upsample_matrix
from can_tpu.ops.separable import separable_hw_contract
from can_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
from can_tpu.train.loss import masked_mse_sum
from can_tpu.train.steps import normalize_on_device


def halo_exchange_rows(x: jax.Array, halo: int, axis_name: str,
                       axis_size: int) -> jax.Array:
    """Concatenate ``halo`` rows from each H-neighbour onto a (N, Hl, W, C)
    block.  Global-edge shards receive zeros (= SAME zero padding)."""
    if halo <= 0:
        return x
    # rows travelling "down" (shard i -> i+1): our top halo comes from above
    from_above = lax.ppermute(
        x[:, -halo:], axis_name, [(i, i + 1) for i in range(axis_size - 1)])
    # rows travelling "up" (shard i -> i-1): our bottom halo comes from below
    from_below = lax.ppermute(
        x[:, :halo], axis_name, [(i + 1, i) for i in range(axis_size - 1)])
    return jnp.concatenate([from_above, x, from_below], axis=1)


def make_spatial_ops(axis_name: str, axis_size: int,
                     feat_hw: Tuple[int, int], *,
                     bn_axes=None, bn_shards: int = 1,
                     bn_ops=None) -> LocalOps:
    """LocalOps whose spatial primitives communicate over ``axis_name``.

    feat_hw: GLOBAL feature-map (H/8, W) shape after the VGG frontend — the
    upsample target and pooling-matrix extent.

    bn_axes/bn_shards: mesh axes (and their total size) that BatchNorm batch
    moments pmean over in train mode — (data, spatial) in the train step, so
    a BN model under dp x sp sees exactly the global-batch statistics
    (SyncBN; reference train.py:116-118).

    bn_ops (ops/bn_moments.py BNOps): how each BN layer's moments are
    reduced before the cross-shard collective — the shard_map body is
    per-device, so the one-pass packed psum (and the Pallas local kernel)
    compose with the mesh axes exactly like the two-pass default.
    """

    def conv2d_sp(x, w, b=None, *, dilation: int = 1, padding=None,
                  precision=None):
        from can_tpu.ops.conv import conv2d

        kh = w.shape[0]
        halo = dilation * (kh // 2) if padding is None else padding
        if kh == 1 or halo == 0:
            return conv2d(x, w, b, dilation=dilation, padding=padding,
                          precision=precision)
        xp = halo_exchange_rows(x, halo, axis_name, axis_size)
        # rows are already materialised (VALID); columns keep SAME padding
        pw = dilation * (w.shape[1] // 2)
        out = lax.conv_general_dilated(
            xp, w, (1, 1), ((0, 0), (pw, pw)), rhs_dilation=(dilation, dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
        )
        if b is not None:
            out = out + b.astype(out.dtype)
        return out.astype(x.dtype)

    def adaptive_pool_sp(x, output_size):
        if isinstance(output_size, int):
            output_size = (output_size, output_size)
        sh, sw = output_size
        hg, w = feat_hw[0], x.shape[-2]
        hl = x.shape[-3]
        idx = lax.axis_index(axis_name)
        ph = adaptive_pool_matrix(hg, sh)  # (sh, Hg), f32
        ph_local = lax.dynamic_slice_in_dim(ph, idx * hl, hl, axis=1)
        partial_sum = separable_hw_contract(x, ph_local,
                                            adaptive_pool_matrix(w, sw))
        return lax.psum(partial_sum, axis_name)

    def upsample_sp(x, size):
        # x: replicated (N, S, S, C); produce only OUR rows of the target
        hg, wg = size
        hl = hg // axis_size
        idx = lax.axis_index(axis_name)
        uh = upsample_matrix(x.shape[-3], hg)  # (Hg, S)
        uh_local = lax.dynamic_slice_in_dim(uh, idx * hl, hl, axis=0)  # (hl, S)
        return separable_hw_contract(x, uh_local,
                                     upsample_matrix(x.shape[-2], wg))

    return LocalOps(
        conv2d=conv2d_sp,
        max_pool=max_pool2d,
        adaptive_pool=adaptive_pool_sp,
        upsample=upsample_sp,
        global_hw=feat_hw,
        bn_axes=bn_axes,
        bn_shards=bn_shards,
        bn_ops=bn_ops,
    )


def _check_spatial_shapes(h: int, sp: int, ds: int = 8) -> None:
    if h % (ds * sp) != 0:
        raise ValueError(
            f"image height {h} must be divisible by downsample*sp = {ds * sp} "
            f"so max-pool windows never straddle shard boundaries "
            f"(pad with data/batching.py pad_multiple={ds * sp})")
    if sp > 1 and h // (ds * sp) < 2:
        # the dilated backend convs exchange a 2-row halo at 1/8 resolution;
        # a shard must own at least that many feature rows
        raise ValueError(
            f"image height {h} over sp={sp} leaves {h // (ds * sp)} feature "
            f"row(s) per shard; need >= 2 (the dilated-conv halo). Use fewer "
            f"spatial shards or taller images")


def make_spatial_apply(mesh: Mesh, image_hw: Tuple[int, int], *,
                       compute_dtype=None) -> Callable:
    """Jitted H-sharded forward:
    ``(params, image (N, H, W, 3), batch_stats_or_None) -> density map``.

    The batch is sharded over ``data`` and H over ``spatial``; output density
    map keeps the same layout.  BN checkpoints pass their (replicated)
    running stats — eval-mode BN is pointwise per channel, so the sharded
    forward needs no extra collective for it.
    """
    sp = mesh.shape[SPATIAL_AXIS]
    h, w = image_hw
    _check_spatial_shapes(h, sp)
    feat_hw = (h // 8, w // 8)
    ops = make_spatial_ops(SPATIAL_AXIS, sp, feat_hw)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(DATA_AXIS, SPATIAL_AXIS, None, None), P()),
             out_specs=P(DATA_AXIS, SPATIAL_AXIS, None, None),
             check_vma=False)
    def fwd(params, x, batch_stats):
        if batch_stats is not None:
            return cannet_apply(params, x, ops=ops,
                                compute_dtype=compute_dtype,
                                batch_stats=batch_stats, train=False)
        return cannet_apply(params, x, ops=ops, compute_dtype=compute_dtype)

    jitted = jax.jit(fwd)

    def apply(params, x, batch_stats=None):
        return jitted(params, x, batch_stats)

    return apply


def make_sp_train_step(optimizer, mesh: Mesh, image_hw: Tuple[int, int], *,
                       compute_dtype=None, donate: bool = True,
                       remat: bool = False,
                       health_metrics: bool = False,
                       bn_ops=None) -> Callable:
    """Jitted train step with BOTH data and spatial parallelism.

    Batch dict layout: image (B, H, W, 3), dmap/pixel_mask (B, H/8, W/8, 1),
    sample_mask (B,) — B sharded over ``data``, H over ``spatial``.
    DDP-parity grad scaling divides by the data-parallel size only (the
    spatial shards jointly compute ONE replica's gradient).

    BN models (state.batch_stats is a tree) get SyncBN: batch moments are
    pmean'd over (data, spatial) inside the shard_map body, so statistics
    equal the global-batch ones exactly (reference train.py:116-118 made
    real in every parallelism mode).  ``bn_ops`` (ops/bn_moments.py)
    selects the moments reduction — one-pass mode halves both the
    activation reads and the per-BN-layer collective rounds (the packed
    psum is one all-reduce where two-pass issues two).

    remat=True rematerialises the sharded forward in backward
    (``jax.checkpoint``) — the combination that serves very large images
    (UCF-QNRF scale): H-sharding splits the activations across chips AND
    remat stops the VGG activations from living in HBM at once.
    """
    sp = mesh.shape[SPATIAL_AXIS]
    dp = mesh.shape[DATA_AXIS]
    h, w = image_hw
    _check_spatial_shapes(h, sp)
    feat_hw = (h // 8, w // 8)
    ops = make_spatial_ops(SPATIAL_AXIS, sp, feat_hw,
                           bn_axes=(DATA_AXIS, SPATIAL_AXIS),
                           bn_shards=dp * sp, bn_ops=bn_ops)

    bspec = P(DATA_AXIS, SPATIAL_AXIS, None, None)
    batch_specs = {"image": bspec, "dmap": bspec, "pixel_mask": bspec,
                   "sample_mask": P(DATA_AXIS)}

    def wrapped(state, batch):
        # run the whole step under one shard_map; loss/metrics psum'd global
        has_bn = state.batch_stats is not None

        def body(state, batch):
            # Differentiate the LOCAL (per-shard) loss, then explicitly psum
            # grads and loss.  (Under check_vma=False a forward psum
            # transposes to a psum of the cotangent — for the replicated
            # scalar-loss seed that would scale gradients by the mesh size,
            # so the loss stays local; for the BN-moment pmeans below the
            # per-shard cotangents are DISTINCT and psum-of-cotangents is
            # exactly the cross-shard term of the true global gradient, so
            # collectives inside the forward are correct.)
            def fwd(params, image):
                if has_bn:
                    # per-shard mask slabs; _batch_norm psums the weighted
                    # sums over the mesh axes, which is exact even for
                    # unequal valid-pixel counts per shard
                    return cannet_apply(params, image, ops=ops,
                                        compute_dtype=compute_dtype,
                                        batch_stats=state.batch_stats,
                                        train=True,
                                        pixel_mask=batch["pixel_mask"],
                                        sample_mask=batch["sample_mask"])
                return cannet_apply(params, image, ops=ops,
                                    compute_dtype=compute_dtype)

            if remat:
                fwd = jax.checkpoint(fwd)

            image = normalize_on_device(batch["image"], batch["pixel_mask"])

            def loss_fn(params):
                if has_bn:
                    pred, new_stats = fwd(params, image)
                else:
                    pred = fwd(params, image)
                    new_stats = None
                local_sse = masked_mse_sum(pred, batch)
                return local_sse / dp, (local_sse, new_stats)

            grads, (local_sse, new_stats) = jax.grad(
                loss_fn, has_aux=True)(state.params)
            grads = jax.tree.map(
                lambda g: lax.psum(g, (DATA_AXIS, SPATIAL_AXIS)), grads)
            sse = lax.psum(local_sse, (DATA_AXIS, SPATIAL_AXIS))
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  state.params, updates)
            metrics = {
                "loss": sse,
                "num_valid": lax.psum(jnp.sum(batch["sample_mask"]), DATA_AXIS),
            }
            if health_metrics:
                # grads/updates are already psum'd (replicated across
                # shards), so these norms are the same global quantities
                # the dp step computes — shard-invariant by construction
                from can_tpu.train.steps import global_norm

                metrics["grad_norm"] = global_norm(grads)
                metrics["update_norm"] = global_norm(updates)
            return state.replace(
                step=state.step + 1, params=params, opt_state=opt_state,
                batch_stats=(jax.lax.stop_gradient(new_stats)
                             if has_bn else state.batch_stats)), metrics

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), batch_specs),
            out_specs=(P(), P()),
            check_vma=False,
        )(state, batch)

    repl = NamedSharding(mesh, P())
    batch_shardings = {k: NamedSharding(mesh, v) for k, v in batch_specs.items()}
    return jax.jit(
        wrapped,
        in_shardings=(repl, batch_shardings),
        out_shardings=(repl, repl),
        donate_argnums=(0,) if donate else (),
    )


def make_sp_eval_step(mesh: Mesh, image_hw: Tuple[int, int], *,
                      compute_dtype=None) -> Callable:
    """Jitted dp x sp eval step: ``(params, batch_dict) -> metrics``.

    The spatial twin of parallel.make_dp_eval_step — needed when one image is
    too large for a single chip (the UCF-QNRF config).  Per-image counts are
    partial per H-shard; psum over ``spatial`` completes them BEFORE the
    |et - gt| (the absolute value does not commute with the shard sum), then
    metric sums psum over ``data``.
    """
    sp = mesh.shape[SPATIAL_AXIS]
    h, w = image_hw
    _check_spatial_shapes(h, sp)
    ops = make_spatial_ops(SPATIAL_AXIS, sp, (h // 8, w // 8))

    bspec = P(DATA_AXIS, SPATIAL_AXIS, None, None)
    batch_specs = {"image": bspec, "dmap": bspec, "pixel_mask": bspec,
                   "sample_mask": P(DATA_AXIS)}

    def body(params, batch, batch_stats):
        # eval-mode BN consumes replicated running stats — pointwise per
        # channel, so no extra collective is needed under sp
        image = normalize_on_device(batch["image"], batch["pixel_mask"])
        pred = cannet_apply(params, image, ops=ops,
                            compute_dtype=compute_dtype,
                            batch_stats=batch_stats, train=False)
        mask = batch["pixel_mask"] * batch["sample_mask"][:, None, None, None]
        et_part = jnp.sum(pred.astype(jnp.float32) * mask, axis=(1, 2, 3))
        gt_part = jnp.sum(batch["dmap"] * mask, axis=(1, 2, 3))
        et = lax.psum(et_part, SPATIAL_AXIS)
        gt = lax.psum(gt_part, SPATIAL_AXIS)
        err = (et - gt) * batch["sample_mask"]
        return {
            "abs_err_sum": lax.psum(jnp.sum(jnp.abs(err)), DATA_AXIS),
            "sq_err_sum": lax.psum(jnp.sum(err * err), DATA_AXIS),
            "num_valid": lax.psum(jnp.sum(batch["sample_mask"]), DATA_AXIS),
        }

    repl = NamedSharding(mesh, P())
    batch_shardings = {k: NamedSharding(mesh, v) for k, v in batch_specs.items()}
    step = shard_map(body, mesh=mesh, in_specs=(P(), batch_specs, P()),
                     out_specs=P(), check_vma=False)
    return jax.jit(step, in_shardings=(repl, batch_shardings, repl),
                   out_shardings=repl)
