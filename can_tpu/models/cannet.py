"""CANNet (CVPR'19 Context-Aware Crowd Counting) as a pure-functional JAX model.

Re-design of the reference torch module (reference: model/CANNet.py:8-121):

* VGG-16 frontend: convs [64,64,M,128,128,M,256,256,256,M,512,512,512]
  (model/CANNet.py:11-12) — 10 conv+ReLU layers, 3 maxpools → 1/8 res.
* Context block: for S in {1,2,3,6}: adaptive-avg-pool to SxS → biasless 1x1
  conv → align-corners bilinear upsample to feature size → contrast c = s - fv
  → biasless 1x1 conv → sigmoid weight (model/CANNet.py:39-84); fused
  fi = sum(w_i * s_i) / (sum(w_i) + 1e-12); concat(fv, fi) → 1024ch.
* Backend: 6 dilated(rate-2) 3x3 convs [512,512,512,256,128,64]
  (model/CANNet.py:13,15-16) + 1x1 output conv → 1-channel density map at 1/8
  input resolution.

TPU-first choices (NOT a torch translation):

* Pure params-pytree + apply function (no Module state) — composes directly
  with jit/grad/shard_map and lets us swap the spatial primitives.
* NHWC activations / HWIO kernels (channels ride the 128-wide TPU lanes).
* Adaptive pool and align-corners upsample are matmuls against tiny static
  matrices (see ops/pooling.py, ops/resize.py) — no gathers, fully fusable.
* ``ops`` injection: the distributed spatial-parallel forward
  (parallel/spatial.py) reuses this exact function body with halo-exchange
  convolutions and psum-based global pooling.
* Optional bf16 compute with f32 params/accumulation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from can_tpu.ops.conv import conv1x1, conv2d, fold_w_pairs, fold_w_pairs_kernel
from can_tpu.ops.pooling import adaptive_avg_pool2d, max_pool2d, max_pool2d_w_pairs
from can_tpu.ops.resize import resize_bilinear_align_corners

# Layer configs (reference: model/CANNet.py:11-13).
FRONTEND_CFG: Sequence = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512)
BACKEND_CFG: Sequence[int] = (512, 512, 512, 256, 128, 64)
CONTEXT_SCALES: Sequence[int] = (1, 2, 3, 6)
_FEAT_CH = 512
# A TPU tile's minor dimension: an activation with fewer channels is stored
# and streamed padded to it (stage1_layout).
_LANES = 128


@dataclasses.dataclass(frozen=True)
class LocalOps:
    """Spatial primitives used by the forward pass.

    The default single-device implementations; parallel/spatial.py provides a
    drop-in replacement whose convs halo-exchange over an ``sp`` mesh axis and
    whose pooling psums across shards.
    """

    conv2d: Callable = conv2d
    max_pool: Callable = max_pool2d
    # The 2x2 pool of a W-pair folded tensor (stage 1, stage1_layout); an
    # ops whose primitives cannot take the folded form states None and the
    # forward keeps stage 1 plain.  parallel/spatial.py shards H and pools
    # locally, so the default serves it unchanged.
    max_pool_pairs: Any = max_pool2d_w_pairs
    adaptive_pool: Callable = adaptive_avg_pool2d
    upsample: Callable = resize_bilinear_align_corners
    # Full (unsharded) feature H, W; None means "use local shape".
    global_hw: Any = None
    # Optional BN-moments implementation (ops/bn_moments.py BNOps): the
    # train-mode batch moments of every BN layer route through it —
    # "onepass" reads the feature map once and issues ONE packed psum per
    # layer instead of two, "pallas" additionally fuses the mask multiply
    # into a VMEM-resident kernel (ops/pallas_bn.py).  None keeps the
    # original two-pass math bit-for-bit (the A/B reference).
    bn_ops: Any = None
    # Collective axis name(s) for cross-shard BatchNorm moments under
    # shard_map (SyncBN over an explicit mesh), plus the static total shard
    # count those axes span (for the unbiased-variance correction).  None
    # means moments are taken over the local (possibly GSPMD-global) batch.
    bn_axes: Any = None
    bn_shards: int = 1


def cannet_init(key: jax.Array, dtype=jnp.float32, *,
                batch_norm: bool = False) -> dict:
    """Initialise params: conv weights ~ N(0, 0.01), biases 0
    (reference: model/CANNet.py:93-101).  Same key => identical params on
    every host — replaces the reference's rank0-save/barrier/load protocol
    (train.py:104-114) by construction.

    batch_norm=True builds the BN variant of ``make_layers``
    (reference model/CANNet.py:104-119, its ``batch_norm`` switch): each
    frontend/backend conv gains a BatchNorm with learnable scale/bias.
    Running statistics live in a separate tree — see ``init_batch_stats``.
    Under the GSPMD data-parallel step the batch statistics are computed
    over the GLOBAL sharded batch, so this IS SyncBatchNorm (the reference's
    ``--syncBN`` conversion, train.py:116-118) by construction.
    """

    def conv_p(key, kh, kw, cin, cout, bias=True, bn=False):
        w = jax.random.normal(key, (kh, kw, cin, cout), dtype) * 0.01
        p = {"w": w}
        if bias:
            p["b"] = jnp.zeros((cout,), dtype)
        if bn:
            p["bn"] = {"scale": jnp.ones((cout,), dtype),
                       "bias": jnp.zeros((cout,), dtype)}
        return p

    keys = iter(jax.random.split(key, 64))
    params: dict = {"frontend": [], "context": {}, "backend": [], "output": None}
    cin = 3
    for v in FRONTEND_CFG:
        if v == "M":
            continue
        params["frontend"].append(conv_p(next(keys), 3, 3, cin, v, bn=batch_norm))
        cin = v
    for s in CONTEXT_SCALES:
        params["context"][f"s{s}"] = {
            # biasless 1x1 convs (reference: model/CANNet.py:18-25): stored as
            # (Cin, Cout) matrices — a 1x1 conv IS a channel matmul.
            "ave": jax.random.normal(next(keys), (_FEAT_CH, _FEAT_CH), dtype) * 0.01,
            "weight": jax.random.normal(next(keys), (_FEAT_CH, _FEAT_CH), dtype) * 0.01,
        }
    cin = 2 * _FEAT_CH
    for v in BACKEND_CFG:
        params["backend"].append(conv_p(next(keys), 3, 3, cin, v, bn=batch_norm))
        cin = v
    params["output"] = conv_p(next(keys), 1, 1, BACKEND_CFG[-1], 1)
    return params


def has_batch_norm(params: Mapping) -> bool:
    return "bn" in params["frontend"][0]


def stage1_layout(params: Mapping, image_shape, ops: LocalOps = LocalOps()) -> str:
    """``"folded"`` or ``"plain"``: how ``cannet_apply`` carries the front
    end's first stage (conv, conv, pool at full resolution) for these
    parameters on an image batch of this shape.  The forward asks it while
    it is traced and notes the answer for ``stage1_traced``.

    Folded: the two 64-channel layers and pool1 run on W-pairs of 128
    channels (ops/conv.py::fold_w_pairs_kernel), so no tensor of the stage
    has its channels padded to twice their bytes and both convolutions
    fill the MXU's columns.  Same dtype, same sums of the same products.
    Plain, by what is observed and no flag: batch-norm parameters (the
    moments are per channel and would need both halves summed), an odd
    width, a stage whose two convolutions are not 3x3 with half a tile's
    channels, an ``ops`` without ``max_pool_pairs``.
    """
    first = params["frontend"][:2]
    stage_folds = (FRONTEND_CFG[2] == "M" and all(
        p["w"].shape[:2] == (3, 3) and 2 * p["w"].shape[3] == _LANES
        for p in first))
    if (stage_folds and not has_batch_norm(params)
            and image_shape[-2] % 2 == 0 and ops.max_pool_pairs is not None):
        return "folded"
    return "plain"


# "BxHxW" of an image batch -> how the newest trace of ``cannet_apply`` on
# such a batch carried stage 1.  Written while a program is traced, read
# after its launch by whoever reports what the program does.
_STAGE1_TRACED: dict = {}


def program_key(image_shape) -> str:
    return "x".join(map(str, image_shape[:3]))


def stage1_traced(image_shape) -> Optional[str]:
    """``"folded"`` / ``"plain"`` as the program traced in this process for
    an image batch of this shape has it; None where none was traced (a
    stub, a binary compiled elsewhere, a per-shard shape under
    ``shard_map``)."""
    return _STAGE1_TRACED.get(program_key(image_shape))


def init_batch_stats(params: Mapping) -> Optional[dict]:
    """Running mean/var tree for a BN model (None for the plain model).
    Mirrors torch BatchNorm2d defaults: mean 0, var 1."""
    if not has_batch_norm(params):
        return None

    def stats_for(p):
        c = p["w"].shape[-1]
        return {"mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32)}

    return {
        "frontend": [stats_for(p) for p in params["frontend"]],
        "backend": [stats_for(p) for p in params["backend"]],
    }


def cannet_apply(
    params: Mapping,
    x: jax.Array,
    *,
    ops: LocalOps = LocalOps(),
    compute_dtype=None,
    precision=None,
    batch_stats: Any = None,
    train: bool = False,
    bn_momentum: float = 0.1,
    pixel_mask: Any = None,
    sample_mask: Any = None,
):
    """Forward pass: NHWC image batch -> (N, H/8, W/8, 1) density map.

    Mirrors reference model/CANNet.py:39-91 semantically; structured around
    injected spatial primitives so the same body runs single-device or
    H-sharded (context-parallel) under shard_map.

    For a BN model (cannet_init(batch_norm=True)): pass ``batch_stats``
    (init_batch_stats) — with ``train=True`` statistics come from the batch
    and the call returns ``(out, new_batch_stats)``; with ``train=False``
    the running statistics are used and only ``out`` returns.  Reductions
    over a GSPMD-sharded batch axis are global, so training-mode BN is
    cross-replica synchronized (SyncBN) with no extra code.

    ``pixel_mask`` ((N, H/8, W/8, 1) validity at density-map resolution,
    the batcher's layout) and ``sample_mask`` ((N,)) restrict train-mode
    BN batch moments to REAL pixels of REAL images: bucket padding and
    fill slots otherwise bias the running statistics by the padding
    fraction of the schedule (the reference's BN never sees padding).
    Valid regions are /8-snapped by the dataset, so the /8 mask upsampled
    by nearest is exact at every frontend resolution.  Both default to
    None = the original unmasked moments.

    Stage 1 (the two full-resolution 64-channel layers and pool1) runs on
    W-pairs of 128 channels where ``stage1_layout`` says so; pool1's output
    is the plain path's, so nothing after it knows.
    """
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    bn = has_batch_norm(params)
    if bn and batch_stats is None and not train:
        raise ValueError("BN model in eval mode needs batch_stats")
    new_stats = {"frontend": [], "backend": []} if (bn and train) else None

    # Per-stage BN mask, tracked alongside x through the pooling ladder.
    # Only materialised when a BN model trains with masks.
    bn_mask = None
    if bn and train and pixel_mask is not None:
        m8 = pixel_mask.astype(jnp.float32)
        if sample_mask is not None:
            m8 = m8 * sample_mask.astype(jnp.float32)[:, None, None, None]
        ds = x.shape[-3] // m8.shape[-3]  # 8 at input resolution
        bn_mask = jnp.repeat(jnp.repeat(m8, ds, axis=-3), ds, axis=-2)

    def conv_block(x, group, i, dilation, mask=None, folded=False):
        p = params[group][i]
        w, b = p["w"], p["b"]
        if folded:
            # linear in w: training differentiates through the fold and
            # updates the ORIGINAL kernel; serving folds inside the program.
            # Folded in the parameters' dtype, so the two blocks that hold a
            # tap hand back their gradients to be summed there, not in x's
            w, b = fold_w_pairs_kernel(w, b)
        y = ops.conv2d(x, w.astype(x.dtype), b.astype(x.dtype),
                       dilation=dilation, precision=precision)
        if bn:
            stats = None if batch_stats is None else batch_stats[group][i]
            y, updated = _batch_norm(y, p["bn"], stats, train, bn_momentum,
                                     axes=ops.bn_axes, n_shards=ops.bn_shards,
                                     mask=mask, bn_ops=ops.bn_ops)
            if new_stats is not None:
                new_stats[group].append(updated)
        # checkpoint_name: identity outside jax.checkpoint; under a named
        # remat policy (save_anything_except_these_names) it lets the
        # backward RECOMPUTE chosen activations instead of reading them
        # from HBM — the selective-remat bandwidth probe
        # (tools/ablate_mfu.py; train/steps.py remat_policy).  Both the
        # pre-activation (the relu-vjp residual) and the relu output (the
        # next conv's residual) are named, so excluding "{group}{i}*"
        # really removes that layer's full activation from HBM.
        y = checkpoint_name(y, f"{group}{i}.pre")
        return checkpoint_name(jax.nn.relu(y), f"{group}{i}")

    # --- VGG-16 frontend ---
    layout = _STAGE1_TRACED[program_key(x.shape)] = stage1_layout(
        params, x.shape, ops)
    folded = layout == "folded"
    if folded:
        x = fold_w_pairs(x)
    i = 0
    n_pool = 0
    for v in FRONTEND_CFG:
        if v == "M":
            n_pool += 1
            # the folded stage ends at its pool, whose output is the plain one
            pool = ops.max_pool_pairs if folded else ops.max_pool
            folded = False
            x = checkpoint_name(pool(x), f"pool{n_pool}")
            if bn_mask is not None:
                # stride-2 subsample tracks the pool; valid regions are
                # /8-aligned so this is exact (no partial cells)
                bn_mask = bn_mask[:, ::2, ::2, :]
        else:
            x = conv_block(x, "frontend", i, 1, mask=bn_mask, folded=folded)
            i += 1
    fv = x

    # --- multi-scale context block ---
    fi = context_block(params["context"], fv, ops=ops, precision=precision)
    x = jnp.concatenate([fv, fi], axis=-1)

    # --- dilated backend --- (at /8: bn_mask is back to pixel_mask res)
    for i in range(len(params["backend"])):
        x = conv_block(x, "backend", i, 2, mask=bn_mask)
    p = params["output"]
    x = ops.conv2d(
        x, p["w"].astype(x.dtype), p["b"].astype(x.dtype), padding=0, precision=precision
    )
    if new_stats is not None:
        return x, new_stats
    return x


def context_block(cparams: Mapping, fv: jax.Array, *,
                  ops: LocalOps = LocalOps(), precision=None) -> jax.Array:
    """Multi-scale context fusion (reference model/CANNet.py:39-84):
    fi = (sum_k w_k * sm_k) / (sum_k w_k + 1e-12) with
    sm_k = upsample(1x1(adaptive_pool(fv, k))), w_k = sigmoid(1x1(sm_k - fv)).
    """
    hw = ops.global_hw or (fv.shape[-3], fv.shape[-2])
    aves = []
    for s in CONTEXT_SCALES:
        cp = cparams[f"s{s}"]
        ave = ops.adaptive_pool(fv, s)
        aves.append(conv1x1(ave, cp["ave"].astype(ave.dtype),
                            precision=precision))
    weights = [cparams[f"s{s}"]["weight"].astype(fv.dtype)
               for s in CONTEXT_SCALES]
    num = 0.0
    den = 0.0
    for ave, wmat in zip(aves, weights):
        sm = ops.upsample(ave, hw)
        contrast = sm - fv
        w = jax.nn.sigmoid(conv1x1(contrast, wmat, precision=precision))
        num = num + w * sm
        den = den + w
    return num / (den + 1e-12)


def _batch_norm(y, bn_params, stats, train: bool, momentum: float,
                eps: float = 1e-5, *, axes=None, n_shards: int = 1,
                mask=None, bn_ops=None):
    """torch-semantics BatchNorm2d over NHWC: normalize with biased batch
    var in train mode, update running stats with unbiased var; f32 stats.

    ``axes`` names shard_map mesh axes to sync the batch moments over —
    so the sharded model IS SyncBatchNorm (the reference's
    convert_sync_batchnorm, train.py:116-118, without a wrapper module).

    ``mask`` (optional, broadcastable to y[..., :1]): per-pixel validity
    weights.  Bucket padding and dead fill slots would otherwise be
    averaged into the batch moments — the reference's BN never sees
    padding, so under ``--pad-multiple`` buckets the unmasked moments
    are biased by exactly the padding fraction (code-review r5).  With a
    mask, moments are weighted sums / weighted count, psum'd over
    ``axes`` (also exact for UNequal per-shard valid pixels, which the
    equal-shard pmean path can't represent).  mask=None keeps the
    original computation bit-for-bit.

    ``bn_ops`` (ops/bn_moments.py BNOps, via ``LocalOps.bn_ops``) selects
    HOW the train-mode moments are reduced — two-pass (default,
    bit-compatible), one-pass packed-collective, or the Pallas kernel.
    The s0 floor / all-fill running-stats guard below are
    implementation-independent: every BNOps returns the same
    (mean, biased var, global valid count) contract.

    Accumulator dtype: f32 is the FLOOR, not a ceiling — bf16/f32 inputs
    take moments in f32 (the TPU contract), but f64 inputs keep f64.
    Hard-pinning f32 here silently injected ~1e-7 reduction-order noise
    into every BN layer of an x64 run, which backprop through the stacked
    BN chain amplified to ~1e-1 at the earliest conv weights — exactly
    the f32 noise floor the x64 parity worker (tests/bn_sp_x64_worker.py)
    exists to escape, making its 1e-4 bound unreachable by construction.
    """
    # can-tpu-lint: disable=F64LIT(deliberate FLOOR check: f64 inputs keep f64 — see the x64 parity note above)
    acc_dtype = jnp.float64 if y.dtype == jnp.float64 else jnp.float32
    yf = y.astype(acc_dtype)
    if train:
        if bn_ops is None:
            from can_tpu.ops.bn_moments import BNOps

            bn_ops = BNOps()
        if mask is not None:
            m = mask.astype(acc_dtype)  # (N, h, w, 1), matching y's NHW
            # s0 floored at 1 (inside masked_moments): an all-fill batch
            # (every slot a dead remnant slot) has zero valid pixels, and
            # 0/0 moments would NaN the whole output — the floor yields
            # mean=var=0 instead, and the zero mask already erases the
            # slots downstream (ADVICE r5)
            mean, var, s0 = bn_ops.masked_moments(yf, m, axes)
            unbiased = var * (s0 / jnp.maximum(s0 - 1.0, 1.0))
            # an all-fill batch must also leave the RUNNING stats alone:
            # blending its mean=var=0 into the EMA would drag the stats
            # toward zero by one momentum step per occurrence
            momentum = momentum * jnp.where(s0 > 0.0, 1.0, 0.0)
        elif axes:
            mean, var = bn_ops.global_moments(yf, axes)
        else:
            mean = jnp.mean(yf, axis=(0, 1, 2))
            var = jnp.var(yf, axis=(0, 1, 2))  # biased, for normalization
        if mask is None:
            n = int(np.prod([y.shape[0], y.shape[1], y.shape[2]])) * n_shards
            unbiased = var * (n / max(n - 1, 1))
        if stats is not None:
            updated = {
                "mean": (1 - momentum) * stats["mean"] + momentum * mean,
                "var": (1 - momentum) * stats["var"] + momentum * unbiased,
            }
        else:
            updated = {"mean": mean, "var": unbiased}
    else:
        mean, var = stats["mean"], stats["var"]
        updated = None
    inv = jax.lax.rsqrt(var + eps)
    out = (yf - mean) * inv * bn_params["scale"].astype(acc_dtype)
    out = out + bn_params["bias"].astype(acc_dtype)
    return out.astype(y.dtype), updated


def load_vgg16_frontend(params: dict, npz_path: str) -> dict:
    """Copy pretrained VGG-16 conv weights into the frontend.

    The reference downloads torchvision's VGG-16 and copies the first 20
    tensors by ordinal position (model/CANNet.py:26-35).  With zero egress we
    instead load a local ``.npz`` produced by tools/convert_vgg16.py (keys
    ``conv{i}_w`` (HWIO) / ``conv{i}_b`` for i in 0..9).
    """
    data = np.load(npz_path)
    out = dict(params)
    frontend = []
    for i, p in enumerate(params["frontend"]):
        w = jnp.asarray(data[f"conv{i}_w"], dtype=p["w"].dtype)
        b = jnp.asarray(data[f"conv{i}_b"], dtype=p["b"].dtype)
        if w.shape != p["w"].shape:
            raise ValueError(f"conv{i}: npz shape {w.shape} != expected {p['w'].shape}")
        if b.shape != p["b"].shape:
            raise ValueError(f"conv{i}: bias shape {b.shape} != expected {p['b'].shape}")
        entry = {"w": w, "b": b}
        if "bn" in p:  # keep the BN params of a BN-variant model
            entry["bn"] = p["bn"]
        frontend.append(entry)
    out["frontend"] = frontend
    return out


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


if __name__ == "__main__":
    # forward smoke, the reference's inline check (model/CANNet.py:125-129)
    import jax as _jax
    import jax.numpy as _jnp

    _p = cannet_init(_jax.random.key(0))
    _out = _jax.jit(lambda p, x: cannet_apply(p, x))(_p, _jnp.ones((1, 256, 256, 3)))
    # can-tpu-lint: disable=HOSTSYNC(__main__ smoke print; not a library path)
    print(f"CANNet forward: {_out.shape}, mean {float(_out.mean()):.3e}, "
          f"{param_count(_p):,} params")
