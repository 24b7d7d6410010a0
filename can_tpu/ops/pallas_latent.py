"""Pallas TPU kernel: one decode step of absorbed latent attention in which
each valid block of the latent cache is read ONCE, where it lies (the fused
form of ``ops/attention.py::decode_latent``, which stays as the plain form
and this kernel's oracle).

``q_lat`` (B, H, R), ``q_rope`` (B, H, Dr), the leaves ``ckv`` (B, S, R) and
``krope`` (B, S, Dr) as the cache keeps them, ``positions`` (B,) -> the
attended latents (B, H, R) in ``q_lat``'s dtype: the softmax over positions
``j <= positions[b]`` of ``(q_lat . ckv_j + q_rope . krope_j) * scale``,
float32 scores, a float32 running maximum, denominator and accumulator (VMEM
scratch, kept across a slot's blocks), the probabilities rounded to the
cache's dtype before they meet ``ckv`` again, one exact division at a slot's
last block.  The SAME block of ``ckv`` in VMEM serves the scores and the
values; no score leaves the chip.

One grid step is one block of ``BLOCK`` positions of one slot.  The step's
``positions`` come by scalar prefetch: blocks wholly at or under a slot's
position run without a mask, the block that holds it runs masked (and with
its rows past the position zeroed before the second product: a probability
of 0 times whatever such a row holds, a NaN among it, would be NaN), and a
block past it does nothing and READS nothing: its index map names the slot's
last needed block again, which Pallas does not fetch twice.  So the bytes a
step reads are each slot's own context, not the cache's allocation, and the
last block of a cache that is no whole number of blocks (16,512 = 16 x 1,024
+ 128) is a partial block like any other: its rows past the cache's end are
past every position.

Layout is the kernel's business.  ``ckv`` is read as stored, blocks of
(BLOCK, R).  ``krope`` is read as (B, Dr, S), rope dimensions by positions
with the positions in the lanes: that IS how XLA:TPU lays a leaf 64 wide out
on its own (``bf16[16,16512,64]{1,2,0}``: under the 128 lanes it keeps the
long axis minor), so the transpose here is a bitcast of the program's own
parameter and the rotary scores are a plain product ``q_rope @ krope^T`` with
the keys already turned, in the order of the positions.  (A kernel that asked
for blocks of (BLOCK, Dr) would get a transposing copy of the leaf a layer.)
The heads are padded to whole sublane tiles of the dtype in the wrapper (20
to 32 in bfloat16: 80 KB a layer).

``supports`` says from what can be observed (the backend, the shapes, the
cache's dtype) whether the kernel can run;
``models/glm_moe_lite.py::attention_absorbed`` asks it and there is no other
switch.  ``interpret=True`` runs the kernel
anywhere (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from can_tpu.ops.attention import NEG

# Positions to a grid step.  TIMED on the v5e at the cell's shape (one
# layer's step: the two row writes and the attention, 16 slots over 16,512
# positions at the contexts of a launch's middle step, 225 MB of valid bytes,
# the leaves donated; ``tools/latent_decode_forms.py``, my chip runs, PR 49;
# the orientations' rows with the tool as it stood in the first call), ms a layer | GB/s of the valid bytes:
#   the form until PR 49 (``decode_latent``, both writes a scatter) 0.994 | 237
#   ``decode_latent``, the rotary keys written an update a slot      0.838 | 282
#   this kernel, the scatter's two copies of ``krope`` left in       0.535 | 439
#     (2.769 on the row's first reading of the first call, not explained
#     and not made again: 0.535, 0.537 there, 0.537 in the second call)
#   this kernel, the writes as they stand                            0.420 | 562
# (69% of the 819 GB/s; the same rows at a launch's first and last positions
# within 0.5%), in blocks of 384 0.563 | 512 0.503 | 1,024 0.419 | 1,536 0.413
# | 2,048 0.431 (a grid step costs about 0.35 us and a skipped one as much:
# 688 steps a layer at 384, 272 at 1,024; 1,536 is 1.4% under 1,024 at all
# three positions, 0.1% of the cell's step: not a second constant), and with
# the other side of a product held in the matrix unit (the kernel holds the
# CACHE block and streams the 32 rows of queries or probabilities past it):
# the scores with the queries held and the cache block streaming 0.502, the
# values so 0.483, both 0.718: each pays a transpose on the way and the
# softmax then runs on scores 32 lanes wide.  In the cell the six launches
# read 2.086 ms a step (0.348 a layer) where the two fusions they replace read
# 5.135.  At the second shape the kernel met (PR 50, LongCat-Flash's cell: 256
# slots over 1,280 positions, 64 heads, contexts of 200 to 1,200; the same
# tool, ``--shape longcat``) a layer reads 1.209 ms in blocks of 1,024, 1.131
# in 512 and 1.070 in 384: with 64 rows of heads a block costs 3.3 us where
# its bytes stream in 1.4 (43% of the bandwidth in the cell), and a context
# under one block computes the whole block.  Not taken there: one constant
# serves both cells until a block chosen from the cache's length is timed in
# both (PERF.md section 7)
BLOCK = 1024

_LANES = 128
# what a step may hold in VMEM (of the v5e's 128 MiB)
_VMEM_BUDGET = 64 * 2**20


def _head_rows(h: int, itemsize: int) -> int:
    """The heads as whole sublane tiles of the dtype: 8 rows of float32, 16
    of bfloat16."""
    tile = 32 // itemsize
    return -(-h // tile) * tile


def _vmem_bytes(h: int, r: int, dr: int, itemsize: int, block: int) -> int:
    rows = _head_rows(h, itemsize)
    cache = 2 * block * (r + dr) * itemsize          # a block of each, twice
    zeroed = block * r * itemsize                    # the masked block's copy
    queries = 2 * rows * (r + _LANES) * itemsize
    out = 2 * rows * r * itemsize + rows * (r + 2 * _LANES) * 4
    scores = 4 * rows * block * 4
    return cache + zeroed + queries + out + scores


def _fits(q_shape, ckv_shape, rope_dim: int, dtype, block: int) -> bool:
    """The shapes' part of ``supports``: a rank of whole lanes (it is the
    lanes of a cache block and of the output), rope dimensions of whole
    sublane tiles of the dtype (they are the ROWS of what the kernel reads of
    ``krope``), a block of whole lanes (the positions are the lanes of a
    score block), at least one block of cache, a step inside the VMEM
    budget."""
    h, r = q_shape[-2:]
    s = ckv_shape[1]
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or not jnp.issubdtype(dtype, jnp.floating):
        return False
    if r % _LANES or rope_dim % (32 // itemsize) or block % _LANES or s < block:
        return False
    return _vmem_bytes(h, r, rope_dim, itemsize, block) <= _VMEM_BUDGET


def supports(q_shape, ckv_shape, rope_dim: int, dtype, *, block: int = BLOCK,
             interpret: bool = False) -> bool:
    """Whether ``fused_latent_decode`` can take queries ``q_lat`` (B, H, R)
    against a cache ``ckv`` (B, S, R) and ``krope`` (B, S, ``rope_dim``) of
    ``dtype``: a TPU backend (or ``interpret``) and shapes that fit."""
    return ((interpret or jax.default_backend() == "tpu")
            and _fits(q_shape, ckv_shape, rope_dim, dtype, block))


def _kernel(pos_ref, ql_ref, qr_ref, ckv_ref, krt_ref, o_ref, m_ref, den_ref,
            acc_ref, *, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    block = ckv_ref.shape[1]
    pos = pos_ref[b]
    last = pos // block               # the block that holds the position

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(masked: bool):
        ckv = ckv_ref[0]                                       # (block, R)
        s = (jax.lax.dot_general(ql_ref[0], ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jnp.dot(qr_ref[0], krt_ref[0],
                       preferred_element_type=jnp.float32)) * scale
        if masked:
            left = pos - j * block    # the position, counted in this block
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= left, s, NEG)
            rows = jax.lax.broadcasted_iota(jnp.int32, ckv.shape, 0)
            ckv = jnp.where(rows <= left, ckv, jnp.zeros_like(ckv))
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m - m_new)
        m_ref[...] = m_new
        den_ref[...] = den_ref[...] * fade + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * fade + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)

    @pl.when(j < last)
    def _under_the_position():
        attend(False)

    @pl.when(j == last)
    def _at_the_position():
        attend(True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / den_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def fused_latent_decode(q_lat, q_rope, ckv, krope, positions, *, scale: float,
                        block: int = BLOCK, interpret: bool = False):
    """``decode_latent``'s answer, with ``valid`` = ``j <= positions[b]``,
    from one kernel launch (module docstring).  The caller asks ``supports``
    first: a shape it refuses raises here.  An inner ``jit``, so that a
    program of many layers traces and lowers the kernel once (as
    ``pallas_retention.fused_step``: the set-up pays for every trace)."""
    b, h, r = q_lat.shape
    s, dr = krope.shape[1:]
    if (q_rope.shape != (b, h, dr) or ckv.shape != (b, s, r)
            or krope.shape[0] != b or krope.dtype != ckv.dtype
            or not _fits(q_lat.shape, ckv.shape, dr, ckv.dtype, block)):
        raise ValueError(f"fused_latent_decode cannot take q_lat {q_lat.shape}, "
                         f"q_rope {q_rope.shape}, ckv {ckv.shape} {ckv.dtype}, "
                         f"krope {krope.shape} {krope.dtype} in blocks of "
                         f"{block}")
    rows = _head_rows(h, ckv.dtype.itemsize)
    pad = ((0, 0), (0, rows - h), (0, 0))
    out_dtype = q_lat.dtype
    q_lat = jnp.pad(q_lat.astype(ckv.dtype), pad)
    q_rope = jnp.pad(q_rope.astype(ckv.dtype), pad)
    # a position the cache does not hold reads the whole cache, as the plain
    # form's ``valid`` does
    positions = jnp.clip(positions.astype(jnp.int32), 0, s - 1)

    def heads(bi, j, pos):
        return bi, 0, 0

    def needed(j, bi, pos):
        # a block past the position reads nothing new: the last needed again
        return jnp.minimum(j, pos[bi] // block)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, pl.cdiv(s, block)),
            in_specs=[pl.BlockSpec((1, rows, r), heads),
                      pl.BlockSpec((1, rows, dr), heads),
                      pl.BlockSpec((1, block, r),
                                   lambda bi, j, pos: (bi, needed(j, bi, pos), 0)),
                      pl.BlockSpec((1, dr, block),
                                   lambda bi, j, pos: (bi, 0, needed(j, bi, pos)))],
            out_specs=pl.BlockSpec((1, rows, r), heads),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, r), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="fused_latent_decode",
        interpret=interpret,
    )(positions, q_lat, q_rope, ckv, jnp.swapaxes(krope, 1, 2))
    return out[:, :h]
