"""Pallas TPU kernel: causal attention over whole prompts in which a score
block never leaves the chip (the fused form of
``ops/attention.py::prefill_causal``, which stays as the plain form and
this kernel's oracle).

``q`` (B, L, H, D), ``k`` (B, L, KV, D), ``v`` (B, L, KV, Dv), ``lengths``
(B,) -> (B, L, H, Dv), the contract ``prefill_causal`` has: query heads
``c * G .. c * G + G - 1`` read key/value head ``c`` (G = H / KV; 1 for
latent attention, which rebuilds a key a head); float32 scores, a float32
running maximum, denominator and accumulator (VMEM scratch, kept across the
key blocks), probabilities rounded to the inputs' dtype before the second
product, one exact division a row at the end.

One grid step is one block of ``BLOCK_Q`` queries of one query head of one
sequence.  Its key/value head's keys and values sit in VMEM whole: their
block's index is ``head // G``, and a block whose index did not change is
not fetched again, so with a group's query heads running one after the other
they are read from HBM once a GROUP (MiMo's full layers: 4 heads' a sequence
and layer, not 64).  The step loops over their blocks of ``BLOCK_K``: first
the blocks wholly under the diagonal (no mask), then those the diagonal
crosses (the causal mask).  Blocks past the diagonal are never visited (the
loop ends there), and a block of queries wholly past its sequence's length
writes zeros and reads nothing new, as the plain form leaves it (``lengths``
by scalar prefetch).  Rows past a length inside a block that is computed are
finite garbage no valid position reads.

Layout is the kernel's business: q, k and v are read as (B, H * D, L),
features by positions with the positions in the lanes.  That is how XLA
lays them out in the prefill program on its own (the rotary parts are 64
wide, so it keeps the long axis minor; in MiMo's program too, whose rotary
part is the first 64 of 192), which makes the reshape and transpose here a
bitcast; a kernel that asked for (B, L, H * D) got three transposing copies
of 335 MB a layer (``tests/test_chip_compile.py`` holds the bitcast in both
programs).  One head is D ROWS of that, so D need only be whole sublane
tiles of the dtype (192 is) and nothing in HBM is padded.  Dv is in the
lanes of the output block and has to be a multiple of 128.  Scores are
``q @ k^T`` with the keys already transposed; the block of queries is turned
once, into scratch, where a D that is no whole number of lanes lies in the
next whole number (192 in 256: ``_vmem_bytes`` counts that) and Mosaic
contracts over the D that is there; values meet the probabilities in the
MXU's transposed-right form.  The output is written (B, L, H * Dv), as the
product after it reads it.

``supports`` says from what can be observed (the backend, the shapes)
whether the kernel can run; ``models/glm_moe_lite.py::attention_expanded``
and ``models/mimo_v2_flash.py::_full_prefill`` ask it and there is no other
switch.  ``interpret=True`` runs the kernel anywhere (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from can_tpu.ops.attention import NEG

# Queries and keys meet in blocks of this many positions.  TIMED on the
# v5e at the GLM cell's shape (2 x 16,384 x 20 heads of 256, the traffic's
# lengths, seconds a launch; ``benchmark/tools/attention_blocks.py``,
# PERF.md section 6, PR 31): 1,024 x 1,024 1.152, 512 x 1,024 1.157,
# 1,024 x 512 1.220, 512 x 512 1.205, 2,048 x 1,024 1.349.  And at the MiMo
# cell's (4 x 8,192 x 64 query heads of 192 over 4 key heads, values 128, the
# traffic's lengths, seconds a launch of two full layers;
# ``tools/attention_forms.py``, PERF.md section 6, PR 44): 1,024 x 1,024
# 0.2534, 512 x 1,024 0.2487, 1,024 x 512 0.4251, 512 x 512 0.3402,
# 2,048 x 1,024 0.3316 (the scanned form 0.9177): the two shapes want the same
# blocks within 2%, so there is one pair.  The contraction over 192, same
# call, blocks of 1,024 x 1,024: as it stands (this kernel) 0.2534, queries
# and keys padded to 256 in VMEM 0.2526, two products 128 + 64 wide 0.2585
BLOCK_Q = 1024
BLOCK_K = 1024

_LANES = 128
# what a step may hold in VMEM (of the v5e's 128 MiB): a head's keys and
# values twice (the next head's arrive while this one's are used), the
# blocks of queries and output twice, the scratch, the score block's
# temporaries
_VMEM_BUDGET = 100 * 2**20


def _vmem_bytes(l: int, d: int, dv: int, itemsize: int, block_q: int,
                block_k: int) -> int:
    resident = 2 * l * (d + dv) * itemsize
    blocks = 2 * block_q * (d + dv) * itemsize
    # the turned block of queries lies in whole lanes whatever D is
    turned = block_q * -(-d // _LANES) * _LANES * itemsize
    scratch = 4 * block_q * (dv + 2 * _LANES)
    scores = 4 * block_q * block_k * 4
    return resident + blocks + turned + scratch + scores


def _fits(q_shape, v_shape, dtype, block_q: int, block_k: int) -> bool:
    """The shapes' part of ``supports``: whole groups of query heads, D whole
    sublane tiles of the dtype (a head is D ROWS of what the kernel reads),
    Dv whole lanes, L whole blocks, a head's keys and values inside the VMEM
    budget."""
    _, l, h, d = q_shape
    kv, dv = v_shape[-2:]
    itemsize = jnp.dtype(dtype).itemsize
    if (h % kv or d % (32 // itemsize) or dv % _LANES or l % block_q
            or l % block_k):
        return False
    return _vmem_bytes(l, d, dv, itemsize, block_q, block_k) <= _VMEM_BUDGET


def supports(q_shape, v_shape, dtype, *, block_q: int = BLOCK_Q,
             block_k: int = BLOCK_K, interpret: bool = False) -> bool:
    """Whether ``fused_causal`` can take ``q`` (B, L, H, D) and ``v``
    (B, L, KV, Dv) of ``dtype`` (``k`` is (B, L, KV, D)): a TPU backend (or
    ``interpret``) and shapes that fit."""
    return ((interpret or jax.default_backend() == "tpu")
            and _fits(q_shape, v_shape, dtype, block_q, block_k))


def _live_blocks(n, block_q: int, blocks: int):
    """How many blocks of queries a sequence of ``n`` positions computes:
    the plain form's count (at least one)."""
    return jnp.clip((n + block_q - 1) // block_q, 1, blocks)


def _kernel(len_ref, qt_ref, kt_ref, vt_ref, o_ref, q_ref, m_ref, den_ref,
            acc_ref, *, scale: float, block_k: int):
    b, i = pl.program_id(0), pl.program_id(2)
    block_q = o_ref.shape[1]
    live = _live_blocks(len_ref[b], block_q, pl.num_programs(2))

    @pl.when(i >= live)
    def _past_the_length():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(i < live)
    def _attend():
        # the queries arrive with positions in the lanes, as the keys do;
        # turned once a block, so that both products are the MXU's own forms
        q_ref[...] = qt_ref[0].T
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        first = i * block_q                      # this block's first row

        def block(j, masked: bool):
            start = pl.multiple_of(j * block_k, block_k)
            kt = kt_ref[0, :, pl.ds(start, block_k)]        # (D, block_k)
            vt = vt_ref[0, :, pl.ds(start, block_k)]        # (Dv, block_k)
            s = jnp.dot(q_ref[...], kt,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                rows = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(cols <= rows, s, NEG)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m - m_new)
            m_ref[...] = m_new
            den_ref[...] = den_ref[...] * fade + jnp.sum(p, axis=-1,
                                                         keepdims=True)
            acc_ref[...] = acc_ref[...] * fade + jax.lax.dot_general(
                p.astype(vt.dtype), vt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        # key blocks wholly at or under this block's first row; then those
        # that begin at or under its last row: the diagonal crosses them
        under = (first + 1) // block_k
        reach = (first + block_q - 1) // block_k + 1
        jax.lax.fori_loop(0, under, lambda j, c: block(j, False), None)
        jax.lax.fori_loop(under, reach, lambda j, c: block(j, True), None)
        o_ref[0] = (acc_ref[...] / den_ref[...]).astype(o_ref.dtype)


def fused_causal(q, k, v, lengths=None, *, scale=None, block_q: int = BLOCK_Q,
                 block_k: int = BLOCK_K, interpret: bool = False):
    """``prefill_causal``'s answer from one kernel launch (module
    docstring); ``scale`` is ``D ** -0.5`` of the D given unless stated.  The
    caller asks ``supports`` first: a shape it refuses raises here."""
    b, l, h, d = q.shape
    kv, dv = v.shape[-2:]
    if (k.shape != (b, l, kv, d)
            or not _fits(q.shape, v.shape, q.dtype, block_q, block_k)):
        raise ValueError(f"fused_causal cannot take q {q.shape}, k {k.shape}, "
                         f"v {v.shape} in blocks of {block_q} x {block_k}")
    scale = d ** -0.5 if scale is None else scale
    if lengths is None:
        lengths = jnp.full((b,), l, jnp.int32)
    blocks, g = l // block_q, h // kv

    def heads_by_positions(x):
        return jnp.swapaxes(x.reshape(b, l, -1), 1, 2)

    def q_block(bi, hi, i, lens):
        # a block past the length reads nothing new: the last live one again
        return bi, hi, jnp.minimum(i, _live_blocks(lens[bi], block_q, blocks) - 1)

    def whole_head(bi, hi, i, lens):
        # the key/value head of query head ``hi``'s group: the same block
        # for G heads running, fetched once
        return bi, hi // g, 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, blocks),
            in_specs=[pl.BlockSpec((1, d, block_q), q_block),
                      pl.BlockSpec((1, d, l), whole_head),
                      pl.BlockSpec((1, dv, l), whole_head)],
            out_specs=pl.BlockSpec((1, block_q, dv),
                                   lambda bi, hi, i, lens: (bi, i, hi)),
            scratch_shapes=[pltpu.VMEM((block_q, d), q.dtype),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, l, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="fused_causal_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), heads_by_positions(q), heads_by_positions(k),
      heads_by_positions(v))
    return out.reshape(b, l, h, dv)
