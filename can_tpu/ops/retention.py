"""Power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; Brumby-14B-Base): a gated linear attention
whose kernel is the SQUARE of the scaled dot product, ONE layer in two forms
as ``ops/ssm.py`` has two of the state-space recurrence.

Per key head ``j`` and a query head ``h`` of its group, with ``g_t`` in (0, 1]
the position's gate and ``G_t`` the running sum of ``log g``:

    a_ts  = (q_t . k_s / sqrt(d))^2 exp(G_t - G_s)       for s <= t, else 0
    y_t   = sum_s a_ts v_s / sum_s a_ts

and the same numbers as a recurrence over a MATRIX state a key head, with
``phi`` the symmetric second power of a ``d``-vector (``phi(a) . phi(b) =
(a . b)^2 / d``):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T;   z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / phi(q_t)^T z_t

Shapes: ``q`` (B, L, KV, G, d) with G query heads to each of the KV key
heads, ``k`` (B, L, KV, d), ``v`` (B, L, KV, dv), ``log_g`` (B, L, KV) float32;
the state ``S`` (B, KV, dv, ROWS) and ``z`` (B, KV, ROWS), float32 in both forms
and never rounded on the way: accumulators over every position seen.  ``S`` is
kept TRANSPOSED, ``S^T`` of the recurrence above, the rows in the LANES: the
layout in which the step's kernel streams it (``phi(k)`` and ``phi(q)`` are
then lane-dense rows that broadcast over the sublanes for free and ``v`` is
one column; rows in the sublanes would need ``phi(k)`` as a column, 65
transposed broadcasts a tile, or an operand padded 128-fold in HBM).  The
prompt form writes it so (its product's own output orientation: no extra
pass) and the bytes a slot keeps are the same.

**The state's rows** (``phi``, ``state_rows``).  The ``d (d + 1) / 2``
distinct monomials ``a_i a_j`` (8,256 at d = 128) are laid out BY OFFSET:
row block ``o`` (``d`` rows) holds ``a_i a_(i + o mod d)`` for ``i`` in 0 ..
d - 1, for ``o`` in 0 .. d / 2: block 0 the squares (weight ``1 / sqrt(d)``),
blocks 1 .. d / 2 - 1 each unordered pair once (weight ``sqrt(2 / d)``), and
block d / 2, whose pairs come twice, keeps its first half and zeros the
rest.  So ``phi(a)`` is ``a`` times ``d / 2 + 1`` rotations of itself (no
gather), ``state_rows(d)`` = ``(d / 2 + 1) d`` rows (8,320: 65 whole rows of
128 lanes, 0.8% over the exact 8,256; the plain outer product would be
16,384), and the ``d / 2`` padding rows of the state stay zero for ever.

* ``power_retention_chunked`` (prefill): inside a chunk of ``chunk``
  positions the quadratic ``a_ts``; between chunks the carried ``S``, ``z``
  (a scan: L / chunk steps).  A chunk as long as the prompt bucket is the
  quadratic form plus one state at the end, with no product against a state.
* ``power_retention_step`` (decode): the state read, decayed, updated,
  queried, written; float32 throughout.  ONE step, two forms with the same
  answers (``step_form``: the backend, the shapes and the state's dtype
  choose, nothing else): on a TPU at heads of 128 the Pallas kernel
  ``ops/pallas_retention.py::fused_step``, in which a (slot, key head) state
  goes through the chip ONCE and is written where it lay; everywhere else
  (every CPU run, the tiny presets, a state that is not float32) the plain
  form ``_step_plain``, the kernel's oracle: the update on the vector unit,
  the query a product at the HIGHEST precision (at the default precision a
  TPU would round the state it reads to bfloat16), two XLA fusions of the
  state, three passes over it.

Prompts are right-padded.  Padding advances nothing: at a position at or
past a sequence's length ``log g`` is 0 and ``k`` is 0 (so ``phi(k)`` is), as
``dt`` is 0 in ``ssm.ssd_chunked``, and the state after the last chunk is the
state at the sequence's own length.  The products inside a chunk take their
inputs in ``q``'s dtype and accumulate in float32, as attention's do; the
weights ``a_ts``, the normaliser and the state are float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Positions to a chunk of the prompt form.  A module constant, not a
# configuration key: the forms are equal in their outputs, so which is
# fastest is the chip's to say.  One layer's retention (weights, state, the
# division) at the cell's prefill slice (4 prompts in a bucket of 1,024, 8 key
# heads x 5 query heads of 128, bfloat16) on the v5e, ms (my chip run, PR 47,
# ``tools/retention_forms.py``):
#   chunk 128  47.5 | 256  45.3 | 512  47.1 | 1,024  7.95
# The bucket as ONE chunk is the quadratic form plus one state at the end,
# and six times faster: a carried state costs each position a product of its
# ``phi(q)`` (8,320 wide, five query heads) against the whole state, 85 MFLOP
# a token and layer, where all 1,024 x 1,024 pairs cost it 21.  (At a chunk
# of 512 a slice does not even fit beside the cell's weights: ``phi(q)`` is
# 3.8 GB.)  The carried form pays from about 5,000 positions on; no cell has
# such a bucket yet (PERF.md section 7).
CHUNK = 1024


def state_rows(d: int) -> int:
    """Rows of the state for heads of ``d``: ``d / 2 + 1`` blocks of ``d``."""
    if d % 2:
        raise ValueError(f"head width {d} is odd: the state's rows are laid "
                         f"out by offset, in d / 2 + 1 blocks")
    return (d // 2 + 1) * d


def _weights_of(row, d: int):
    """float32, ``row``'s shape: what the monomial ``a_i a_(i + o)`` of row
    ``o d + i`` is weighted by so that ``phi(a) . phi(b) = (a . b)^2 / d``:
    the squares once, every other pair twice, and nothing for the second
    half of the last block, whose pairs are its first half's again."""
    return jnp.where(row < d, 1.0 / math.sqrt(d),
                     jnp.where(row < d * d // 2 + d // 2, math.sqrt(2.0 / d),
                               0.0)).astype(jnp.float32)


def _weights(d: int):
    """(``state_rows(d)``,): ``_weights_of`` every row."""
    return _weights_of(jnp.arange(state_rows(d)), d)


def _turns(d: int, dtype):
    """(d, ``state_rows(d)``) of 0 and 1: ``a @ _turns`` is ``a`` turned by
    each offset 0 .. d / 2 in turn, ``a_(i + o mod d)`` at row ``o d + i``.
    Made from an iota where it is used (a literal would be 2 MB of the
    program's text at each of a layer's three uses)."""
    row = jnp.arange(state_rows(d))
    return (jnp.arange(d)[:, None] == ((row % d + row // d) % d)[None, :]
            ).astype(dtype)


def phi(a, dtype=jnp.float32):
    """The symmetric second power, by offset: ``a`` (..., d) -> (...,
    ``state_rows(d)``) in ``dtype``, the products formed in float32.  The
    rotations are ONE product with a matrix of 0 and 1 (exact: each output
    is one input; on a TPU a gather over the lanes moves the rows to the
    front and back, and 65 slices are 65 programs)."""
    d = a.shape[-1]
    # in ``a``'s own dtype: a selection of its numbers loses nothing
    turned = jnp.dot(a, _turns(d, a.dtype),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=a.dtype)
    blocks = turned.reshape(*a.shape[:-1], d // 2 + 1, d).astype(jnp.float32)
    out = a.astype(jnp.float32)[..., None, :] * blocks
    return (out.reshape(turned.shape) * _weights(d)).astype(dtype)


def _chunk(q, k, v, a, S, z):
    """One chunk of ``c`` positions: ``q`` (B, c, KV, G, d), ``k``, ``v``,
    ``a`` (B, c, KV) the positions' ``log g`` -> (numerator (B, c, KV, G, dv)
    and normaliser (B, c, KV, G), float32; ``S``, ``z`` at the chunk's end).
    ``S`` None: the state before the chunk is zero, and nothing is read."""
    d, c, dtype = q.shape[-1], q.shape[1], q.dtype
    with jax.named_scope("ret.core"):
        a_cum = jnp.cumsum(a, axis=1)                            # (B, c, KV)
        a_end = a_cum[:, -1]                                     # (B, KV)
        s = jnp.einsum("bikgd,bjkd->bkgij", q, k,
                       preferred_element_type=jnp.float32)
        causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        at = a_cum.transpose(0, 2, 1)                            # (B, KV, c)
        decay = jnp.exp(jnp.where(causal, at[..., :, None] - at[..., None, :],
                                  -jnp.inf))                     # (B, KV, i, j)
        w = s * s * (1.0 / d) * decay[:, :, None]                # (B,KV,G,i,j)
        num = jnp.einsum("bkgij,bjkv->bikgv", w.astype(dtype), v,
                         preferred_element_type=jnp.float32)
        den = jnp.sum(w, -1).transpose(0, 3, 1, 2)               # (B, c, KV, G)
    with jax.named_scope("ret.state"):
        if S is not None:
            # what the chunks before put into y_i: exp(G_i) phi(q_i)^T S
            lead = jnp.exp(a_cum)
            pq = phi(q, dtype)
            num = num + lead[..., None, None] * jnp.einsum(
                "bikgm,bkvm->bikgv", pq, S.astype(dtype),
                preferred_element_type=jnp.float32)
            den = den + lead[..., None] * jnp.einsum(
                "bikgm,bkm->bikg", pq, z.astype(dtype),
                preferred_element_type=jnp.float32)
        # the chunk's own part of the state at its end
        to_end = jnp.exp(a_end[:, None] - a_cum)                 # (B, c, KV)
        pk = phi(k)
        # rows by dv, the orientation this product writes by itself (``phi(k)``
        # has its positions minor, as the product that made it left it),
        # then turned into the state's: asked for as "bkvm" the COMPILER
        # turns ``phi(k)`` instead, a copy of 1.09 GB a layer of the cell's
        # prefill slice where the state's part is 136 MB (compiled for a
        # described v5e: the slice's temporaries 2.34 GB against 1.38; on
        # the chip ``ret.state`` 17.75 ms per 1,000 tokens against 9.64, my
        # chip run, PR 48); the barrier keeps the two apart.  The inputs
        # are rounded to ``dtype`` and multiplied as float32 (the same
        # numbers as a ``dtype`` product accumulated in float32, one pass of
        # the matrix unit at the default precision): XLA:CPU has no
        # bfloat16 x bfloat16 = float32 product whose result is turned
        rounded = lambda x: x.astype(dtype).astype(jnp.float32)   # noqa: E731
        own_S = jnp.swapaxes(jax.lax.optimization_barrier(jnp.einsum(
            "bjkm,bjkv->bkmv", rounded(pk),
            rounded(to_end[..., None] * v.astype(jnp.float32)))), -1, -2)
        own_z = jnp.sum(pk * to_end[..., None], axis=1)
        if S is not None:
            fade = jnp.exp(a_end)
            own_S = own_S + fade[..., None, None] * S
            own_z = own_z + fade[..., None] * z
    return num, den, own_S, own_z


def _normalised(num, den, dtype):
    """``num / den``: the guard is for a row whose every weight is exactly
    zero (a query orthogonal to its own key at position 0), where the
    quotient is not defined; it moves no other number."""
    with jax.named_scope("ret.out"):
        return (num / jnp.maximum(den, jnp.finfo(jnp.float32).tiny)[..., None]
                ).astype(dtype)


def power_retention_chunked(q, k, v, log_g, lengths, *, chunk: int = CHUNK):
    """Whole prompts -> (y (B, L, KV, G, dv) in ``q``'s dtype, the float32
    state ``S`` (B, KV, ROWS, dv) and ``z`` (B, KV, ROWS) at each sequence's
    own length)."""
    b, l, kv, g, d = q.shape
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"prompt bucket {l} is not a multiple of the "
                         f"retention chunk {chunk}")
    n = l // chunk
    with jax.named_scope("ret.core"):
        live = jnp.arange(l)[None, :] < lengths[:, None]
        a = jnp.where(live[..., None], log_g.astype(jnp.float32), 0.0)
        k = jnp.where(live[..., None, None], k, jnp.zeros((), k.dtype))
    if n == 1:
        num, den, S, z = _chunk(q, k, v, a, None, None)
        # the layer's state is finished before its output goes on: left to
        # itself the scheduler builds several layers' states side by side,
        # each beside a gigabyte of ``phi(k)`` (19-21 GB a prefill slice of
        # the cell where this takes 13.6, compiled for a described v5e)
        return jax.lax.optimization_barrier(
            (_normalised(num, den, q.dtype), S, z))

    def chunks(x):   # (B, L, ...) -> (n, B, chunk, ...)
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 1, 0)

    def carry(state, xs):
        num, den, S, z = _chunk(*xs, *state)
        return (S, z), _normalised(num, den, q.dtype)

    rows = state_rows(d)
    zero = (jnp.zeros((b, kv, v.shape[-1], rows), jnp.float32),
            jnp.zeros((b, kv, rows), jnp.float32))
    (S, z), y = jax.lax.scan(carry, zero, tuple(map(chunks, (q, k, v, a))))
    return jnp.moveaxis(y, 0, 1).reshape(b, l, kv, g, v.shape[-1]), S, z


def step_form(S, q) -> str:
    """``"fused"`` / ``"step"``: the form ``power_retention_step`` takes for
    a state ``S`` (B, KV, dv, ROWS) and queries ``q`` (B, KV, G, d) (arrays
    or their shapes with a dtype).  The backend, the shapes and the state's
    dtype choose, nothing else."""
    # One layer's step at the cell's shape (16 slots x 8 key heads x 5 query
    # heads of 128, 1.10 GB of state read and written once each way, the state
    # donated) on the v5e, ms and GB/s of that (my chip run, PR 48,
    # ``tools/retention_forms.py``; ``y_gap`` to the plain form 0.001 in
    # bfloat16 for every row):
    #   fused 1.698 (647 GB/s, 79% of the peak) | plain 2.412 (456, 56%)
    # and the four forms XLA was given on the layout the state had until
    # PR 48, rows in the sublanes (kept in the tool): the query a float32
    # product at the highest precision 2.419 | a multiply and a sum over the
    # rows on the vector unit 3.298 | that BEFORE the update 3.301 | the
    # group's heads side by side in the lanes 3.299.  The compiler makes two
    # fusions of a layer's state in every one of them: three passes.
    # (imported here: ``jax.experimental.pallas`` takes over a second to
    # load, paid only by a process that traces a retention layer's step)
    from can_tpu.ops import pallas_retention

    return ("fused" if pallas_retention.supports(S.shape, q.shape, S.dtype)
            else "step")


def power_retention_step(S, z, q, k, v, log_g, active=None):
    """One position: ``S`` (B, KV, dv, ROWS), ``z`` (B, KV, ROWS) float32,
    ``q`` (B, KV, G, d), ``k`` (B, KV, d), ``v`` (B, KV, dv), ``log_g``
    (B, KV) -> (y (B, KV, G, dv) in ``q``'s dtype, the state moved on by
    one).  Slots ``active`` (B,) marks False keep their state (their ``y``
    is nobody's: the plain form answers them from the state they keep, the
    kernel with zeros)."""
    if step_form(S, q) != "fused":
        return _step_plain(S, z, q, k, v, log_g, active)
    from can_tpu.ops import pallas_retention

    with jax.named_scope("ret.state"):
        if active is None:
            active = jnp.ones(S.shape[:1], bool)
        num, den, S, z = pallas_retention.fused_step(
            S, z, q, k, v, jnp.exp(log_g.astype(jnp.float32)), active)
    return _normalised(num, den, q.dtype), S, z


def _step_plain(S, z, q, k, v, log_g, active=None):
    """``power_retention_step`` in plain ``jax.numpy``: the form of every
    shape the kernel does not take (the CPU, heads that are not one row of
    lanes, a state that is not float32) and the kernel's oracle."""
    with jax.named_scope("ret.state"):
        fade = jnp.exp(log_g.astype(jnp.float32))
        pk = phi(k)                                              # (B, KV, R)
        new_S = (fade[..., None, None] * S
                 + v.astype(jnp.float32)[..., :, None] * pk[..., None, :])
        new_z = fade[..., None] * z + pk
        if active is not None:
            new_S = jnp.where(active[:, None, None, None], new_S, S)
            new_z = jnp.where(active[:, None, None], new_z, z)
        # the state as it is kept (float32: nothing is rounded) is what is read
        new_S, new_z = new_S.astype(S.dtype), new_z.astype(z.dtype)
        pq = phi(q)                                              # (B, KV, G, R)
        # float32 against float32 at the HIGHEST precision: at the default a
        # TPU rounds both to bfloat16, and the float32 state would be read as
        # a bfloat16 one
        num = jnp.einsum("bkgm,bkvm->bkgv", pq, new_S.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.sum(pq * new_z[:, :, None], axis=-1, dtype=jnp.float32)
    return _normalised(num, den, q.dtype), new_S, new_z
