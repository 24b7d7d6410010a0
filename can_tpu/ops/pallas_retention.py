"""Pallas TPU kernel: one decode step of power retention in which a (slot,
key head) matrix state goes through the chip ONCE (the fused form of
``ops/retention.py::power_retention_step``, whose plain form stays as this
kernel's oracle).

``S`` (B, KV, dv, ROWS) and ``z`` (B, KV, ROWS) float32 (the state as the
cache keeps it: the rows in the LANES), ``q`` (B, KV, G, d), ``k`` (B, KV, d),
``v`` (B, KV, dv), ``fade`` (B, KV) float32 = ``exp(log g)``, ``active`` (B,)
bool -> (numerator (B, KV, G, dv) and normaliser (B, KV, G), float32; ``S``
and ``z`` moved on by one position, WRITTEN WHERE THEY LAY:
``input_output_aliases``, so a donated cache leaf is never copied).  For a
slot ``active`` marks True

    S' = g S + v phi(k)^T      z' = g z + phi(k)
    num_h = S' phi(q_h)        den_h = phi(q_h) . z'     for the G query heads

and a slot it marks False keeps ``S`` and ``z`` bit for bit (a copy through
VMEM, not ``g = 1, phi(k) = 0``: 0 x NaN of a dead slot's ``v`` is NaN) and
is answered with zeros: its ``y`` is nobody's.

One grid step is one (slot, key head): its state, ``dv`` x 8,320 float32 =
4.26 MB, is one block in and one block out (double-buffered: 17 MB of VMEM),
so a layer is B x KV steps and the pipeline's prologue and epilogue are two
blocks of 128.  With the rows in the lanes, ``phi(k)`` and ``phi(q_h)`` are
lane-dense rows that broadcast over the sublanes for free, and ``v`` is ONE
column a step (a broadcast row turned once).  A step's work:

* ``phi`` of the G query heads and of the key, built on the chip as
  ``ops/retention.py::phi`` builds them (``a`` times a rotation of itself
  times the row block's weight, in that order: the same float32 numbers),
  one lane rotation a row block, into VMEM scratch; ``z`` moved on and the
  normaliser summed on the way (``z`` comes as the slot's whole (KV, ROWS)
  block, fetched once a slot, and each key head writes its row);
* the state in stripes of ``STRIPE`` of its ``dv`` sublanes: for each block
  of 128 lanes the stripe is read, decayed, updated, written, and multiplied
  into G float32 accumulators (``STRIPE`` x 128 each, in registers), ALL ON
  THE VECTOR UNIT: the state is read as the float32 it is, with no product
  whose inputs the matrix unit would round (``tests/test_pallas_retention.py``
  holds that with a state bfloat16 cannot hold);
* a sum over the lanes a head and stripe, the heads laid in the lanes of a
  (dv, 128) scratch that is turned once at the end: the numerator leaves as
  (heads, dv), lane-dense.

``supports`` says from what can be observed (the backend, the shapes, the
state's dtype) whether the kernel can run; ``ops/retention.py::step_form``
asks it and there is no other switch.  ``interpret=True`` runs the kernel
anywhere (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from can_tpu.ops.retention import _weights_of, state_rows

_LANES = 128
_SUBLANES = 8
# sublanes of the state (of its ``dv``) to a stripe: G accumulators of
# ``STRIPE`` x 128 float32 stay in the 64 vector registers beside the stripe
# itself and ``v``'s column (G = 5: 20 + 4 + 4 of 64)
STRIPE = 32
# TIMED on the v5e at the cell's shape (one layer's step: 16 slots x 8 key
# heads x 5 query heads of 128, 1.10 GB of state read and written, the state
# donated; ``tools/retention_forms.py`` as it stood in this PR's first call,
# my chip run, PR 48), ms a layer: stripe 32 1.698 | 16 1.699 | 64 1.697; the
# stripe's loop unrolled by hand 5 blocks a trip 1.698 | 13 1.696 | not at
# all 1.696; the query as a float32 product at the HIGHEST precision a block
# of 128 x 128 on the matrix unit 1.696: the arithmetic hides behind the
# stream whatever its form (647 GB/s read and written in place, 79% of the
# 819 GB/s; XLA's update fusion alone reached 80%), so none of the three is
# a parameter: the stripe is a constant and the loops run ON THE CHIP
# (``fori_loop``s; a kernel's text is traced and lowered at every start, warm
# or cold: unrolled in Python, 65 row blocks and 5 blocks a trip, the cell's
# two programs took 11.5 s of warm set-up against the parent's 1.9); as it
# stands the row reads 1.709 (643 GB/s)
# what a step may hold in VMEM (of the v5e's 128 MiB)
_VMEM_BUDGET = 64 * 2**20


def _qk_rows(g: int) -> int:
    """Sublanes of the block that carries the G query heads and, under
    them, the key: whole tiles of 8."""
    return -(-(g + 1) // _SUBLANES) * _SUBLANES


def _vmem_bytes(kv: int, g: int, d: int, dv: int) -> int:
    rows = state_rows(d)
    state = 2 * 2 * dv * rows * 4               # a block in, a block out, twice
    z = 2 * 2 * -(-kv // _SUBLANES) * _SUBLANES * rows * 4
    scratch = _qk_rows(g) * rows * 4 + dv * _LANES * 4
    return state + z + scratch


def _fits(S_shape, q_shape, dtype) -> bool:
    """The shapes' part of ``supports``: a float32 state, heads of exactly
    one row of lanes (``phi``'s rotations are rotations of one vector
    register), ``dv`` whole lanes, a step's blocks inside the VMEM budget."""
    b, kv, dv, rows = S_shape
    g, d = q_shape[-2:]
    if (jnp.dtype(dtype) != jnp.float32 or d != _LANES or dv % _LANES
            or rows != state_rows(d)):
        return False
    return _vmem_bytes(kv, g, d, dv) <= _VMEM_BUDGET


def supports(S_shape, q_shape, dtype, *, interpret: bool = False) -> bool:
    """Whether ``fused_step`` can take a state ``S`` (B, KV, dv, ROWS) of
    ``dtype`` and queries ``q`` (B, KV, G, d): a TPU backend (or
    ``interpret``) and shapes that fit."""
    return ((interpret or jax.default_backend() == "tpu")
            and _fits(S_shape, q_shape, dtype))


def _term(p, s):
    """A row of ``phi(q)`` against a stripe of the state, float32 by float32
    on the vector unit.  (A function of its own so that a test can show what
    a body that rounds the state would read.)"""
    return p * s


def _kernel(active_ref, fade_ref, qk_ref, v_ref, s_ref, z_ref,
            num_ref, den_ref, so_ref, zo_ref, phi_ref, numt_ref, *,
            g: int):
    b, j = pl.program_id(0), pl.program_id(1)
    dv, rows = s_ref.shape[2:]
    d, blocks = qk_ref.shape[-1], rows // _LANES
    # this key head's row of the slot's ``z``: a select over the block's
    # sublanes (Mosaic loads no single sublane at an index it cannot see)
    mine = jax.lax.broadcasted_iota(jnp.int32, (z_ref.shape[1], _LANES), 0) == j

    def lanes_of(o):
        return pl.ds(pl.multiple_of(o * _LANES, _LANES), _LANES)

    @pl.when(active_ref[b] == 0)
    def _keep():
        so_ref[...] = s_ref[...]

        def block(o, _):
            zo_ref[0, :, lanes_of(o)] = jnp.where(
                mine, z_ref[0, :, lanes_of(o)], zo_ref[0, :, lanes_of(o)])

        jax.lax.fori_loop(0, blocks, block, None)
        num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)

    @pl.when(active_ref[b] != 0)
    def _step():
        fade = fade_ref[b * pl.num_programs(1) + j]
        qk = qk_ref[0, 0]                               # (heads + key, 128)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

        def block(o, den):
            # row ``o d + i`` of phi(a): a_i a_(i + o) times the row's weight
            turned = pltpu.roll(qk, (_LANES - o) % _LANES, axis=1)
            both = (qk * turned) * _weights_of(o * d + lane, d)
            phi_ref[:, lanes_of(o)] = both
            # every key head's row moved on by THIS head's numbers; its own kept
            z_new = fade * z_ref[0, :, lanes_of(o)] + both[g:g + 1]
            zo_ref[0, :, lanes_of(o)] = jnp.where(mine, z_new,
                                                  zo_ref[0, :, lanes_of(o)])
            return den + both * jnp.sum(jnp.where(mine, z_new, 0.0), axis=0,
                                        keepdims=True)

        den = jax.lax.fori_loop(0, blocks, block,
                                jnp.zeros(qk.shape, jnp.float32))
        den_ref[0, 0] = jnp.broadcast_to(
            jnp.sum(den, axis=1, keepdims=True), den.shape)

        for c in range(dv // _LANES):
            # v as a column: v_r in every lane of sublane r
            column = jnp.broadcast_to(
                v_ref[0, 0, :, c * _LANES:(c + 1) * _LANES],
                (_LANES, _LANES)).T
            for r in range(_LANES // STRIPE):
                subl = slice(c * _LANES + r * STRIPE,
                             c * _LANES + (r + 1) * STRIPE)
                v_col = column[r * STRIPE:(r + 1) * STRIPE]

                def block(o, accs, subl=subl, v_col=v_col):
                    new = (fade * s_ref[0, 0, subl, lanes_of(o)]
                           + phi_ref[g:g + 1, lanes_of(o)] * v_col)
                    so_ref[0, 0, subl, lanes_of(o)] = new
                    return tuple(
                        acc + _term(phi_ref[h:h + 1, lanes_of(o)], new)
                        for h, acc in enumerate(accs))

                accs = jax.lax.fori_loop(
                    0, blocks, block,
                    (jnp.zeros((STRIPE, _LANES), jnp.float32),) * g)
                heads = jax.lax.broadcasted_iota(jnp.int32, (STRIPE, _LANES), 1)
                tile = jnp.zeros((STRIPE, _LANES), jnp.float32)
                for h, acc in enumerate(accs):
                    tile = jnp.where(heads == h,
                                     jnp.sum(acc, axis=1, keepdims=True), tile)
                numt_ref[subl, :] = tile
            # (dv, heads in the lanes) -> (heads, dv in the lanes)
            num_ref[0, 0, :, c * _LANES:(c + 1) * _LANES] = numt_ref[
                c * _LANES:(c + 1) * _LANES, :].T[:num_ref.shape[2]]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_step(S, z, q, k, v, fade, active, *, interpret: bool = False):
    """The module docstring's step from one kernel launch.  The caller asks
    ``supports`` first: a shape it refuses raises here.  An inner ``jit``, so
    that a program of many layers traces and lowers the kernel once (as
    ``ops/moe.py::_sorted_in_passes``: the set-up pays for every trace)."""
    b, kv, dv, rows = S.shape
    g, d = q.shape[-2:]
    if (z.shape != (b, kv, rows) or k.shape != (b, kv, d)
            or v.shape != (b, kv, dv)
            or not _fits(S.shape, q.shape, S.dtype)):
        raise ValueError(f"fused_step cannot take S {S.shape} {S.dtype}, "
                         f"z {z.shape}, q {q.shape}, k {k.shape}, v {v.shape}")
    heads = _qk_rows(g)
    # the query heads and, under them, the key: one block, one rotation
    qk = jnp.concatenate([q.astype(jnp.float32),
                          k.astype(jnp.float32)[:, :, None]], axis=2)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, heads - g - 1), (0, 0)))

    def tile(bi, ji, act, fd):
        return bi, ji, 0, 0

    def slot(bi, ji, act, fd):
        return bi, 0, 0

    num, den, S, z = pl.pallas_call(
        functools.partial(_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv),
            in_specs=[pl.BlockSpec((1, 1, heads, d), tile),
                      pl.BlockSpec((1, 1, 1, dv), tile),
                      pl.BlockSpec((1, 1, dv, rows), tile),
                      pl.BlockSpec((1, kv, rows), slot)],
            out_specs=[pl.BlockSpec((1, 1, heads, dv), tile),
                       pl.BlockSpec((1, 1, heads, d), tile),
                       pl.BlockSpec((1, 1, dv, rows), tile),
                       pl.BlockSpec((1, kv, rows), slot)],
            scratch_shapes=[pltpu.VMEM((heads, rows), jnp.float32),
                            pltpu.VMEM((dv, _LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, kv, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, heads, d), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        # operands count the two scalar-prefetch arrays: S is 4, z is 5
        input_output_aliases={4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="fused_retention_step",
        interpret=interpret,
    )(active.astype(jnp.int32), fade.astype(jnp.float32).reshape(b * kv),
      qk, v.astype(jnp.float32)[:, :, None], S, z)
    return num[:, :, :g], den[:, :, :g, 0], S, z
