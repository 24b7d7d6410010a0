"""Pallas TPU kernel: the held experts' three products for a few tokens,
reading only the experts that a token of the step chose (the skipping form
of ``ops/moe.py::share_apply``'s few-tokens branch; ``_share_apply_batched``
stays as the plain form and this kernel's oracle).

``x`` (T, d), ``w_te`` (T, held) float32 (the weight with which token t
chose held expert e, else 0), ``active`` (held,) int32 (the held experts
with a token, ascending, the tail repeating the last of them),
``n_active`` () int32, ``experts`` {"gate", "up": (held, d, f); "down":
(held, f, d)} -> ``sum_e w_te[t, e] * down_e(silu(gate_e x_t) * up_e x_t)``,
(T, d) in ``x``'s dtype.

The grid walks expert SLOTS, times tiles of ``f``.  The experts' ids reach
the index maps by scalar prefetch: slot ``i < n_active`` names the blocks
of expert ``active[i]``; a slot past ``n_active`` names the block of the
step before it, so the pipeline issues no new copy for it, and
``pl.when`` skips its arithmetic.  An expert no token chose is never read.
The weights are read AS STORED (``gate`` / ``up`` in blocks of (d, tile),
``down`` of (tile, d)): no transposed or re-tiled copy of them exists in
the program (``tests/test_chip_compile.py`` holds that).

The roundings are the plain form's: each product accumulates in float32
and is rounded to the inputs' dtype (``gate x``, ``up x``, and the ``down``
product after the whole of ``f``: its tiles add up in a float32 scratch
first), ``silu * up`` is rounded once, the routing weight and the sum over
experts are float32 (a second scratch, written out once at the end).

``supports`` says from what can be observed (the backend, the shapes)
whether the kernel can run; ``ops/moe.py::share_form`` asks it, beside its
own test of the shape, and there is no other switch.  ``interpret=True``
runs the kernel anywhere (CPU tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# rows are padded to whole sublane tiles of the narrowest dtype taken
_ROWS = 16
# the most tokens one block of rows holds: past a few dozen every expert
# has a token and there is nothing to skip (``ops/moe.py::SKIP_MIN_IDLE``)
MAX_TOKENS = 256
# an expert's matrices are cut in tiles of at most this many of their ``f``
# columns (rows, for ``down``).  TIMED on the v5e at the GLM cell's shape
# (16 tokens, 64 held of 64, 2048 x 1536, top-4, bfloat16;
# ``benchmark/tools/expert_decode_forms.py``): see the table beside
# ``ops/moe.py::SKIP_MIN_IDLE``
TILE_F = 512
# what a step may hold in VMEM (of the v5e's 128 MiB)
_VMEM_BUDGET = 64 * 2**20


def _pad_rows(t: int) -> int:
    return -(-t // _ROWS) * _ROWS


def tile_of(f: int, tile_f: int = TILE_F) -> int:
    """The largest whole-lane divisor of ``f`` at or under ``tile_f`` (0:
    none)."""
    for tile in range(min(tile_f, f) // _LANES * _LANES, 0, -_LANES):
        if f % tile == 0:
            return tile
    return 0


def _vmem_bytes(t: int, d: int, tile: int, itemsize: int) -> int:
    weights = 2 * 3 * d * tile * itemsize          # three blocks, twice
    rows = _pad_rows(t)
    return (weights + 2 * 2 * rows * d * itemsize  # x and the output
            + 2 * rows * d * 4                     # the two accumulators
            + 4 * rows * tile * 4)                 # gate x, up x, their product


def _fits(t: int, d: int, f: int, dtype, tile_f: int) -> bool:
    tile = tile_of(f, tile_f)
    return (0 < t <= MAX_TOKENS and d % _LANES == 0 and tile > 0
            and _vmem_bytes(t, d, tile, jnp.dtype(dtype).itemsize)
            <= _VMEM_BUDGET)


def supports(tokens: int, d: int, f: int, dtype, *, tile_f: int = TILE_F,
             interpret: bool = False) -> bool:
    """Whether ``skipping_experts`` can take ``tokens`` rows of width ``d``
    through experts of width ``f``: a TPU backend (or ``interpret``) and
    shapes that fit (``d`` and a tile of ``f`` whole lanes, the rows in one
    block, a step's blocks inside the VMEM budget)."""
    return ((interpret or jax.default_backend() == "tpu")
            and _fits(tokens, d, f, dtype, tile_f))


def _kernel(active_ref, n_ref, x_ref, w_ref, gate_ref, up_ref, down_ref,
            o_ref, acc_ref, part_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    last_i, last_j = pl.num_programs(0) - 1, pl.num_programs(1) - 1

    @pl.when((i == 0) & (j == 0))
    def _first():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < n_ref[0])
    def _expert_with_a_token():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, up_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        g32 = g.astype(jnp.float32)
        h = (g32 * jax.nn.sigmoid(g32) * u.astype(jnp.float32)).astype(x.dtype)
        part = jnp.dot(h, down_ref[0], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first_tile():
            part_ref[...] = part

        @pl.when(j > 0)
        def _later_tile():
            part_ref[...] += part

        @pl.when(j == last_j)
        def _whole_expert():
            out = part_ref[...].astype(x.dtype).astype(jnp.float32)
            acc_ref[...] += out * w_ref[0]                  # (rows, 1)

    @pl.when((i == last_i) & (j == last_j))
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def skipping_experts(x, w_te, active, n_active, experts, *,
                     tile_f: int = TILE_F, interpret: bool = False):
    """The module docstring's sum from one kernel launch.  The caller asks
    ``supports`` first: a shape it refuses raises here."""
    t, d = x.shape
    held, _, f = experts["gate"].shape
    if not _fits(t, d, f, x.dtype, tile_f):
        raise ValueError(f"skipping_experts cannot take x {x.shape} through "
                         f"experts of {experts['gate'].shape} in tiles of "
                         f"{tile_f}")
    tile = tile_of(f, tile_f)
    tiles = f // tile
    rows = _pad_rows(t)
    x_rows = jnp.pad(x, ((0, rows - t), (0, 0)))
    # (held, rows, 1): an expert's weights as a column beside the rows
    w_rows = jnp.pad(w_te.astype(jnp.float32).T, ((0, 0), (0, rows - t)))[..., None]

    def tile_index(i, j, n):
        # a slot past the last active one reads nothing new: the step
        # before it was the last tile of the expert its ``active`` repeats
        return jnp.where(i < n[0], j, tiles - 1)

    def whole(i, j, act, n):
        return 0, 0

    def columns(i, j, act, n):                      # gate, up: (d, tile)
        return act[i], 0, tile_index(i, j, n)

    def rows_of(i, j, act, n):                      # down: (tile, d)
        return act[i], tile_index(i, j, n), 0

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, tiles),
            in_specs=[pl.BlockSpec((rows, d), whole),
                      pl.BlockSpec((1, rows, 1),
                                   lambda i, j, act, n: (act[i], 0, 0)),
                      pl.BlockSpec((1, d, tile), columns),
                      pl.BlockSpec((1, d, tile), columns),
                      pl.BlockSpec((1, tile, d), rows_of)],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="skipping_experts",
        interpret=interpret,
    )(active.astype(jnp.int32), jnp.reshape(n_active, (1,)).astype(jnp.int32),
      x_rows, w_rows, experts["gate"], experts["up"], experts["down"])
    return out[:t]
