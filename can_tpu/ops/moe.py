"""Sparse experts, one chip's share of them.

``route`` scores every token against ALL experts of the layer (the router
keeps its published width) and picks its ``top_k``.  ``share_apply``
computes what the experts held HERE add for the tokens routed to them.
What the absent experts would have added is left out: that partial sum is
the layer's routed output on this chip.  Two forms of the grouped product,
chosen by the (static) number of tokens:

* many tokens (a prefill): the assignments that landed on a held expert
  are put in expert order (a counting sort: no comparison sort over T x k
  keys), one grouped matrix product per projection runs over the sorted
  rows (``jax.lax.ragged_dot``, which XLA:TPU lowers to its own
  grouped-matmul kernel and XLA:CPU to a plain loop), and every token
  gathers its own rows back with its routing weights.  The sorted buffer has
  room for every assignment that CAN land here, ``T * min(top_k, held)``
  rows, and the grouped product visits only the rows in use;
* few tokens (a decode step: ``T <= DENSE_MAX_TOKENS``): every held expert
  takes every token in one batched product and the routing weights (zero
  where a token did not choose the expert) sum the results.  A step of 64
  tokens gives a held expert 4 rows: the grouped kernel pads each expert's
  rows to a tile of 512 and its tiles' arithmetic, not the weights' bytes,
  took the time (20.0 ms a step of which the three products' tiles about
  13; my chip run, PR 26); batched, the arithmetic is 16 x T rows and each
  weight is read once.

No token is dropped under any imbalance in either form.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


# at or under this many tokens every held expert takes every token; above,
# the tokens are sorted by expert.  Timed on the v5e at the published widths
# (16 of 128 held, 6144 x 2048, top-8, bfloat16; my chip run, PR 26), batched
# / sorted, ms: 64 tokens 1.75 / 3.92, 128 1.72 / 3.99, 256 2.01 / 4.10, 384
# 2.89 / 4.21, 512 4.08 / 4.21, 768 5.61 / 4.71, 1024 7.80 / 4.97, 2048 16.0 /
# 8.02: they cross near 550, where the tile arithmetic puts it (T * held =
# T * k * held / total + held * 512, the grouped kernel's tile: T = 546).
# NOT re-read where every expert is held (64 of 64, 2048 x 1536, top-4: a
# decode step of 16 tokens takes the batched form and reads all 64 experts;
# the same arithmetic would put the crossing at T = 546 there too, but
# nobody timed it: PERF.md section 7)
DENSE_MAX_TOKENS = 512


class ExpertShare(NamedTuple):
    """Experts ``first .. first + held - 1`` of ``total`` live on this chip."""

    first: int
    held: int
    total: int

    @classmethod
    def of_rank(cls, rank: int, ranks: int, total: int) -> "ExpertShare":
        if total % ranks:
            raise ValueError(f"{total} experts do not divide over {ranks} chips")
        held = total // ranks
        return cls(rank * held, held, total)


def route(x, router_w, bias, *, top_k: int, scale: float,
          normalize: bool = True):
    """-> (chosen experts (T, k) int32, their weights (T, k) float32).

    Sigmoid scores in float32 over all experts; the choice is by
    ``score + bias`` (the correction bias moves the choice, never the
    weight), the weights are the chosen scores, normalised to sum to one
    and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def _held(idx, share: ExpertShare):
    local = idx - share.first
    return local, (local >= 0) & (local < share.held)


def held_counts(idx, share: ExpertShare):
    """(held,) int32: assignments that landed on each held expert."""
    local, held = _held(idx, share)
    hot = (jnp.where(held, local, share.held)[..., None]
           == jnp.arange(share.held)).astype(jnp.int32)
    return hot.reshape(-1, share.held).sum(0)


def share_apply(x, idx, w, experts, share: ExpertShare):
    """sum over the chosen experts held here of ``w_i E_i(x)``, (T, d).

    ``experts``: {"gate", "up": (held, d, f); "down": (held, f, d)};
    ``E(x) = (silu(x gate) * (x up)) down``."""
    if idx.shape[0] <= DENSE_MAX_TOKENS:
        return _share_apply_batched(x, idx, w, experts, share)
    return _share_apply_sorted(x, idx, w, experts, share)


def _share_apply_batched(x, idx, w, experts, share: ExpertShare):
    """Every held expert on every token; the routing weights pick."""
    local, held = _held(idx, share)
    # (T, held): the weight with which token t chose held expert e, else 0
    w_te = jnp.sum(jnp.where(held[..., None]
                             & (local[..., None] == jnp.arange(share.held)),
                             w[..., None], 0.0), axis=1)
    g = jnp.einsum("td,edf->etf", x, experts["gate"])
    u = jnp.einsum("td,edf->etf", x, experts["up"])
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, experts["down"])
    return jnp.sum(out.astype(jnp.float32) * w_te.T[..., None],
                   axis=0).astype(x.dtype)


def _share_apply_sorted(x, idx, w, experts, share: ExpertShare):
    """The tokens in expert order, one grouped product per projection."""
    t, k = idx.shape
    n_groups = share.held + 1                       # the last: held elsewhere
    local, held = _held(idx, share)
    group = jnp.where(held, local, share.held).reshape(-1)          # (T k,)
    hot = (group[:, None] == jnp.arange(n_groups)).astype(jnp.int32)
    sizes = hot.sum(0)
    starts = jnp.cumsum(sizes) - sizes
    rank = jnp.take_along_axis(jnp.cumsum(hot, axis=0), group[:, None],
                               axis=1)[:, 0] - 1
    dest = starts[group] + rank                     # row in expert order
    rows = t * min(k, share.held)                   # every held assignment fits
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(token, mode="drop")
    in_use = jnp.arange(rows) < (t * k - sizes[-1])
    xs = x[row_token]
    held_sizes = sizes[:-1]
    g = jax.lax.ragged_dot(xs, experts["gate"], held_sizes)
    u = jax.lax.ragged_dot(xs, experts["up"], held_sizes)
    out = jax.lax.ragged_dot(jax.nn.silu(g) * u, experts["down"], held_sizes)
    out = jnp.where(in_use[:, None], out, 0)        # rows past the last group
    back = out[jnp.minimum(dest, rows - 1).reshape(t, k)]           # (T, k, d)
    wk = jnp.where(held, w, 0.0)
    return jnp.sum(back.astype(jnp.float32) * wk[..., None], axis=1).astype(x.dtype)
