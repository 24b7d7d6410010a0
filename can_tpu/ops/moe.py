"""Sparse experts, one chip's share of them.

``route`` scores every token against ALL experts of the layer (the router
keeps its published width) and picks its ``top_k``.  ``share_apply``
computes what the experts held HERE add for the tokens routed to them.
What the absent experts would have added is left out: that partial sum is
the layer's routed output on this chip.  A router may be wider than its
routed experts: outputs ``total .. total + zero - 1`` (``ExpertShare.zero``;
LongCat-Flash's zero-compute experts) are IDENTITY experts, which return
their input: a token that chose some gets ``(sum of their weights) x``
(``zero_weight``, added by the layer: ``lm_blocks.expert_layer``), reads no
weight and is held nowhere, so the three forms below see such a choice as
one held elsewhere, and ``zero_counts`` counts it.  Three forms of the grouped product,
chosen by ``share_form`` from the (static) shapes and the backend:

* many tokens (a prefill), ``"sorted"``: the assignments that landed on a
  held expert are put in expert order (a counting sort: no comparison sort
  over T x k keys), one grouped matrix product per projection runs over the
  sorted rows (``jax.lax.ragged_dot``, which XLA:TPU lowers to its own
  grouped-matmul kernel and XLA:CPU to a plain loop), and every row's
  output, times its routing weight, is added to its token's sum.  The
  sorted buffer holds a BOUND on the rows in use, ``sorted_rows``: the
  ``T * top_k * held / total`` rows that even routing lands here times
  ``SORTED_MARGIN``, in whole tiles of the grouped kernel, never more than
  the ``T * min(top_k, held)`` assignments that CAN land here.  Rows past
  the bound, if the routing sends any, take further passes over the same
  buffer (a ``while`` over the sorted order), and each call says how many
  passes it took.  Where the bound is the whole, as with every expert
  held, there is one pass and no loop, and every token gathers its rows
  back instead (over all the rows the cheaper combine);
* few tokens (a decode step: ``T <= DENSE_MAX_TOKENS``), ``"batched"``:
  every held expert takes every token in one batched product and the
  routing weights (zero where a token did not choose the expert) sum the
  results.  A step of 64 tokens gives a held expert 4 rows: the grouped
  kernel pads each expert's rows to a tile of 512 and its tiles'
  arithmetic, not the weights' bytes, took the time (20.0 ms a step of
  which the three products' tiles about 13; my chip run, PR 26); batched,
  the arithmetic is 16 x T rows and each weight is read once.  The plain
  form of the few-tokens branch, the CPU's, and the oracle of the next;
* few tokens of which many held experts get none, ``"skipping"`` (PR 33):
  the same sum over only the held experts that a token of the step chose,
  one Pallas kernel (``ops/pallas_experts.py``) whose grid walks those
  experts, their ids by scalar prefetch, and reads no other expert's
  weights.  XLA cannot do that (static shapes: its batched product is over
  all ``held``).  Taken where the EXPECTED share of held experts without a
  token, ``(1 - top_k / width) ** T`` (``width``: the router's outputs), is at least ``SKIP_MIN_IDLE`` and the
  kernel's ``supports`` takes the backend and the widths: 16 tokens' top-4
  of 64 leave 36% idle, 64 tokens' top-8 of 128 leave 1.6% and stay
  batched.  This form counts the experts it read.

No token is dropped under any imbalance in any form.

Scopes (``models/lm_blocks.py::PARTS``): the grouped products of every form
are ``moe.experts``; what the sorted form does around them (the counting
sort, the gather of rows, the scatter back, the weighted combine) is
``moe.dispatch``, which no other form has; the few-tokens forms' weights by
held expert are the router's, ``moe.router``.
"""

from __future__ import annotations

import functools
import math
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
# Where every expert is held (64 of 64, 2048 x 1536, top-4) the two
# few-tokens forms were timed at PR 33 (below); sorted against batched was
# not re-timed there (the same arithmetic puts that crossing at T = 546 too)
DENSE_MAX_TOKENS = 512

# the few-tokens branch skips (reads only the experts with a token) where the
# EXPECTED share of held experts without one, (1 - top_k / total) ** T, is at
# least this.  Timed on the v5e, one layer, bfloat16, uniform routing
# (``benchmark/tools/expert_decode_forms.py``; my chip run, PR 33), batched /
# skipping, ms (GB/s of the weights each read; experts read of held):
#   64 of 64 held, 2048 x 1536, top-4 (GLM-4.7-Flash)
#     T =   8, idle 0.597: 1.642 (736) / 0.591 (671; 21 of 64)
#     T =  16, idle 0.356: 1.633 (740) / 1.055 (716; 40)
#     T =  32, idle 0.127: 1.636 (739) / 1.403 (726; 54)
#     T =  64, idle 0.016: 1.637 (738) / 1.660 (728; 64)
#     T = 128, idle 0.000: 1.645 (735) / 1.657 (729; 64)
#   16 of 128 held, 6144 x 2048, top-8 (K-EXAONE)
#     T =  64, idle 0.016: 1.718 (703) / 1.663 (726; 16)
# Both forms stream near 90% of the 819 GB/s (XLA's batched products too: it
# is not slow, it cannot skip), so the kernel's time is 0.05 ms + 0.0251 ms
# an expert READ and it wins by the share it skips: -36% at 0.356, -14% at
# 0.127, a tie (+1.4%, -3.2%) at 0.016.  The line is drawn between the last
# two, where the bytes skipped are a few times the kernel's fixed cost and
# the spread of either form.  The kernel's tile of ``f`` (``TILE_F``), at
# T = 16: 256 1.066, 512 1.057, 768 1.063, 1536 (whole) 1.054: no matter
SKIP_MIN_IDLE = 0.05

# the sorted form's buffer holds this many times the rows that even routing
# lands on the held experts (``sorted_rows``); a call whose routing lands
# more takes a further pass.  Timed on the v5e, one layer and prefill slice,
# bfloat16 (``benchmark/tools/expert_prefill_forms.py``; my chip run, PR 43),
# ms a call under a seeded router of the cells' kind (its rows in use over
# the even share) | under one whose bias lifts the held experts; (2): the
# call took two passes:
#   8 of 64 held, 2048 x 1536, top-4, 8,192 tokens (LFM2; 1.52 | 1.99)
#     T x min(k, held) rows, gathered back (PR 40's form)  5.05 | 5.25
#     the same 32,768 rows, added to the sums               5.78 | 5.97
#     margin 1.25 (5,120 rows) 4.02 (2) | 4.20 (2);  1.5 (6,144) 4.15 (2) |
#     4.32 (2);  2 (8,192) 3.41 | 3.60
#   16 of 256 held, 4096 x 2048, top-8, 32,768 tokens (MiMo; 1.26 | 1.96)
#     262,144 rows gathered back 60.84 | 65.10;  added 94.35 | 98.63
#     1.25 (20,480) 31.83 (2) | 35.98 (2);  1.5 (24,576) 25.49 | 38.00 (2);
#     2 (32,768) 28.08 | 32.40
#   16 of 128 held, 6144 x 2048, top-8, 8,192 tokens (K-EXAONE; 1.15 | 1.57)
#     65,536 rows gathered back 22.26 | 24.18;  added 34.45 | 36.38
#     1.25 (10,240) 16.51 | 23.94 (2);  1.5 (12,288) 17.14 | 25.22 (2);
#     2 (16,384) 18.52 | 20.34
#   64 of 64 held, 2048 x 1536, top-4, 32,768 tokens (GLM): the bound is the
#     whole at any margin: 131,072 rows gathered back 42.44, added 46.22
# Over 16 more such routers a shape the most rows in use read 1.30 / 1.22 /
# 1.19 times the even share (one call of 16 past 1.25 at LFM2's shape, none
# past 1.5; the timed router above read 1.52).  One pass at 1.5 is 7-9% under
# one pass at 2, a second pass 47-49% over it, and trained routers skew more
# than seeded ones: 2, which no call of the four cells passed (``prefill_
# dispatch_passes_per_call.lm`` 1.0).  Adding a row to its token's sum costs
# more than a token's gathering it back, row for row, so the bound pays by
# the rows it leaves out (it keeps a quarter to an eighth), and where it
# leaves none out (the last shape) the rows are gathered back
SORTED_MARGIN = 2.0
SORTED_TILE = 512    # the grouped kernel's tile of rows: the buffer is whole tiles


class ExpertShare(NamedTuple):
    """Experts ``first .. first + held - 1`` of ``total`` live on this chip;
    the router has ``zero`` more outputs behind them, ids ``total .. total +
    zero - 1``: identity experts, which every chip computes and none holds."""

    first: int
    held: int
    total: int
    zero: int = 0

    @property
    def width(self) -> int:
        """The router's outputs: what a token's ``top_k`` are chosen among."""
        return self.total + self.zero

    @classmethod
    def of_rank(cls, rank: int, ranks: int, total: int) -> "ExpertShare":
        if total % ranks:
            raise ValueError(f"{total} experts do not divide over {ranks} chips")
        held = total // ranks
        return cls(rank * held, held, total)


def route(x, router_w, bias, *, top_k: int, scale: float,
          normalize: bool = True, scoring: str = "sigmoid"):
    """-> (chosen experts (T, k) int32, their weights (T, k) float32).

    Scores in float32 over all the router's outputs, each output's own
    ``sigmoid`` or a ``softmax`` over them (``scoring``); the choice is by
    ``score + bias`` (the correction bias moves the choice, never the
    weight), the weights are the chosen scores, normalised to sum to one
    where ``normalize``, and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
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


def zero_weight(idx, w, share: ExpertShare):
    """(T,) float32: the sum of each token's weights on the identity experts
    it chose (ids ``>= share.total``); the layer adds that times the token."""
    return jnp.sum(jnp.where(idx >= share.total, w, 0.0), axis=-1)


def zero_counts(idx, share: ExpertShare):
    """() int32: the choices that are identity experts (as ``held_counts``,
    a choice masked to -1 is nobody's)."""
    return jnp.sum((idx >= share.total).astype(jnp.int32))


def share_form(tokens: int, top_k: int, share: ExpertShare, d: int, f: int,
               dtype) -> str:
    """``"sorted"`` / ``"batched"`` / ``"skipping"``: the form ``share_apply``
    takes for ``tokens`` tokens of width ``d`` through experts of width
    ``f``.  Shapes and the backend choose, nothing else."""
    if tokens > DENSE_MAX_TOKENS:
        return "sorted"
    if (1.0 - top_k / share.width) ** tokens < SKIP_MIN_IDLE:
        return "batched"
    # imported here: ``jax.experimental.pallas`` takes over a second to
    # load, paid only by a process one of whose shapes could skip
    from can_tpu.ops import pallas_experts

    return ("skipping" if pallas_experts.supports(tokens, d, f, dtype)
            else "batched")


def sorted_rows(tokens: int, top_k: int, share: ExpertShare) -> int:
    """Rows of the sorted form's buffer for ``tokens`` tokens: what even
    routing (over all the router's outputs) lands on the held experts times
    ``SORTED_MARGIN``, in whole tiles, and never more than every assignment
    that CAN land here."""
    expected = tokens * top_k * share.held / share.width
    tiles = math.ceil(expected * SORTED_MARGIN / SORTED_TILE)
    return min(tiles * SORTED_TILE, tokens * min(top_k, share.held))


def share_apply(x, idx, w, experts, share: ExpertShare):
    """-> (sum over the chosen experts held here of ``w_i E_i(x)``, (T, d);
    the number of held experts whose weights were read, () int32, where the
    form counts it (``"skipping"``), else None: every held expert was; the
    passes over its buffer that the call took, () int32, where the form has
    a buffer (``"sorted"``), else None).

    ``experts``: {"gate", "up": (held, d, f); "down": (held, f, d)};
    ``E(x) = (silu(x gate) * (x up)) down``."""
    form = share_form(idx.shape[0], idx.shape[1], share,
                      *experts["gate"].shape[1:], x.dtype)
    if form == "skipping":
        return (*_share_apply_skipping(x, idx, w, experts, share), None)
    if form == "batched":
        return _share_apply_batched(x, idx, w, experts, share), None, None
    out, passes = _sorted_in_passes(x, idx, w, experts, share)
    return out, None, passes


def _share_apply_skipping(x, idx, w, experts, share: ExpertShare, *,
                          kernel=None):
    """Only the held experts a token chose, their weights read once
    (``kernel``: ``pallas_experts.skipping_experts`` unless given); -> (the
    sum, how many experts that were)."""
    if kernel is None:
        from can_tpu.ops import pallas_experts

        kernel = pallas_experts.skipping_experts
    with jax.named_scope("moe.router"):
        local, held = _held(idx, share)
        slots = jnp.arange(share.held)
        hot = held[..., None] & (local[..., None] == slots)   # (T, k, held)
        w_te = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)
        hit = jnp.any(hot, axis=(0, 1))
        n_active = jnp.sum(hit.astype(jnp.int32))
        # the hit experts' ids in ascending order, by their rank among the
        # hit (a compare over held x held: no sort); the tail repeats the last
        rank = jnp.cumsum(hit.astype(jnp.int32)) - 1
        active = jnp.sum(jnp.where(hit[None, :]
                                   & (rank[None, :] == slots[:, None]),
                                   slots[None, :], 0), axis=1)
        active = jnp.where(slots < n_active, active, jnp.max(active))
    with jax.named_scope("moe.experts"):
        return kernel(x, w_te, active, n_active, experts), n_active


def _share_apply_batched(x, idx, w, experts, share: ExpertShare):
    """Every held expert on every token; the routing weights pick."""
    with jax.named_scope("moe.router"):
        local, held = _held(idx, share)
        # (T, held): the weight with which token t chose held expert e, else 0
        w_te = jnp.sum(jnp.where(held[..., None]
                                 & (local[..., None] == jnp.arange(share.held)),
                                 w[..., None], 0.0), axis=1)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("td,edf->etf", x, experts["gate"])
        u = jnp.einsum("td,edf->etf", x, experts["up"])
        out = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, experts["down"])
        return jnp.sum(out.astype(jnp.float32) * w_te.T[..., None],
                       axis=0).astype(x.dtype)


def _share_apply_sorted(x, idx, w, experts, share: ExpertShare):
    """The tokens in expert order, one grouped product per projection."""
    return _sorted_in_passes(x, idx, w, experts, share)[0]


# jitted with ``share`` static: a model's expert layers have one signature,
# so a program of 38 of them traces and lowers this function once and calls
# it 38 times (XLA inlines the calls: the compiled program is the one
# without them)
@functools.partial(jax.jit, static_argnames="share")
def _sorted_in_passes(x, idx, w, experts, share: ExpertShare):
    """-> (the sum (T, d), the passes over the buffer that it took, ()
    int32).  The counting sort runs once; pass ``p`` takes rows ``p cap ..
    (p + 1) cap - 1`` of the sorted order (``cap``: ``sorted_rows``) through
    the grouped products and adds them, weighted, to their tokens' sums in
    float32, while rows in use are left.  Where the bound is the whole
    buffer (every expert held) there is one pass and no loop, and every
    token gathers its ``k`` rows back instead: over ALL the rows that is
    the cheaper combine (the table above ``SORTED_MARGIN``)."""
    t, k = idx.shape
    cap = sorted_rows(t, k, share)
    rows = t * min(k, share.held)                   # every held assignment fits
    with jax.named_scope("moe.dispatch"):
        local, held = _held(idx, share)
        group = jnp.where(held, local, share.held).reshape(-1)      # (T k,)
        n_groups = share.held + 1                   # the last: held elsewhere
        hot = (group[:, None] == jnp.arange(n_groups)).astype(jnp.int32)
        sizes = hot.sum(0)
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.take_along_axis(jnp.cumsum(hot, axis=0), group[:, None],
                                   axis=1)[:, 0] - 1
        dest = starts[group] + rank                 # row in expert order
        n = t * k - sizes[-1]                       # rows in use
        room = -(-rows // cap) * cap                # whole passes
        token = jnp.arange(t * k, dtype=jnp.int32) // k
        row_token = jnp.zeros((room,), jnp.int32).at[dest].set(token,
                                                               mode="drop")

    def products(xs, sizes_p):
        with jax.named_scope("moe.experts"):
            g = jax.lax.ragged_dot(xs, experts["gate"], sizes_p)
            u = jax.lax.ragged_dot(xs, experts["up"], sizes_p)
            return jax.lax.ragged_dot(jax.nn.silu(g) * u, experts["down"],
                                      sizes_p)

    if cap == rows:                                 # the bound is the whole
        with jax.named_scope("moe.dispatch"):
            xs = x[row_token]
        out = products(xs, sizes[:-1])
        with jax.named_scope("moe.dispatch"):
            out = jnp.where((jnp.arange(rows) < n)[:, None], out, 0)
            back = out[jnp.minimum(dest, rows - 1).reshape(t, k)]   # (T, k, d)
            wk = jnp.where(held, w, 0.0)
            return (jnp.sum(back.astype(jnp.float32) * wk[..., None],
                            axis=1).astype(x.dtype), jnp.ones((), jnp.int32))

    with jax.named_scope("moe.dispatch"):
        row_w = jnp.zeros((room,), jnp.float32).at[dest].set(w.reshape(-1),
                                                             mode="drop")
        first, last = starts[:-1], starts[:-1] + sizes[:-1]

    def one_pass(p, acc):
        lo = p * cap
        with jax.named_scope("moe.dispatch"):
            tok = jax.lax.dynamic_slice(row_token, (lo,), (cap,))
            xs = x[tok]
            sizes_p = (jnp.clip(last - lo, 0, cap)
                       - jnp.clip(first - lo, 0, cap))   # the groups' rows here
        out = products(xs, sizes_p)
        with jax.named_scope("moe.dispatch"):
            wr = jax.lax.dynamic_slice(row_w, (lo,), (cap,))
            in_use = lo + jnp.arange(cap) < n       # rows past the last group
            return acc.at[tok].add(jnp.where(
                in_use[:, None], out.astype(jnp.float32) * wr[:, None], 0.0))

    with jax.named_scope("moe.dispatch"):
        passes, acc = jax.lax.while_loop(
            lambda c: c[0] * cap < n,
            lambda c: (c[0] + 1, one_pass(c[0], c[1])),
            (jnp.zeros((), jnp.int32), jnp.zeros((t, x.shape[1]), jnp.float32)))
        return acc.astype(x.dtype), jnp.maximum(passes, 1)
