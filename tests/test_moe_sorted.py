"""The sorted form of the held experts' product over a BOUND on the rows in
use (``ops/moe.py::_sorted_in_passes``): whatever the routing lands here, in
however many passes over the buffer, it is the batched form's sum, and it says
how many passes it took."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu.ops import moe as moe_ops

T, D, F = 1024, 32, 16
# (held, total, top_k): LFM2's, MiMo's and GLM's shares
SHARES = {"8-of-64": (8, 64, 4), "16-of-256": (16, 256, 8),
          "64-of-64": (64, 64, 4)}


def _layer(held, first=0):
    ks = jax.random.split(jax.random.key(held), 4)
    experts = {"gate": jax.random.normal(ks[0], (held, D, F)) * D ** -0.5,
               "up": jax.random.normal(ks[1], (held, D, F)) * D ** -0.5,
               "down": jax.random.normal(ks[2], (held, F, D)) * F ** -0.5}
    return experts, jax.random.normal(ks[3], (T, D))


def _distinct(key, rows, lo, hi, k):
    """(rows, k) distinct experts of ``lo .. hi - 1`` a row, uniform."""
    return (lo + jnp.argsort(jax.random.uniform(key, (rows, hi - lo)),
                             axis=-1)[:, :k]).astype(jnp.int32)


def _routing(kind, held, total, k, first):
    """``idx`` (T, k) of the routing ``kind``; ``first``: the share's first
    expert.  ``exactly-N``: N assignments held here, the rest elsewhere."""
    key = jax.random.key(7)
    here = (first, first + held)
    # with every expert held nothing is away: the other places land here too
    away = here if held == total else (0, first) if first else (held, total)
    if kind == "uniform":
        return _distinct(key, T, 0, total, k)
    if kind == "all-here":
        return _distinct(key, T, *here, k)
    if kind == "none-here":
        return _distinct(key, T, *away, k)
    if kind == "one-expert":        # one held expert takes a place of every token
        idx = _distinct(key, T, *away, k)
        return idx.at[:, 0].set(first + held - 1)
    n = int(kind.split("-")[1])     # the first n assignments, in (T, k) order
    idx = _distinct(key, T, *away, k).reshape(-1)
    mine = first + (jnp.arange(T * k) % held)
    return jnp.where(jnp.arange(T * k) < n, mine, idx).reshape(T, k).astype(
        jnp.int32)


def _passes_wanted(idx, share, k):
    n = int(moe_ops.held_counts(idx, share).sum())
    cap = moe_ops.sorted_rows(T, k, share)
    if cap == T * min(k, share.held):
        return n, cap, 1
    return n, cap, max(1, math.ceil(n / cap))


@pytest.mark.parametrize("kind", ["uniform", "all-here", "none-here",
                                  "one-expert", "exactly-cap",
                                  "exactly-cap+1"])
@pytest.mark.parametrize("share_id", list(SHARES))
def test_sorted_in_passes_is_the_batched_sum_and_counts_its_passes(share_id,
                                                                   kind):
    held, total, k = SHARES[share_id]
    first = 0 if held == total else held        # a share in the middle
    share = moe_ops.ExpertShare(first, held, total)
    if kind.startswith("exactly"):
        cap = moe_ops.sorted_rows(T, k, share)
        kind = f"exactly-{min(cap + kind.endswith('+1'), T * k)}"
    if held == total and kind == "none-here":
        pytest.skip("every expert is held: nothing lands elsewhere")
    idx = _routing(kind, held, total, k, first)
    experts, x = _layer(held)
    w = jax.random.uniform(jax.random.key(9), (T, k)) + 0.1
    got, passes = moe_ops._sorted_in_passes(x, idx, w, experts, share)
    want = moe_ops._share_apply_batched(x, idx, w, experts, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    n, cap, wanted = _passes_wanted(idx, share, k)
    assert int(passes) == wanted, (n, cap)
    if kind == "all-here":
        assert n == T * k and (held == total or wanted == T * k // cap > 1)
    if kind == "none-here":
        assert n == 0 and float(jnp.abs(got).max()) == 0.0
    # the public entry takes this form for so many tokens and hands both on
    out, read, said = moe_ops.share_apply(x, idx, w, experts, share)
    assert read is None and int(said) == wanted
    np.testing.assert_array_equal(np.asarray(out), np.asarray(got))
    np.testing.assert_array_equal(
        np.asarray(moe_ops._share_apply_sorted(x, idx, w, experts, share)),
        np.asarray(got))


def test_a_token_that_chose_one_expert_twice_gets_it_twice():
    """The reference routers cannot choose an expert twice; the function
    does not care: both places count, each with its weight."""
    held, total, k = 8, 64, 4
    share = moe_ops.ExpertShare(8, held, total)
    experts, x = _layer(held)
    idx = _routing("uniform", held, total, k, 8).at[:, 1].set(9).at[:, 3].set(9)
    w = jax.random.uniform(jax.random.key(3), (T, k)) + 0.1
    got, passes = moe_ops._sorted_in_passes(x, idx, w, experts, share)
    want = moe_ops._share_apply_batched(x, idx, w, experts, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    n, cap, wanted = _passes_wanted(idx, share, k)
    assert n >= 2 * T and int(passes) == wanted >= 2


@pytest.mark.parametrize("tokens,top_k,held,total,rows", [
    (8192, 4, 8, 64, 8192),          # LFM2's slice: 4,096 even, of 32,768
    (32768, 8, 16, 256, 32768),      # MiMo's: 16,384 even, of 262,144
    (8192, 8, 16, 128, 16384),       # K-EXAONE's: 8,192 even, of 65,536
    (32768, 4, 64, 64, 131072),      # GLM's: every expert held, the whole
    (1024, 4, 32, 64, 4096),         # half held: twice the even is the whole
    (520, 8, 1, 128, 512),           # 32.5 even rows: one tile
    (520, 2, 1, 2, 520),             # the whole is under a tile
])
def test_the_buffer_is_whole_tiles_over_the_even_share_and_never_past_the_whole(
        tokens, top_k, held, total, rows):
    share = moe_ops.ExpertShare(0, held, total)
    cap = moe_ops.sorted_rows(tokens, top_k, share)
    whole = tokens * min(top_k, held)
    assert cap == rows <= whole
    assert cap == whole or cap % moe_ops.SORTED_TILE == 0
    assert cap >= min(whole, tokens * top_k * held / total)


@pytest.mark.parametrize("share_id", list(SHARES))
def test_a_loop_only_where_the_buffer_is_under_the_whole(share_id):
    """One sort and one set of products, two combines: a ``while`` over the
    passes where the bound is under every assignment that can land here,
    each adding its rows to their tokens' sums (a scatter-add); one pass and
    no loop where it is the whole (every expert held), every token gathering
    its rows back (over all the rows the cheaper combine on the chip)."""
    held, total, k = SHARES[share_id]
    share = moe_ops.ExpertShare(0, held, total)
    experts, x = _layer(held)
    idx, w = jnp.zeros((T, k), jnp.int32), jnp.ones((T, k))
    text = str(jax.make_jaxpr(
        lambda *a: moe_ops._sorted_in_passes(*a, share))(x, idx, w, experts))
    assert text.count("while[") == (0 if held == total else 1)
    assert text.count("ragged_dot_general[") == 3
    assert text.count("cumsum[") == 2      # the groups' starts, the ranks
    assert text.count("scatter-add[") == (0 if held == total else 1)


def test_the_form_is_traced_once_for_layers_of_one_signature():
    """Under an outer ``jit`` the function is one lowered function that
    every layer calls (38 layers of LFM2's prefill trace it once)."""
    held, total, k = 8, 64, 4
    share = moe_ops.ExpertShare(0, held, total)
    experts, x = _layer(held)
    idx = _routing("uniform", held, total, k, 0)
    w = jnp.ones((T, k))

    def four_layers(x, idx, w, experts):
        for _ in range(4):
            x = x + moe_ops.share_apply(x, idx, w, experts, share)[0]
        return x

    text = jax.jit(four_layers).lower(x, idx, w, experts).as_text()
    assert text.count("func.func private @_sorted_in_passes") == 1
    assert text.count("call @_sorted_in_passes") == 4
