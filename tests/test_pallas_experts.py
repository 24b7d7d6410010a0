"""The skipping experts kernel (``ops/pallas_experts.py``) in interpret
mode against the plain form it replaces where it can run
(``ops/moe.py::_share_apply_batched``, its oracle): the same sum, at the
same roundings, over only the experts a token chose."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.ops import moe as moe_ops
from can_tpu.ops import pallas_experts

INTERPRETED = functools.partial(pallas_experts.skipping_experts,
                                interpret=True, tile_f=128)


def _experts(key, held, d, f, dtype):
    ks = jax.random.split(key, 3)
    return {"gate": (jax.random.normal(ks[0], (held, d, f)) * d ** -0.5).astype(dtype),
            "up": (jax.random.normal(ks[1], (held, d, f)) * d ** -0.5).astype(dtype),
            "down": (jax.random.normal(ks[2], (held, f, d)) * f ** -0.5).astype(dtype)}


def _uniform_choices(key, tokens, total, k):
    """Every token's ``k`` distinct experts of ``total``."""
    return jnp.argsort(jax.random.uniform(key, (tokens, total)),
                       axis=-1)[:, :k].astype(jnp.int32)


def _both(x, idx, w, experts, share, kernel=INTERPRETED):
    want = moe_ops._share_apply_batched(x, idx, w, experts, share)
    got, read = jax.jit(lambda *a: moe_ops._share_apply_skipping(
        *a, share, kernel=kernel))(x, idx, w, experts)
    return np.asarray(got, np.float32), np.asarray(want, np.float32), int(read)


def _non_empty(idx, share) -> int:
    return int((np.asarray(moe_ops.held_counts(idx, share)) > 0).sum())


@pytest.mark.parametrize("tokens,tile", [(16, 128), (16, 384), (1, 128),
                                         (5, 256), (40, 128)])
def test_glm_s_shape_cut_to_whole_lanes(tokens, tile):
    """64 of 64 held, top-4, bfloat16, ``d`` 256 and ``f`` 384 (three tiles
    of 128, or one): the plain form's answer within a rounding of bfloat16,
    and ``n_active`` the oracle's count of experts with a token."""
    share = moe_ops.ExpertShare(0, 64, 64)
    ks = jax.random.split(jax.random.key(tokens), 4)
    experts = _experts(ks[0], 64, 256, 384, jnp.bfloat16)
    x = jax.random.normal(ks[1], (tokens, 256)).astype(jnp.bfloat16)
    idx = _uniform_choices(ks[2], tokens, 64, 4)
    w = jax.random.uniform(ks[3], (tokens, 4), jnp.float32, 0.1, 1.0)
    got, want, read = _both(x, idx, w, experts, share, functools.partial(
        pallas_experts.skipping_experts, interpret=True, tile_f=tile))
    assert got.shape == (tokens, 256)
    assert read == _non_empty(idx, share) <= min(64, tokens * 4)
    # the plain form on the CPU rounds silu and its product one step each,
    # the kernel once (as XLA:TPU does inside a fusion): a bfloat16 step
    np.testing.assert_allclose(got, want, atol=2 ** -6 * np.abs(want).max())


def test_float32_is_the_plain_form_to_the_last_bits():
    share = moe_ops.ExpertShare(0, 8, 8)
    ks = jax.random.split(jax.random.key(7), 4)
    experts = _experts(ks[0], 8, 128, 256, jnp.float32)
    x = jax.random.normal(ks[1], (6, 128))
    idx = _uniform_choices(ks[2], 6, 8, 2)
    w = jax.random.uniform(ks[3], (6, 2), jnp.float32)
    got, want, read = _both(x, idx, w, experts, share)
    assert read == _non_empty(idx, share)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("chosen", [
    "every", "one", "none_at_the_head", "none_in_the_middle",
    "none_at_the_tail", "nobody"])
def test_which_experts_have_a_token(chosen):
    """``active`` lists the experts with a token in ascending order and
    repeats the last; experts without one, wherever they sit, add nothing
    and are not counted."""
    held, k = 8, 2
    share = moe_ops.ExpertShare(0, held, held)
    ks = jax.random.split(jax.random.key(11), 3)
    experts = _experts(ks[0], held, 128, 128, jnp.float32)
    pairs = {"every": [(0, 1), (2, 3), (4, 5), (6, 7)],
             "one": [(3, 3)] * 4,        # one expert takes every token
             "none_at_the_head": [(2, 3), (4, 5), (6, 7), (5, 2)],
             "none_in_the_middle": [(0, 1), (6, 7), (0, 7), (1, 6)],
             "none_at_the_tail": [(0, 1), (2, 3), (1, 2), (0, 3)],
             "nobody": [(-1, -1)] * 4}[chosen]
    idx = jnp.asarray(pairs, jnp.int32)
    if chosen == "one":      # the same expert twice is one assignment here
        idx = idx.at[:, 1].set(-1)
    x = jax.random.normal(ks[1], (4, 128))
    w = jax.random.uniform(ks[2], (4, k), jnp.float32, 0.2, 1.0)
    got, want, read = _both(x, idx, w, experts, share)
    assert read == len({e for p in np.asarray(idx) for e in p if e >= 0})
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    if chosen == "nobody":
        assert read == 0 and not got.any()


def test_a_share_of_16_of_128_ignores_what_is_held_elsewhere():
    """Experts 32..47 of 128 live here: an assignment to any other expert
    gets no weight and wakes no expert."""
    share = moe_ops.ExpertShare(32, 16, 128)
    ks = jax.random.split(jax.random.key(5), 4)
    experts = _experts(ks[0], 16, 128, 256, jnp.bfloat16)
    x = jax.random.normal(ks[1], (12, 128)).astype(jnp.bfloat16)
    idx = _uniform_choices(ks[2], 12, 128, 8)
    w = jax.random.uniform(ks[3], (12, 8), jnp.float32, 0.1, 1.0)
    got, want, read = _both(x, idx, w, experts, share)
    assert 0 < read == _non_empty(idx, share) < 16
    np.testing.assert_allclose(got, want, atol=2 ** -6 * np.abs(want).max())
    elsewhere = jnp.where((idx >= 32) & (idx < 48), 0, idx)   # all held elsewhere
    got, _, read = _both(x, elsewhere, w, experts, share)
    assert read == 0 and not got.any()


def test_the_active_list_is_ascending_with_its_tail_repeated():
    seen = {}

    def spy(x, w_te, active, n_active, experts):
        seen["active"], seen["n"] = active, n_active
        return jnp.zeros_like(x)

    share = moe_ops.ExpertShare(0, 8, 8)
    idx = jnp.asarray([[6, 1], [4, 1], [6, 4]], jnp.int32)
    moe_ops._share_apply_skipping(jnp.zeros((3, 128)), idx, jnp.ones((3, 2)),
                                  _experts(jax.random.key(0), 8, 128, 128,
                                           jnp.float32), share, kernel=spy)
    assert int(seen["n"]) == 3
    assert np.asarray(seen["active"]).tolist() == [1, 4, 6, 6, 6, 6, 6, 6]


@pytest.mark.parametrize("tokens,d,f,ok", [
    (16, 2048, 1536, True),      # GLM's decode step
    (64, 6144, 2048, True),      # K-EXAONE's (the shape test refuses it)
    (1, 128, 128, True),
    (16, 2048, 1500, False),     # f has no whole-lane tile
    (16, 2000, 1536, False),     # d not whole lanes
    (pallas_experts.MAX_TOKENS + 1, 2048, 1536, False),
    (16, 65536, 1536, False),    # three blocks of (d, 512) twice: over VMEM
])
def test_supports_reads_the_shapes(tokens, d, f, ok):
    assert pallas_experts.supports(tokens, d, f, jnp.bfloat16,
                                   interpret=True) is ok
    # off a TPU (the CPU these tests run on) it never runs uninterpreted
    assert pallas_experts.supports(tokens, d, f, jnp.bfloat16) is False


def test_a_refused_shape_raises():
    with pytest.raises(ValueError, match="cannot take"):
        pallas_experts.skipping_experts(
            jnp.zeros((4, 100)), jnp.zeros((4, 2)), jnp.zeros((2,), jnp.int32),
            jnp.zeros((), jnp.int32),
            {"gate": jnp.zeros((2, 100, 128)), "up": jnp.zeros((2, 100, 128)),
             "down": jnp.zeros((2, 128, 100))}, interpret=True)


def test_tile_of_is_the_largest_whole_lane_divisor():
    assert pallas_experts.tile_of(1536, 512) == 512
    assert pallas_experts.tile_of(1536, 1024) == 768
    assert pallas_experts.tile_of(2048, 512) == 512
    assert pallas_experts.tile_of(384, 512) == 384
    assert pallas_experts.tile_of(1500, 512) == 0
