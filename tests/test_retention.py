"""``ops/retention.py``: the symmetric second power, and power retention's
two forms against each other and against the quadratic definition, float32,
tiny sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.ops import retention as ret

B, L, KV, G, D, DV = 3, 24, 2, 3, 8, 6
LENGTHS = (24, 13, 7)     # not multiples of any chunk below but the first


def _inputs(seed=0, lengths=LENGTHS, l=L):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, l, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, l, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, l, KV, DV), jnp.float32)
    log_g = jax.nn.log_sigmoid(2.0 + jax.random.normal(ks[3], (B, l, KV)))
    return q, k, v, log_g, jnp.asarray(lengths, jnp.int32)


def quadratic(q, k, v, log_g):
    """The definition over all pairs, one sequence at a time, in numpy
    float64: -> y (B, L, KV, G, DV)."""
    q, k, v, a = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    cum = np.cumsum(a, axis=1)                                   # (B, L, KV)
    s = np.einsum("bikgd,bjkd->bkgij", q, k) ** 2 / q.shape[-1]
    decay = np.exp(cum.transpose(0, 2, 1)[..., :, None]
                   - cum.transpose(0, 2, 1)[..., None, :])
    w = s * np.tril(np.ones(s.shape[-2:]))[None, None, None] * decay[:, :, None]
    return (np.einsum("bkgij,bjkv->bikgv", w, v)
            / w.sum(-1).transpose(0, 3, 1, 2)[..., None])


def stepped(q, k, v, log_g, lengths):
    """``power_retention_step`` from a zero state, position by position,
    each sequence active up to its own length: -> (y, S, z)."""
    S = jnp.zeros((B, KV, DV, ret.state_rows(D)), jnp.float32)
    z = jnp.zeros((B, KV, ret.state_rows(D)), jnp.float32)
    ys = []
    for t in range(q.shape[1]):
        y, S, z = ret.power_retention_step(S, z, q[:, t], k[:, t], v[:, t],
                                           log_g[:, t], t < lengths)
        ys.append(y)
    return jnp.stack(ys, 1), S, z


@pytest.mark.parametrize("d", [2, 8, 128])
def test_phi_is_the_square_of_the_scaled_dot_product(d):
    a, b = jax.random.normal(jax.random.key(d), (2, 5, d), jnp.float32)
    assert ret.phi(a).shape == (5, ret.state_rows(d))
    np.testing.assert_allclose(
        np.sum(np.asarray(ret.phi(a), np.float64) * np.asarray(ret.phi(b)), -1),
        np.sum(np.asarray(a, np.float64) * np.asarray(b), -1) ** 2 / d,
        rtol=2e-5, atol=1e-6)
    # every distinct monomial once: the rows that are not padding
    assert int(np.sum(np.asarray(ret.phi(jnp.ones((d,)))) > 0)) == d * (d + 1) // 2


def test_an_odd_head_width_is_refused():
    with pytest.raises(ValueError, match="odd"):
        ret.state_rows(7)


@pytest.mark.parametrize("chunk", [4, 8, 12, 24])
def test_chunked_is_the_quadratic_definition_for_a_ragged_batch(chunk):
    q, k, v, log_g, lengths = _inputs()
    y, _, _ = ret.power_retention_chunked(q, k, v, log_g, lengths, chunk=chunk)
    want = quadratic(q, k, v, log_g)
    for i, n in enumerate(LENGTHS):      # the valid rows of each sequence
        np.testing.assert_allclose(np.asarray(y)[i, :n], want[i, :n],
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("chunk", [4, 8, 24])
def test_chunked_hands_on_the_state_that_stepping_reaches(chunk):
    """The state after the last chunk is the state at each prompt's OWN
    length: what position-by-position steps reach when they stop there."""
    q, k, v, log_g, lengths = _inputs(1)
    y, S, z = ret.power_retention_chunked(q, k, v, log_g, lengths, chunk=chunk)
    ys, Ss, zs = stepped(q, k, v, log_g, lengths)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Ss), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zs), rtol=2e-4, atol=2e-5)
    for i, n in enumerate(LENGTHS):
        np.testing.assert_allclose(np.asarray(y)[i, :n], np.asarray(ys)[i, :n],
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("chunk", [6, 12])
def test_a_padded_and_an_unpadded_prompt_give_equal_states(chunk):
    q, k, v, log_g, _ = _inputs(2)
    short = jnp.asarray((12, 12, 12), jnp.int32)
    _, S, z = ret.power_retention_chunked(q, k, v, log_g, short, chunk=chunk)
    _, S0, z0 = ret.power_retention_chunked(
        q[:, :12], k[:, :12], v[:, :12], log_g[:, :12], short, chunk=chunk)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z0), rtol=1e-5, atol=1e-6)


def test_a_step_after_a_prompt_continues_the_quadratic_definition():
    q, k, v, log_g, _ = _inputs(3)
    full = jnp.full((B,), L - 1, jnp.int32)
    _, S, z = ret.power_retention_chunked(q[:, :-1], k[:, :-1], v[:, :-1],
                                          log_g[:, :-1], full, chunk=L - 1)
    y, _, _ = ret.power_retention_step(S, z, q[:, -1], k[:, -1], v[:, -1],
                                       log_g[:, -1])
    np.testing.assert_allclose(np.asarray(y), quadratic(q, k, v, log_g)[:, -1],
                               rtol=2e-4, atol=2e-5)


def test_an_inactive_slot_keeps_its_state():
    q, k, v, log_g, lengths = _inputs(4)
    _, S, z = ret.power_retention_chunked(q, k, v, log_g, lengths, chunk=8)
    active = jnp.asarray((True, False, True))
    _, S1, z1 = ret.power_retention_step(S, z, q[:, 0], k[:, 0], v[:, 0],
                                         log_g[:, 0], active)
    assert np.array_equal(np.asarray(S1)[1], np.asarray(S)[1])
    assert np.array_equal(np.asarray(z1)[1], np.asarray(z)[1])
    assert not np.array_equal(np.asarray(S1)[0], np.asarray(S)[0])


def test_the_state_stays_float32_whatever_the_inputs_are():
    q, k, v, log_g, lengths = _inputs(5)
    bf = lambda x: x.astype(jnp.bfloat16)   # noqa: E731
    y, S, z = ret.power_retention_chunked(bf(q), bf(k), bf(v), log_g, lengths,
                                          chunk=8)
    assert (y.dtype, S.dtype, z.dtype) == (jnp.bfloat16, jnp.float32, jnp.float32)
    y, S, z = ret.power_retention_step(S, z, bf(q[:, 0]), bf(k[:, 0]),
                                       bf(v[:, 0]), log_g[:, 0])
    assert (y.dtype, S.dtype, z.dtype) == (jnp.bfloat16, jnp.float32, jnp.float32)


def test_a_bucket_that_is_no_multiple_of_the_chunk_is_refused():
    q, k, v, log_g, lengths = _inputs()
    with pytest.raises(ValueError, match="multiple"):
        ret.power_retention_chunked(q, k, v, log_g, lengths, chunk=7)


def test_the_step_reads_its_state_at_the_highest_precision():
    """A float32 product at the default precision rounds its inputs to
    bfloat16 on a TPU: the state would be kept in float32 and read as
    bfloat16.  The query says HIGHEST in the program's text."""
    q, k, v, log_g, lengths = _inputs(6)
    _, S, z = ret.power_retention_chunked(q, k, v, log_g, lengths, chunk=8)
    text = jax.jit(ret.power_retention_step).lower(
        S, z, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0]).as_text()
    products = [l for l in text.splitlines() if "dot_general" in l]
    assert products and all("HIGHEST" in l for l in products), products
