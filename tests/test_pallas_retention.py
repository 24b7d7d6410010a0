"""``ops/pallas_retention.py``: the fused decode step of power retention
(interpreted on the CPU) against the plain form of
``ops/retention.py::power_retention_step``, its oracle, and against the
quadratic definition; heads of 128 (the kernel takes no other), 2-3 slots,
2 key heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.ops import pallas_retention as pr
from can_tpu.ops import retention as ret

from lm_tiny import interpret_fused_retention
from test_retention import quadratic

B, KV, D, DV = 3, 2, 128, 128
ROWS = ret.state_rows(D)


def _sequences(g, l, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, l, KV, g, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, l, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, l, KV, DV), jnp.float32).astype(dtype)
    log_g = jax.nn.log_sigmoid(2.0 + jax.random.normal(ks[3], (B, l, KV)))
    return q, k, v, log_g


def _after_a_prompt(g, l=9, seed=0, lengths=(8, 5, 3), dtype=jnp.float32):
    """The state ``l - 1`` positions of prompt leave (each slot at its own
    length) and the inputs of the position after."""
    q, k, v, log_g = _sequences(g, l, seed, dtype)
    _, S, z = ret.power_retention_chunked(
        q[:, :-1], k[:, :-1], v[:, :-1], log_g[:, :-1],
        jnp.asarray(lengths, jnp.int32), chunk=l - 1)
    return S, z, (q[:, -1], k[:, -1], v[:, -1], log_g[:, -1])


def _fused(S, z, q, k, v, log_g, active=None):
    """The kernel as ``power_retention_step`` calls it: -> (y, S, z)."""
    active = jnp.ones(S.shape[:1], bool) if active is None else active
    num, den, S, z = pr.fused_step(S, z, q, k, v, jnp.exp(log_g), active,
                                   interpret=True)
    return ret._normalised(num, den, q.dtype), S, z


@pytest.mark.parametrize("g", [5, 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_is_the_plain_form(g, dtype):
    S, z, one = _after_a_prompt(g, seed=g, dtype=dtype)
    num, den, S1, z1 = pr.fused_step(S, z, *one[:3], jnp.exp(one[3]),
                                     jnp.ones((B,), bool), interpret=True)
    y = ret._normalised(num, den, dtype)
    y0, S0, z0 = jax.jit(ret._step_plain)(S, z, *one)
    assert (y.dtype, S1.dtype, z1.dtype) == (dtype, jnp.float32, jnp.float32)
    assert y.shape == (B, KV, g, DV) and S1.shape == S.shape
    # the update is the same arithmetic (a multiply and an add contracted
    # or not: an ulp); phi is the same numbers
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=3e-7,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z0), rtol=3e-7,
                               atol=1e-7)
    # the query, against float64 on the state the kernel wrote: float32's
    # rounding on the scale of what is summed (a normaliser can be a small
    # difference of large terms: the quotient then carries both forms' noise)
    pq = np.asarray(ret.phi(one[0]), np.float64)
    S64, z64 = np.asarray(S1, np.float64), np.asarray(z1, np.float64)
    scale = np.einsum("bkgm,bkvm->bkgv", np.abs(pq), np.abs(S64))
    assert (np.abs(num - np.einsum("bkgm,bkvm->bkgv", pq, S64))
            <= 2e-6 * scale).all()
    assert (np.abs(den - np.einsum("bkgm,bkm->bkg", pq, z64))
            <= 2e-6 * np.einsum("bkgm,bkm->bkg", np.abs(pq), np.abs(z64))).all()
    tol = dict(rtol=1e-3, atol=1e-3) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y0, np.float32), **tol)
    assert np.abs(np.asarray(y0, np.float32)).max() > 0.3


def test_an_inactive_slot_keeps_its_state_bit_for_bit():
    """Its neighbours move; a dead slot's ``v`` of NaN touches nothing (a
    select on ``active``, not a gate of one and a key of zero)."""
    S, z, (q, k, v, log_g) = _after_a_prompt(5, seed=4)
    active = jnp.asarray((True, False, True))
    v = v.at[1].set(jnp.nan)
    y, S1, z1 = _fused(S, z, q, k, v, log_g, active)
    assert np.array_equal(np.asarray(S1)[1], np.asarray(S)[1])
    assert np.array_equal(np.asarray(z1)[1], np.asarray(z)[1])
    assert not np.asarray(y)[1].any()            # answered with zeros
    y0, S0, z0 = jax.jit(ret._step_plain)(S, z, q, k, v, log_g, active)
    for slot in (0, 2):
        assert not np.array_equal(np.asarray(S1)[slot], np.asarray(S)[slot])
        assert not np.array_equal(np.asarray(z1)[slot], np.asarray(z)[slot])
        np.testing.assert_allclose(np.asarray(S1)[slot], np.asarray(S0)[slot],
                                   rtol=3e-7, atol=1e-7)
        np.testing.assert_allclose(np.asarray(y)[slot], np.asarray(y0)[slot],
                                   rtol=1e-3, atol=1e-3)
    assert np.isfinite(np.asarray(S1)).all() and np.isfinite(np.asarray(y)).all()


def test_a_prompt_then_12_fused_steps_continue_the_quadratic_definition():
    """A ragged batch: each slot steps while it has positions left, then
    keeps its state."""
    prompt, new, g = 6, 12, 5
    q, k, v, log_g = _sequences(g, prompt + new, seed=7)
    lengths = np.asarray((prompt + new, prompt + 7, prompt + 2))
    _, S, z = ret.power_retention_chunked(
        q[:, :prompt], k[:, :prompt], v[:, :prompt], log_g[:, :prompt],
        jnp.full((B,), prompt, jnp.int32), chunk=prompt)
    step = jax.jit(_fused)
    ys = []
    for t in range(prompt, prompt + new):
        y, S, z = step(S, z, q[:, t], k[:, t], v[:, t], log_g[:, t],
                       jnp.asarray(t < lengths))
        ys.append(y)
    got, want = np.stack(ys, 1), quadratic(q, k, v, log_g)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(got[i, :n - prompt], want[i, prompt:n],
                                   rtol=2e-4, atol=2e-5)
    # the slots that stopped early are where stepping alone would have left
    # them (the plain form, from the same prompt's state)
    _, S0, z0 = ret.power_retention_chunked(
        q, k, v, log_g, jnp.asarray(lengths, jnp.int32), chunk=prompt + new)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S0), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z0), rtol=2e-4,
                               atol=2e-5)


def _read_of_a_fine_state():
    """A state of ``1 + n 2^-12``, which bfloat16 rounds to 1, left as it is
    by a step (a gate of one, a key of zero) and queried: the gap between
    the kernel's numerator and float64's, over the sum of the weights'
    magnitudes, for head 0 (whose weights' signs the ``n`` follow, so that
    what a rounded state loses adds up)."""
    ks = jax.random.split(jax.random.key(48), 3)
    q = jax.random.normal(ks[0], (B, KV, 5, D), jnp.float32)
    pq = np.asarray(ret.phi(q), np.float64)                  # (B, KV, G, R)
    n = np.sign(pq[:, :, 0])[:, :, None, :] * (1 + np.arange(DV) % 8)[:, None]
    S = jnp.asarray(1.0 + n * 2.0 ** -12, jnp.float32)       # (B, KV, dv, R)
    assert not np.array_equal(np.asarray(S.astype(jnp.bfloat16), np.float32),
                              np.asarray(S))
    z = jnp.ones((B, KV, ROWS), jnp.float32)
    v = jax.random.normal(ks[1], (B, KV, DV), jnp.float32)
    num, _, S1, _ = pr.fused_step(
        S, z, q, jnp.zeros((B, KV, D), jnp.float32), v,
        jnp.ones((B, KV), jnp.float32), jnp.ones((B,), bool), interpret=True)
    assert np.array_equal(np.asarray(S1), np.asarray(S))
    want = np.einsum("bkgm,bkvm->bkgv", pq, np.asarray(S, np.float64))
    return (np.abs(np.asarray(num, np.float64) - want)[:, :, 0]
            / np.abs(pq[:, :, 0]).sum(-1)[..., None]).max()


def test_the_kernel_reads_its_state_as_float32():
    """What the plain form's text says with HIGHEST
    (``tests/test_retention.py``) the kernel's body cannot say in a
    ``dot_general`` line: held by the numbers."""
    assert _read_of_a_fine_state() < 1e-6


def test_a_body_that_rounds_the_state_to_bfloat16_is_caught(monkeypatch):
    monkeypatch.setattr(pr, "_term", lambda p, s: p * s.astype(
        jnp.bfloat16).astype(jnp.float32))
    # ``fused_step`` is a ``jit``: neither the sound trace before this body
    # nor this body's after it
    pr.fused_step.clear_cache()
    try:
        gap = _read_of_a_fine_state()
    finally:
        pr.fused_step.clear_cache()
    assert 1e-4 < gap < 3e-3          # (1 .. 8) x 2^-12 of every weight lost


@pytest.mark.parametrize("S_shape,q_shape,dtype,why", [
    ((4, 2, 6, 40), (4, 2, 3, 8), jnp.float32, "the tiny preset's heads of 8"),
    ((4, 2, 7, 28), (4, 2, 3, 7), jnp.float32, "an odd head width"),
    ((4, 2, 64, ROWS), (4, 2, 5, 128), jnp.float32, "a dv that is no whole lane"),
    ((4, 2, 256, ret.state_rows(256)), (4, 2, 5, 256), jnp.float32,
     "heads of two rows of lanes"),
    ((4, 2, 128, ROWS), (4, 2, 5, 128), jnp.bfloat16, "a state kept in bfloat16"),
    ((4, 2, 128, ROWS + 128), (4, 2, 5, 128), jnp.float32, "rows of another layout"),
])
def test_supports_refuses_what_the_kernel_cannot_take(S_shape, q_shape, dtype,
                                                      why):
    assert not pr.supports(S_shape, q_shape, dtype, interpret=True), why
    with pytest.raises(ValueError, match="fused_step cannot take"):
        b, kv, dv, rows = S_shape
        pr.fused_step(jnp.zeros(S_shape, dtype), jnp.zeros((b, kv, rows), dtype),
                      jnp.zeros(q_shape), jnp.zeros(q_shape[:2] + q_shape[3:]),
                      jnp.zeros((b, kv, dv)), jnp.ones((b, kv)),
                      jnp.ones((b,), bool), interpret=True)


def test_supports_asks_the_backend_and_nothing_else(monkeypatch):
    cell = ((16, 8, 128, ROWS), (16, 8, 5, 128), jnp.float32)
    assert not pr.supports(*cell)                 # the CPU, not interpreted
    assert pr.supports(*cell, interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pr.supports(*cell)
    assert pr.supports((16, 8, 128, ROWS), (16, 8, 1, 128), jnp.float32)
    assert not pr.supports((16, 8, 128, ROWS), (16, 8, 5, 128), jnp.bfloat16)
    # a group too large for the VMEM budget's scratch is a shape like another
    assert not pr.supports((16, 4096, 128, ROWS), (16, 4096, 5, 128), jnp.float32)


def test_the_step_takes_the_kernel_where_supports_says_yes(monkeypatch):
    S, z, one = _after_a_prompt(5, seed=9)
    active = jnp.asarray((True, True, False))
    assert ret.step_form(S, one[0]) == "step"      # the CPU
    want = ret.power_retention_step(S, z, *one, active)
    interpret_fused_retention(monkeypatch)
    assert ret.step_form(S, one[0]) == "fused"
    assert ret.step_form(S.astype(jnp.bfloat16), one[0]) == "step"
    got = ret.power_retention_step(S, z, *one, active)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-7,
                                   atol=1e-7)
    np.testing.assert_allclose(np.asarray(got[0])[:2], np.asarray(want[0])[:2],
                               rtol=1e-3, atol=1e-3)
    # no ``active``: every slot steps
    got = ret.power_retention_step(S, z, *one)
    want = ret._step_plain(S, z, *one)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-3, atol=1e-3)
    # the kernel's launch belongs to the part the plain form's fusions had
    text = jax.jit(ret.power_retention_step).lower(S, z, *one).as_text(
        debug_info=True)
    # (the kernel's call is an inner ``jit``, traced once a program)
    assert "ret.state/jit(fused_step)" in text
    assert "fused_retention_step/pallas_call" in text
