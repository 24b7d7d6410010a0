"""``ops/ssm.py``: the Mamba-2 recurrence in its two forms and the causal
depthwise convolution in front of it, against the equations written out
token by token in numpy.  Tiny sizes, float32, CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.ops import ssm

B, H, P, G, N = 2, 6, 8, 2, 16


def _inputs(l, seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        x=rng.normal(size=(B, l, H, P)).astype(np.float32),
        dt=rng.uniform(0.005, 0.6, size=(B, l, H)).astype(np.float32),
        A=-rng.uniform(1, 16, size=(H,)).astype(np.float32),
        B=rng.normal(size=(B, l, G, N)).astype(np.float32),
        C=rng.normal(size=(B, l, G, N)).astype(np.float32),
        D=rng.normal(size=(H,)).astype(np.float32))


def recurrence(v, lengths):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t, one
    position after another in float64 -> (y, the state after each
    sequence's own last position)."""
    b, l = v["x"].shape[:2]
    y = np.zeros((b, l, H, P))
    last = np.zeros((b, H, P, N))
    for i in range(b):
        s = np.zeros((H, P, N))
        for t in range(int(lengths[i])):
            for h in range(H):
                g = h // (H // G)
                s[h] = (np.exp(v["dt"][i, t, h] * v["A"][h]) * s[h]
                        + v["dt"][i, t, h] * np.outer(v["x"][i, t, h],
                                                      v["B"][i, t, g]))
                y[i, t, h] = s[h] @ v["C"][i, t, g] + v["D"][h] * v["x"][i, t, h]
        last[i] = s
    return y, last


@pytest.mark.parametrize("l,chunk,lengths", [
    (40, 8, (40, 23)),      # a whole bucket; a length that is no whole chunk
    (24, 8, (1, 17)),       # one token; two chunks and one position
    (16, 16, (16, 9)),      # one chunk
    (12, 128, (12, 5)),     # a bucket shorter than the chunk
], ids=["40by8", "24by8", "one-chunk", "short-bucket"])
def test_chunked_is_the_recurrence_at_lengths_that_are_not_whole_chunks(
        l, chunk, lengths):
    v = _inputs(l)
    want_y, want_s = recurrence(v, lengths)
    y, s = ssm.ssd_chunked(*(jnp.asarray(v[k]) for k in "x dt A B C D".split()),
                           jnp.asarray(lengths), chunk=chunk)
    assert s.dtype == jnp.float32 and y.shape == (B, l, H, P)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[i, :n], want_y[i, :n],
                                   atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5, rtol=2e-4)


def test_chunked_refuses_a_bucket_that_is_no_multiple_of_the_chunk():
    v = _inputs(20)
    with pytest.raises(ValueError, match="not a multiple of the state-space chunk"):
        ssm.ssd_chunked(*(jnp.asarray(v[k]) for k in "x dt A B C D".split()),
                        jnp.asarray([20, 20]), chunk=8)


def test_padding_advances_nothing():
    """The same prompt alone in a bucket of its own length and right-padded
    into a longer one beside another prompt: the same state, to the bit of
    float32's rounding, and garbage in the padding changes nothing."""
    v = _inputs(32)
    args = lambda d, sl: [jnp.asarray(d[k][sl] if d[k].ndim > 1 else d[k])  # noqa: E731
                          for k in "x dt A B C D".split()]
    _, alone = ssm.ssd_chunked(*args(v, np.s_[1:2, :16]), jnp.asarray([16]),
                               chunk=8)
    loud = {k: a.copy() for k, a in v.items()}
    loud["x"][1, 16:] = 1e6
    loud["dt"][1, 16:] = 50.0
    _, padded = ssm.ssd_chunked(*args(loud, np.s_[:]), jnp.asarray([32, 16]),
                                chunk=8)
    np.testing.assert_allclose(np.asarray(padded)[1], np.asarray(alone)[0],
                               atol=1e-6, rtol=1e-6)


def test_step_continues_what_chunked_left():
    """Prefill of 19 positions, then 5 single steps: the recurrence over all
    24, and an inactive slot keeps its state."""
    v = _inputs(24)
    want_y, want_s = recurrence(v, (24, 24))
    j = lambda k, sl: jnp.asarray(v[k][sl])  # noqa: E731
    _, s = ssm.ssd_chunked(j("x", np.s_[:]), j("dt", np.s_[:]),
                           jnp.asarray(v["A"]), j("B", np.s_[:]),
                           j("C", np.s_[:]), jnp.asarray(v["D"]),
                           jnp.asarray([19, 19]), chunk=8)
    active = jnp.asarray([True, False])
    kept = np.asarray(s)[1].copy()
    for t in range(19, 24):
        at = np.s_[:, t]
        y, s = ssm.ssd_step(s, j("x", at), j("dt", at), jnp.asarray(v["A"]),
                            j("B", at), j("C", at), jnp.asarray(v["D"]), active)
        np.testing.assert_allclose(np.asarray(y)[0], want_y[0, t], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s)[0], want_s[0], atol=2e-5, rtol=2e-4)
    assert (np.asarray(s)[1] == kept).all()
    assert s.dtype == jnp.float32


def test_step_keeps_the_state_s_dtype_and_rounds_only_on_the_way_out():
    v = _inputs(1)
    s16 = jnp.ones((B, H, P, N), jnp.bfloat16)
    at = np.s_[:, 0]
    _, out = ssm.ssd_step(s16, *(jnp.asarray(v[k][at]) for k in ("x", "dt")),
                          jnp.asarray(v["A"]), jnp.asarray(v["B"][at]),
                          jnp.asarray(v["C"][at]), jnp.asarray(v["D"]))
    assert out.dtype == jnp.bfloat16     # a control's cache; the model's is float32


# -- the convolution ------------------------------------------------------
def _conv_inputs(l, c=10, k=4, seed=1):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(B, l, c)).astype(np.float32),
            rng.normal(size=(c, k)).astype(np.float32),
            rng.normal(size=(c,)).astype(np.float32))


def test_conv_is_causal_depthwise_with_the_current_position_last():
    x, w, b = _conv_inputs(9)
    y = np.asarray(ssm.conv1d_causal(jnp.asarray(x), jnp.asarray(w),
                                     jnp.asarray(b)))
    for t in range(9):
        want = b.copy()
        for j in range(4):
            if t - 3 + j >= 0:
                want = want + w[:, j] * x[:, t - 3 + j]
        np.testing.assert_allclose(y[:, t], want, atol=1e-5)


@pytest.mark.parametrize("lengths", [(9, 5), (2, 1), (3, 9)],
                         ids=["9-5", "shorter-than-the-tail", "3-9"])
def test_the_tail_is_gathered_at_each_length_and_a_step_continues_it(lengths):
    """``conv_tail`` at lengths (n0, n1) of right-padded prompts, then one
    step with the next input: the convolution over the unpadded sequence at
    that position."""
    x, w, b = _conv_inputs(10)
    tail = ssm.conv_tail(jnp.asarray(x), jnp.asarray(lengths), 4)
    assert tail.shape == (B, 10, 3)
    for i, n in enumerate(lengths):
        for j in range(3):
            at = n - 3 + j
            want = x[i, at] if at >= 0 else np.zeros(10)
            np.testing.assert_array_equal(np.asarray(tail)[i, :, j], want)
    nxt = np.random.default_rng(2).normal(size=(B, 10)).astype(np.float32)
    y, moved = ssm.conv1d_step(tail, jnp.asarray(nxt), jnp.asarray(w),
                               jnp.asarray(b))
    for i, n in enumerate(lengths):
        seq = np.concatenate([x[i, :n], nxt[i:i + 1]])[None]
        full = ssm.conv1d_causal(jnp.asarray(seq), jnp.asarray(w), jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(y)[i], np.asarray(full)[0, -1],
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(moved)[i, :, -1], nxt[i])
        np.testing.assert_array_equal(np.asarray(moved)[i, :, 0],
                                      np.asarray(tail)[i, :, 1])
