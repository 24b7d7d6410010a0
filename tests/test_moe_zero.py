"""Zero-compute experts and softmax routing in ``ops/moe.py``: a router wider
than its routed experts (``ExpertShare.zero`` identity experts behind them)
through all three forms of the held experts' product, each against a plain
oracle that walks the tokens one by one; the forms' estimates (``share_form``'s
idle share, ``sorted_rows``' even share) taken over the router's WIDTH."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu.models import lm_blocks as lb
from can_tpu.ops import moe as moe_ops

from lm_tiny import interpret_skipping_experts

D, F = 128, 128
# experts 4..7 of 16 routed live here; 8 identity experts behind the 16
SHARE = moe_ops.ExpertShare(4, 4, 16, 8)


class _Cfg:
    num_experts_per_tok = 3
    routed_scaling_factor = 6.0
    norm_topk_prob = False
    scoring_func = "softmax"
    share = SHARE
    hidden_size, moe_intermediate_size = D, F


def _layer(tokens, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    p = {"router": jax.random.normal(ks[0], (D, SHARE.width)) * 2 * D ** -0.5,
         "bias": 0.005 * jax.random.normal(ks[1], (SHARE.width,)),
         "experts": {
             "gate": jax.random.normal(ks[2], (SHARE.held, D, F)) * D ** -0.5,
             "up": jax.random.normal(ks[3], (SHARE.held, D, F)) * D ** -0.5,
             "down": jax.random.normal(ks[4], (SHARE.held, F, D)) * F ** -0.5}}
    return p, jax.random.normal(ks[5], (tokens, D))


def _oracle(p, x):
    """Token by token, choice by choice: -> (the layer's output, zero
    choices, assignments on each held expert)."""
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.softmax(jnp.dot(x, p["router"]), -1), np.float64)
        x64 = np.asarray(x, np.float64)
        e = {k: np.asarray(v, np.float64) for k, v in p["experts"].items()}
    out, zero, held = np.zeros_like(x64), 0, np.zeros(SHARE.held, int)
    for t in range(x.shape[0]):
        chosen = np.argsort(-(s[t] + np.asarray(p["bias"], np.float64)))[:3]
        for i in chosen:
            w = 6.0 * s[t, i]
            if i >= SHARE.total:
                out[t] += w * x64[t]
                zero += 1
            elif SHARE.first <= i < SHARE.first + SHARE.held:
                j = i - SHARE.first
                g = x64[t] @ e["gate"][j]
                out[t] += w * ((g / (1 + np.exp(-g)) * (x64[t] @ e["up"][j]))
                               @ e["down"][j])
                held[j] += 1
    return out, zero, held


@pytest.mark.parametrize("form,tokens", [("skipping", 4), ("batched", 64),
                                         ("sorted", 1024)])
def test_every_form_adds_the_identity_term_and_holds_it_nowhere(
        monkeypatch, form, tokens):
    interpret_skipping_experts(monkeypatch)
    assert moe_ops.share_form(tokens, 3, SHARE, D, F, jnp.float32) == form
    p, x = _layer(tokens)
    with jax.default_matmul_precision("highest"):
        out, routed = lb.expert_layer(p, x, _Cfg)
    want, zero, held = _oracle(p, x)
    np.testing.assert_allclose(np.asarray(out), want, atol=5e-5, rtol=5e-5)
    assert int(moe_ops.zero_counts(routed.idx, SHARE)) == zero > 0
    assert moe_ops.held_counts(routed.idx, SHARE).tolist() == held.tolist()
    assert (routed.read is not None) == (form == "skipping")
    assert (routed.passes is not None) == (form == "sorted")
    if form == "skipping":      # a zero choice wakes no expert
        assert int(routed.read) == int((held > 0).sum())


def test_softmax_scores_are_over_every_output_and_not_renormalised():
    p, x = _layer(32, seed=3)
    idx, w = moe_ops.route(x, p["router"], p["bias"], top_k=3, scale=6.0,
                           normalize=False, scoring="softmax")
    scores = jax.nn.softmax(jnp.dot(x, p["router"],
                                    precision=jax.lax.Precision.HIGHEST), -1)
    np.testing.assert_allclose(np.asarray(jnp.sum(scores, -1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(w), 6 * np.asarray(jnp.take_along_axis(scores, idx, -1)),
        rtol=1e-6)
    assert int(idx.max()) >= SHARE.total       # identity experts are chosen
    _, normed = moe_ops.route(x, p["router"], p["bias"], top_k=3, scale=6.0,
                              normalize=True, scoring="softmax")
    np.testing.assert_allclose(np.asarray(jnp.sum(normed, -1)), 6.0, rtol=1e-6)
    with pytest.raises(ValueError, match="unknown router scoring"):
        moe_ops.route(x, p["router"], p["bias"], top_k=3, scale=1.0,
                      scoring="tanh")


def test_the_identity_weight_is_the_chosen_zero_experts_sum():
    idx = jnp.asarray([[0, 16, 23], [5, 6, 7], [17, 18, 19], [-1, -1, -1]])
    w = jnp.asarray([[1.0, 2.0, 4.0]] * 4)
    assert moe_ops.zero_weight(idx, w, SHARE).tolist() == [6.0, 0.0, 7.0, 0.0]
    assert int(moe_ops.zero_counts(idx, SHARE)) == 5
    assert moe_ops.held_counts(idx, SHARE).tolist() == [0, 1, 1, 1]


@pytest.mark.parametrize("share,width", [
    (moe_ops.ExpertShare(0, 16, 512, 256), 768),
    (moe_ops.ExpertShare(0, 16, 128), 128)])
def test_the_estimates_are_over_the_routers_width(share, width):
    """LongCat's share: 12 of 768 a choice, not 12 of 512: 8,192 tokens' even
    share is 2,048 rows (a buffer of 4,096), and 256 tokens leave a held
    expert idle with probability (1 - 12 / 768) ** 256 = 1.8%: batched.  A
    share without identity experts reads as it did."""
    assert share.width == width
    k = 12 if share.zero else 8
    even = 8192 * k * share.held / width
    assert moe_ops.sorted_rows(8192, k, share) == 2 * even
    assert moe_ops.share_form(256, k, share, 6144, 2048, jnp.bfloat16) == "batched"
    assert moe_ops.share_form(8192, k, share, 6144, 2048, jnp.bfloat16) == "sorted"
    idle = (1 - k / width) ** 256
    assert idle < moe_ops.SKIP_MIN_IDLE
