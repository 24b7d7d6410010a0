"""K-EXAONE (``models/exaone_moe.py``, ``ops/moe.py``, ``ops/attention.py``)
against the plain reference (``testing/exaone_moe_ref.py``) at the tiny
preset of ``tests/lm_tiny.py``, float32 on the CPU so that the comparison
is tight enough to see a wrong index, and once in bfloat16."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.models import exaone_moe as em
from can_tpu.models import lm_blocks as lb
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import moe as moe_ops
from can_tpu.testing import exaone_moe_ref as ref

from lm_tiny import tiny_config, tiny_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKET, NEW = 32, 12


def _prompts(lengths, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((len(lengths), BUCKET), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, vocab, n)
    return tokens, np.asarray(lengths, np.int32)


@pytest.fixture(scope="module")
def generated():
    """Prefill + 12 decode steps of three prompts of unequal length in one
    padded batch (32, 19 and 9 tokens: the longest is four windows long, so
    every ring has wrapped before decoding starts and wraps again during
    it), with the logits of every step and the reference's full forward
    over each finished sequence."""
    d, cfg, params = tiny_model(0, mtp=0)
    spec = ref.spec_from_config(d)
    tokens, lengths = _prompts([32, 19, 9])
    prefill = jax.jit(em.prefill, static_argnums=(3, 4))
    step = jax.jit(em.decode_step, static_argnums=(4,))
    logits, cache, routing = prefill(params, tokens, lengths, cfg, BUCKET + NEW)
    seqs = [list(tokens[i, :n]) for i, n in enumerate(lengths)]
    per_step, choices = [np.asarray(logits)], [np.asarray(routing["choices"])]
    pos = jnp.asarray(lengths)
    for _ in range(NEW):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(len(seqs)):
            seqs[i].append(int(tok[i]))
        logits, cache, routing = step(params, cache, tok, pos, cfg)
        per_step.append(np.asarray(logits))
        choices.append(np.asarray(routing["choices"]))
        pos = pos + 1
    refs = [ref.forward(params, np.asarray(s), spec) for s in seqs]
    return lengths, per_step, choices, refs, cache


class TestPrefillDecodeAgainstReference:
    @pytest.mark.parametrize("step", range(NEW + 1))
    def test_logits_at_every_step(self, generated, step):
        """Step 0 is prefill's last position; step s feeds the token
        generated at s - 1 through both kinds of cache."""
        lengths, per_step, _, refs, _ = generated
        for i, n in enumerate(lengths):
            want = np.asarray(refs[i]["logits"][n - 1 + step])
            np.testing.assert_allclose(per_step[step][i], want, atol=2e-5,
                                       rtol=2e-5)

    def test_routing_choices_are_the_references(self, generated):
        lengths, _, choices, refs, _ = generated
        for step in (0, 1, NEW):
            for i, n in enumerate(lengths):
                for layer, chosen in enumerate(refs[i]["chosen"]):
                    assert (np.sort(choices[step][layer, i])
                            == np.sort(np.asarray(chosen[n - 1 + step]))).all()

    def test_cache_shapes_by_kind(self, generated):
        *_, cache = generated
        shapes = [e["k"].shape for e in cache["layers"]]
        ring, full = (3, 2, 8, 16), (3, 2, BUCKET + NEW, 16)
        assert shapes == [ring, ring, ring, full, ring]

    def test_ring_has_wrapped(self, generated):
        """The longest prompt ends at position 31 + 12: its window layers
        hold positions 36..43 in slots p % 8, nothing older."""
        held = np.asarray(attn_ops.ring_positions(jnp.asarray([43]), 8))[0]
        assert sorted(held) == list(range(36, 44))
        assert all(p % 8 == r for r, p in enumerate(held))


def test_bfloat16_runs_and_stays_near_the_reference():
    """The served dtype on the CPU: bfloat16 weights and activations give
    float32 logits near the float32 reference's on the same weights.  How
    near is the chip's to say (the benchmark's ``logit_gap_ratio``, against
    bfloat16's own gap): XLA:CPU rounds bfloat16 products otherwise, and at
    hidden size 64 one flipped routing choice is a tenth of a logit."""
    d, cfg, params = tiny_model(1, dtype=jnp.bfloat16, mtp=0)
    tokens, lengths = _prompts([24, 11, 32, 17], seed=1)
    logits, _, _ = jax.jit(em.prefill, static_argnums=(3, 4))(
        params, tokens, lengths, cfg, BUCKET + 4)
    assert logits.dtype == jnp.float32
    spec = ref.spec_from_config(d)
    for i, n in enumerate(lengths):
        want = np.asarray(ref.forward(params, tokens[i, :n], spec)["logits"][-1])
        err = np.linalg.norm(np.asarray(logits[i]) - want) / np.linalg.norm(want)
        assert err < 0.4, err


class TestExpertShare:
    def _layer(self, held, rank, x, seed=5):
        """One expert layer's routed part + shared expert on rank ``rank``
        of 8 // held, cut from ONE uncut layer's weights."""
        _, full_cfg, full = tiny_model(seed, mtp=0)
        p = dict(full["layers"][1]["moe"])
        lo = rank * held
        p["experts"] = {k: v[lo:lo + held] for k, v in p["experts"].items()}
        cfg = em.ExaoneMoeConfig.from_dict(tiny_config(held=held, rank=rank))
        return lb.expert_layer(p, x, cfg)[0], lb.swiglu(x, p["shared"]), full

    @pytest.mark.parametrize("held", [1, 2, 4])
    def test_shares_of_all_ranks_add_up_to_the_uncut_layer(self, held):
        """Every rank's routed part, with the shared expert (which every
        rank computes alike) counted once, is the uncut reference's layer."""
        x = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
        total = 0.0
        for rank in range(8 // held):
            y, shared, full = self._layer(held, rank, x)
            total = total + (y - shared)
        total = total + shared
        d = tiny_config()
        want, _ = ref._experts(full["layers"][1]["moe"], x,
                               ref.spec_from_config(d), "f32", None)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_no_token_dropped_when_every_token_routes_to_one_expert(self):
        """A bias that sends every token to experts 0 and 1, both held
        here: every assignment lands on this chip (2 T rows, the sorted
        buffer's whole room) and none is lost."""
        _, cfg, params = tiny_model(3, mtp=0)
        p = dict(params["layers"][1]["moe"])
        p["bias"] = jnp.zeros((8,)).at[:2].set(10.0)
        share = moe_ops.ExpertShare(0, 2, 8)
        p["experts"] = {k: v[:2] for k, v in p["experts"].items()}
        x = jax.random.normal(jax.random.key(4), (33, 64), jnp.float32)
        idx, w = moe_ops.route(x, p["router"], p["bias"], top_k=2, scale=2.5)
        assert set(np.asarray(idx).ravel()) == {0, 1}
        assert np.asarray(moe_ops.held_counts(idx, share)).tolist() == [33, 33]
        got = moe_ops._share_apply_sorted(x, idx, w, p["experts"], share)
        np.testing.assert_allclose(
            np.asarray(moe_ops._share_apply_batched(x, idx, w, p["experts"], share)),
            np.asarray(got), atol=2e-5, rtol=2e-5)
        want = 0.0
        for e in range(2):
            one = {k: v[e] for k, v in p["experts"].items()}
            w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
            want = want + w_e[:, None] * lb.swiglu(x, one)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("tokens", [7, 64, 200])
    def test_sorted_and_batched_products_agree(self, tokens, monkeypatch):
        """The two forms of the grouped product (many tokens: sorted by
        expert; few: every held expert on every token) are one function."""
        _, cfg, params = tiny_model(6, mtp=0)
        p = params["layers"][2]["moe"]
        share = moe_ops.ExpertShare(2, 4, 8)
        experts = {k: v[2:6] for k, v in p["experts"].items()}
        x = jax.random.normal(jax.random.key(tokens), (tokens, 64), jnp.float32)
        idx, w = moe_ops.route(x, p["router"], p["bias"], top_k=2, scale=2.5)
        a = moe_ops._share_apply_sorted(x, idx, w, experts, share)
        b = moe_ops._share_apply_batched(x, idx, w, experts, share)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)
        monkeypatch.setattr(moe_ops, "DENSE_MAX_TOKENS", 64)
        c = moe_ops.share_apply(x, idx, w, experts, share)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a), atol=2e-5,
                                   rtol=2e-5)

    def test_nothing_routed_here_is_zero(self):
        _, cfg, params = tiny_model(3, mtp=0)
        p = params["layers"][1]["moe"]
        share = moe_ops.ExpertShare(6, 2, 8)
        x = jax.random.normal(jax.random.key(4), (9, 64), jnp.float32)
        idx = jnp.zeros((9, 2), jnp.int32).at[:, 1].set(1)
        w = jnp.ones((9, 2))
        experts = {k: v[6:] for k, v in p["experts"].items()}
        got = moe_ops.share_apply(x, idx, w, experts, share)
        assert float(jnp.abs(got).max()) == 0.0

    def test_router_weights(self):
        """Chosen by score + bias, weighted by the scores alone, normalised
        over the chosen and scaled by 2.5."""
        x = jax.random.normal(jax.random.key(0), (5, 64), jnp.float32)
        wr = jax.random.normal(jax.random.key(1), (64, 8)) / 8.0
        bias = jnp.zeros((8,)).at[7].set(5.0)
        idx, w = moe_ops.route(x, wr, bias, top_k=2, scale=2.5)
        assert (np.asarray(idx) == 7).any(axis=1).all()
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
        s = jax.nn.sigmoid(x @ wr)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(2.5 * jnp.take_along_axis(s, idx, -1)
                                      / jnp.take_along_axis(s, idx, -1).sum(-1, keepdims=True)),
            rtol=1e-5)


class TestAttention:
    def _qkv(self, l=32, seed=0):
        k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(k1, (2, l, 2, 2, 16))
        k = jax.random.normal(k2, (2, l, 2, 16))
        v = jax.random.normal(k3, (2, l, 2, 16))
        return q, k, v

    def _dense(self, q, k, v, window=None):
        l = q.shape[1]
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / 4.0
        i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
        mask = j <= i
        if window:
            mask &= i - j < window
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    @pytest.mark.parametrize("block", [8, 16, 32])
    def test_prefill_full_in_blocks(self, block):
        q, k, v = self._qkv()
        np.testing.assert_allclose(
            np.asarray(attn_ops.prefill_full(q, k, v, block=block)),
            np.asarray(self._dense(q, k, v)), atol=1e-5)

    @pytest.mark.parametrize("window", [4, 8, 16])
    def test_prefill_window(self, window):
        q, k, v = self._qkv()
        np.testing.assert_allclose(
            np.asarray(attn_ops.prefill_window(q, k, v, window=window)),
            np.asarray(self._dense(q, k, v, window)), atol=1e-5)

    def test_rope_is_relative(self):
        """q.k after rotation depends on the distance alone."""
        q = jax.random.normal(jax.random.key(0), (1, 1, 1, 16))
        k = jax.random.normal(jax.random.key(1), (1, 1, 1, 16))
        dots = []
        for a, b in ((3, 1), (10, 8), (1000, 998)):
            qa = attn_ops.rope(q, jnp.asarray([[a]]), 1e6)
            kb = attn_ops.rope(k, jnp.asarray([[b]]), 1e6)
            dots.append(float(jnp.sum(qa * kb)))
        np.testing.assert_allclose(dots, dots[0], rtol=1e-4)


def test_mtp_module_against_reference():
    """h' = W_p [RMSNorm(h_t); RMSNorm(Emb(x_{t+1}))], one full-attention
    block with an expert layer, the module's norm, the shared head."""
    d, cfg, params = tiny_model(2, mtp=1)
    assert "mtp" in params
    spec = ref.spec_from_config(d)
    tokens, lengths = _prompts([32], seed=2)
    hidden, _, _ = em.prefill_hidden(params, tokens, lengths, cfg)
    nxt = np.roll(tokens, -1, axis=1)
    got = em.mtp_logits(params, hidden, nxt, cfg)
    r = ref.forward(params, tokens[0], spec)
    np.testing.assert_allclose(np.asarray(hidden[0]), np.asarray(r["hidden"]),
                               atol=2e-5, rtol=2e-5)
    want = ref.mtp_forward(params, r["hidden"], nxt[0], spec)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


def test_reference_copies_are_the_same_text():
    """``can_tpu/testing/`` and ``benchmark/reference/`` hold one reference:
    the same text below their docstrings."""
    def body(path):
        text = open(os.path.join(ROOT, path)).read()
        assert text.startswith('"""')
        return text[text.index('"""', 3) + 3:]

    assert (body("can_tpu/testing/exaone_moe_ref.py")
            == body("benchmark/reference/exaone_moe_ref.py"))


@pytest.mark.parametrize("variant", ["window+1", "rope_on_full",
                                     "unnormalised_topk", "expert_zeroed"])
def test_reference_variants_change_the_answer(variant):
    """Each control of the calibration breaks the mathematics for real."""
    d, cfg, params = tiny_model(0, mtp=0)
    spec = ref.spec_from_config(d)
    tokens, _ = _prompts([32])
    a = ref.forward(params, tokens[0], spec)["logits"]
    b = ref.forward(params, tokens[0], spec, variant=variant)["logits"]
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_published_configuration_counts():
    """The benchmark's configuration: 3,712 M parameters held (7.42 GB in
    bfloat16), 16 of 128 experts, 19,200 of 153,600 rows, layers L L L G L."""
    cfg = em.ExaoneMoeConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-ep8-serve-bf16.json"))
    assert em.param_count(cfg) == 3_712_028_416
    assert cfg.share == (0, 16, 128) and cfg.vocab == (0, 19200, 153600)
    assert [t == em.WINDOW for t in cfg.layer_types] == [True, True, True,
                                                         False, True]
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert cfg.mtp_layers == 0
