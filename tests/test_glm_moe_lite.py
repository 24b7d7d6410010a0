"""GLM-4.7-Flash (``models/glm_moe_lite.py``; latent attention in
``ops/attention.py``, the expert layer in ``ops/moe.py``) against the plain
reference (``benchmark/reference/glm_moe_lite_ref.py``) at the tiny preset of
``tests/lm_tiny.py``, float32 on the CPU so that the comparison is tight
enough to see a wrong index, and once in bfloat16."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_moe_lite_ref as ref
from can_tpu.models import glm_moe_lite as gm
from can_tpu.models import lm_blocks as lb
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import moe as moe_ops

from lm_tiny import (interpret_skipping_experts, tiny_glm_config,
                     tiny_glm_model)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKET, NEW = 32, 12
VARIANTS = ["scale_nope", "no_kv_norm", "rope_on_nope", "unnormalised_topk",
            "expert_zeroed"]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Prefill attention in blocks of 8 positions: a bucket of 32 is four
    blocks of queries, the running softmax crosses up to three key blocks."""
    monkeypatch.setattr(gm, "PREFILL_BLOCK", 8)


def _prompts(lengths, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((len(lengths), BUCKET), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, vocab, n)
    return tokens, np.asarray(lengths, np.int32)


@pytest.fixture(scope="module")
def generated():
    """Prefill + 12 decode steps of three prompts of unequal length in one
    padded batch (32, 19 and 9 tokens: one, two and three of the four query
    blocks are past the prompt's end), with the logits of every step and the
    reference's full forward over each finished sequence."""
    block, gm.PREFILL_BLOCK = gm.PREFILL_BLOCK, 8
    try:
        d, cfg, params = tiny_glm_model(0, mtp=0)
        spec = ref.spec_from_config(d)
        tokens, lengths = _prompts([32, 19, 9])
        prefill = jax.jit(gm.prefill, static_argnums=(3, 4))
        step = jax.jit(gm.decode_step, static_argnums=(4,))
        logits, cache, routing = prefill(params, tokens, lengths, cfg,
                                         BUCKET + NEW)
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
    finally:
        gm.PREFILL_BLOCK = block
    refs = [ref.forward(params, np.asarray(s), spec) for s in seqs]
    return lengths, per_step, choices, refs, cache


class TestPrefillDecodeAgainstReference:
    @pytest.mark.parametrize("step", range(NEW + 1))
    def test_logits_at_every_step(self, generated, step):
        """Step 0 is the expanded form's last position; step s feeds the
        token generated at s - 1 through the latent cache in the absorbed
        form; the reference knows the expanded form only."""
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

    def test_the_cache_is_latent(self, generated):
        """24 numbers a position a layer (16 + 8), no heads."""
        *_, cache = generated
        assert [{k: v.shape for k, v in e.items()} for e in cache["layers"]] == [
            {"ckv": (3, BUCKET + NEW, 16), "krope": (3, BUCKET + NEW, 8)}] * 3

    def test_rows_asked_for_are_the_rows_of_the_whole(self, generated):
        """The reference's ``rows``: the chip's comparison reads 129 rows of
        154,880 logits, not 16,512."""
        d, _, params = tiny_glm_model(0, mtp=0)
        lengths, _, _, refs, _ = generated
        n = int(lengths[1])
        seq = np.concatenate([_prompts([32, 19, 9])[0][1, :n],
                              np.zeros(NEW, np.int32)])
        whole = ref.forward(params, seq, ref.spec_from_config(d))
        rows = np.arange(n - 1, n + NEW)
        part = ref.forward(params, seq, ref.spec_from_config(d), rows=rows)
        assert part["logits"].shape == (NEW + 1, 256)
        np.testing.assert_array_equal(np.asarray(part["logits"]),
                                      np.asarray(whole["logits"])[rows])
        for a, b in zip(part["chosen"], whole["chosen"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[rows])


def test_absorbed_form_is_the_expanded_form():
    """One layer, the same weights: the last position's output through the
    cache in the latent space (``q_nope W_uk^T`` against ``c_kv``, ``o_lat
    W_uv``) is the expanded form's row, per head keys and values rebuilt."""
    _, cfg, params = tiny_glm_model(3, mtp=0)
    p = params["layers"][1]["attn"]
    xn = jax.random.normal(jax.random.key(4), (2, BUCKET, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(BUCKET)[None], (2, BUCKET))
    lengths = jnp.asarray([BUCKET, 21])
    want, ckv, krope = gm.attention_expanded(p, xn, positions, lengths, cfg)
    at = lengths - 1
    # the cache as prefill leaves it, the last position not yet written
    entry = {"ckv": ckv.at[jnp.arange(2), at].set(0.0),
             "krope": krope.at[jnp.arange(2), at].set(0.0)}
    last = jnp.take_along_axis(xn, at[:, None, None], axis=1)
    got, entry = gm.attention_absorbed(p, last, at, entry, cfg)
    rows = jnp.take_along_axis(want, at[:, None, None], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rows), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(entry["ckv"][1, 20]),
                               np.asarray(ckv[1, 20]), atol=1e-6)


def test_bfloat16_runs_and_stays_near_the_reference():
    """The served dtype on the CPU: how near is the chip's to say (the
    benchmark's ``logit_gap_ratio``, against bfloat16's own gap)."""
    d, cfg, params = tiny_glm_model(1, dtype=jnp.bfloat16, mtp=0)
    tokens, lengths = _prompts([24, 11, 32, 17], seed=1)
    logits, cache, _ = jax.jit(gm.prefill, static_argnums=(3, 4))(
        params, tokens, lengths, cfg, BUCKET + 4)
    assert logits.dtype == jnp.float32
    assert cache["layers"][0]["ckv"].dtype == jnp.bfloat16
    spec = ref.spec_from_config(d)
    for i, n in enumerate(lengths):
        want = np.asarray(ref.forward(params, tokens[i, :n], spec)["logits"][-1])
        err = np.linalg.norm(np.asarray(logits[i]) - want) / np.linalg.norm(want)
        assert err < 0.4, err


class TestExpertShare:
    @pytest.mark.parametrize("held", [4, 8])
    def test_shares_of_all_ranks_add_up_to_the_uncut_layer(self, held):
        """Four shares of 4 (two of 8) of the 16 experts, cut from ONE uncut
        layer's weights: every rank's routed part, with the shared expert
        (which every rank computes alike) counted once, is the uncut
        reference's layer."""
        x = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
        d, _, full = tiny_glm_model(5, mtp=0)
        moe = full["layers"][1]["moe"]
        shared = lb.swiglu(x, moe["shared"])
        total = shared
        for rank in range(16 // held):
            p = dict(moe)
            lo = rank * held
            p["experts"] = {k: v[lo:lo + held] for k, v in moe["experts"].items()}
            cfg = gm.Glm4MoeLiteConfig.from_dict(
                tiny_glm_config(held=held, rank=rank))
            assert cfg.share == moe_ops.ExpertShare(lo, held, 16)
            total = total + (lb.expert_layer(p, x, cfg)[0] - shared)
        spec = ref.spec_from_config(d)
        routed, _ = ref._experts(moe, x, spec, "f32", None)
        with jax.default_matmul_precision("highest"):
            want = routed + ref._swiglu(x, moe["shared"], "f32")
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_the_cell_holds_every_expert(self):
        cfg = gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config())
        assert cfg.share == moe_ops.ExpertShare(0, 16, 16) and cfg.num_experts_per_tok == 4


class TestPrefillCausal:
    def _plain(self, q, k, v, scale):
        l = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @pytest.mark.parametrize("block", [4, 8, 32, 1024])
    def test_blocks_with_a_running_softmax(self, block):
        """Different key and value widths (as the expanded form has them at
        the tiny size), any block that divides the bucket."""
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (2, 32, 3, 16))
        k = jax.random.normal(ks[1], (2, 32, 3, 16))
        v = jax.random.normal(ks[2], (2, 32, 3, 24))
        got = attn_ops.prefill_causal(q, k, v, scale=0.25, block=block)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(self._plain(q, k, v, 0.25)),
                                   atol=2e-5, rtol=2e-5)

    def test_blocks_past_a_sequence_s_length_are_skipped(self):
        ks = jax.random.split(jax.random.key(1), 3)
        q, k, v = (jax.random.normal(kk, (2, 32, 2, 8)) for kk in ks)
        got = np.asarray(attn_ops.prefill_causal(
            q, k, v, jnp.asarray([32, 11]), block=8))
        want = np.asarray(self._plain(q, k, v, 8 ** -0.5))
        np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
        # 11 tokens: blocks 0 and 1 computed (rows 0-15), 2 and 3 left zero
        np.testing.assert_allclose(got[1, :16], want[1, :16], atol=2e-5, rtol=2e-5)
        assert not got[1, 16:].any()

    def test_a_bucket_the_block_does_not_divide_is_refused(self):
        x = jnp.zeros((1, 12, 1, 4))
        with pytest.raises(ValueError, match="multiple"):
            attn_ops.prefill_causal(x, x, x, block=8)


def test_mtp_module_against_reference():
    """h' = W_p [RMSNorm(h_t); RMSNorm(Emb(x_{t+1}))], one block of the
    model's own kind (latent attention + expert layer), the module's norm,
    the shared head."""
    d, cfg, params = tiny_glm_model(2, mtp=1)
    assert "mtp" in params
    spec = ref.spec_from_config(d)
    tokens, lengths = _prompts([32], seed=2)
    hidden, _, _ = gm.prefill_hidden(params, tokens, lengths, cfg)
    nxt = np.roll(tokens, -1, axis=1)
    got = gm.mtp_logits(params, hidden, nxt, cfg)
    r = ref.forward(params, tokens[0], spec)
    np.testing.assert_allclose(np.asarray(hidden[0]), np.asarray(r["hidden"]),
                               atol=2e-5, rtol=2e-5)
    want = ref.mtp_forward(params, r["hidden"], nxt[0], spec)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("variant", VARIANTS)
def test_reference_variants_change_the_answer(variant):
    """Each control of the calibration breaks the mathematics for real."""
    d, cfg, params = tiny_glm_model(0, mtp=0)
    spec = ref.spec_from_config(d)
    tokens, _ = _prompts([32])
    a = ref.forward(params, tokens[0], spec)["logits"]
    b = ref.forward(params, tokens[0], spec, variant=variant)["logits"]
    assert float(jnp.abs(a - b).max()) > 1e-3


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_reference_modes_round_and_stay_near(mode):
    d, cfg, params = tiny_glm_model(0, mtp=0)
    spec = ref.spec_from_config(d)
    tokens, _ = _prompts([32])
    a = np.asarray(ref.forward(params, tokens[0], spec)["logits"])
    b = np.asarray(ref.forward(params, tokens[0], spec, mode)["logits"])
    gap = np.linalg.norm(a - b) / np.linalg.norm(a)
    assert 1e-4 < gap < 0.5, gap


def test_published_configuration_counts():
    """The benchmark's configuration: 3,895.6 M parameters held (7.79 GB in
    bfloat16): the dense layer, five expert layers with all 64 experts, the
    whole vocabulary; nothing cut but the depth."""
    cfg = gm.Glm4MoeLiteConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", "glm-4.7-flash-pp8-serve-bf16.json"))
    assert gm.param_count(cfg) == 3_895_625_536
    assert cfg.share == moe_ops.ExpertShare(0, 64, 64) and cfg.vocab == (0, 154880, 154880)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 5
    assert cfg.mtp_layers == 0 and cfg.scale == 1 / 16
    shapes = gm.param_shapes(cfg)["layers"][1]
    attn = sum(np.prod(s) for s in shapes["attn"].values())
    assert attn == 21_759_232                     # ISSUE: 21.76 M a layer
    assert (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2 * cfg.num_layers == 6912


@pytest.mark.parametrize("key,value,what", [
    ("n_group", 2, "group-limited"), ("rope_scaling", {"factor": 4}, "rotary scaling"),
    ("partial_rotary_factor", 0.5, "partial_rotary_factor"),
    ("attention_bias", True, "attention biases")])
def test_what_is_not_implemented_is_refused(key, value, what):
    d = tiny_glm_config()
    d[key] = value
    with pytest.raises(ValueError, match=what):
        gm.Glm4MoeLiteConfig.from_dict(d)


@pytest.mark.parametrize("name,value", [("scoring_func", "softmax"),
                                        ("rope_pairing", "interleaved"),
                                        ("softmax_scale", "1/sqrt(qk_nope_head_dim)"),
                                        ("mtp_layout", "other")])
def test_an_assumption_is_stated_and_only_what_is_implemented(name, value):
    """What config.json leaves open: the cell's configuration file states
    the one value the module implements, and another is refused."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash-pp8-serve-bf16.json")) as f:
        assert json.load(f)["assumed"][name] == gm.ASSUMED[name]
    with pytest.raises(ValueError, match=name):
        gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config(**{name: value}))


# -- the decode step's expert layers on the skipping kernel ------------------
class TestSkippingDecode:
    """Hidden 128 and experts 128 wide (whole lanes), the kernel interpreted
    where a TPU backend would compile it."""

    @pytest.fixture()
    def wide(self, monkeypatch):
        interpret_skipping_experts(monkeypatch)
        d = tiny_glm_config(mtp=0)
        d.update(hidden_size=128, moe_intermediate_size=128)
        cfg = gm.Glm4MoeLiteConfig.from_dict(d)
        return d, cfg, gm.init_params(jax.random.key(1), cfg, jnp.float32)

    def test_the_model_s_form_is_the_shapes(self, wide):
        _, cfg, _ = wide
        assert gm.experts_form(cfg, 3, jnp.float32) == "skipping"
        assert gm.experts_form(cfg, 3 * BUCKET, jnp.float32) == "batched"
        assert gm.experts_form(cfg, 1024, jnp.float32) == "sorted"
        narrow = gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config(mtp=0))
        assert gm.experts_form(narrow, 3, jnp.float32) == "batched"

    def test_decode_reports_what_it_read_and_matches_the_reference(self, wide):
        """Three sequences' decode steps through the kernel: the reference's
        logits at every step, and ``experts_read`` the distinct experts the
        step's three rows chose, summed over the two expert layers; the
        prefill (96 rows: nobody idle) reports none."""
        d, cfg, params = wide
        spec = ref.spec_from_config(d)
        tokens, lengths = _prompts([32, 19, 9])
        logits, cache, routing = jax.jit(gm.prefill, static_argnums=(3, 4))(
            params, tokens, lengths, cfg, BUCKET + 3)
        assert "experts_read" not in routing
        step = jax.jit(gm.decode_step, static_argnums=(4,))
        seqs = [list(tokens[i, :n]) for i, n in enumerate(lengths)]
        pos = jnp.asarray(lengths)
        for _ in range(3):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            for i in range(3):
                seqs[i].append(int(tok[i]))
            logits, cache, routing = step(params, cache, tok, pos, cfg)
            chosen = np.asarray(routing["choices"])          # (2, 3, 4)
            assert int(routing["experts_read"]) == sum(
                len(np.unique(layer)) for layer in chosen) <= 2 * 12
            pos = pos + 1
            for i, s in enumerate(seqs):
                want = ref.forward(params, np.asarray(s), spec)["logits"][-1]
                np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(want),
                                           atol=3e-5, rtol=3e-5)
