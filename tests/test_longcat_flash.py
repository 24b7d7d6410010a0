"""LongCat-Flash (``models/longcat_flash.py``: two latent-attention sublayers
a layer, ``models/glm_moe_lite.py``'s, with scaled latents; an expert layer
on the shortcut with zero-compute experts behind a softmax router,
``ops/moe.py``) against the plain reference
(``benchmark/reference/longcat_flash_ref.py``) at the tiny preset of
``tests/lm_tiny.py``, float32 on the CPU so that the comparison is tight
enough to see a wrong index, and once in bfloat16."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import longcat_flash_ref as ref
from can_tpu.models import glm_moe_lite as gm
from can_tpu.models import lm_blocks as lb
from can_tpu.models import longcat_flash as lf
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import moe as moe_ops

from lm_tiny import tiny_longcat_config, tiny_longcat_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REAL = os.path.join(ROOT, "benchmark", "configs",
                    "longcat-flash-omni-ep32-serve-bf16.json")
BUCKET, NEW = 32, 8
LENGTHS = [32, 19, 9]
VARIANTS = ["no_zero_term", "normalised_topk", "sigmoid_scoring",
            "no_scale_factor", "no_q_scale", "no_kv_scale",
            "sequential_block", "expert_zeroed"]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Prefill attention in blocks of 8 positions: a bucket of 32 is four
    blocks of queries, the running softmax crosses up to three key blocks."""
    monkeypatch.setattr(gm, "PREFILL_BLOCK", 8)


def _prompts(lengths, seed=0, vocab=256, bucket=BUCKET):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((len(lengths), bucket), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, vocab, n)
    return tokens, np.asarray(lengths, np.int32)


def _generate(params, cfg, tokens, lengths, new=NEW):
    """Prefill + ``new`` greedy decode steps through the cache: -> (the
    logits of every step, the routing choices of every step, the finished
    sequences, the cache)."""
    prefill = jax.jit(lf.prefill, static_argnums=(3, 4))
    step = jax.jit(lf.decode_step, static_argnums=(4,))
    logits, cache, routing = prefill(params, tokens, lengths, cfg,
                                     tokens.shape[1] + new)
    seqs = [list(tokens[i, :n]) for i, n in enumerate(lengths)]
    per_step, choices = [np.asarray(logits)], [np.asarray(routing["choices"])]
    pos = jnp.asarray(lengths)
    for _ in range(new):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(len(seqs)):
            seqs[i].append(int(tok[i]))
        logits, cache, routing = step(params, cache, tok, pos, cfg)
        per_step.append(np.asarray(logits))
        choices.append(np.asarray(routing["choices"]))
        pos = pos + 1
    return per_step, choices, seqs, cache


@pytest.fixture(scope="module")
def generated():
    """Prefill + 8 decode steps of three prompts of unequal length in one
    padded batch (32, 19 and 9 tokens), with the logits of every step and
    the reference's full forward over each finished sequence."""
    block, gm.PREFILL_BLOCK = gm.PREFILL_BLOCK, 8
    try:
        d, cfg, params = tiny_longcat_model(0)
        tokens, lengths = _prompts(LENGTHS)
        per_step, choices, seqs, cache = _generate(params, cfg, tokens, lengths)
    finally:
        gm.PREFILL_BLOCK = block
    spec = ref.spec_from_config(d)
    refs = [ref.forward(params, np.asarray(s), spec) for s in seqs]
    return d, params, lengths, per_step, choices, seqs, refs, cache


class TestPrefillDecodeAgainstReference:
    @pytest.mark.parametrize("step", range(NEW + 1))
    def test_logits_at_every_step(self, generated, step):
        """Step 0 is the expanded form's last position; step s feeds the
        token generated at s - 1 through BOTH sublayers' latent caches in the
        absorbed form; the reference knows whole sequences only."""
        _, _, lengths, per_step, _, _, refs, _ = generated
        for i, n in enumerate(lengths):
            want = np.asarray(refs[i]["logits"][n - 1 + step])
            np.testing.assert_allclose(per_step[step][i], want, atol=3e-5,
                                       rtol=3e-5)

    def test_routing_choices_are_the_references(self, generated):
        _, _, lengths, _, choices, _, refs, _ = generated
        for step in (0, 1, NEW):
            for i, n in enumerate(lengths):
                for layer, chosen in enumerate(refs[i]["chosen"]):
                    assert (np.sort(choices[step][layer, i])
                            == np.sort(np.asarray(chosen[n - 1 + step]))).all()

    def test_the_cache_holds_both_sublayers_latents(self, generated):
        """24 numbers a position a SUBLAYER (16 + 8), no heads, two
        sublayers under distinct leaves in one entry a layer."""
        *_, cache = generated
        one = {"ckv0": (3, BUCKET + NEW, 16), "krope0": (3, BUCKET + NEW, 8),
               "ckv1": (3, BUCKET + NEW, 16), "krope1": (3, BUCKET + NEW, 8)}
        assert [{k: v.shape for k, v in e.items()}
                for e in cache["layers"]] == [one] * 2
        first = cache["layers"][0]
        assert not np.allclose(np.asarray(first["ckv0"]), np.asarray(first["ckv1"]))

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_every_variant_of_the_reference_is_another_computation(
            self, generated, variant):
        """What the calibration's controls break is seen at the tiny preset
        too: the program agrees with the reference proper (above) and not
        with the reference computing something else."""
        d, params, lengths, per_step, _, seqs, refs, _ = generated
        spec = ref.spec_from_config(d)
        gaps = []
        for i, n in enumerate(lengths):
            other = np.asarray(ref.forward(params, np.asarray(seqs[i]), spec,
                                           "f32", variant)["logits"])
            mine = np.stack([per_step[s][i] for s in range(NEW + 1)])
            gaps.append(np.abs(other[n - 1:n + NEW] - mine).max())
        assert max(gaps) > 1e-2, (variant, gaps)


def test_right_padded_prompts_read_like_unpadded_ones(monkeypatch):
    """A prompt of 9 tokens in a bucket of 32 beside longer ones, and alone
    in a bucket of its own length: the same logits and the same latents."""
    monkeypatch.setattr(gm, "PREFILL_BLOCK", 1024)   # a bucket of 19 is one block
    _, cfg, params = tiny_longcat_model(2)
    tokens, lengths = _prompts(LENGTHS, seed=3)
    prefill = jax.jit(lf.prefill, static_argnums=(3, 4))
    logits, cache, _ = prefill(params, tokens, lengths, cfg, BUCKET)
    for i, n in enumerate(LENGTHS):
        alone, cache1, _ = prefill(params, tokens[i:i + 1, :n],
                                   lengths[i:i + 1], cfg, n)
        np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(alone[0]),
                                   atol=3e-5, rtol=3e-5)
        for name, leaf in cache1["layers"][1].items():
            np.testing.assert_allclose(
                np.asarray(cache["layers"][1][name][i, :n]),
                np.asarray(leaf[0]), atol=3e-5, rtol=3e-5)


def test_bfloat16_stays_within_three_of_bfloat16s_own_gap():
    """The served dtype on the CPU, prefill then decode through the cache:
    the program's gap to the float32 reference is within 3 times the gap the
    REFERENCE makes when it computes in bfloat16 (the chip's number is the
    benchmark's ``logit_gap_ratio``)."""
    d, cfg, params = tiny_longcat_model(1, dtype=jnp.bfloat16)
    tokens, lengths = _prompts([24, 11, 32, 17], seed=1)
    per_step, _, seqs, cache = _generate(params, cfg, tokens, lengths, new=4)
    assert per_step[0].dtype == np.float32
    assert cache["layers"][0]["ckv1"].dtype == jnp.bfloat16
    spec = ref.spec_from_config(d)
    mine, own = [], []
    for i, n in enumerate(lengths):
        seq = np.asarray(seqs[i])
        want = np.asarray(ref.forward(params, seq, spec)["logits"])
        yard = np.asarray(ref.forward(params, seq, spec, "bf16")["logits"])
        own.append(np.linalg.norm(yard - want, axis=-1))
        for s in range(5):
            mine.append(np.linalg.norm(per_step[s][i] - want[n - 1 + s]))
    assert np.median(mine) < 3 * np.median(np.concatenate(own)), (
        np.median(mine), np.median(np.concatenate(own)))


# -- the expert layer -----------------------------------------------------
def _layer_input(seed=5, tokens=40):
    d, cfg, params = tiny_longcat_model(seed, held=8)
    x = jax.random.normal(jax.random.key(seed + 1), (tokens, 64), jnp.float32)
    return d, cfg, params["layers"][1]["moe"], x


class TestExpertShare:
    def test_shares_of_all_ranks_add_up_to_the_uncut_layer(self):
        """Four shares of 2 of the 8 experts, cut from ONE uncut layer's
        weights: every rank's routed part, with the identity experts' term
        (which every rank computes alike) counted once, is the uncut
        reference's ``m``."""
        d, _, moe, x = _layer_input()
        zero = None
        total = jnp.zeros_like(x)
        for rank in range(4):
            p = dict(moe)
            p["experts"] = {k: v[2 * rank:2 * rank + 2]
                            for k, v in moe["experts"].items()}
            cfg = lf.LongcatFlashConfig.from_dict(
                tiny_longcat_config(held=2, rank=rank))
            assert cfg.share == moe_ops.ExpertShare(2 * rank, 2, 8, 4)
            idx, w = moe_ops.route(x, p["router"], p["bias"], top_k=3, scale=6.0,
                                   normalize=False, scoring="softmax")
            zero = moe_ops.zero_weight(idx, w, cfg.share)[:, None] * x
            total = total + (lb.expert_layer(p, x, cfg)[0] - zero)
        want, _ = ref._experts(moe, x, ref.spec_from_config(d), "f32", None)
        np.testing.assert_allclose(np.asarray(total + zero), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        assert float(jnp.abs(zero).max()) > 0.1     # the term is not a rounding

    def test_a_token_of_identity_experts_alone_is_its_weights_times_itself(self):
        """A bias that lifts the four identity experts over every routed
        one: every token's three choices are identity experts, its output is
        exactly ``(sum w) n``, no held expert gets a row and every choice is
        counted as a zero choice."""
        _, cfg, moe, x = _layer_input()
        p = dict(moe, bias=moe["bias"].at[8:].add(10.0))
        out, routed = lb.expert_layer(p, x, cfg)
        assert int(routed.idx.min()) >= 8
        idx, w = moe_ops.route(x, p["router"], p["bias"], top_k=3, scale=6.0,
                               normalize=False, scoring="softmax")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.sum(w, -1)[:, None] * x))
        assert int(moe_ops.held_counts(routed.idx, cfg.share).sum()) == 0
        assert int(moe_ops.zero_counts(routed.idx, cfg.share)) == x.shape[0] * 3
        # the same through the skeleton's report: a masked token's choices
        # are nobody's
        mask = jnp.arange(x.shape[0])[None, :] < 30
        report = lb.routing_report([routed._replace(idx=routed.idx[None])],
                                   mask, jnp.zeros((1,), jnp.int32), cfg)
        assert report["zero"].tolist() == [90] and int(report["counts"].sum()) == 0

    def test_the_bias_moves_the_choice_and_never_the_weight(self):
        _, cfg, moe, x = _layer_input()
        kw = dict(top_k=3, scale=6.0, normalize=False, scoring="softmax")
        plain, _ = moe_ops.route(x, moe["router"], jnp.zeros_like(moe["bias"]), **kw)
        moved, w = moe_ops.route(x, moe["router"], moe["bias"].at[5].add(1.0), **kw)
        assert (np.asarray(moved) == 5).any(axis=-1).all()
        assert not (np.asarray(plain) == 5).any(axis=-1).all()
        scores = jax.nn.softmax(jnp.dot(x, moe["router"],
                                        precision=jax.lax.Precision.HIGHEST), -1)
        np.testing.assert_allclose(
            np.asarray(w), 6.0 * np.asarray(jnp.take_along_axis(scores, moved, -1)),
            rtol=1e-6)
        # not normalised: the twelve... here three weights do not sum to 6
        assert float(jnp.abs(jnp.sum(w, -1) - 6.0).min()) > 0.1


# -- the program broken underneath: what the chip's numbers cannot see ------
def _decode_gap(generated_like, breaker):
    """The largest gap, over the decode steps, between the reference and the
    program with ``breaker`` applied (``benchmark/tools/calibrate_*``'s)."""
    d, params, cfg = generated_like
    tokens, lengths = _prompts(LENGTHS)
    breaker(None)
    try:
        per_step, _, seqs, _ = _generate(params, cfg, tokens, lengths, new=4)
    finally:
        breaker.undo()
    spec = ref.spec_from_config(d)
    gap = 0.0
    for i, n in enumerate(lengths):
        want = np.asarray(ref.forward(params, np.asarray(seqs[i]), spec)["logits"])
        gap = max(gap, max(np.abs(per_step[s][i] - want[n - 1 + s]).max()
                           for s in range(1, 5)))
    return gap


@pytest.mark.parametrize("name", ["leaves_swapped", "late_write"])
def test_the_program_broken_underneath_leaves_the_reference(name):
    """The calibration's two breaks of the timed path (the second sublayer
    reading the first one's leaves; decode writing a latent one position
    late), on the CPU where the comparison is tight: each moves the decode
    steps' logits far from the reference's."""
    from benchmark.tools import calibrate_longcat_flash as cal

    d, cfg, params = tiny_longcat_model(0)
    assert _decode_gap((d, params, cfg), cal.PROGRAM_BREAKS[name]) > 1e-2


def test_the_shortcut_reads_the_first_sublayers_stream():
    """The expert layer's input is ``RMSNorm(a0)``: with the second
    sublayer's attention output replaced by zeros the routing choices stay
    what they were (a sequential block's would move with ``a1``)."""
    _, cfg, params = tiny_longcat_model(4)
    tokens, lengths = _prompts(LENGTHS, seed=4)
    chosen = []
    for scale in (1.0, 0.0):
        p = jax.tree.map(lambda a: a, params)
        p["layers"][0]["sub"][1]["attn"]["wo"] = (
            params["layers"][0]["sub"][1]["attn"]["wo"] * scale)
        _, _, routing = lf.prefill(p, tokens, lengths, cfg, BUCKET)
        chosen.append(np.asarray(routing["choices"][0]))
    np.testing.assert_array_equal(chosen[0], chosen[1])


# -- the configuration ------------------------------------------------------
class TestConfig:
    @pytest.mark.parametrize("edit", [
        {"attention_bias": True}, {"rope_scaling": {"type": "yarn"}},
        {"tie_word_embeddings": True}, {"zero_expert_type": "copy"},
        {"attention_method": "MHA"},
        {"assumed": {"scoring_func": "sigmoid"}},
        {"assumed": {"norm_topk_prob": True}},
        {"assumed": {"rope_pairing": "interleaved"}},
        {"assumed": {"mla_scale_on": "q_and_kv"}},
    ], ids=lambda e: next(iter(e)) + ":" + str(next(iter(e.values()))))
    def test_from_dict_refuses_what_it_does_not_implement(self, edit):
        with pytest.raises(ValueError, match="not implemented"):
            lf.LongcatFlashConfig.from_dict(tiny_longcat_config(**edit))

    def test_the_scales_follow_the_switches(self):
        cfg = lf.LongcatFlashConfig.from_dict(tiny_longcat_config())
        assert cfg.q_lora_scale == (64 / 24) ** 0.5 and cfg.kv_lora_scale == 2.0
        off = lf.LongcatFlashConfig.from_dict(tiny_longcat_config(
            mla_scale_q_lora=False, mla_scale_kv_lora=False))
        assert off.q_lora_scale is None and off.kv_lora_scale is None

    def test_the_cells_configuration(self):
        """Every width as published, the cut as the file states it, the held
        parameters to the parameter, two latent parts a layer with distinct
        leaves and 2 x 576 numbers a position."""
        with open(REAL) as f:
            d = json.load(f)
        cfg = lf.LongcatFlashConfig.from_dict(d)
        assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size,
                cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.rope_theta) == (6144, 12288, 2048, 64, 1536, 512, 128, 64,
                                    128, 12, 6.0, 1e7)
        assert cfg.share == moe_ops.ExpertShare(0, 16, 512, 256)
        assert cfg.share.width == 768 and cfg.vocab == (0, 16384, 131072)
        assert cfg.q_lora_scale == 2.0 and cfg.kv_lora_scale == 12 ** 0.5
        assert d["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
        assert lf.param_count(cfg) == 5_172_749_312
        specs = lf.cache_layout(cfg)
        assert len(specs) == cfg.num_layers == 4
        for layer in specs:
            parts = layout.parts(layer)
            assert [p.kind for p in parts] == [layout.LATENT] * 2
            names = [n for p in parts for n, _, _ in p.leaves]
            assert names == ["ckv0", "krope0", "ckv1", "krope1"]
            assert sum(w for p in parts for _, _, w in p.leaves) == 2 * 576
        assert lf.experts_form(cfg, 256, jnp.bfloat16) == "batched"
        assert lf.experts_form(cfg, 32 * 256, jnp.bfloat16) == "sorted"

    def test_the_benchmarks_weights_are_the_models_tree(self):
        from benchmark.harness import weights_longcat_flash as weights

        with open(REAL) as f:
            d = json.load(f)
        assert weights.shapes(d) == lf.param_shapes(
            lf.LongcatFlashConfig.from_dict(d))
        assert weights.ROUTER_GAIN == lf.ROUTER_GAIN
