"""K-EXAONE (``models/exaone_moe.py``, ``ops/moe.py``, ``ops/attention.py``)
against the plain reference (``benchmark/reference/exaone_moe_ref.py``) at the tiny
preset of ``tests/lm_tiny.py``, float32 on the CPU so that the comparison
is tight enough to see a wrong index, and once in bfloat16."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import exaone_moe_ref as ref
from can_tpu.models import exaone_moe as em
from can_tpu.models import lm_blocks as lb
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import moe as moe_ops

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
        c, _, passes = moe_ops.share_apply(x, idx, w, experts, share)
        assert (passes is None) == (tokens <= 64)
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
        got, _, _ = moe_ops.share_apply(x, idx, w, experts, share)
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

    # the cells' leaves cut small: (slots, kv heads, positions, head dim),
    # the cache's dtype, ``new``'s dtype, the positions of the slots' tokens
    @pytest.mark.parametrize("late", [False, True], ids=["sound", "late_write"])
    @pytest.mark.parametrize("shape,window,dtype,new_dtype,positions", [
        ((4, 2, 16, 8), None, jnp.bfloat16, jnp.bfloat16, [0, 5, 11, 15]),
        ((4, 2, 8, 8), 8, jnp.bfloat16, jnp.bfloat16, [3, 8, 21, 47]),
        ((3, 1, 16, 8), None, jnp.bfloat16, jnp.bfloat16, [2, 9, 15]),
        ((4, 4, 16, 8), None, jnp.bfloat16, jnp.float32, [7, 7, 7, 1]),
        ((4, 2, 8, 8), 8, jnp.float32, jnp.float32, [9, 1, 17, 25]),
        ((1, 3, 12, 4), None, jnp.float32, jnp.bfloat16, [11]),
    ], ids=["full", "ring", "one-head", "slot-repeated-f32-new",
            "ring-slot-repeated-f32", "one-sequence-bf16-new"])
    def test_write_slot_is_the_plain_indexed_write(self, shape, window, dtype,
                                                   new_dtype, positions, late):
        """The merged (slot x head) scatter against the indexed write it
        replaced, bit for bit: a full layer writes at its position, a ring
        at ``position % window``; alone and under
        ``calibrate_lm.late_write``'s wrapper, whose late slot wraps at
        ``cache.shape[2]``."""
        from benchmark.tools import calibrate_lm

        b, kv, s, d = shape
        k1, k2 = jax.random.split(jax.random.key(sum(shape)))
        cache = jax.random.normal(k1, shape).astype(dtype)
        new = jax.random.normal(k2, (b, kv, d)).astype(new_dtype)
        slot = jnp.asarray(positions, jnp.int32)
        if window:
            slot = slot % window
        want_at = (slot + 1) % s if late else slot
        want = cache.at[jnp.arange(b), :, want_at].set(new.astype(dtype))
        if late:
            calibrate_lm.late_write(None)
        try:
            got = jax.jit(attn_ops.write_slot)(cache, new, slot)
        finally:
            if late:
                calibrate_lm.late_write.undo()
        assert got.shape == shape and got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        # every other row is the cache's own
        untouched = np.ones((b, s), bool)
        untouched[np.arange(b), np.asarray(want_at)] = False
        np.testing.assert_array_equal(
            np.asarray(got, np.float32).transpose(0, 2, 1, 3)[untouched],
            np.asarray(cache, np.float32).transpose(0, 2, 1, 3)[untouched])

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
    assert cfg.share == moe_ops.ExpertShare(0, 16, 128) and cfg.vocab == (0, 19200, 153600)
    assert [t == em.WINDOW for t in cfg.layer_types] == [True, True, True,
                                                         False, True]
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert cfg.mtp_layers == 0


# -- which form the held experts' product takes (ops/moe.py::share_form) ----
GLM_SHARE, EXAONE_SHARE = moe_ops.ExpertShare(0, 64, 64), moe_ops.ExpertShare(0, 16, 128)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("what,args,on_tpu", [
    ("GLM's decode step: a third of the experts idle",
     (16, 4, GLM_SHARE, 2048, 1536), "skipping"),
    ("GLM, 8 tokens", (8, 4, GLM_SHARE, 2048, 1536), "skipping"),
    ("GLM, 64 tokens: nobody idle", (64, 4, GLM_SHARE, 2048, 1536), "batched"),
    ("K-EXAONE's decode step: nobody idle",
     (64, 8, EXAONE_SHARE, 6144, 2048), "batched"),
    ("K-EXAONE, 8 tokens", (8, 8, EXAONE_SHARE, 6144, 2048), "skipping"),
    ("widths that are no whole lanes", (4, 2, moe_ops.ExpertShare(0, 8, 8), 64, 32),
     "batched"),
    ("a token over the few", (moe_ops.DENSE_MAX_TOKENS + 1, 4, GLM_SHARE, 2048,
                              1536), "sorted"),
    ("a prefill slice", (32768, 4, GLM_SHARE, 2048, 1536), "sorted"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_form_is_chosen_by_backend_and_shapes(backend, what, args, on_tpu,
                                                  monkeypatch):
    """Off a TPU always a plain form; on one the kernel where the expected
    share of idle experts, ``(1 - k / total) ** T``, is worth skipping and
    the kernel's ``supports`` takes the widths."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    want = on_tpu if backend == "tpu" or on_tpu == "sorted" else "batched"
    assert moe_ops.share_form(*args, jnp.bfloat16) == want


@pytest.mark.parametrize("form", ["skipping", "batched", "sorted"])
def test_share_apply_runs_the_form_chosen(form, monkeypatch):
    """-> (the sum, the experts read where the form counts them, the passes
    it took where the form has a buffer)."""
    ran = []
    says = {"skipping": ("y", 3), "batched": "y", "sorted": ("y", 2)}
    for name, fn in (("skipping", "_share_apply_skipping"),
                     ("batched", "_share_apply_batched"),
                     ("sorted", "_sorted_in_passes")):
        monkeypatch.setattr(moe_ops, fn, lambda *a, name=name: (
            ran.append(name) or says[name]))
    monkeypatch.setattr(moe_ops, "share_form", lambda *a: form)
    x, idx = jnp.zeros((4, 128)), jnp.zeros((4, 2), jnp.int32)
    experts = {"gate": jnp.zeros((8, 128, 256))}
    got = moe_ops.share_apply(x, idx, None, experts, moe_ops.ExpertShare(0, 8, 8))
    assert ran == [form]
    assert got == {"skipping": ("y", 3, None), "batched": ("y", None, None),
                   "sorted": ("y", None, 2)}[form]


# -- the serving programs of the models this change must not move -----------
# sha256 of the StableHLO text of ``LMPrograms.decode`` / ``.prefill_slice``
# at the benchmark's configurations, lowered on the CPU with jax 0.9.0 at
# commit 0227fb7 (PR 32).  A PR that means to change one of these programs
# prints the new value with this test's failure and replaces it here: PR 37
# replaced the two ``decode`` texts of K-EXAONE and Falcon-H1 (``write_slot``
# as a scatter over the merged slot x head axis); the other four stand.  PR 40
# (which gave ``ops/attention.py``'s grouped-query functions values of their
# own width, a sink and keys and values that pack apart, and moved the three
# projections by head and the ring's prefill entry into shared functions)
# added LFM2's two, lowered at its parent 7a2007b: a ``sink`` of None, ``dv ==
# d`` and one ``pack`` for both leaves trace to what stood, in all four models.
# PR 43 replaced the three ``prefill_slice`` texts of the models with experts
# (``ops/moe.py``'s sorted form over a bound on the rows in use, an inner
# ``jit``); Falcon-H1's and every ``decode`` text stand.  PR 45 (one cached
# key/value layer and one skeleton in ``lm_blocks.py``) replaced K-EXAONE's
# ``prefill_slice`` text and no other: its full layer's entry is ``as_leaf``'s
# now, the same two transposes and two pads with the keys' pad before the
# values' transpose; compiled for the described v5e the program is the
# parent's, instruction for instruction (PERF.md section 6, PR 45).  PR 47
# added Brumby's two (power retention: the backend changes neither) and moved
# none of the eight.  PR 50 (a router's scoring and identity experts in
# ``ops/moe.py`` and ``lm_blocks.expert_layer``, latent attention's optional
# factors and leaf names, ``VocabSlice`` a tuple of its own) added
# LongCat-Flash's two and moved none of the ten: sigmoid scoring, a share
# without identity experts, a configuration without factors and the leaves
# ``ckv`` / ``krope`` trace to what stood.
PROGRAM_TEXT = {
    ("k-exaone-ep8-serve-bf16", "decode"):
        "2c53b1638274f93f0d4919dc5558a2e6378313e37e545c1402c69c3c3a01403d",
    ("k-exaone-ep8-serve-bf16", "prefill_slice"):
        "27beeaf63e0a7d06d7786383ada6e3456151332ea2f1b724615fa387118bce73",
    ("falcon-h1-34b-pp12-serve-bf16", "decode"):
        "06d5c406ee95631fb2d429365a24acb1623aef8e99a46f6cbc3d113e7c48c8a0",
    ("falcon-h1-34b-pp12-serve-bf16", "prefill_slice"):
        "7dbdc1aaac65afe9ff78235ea2ebc774d5fa7b43881686bb73b86555ca11ee7a",
    ("glm-4.7-flash-pp8-serve-bf16", "decode"):
        "9bafbae804c45ab05d80ff531d20944e158ad3719d5d49f1a1dfed610a8e1323",
    ("glm-4.7-flash-pp8-serve-bf16", "prefill_slice"):
        "c559b2a514ec802e648d8cb975be7456086ac7ffd849785acfef383446aa68a7",
    ("lfm2-24b-a2b-ep8-serve-bf16", "decode"):
        "8c7d19f0bdfbfe4b323aedbd4a0fb791f1abf2ac18a6bd3daa2cc9ccd4439952",
    ("lfm2-24b-a2b-ep8-serve-bf16", "prefill_slice"):
        "48d2b79687d2ca3c0b80e047ae1116f0406ab531e79760cd4938c5a5fe4bcdb3",
    ("brumby-14b-pp5-serve-bf16", "decode"):
        "72fe0af25f4e4e352c5a2bd6dffec3ae6035a4cd35f2d7770ca7ac4d50d0eedc",
    ("brumby-14b-pp5-serve-bf16", "prefill_slice"):
        "b8fb8818bfc0b42cf7584453a6983ddfb733910cdf519ac636388753f40f2399",
    ("longcat-flash-omni-ep32-serve-bf16", "decode"): 
        "c82827d0a738bb8d9d2928e321768308845f8389621b9236b9a5592866c04167",
    ("longcat-flash-omni-ep32-serve-bf16", "prefill_slice"): 
        "a04405232c03c35f5a0878383fe5d787778d694b693b510bb900d9bf141ecf19",
}


def _program_text(name: str, program: str) -> str:
    import json

    from can_tpu.serve import programs as serve_programs

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    slots, part = int(config["max_batch"]), int(config["prefill_slice"])
    if config["model_type"] == "exaone_moe":
        model, cfg = em, em.ExaoneMoeConfig.from_dict(config)
    elif config["model_type"] == "falcon_h1":
        from can_tpu.models import falcon_h1 as model

        cfg = model.FalconH1Config.from_dict(config)
    elif config["model_type"] == "lfm2_moe":
        from can_tpu.models import lfm2_moe as model

        cfg = model.Lfm2MoeConfig.from_dict(config)
    elif config["model_type"] == "brumby":
        from can_tpu.models import brumby as model

        cfg = model.BrumbyConfig.from_dict(config)
    elif config["model_type"] == "longcat_flash":
        from can_tpu.models import longcat_flash as model

        cfg = model.LongcatFlashConfig.from_dict(config)
    else:
        from can_tpu.models import glm_moe_lite as model

        cfg = model.Glm4MoeLiteConfig.from_dict(config)
    programs = serve_programs.LMPrograms(
        model, cfg, max_new_tokens=int(config["max_new_tokens"]))
    shape = jax.ShapeDtypeStruct
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    params = jax.tree_util.tree_unflatten(treedef, [
        shape(s, jnp.float32 if path[-1].key == "bias" else jnp.bfloat16)
        for path, s in flat])
    bucket = int(config["length_ladder"][-1])
    cache = jax.eval_shape(lambda: programs.new_cache(slots, bucket))
    batch = {"tokens": shape((part, bucket), jnp.int32),
             "lengths": shape((part,), jnp.int32),
             "active": shape((part,), jnp.bool_)}
    start = shape((), jnp.int32)
    if program == "prefill_slice":
        return jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
            params, batch, cache, start).as_text()
    out = jax.eval_shape(lambda *a: programs.prefill_slice(*a)[0], params,
                         batch, cache, start)
    state = jax.eval_shape(
        lambda outs: programs.new_state(outs, jnp.ones((slots,), jnp.int32),
                                        jnp.ones((slots,), bool))[0],
        [out] * (slots // part))
    return jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).as_text()


@pytest.mark.parametrize("name,program,backend", [
    (name, program, backend) for name, program in sorted(PROGRAM_TEXT)
    for backend in ("cpu", "tpu")
    # on a TPU GLM's two programs take the kernels, which only lower there
    # (tests/test_chip_compile.py compiles both for a described v5e)
    # and LongCat-Flash's decode step takes GLM's decode kernel
    if not (backend == "tpu" and name.startswith(("glm", "longcat")))])
def test_a_serving_program_lowers_to_the_text_it_had(name, program, backend,
                                                     monkeypatch):
    """K-EXAONE's, Falcon-H1's and LFM2's programs are the pinned ones,
    whatever the backend says (K-EXAONE's and LFM2's shapes leave nothing to
    skip, Falcon-H1 has no expert layer); GLM's are off a TPU; Brumby's
    prefill slice whatever the backend, its decode step off a TPU (PR 48
    replaced both: the state has its rows in the lanes)."""
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip(f"the texts were lowered with jax 0.9.0, not {jax.__version__}")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "tpu" and (name, program) == ("brumby-14b-pp5-serve-bf16",
                                                "decode"):
        # on a TPU Brumby's step is the fused kernel (PR 48), which only
        # lowers there, as GLM's kernels do: the trace says it was taken
        from can_tpu.models import brumby

        with pytest.raises(ValueError, match="interpret mode"):
            _program_text(name, program)
        assert brumby.retention_traced((16, 1)) == "fused"
        return
    got = hashlib.sha256(_program_text(name, program).encode()).hexdigest()
    assert got == PROGRAM_TEXT[name, program]


def test_a_model_that_never_skips_does_not_load_pallas():
    """``jax.experimental.pallas`` takes over a second to import
    (``setup_s``): ``ops/moe.py`` loads the kernel's module only where a
    shape could skip, so K-EXAONE's and Falcon-H1's processes never do."""
    import subprocess
    import sys

    code = ("import sys, jax.numpy as jnp\n"
            "from can_tpu.models import exaone_moe, falcon_h1\n"
            "from can_tpu.ops import moe\n"
            "import can_tpu.serve.programs\n"
            "share = moe.ExpertShare(0, 16, 128)\n"
            "assert moe.share_form(64, 8, share, 6144, 2048, jnp.bfloat16) == 'batched'\n"
            "assert moe.share_form(8192, 8, share, 6144, 2048, jnp.bfloat16) == 'sorted'\n"
            "bad = [m for m in sys.modules if 'pallas' in m]\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
