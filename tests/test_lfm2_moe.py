"""``models/lfm2_moe.py`` (a gated short convolution or grouped-query
attention as each layer's mixer, two leading dense layers, sparse experts
without a shared one, a tied head) against the plain reference
(``benchmark/reference/lfm2_moe_ref.py``: one full forward, no cache) on
seeded weights, alone and through the ONE serving path.  Tiny preset: hidden
64, 4 heads of 16, 8 layers ``c c A c c c A c`` with both dense layers, 16
experts top-4, vocabulary 512, float32, CPU."""

import inspect
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe_ref as ref
from can_tpu.models import lfm2_moe as lm
from can_tpu.models import lm_blocks
from can_tpu.obs import Telemetry, spans
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import moe as moe_ops
from can_tpu.ops import ssm as ssm_ops
from can_tpu.serve import GenerateService, build_model_service, lm_probe_steps
from can_tpu.serve import cache as kv_cache
from can_tpu.serve import programs as serve_programs

from lm_tiny import tiny_lfm2_config, tiny_lfm2_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_FILE = os.path.join(REPO, "benchmark", "configs",
                         "lfm2-24b-a2b-ep8-serve-bf16.json")
TOL = dict(atol=1e-4, rtol=1e-4)      # logits of rms 8: 1e-5 relative
NEW = 12


@pytest.fixture(scope="module")
def tiny():
    d, cfg, params = tiny_lfm2_model(seed=5)
    return d, cfg, params, ref.spec_from_config(d)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _padded(prompts, bucket):
    toks = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) for p in prompts], jnp.int32)


# jitted: a test that calls the model a few times compiles it once a shape,
# not every small op of its 8 layers once a shape
PREFILL = jax.jit(lm.prefill, static_argnums=(3, 4))
STEP = jax.jit(lm.decode_step, static_argnums=(4,))


def _cell_config():
    with open(CELL_FILE) as f:
        return json.load(f)


# -- the model against the reference ---------------------------------------
def test_the_tiny_preset_has_every_mechanism(tiny):
    d, cfg, params, _ = tiny
    assert set(cfg.layer_types) == {lm.CONV, lm.FULL} and cfg.layer_types[:3] == (
        lm.CONV, lm.CONV, lm.FULL)
    assert cfg.num_dense_layers == 2 and cfg.groups == 2 and cfg.head_dim == 16
    kinds = [("conv" in l, "mlp" in l) for l in params["layers"]]
    # a dense conv layer, a sparse conv layer, a sparse attention layer
    assert {(True, True), (True, False), (False, False)} <= set(kinds)
    assert params["layers"][0]["conv"]["in_proj"].shape == (64, 192)
    assert params["layers"][0]["conv"]["conv_w"].shape == (64, 3)
    assert "shared" not in params["layers"][2]["moe"] and "head" not in params
    assert params["layers"][2]["moe"]["bias"].dtype == jnp.float32


@pytest.mark.parametrize("held,rank,head_dim,pack", [
    (16, 0, 16, 1), (2, 3, 16, 1), (16, 0, 64, 2),
], ids=["all", "share", "heads-of-64-two-to-a-row"])
def test_prefill_then_12_decode_steps_match_the_reference(held, rank, head_dim,
                                                          pack):
    """Prompts of unequal length right-padded into one bucket, then 12
    greedy steps through the cache (keys, values and the convolution's
    tails), against the reference's ONE full forward over prompt +
    generated tokens: logits at every position; with every expert held and
    with rank 3's two of sixteen.  The preset's 2 key/value heads of 16
    never share a row (``kv_pack`` 1): the third case gives them the cell's
    width of 64, so the keys and values it writes and reads are packed two
    to a row, and says so before it compares."""
    d, cfg, params = tiny_lfm2_model(seed=5, held=held, rank=rank,
                                     head_dim=head_dim)
    assert cfg.head_dim == head_dim
    assert layout.kv_pack(cfg.num_key_value_heads, cfg.head_dim) == pack
    spec = ref.spec_from_config(d)
    prompts = [_tokens(21, 6), _tokens(9, 7), _tokens(32, 8)]
    toks, lengths = _padded(prompts, 32)
    logits, cache, routing = PREFILL(params, toks, lengths, cfg, 32 + NEW)
    assert cache["layers"][2]["k"].shape == (3, 2 // pack, 32 + NEW,
                                             pack * head_dim)
    assert routing["counts"].shape == (6, held)
    assert routing["choices"].shape == (6, 3, 4)
    got, seqs = [np.asarray(logits)], [list(p) for p in prompts]
    tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), lengths
    for _ in range(NEW):
        for s, t in zip(seqs, np.asarray(tok)):
            s.append(int(t))
        logits, cache, _ = STEP(params, cache, tok, pos, cfg)
        got.append(np.asarray(logits))
        tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), pos + 1
    got = np.stack(got, 1)                                   # (B, 13, V)
    for i, p in enumerate(prompts):
        out = ref.forward(params, np.asarray(seqs[i], np.int32), spec)
        np.testing.assert_allclose(got[i], np.asarray(out["logits"])[len(p) - 1:],
                                   **TOL)
        assert len(out["chosen"]) == 6 and out["chosen"][0].shape == (
            len(seqs[i]), 4)
    assert np.abs(got).max() > 5


def test_two_padded_prompts_hand_decode_what_each_would_have_alone(tiny):
    """The tail written to the cache is ``B * X`` at the last two positions
    of EACH PROMPT'S OWN LENGTH, whatever the padding behind it; a prompt of
    one token has a zero in front of its one input."""
    _, cfg, params, _ = tiny
    prompts = [_tokens(13, 4), _tokens(22, 5), _tokens(1, 6)]
    toks, lengths = _padded(prompts, 24)
    _, cache, _ = PREFILL(params, toks, lengths, cfg, 40)
    for i, p in enumerate(prompts):
        _, alone, _ = PREFILL(params, jnp.asarray(p)[None],
                              jnp.asarray([len(p)]), cfg, 40)
        for kind, mine, own in zip(cfg.layer_types, cache["layers"],
                                   alone["layers"]):
            if kind == lm.CONV:
                assert sorted(mine) == ["conv"]
                np.testing.assert_allclose(np.asarray(mine["conv"])[i],
                                           np.asarray(own["conv"])[0], **TOL)
            else:
                assert sorted(mine) == ["k", "v"]
                np.testing.assert_allclose(np.asarray(mine["k"])[i, :, :len(p)],
                                           np.asarray(own["k"])[0, :, :len(p)],
                                           **TOL)
    first = np.asarray(cache["layers"][0]["conv"])
    assert (first[2, :, 0] == 0).all() and (first[2, :, 1] != 0).any()


def test_an_inactive_slot_is_counted_in_no_routing(tiny):
    _, cfg, params, _ = tiny
    toks, lengths = _padded([_tokens(8, 9), _tokens(8, 10)], 8)
    _, cache, pre = PREFILL(params, toks, lengths, cfg, 16,
                            jnp.asarray([True, False]))
    assert int(pre["counts"].sum()) == 6 * 8 * 4       # one prompt's choices
    _, _, routing = STEP(params, cache, jnp.asarray([3, 4], jnp.int32),
                         lengths, cfg, jnp.asarray([True, False]))
    assert int(routing["counts"].sum()) == 6 * 4


# -- the share tied to the model ----------------------------------------
def test_the_eight_shares_of_an_expert_layer_sum_to_the_uncut_reference(tiny):
    """One expert layer's routed output, rank by rank (each holds 2 of the 16
    experts; nothing here is computed by every rank alike: no shared
    expert), sums to what the reference gives with all 16 held."""
    d, cfg, params, spec = tiny
    p = params["layers"][3]["moe"]
    x = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    whole = np.asarray(ref.expert_layer(p, x, spec))
    total = np.zeros_like(whole)
    for rank in range(8):
        d_r = tiny_lfm2_config(held=2, rank=rank)
        cfg_r = lm.Lfm2MoeConfig.from_dict(d_r)
        assert cfg_r.share == moe_ops.ExpertShare(2 * rank, 2, 16)
        part = {"router": p["router"], "bias": p["bias"],
                "experts": jax.tree.map(lambda w: w[2 * rank:2 * rank + 2],
                                        p["experts"])}
        mine, routed = lm_blocks.expert_layer(part, x, cfg_r)
        assert routed.idx.shape == (40, 4)
        # the same share of the reference is the same part
        np.testing.assert_allclose(
            np.asarray(mine),
            np.asarray(ref.expert_layer(part, x, ref.spec_from_config(d_r))),
            **TOL)
        total += np.asarray(mine)
    np.testing.assert_allclose(total, whole, **TOL)
    assert np.abs(whole).max() > 0.1


# -- the gated convolution ----------------------------------------------
def test_the_gated_convolution_s_two_forms_agree_and_match_the_reference(tiny):
    """``gated_conv_causal`` over a sequence, ``gated_conv_step`` position by
    position from a zero tail, and the reference's mixer between the same
    projections: one function; the tail after ``n`` steps is the causal
    form's at length ``n``."""
    _, cfg, params, spec = tiny
    p = params["layers"][0]["conv"]
    l, d = 11, 64
    bcx = jax.random.normal(jax.random.key(2), (2, l, 3 * d), jnp.float32)
    lengths = jnp.asarray([l, 7])
    y, tail = ssm_ops.gated_conv_causal(bcx, p["conv_w"], lengths)
    t, steps = jnp.zeros((2, d, 2), jnp.float32), []
    for i in range(l):
        out, t = ssm_ops.gated_conv_step(t, bcx[:, i], p["conv_w"])
        steps.append(out)
        if i + 1 == 7:
            at_seven = t
    np.testing.assert_allclose(np.asarray(jnp.stack(steps, 1)), np.asarray(y),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(t[0]), np.asarray(tail[0]))
    np.testing.assert_allclose(np.asarray(at_seven[1]), np.asarray(tail[1]))
    # the reference, with identities for the two projections
    eye = {"in_proj": jnp.eye(3 * d), "conv_w": p["conv_w"],
           "out_proj": jnp.eye(d)}
    with jax.default_matmul_precision("highest"):
        want = ref._conv_mixer(eye, bcx[0], spec, "f32", None)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    gate_in, gate_out, xs = np.split(np.asarray(bcx[0]), 3, -1)
    u = gate_in * xs
    w = np.asarray(p["conv_w"])
    np.testing.assert_allclose(
        np.asarray(y[0, 5]),
        gate_out[5] * (w[:, 0] * u[3] + w[:, 1] * u[4] + w[:, 2] * u[5]),
        atol=1e-5, rtol=1e-5)


def test_a_tail_one_position_late_is_caught(tiny, monkeypatch):
    """The calibration's break: decode then starts from the wrong two inputs
    and its first step's logits leave the reference's."""
    _, cfg, params, spec = tiny
    prompts = [_tokens(13, 4), _tokens(22, 5)]
    toks, lengths = _padded(prompts, 24)

    def first_step():
        # (a new function each time, so traced anew: the break is patched
        # in between)
        logits, cache, _ = jax.jit(lambda *a: lm.prefill(*a, cfg, 40))(
            params, toks, lengths)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return tok, STEP(params, cache, tok, lengths, cfg)[0]

    sound_tail = ssm_ops.conv_tail
    tok, sound = first_step()
    monkeypatch.setattr(ssm_ops, "conv_tail",
                        lambda x, n, width: sound_tail(x, n + 1, width))
    _, late = first_step()
    for i, p in enumerate(prompts):
        want = np.asarray(ref.forward(
            params, np.append(p, int(tok[i])).astype(np.int32), spec)["logits"])[-1]
        np.testing.assert_allclose(np.asarray(sound)[i], want, **TOL)
        assert np.abs(np.asarray(late)[i] - want).max() > 0.05


# -- the router -----------------------------------------------------------
def test_the_bias_moves_the_choice_and_never_the_weight():
    x = jax.random.normal(jax.random.key(0), (6, 64), jnp.float32)
    router = jax.random.normal(jax.random.key(1), (64, 16), jnp.float32) / 8
    zero = jnp.zeros((16,), jnp.float32)
    idx0, w0 = moe_ops.route(x, router, zero, top_k=4, scale=1.0)
    bias = zero.at[11].set(10.0)                  # expert 11 always chosen
    idx1, w1 = moe_ops.route(x, router, bias, top_k=4, scale=1.0)
    assert (np.asarray(idx1) == 11).any(-1).all()
    assert not (np.asarray(idx0) == 11).any(-1).all()
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    for t in range(6):
        s = scores[t, np.asarray(idx1)[t]]
        # sigmoid scores, never score + bias; ``route`` divides by the sum,
        # the published code by the sum + 1e-6 (the reference's): 5e-7
        np.testing.assert_allclose(np.asarray(w1)[t], s / s.sum(), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w1)[t], s / (s.sum() + 1e-6),
                                   rtol=2e-6)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, rtol=1e-6)
    chosen, w_ref = ref._route(x, router, bias, {"top_k": 4, "scale": 1.0,
                                                 "normalise": True}, None)
    assert (np.sort(np.asarray(chosen)) == np.sort(np.asarray(idx1))).all()
    np.testing.assert_allclose(np.sort(np.asarray(w_ref)),
                               np.sort(np.asarray(w1)), rtol=2e-6)


# -- the reference's controls ----------------------------------------------
@pytest.mark.parametrize("variant", ["no_gate_b", "no_gate_c", "no_qk_norm",
                                     "unnormalised_topk", "bias_in_weights",
                                     "expert_zeroed"])
def test_a_broken_piece_of_the_mathematics_moves_the_reference(tiny, variant):
    _, _, params, spec = tiny
    seq = _tokens(24, 3)
    sound = np.asarray(ref.forward(params, seq, spec)["logits"])
    broken = np.asarray(ref.forward(params, seq, spec, "f32", variant)["logits"])
    assert np.abs(broken - sound).max() > 0.02 * np.abs(sound).max()


def test_the_yardstick_rounds_and_a_bit_fewer_rounds_more(tiny):
    _, _, params, spec = tiny
    seq = _tokens(24, 3)
    sound = np.asarray(ref.forward(params, seq, spec)["logits"])
    gaps = {m: np.linalg.norm(np.asarray(ref.forward(
        params, seq, spec, m)["logits"]) - sound, axis=-1).mean()
        for m in ("bf16", "bf16-1", "int8")}
    assert 0 < gaps["bf16"] < 0.05 * np.linalg.norm(sound, axis=-1).mean()
    assert gaps["bf16-1"] > 1.5 * gaps["bf16"]
    assert gaps["int8"] > gaps["bf16"]
    assert "can_tpu" not in inspect.getsource(ref).split('"""', 2)[2]


def test_rows_returns_the_rows_asked_for(tiny):
    _, _, params, spec = tiny
    seq = _tokens(20, 11)
    full = ref.forward(params, seq, spec)
    some = ref.forward(params, seq, spec, rows=np.asarray([4, 19]))
    np.testing.assert_allclose(np.asarray(some["logits"]),
                               np.asarray(full["logits"])[[4, 19]], **TOL)
    assert [c.shape for c in some["chosen"]] == [(2, 4)] * 6


# -- keys and values in rows of whole lanes --------------------------------
@pytest.mark.parametrize("kv_heads,head_dim,pack", [
    (8, 64, 2), (8, 128, 1), (8, 32, 4), (8, 96, 4), (3, 64, 1), (2, 16, 1),
    (4, 256, 1),
], ids=["lfm2-64", "whole-lanes-128", "four-of-32", "four-of-96-in-three-lane-rows",
        "3-heads-are-no-whole-rows", "the-tiny-presets", "wider-than-a-row"])
def test_a_leaf_s_row_is_whole_lanes_or_one_head(kv_heads, head_dim, pack):
    """``kv_layer``: heads that fill a row of 128 lanes exactly lie ``pack``
    side by side, in whole rows or not at all; every other head keeps a row
    of its own.  The bytes are the same either way."""
    assert layout.kv_pack(kv_heads, head_dim) == pack
    for kind, window, held in (("full", None, 40), ("ring", 8, 8)):
        spec = layout.kv_layer(kind, kv_heads=kv_heads, head_dim=head_dim,
                               window=window)
        assert spec.shapes(5, 40) == {
            n: (5, kv_heads // pack, held, pack * head_dim) for n in "kv"}


def _a_head_a_row(leaf, pack):
    """A packed leaf (B, rows, S, pack * D) as (B, rows * pack, S, D)."""
    b, rows, s, width = leaf.shape
    return leaf.reshape(b, rows, s, pack, width // pack).transpose(
        0, 1, 3, 2, 4).reshape(b, rows * pack, s, width // pack)


@pytest.mark.parametrize("late", [False, True], ids=["sound", "late_write"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kv_heads,head_dim,window", [
    (8, 64, None), (4, 64, 16), (8, 32, None),
], ids=["full-two-to-a-row", "ring-two-to-a-row", "full-four-to-a-row"])
def test_packed_write_and_decode_equal_a_head_a_row(
        kv_heads, head_dim, window, dtype, late):
    """``write_slot`` then ``decode`` over a leaf of packed rows against the
    same two over a leaf with a row a head, on the same random keys, values,
    queries, slots and ``valid``: the leaves equal row for row, and the
    outputs BIT FOR BIT in float32 (the other heads' lanes meet zeros in
    the score and are dropped from the values).  In bfloat16 the CPU's
    product sums a row of 128 in another order than a row of 64 (the float32
    scores differ by 4e-6), so one output in a thousand rounds to the
    neighbouring bfloat16: equal but for those, each within one step.  A
    ring of packed heads too, and under ``calibrate_lm.late_write``, which
    patches ``write_slot`` by its signature and wraps the late slot at
    ``cache.shape[2]``."""
    from benchmark.tools import calibrate_lm

    assert list(inspect.signature(attn_ops.write_slot).parameters) == [
        "cache", "new", "slot"]
    b, g, s = 5, 4, window or 48
    pack = layout.kv_pack(kv_heads, head_dim)
    assert pack == 128 // head_dim
    ks = jax.random.split(jax.random.key(kv_heads + head_dim), 7)
    packed = (b, kv_heads // pack, s, pack * head_dim)
    kc, vc = (jax.random.normal(k, packed).astype(dtype) for k in ks[:2])
    q = jax.random.normal(ks[2], (b, kv_heads, g, head_dim)).astype(dtype)
    nk, nv = (jax.random.normal(k, (b, kv_heads, head_dim)).astype(dtype)
              for k in ks[3:5])
    pos = jax.random.randint(ks[5], (b,), 0, 3 * s)
    if window:
        slot = pos % window
        valid = attn_ops.ring_positions(pos, window) >= 0
    else:
        slot = pos % s
        valid = jnp.arange(s)[None] <= slot[:, None]
    valid &= jax.random.bernoulli(ks[6], 0.8, (b, s))

    @jax.jit
    def step(kc, vc):
        kc = attn_ops.write_slot(kc, nk, slot)
        vc = attn_ops.write_slot(vc, nv, slot)
        return kc, vc, attn_ops.decode(q, kc, vc, valid)

    if late:
        calibrate_lm.late_write(None)
    try:
        k2, v2, got = step(kc, vc)
        k1, v1, want = step(_a_head_a_row(kc, pack), _a_head_a_row(vc, pack))
    finally:
        if late:
            calibrate_lm.late_write.undo()
    assert k2.shape == packed and k1.shape == (b, kv_heads, s, head_dim)
    written = np.asarray(k1, np.float32)[np.arange(b), :,
                                         (np.asarray(slot) + late) % s]
    np.testing.assert_array_equal(written, np.asarray(nk, np.float32))
    for mine, plain in ((k2, k1), (v2, v1)):
        np.testing.assert_array_equal(
            np.asarray(_a_head_a_row(mine, pack), np.float32),
            np.asarray(plain, np.float32))
    assert got.shape == want.shape == q.shape and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got, want)
    else:
        assert (got != want).mean() < 0.01
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -9)
    assert np.abs(got).max() > 0.1


@pytest.mark.parametrize("kv_heads,head_dim", [(8, 64), (2, 16), (4, 128)],
                         ids=["two-to-a-row", "tiny", "whole-lanes"])
def test_a_prefill_s_entry_is_write_slot_position_by_position(kv_heads,
                                                              head_dim):
    """``as_leaf`` (``lm_blocks.kv_entry``'s full-layer entry) places a prompt's
    keys where ``write_slot`` would have written them one position at a
    time, in the shape ``kv_layer`` states; the positions behind are zero."""
    b, l, s = 3, 7, 12
    shape = layout.kv_layer("full", kv_heads=kv_heads,
                            head_dim=head_dim).shapes(b, s)["k"]
    k = jax.random.normal(jax.random.key(head_dim), (b, l, kv_heads, head_dim))
    want = jnp.zeros(shape)
    for p in range(l):
        want = attn_ops.write_slot(want, k[:, p], jnp.full((b,), p))
    got = attn_ops.as_leaf(k, shape)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[:, :, l:] == 0).all() and np.asarray(got).any()


# -- the cell's file ------------------------------------------------------
def test_param_count_and_cache_of_the_cell_s_file():
    """The builder's own reckoning (the configuration's ``reduced_how``): 30
    ``state``-only and 10 ``full``-only entries at the published pattern."""
    d = _cell_config()
    cfg = lm.Lfm2MoeConfig.from_dict(d)
    assert lm.param_count(cfg) == (
        30 * 16_783_360 + 10 * 10_485_888 + 2 * 72_351_744
        + 38 * (131_136 + 8 * 9_437_184) + 163_840 + 134_219_776
    ) == 3_761_333_888
    layout = lm.cache_layout(cfg)
    kinds = [s.kind for s in layout]
    assert kinds.count("state") == 30 and kinds.count("full") == 10
    assert [i for i, k in enumerate(kinds) if k == "full"] == list(range(2, 40, 4))
    cache = jax.eval_shape(lambda: kv_cache.allocate(
        layout, slots=64, positions=1280))
    assert kv_cache.nbytes_by_kind(cache, layout) == {
        "state": 64 * 245_760, "full": 64 * 1280 * 20_480}
    assert cache["layers"][0]["conv"].shape == (64, 2048, 2)
    assert cache["layers"][2]["k"].shape == (64, 4, 1280, 128)   # 8 x 64
    assert cfg.share == moe_ops.ExpertShare(0, 8, 64) and cfg.head_dim == 64
    assert lm_blocks.experts_form(cfg, 64, jnp.bfloat16) == "batched"
    assert lm_blocks.experts_form(cfg, 8192, jnp.bfloat16) == "sorted"


def test_the_cell_s_file_states_its_cut_and_its_assumptions():
    d = _cell_config()
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"LFM2-24B-A2B"' in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    if row:
        changed = {k for k, v in row[0]["config"].items() if d.get(k) != v}
        assert changed == {"num_experts"} == set(d["reduced"])
        assert d["source"] == row[0]["source_url"]
    assert d["num_experts"] == 8 and d["published"]["num_experts"] == 64
    assert d["num_hidden_layers"] == 40 == len(d["layer_types"])
    assert d["deployment"]["chips_per_layer"] == 8 and d["deployment"]["rank"] == 0
    for key in ("head_dim", "tie_word_embeddings", "rope_pairing",
                "conv_tail_dtype", "max_batch", "prefill_slice", "sampling"):
        assert key in d["assumed"], key
    assert (d["max_batch"], d["queue_capacity"], d["length_ladder"],
            d["max_new_tokens"], d["prefill_slice"]) == (64, 256, [1024], 256, 8)


def test_the_benchmark_s_own_weights_are_the_tree_the_program_reads():
    from benchmark.harness import weights_lfm2_moe as w

    assert "can_tpu" not in inspect.getsource(w).split('"""', 2)[2]
    for d in (_cell_config(), tiny_lfm2_config(held=2, rank=1)):
        assert w.shapes(d) == lm.param_shapes(lm.Lfm2MoeConfig.from_dict(d))
    params = w.make_params(tiny_lfm2_config(), 2**31 + 3)
    assert params["layers"][2]["moe"]["bias"].dtype == jnp.float32
    assert params["embed"].dtype == jnp.bfloat16
    std = float(np.std(np.asarray(params["embed"], np.float32)))
    assert std == pytest.approx(64 ** -0.25, rel=0.05)


@pytest.mark.parametrize("edit,match", [
    ({"num_hidden_layers": 7}, "layer_types names 8 layers"),
    ({"layer_types": ["conv"] * 7 + ["sliding_attention"]}, "not implemented"),
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"assumed": {"tie_word_embeddings": False}}, "tie_word_embeddings"),
    ({"assumed": {"rope_pairing": "interleaved"}}, "rope_pairing"),
    ({"assumed": {"conv_tail_dtype": "float32"}}, "conv_tail_dtype"),
    ({"num_key_value_heads": 3}, "heads do not divide"),
])
def test_what_is_not_implemented_is_refused(edit, match):
    d = tiny_lfm2_config()
    d.update(edit)
    with pytest.raises(ValueError, match=match):
        lm.Lfm2MoeConfig.from_dict(d)


def test_any_pattern_of_layers_is_honoured():
    """``layer_types`` and ``num_dense_layers`` are read layer by layer, not
    the published pattern hard-wired: attention first, one dense layer."""
    d = tiny_lfm2_config(layer_types=["full_attention", "conv", "conv"])
    d["num_dense_layers"] = 1
    cfg = lm.Lfm2MoeConfig.from_dict(d)
    params = lm.init_params(jax.random.key(0), cfg, jnp.float32)
    assert [("attn" in l, "mlp" in l) for l in params["layers"]] == [
        (True, True), (False, False), (False, False)]
    assert [s.kind for s in lm.cache_layout(cfg)] == ["full", "state", "state"]
    seq = _tokens(10, 2)
    hidden, _, _ = lm.prefill_hidden(params, jnp.asarray(seq)[None],
                                     jnp.asarray([10]), cfg)
    want = ref.forward(params, seq, ref.spec_from_config(d))["hidden"]
    np.testing.assert_allclose(np.asarray(hidden)[0], np.asarray(want), **TOL)


# -- through the serving path ---------------------------------------------
def lfm2_config(**kw) -> dict:
    d = tiny_lfm2_config(held=4, rank=1)
    d.update(max_new_tokens=NEW, prefill_slice=2, length_ladder=[16, 32],
             max_batch=4, queue_capacity=16, max_wait_ms=5.0)
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def service():
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = lm.Lfm2MoeConfig.from_dict(lfm2_config())
    params = lm.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(lfm2_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


def test_the_table_builds_the_same_programs_class():
    entry = serve_programs.serving_model("lfm2_moe")
    made, params = entry.programs(lfm2_config(), None, 3)
    assert isinstance(made, serve_programs.LMPrograms)
    assert made.vocab_size == 512 and made.attention_traced is None
    assert made.ssm_traced is None and made.conv_traced is lm.conv_traced
    assert [s.kind for s in made.cache_layout] == [
        "state", "state", "full", "state", "state", "state", "full", "state"]
    assert params["embed"].dtype == jnp.bfloat16 == made.dtype
    assert made.decode_experts(4) == "batched"
    assert {"conv.proj", "conv.mix", "conv.out"} <= set(made.parts)


def test_greedy_ids_and_probe_logits_match_the_reference_through_the_queue(service):
    """Three prompts of different lengths in one launch: each request's 12
    greedy ids and its probed logits and routing are the reference's over
    ITS OWN sequence (rank 1's 4 of 16 experts on both sides)."""
    svc, report, _ = service
    assert isinstance(svc, GenerateService)
    assert report["compiles"] == 2 * 2 == svc.engine.compile_count
    prompts = [_tokens(27, 99), _tokens(18, 98), _tokens(32, 97)]
    tickets = [svc.submit(p, want_logits=(i != 1)) for i, p in enumerate(prompts)]
    results = [t.result(120) for t in tickets]
    assert svc.engine.compile_count == 4 and results[1].logits is None
    spec = ref.spec_from_config(lfm2_config())
    steps = lm_probe_steps(NEW)
    for p, r in zip(prompts, results):
        assert r.tokens.shape == (NEW,) and r.bucket_hw == (1, 32)
        out = ref.forward(svc.engine.params, np.concatenate([p, r.tokens]), spec)
        full = np.asarray(out["logits"])
        want = full[len(p) - 1:len(p) - 1 + NEW]
        margin = want[np.arange(NEW), r.tokens] - want.max(-1)
        assert (margin > -1e-3).all(), margin            # the greedy ids
        if r.logits is None:
            continue
        for name, at in [("prefill", len(p) - 1)] + [
                (f"step{s}", len(p) - 1 + s) for s in steps]:
            np.testing.assert_allclose(r.logits[name], full[at], **TOL)
            assert r.routing[name].shape == (6, 4)
            for layer, chosen in enumerate(out["chosen"]):
                assert set(r.routing[name][layer].tolist()) == set(
                    np.asarray(chosen)[at].tolist())


def test_the_counters_read_the_share_and_both_kinds_of_cache(service):
    svc, _, _ = service
    svc.submit(_tokens(8, 1)).result(120)
    stats = svc.stats()["lm"]
    assert 0 < stats["assignments_held"] < stats["assignments_all"]
    # 4 slots x (16 + 12) positions x 2 attention layers x 2 x 2 heads x 16 x
    # 4 bytes; 4 slots x 6 conv layers x 64 channels x 2 x 4 bytes
    assert stats["cache_bytes"] == {"full": 4 * 28 * 2 * 2 * 2 * 16 * 4,
                                    "state": 4 * 6 * 64 * 2 * 4}
    assert stats["decode_experts_read"] == stats["decode_experts_held"] > 0


def test_the_spans_say_which_form_the_convolution_ran_in(service):
    svc, _, tracer = service
    ticket = svc.submit(_tokens(9, 7))
    ticket.result(120)
    want = ticket._request.batch_span.span_id
    for _ in range(500):
        ring = tracer.snapshot()
        if any(s["span_id"] == want for s in ring):
            break
        time.sleep(0.01)
    launch = next(s for s in ring if s["name"] == "serve.dispatch"
                  and s.get("parent_id") == want)
    inner = {s["name"]: s for s in ring if s.get("parent_id") == launch["span_id"]}
    assert inner["lm.prefill"]["conv"] == "causal"
    assert inner["lm.decode"]["conv"] == "step"
    assert inner["lm.decode"]["experts"] == "batched"
    assert "ssm" not in inner["lm.prefill"] and "ssm" not in inner["lm.decode"]
    assert svc.engine.conv_forms == {(2, 16): "causal", (2, 32): "causal",
                                     (4, 1): "step"}
