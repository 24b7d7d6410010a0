"""``models/falcon_h1.py`` (a Mamba-2 mixer and grouped-query attention side
by side in every block) against the plain reference
(``benchmark/reference/falcon_h1_ref.py``: the recurrence token by token,
no cache) on seeded weights.  Tiny preset with every multiplier different
from 1, G = 2 groups, 5 query heads to a key/value head, float32, CPU."""

import dataclasses
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1_ref as ref
from can_tpu.models import falcon_h1 as fh

from lm_tiny import tiny_falcon_config, tiny_falcon_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_FILE = os.path.join(REPO, "benchmark", "configs",
                         "falcon-h1-34b-pp12-serve-bf16.json")
TOL = dict(atol=3e-5, rtol=3e-5)


@pytest.fixture(scope="module")
def tiny():
    d, cfg, params = tiny_falcon_model(seed=5)
    return d, cfg, params, ref.spec_from_config(d)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _padded(prompts, bucket):
    toks = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) for p in prompts], jnp.int32)


def test_the_tiny_preset_has_every_mechanism(tiny):
    d, cfg, params, _ = tiny
    mults = [cfg.embedding_multiplier, cfg.lm_head_multiplier,
             cfg.attention_in_multiplier, cfg.attention_out_multiplier,
             cfg.key_multiplier, cfg.ssm_in_multiplier, cfg.ssm_out_multiplier,
             *cfg.ssm_multipliers, *cfg.mlp_multipliers]
    assert all(m != 1 for m in mults) and len(set(mults)) >= 9
    assert cfg.mamba_n_groups == 2 and cfg.groups == 5
    assert cfg.conv_dim == 48 + 2 * 2 * 16 and cfg.in_proj_dim == 48 + 112 + 6
    assert params["layers"][0]["mixer"]["in_proj"].shape == (64, 166)
    assert params["layers"][0]["mixer"]["conv_w"].shape == (112, 4)
    a = -np.exp(np.asarray(params["layers"][1]["mixer"]["A_log"]))
    assert ((a <= -1) & (a >= -16)).all()          # Mamba-2's range


def test_prefill_hidden_matches_the_reference_s_full_forward(tiny):
    """Whole prompts of different lengths right-padded into one bucket: every
    valid position's hidden state is the reference's over the prompt alone
    (the chunked recurrence against the token-by-token scan)."""
    _, cfg, params, spec = tiny
    prompts = [_tokens(29, 1), _tokens(17, 2), _tokens(32, 3)]
    toks, lengths = _padded(prompts, 32)
    hidden, _ = fh.prefill_hidden(params, toks, lengths, cfg)
    for i, p in enumerate(prompts):
        want = ref.forward(params, p, spec)["hidden"]
        np.testing.assert_allclose(np.asarray(hidden)[i, :len(p)],
                                   np.asarray(want), **TOL)


def test_two_padded_prompts_hand_decode_what_each_would_have_alone(tiny):
    """The duty attention never had: the state and the convolution's tail
    written to the cache are those AT EACH PROMPT'S OWN LENGTH."""
    _, cfg, params, _ = tiny
    prompts = [_tokens(13, 4), _tokens(22, 5)]
    toks, lengths = _padded(prompts, 24)
    _, cache, _ = fh.prefill(params, toks, lengths, cfg, 40)
    for i, p in enumerate(prompts):
        # alone: a bucket of its own length, one chunk of that length
        own = dataclasses.replace(cfg, mamba_chunk_size=len(p))
        _, alone, _ = fh.prefill(params, jnp.asarray(p)[None],
                                 jnp.asarray([len(p)]), own, 40)
        for layer, mine in zip(alone["layers"], cache["layers"]):
            np.testing.assert_allclose(np.asarray(mine["ssm"])[i],
                                       np.asarray(layer["ssm"])[0], **TOL)
            np.testing.assert_allclose(np.asarray(mine["conv"])[i],
                                       np.asarray(layer["conv"])[0], **TOL)
            np.testing.assert_allclose(np.asarray(mine["k"])[i, :, :len(p)],
                                       np.asarray(layer["k"])[0, :, :len(p)],
                                       **TOL)
    assert cache["layers"][0]["ssm"].dtype == jnp.float32


def test_prefill_then_12_decode_steps_match_the_reference(tiny):
    """Prefill, then 12 greedy steps through the cache (keys, values, state
    and tail), against the reference's ONE full forward over prompt +
    generated tokens: logits at every position."""
    _, cfg, params, spec = tiny
    prompts = [_tokens(21, 6), _tokens(9, 7), _tokens(32, 8)]
    toks, lengths = _padded(prompts, 32)
    logits, cache, routing = fh.prefill(params, toks, lengths, cfg, 32 + 12)
    assert routing["counts"].shape == (0, 0)
    assert routing["choices"].shape == (0, 3, 0)
    got, seqs = [np.asarray(logits)], [list(p) for p in prompts]
    tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), lengths
    step = jax.jit(fh.decode_step, static_argnums=(4,))
    for _ in range(12):
        for s, t in zip(seqs, np.asarray(tok)):
            s.append(int(t))
        logits, cache, _ = step(params, cache, tok, pos, cfg)
        got.append(np.asarray(logits))
        tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), pos + 1
    got = np.stack(got, 1)                                   # (B, 13, V)
    for i, p in enumerate(prompts):
        want = np.asarray(ref.forward(params, np.asarray(seqs[i], np.int32),
                                      spec)["logits"])[len(p) - 1:]
        np.testing.assert_allclose(got[i], want, **TOL)
    assert np.abs(want).max() > 0.5      # logits of order one after 0.0078


def test_an_inactive_slot_keeps_its_state(tiny):
    _, cfg, params, _ = tiny
    toks, lengths = _padded([_tokens(8, 9), _tokens(8, 10)], 8)
    _, cache, _ = fh.prefill(params, toks, lengths, cfg, 16)
    _, moved, _ = fh.decode_step(params, cache, jnp.asarray([3, 4], jnp.int32),
                                 lengths, cfg,
                                 active=jnp.asarray([True, False]))
    for before, after in zip(cache["layers"], moved["layers"]):
        assert (np.asarray(after["ssm"])[1] == np.asarray(before["ssm"])[1]).all()
        assert (np.asarray(after["ssm"])[0] != np.asarray(before["ssm"])[0]).any()


@pytest.mark.parametrize("variant", ["state_bf16", "norm_all_channels",
                                     "no_key_multiplier", "no_ssm_multipliers"])
def test_a_broken_piece_of_the_mathematics_moves_the_reference(tiny, variant):
    """Each control of the calibration is a different computation."""
    _, _, params, spec = tiny
    p = _tokens(24, 11)
    sound = np.asarray(ref.forward(params, p, spec)["logits"])
    broken = np.asarray(ref.forward(params, p, spec, "f32", variant)["logits"])
    assert np.abs(broken - sound).max() > (1e-4 if variant == "state_bf16"
                                           else 0.1)


def test_the_yardstick_rounds_and_stays_near(tiny):
    _, _, params, spec = tiny
    p = _tokens(24, 12)
    sound = np.asarray(ref.forward(params, p, spec)["logits"])
    bf16 = np.asarray(ref.forward(params, p, spec, "bf16")["logits"])
    gap = np.abs(bf16 - sound).max()
    assert 1e-3 < gap < 0.3
    # PR 30's finding: the yardstick rounds with reduce_precision; an astype
    # pair keeps excess precision inside a fusion on the TPU
    code = [l for l in inspect.getsource(ref).splitlines()
            if not l.strip().startswith("#")]
    assert not any(".astype(" in l for l in code)
    assert "reduce_precision" in inspect.getsource(ref._round)
    assert "can_tpu" not in "".join(
        l for l in inspect.getsource(ref).splitlines()
        if l.startswith(("import", "from")))


def test_rows_returns_the_rows_asked_for(tiny):
    _, _, params, spec = tiny
    p = _tokens(20, 13)
    full = np.asarray(ref.forward(params, p, spec)["logits"])
    some = np.asarray(ref.forward(params, p, spec, rows=np.arange(15, 20))["logits"])
    np.testing.assert_allclose(some, full[15:], atol=1e-6)


# -- the configuration --------------------------------------------------
def test_param_count_of_the_cell_s_file():
    with open(CELL_FILE) as f:
        d = json.load(f)
    cfg = fh.FalconH1Config.from_dict(d)
    assert fh.param_count(cfg) == 5_254_594_112
    assert cfg.num_layers == 6 and cfg.vocab.held == cfg.vocab.total == 261120
    # one layer: attention, mixer, MLP, two norms (ISSUE 32's arithmetic)
    one = dataclasses.replace(cfg, num_hidden_layers=1)
    assert fh.param_count(one) - fh.param_count(
        dataclasses.replace(cfg, num_hidden_layers=0)) == 430_120_032
    layer = fh.param_shapes(cfg)["layers"][0]
    assert layer["mixer"]["in_proj"] == (5120, 9248)
    assert layer["mixer"]["conv_w"] == (5120, 4)


def test_the_cell_s_cache_is_the_issue_s_arithmetic():
    from can_tpu.serve import cache as kv_cache

    with open(CELL_FILE) as f:
        cfg = fh.FalconH1Config.from_dict(json.load(f))
    made = jax.eval_shape(lambda: kv_cache.allocate(
        fh.cache_layout(cfg), slots=64, positions=1280))
    by_kind = kv_cache.nbytes_by_kind(made, fh.cache_layout(cfg))
    assert by_kind == {"full": 64 * 1280 * 12_288, "state": 64 * 25_350_144}
    assert by_kind["state"] > by_kind["full"]    # the state IS the larger cache


@pytest.mark.parametrize("key,value,match", [
    ("attn_layer_indices", [0, 2], "attn_layer_indices must be null"),
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("attention_bias", True, "attention_bias"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling must be null"),
    ("mamba_d_ssm", 40, "mamba_n_heads x mamba_d_head"),
])
def test_what_is_not_implemented_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        fh.FalconH1Config.from_dict(dict(tiny_falcon_config(), **{key: value}))


@pytest.mark.parametrize("name,other", [
    ("state_dtype", "bfloat16"), ("gated_norm", "all_channels"),
    ("rope_pairing", "interleaved"), ("dt_limit", "clamped"),
    ("conv_tail_dtype", "float32")])
def test_an_assumed_value_other_than_the_one_is_refused(name, other):
    """The state's float32 among them: a bfloat16 state is another result,
    not a configuration of this one."""
    assert fh.FalconH1Config.from_dict(
        tiny_falcon_config(**{name: fh.ASSUMED[name]})).num_layers == 2
    with pytest.raises(ValueError, match=f"{name} .* is not implemented"):
        fh.FalconH1Config.from_dict(tiny_falcon_config(**{name: other}))


def test_the_cell_s_file_states_its_cut_and_its_assumptions():
    with open(CELL_FILE) as f:
        d = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == d["name"])
    assert d["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert d["source"] == entry["source"]
    assert d["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    assert {k: d["assumed"][k] for k in fh.ASSUMED} == fh.ASSUMED
    assert d["deployment"]["pipeline_stages"] == 12 and d["deployment"]["stage"] == 0
    # every width, head count, state and chunk size and the vocabulary published
    assert [d[k] for k in ("hidden_size", "intermediate_size", "head_dim",
                           "num_attention_heads", "num_key_value_heads",
                           "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
                           "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                           "mamba_chunk_size", "vocab_size")] == [
        5120, 21504, 128, 20, 4, 4096, 32, 128, 256, 2, 4, 128, 261120]
    assert set(d["limits_why"]) >= set(d["limits"])


def test_init_leaves_every_branch_of_order_one_after_its_multiplier(tiny):
    """With N(0, 1 / fan_in) projections the published multipliers would
    leave mixer, attention and logits vanishing: the initialiser divides
    each projection by the multiplier that follows it."""
    _, cfg, params, _ = tiny
    toks, lengths = _padded([_tokens(32, 14)], 32)
    layer = params["layers"][0]
    x = fh._scaled(params["embed"][toks], cfg.embedding_multiplier)
    u = fh.rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
    m, _, _ = fh.mixer_chunked(layer["mixer"],
                               fh._scaled(u, cfg.ssm_in_multiplier), lengths, cfg)
    positions = jnp.arange(32)[None]
    q, k, v = fh._qkv(layer["attn"], fh._scaled(u, cfg.attention_in_multiplier),
                      positions, cfg)
    for name, a in (("residual", x), ("mixer", m * cfg.ssm_out_multiplier),
                    ("keys", k), ("queries", q)):
        rms = float(jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32)))))
        assert 0.2 < rms < 5.0, (name, rms)
