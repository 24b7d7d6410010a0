"""``models/mimo_v2_flash.py`` (window and full attention layers that differ
in their key/value heads, their rotary base and a learned sink; keys wider
than values; rotary on part of a head; a value scale; sparse experts without
a shared one) against the plain reference
(``benchmark/reference/mimo_v2_flash_ref.py``: one full forward, no cache) on
seeded weights, alone and through the ONE serving path.  Tiny preset: hidden
64, 8 query heads, 2 / 4 key/value heads, keys 24 wide of which 8 rotary,
values 16, window 8, 7 layers ``F S S S S F S`` with layer 0 dense, 32
experts top-4, vocabulary 512, float32, CPU."""

import functools
import inspect
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2_flash_ref as ref
from can_tpu.models import lm_blocks
from can_tpu.models import mimo_v2_flash as mv
from can_tpu.obs import Telemetry, spans
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import moe as moe_ops
from can_tpu.ops import pallas_attention as fused_attn
from can_tpu.serve import GenerateService, build_model_service, lm_probe_steps
from can_tpu.serve import cache as kv_cache
from can_tpu.serve import programs as serve_programs

from lm_tiny import tiny_mimo_config, tiny_mimo_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_FILE = os.path.join(REPO, "benchmark", "configs",
                         "mimo-v2-flash-ep16-serve-bf16.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOL = dict(atol=1e-4, rtol=1e-4)
NEW = 14          # decode steps: past a ring of 8's wrap
# the published widths of a head at a size the CPU runs: keys 192 (64
# rotary) two heads to a row of 384 lanes, values 128 a head a row
PUBLISHED_HEADS = dict(head_dim=192, swa_head_dim=192, v_head_dim=128,
                       swa_v_head_dim=128)


@pytest.fixture(scope="module")
def tiny():
    d, cfg, params = tiny_mimo_model(seed=5)
    return d, cfg, params, ref.spec_from_config(d)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _padded(prompts, bucket):
    toks = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) for p in prompts], jnp.int32)


# jitted: a test that calls the model a few times compiles it once a shape
PREFILL = jax.jit(mv.prefill, static_argnums=(3, 4))
STEP = jax.jit(mv.decode_step, static_argnums=(4,))


def _cell_config():
    with open(CELL_FILE) as f:
        return json.load(f)


# -- the model against the reference ---------------------------------------
def test_the_tiny_preset_has_every_mechanism(tiny):
    d, cfg, params, _ = tiny
    assert cfg.window_layers == (False, True, True, True, True, False, True)
    assert cfg.sparse_layers == (False,) + (True,) * 6
    assert (cfg.kv_heads_full, cfg.kv_heads_window) == (2, 4)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim) == (24, 16, 8)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (5e6, 1e4)
    assert cfg.routed_scaling_factor == 1.0 and cfg.window_sink
    full, window = params["layers"][5]["attn"], params["layers"][1]["attn"]
    assert full["wk"].shape == (64, 2 * 24) and full["wv"].shape == (64, 2 * 16)
    assert window["wk"].shape == (64, 4 * 24) and window["wv"].shape == (64, 4 * 16)
    assert full["wo"].shape == window["wo"].shape == (8 * 16, 64)
    assert window["sink"].shape == (8,) and "sink" not in full
    assert float(jnp.std(window["sink"])) > 0.3            # drawn, not zeros
    assert float(jnp.mean(window["sink"])) > 1.0           # about ln(window) = 2.08
    assert "mlp" in params["layers"][0] and "moe" in params["layers"][1]
    assert "shared" not in params["layers"][1]["moe"] and "head" in params
    assert params["layers"][1]["moe"]["bias"].dtype == jnp.float32


@pytest.mark.parametrize("held,rank,heads", [
    (32, 0, {}), (8, 1, {}), (32, 0, PUBLISHED_HEADS), (8, 3, PUBLISHED_HEADS),
], ids=["all", "share", "keys-192-values-128-packed", "share-packed"])
def test_prefill_then_14_decode_steps_match_the_reference(held, rank, heads):
    """Prompts of unequal length right-padded into one bucket (one of 5,
    shorter than the window of 8), then 14 greedy steps through the cache
    (past the rings' wrap), against the reference's ONE full forward over
    prompt + generated tokens: logits at every position; with every expert
    held and with a rank's eight of thirty-two.  The preset's heads of 24
    never share a row (``kv_pack`` 1): the third and fourth cases give them
    the published widths, so their keys lie two heads to a row of 384 lanes
    beside values of 128 a head a row.  The full layers' prefill is the
    scanned ``prefill_causal`` over groups at every bucket."""
    d, cfg, params = tiny_mimo_model(seed=5, held=held, rank=rank, **heads)
    spec = ref.spec_from_config(d)
    prompts = [_tokens(5, 6), _tokens(19, 7), _tokens(32, 8)]
    toks, lengths = _padded(prompts, 32)
    logits, cache, routing = PREFILL(params, toks, lengths, cfg, 32 + NEW)
    pack = 2 if heads else 1
    hd, dv = cfg.head_dim, cfg.v_head_dim
    assert cache["layers"][0]["k"].shape == (3, 2 // pack, 32 + NEW, pack * hd)
    assert cache["layers"][0]["v"].shape == (3, 2, 32 + NEW, dv)
    assert cache["layers"][1]["k"].shape == (3, 4 // pack, 8, pack * hd)
    assert cache["layers"][1]["v"].shape == (3, 4, 8, dv)
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
    got = np.stack(got, 1)                                   # (B, 15, V)
    for i, p in enumerate(prompts):
        out = ref.forward(params, np.asarray(seqs[i], np.int32), spec)
        np.testing.assert_allclose(got[i], np.asarray(out["logits"])[len(p) - 1:],
                                   **TOL)
        assert len(out["chosen"]) == 6 and out["chosen"][0].shape == (
            len(seqs[i]), 4)
    assert np.abs(got).max() > 2


def test_an_inactive_slot_is_counted_in_no_routing(tiny):
    _, cfg, params, _ = tiny
    toks, lengths = _padded([_tokens(9, 1), _tokens(12, 2)], 16)
    _, _, both = mv.prefill(params, toks, lengths, cfg, 20)
    _, _, one = mv.prefill(params, toks, lengths, cfg, 20,
                           active=jnp.asarray([True, False]))
    assert int(both["counts"].sum()) == 21 * 6 * 4
    assert int(one["counts"].sum()) == 9 * 6 * 4


def test_the_four_shares_of_an_expert_layer_sum_to_the_uncut_reference(tiny):
    """The share tied to the model: the routed outputs of all four ranks
    (eight experts each; there is no shared expert to count once) add up to
    what the UNCUT reference gives for the whole layer."""
    d, _, params, _ = tiny
    x = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    whole = params["layers"][2]["moe"]
    want = ref.expert_layer(whole, x, ref.spec_from_config(d))
    total = jnp.zeros_like(x)
    for rank in range(4):
        dr, cfg, _ = tiny_mimo_model(seed=5, held=8, rank=rank)
        mine = dict(whole, experts=jax.tree.map(
            lambda w: w[rank * 8:rank * 8 + 8], whole["experts"]))
        y, routed = lm_blocks.expert_layer(mine, x, cfg)
        assert routed.idx.shape == (40, 4)
        part = ref.expert_layer(mine, x, ref.spec_from_config(dr))
        np.testing.assert_allclose(np.asarray(y), np.asarray(part), **TOL)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)
    assert float(jnp.abs(want).max()) > 0.1


# -- attention's forms, each against the reference's one layer ---------------
def _layer_io(tiny, window, l=32, heads=None):
    """One attention layer of the kind ``window`` on random inputs: -> (its
    parameters, cfg, x (1, L, d), q, k, v as the model projects them, the
    reference's output (L, d))."""
    if heads:
        d, cfg, params = tiny_mimo_model(seed=5, **heads)
        spec = ref.spec_from_config(d)
    else:
        d, cfg, params, spec = tiny
    p = params["layers"][1 if window else 5]["attn"]
    x = jax.random.normal(jax.random.key(7), (1, l, 64), jnp.float32)
    positions = jnp.arange(l)[None]
    q, k, v = mv._qkv(p, x, positions, window, cfg)
    return p, cfg, x, q, k, v, np.asarray(ref.attention(p, x[0], window, spec))


def _out(o, p):
    return np.asarray(jnp.dot(o.reshape(o.shape[1], -1), p["wo"]))


def test_a_full_layer_s_prefill_forms_take_values_narrower_than_keys(tiny):
    """``dv != d``: ``prefill_full`` (no model calls it so: its einsums read
    the values' own width as they stood), and the scanned ``prefill_causal``
    over GROUPS (4 query heads to a key head, blocks of 8: three key blocks
    under the last query block), with a length that stops short of the
    bucket."""
    p, cfg, x, q, k, v, want = _layer_io(tiny, False)
    assert q.shape == (1, 32, 2, 4, 24) and k.shape == (1, 32, 2, 24)
    assert v.shape == (1, 32, 2, 16)
    np.testing.assert_allclose(_out(attn_ops.prefill_full(q, k, v, block=8), p),
                               want, **TOL)
    o = attn_ops.prefill_causal(q.reshape(1, 32, 8, 24), k, v, block=8)
    assert o.shape == (1, 32, 8, 16)
    np.testing.assert_allclose(_out(o, p), want, **TOL)
    short = attn_ops.prefill_causal(q.reshape(1, 32, 8, 24), k, v,
                                    jnp.asarray([13]), block=8)
    np.testing.assert_allclose(_out(short, p)[:13], want[:13], **TOL)
    assert not np.asarray(short)[0, 16:].any()      # blocks past the length


@pytest.mark.parametrize("heads,form", [(None, "scanned"),
                                        (PUBLISHED_HEADS, "fused")],
                         ids=["tiny-scanned", "keys-192-values-128-fused"])
def test_a_full_layer_s_prefill_asks_the_kernel_s_supports(tiny, monkeypatch,
                                                           heads, form):
    """``_full_prefill`` runs the fused kernel where ``supports`` says yes
    (here: interpreted, blocks of 8; keys of 192 beside values of 128, four
    query heads to a key head) and the scanned form where it refuses (the
    tiny preset's values of 16 are no whole lanes); either way the layer's
    output is the reference's, with a length that stops short of the bucket
    too, and the model notes the form its trace took.  On the CPU backend
    nothing is steered and the form is the scanned one."""
    p, cfg, x, q, k, v, want = _layer_io(tiny, False, heads=heads)
    lengths = jnp.asarray([32], jnp.int32)
    plain = mv._full_prefill(q, k, v, lengths)
    assert mv.attention_traced((1, 32)) == "scanned"
    monkeypatch.setattr(mv, "fused_attn", types.SimpleNamespace(
        supports=functools.partial(fused_attn.supports, block_q=8, block_k=8,
                                   interpret=True),
        fused_causal=functools.partial(fused_attn.fused_causal, block_q=8,
                                       block_k=8, interpret=True)))
    o = mv._full_prefill(q, k, v, lengths)
    assert mv.attention_traced((1, 32)) == form
    assert o.shape == plain.shape == q.shape[:-1] + v.shape[-1:]
    np.testing.assert_allclose(_out(o, p), want, **TOL)
    np.testing.assert_allclose(np.asarray(o), np.asarray(plain), **TOL)
    short = mv._full_prefill(q, k, v, jnp.asarray([13]))
    np.testing.assert_allclose(_out(short, p)[:13], want[:13], **TOL)
    if form == "fused":         # blocks of 8 past the length: zeros, unread
        assert not np.asarray(short)[0, 16:].any()


def test_the_whole_prefill_through_the_fused_kernel_is_the_scanned_one(
        monkeypatch):
    """Both full layers of the tiny model at the published head widths
    through the interpreted kernel: the logits, the cache and the routing of
    the whole prefill are the scanned form's."""
    d, cfg, params = tiny_mimo_model(seed=5, **PUBLISHED_HEADS)
    tokens, lengths = _padded([_tokens(32, 1), _tokens(13, 2)], 32)
    want, want_cache, want_routing = mv.prefill(params, tokens, lengths, cfg, 40)
    assert mv.attention_traced((2, 32)) == "scanned"
    monkeypatch.setattr(mv, "fused_attn", types.SimpleNamespace(
        supports=lambda *a, **kw: True,
        fused_causal=functools.partial(fused_attn.fused_causal, block_q=16,
                                       block_k=16, interpret=True)))
    got, got_cache, got_routing = mv.prefill(params, tokens, lengths, cfg, 40)
    assert mv.attention_traced((2, 32)) == "fused"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    np.testing.assert_array_equal(np.asarray(got_routing["choices"]),
                                  np.asarray(want_routing["choices"]))
    for a, b, spec in zip(got_cache["layers"], want_cache["layers"],
                          mv.cache_layout(cfg)):
        for name in a:
            if spec.kind == "ring":
                np.testing.assert_allclose(np.asarray(a[name]),
                                           np.asarray(b[name]), **TOL)
            else:       # positions past a length hold what nobody reads
                valid = (np.arange(40)[None] < np.asarray(lengths)[:, None])
                valid = valid[:, None, :, None]
                np.testing.assert_allclose(np.where(valid, a[name], 0),
                                           np.where(valid, b[name], 0), **TOL)


def test_prefill_causal_without_groups_is_what_it_was():
    """A key a head (latent attention's use): the grouped branch is not
    taken, and the result equals the grouped form given every key twice."""
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 8))
    k = jax.random.normal(ks[1], (2, 16, 2, 8))
    v = jax.random.normal(ks[2], (2, 16, 2, 6))
    grouped = attn_ops.prefill_causal(q, k, v, block=8)
    plain = attn_ops.prefill_causal(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                                    block=8)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(plain), **TOL)


@pytest.mark.parametrize("heads", [None, PUBLISHED_HEADS],
                         ids=["tiny", "keys-192-values-128-packed"])
def test_a_window_layer_s_prefill_and_ring_decode_carry_the_sink(tiny, heads):
    """``prefill_window`` over two blocks and ``decode`` against the ring,
    position by position through ``write_slot``, both with the learned sink
    in the denominator and ``dv != d``, against the reference's L x L
    window mask."""
    p, cfg, x, q, k, v, want = _layer_io(tiny, True, heads=heads)
    sink = mv._sink(p, True, cfg)
    assert sink.shape == (4, 2)
    np.testing.assert_allclose(
        _out(attn_ops.prefill_window(q, k, v, window=8, sink=sink), p), want,
        **TOL)
    shapes = mv._kv_spec(cfg, True).shapes(1, 40)
    kc, vc = (jnp.zeros(shapes[n], jnp.float32) for n in "kv")
    rows = []
    for t in range(32):
        pos = jnp.asarray([t])
        kc = attn_ops.write_slot(kc, k[:, t], pos % 8)
        vc = attn_ops.write_slot(vc, v[:, t], pos % 8)
        valid = attn_ops.ring_positions(pos, 8) >= 0
        rows.append(attn_ops.decode(q[:, t], kc, vc, valid, sink))
    o = jnp.stack(rows, 1)
    assert o.shape == (1, 32, 4, 2, cfg.v_head_dim)
    np.testing.assert_allclose(_out(o, p), want, **TOL)
    # the prefill's ring entry is what those writes left
    entry = attn_ops.ring_entry(k, v, jnp.asarray([32]), 8, shapes)
    np.testing.assert_array_equal(np.asarray(entry["k"]), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(entry["v"]), np.asarray(vc))


@pytest.mark.parametrize("heads", [None, PUBLISHED_HEADS],
                         ids=["tiny", "keys-192-values-128-packed"])
def test_a_full_layer_s_decode_reads_keys_and_values_each_in_their_rows(tiny,
                                                                         heads):
    p, cfg, x, q, k, v, want = _layer_io(tiny, False, heads=heads)
    shapes = mv._kv_spec(cfg, False).shapes(1, 32)
    pack = 2 if heads else 1
    assert shapes["k"][1] == 2 // pack and shapes["v"][1] == 2
    kc, vc = (attn_ops.as_leaf(a, shapes[n]) for n, a in (("k", k), ("v", v)))
    for t in (0, 9, 31):
        valid = jnp.arange(32)[None] <= t
        o = attn_ops.decode(q[:, t], kc, vc, valid)
        np.testing.assert_allclose(_out(o[:, None], p)[0], want[t], **TOL)


def test_the_sink_takes_mass_and_adds_no_value(tiny):
    """In the denominator, never a value: with a sink of +30 nearly all the
    mass is the sink's and the output nearly zero; with -30 the softmax is
    the plain one; left out or given a value row, the reference moves far
    outside the tolerance."""
    p, cfg, x, q, k, v, want = _layer_io(tiny, True)
    big = attn_ops.prefill_window(q, k, v, window=8,
                                  sink=jnp.full((4, 2), 30.0))
    assert float(jnp.abs(big).max()) < 1e-6
    gone = attn_ops.prefill_window(q, k, v, window=8,
                                   sink=jnp.full((4, 2), -30.0))
    plain = attn_ops.prefill_window(q, k, v, window=8)
    np.testing.assert_allclose(np.asarray(gone), np.asarray(plain), **TOL)
    d, _, params, spec = tiny
    for variant in ("no_sink", "sink_value"):
        other = np.asarray(ref.attention(p, x[0], True, spec, variant=variant))
        assert np.abs(other - want).max() > 0.05, variant


def test_rotary_turns_the_first_dimensions_with_the_kind_s_theta(tiny):
    _, cfg, _, _ = tiny
    x = jax.random.normal(jax.random.key(2), (1, 6, 3, 24))
    pos = jnp.arange(10, 16)[None]
    got = mv._rotary(x, pos, 1e4, 8)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    np.testing.assert_allclose(np.asarray(got[..., :8]),
                               np.asarray(attn_ops.rope(x[..., :8], pos, 1e4)),
                               **TOL)
    assert np.abs(np.asarray(got[..., :8] - x[..., :8])).max() > 0.1
    assert np.abs(np.asarray(mv._rotary(x, pos, 5e6, 8) - got)).max() > 0.01
    assert cfg.theta(True) == 1e4 and cfg.theta(False) == 5e6


@pytest.mark.parametrize("variant", [
    "no_sink", "sink_value", "window_minus_1", "window_plus_1",
    "full_groups_of_window", "rope_whole_head", "thetas_swapped",
    "no_value_scale", "unnormalised_topk", "bias_in_weights", "expert_zeroed"])
def test_a_broken_piece_of_the_mathematics_moves_the_reference(tiny, variant):
    """Each control of the chip's calibration computes something else: the
    model, which equals the sound reference at 1e-4, would be caught."""
    d, _, params, spec = tiny
    seq = _tokens(24, 3)
    sound = np.asarray(ref.forward(params, seq, spec)["logits"])
    other = np.asarray(ref.forward(params, seq, spec, variant=variant)["logits"])
    assert np.abs(other - sound).max() > 3e-3, variant


def test_the_yardstick_rounds_and_a_bit_fewer_rounds_more(tiny):
    d, _, params, spec = tiny
    seq = _tokens(24, 3)
    f32 = np.asarray(ref.forward(params, seq, spec)["logits"])
    gap = {m: np.linalg.norm(np.asarray(
        ref.forward(params, seq, spec, m)["logits"]) - f32, axis=-1).mean()
        for m in ("bf16", "bf16-1", "int8")}
    assert 0 < gap["bf16"] < gap["bf16-1"] and gap["bf16"] < gap["int8"]
    assert 1.5 < gap["bf16-1"] / gap["bf16"] < 2.6


def test_rows_returns_the_rows_asked_for(tiny):
    d, _, params, spec = tiny
    seq = _tokens(20, 4)
    full = ref.forward(params, seq, spec)
    some = ref.forward(params, seq, spec, rows=np.asarray([4, 19]))
    np.testing.assert_allclose(np.asarray(some["logits"]),
                               np.asarray(full["logits"])[[4, 19]], **TOL)
    assert [c.shape for c in some["chosen"]] == [(2, 4)] * 6
    code = inspect.getsource(ref)
    assert "import can_tpu" not in code and "from can_tpu" not in code


# -- the cache's rows -------------------------------------------------------
@pytest.mark.parametrize("kv_heads,head_dim,pack", [
    (8, 64, 2), (8, 128, 1), (4, 192, 2), (8, 192, 2), (1, 192, 1), (4, 24, 1),
], ids=["lfm2-64-as-it-was", "whole-lanes-128-as-it-was", "mimo-full-192",
        "mimo-window-192", "one-head-is-no-whole-row", "the-tiny-preset"])
def test_kv_pack_is_the_fewest_heads_whose_row_is_whole_lanes(kv_heads,
                                                              head_dim, pack):
    assert layout.kv_pack(kv_heads, head_dim) == pack
    assert pack == 1 or (pack * head_dim) % 128 == 0


def test_the_cell_s_cache_has_two_kinds_of_layer_and_no_padding():
    """At the published widths: 2 ``full`` entries of 4 heads and 5 ``ring``
    entries of 8, every leaf's row a whole number of lanes, and exactly 2,560
    B a position and 655,360 B a slot a layer."""
    cfg = mv.MimoV2FlashConfig.from_dict(_cell_config())
    specs = mv.cache_layout(cfg)
    assert [s.kind for s in specs] == ["full", "ring", "ring", "ring", "ring",
                                       "full", "ring"]
    full, ring = specs[0], specs[1]
    assert full.shapes(16, 8448) == {"k": (16, 2, 8448, 384),
                                     "v": (16, 4, 8448, 128)}
    assert ring.shapes(16, 8448) == {"k": (16, 4, 128, 384),
                                     "v": (16, 8, 128, 128)}
    for spec in specs:
        assert all(width % 128 == 0 for _, _, width in spec.leaves)
    cache = jax.eval_shape(lambda: kv_cache.allocate(
        specs, slots=16, positions=8448))
    assert kv_cache.nbytes_by_kind(cache, specs) == {
        "full": 2 * 16 * 8448 * 2560, "ring": 5 * 16 * 655_360}
    assert 2 * 2560 == 5120 and 5 * 655_360 == 3_276_800   # the two readers'


# -- the cell's file ------------------------------------------------------
def test_param_count_of_the_cell_s_file():
    """The builder's own reckoning (the configuration's ``reduced_how``)."""
    d = _cell_config()
    cfg = mv.MimoV2FlashConfig.from_dict(d)
    full, window = 89_128_960, 94_371_904
    expert_layer = 1_048_576 + 256 + 16 * 25_165_824 + 8_192
    assert mv.param_count(cfg) == (
        (full + 201_326_592 + 8_192) + 5 * (window + expert_layer)
        + (full + expert_layer) + 2 * 78_118_912 + 4_096) == 3_429_955_392
    assert full + 201_326_592 + 8_192 == 290_463_744
    assert window + expert_layer == 498_082_112
    assert full + expert_layer == 492_839_168
    assert cfg.share == moe_ops.ExpertShare(0, 16, 256)
    assert cfg.vocab == (0, 19_072, 152_576) and cfg.rotary_dim == 64
    assert lm_blocks.experts_form(cfg, 16, jnp.bfloat16) == "batched"   # a CPU
    assert lm_blocks.experts_form(cfg, 32768, jnp.bfloat16) == "sorted"


def test_the_cell_s_file_states_its_cut_and_its_assumptions():
    d = _cell_config()
    if os.path.exists(CATALOG):
        row = [json.loads(l) for l in open(CATALOG)
               if '"name": "MiMo-V2-Flash"' in l][0]
        changed = {k for k, v in row["config"].items() if d.get(k, "absent") != v}
        assert changed == set(d["reduced"]) == {
            "num_hidden_layers", "n_routed_experts", "vocab_size"}
        assert d["source"] == row["source_url"]
        assert d["published"] == {k: row["config"][k] for k in d["reduced"]}
    assert (d["num_hidden_layers"], d["n_routed_experts"], d["vocab_size"]) == (
        7, 16, 19_072)
    assert set(d["reduced_how"]) >= set(d["reduced"])
    dep = d["deployment"]
    assert (dep["chips_per_layer"], dep["rank"], dep["stages"], dep["stage"]) == (
        16, 0, 7, 0)
    for key in list(mv.ASSUMED) + ["rotary_dim", "max_batch", "prefill_slice",
                                   "sampling", "sink_init"]:
        assert key in d["assumed"], key
    assert (d["max_batch"], d["queue_capacity"], d["length_ladder"],
            d["max_new_tokens"], d["prefill_slice"]) == (16, 64, [8192], 256, 4)


def test_the_benchmark_s_own_weights_are_the_tree_the_program_reads():
    from benchmark.harness import weights_mimo_v2_flash as w

    assert "can_tpu" not in inspect.getsource(w).split('"""', 2)[2]
    for d in (_cell_config(), tiny_mimo_config(held=8, rank=1)):
        assert w.shapes(d) == mv.param_shapes(mv.MimoV2FlashConfig.from_dict(d))
    params = w.make_params(tiny_mimo_config(), 2**31 + 3)
    assert params["layers"][1]["moe"]["bias"].dtype == jnp.float32
    assert params["layers"][1]["attn"]["sink"].dtype == jnp.bfloat16
    sinks = np.concatenate([np.asarray(l["attn"]["sink"], np.float32)
                            for l in params["layers"] if "sink" in l["attn"]])
    # N(ln(window), 1): about a third to a half of a full window's mass
    assert 0.6 < sinks.std() < 1.4 and abs(sinks.mean() - np.log(8)) < 0.5


@pytest.mark.parametrize("edit,match", [
    ({"num_hidden_layers": 13}, "hybrid_layer_pattern names 12 layers"),
    ({"moe_layer_freq": [0, 1, 1]}, "moe_layer_freq 3"),
    ({"hybrid_layer_pattern": [0, 2, 1, 1, 1, 0, 1]}, "0 and 1 only"),
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
    ({"n_shared_experts": 1}, "shared expert"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"swa_head_dim": 32}, "swa_head_dim"),
    ({"num_key_value_heads": 3}, "heads do not divide"),
    ({"assumed": {"qk_norm": True}}, "qk_norm"),
    ({"assumed": {"sink": "value_row"}}, "sink"),
    ({"assumed": {"rotary_dims": "last"}}, "rotary_dims"),
    ({"assumed": {"value_scale_on": "output"}}, "value_scale_on"),
    ({"assumed": {"mtp_layers": 3}}, "mtp_layers"),
    ({"assumed": {"rotary_dim": 7}}, "rotary_dim 7"),
])
def test_what_is_not_implemented_is_refused(edit, match):
    d = tiny_mimo_config()
    d.update(edit)
    with pytest.raises(ValueError, match=match):
        mv.MimoV2FlashConfig.from_dict(d)


def test_any_pattern_of_layers_is_honoured():
    """``hybrid_layer_pattern`` and ``moe_layer_freq`` are read layer by
    layer, not the published pattern hard-wired: window first, a dense layer
    in the middle, no sink at all."""
    d = tiny_mimo_config(layers=3, hybrid_layer_pattern=[1, 0, 1],
                         moe_layer_freq=[1, 0, 1],
                         add_swa_attention_sink_bias=False)
    cfg = mv.MimoV2FlashConfig.from_dict(d)
    params = mv.init_params(jax.random.key(0), cfg, jnp.float32)
    assert [("sink" in l["attn"], "mlp" in l) for l in params["layers"]] == [
        (False, False), (False, True), (False, False)]
    assert [s.kind for s in mv.cache_layout(cfg)] == ["ring", "full", "ring"]
    seq = _tokens(24, 2)
    hidden, _, _ = mv.prefill_hidden(params, jnp.asarray(seq)[None],
                                     jnp.asarray([24]), cfg)
    want = ref.forward(params, seq, ref.spec_from_config(d))["hidden"]
    np.testing.assert_allclose(np.asarray(hidden)[0], np.asarray(want), **TOL)


# -- through the serving path ---------------------------------------------
def mimo_config(**kw) -> dict:
    d = tiny_mimo_config(held=8, rank=1)
    d.update(max_new_tokens=NEW, prefill_slice=2, length_ladder=[16, 32],
             max_batch=4, queue_capacity=16, max_wait_ms=5.0)
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def service():
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = mv.MimoV2FlashConfig.from_dict(mimo_config())
    params = mv.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(mimo_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


def test_the_table_builds_the_same_programs_class():
    entry = serve_programs.serving_model("mimo_v2_flash")
    made, params = entry.programs(mimo_config(), None, 3)
    assert isinstance(made, serve_programs.LMPrograms)
    assert made.vocab_size == 512
    # two forms of the full layers' prefill: the model notes which it traced
    assert made.attention_traced is mv.attention_traced
    assert made.ssm_traced is None and made.conv_traced is None
    assert [s.kind for s in made.cache_layout] == [
        "full", "ring", "ring", "ring", "ring", "full", "ring"]
    assert params["embed"].dtype == jnp.bfloat16 == made.dtype
    assert made.decode_experts(4) == "batched"
    assert "attn.window" in made.parts


def test_greedy_ids_and_probe_logits_match_the_reference_through_the_queue(service):
    """Three prompts of different lengths, two in one launch of the bucket
    of 32 and one (shorter than the window) in the bucket of 16: each
    request's 14 greedy ids and its probed logits and routing are the
    reference's over ITS OWN sequence (rank 1's 8 of 32 experts on both
    sides)."""
    svc, report, _ = service
    assert isinstance(svc, GenerateService)
    assert report["compiles"] == 2 * 2 == svc.engine.compile_count
    prompts = [_tokens(27, 99), _tokens(6, 98), _tokens(32, 97)]
    tickets = [svc.submit(p, want_logits=(i != 2)) for i, p in enumerate(prompts)]
    results = [t.result(120) for t in tickets]
    assert svc.engine.compile_count == 4 and results[2].logits is None
    spec = ref.spec_from_config(mimo_config())
    steps = lm_probe_steps(NEW)
    for p, r in zip(prompts, results):
        assert r.tokens.shape == (NEW,)
        assert r.bucket_hw == (1, 16 if len(p) <= 16 else 32)
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
    # 4 slots x (16 + 14) positions x 2 full layers x 2 heads x (24 + 16) x 4
    # bytes; 4 slots x 5 window layers x 4 heads x 40 x 8 positions x 4 bytes
    assert stats["cache_bytes"] == {"full": 4 * 30 * 2 * 2 * 40 * 4,
                                    "ring": 4 * 5 * 4 * 40 * 8 * 4}
    assert stats["decode_experts_read"] == stats["decode_experts_held"] > 0
    # on the CPU every prefill's full layers ran the scanned form
    assert set(stats["prefill_attention"]) == {"scanned"}
    assert 0 < stats["prefill_attention"]["scanned"] <= stats["launches"]
    assert svc.engine.prefill_attention == {(2, 16): "scanned",
                                            (2, 32): "scanned"}


def test_the_spans_name_the_experts_form_and_both_kinds_of_attention(service):
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
    # the full layers' form as the slices' programs traced it: on the CPU
    # ``pallas_attention.supports`` says no
    assert inner["lm.prefill"]["attention"] == "scanned"
    assert inner["lm.decode"]["experts"] == "batched"
    scopes = [s for s in ring if s["name"] == "program.scopes"]
    assert {s["program"] for s in scopes} == {"jit_prefill_slice", "jit_decode"}
    for s in scopes:
        assert "attn.window" in set(s["parts"].values())
        assert "attn.core" in set(s["parts"].values())
        assert s["cache_copies"] >= 0
