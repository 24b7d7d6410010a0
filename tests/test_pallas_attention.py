"""The fused causal attention kernel (``ops/pallas_attention.py``) in
interpret mode on the CPU: against the scanned ``prefill_causal`` it stands
in for and against the plain softmax of ``test_glm_moe_lite``; what it
skips; what ``supports`` refuses; and the dispatch in
``glm_moe_lite.attention_expanded``, which nothing but ``supports`` steers.
The compiled kernel is ``tests/test_chip_compile.py``'s (for a described
v5e) and ``chip_smoke.py``'s (on the chip)."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.models import glm_moe_lite as gm
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import pallas_attention as fused_attn

import test_glm_moe_lite as glm_tests
from lm_tiny import tiny_glm_config

L = 64


def _qkv(h, d, dv, seed=0, b=2, dtype=jnp.float32, kv=None):
    """``kv`` key/value heads under ``h`` query heads (as many when None)."""
    kv = h if kv is None else kv
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, L, h, d), dtype),
            jax.random.normal(ks[1], (b, L, kv, d), dtype),
            jax.random.normal(ks[2], (b, L, kv, dv), dtype))


def _plain(q, k, v, scale):
    """The plain softmax of ``test_glm_moe_lite``, a group's query heads
    reading the key/value head they share."""
    g = q.shape[2] // k.shape[2]
    return glm_tests.TestPrefillCausal()._plain(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), scale)


def _valid(lengths):
    return (np.arange(L)[None] < np.asarray(lengths)[:, None])[:, :, None, None]


@pytest.mark.parametrize("blocks", [(16, 16), (32, 16)],
                         ids=["16x16", "32x16"])
@pytest.mark.parametrize("d,dv,h,kv", [
    (128, 128, 3, 3), (128, 256, 3, 3),
    # keys that are no whole number of lanes beside values that are, and
    # groups of G = H / KV query heads to a key/value head (MiMo's full
    # layers: 192 beside 128, G = 16)
    (192, 128, 3, 3), (192, 128, 8, 2), (192, 128, 16, 1), (64, 128, 4, 2),
    (128, 128, 8, 2), (16, 256, 6, 3),
], ids=["D=Dv", "D!=Dv", "192-G1", "192-G4", "192-G16", "64-G2", "128-G4",
        "16-G2"])
@pytest.mark.parametrize("lengths", [[64, 64], [64, 37], [48, 32], [16, 5]],
                         ids=["full", "inside-a-block", "at-an-edge",
                              "one-block"])
def test_the_kernel_against_the_scanned_form_and_the_plain_softmax(
        lengths, d, dv, h, kv, blocks):
    """Three heads (not a power of two) a key head each, and groups of query
    heads over fewer key heads.  Where both forms cut the queries into the
    same blocks they agree on every row, the zeros of the blocks past a
    length included; rows of valid positions agree always."""
    bq, bk = blocks
    q, k, v = _qkv(h, d, dv, kv=kv)
    n = jnp.asarray(lengths, jnp.int32)
    got = np.asarray(fused_attn.fused_causal(q, k, v, n, scale=0.1,
                                             block_q=bq, block_k=bk,
                                             interpret=True))
    scanned = np.asarray(attn_ops.prefill_causal(q, k, v, n, scale=0.1,
                                                 block=bq))
    np.testing.assert_allclose(got, scanned, atol=2e-5, rtol=2e-5)
    plain = np.asarray(_plain(q, k, v, 0.1))
    valid = _valid(lengths)
    np.testing.assert_allclose(np.where(valid, got, 0),
                               np.where(valid, plain, 0), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (64, 64)],
                         ids=["16x16", "16x32", "64x64"])
def test_no_lengths_means_every_position(blocks):
    q, k, v = _qkv(2, 128, 128, seed=3)
    got = fused_attn.fused_causal(q, k, v, block_q=blocks[0],
                                  block_k=blocks[1], interpret=True)
    want = glm_tests.TestPrefillCausal()._plain(q, k, v, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_rows_past_a_length_are_finite_and_blocks_past_it_zero():
    """bfloat16, as the chip runs it: a block the length ends in computes
    all its rows (garbage no valid position reads, never NaN: the products
    after the attention run on every launched row); blocks wholly past the
    length are written as zeros."""
    q, k, v = _qkv(3, 128, 128, seed=1, dtype=jnp.bfloat16)
    got = np.asarray(fused_attn.fused_causal(
        q, k, v, jnp.asarray([64, 21]), block_q=16, block_k=16,
        interpret=True).astype(jnp.float32))
    assert np.isfinite(got).all()
    assert got[1, 21:32].any() and not got[1, 32:].any()
    # a length of nothing still computes the first block, as the plain form
    none = np.asarray(fused_attn.fused_causal(
        q, k, v, jnp.asarray([0, 1]), block_q=16, block_k=16,
        interpret=True).astype(jnp.float32))
    assert np.isfinite(none).all() and not none[:, 16:].any()


@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32)],
                         ids=["16x16", "32x16", "16x32"])
def test_blocks_past_the_diagonal_and_past_a_length_are_never_read(blocks):
    """Keys and values are NaN wherever no block the kernel may visit
    reaches: past the key block that holds a sequence's last computed row.
    A visit there would put NaN into the output (0 x NaN), whatever mask
    followed; and the first block of queries alone visits one block."""
    bq, bk = blocks
    q, k, v = _qkv(2, 128, 128, seed=2)
    lengths = [40, 9]
    k, v = np.array(k), np.array(v)
    for s, n in enumerate(lengths):
        rows = -(-n // bq) * bq                  # rows the kernel computes
        k[s, -(-rows // bk) * bk:] = np.nan
        v[s, -(-rows // bk) * bk:] = np.nan
    got = np.asarray(fused_attn.fused_causal(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(lengths), scale=0.1,
        block_q=bq, block_k=bk, interpret=True))
    assert np.isfinite(got).all()
    want = np.asarray(attn_ops.prefill_causal(
        q, jnp.nan_to_num(k), jnp.nan_to_num(v), jnp.asarray(lengths),
        scale=0.1, block=bq))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


class TestSupports:
    Q, V = (2, 2048, 3, 256), (2, 2048, 3, 128)

    def test_not_on_the_cpu_backend(self):
        assert jax.default_backend() == "cpu"
        assert not fused_attn.supports(self.Q, self.V, jnp.bfloat16)
        assert fused_attn.supports(self.Q, self.V, jnp.bfloat16,
                                   interpret=True)

    def test_on_a_tpu_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fused_attn.supports(self.Q, self.V, jnp.bfloat16)

    @pytest.mark.parametrize("q,v", [
        ((2, 2048, 3, 200), (2, 2048, 3, 128)),      # D not whole sublane tiles
        ((2, 2048, 3, 256), (2, 2048, 3, 64)),       # Dv not whole lanes
        ((2, 2048, 3, 192), (2, 2048, 3, 192)),      # Dv 192: D may be, Dv not
        ((2, 1536, 3, 256), (2, 1536, 3, 128)),      # L not whole blocks
        ((2, 512, 64, 192), (2, 512, 4, 128)),       # a bucket under a block
        ((2, 2048, 6, 192), (2, 2048, 4, 128)),      # H no whole groups of KV
        ((2, 65536, 3, 256), (2, 65536, 3, 256)),    # a head over the VMEM
        # 100 MiB to the byte without the turned queries' scratch of
        # (1,024, 256): counted (192 lies in 256 lanes), it is over
        ((1, 66560, 16, 192), (1, 66560, 1, 128)),
    ], ids=["D", "Dv", "Dv-192", "L", "L-under-a-block", "groups", "vmem",
            "vmem-by-the-padded-scratch"])
    def test_a_shape_it_cannot_take(self, q, v):
        assert not fused_attn.supports(q, v, jnp.bfloat16, interpret=True)

    @pytest.mark.parametrize("q,v", [
        ((2, 2048, 3, 192), (2, 2048, 3, 128)),      # D of 192: whole tiles
        ((2, 2048, 64, 192), (2, 2048, 4, 128)),     # 16 query heads a key head
        ((1, 65536, 16, 192), (1, 65536, 1, 128)),   # a block under the budget
        ((4, 8192, 64, 192), (4, 8192, 4, 128)),     # MiMo's slice
    ], ids=["D-192", "groups", "vmem", "mimo"])
    def test_a_shape_it_takes(self, q, v):
        assert fused_attn.supports(q, v, jnp.bfloat16, interpret=True)

    def test_the_scratch_is_counted_in_whole_lanes(self):
        """A head of 192 turned lies in 256 lanes: its scratch costs what a
        head of 256's does; the resident keys and blocks cost 192."""
        at = functools.partial(fused_attn._vmem_bytes, 8192, dv=128,
                               itemsize=2, block_q=1024, block_k=1024)
        assert at(d=256) - at(d=192) == (2 * 8192 + 2 * 1024) * 64 * 2
        assert at(d=192) - at(d=128) == ((2 * 8192 + 2 * 1024) * 64 * 2
                                         + 1024 * 128 * 2)

    def test_the_kernel_refuses_what_supports_refuses(self):
        x = jnp.zeros((1, 32, 1, 96))
        with pytest.raises(ValueError, match="cannot take"):
            fused_attn.fused_causal(x, x, x, block_q=16, block_k=16,
                                    interpret=True)

    def test_the_kernel_refuses_keys_of_another_shape_than_the_queries_say(self):
        """``supports`` reads ``q`` and ``v``; ``k`` has to be ``v``'s heads
        of ``q``'s width."""
        q, v = jnp.zeros((1, 32, 4, 128)), jnp.zeros((1, 32, 2, 128))
        for k in (jnp.zeros((1, 32, 4, 128)), jnp.zeros((1, 32, 2, 64))):
            with pytest.raises(ValueError, match="cannot take"):
                fused_attn.fused_causal(q, k, v, block_q=16, block_k=16,
                                        interpret=True)

    def test_the_cell_s_shape_fits(self):
        assert fused_attn.supports((2, 16384, 20, 256), (2, 16384, 20, 256),
                                   jnp.bfloat16, interpret=True)


def _aligned_glm(seed=0):
    """The tiny GLM preset with heads of whole lanes (96 + 32, values 128)."""
    d = tiny_glm_config(mtp=0)
    d.update(num_attention_heads=2, qk_nope_head_dim=96, qk_rope_head_dim=32,
             v_head_dim=128)
    cfg = gm.Glm4MoeLiteConfig.from_dict(d)
    return cfg, gm.init_params(jax.random.key(seed), cfg, jnp.float32)


def _layer_inputs(cfg, lengths, bucket=32, seed=5):
    b = len(lengths)
    xn = jax.random.normal(jax.random.key(seed), (b, bucket, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(bucket)[None], (b, bucket))
    return xn, positions, jnp.asarray(lengths, jnp.int32)


class TestDispatch:
    def test_the_cpu_runs_prefill_causal_bit_for_bit(self, monkeypatch):
        """Lane-aligned heads and all: on the CPU backend ``supports`` says
        no, and the layer's output is ``prefill_causal``'s through ``wo``."""
        cfg, params = _aligned_glm()
        p = params["layers"][0]["attn"]
        xn, positions, lengths = _layer_inputs(cfg, [32, 13])
        seen, scanned = [], attn_ops.prefill_causal

        def spy(*a, **kw):
            seen.append(scanned(*a, **kw))
            return seen[-1]

        monkeypatch.setattr(attn_ops, "prefill_causal", spy)
        monkeypatch.setattr(fused_attn, "fused_causal", None)   # never called
        out, _, _ = gm.attention_expanded(p, xn, positions, lengths, cfg)
        assert len(seen) == 1 and gm.attention_traced((2, 32)) == "scanned"
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(jnp.dot(seen[0].reshape(2, 32, -1), p["wo"])))

    def test_a_misaligned_head_keeps_the_scanned_form_on_a_tpu(self, monkeypatch):
        """The tiny preset's heads of 16: no kernel, whatever the backend."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fused_attn, "fused_causal", None)
        cfg = gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config(mtp=0))
        params = gm.init_params(jax.random.key(0), cfg, jnp.float32)
        xn, positions, lengths = _layer_inputs(cfg, [32, 13])
        gm.attention_expanded(params["layers"][0]["attn"], xn, positions,
                              lengths, cfg)
        assert gm.attention_traced((2, 32)) == "scanned"

    def test_where_supports_says_yes_the_kernel_runs(self, monkeypatch):
        """The whole prefill through the kernel (interpreted, blocks of 16)
        against the whole prefill through the scanned form."""
        cfg, params = _aligned_glm(seed=1)
        tokens = np.asarray(jax.random.randint(jax.random.key(2), (2, 32), 0,
                                               256))
        lengths = jnp.asarray([32, 13], jnp.int32)
        monkeypatch.setattr(gm, "PREFILL_BLOCK", 16)
        want, want_cache, _ = gm.prefill(params, tokens, lengths, cfg, 40)
        assert gm.attention_traced((2, 32)) == "scanned"
        monkeypatch.setattr(gm, "fused_attn", types.SimpleNamespace(
            supports=lambda *a, **kw: True,
            fused_causal=functools.partial(fused_attn.fused_causal,
                                           block_q=16, block_k=16,
                                           interpret=True)))
        got, got_cache, _ = gm.prefill(params, tokens, lengths, cfg, 40)
        assert gm.attention_traced((2, 32)) == "fused"
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
            valid = (np.arange(40)[None] < np.asarray(lengths)[:, None])[..., None]
            np.testing.assert_allclose(np.where(valid, a, 0),
                                       np.where(valid, b, 0), atol=3e-5,
                                       rtol=3e-5)
