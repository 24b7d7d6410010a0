"""The fused latent decode kernel (``ops/pallas_latent.py``) in interpret
mode on the CPU, against ``ops/attention.py::decode_latent`` (the plain form
it stands in for): every position a slot can stand at around a block's
edges, caches that are and are not whole blocks, heads that are and are not
a sublane tile, what it never reads, what ``supports`` refuses, the
dispatch in ``glm_moe_lite.attention_absorbed``, which
nothing but ``supports`` steers, and ``write_row`` where a leaf narrower than
the lanes is written a ``dynamic_update_slice`` a sequence.  The compiled
kernel is ``tests/test_chip_compile.py``'s (for a described v5e)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.models import glm_moe_lite as gm
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import pallas_latent as fused

from lm_tiny import interpret_fused_latent, tiny_glm_config

BLOCK = 128          # of the tests' kernel: one row of lanes
R, DR = 128, 16      # rank (whole lanes), rotary width (a bfloat16 tile)
SCALE = 0.07
TOL = {jnp.float32: dict(atol=1e-5, rtol=1e-5),
       # bfloat16: the probabilities are rounded before the division here
       # and after it in the plain form, and the output is rounded once more
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


def _inputs(h, s, b, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, h, R), dtype),
            jax.random.normal(ks[1], (b, h, DR), dtype),
            jax.random.normal(ks[2], (b, s, R), dtype),
            jax.random.normal(ks[3], (b, s, DR), dtype))


def _plain(q_lat, q_rope, ckv, krope, positions):
    valid = jnp.arange(ckv.shape[1])[None, :] <= jnp.asarray(positions)[:, None]
    return attn_ops.decode_latent(q_lat, q_rope, ckv, krope, valid, scale=SCALE)


def _kernel(q_lat, q_rope, ckv, krope, positions, **how):
    return fused.fused_latent_decode(
        q_lat, q_rope, ckv, krope, jnp.asarray(positions, jnp.int32),
        scale=SCALE, interpret=True, **{"block": BLOCK, **how})


def _poisoned(ckv, krope, positions):
    """The leaves with every position past a slot's own NaN (the latent) and
    inf (the rotary keys)."""
    past = (jnp.arange(ckv.shape[1])[None, :]
            > jnp.asarray(positions)[:, None])[..., None]
    return jnp.where(past, jnp.nan, ckv), jnp.where(past, jnp.inf, krope)


# a cache of whole blocks (4 x 128) and one that is not (16,512-like:
# 129 x 4 = 4 x 128 + 4: a partial last block)
@pytest.mark.parametrize("s", [512, 516], ids=["whole-blocks", "129x4"])
@pytest.mark.parametrize("h", [20, 16], ids=["20-heads", "16-heads"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("position", [0, 127, 128, 129, 300, -1],
                         ids=["first", "short-of-an-edge", "on-an-edge",
                              "past-an-edge", "inside", "last"])
def test_the_kernel_is_the_plain_form(position, dtype, h, s):
    """Three slots: one at ``position`` (-1: the cache's last, in the partial
    block where there is one), one a block further on, one at 5.  The
    positions past each slot's own are poisoned: a block the kernel does not
    need is not read, and what it reads past a position it masks."""
    at = position % s
    positions = [at, min(at + BLOCK, s - 1), 5]
    q_lat, q_rope, ckv, krope = _inputs(h, s, 3, dtype, seed=at)
    want = np.asarray(_plain(q_lat, q_rope, ckv, krope, positions), np.float32)
    got = _kernel(q_lat, q_rope, *_poisoned(ckv, krope, positions), positions)
    assert got.shape == (3, h, R) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL[dtype])
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("block", [128, 256, 384], ids=str)
def test_any_block_of_whole_lanes_gives_the_same_answer(block):
    positions = [0, 255, 256, 383, 384, 515]
    q_lat, q_rope, ckv, krope = _inputs(20, 516, 6, seed=block)
    want = np.asarray(_plain(q_lat, q_rope, ckv, krope, positions))
    got = np.asarray(_kernel(q_lat, q_rope, *_poisoned(ckv, krope, positions),
                             positions, block=block))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_a_position_the_cache_does_not_hold_reads_all_of_it():
    """As the plain form's ``valid`` does for a position past the end."""
    q_lat, q_rope, ckv, krope = _inputs(4, 256, 2)
    want = np.asarray(_plain(q_lat, q_rope, ckv, krope, [255, 255]))
    got = np.asarray(_kernel(q_lat, q_rope, ckv, krope, [256, 9999]))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_one_position_attends_to_itself_alone():
    q_lat, q_rope, ckv, krope = _inputs(4, 256, 2, seed=3)
    got = np.asarray(_kernel(q_lat, q_rope, ckv, krope, [0, 0]))
    np.testing.assert_allclose(
        got, np.broadcast_to(np.asarray(ckv)[:, :1], got.shape), atol=1e-6)


class TestSupports:
    Q, CKV = (16, 20, 512), (16, 16512, 512)      # the cell's

    def test_it_asks_the_backend(self, monkeypatch):
        assert not fused.supports(self.Q, self.CKV, 64, jnp.bfloat16)   # the CPU
        assert fused.supports(self.Q, self.CKV, 64, jnp.bfloat16,
                              interpret=True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fused.supports(self.Q, self.CKV, 64, jnp.bfloat16)
        assert fused.supports(self.Q, self.CKV, 64, jnp.float32)

    @pytest.mark.parametrize("q,ckv,rope,dtype,block", [
        ((4, 4, 16), (4, 2048, 16), 8, jnp.bfloat16, 1024),     # the tiny preset
        ((16, 20, 576), (16, 16512, 576), 64, jnp.bfloat16, 1024),  # rank not whole lanes
        ((16, 20, 512), (16, 16512, 512), 8, jnp.bfloat16, 1024),   # rope no bf16 tile
        ((16, 20, 512), (16, 16512, 512), 64, jnp.int8, 1024),
        ((16, 20, 512), (16, 16512, 512), 64, jnp.bfloat16, 1000),  # block not whole lanes
        ((16, 20, 512), (16, 640, 512), 64, jnp.bfloat16, 1024),    # under one block
        ((16, 20, 512), (16, 16512, 512), 64, jnp.bfloat16, 32768),  # over the VMEM budget
    ], ids=["tiny", "rank-576", "rope-8", "int8", "block-1000", "short",
            "vmem"])
    def test_a_shape_it_cannot_take(self, q, ckv, rope, dtype, block):
        assert not fused.supports(q, ckv, rope, dtype, block=block,
                                  interpret=True)

    def test_a_float32_cache_takes_a_rope_of_8(self):
        assert fused.supports((2, 4, 128), (2, 256, 128), 8, jnp.float32,
                              block=128, interpret=True)

    def test_the_kernel_refuses_what_supports_refuses(self):
        q_lat, q_rope, ckv, krope = _inputs(4, 256, 2)
        with pytest.raises(ValueError, match="cannot take"):
            _kernel(q_lat, q_rope, ckv, krope, [3, 4], block=100)
        with pytest.raises(ValueError, match="cannot take"):
            _kernel(q_lat, q_rope[:, :3], ckv, krope, [3, 4])
        with pytest.raises(ValueError, match="cannot take"):
            _kernel(q_lat, q_rope, ckv, krope[:, :128], [3, 4])


def _aligned_glm(seed=0):
    """The tiny GLM preset with a latent of whole lanes (rank 128, rotary
    keys 16 wide)."""
    d = tiny_glm_config(mtp=0)
    d.update(kv_lora_rank=128, qk_rope_head_dim=16)
    cfg = gm.Glm4MoeLiteConfig.from_dict(d)
    return cfg, gm.init_params(jax.random.key(seed), cfg, jnp.float32)


class TestDispatch:
    def test_the_cpu_runs_decode_latent_bit_for_bit(self, monkeypatch):
        """A latent of whole lanes and all: on the CPU backend ``supports``
        says no, and the layer attends through ``decode_latent`` over
        ``valid`` = every position up to the sequence's own."""
        cfg, params = _aligned_glm()
        entry = {"ckv": jnp.zeros((2, 132, 128)),
                 "krope": jnp.zeros((2, 132, 16))}
        xn = jax.random.normal(jax.random.key(1), (2, 1, cfg.hidden_size))
        seen, plain = [], attn_ops.decode_latent

        def spy(q_lat, q_rope, ckv, krope, valid, *, scale):
            seen.append(np.asarray(valid))
            return plain(q_lat, q_rope, ckv, krope, valid, scale=scale)

        monkeypatch.setattr(attn_ops, "decode_latent", spy)
        monkeypatch.setattr(fused, "fused_latent_decode", None)  # never called
        gm.attention_absorbed(params["layers"][0]["attn"], xn,
                              jnp.asarray([7, 100], jnp.int32), entry, cfg)
        assert gm.latent_traced((2, 1)) == "plain" and len(seen) == 1
        np.testing.assert_array_equal(
            seen[0], np.arange(132)[None] <= np.asarray([[7], [100]]))

    def test_a_rank_under_the_lanes_keeps_the_plain_form_on_a_tpu(
            self, monkeypatch):
        """The tiny preset's latent of 16: no kernel, whatever the backend,
        and the step's lowered text is the one it has on the CPU but for the
        rotary keys' write (``write_row`` on a TPU)."""
        cfg = gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config(mtp=0))
        params = gm.init_params(jax.random.key(0), cfg, jnp.float32)
        cache = {"layers": [{"ckv": jnp.zeros((2, 40, cfg.kv_lora_rank)),
                             "krope": jnp.zeros((2, 40, cfg.qk_rope_head_dim))}
                            for _ in range(cfg.num_layers)]}
        step = jax.jit(functools.partial(gm.decode_step, cfg=cfg))
        args = (params, cache, jnp.zeros((2,), jnp.int32),
                jnp.asarray([3, 9], jnp.int32))
        on_cpu = step.lower(*args).as_text()
        monkeypatch.setattr(attn_ops, "write_row", _scatter_write)
        assert step.lower(*args).as_text() == on_cpu
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fused, "fused_latent_decode", None)
        assert step.lower(*args).as_text() == on_cpu
        assert gm.latent_traced((2, 1)) == "plain"

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_where_supports_says_yes_the_kernel_runs(self, monkeypatch, dtype):
        """A prompt's prefill, then three decode steps through the kernel
        against three through the plain form: logits and the cache."""
        cfg, params = _aligned_glm(seed=1)
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        tokens = jax.random.randint(jax.random.key(2), (2, 128), 0, 256)
        lengths = jnp.asarray([128, 77], jnp.int32)

        def run():
            logits, cache, _ = gm.prefill(params, tokens, lengths, cfg, 132)
            out, positions = [], lengths
            for _ in range(3):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                logits, cache, _ = gm.decode_step(params, cache, nxt,
                                                  positions, cfg)
                out.append(logits)
                positions = positions + 1
            return np.asarray(jnp.stack(out), np.float32), cache

        want, want_cache = run()
        assert gm.latent_traced((2, 1)) == "plain"
        interpret_fused_latent(monkeypatch)
        got, got_cache = run()
        assert gm.latent_traced((2, 1)) == "fused"
        tol = (dict(atol=3e-5, rtol=3e-5) if dtype == jnp.float32
               else dict(atol=6e-2, rtol=6e-2))
        np.testing.assert_allclose(got, want, **tol)
        for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), **tol)

    def test_the_kernel_s_launch_belongs_to_attn_core(self, monkeypatch):
        cfg, params = _aligned_glm()
        interpret_fused_latent(monkeypatch)
        cache = {"layers": [{"ckv": jnp.zeros((2, 132, 128)),
                             "krope": jnp.zeros((2, 132, 16))}
                            for _ in range(cfg.num_layers)]}
        text = jax.jit(functools.partial(gm.decode_step, cfg=cfg)).lower(
            params, cache, jnp.zeros((2,), jnp.int32),
            jnp.asarray([3, 9], jnp.int32)).as_text(debug_info=True)
        # (the kernel's call is an inner ``jit``, traced once a program)
        assert "attn.core/jit(fused_latent_decode)" in text
        assert "fused_latent_decode/pallas_call" in text


def _scatter_write(cache, new, pos):
    """``write_row`` as it was until PR 49, whatever the leaf and backend."""
    return cache.at[jnp.arange(cache.shape[0]), pos].set(new.astype(cache.dtype))


class TestWriteRow:
    """A leaf narrower than the 128 lanes is written on a TPU without a
    scatter (it would re-lay the leaf): by a select over the whole leaf where
    a slot holds at most ``SELECT_MAX_POSITIONS`` positions, by one
    ``dynamic_update_slice`` a sequence over that; a leaf of whole lanes, and
    every leaf off a TPU, by the scatter."""

    @pytest.mark.parametrize("width", [64, 8, 512], ids=str)
    @pytest.mark.parametrize("positions", [40, 2 * 2048], ids=str)
    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_position_by_position_it_is_the_scatter(self, monkeypatch, width,
                                                    positions, backend):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        cache = jax.random.normal(jax.random.key(0), (3, positions, width),
                                  jnp.bfloat16)
        new = jax.random.normal(jax.random.key(1), (3, width), jnp.float32)
        for pos in ([0, positions - 1, 17], [5, 5, 5]):
            pos = jnp.asarray(pos, jnp.int32)
            np.testing.assert_array_equal(
                np.asarray(attn_ops.write_row(cache, new, pos), np.float32),
                np.asarray(_scatter_write(cache, new, pos), np.float32))

    @pytest.mark.parametrize("width,positions,backend,form", [
        (64, 2 * 2048, "tpu", "updates"), (64, 2048, "tpu", "select"),
        (64, 40, "cpu", "scatter"), (512, 40, "tpu", "scatter"),
        (512, 2 * 2048, "tpu", "scatter")],
        ids=["narrow-and-long-on-a-tpu", "narrow-and-short-on-a-tpu",
             "narrow-on-the-cpu", "whole-lanes-on-a-tpu",
             "whole-lanes-and-long-on-a-tpu"])
    def test_which_form_the_program_has(self, monkeypatch, width, positions,
                                        backend, form):
        assert attn_ops.SELECT_MAX_POSITIONS == 2048
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        # (a function of its own: a ``jit`` of ``write_row`` itself would
        # hand back the trace of whichever backend asked first)
        text = jax.jit(lambda *a: attn_ops.write_row(*a)).lower(
            jnp.zeros((3, positions, width), jnp.bfloat16),
            jnp.zeros((3, width)), jnp.zeros((3,), jnp.int32)).as_text()
        assert text.count("dynamic_update_slice") == (3 if form == "updates"
                                                      else 0)
        assert ("scatter" in text) == (form == "scatter")
        if form == "select":     # one elementwise pass over the leaf
            assert "stablehlo.select" in text and "stablehlo.compare" in text

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_a_prompt_s_fill_is_the_step_by_step_writes(self, monkeypatch,
                                                        backend):
        """The rows a prefill pads into a leaf, written one position a step
        into an empty one: the same leaf."""
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        rows = jax.random.normal(jax.random.key(2), (2, 12, 8), jnp.bfloat16)
        filled = jnp.pad(rows, ((0, 0), (0, 4), (0, 0)))
        leaf = jnp.zeros((2, 16, 8), jnp.bfloat16)
        for p in range(12):
            leaf = attn_ops.write_row(leaf, rows[:, p],
                                      jnp.full((2,), p, jnp.int32))
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(filled, np.float32))

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_the_calibration_s_late_write_moves_both_leaves_one_position(
            self, monkeypatch, backend):
        """``benchmark/tools/calibrate_glm.py::late_write`` replaces
        ``attn_ops.write_row``: GLM's step then writes the latent AND the
        rotary key one position after the token's own, whatever the form of
        the write."""
        from benchmark.tools import calibrate_glm

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(fused, "supports", lambda *a, **kw: False)
        cfg = gm.Glm4MoeLiteConfig.from_dict(tiny_glm_config(mtp=0))
        params = gm.init_params(jax.random.key(0), cfg, jnp.float32)
        layer = params["layers"][0]["attn"]
        entry = {"ckv": jnp.zeros((2, 40, cfg.kv_lora_rank)),
                 "krope": jnp.zeros((2, 40, cfg.qk_rope_head_dim))}
        xn = jax.random.normal(jax.random.key(1), (2, 1, cfg.hidden_size))
        positions = jnp.asarray([3, 39], jnp.int32)
        _, sound = gm.attention_absorbed(layer, xn, positions, entry, cfg)
        calibrate_glm.late_write(None)
        try:
            _, late = gm.attention_absorbed(layer, xn, positions, entry, cfg)
        finally:
            calibrate_glm.late_write.undo()
        for name in ("ckv", "krope"):
            a, b = np.asarray(sound[name]), np.asarray(late[name])
            assert np.abs(a[0, 3]).max() > 0 and not a[0, 4].any()
            np.testing.assert_array_equal(b[0, 4], a[0, 3])
            np.testing.assert_array_equal(b[1, 0], a[1, 39])   # the last wraps
            assert not b[0, 3].any() and not b[1, 39].any()
