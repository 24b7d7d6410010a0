"""The plain reference of Brumby's forward pass (Qwen3's dense decoder with
every softmax attention replaced by power retention, a SwiGLU): the yardstick
of ``correct`` in the ``hybrid_serve`` cell, and what the CPU tests hold
``can_tpu/models/brumby.py`` against.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"); power retention in its QUADRATIC
# form alone, the weights a_ts over all pairs s <= t (so it is independent of
# the program's chunked form, of its matrix state and of its one-step form
# over a cache: there is no state here, no chunk, no phi); no cache, no
# batching, no kernels.
#
# The description (Brumby-14B-Base's config.json, model_type brumby: Qwen3's
# keys; power retention: Manifest AI, "Scaling Context Requires Rethinking
# Attention", arXiv:2507.04239, and the Brumby-14B release, 2025-10).  h is
# (L, d); key head j, a query head h of j's group of heads / kv_heads:
#   embed   h = E[ids]
#   block   x' = RMSNorm_in(h)
#           q_h = rope(RMSNorm_head(x' W_q)_h);  k_j = rope(RMSNorm_head(x' W_k)_j)
#           v_j = (x' W_v)_j;  log g_t,j = log_sigmoid((x' W_g)_j)   (float32)
#           G_t,j = sum_{r <= t} log g_r,j
#           a_ts = (q_h,t . k_j,s / sqrt(head_dim))^2 exp(G_t,j - G_s,j), s <= t
#           y_h,t = sum_s a_ts v_j,s / sum_s a_ts
#           h = h + concat_h(y_h) W_o;   h = h + MLP(RMSNorm_ff(h))
#   MLP     down(silu(gate(x)) * up(x))
#   head    logits = RMSNorm(h) W_head
# Rotary embedding: rotate-half over the whole head, theta, position = row.
# No bias anywhere.
#
# Departures from the published model, each the cut the configuration states:
#   * only the first num_hidden_layers layers exist;
#   * what config.json leaves open (the configuration's `assumed`, one value
#     each of can_tpu/models/brumby.py::ASSUMED): the kernel's degree is 2;
#     the gate is a bias-free projection to one number a key head, through
#     log_sigmoid; queries and keys are RMS-normalised per head (Qwen3's);
#     the rotary pairing is rotate-half over the whole head; the scale
#     1 / sqrt(head_dim) is inside the power; the output is divided by the
#     summed weights; and (the program's alone: nothing here has a state)
#     the state's precision and rows.
#
# So that a large vocabulary fits, the head runs a block of its columns at a
# time, logits are computed only for the rows asked for, and the weights
# a_ts are formed for a block of query rows at a time.
#
# `mode` computes the same mathematics in a lower precision, in the
# program's place, for the yardstick and the controls of `correct`:
#   "f32"   float32, matmuls at "highest" (the reference proper)
#   "bf16"  weights and activations rounded to bfloat16; float32 scores,
#           gates, weights a_ts, normaliser and norm statistics: what a
#           sound program computes
#   "int8"  as bf16 with every matrix rounded to 8 bits per output column
# `variant` breaks one piece of the mathematics (controls only):
#   "no_gate" (g = 1), "no_normaliser" (y = sum_s a_ts v_s), "no_scale" (the
#   power of the unscaled dot product: the normaliser cancels it, so this one
#   moves nothing, which PERF.md section 7 says), "no_head_norm", "no_rope".

QUERY_BLOCK = 256
HEAD_BLOCK = 32768     # columns of the head at a time


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied)."""
    return {
        "heads": int(d["num_attention_heads"]),
        "kv_heads": int(d["num_key_value_heads"]),
        "head_dim": int(d["head_dim"]),
        "eps": float(d["rms_norm_eps"]),
        "theta": float(d["rope_theta"]),
        "layers": int(d["num_hidden_layers"]),
    }


def _round(x, mode):
    # reduce_precision, not astype(bfloat16).astype(float32): inside a fusion
    # XLA:TPU may keep the excess precision of such a pair, and the yardstick
    # then rounds less than bfloat16 does (PERF.md, PR 30's finding)
    return x if mode == "f32" else jax.lax.reduce_precision(x, 8, 7)


def _weight(w, mode):
    w = jnp.asarray(w, jnp.float32)
    if mode == "int8" and w.ndim >= 2:
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        w = jnp.round(w / jnp.maximum(s, 1e-30)) * s
    return w


def _mm(x, w, mode):
    return _round(jnp.dot(_round(x, mode), _weight(w, mode)), mode)


def _rms(x, g, eps, mode):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _round(y * jnp.asarray(g, jnp.float32), mode)


def _rope(x, theta):
    """x (L, H, D), position = row."""
    l, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def _retention(p, x, spec, mode, variant):
    """x (L, d) = RMSNorm_in(h) -> concat_h(y_h) W_o."""
    l = x.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = _mm(x, p["wq"], mode).reshape(l, h, hd)
    k = _mm(x, p["wk"], mode).reshape(l, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(l, kv, hd)
    if variant != "no_head_norm":
        q = _rms(q, p["q_norm"], spec["eps"], mode)
        k = _rms(k, p["k_norm"], spec["eps"], mode)
    if variant != "no_rope":
        q = _round(_rope(q, spec["theta"]), mode)
        k = _round(_rope(k, spec["theta"]), mode)
    # one gate a key head and position, float32 whatever the mode
    log_g = jax.nn.log_sigmoid(jnp.dot(_round(x, mode), _weight(p["wg"], mode)))
    if variant == "no_gate":
        log_g = jnp.zeros_like(log_g)
    # query head i reads key head i // (h / kv)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    cum = jnp.repeat(jnp.cumsum(log_g, axis=0), h // kv, axis=1).T   # (H, L)
    scale = 1.0 if variant == "no_scale" else 1.0 / math.sqrt(hd)
    blocks = -(-l // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - l), (0, 0), (0, 0)))
    cum_p = jnp.pad(cum, ((0, 0), (0, blocks * QUERY_BLOCK - l)))
    j = jnp.arange(l)[None, :]

    def one(args):
        qb, cb, i0 = args                 # (Q, H, D), (H, Q), ()
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        s = jnp.einsum("ihd,jhd->hij", qb, k) * scale
        a = s * s * jnp.exp(jnp.where((j <= i)[None],
                                      cb[:, :, None] - cum[:, None, :], -jnp.inf))
        num = jnp.einsum("hij,jhd->ihd", _round(a, mode), v)
        if variant == "no_normaliser":
            return _round(num, mode)
        return _round(num / jnp.sum(a, -1).T[..., None], mode)

    y = jax.lax.map(one, (qp.reshape(blocks, QUERY_BLOCK, h, hd),
                          cum_p.reshape(h, blocks, QUERY_BLOCK).transpose(1, 0, 2),
                          jnp.arange(blocks) * QUERY_BLOCK))
    return _mm(y.reshape(blocks * QUERY_BLOCK, h * hd)[:l], p["wo"], mode)


def _retention_half(layer, x, spec, mode, variant):
    """-> h = x + Retention(RMSNorm_in(x))."""
    u = _rms(x, layer["ln_in"], spec["eps"], mode)
    return _round(x + _retention(layer["ret"], u, spec, mode, variant), mode)


def _mlp_half(layer, h, spec, mode):
    """-> h + MLP(RMSNorm_ff(h))."""
    p = layer["mlp"]
    x = _rms(h, layer["ln_post"], spec["eps"], mode)
    y = _mm(_round(jax.nn.silu(_mm(x, p["gate"], mode)) * _mm(x, p["up"], mode),
                   mode), p["down"], mode)
    return _round(h + y, mode)


_retention_half_jit = jax.jit(_retention_half, static_argnums=(2, 3, 4))
_mlp_half_jit = jax.jit(_mlp_half, static_argnums=(2, 3))


def forward(params, tokens, spec: dict, mode: str = "f32", variant=None,
            rows=None) -> dict:
    """One sequence ``tokens`` (L,) -> {"logits" float32 (L, V), or (len(rows),
    V) for the positions ``rows`` alone, "hidden" (L, d) before the final
    norm, "chosen": [] (no expert layer)}.  Each block runs two jitted
    calls, fed that layer of the tree the program serves from, so that only
    one layer is ever upcast at a time."""
    frozen = _freeze(spec)
    with jax.default_matmul_precision("highest"):
        x = _round(jnp.asarray(params["embed"][tokens], jnp.float32), mode)
        for layer in params["layers"][:spec["layers"]]:
            h = _retention_half_jit(layer, x, frozen, mode, variant)
            x = _mlp_half_jit(layer, h, frozen, mode)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head(params, x[at], spec, mode)
    return {"logits": logits, "hidden": x, "chosen": []}


def _head_block(norm, head, x, eps, mode):
    return jnp.dot(_round(_rms(x, norm, eps, mode), mode), _weight(head, mode))


_head_block_jit = jax.jit(_head_block, static_argnums=(3, 4))


def _head(params, x, spec, mode):
    vocab = params["head"].shape[1]
    return jnp.concatenate([
        _head_block_jit(params["final_norm"], params["head"][:, lo:lo + HEAD_BLOCK],
                        x, spec["eps"], mode)
        for lo in range(0, vocab, HEAD_BLOCK)], axis=-1)


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
