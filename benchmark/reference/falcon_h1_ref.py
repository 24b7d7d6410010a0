"""The plain reference of Falcon-H1's forward pass (a Mamba-2 mixer and
grouped-query attention side by side in every block, a SwiGLU, every branch
with its configuration's multiplier): the yardstick of ``correct`` in the
``hybrid_serve`` cell, and what the CPU tests hold
``can_tpu/models/falcon_h1.py`` against.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"); the recurrence as the plain
# token-by-token scan of its equations (so it is independent of the
# program's chunked form and of its one-step form over a cache); no cache, no
# batching, no kernels.
#
# The description (Falcon-H1-34B-Instruct's config.json, model_type falcon_h1,
# and the published FalconH1 model code; the mixer is Mamba-2's,
# arXiv:2405.21060).  h is (L, d); every multiplier is a key of the config:
#   embed   h = E[ids] * embedding_multiplier
#   block   u = RMSNorm_in(h)
#           h = h + ssm_out_multiplier * Mixer(u * ssm_in_multiplier)
#                 + attention_out_multiplier * Attn(u * attention_in_multiplier)
#           h = h + MLP(RMSNorm_ff(h))
#   attn    q, k, v = x W_q, (x W_k) * key_multiplier, x W_v; heads of
#           head_dim, num_attention_heads / num_key_value_heads query heads to a
#           key/value head; rotary embedding (rotate-half over the whole head,
#           theta, position = row) on q and k; scores q.k / sqrt(head_dim) for
#           j <= i; o = concat_h(p v) W_o.  No bias, no q/k norm.
#   MLP     down(up(x) * silu(gate(x) * mlp_multipliers[0])) * mlp_multipliers[1]
#   mixer   [z | xBC | dt] = (x W_in) * mup, mup scaling the column groups
#           z, x, B, C, dt by ssm_multipliers[0..4]; xBC = silu(conv(xBC)), a
#           causal depthwise convolution of width d_conv with bias, zeros
#           before position 0; xBC = [x (heads x head_dim) | B | C (groups x
#           d_state)]; dt = softplus(dt + dt_bias); A = -exp(A_log); per head,
#           with the state S (head_dim x d_state) zero before position 0 and B,
#           C those of the head's group:
#               S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
#           y = y * silu(z), RMS-normalised within each group's channels,
#           times the norm's weight; out = y W_out.
#   head    logits = (RMSNorm(h) W_head) * lm_head_multiplier
#
# Departures from the published model, each the cut the configuration states:
#   * only the first num_hidden_layers layers exist;
#   * what config.json leaves open (the configuration's `assumed`): the gated
#     norm is per group, the rotary pairing is rotate-half, dt is not clamped.
#
# So that a large vocabulary fits, the head runs a block of its columns at a
# time, and logits are computed only for the rows asked for.
#
# `mode` computes the same mathematics in a lower precision, in the
# program's place, for the yardstick and the controls of `correct`:
#   "f32"   float32, matmuls at "highest" (the reference proper)
#   "bf16"  weights and activations rounded to bfloat16; float32 softmax,
#           norm statistics, step sizes and recurrent state: what a sound
#           program computes
#   "int8"  as bf16 with every matrix rounded to 8 bits per output column
# `variant` breaks one piece of the mathematics (controls only):
#   "state_bf16" (the recurrent state rounded to bfloat16 at every position),
#   "norm_all_channels" (the gated norm over all of d_ssm), "no_key_multiplier",
#   "no_ssm_multipliers".

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
        "d_ssm": int(d["mamba_d_ssm"]),
        "m_heads": int(d["mamba_n_heads"]),
        "m_head_dim": int(d["mamba_d_head"]),
        "d_state": int(d["mamba_d_state"]),
        "n_groups": int(d["mamba_n_groups"]),
        "d_conv": int(d["mamba_d_conv"]),
        "embedding_multiplier": float(d["embedding_multiplier"]),
        "lm_head_multiplier": float(d["lm_head_multiplier"]),
        "attention_in_multiplier": float(d["attention_in_multiplier"]),
        "attention_out_multiplier": float(d["attention_out_multiplier"]),
        "key_multiplier": float(d["key_multiplier"]),
        "ssm_in_multiplier": float(d["ssm_in_multiplier"]),
        "ssm_out_multiplier": float(d["ssm_out_multiplier"]),
        "ssm_multipliers": tuple(float(m) for m in d["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(m) for m in d["mlp_multipliers"]),
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


def _attention(p, x, spec, mode, variant):
    l = x.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    key_mult = 1.0 if variant == "no_key_multiplier" else spec["key_multiplier"]
    q = _mm(x, p["wq"], mode).reshape(l, h, hd)
    k = _round(_mm(x, p["wk"], mode) * key_mult, mode).reshape(l, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(l, kv, hd)
    q = _round(_rope(q, spec["theta"]), mode)
    k = _round(_rope(k, spec["theta"]), mode)
    # query head i reads key/value head i // (h / kv)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    blocks = -(-l // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - l), (0, 0), (0, 0)))
    j = jnp.arange(l)[None, :]

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        s = jnp.einsum("ihd,jhd->hij", qb, k) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return _round(jnp.einsum("hij,jhd->ihd", _round(pr, mode), v), mode)

    o = jax.lax.map(one, (qp.reshape(blocks, QUERY_BLOCK, h, hd),
                          jnp.arange(blocks) * QUERY_BLOCK))
    return _mm(o.reshape(blocks * QUERY_BLOCK, h * hd)[:l], p["wo"], mode)


def _mixer(p, x, spec, mode, variant):
    l = x.shape[0]
    ds, hm, hp = spec["d_ssm"], spec["m_heads"], spec["m_head_dim"]
    g, n, kc = spec["n_groups"], spec["d_state"], spec["d_conv"]
    gn = g * n
    mults = ((1.0,) * 5 if variant == "no_ssm_multipliers"
             else spec["ssm_multipliers"])
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                           zip((ds, ds, gn, gn, hm), mults)])
    zxbcdt = _round(_mm(x, p["in_proj"], mode) * mup, mode)
    z, xbc, dt = (zxbcdt[:, :ds], zxbcdt[:, ds:2 * ds + 2 * gn],
                  zxbcdt[:, 2 * ds + 2 * gn:])
    # the convolution: position t reads inputs t - d_conv + 1 .. t
    w = jnp.asarray(p["conv_w"], jnp.float32)                 # (C, d_conv)
    padded = jnp.pad(xbc, ((kc - 1, 0), (0, 0)))
    conv = jnp.asarray(p["conv_b"], jnp.float32) + sum(
        padded[j:j + l] * w[:, j] for j in range(kc))
    xbc = _round(jax.nn.silu(_round(conv, mode)), mode)
    xs = xbc[:, :ds].reshape(l, hm, hp)
    bs = jnp.repeat(xbc[:, ds:ds + gn].reshape(l, g, n), hm // g, axis=1)
    cs = jnp.repeat(xbc[:, ds + gn:].reshape(l, g, n), hm // g, axis=1)
    dt = jax.nn.softplus(dt + jnp.asarray(p["dt_bias"], jnp.float32))  # (L, H)
    a = -jnp.exp(jnp.asarray(p["A_log"], jnp.float32))
    d_skip = jnp.asarray(p["D"], jnp.float32)

    def step(s, t):
        xt, bt, ct, dtt = t                       # (H, P), (H, N), (H, N), (H,)
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        if variant == "state_bf16":
            s = jax.lax.reduce_precision(s, 8, 7)
        return s, jnp.einsum("hpn,hn->hp", s, ct) + d_skip[:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((hm, hp, n), jnp.float32),
                        (xs, bs, cs, dt))
    y = _round(y, mode).reshape(l, ds) * jax.nn.silu(z)
    groups = 1 if variant == "norm_all_channels" else g
    y = y.reshape(l, groups, ds // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + spec["eps"])
    y = _round(y.reshape(l, ds) * jnp.asarray(p["gate_norm"], jnp.float32), mode)
    return _mm(y, p["out_proj"], mode)


def _mixing_half(layer, x, spec, mode, variant):
    """-> h = x + ssm_out * Mixer(u * ssm_in) + attn_out * Attn(u * attn_in),
    u = RMSNorm_in(x)."""
    u = _rms(x, layer["ln_in"], spec["eps"], mode)
    o = _attention(layer["attn"], _round(u * spec["attention_in_multiplier"], mode),
                   spec, mode, variant)
    m = _mixer(layer["mixer"], _round(u * spec["ssm_in_multiplier"], mode), spec,
               mode, variant)
    return _round(x + m * spec["ssm_out_multiplier"]
                  + o * spec["attention_out_multiplier"], mode)


def _mlp_half(layer, h, spec, mode):
    """-> h + MLP(RMSNorm_ff(h))."""
    m_gate, m_down = spec["mlp_multipliers"]
    p = layer["mlp"]
    x = _rms(h, layer["ln_post"], spec["eps"], mode)
    gate = _round(_mm(x, p["gate"], mode) * m_gate, mode)
    y = _mm(_round(jax.nn.silu(gate) * _mm(x, p["up"], mode), mode), p["down"],
            mode)
    return _round(h + _round(y * m_down, mode), mode)


_mixing_half_jit = jax.jit(_mixing_half, static_argnums=(2, 3, 4))
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
        x = jnp.asarray(params["embed"][tokens], jnp.float32)
        x = _round(x * spec["embedding_multiplier"], mode)
        for layer in params["layers"][:spec["layers"]]:
            h = _mixing_half_jit(layer, x, frozen, mode, variant)
            x = _mlp_half_jit(layer, h, frozen, mode)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head(params, x[at], spec, mode)
    return {"logits": logits, "hidden": x, "chosen": []}


def _head_block(norm, head, x, eps, mult, mode):
    return jnp.dot(_round(_rms(x, norm, eps, mode), mode),
                   _weight(head, mode)) * mult


_head_block_jit = jax.jit(_head_block, static_argnums=(3, 4, 5))


def _head(params, x, spec, mode):
    vocab = params["head"].shape[1]
    return jnp.concatenate([
        _head_block_jit(params["final_norm"], params["head"][:, lo:lo + HEAD_BLOCK],
                        x, spec["eps"], spec["lm_head_multiplier"], mode)
        for lo in range(0, vocab, HEAD_BLOCK)], axis=-1)


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
