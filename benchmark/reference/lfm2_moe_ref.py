"""The plain reference of LFM2-MoE's forward pass (a gated short convolution
or grouped-query attention as each layer's mixer, dense or sparse-expert
feed-forwards, a tied head): the yardstick of ``correct`` in the
``serve-lfm2-chat-closed`` cell, and what the CPU tests hold
``can_tpu/models/lfm2_moe.py`` against.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"); the convolution as its sum over
# taps of the zero-padded sequence (so it is independent of the program's
# one-step form over a cached tail); no cache, no batching, no kernels.
#
# The description (LFM2-24B-A2B's config.json, model_type lfm2_moe, and Hugging
# Face's Lfm2Moe* model code).  h is (L, d); rms(x, g) = g * x / sqrt(mean(x^2)
# + norm_eps):
#   embed   h = E[ids]
#   block   h = h + mixer_i(rms(h, operator_norm))
#           h = h + ffn_i(rms(h, ffn_norm))
#   conv    [B | C | X] = x W_in (d -> 3 d, split in that order); u = B * X;
#           v[t] = sum_{j < K} w[:, j] u[t - K + 1 + j] per channel (depthwise,
#           causal, K = conv_L_cache, zeros before position 0, no bias, no
#           activation); y = (C * v) W_out
#   attn    q, k, v = x W_q, x W_k, x W_v; heads of head_dim,
#           num_attention_heads / num_key_value_heads query heads to a
#           key/value head; q = rms(q, q_layernorm), k = rms(k, k_layernorm)
#           over each head; rotary embedding (rotate-half over the whole head,
#           theta, position = row) on q and k; scores q.k / sqrt(head_dim) for
#           j <= i; o = concat_h(p v) W_o.  No bias.
#   ffn     layers < num_dense_layers: (silu(x W_1) * (x W_3)) W_2; the others:
#           s = sigmoid(x W_g) in float32 over ALL experts; the top
#           num_experts_per_tok of s + expert_bias chosen; weights s[chosen] /
#           (sum + 1e-6) * routed_scaling_factor; sum_i w_i E_i(x), E the same
#           SwiGLU at moe_intermediate_size.  No shared expert.
#   head    logits = rms(h, embedding_norm) E^T (tied)
#
# Departures from the published model, each the cut the configuration states:
#   * only the experts first_expert .. first_expert + held - 1 exist: a chosen
#     expert held elsewhere adds nothing (the chip's share of the deployment);
#   * what config.json leaves open (the configuration's `assumed`): head_dim =
#     hidden_size / num_attention_heads, the head tied, rotate-half pairing.
#
# So that a large vocabulary fits, the head runs a block of the embedding's
# rows at a time, and logits are computed only for the rows asked for.
#
# `mode` computes the same mathematics in a lower precision, in the
# program's place, for the yardstick and the controls of `correct`:
#   "f32"   float32, matmuls at "highest" (the reference proper)
#   "bf16"  weights and activations rounded to bfloat16; float32 router,
#           softmax and norm statistics: what a sound program computes
#   "bf16-1"  as bf16 with every activation rounded to ONE MANTISSA BIT FEWER
#           (7 explicit bits -> 6): the nearest precision below the one stated
#   "int8"  as bf16 with every matrix rounded to 8 bits per output column
# `variant` breaks one piece of the mathematics (controls only):
#   "no_gate_b" (u = X), "no_gate_c" (y = v W_out), "no_qk_norm",
#   "unnormalised_topk", "bias_in_weights" (the weights are s + expert_bias at
#   the chosen), "expert_zeroed" (the first held expert adds nothing).

QUERY_BLOCK = 256
EXPERT_ROWS = 128      # an expert's tokens are padded to a multiple of this
HEAD_BLOCK = 16384     # rows of the embedding at a time

CONV = "conv"      # a layer of any other kind is ``full_attention``


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied; `published` the uncut counts; `deployment.rank`)."""
    rank = int(d.get("deployment", {}).get("rank", 0))
    held = int(d["num_experts"])
    heads = int(d["num_attention_heads"])
    return {
        "heads": heads,
        "kv_heads": int(d["num_key_value_heads"]),
        "head_dim": int(d.get("assumed", {}).get(
            "head_dim", int(d["hidden_size"]) // heads)),
        "eps": float(d["norm_eps"]),
        "theta": float(d["rope_parameters"]["rope_theta"]),
        "layer_types": tuple(d["layer_types"]),
        "taps": int(d["conv_L_cache"]),
        "top_k": int(d["num_experts_per_tok"]),
        "scale": float(d["routed_scaling_factor"]),
        "normalise": bool(d["norm_topk_prob"]),
        "first_expert": rank * held,
        "held_experts": held,
    }


def _round(x, mode):
    # reduce_precision, not astype(bfloat16).astype(float32): inside a fusion
    # XLA:TPU may keep the excess precision of such a pair, and the yardstick
    # then rounds less than bfloat16 does (PERF.md, PR 30's finding)
    if mode == "f32":
        return x
    return jax.lax.reduce_precision(x, 8, 6 if mode == "bf16-1" else 7)


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


def _swiglu(x, p, mode):
    return _mm(_round(jax.nn.silu(_mm(x, p["gate"], mode)) * _mm(x, p["up"], mode),
                      mode), p["down"], mode)


def _conv_mixer(p, x, spec, mode, variant):
    l = x.shape[0]
    bcx = _mm(x, p["in_proj"], mode)
    d = bcx.shape[1] // 3
    gate_in, gate_out, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = xs if variant == "no_gate_b" else _round(gate_in * xs, mode)
    # the convolution: position t reads inputs t - taps + 1 .. t
    w = jnp.asarray(p["conv_w"], jnp.float32)                 # (d, taps)
    padded = jnp.pad(u, ((spec["taps"] - 1, 0), (0, 0)))
    v = _round(sum(padded[j:j + l] * w[:, j] for j in range(spec["taps"])), mode)
    y = v if variant == "no_gate_c" else _round(gate_out * v, mode)
    return _mm(y, p["out_proj"], mode)


def _attention(p, x, spec, mode, variant):
    l = x.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = _mm(x, p["wq"], mode).reshape(l, h, hd)
    k = _mm(x, p["wk"], mode).reshape(l, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(l, kv, hd)
    if variant != "no_qk_norm":
        q = _rms(q, p["q_norm"], spec["eps"], mode)
        k = _rms(k, p["k_norm"], spec["eps"], mode)
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


def _mixer_half(layer, x, kind, spec, mode, variant):
    """-> (h = x + mixer(rms(x, operator_norm)), rms(h, ffn_norm))."""
    xn = _rms(x, layer["ln_in"], spec["eps"], mode)
    m = (_conv_mixer(layer["conv"], xn, spec, mode, variant) if kind == CONV
         else _attention(layer["attn"], xn, spec, mode, variant))
    h = _round(x + m, mode)
    return h, _rms(h, layer["ln_post"], spec["eps"], mode)


def _route(x, router, bias, spec, variant):
    s = jax.nn.sigmoid(jnp.dot(x, jnp.asarray(router, jnp.float32)))
    biased = s + jnp.asarray(bias, jnp.float32)
    _, chosen = jax.lax.top_k(biased, spec["top_k"])
    w = jnp.take_along_axis(biased if variant == "bias_in_weights" else s,
                            chosen, axis=-1)
    if spec["normalise"] and variant != "unnormalised_topk":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * spec["scale"]


def _expert_add(y, x, rows, w, gate, up, down, mode):
    """y with w * E(x[rows]) added at ``rows`` (rows == len(x): padding)."""
    take = jnp.minimum(rows, x.shape[0] - 1)
    out = w[:, None] * _swiglu(x[take], {"gate": gate, "up": up, "down": down}, mode)
    return y.at[rows].add(out, mode="drop")


def _dense_tail(h, hn, mlp, mode):
    return _round(h + _swiglu(hn, mlp, mode), mode)


def _sparse_tail(h, y, mode):
    return _round(h + _round(y, mode), mode)


_mixer_half_jit = jax.jit(_mixer_half, static_argnums=(2, 3, 4, 5))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_expert_add_jit = jax.jit(_expert_add, static_argnums=(7,))
_dense_tail_jit = jax.jit(_dense_tail, static_argnums=(3,))
_sparse_tail_jit = jax.jit(_sparse_tail, static_argnums=(2,))


def _experts(p, x, spec, mode, variant):
    """-> (the held experts' part of the routed sum (L, d), the experts each
    token chose (L, k)).  Each held expert runs on the tokens that chose it,
    their number padded to a multiple of EXPERT_ROWS."""
    chosen, w = _route_jit(x, p["router"], p["bias"], _freeze(spec), variant)
    picked, weights = np.asarray(chosen), np.asarray(w)
    y = jnp.zeros_like(x)
    for e in range(spec["held_experts"]):
        if variant == "expert_zeroed" and e == 0:
            continue
        tok, slot = np.nonzero(picked == spec["first_expert"] + e)
        if not len(tok):
            continue
        room = -(-len(tok) // EXPERT_ROWS) * EXPERT_ROWS
        rows = np.full((room,), x.shape[0], np.int32)
        rows[:len(tok)] = tok
        w_e = np.zeros((room,), np.float32)
        w_e[:len(tok)] = weights[tok, slot]
        y = _expert_add_jit(y, x, rows, w_e, p["experts"]["gate"][e],
                            p["experts"]["up"][e], p["experts"]["down"][e], mode)
    return y, chosen


def _block(layer, x, kind, spec, mode, variant):
    """One block over the sequence: -> (y, chosen or None)."""
    h, hn = _mixer_half_jit(layer, x, kind, _freeze(spec), mode, variant)
    if "mlp" in layer:
        return _dense_tail_jit(h, hn, layer["mlp"], mode), None
    y, chosen = _experts(layer["moe"], hn, spec, mode, variant)
    return _sparse_tail_jit(h, y, mode), chosen


def forward(params, tokens, spec: dict, mode: str = "f32", variant=None,
            rows=None) -> dict:
    """One sequence ``tokens`` (L,) -> {"logits" float32 (L, V), or (len(rows),
    V) for the positions ``rows`` alone, "hidden" (L, d) before the final
    norm, "chosen": [(L, k) or (len(rows), k) per expert layer]}.  The blocks
    run a few jitted calls each, fed that layer of the tree the program
    serves from, so that only one matrix is ever upcast at a time."""
    with jax.default_matmul_precision("highest"):
        x = _round(jnp.asarray(params["embed"][tokens], jnp.float32), mode)
        chosen = []
        for layer, kind in zip(params["layers"], spec["layer_types"]):
            x, c = _block(layer, x, kind, spec, mode, variant)
            if c is not None:
                chosen.append(c)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head(params, x[at], spec["eps"], mode)
    return {"logits": logits, "hidden": x, "chosen": [c[at] for c in chosen]}


def _head_block(norm, rows, x, eps, mode):
    return jnp.dot(_round(_rms(x, norm, eps, mode), mode),
                   _weight(jnp.asarray(rows).T, mode))


_head_block_jit = jax.jit(_head_block, static_argnums=(3, 4))


def _head(params, x, eps, mode):
    """The tied head: ``rms(x, embedding_norm) E^T``, a block of ``E``'s rows
    at a time (int8: each row of ``E`` is an output column)."""
    vocab = params["embed"].shape[0]
    return jnp.concatenate([
        _head_block_jit(params["final_norm"], params["embed"][lo:lo + HEAD_BLOCK],
                        x, eps, mode)
        for lo in range(0, vocab, HEAD_BLOCK)], axis=-1)


def expert_layer(p, x, spec: dict, mode: str = "f32"):
    """One sparse feed-forward on ``x`` (L, d) alone: the held experts' part
    of the routed sum (the CPU test that ties the share to the model adds
    the ranks' parts up)."""
    with jax.default_matmul_precision("highest"):
        return _experts(p, jnp.asarray(x, jnp.float32), spec, mode, None)[0]


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
