"""The plain reference of MiMo-V2-Flash's forward pass (window and full
attention layers that differ in their key/value heads, their rotary base and
a learned sink; keys wider than values; rotary on part of a head; a dense or
a sparse-expert feed-forward without a shared expert; an untied head): the
yardstick of ``correct`` in the ``serve-mimo-doc8k-closed`` cell, and what
the CPU tests hold ``can_tpu/models/mimo_v2_flash.py`` against.  Imports
nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"); every layer's L x L mask computed a
# block of query rows at a time (so that 8,448 positions fit); no cache, no
# ring, no batching, no kernels.
#
# The description (MiMo-V2-Flash's config.json, model_type mimo_v2_flash).  h
# is (L, d); rms(x, g) = g * x / sqrt(mean(x^2) + layernorm_epsilon):
#   embed   h = E[ids]
#   block   h = h + attn_i(rms(h, input_layernorm))
#           h = h + ffn_i(rms(h, post_attention_layernorm))
#   attn    of kind c = hybrid_layer_pattern[i] (0 full, 1 window), with KV_c =
#           num_key_value_heads / swa_num_key_value_heads key/value heads and
#           theta_c = rope_theta / swa_rope_theta:
#           q = x W_q (H heads of head_dim), k = x W_k (KV_c heads of head_dim),
#           v = attention_value_scale * (x W_v) (KV_c heads of v_head_dim);
#           rotary embedding (rotate-half, theta_c, position = row) on
#           dimensions 0 .. rotary_dim - 1 of every q and k head, the others
#           untouched; query head i reads key/value head i // (H / KV_c);
#           scores s[t, j] = q_t . k_j / sqrt(head_dim) for j <= t, in a window
#           layer also t - j < sliding_window.  Full: p = softmax_j(s).  Window,
#           head h with its learned scalar b_h: p[t, j] = exp(s[t, j] - m) /
#           (sum_j' exp(s[t, j'] - m) + exp(b_h - m)), m = max(max_j s, b_h):
#           the sink takes mass and adds no value.  o = concat_h(p v) W_o.  No
#           bias, no q/k norm.
#   ffn     moe_layer_freq[i] == 0: (silu(x W_1) * (x W_3)) W_2; else: s =
#           sigmoid(x W_g) in float32 over ALL experts; the top
#           num_experts_per_tok of s + e_score_correction_bias chosen; weights
#           s[chosen] / sum(s[chosen]) (norm_topk_prob) * routed_scaling_factor
#           (null = 1); sum_i w_i E_i(x), E the same SwiGLU at
#           moe_intermediate_size.  No shared expert.
#   head    logits = rms(h, norm) W_head (untied)
#
# Departures from the published model, each the cut the configuration states:
#   * only the experts first_expert .. first_expert + held - 1 exist: a chosen
#     expert held elsewhere adds nothing (the chip's share of the deployment);
#   * the vocabulary is the slice held (embedding rows and head columns);
#   * what config.json leaves open (the configuration's `assumed`): RMSNorm
#     under `layernorm_epsilon`, no q/k norm, rotary_dim = int(head_dim *
#     partial_rotary_factor) on the FIRST dimensions, rotate-half, scale 1 /
#     sqrt(head_dim), the sink in the denominator only, the value scale on v,
#     attention_chunk_size the published kernel's tile (the mask is the sliding
#     window), no MTP layers.
#
# Logits are computed only for the rows asked for.
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
#   "no_sink" (a plain softmax in window layers), "sink_value" (the sink's
#   mass multiplies the head's value at position 0: a sink WITH a value row),
#   "window_minus_1" / "window_plus_1" (127 / 129 at the published 128),
#   "full_groups_of_window" (a full layer's query head i reads key head (i //
#   (H / KV_window)) % KV_full: the window layers' grouping), "rope_whole_head" (rotary over all head_dim dimensions),
#   "thetas_swapped", "no_value_scale", "unnormalised_topk", "bias_in_weights"
#   (the weights are s + bias at the chosen), "expert_zeroed" (the first held
#   expert adds nothing).

QUERY_BLOCK = 256
# an expert's tokens are padded to a power of two, this at least: at most
# log2(L / EXPERT_ROWS) + 2 row counts ever meet the compiler for sequences
# of L positions (7 at 8,448), whatever the seed routes where; a multiple of
# 128 met a new count, and so a new program, in run after run (PR 40)
EXPERT_ROWS = 128


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied; `published` the uncut counts; `deployment.rank`)."""
    rank = int(d.get("deployment", {}).get("rank", 0))
    held = int(d["n_routed_experts"])
    n = int(d["num_hidden_layers"])
    hd = int(d["head_dim"])
    scale = d.get("routed_scaling_factor")
    return {
        "heads": int(d["num_attention_heads"]),
        "kv_full": int(d["num_key_value_heads"]),
        "kv_window": int(d["swa_num_key_value_heads"]),
        "head_dim": hd, "v_head_dim": int(d["v_head_dim"]),
        "rotary_dim": int(d.get("assumed", {}).get(
            "rotary_dim", int(hd * float(d["partial_rotary_factor"])))),
        "theta_full": float(d["rope_theta"]),
        "theta_window": float(d["swa_rope_theta"]),
        "window": int(d["sliding_window"]),
        "value_scale": float(d["attention_value_scale"]),
        "eps": float(d["layernorm_epsilon"]),
        "window_layers": tuple(bool(x) for x in d["hybrid_layer_pattern"][:n]),
        "top_k": int(d["num_experts_per_tok"]),
        "scale": 1.0 if scale is None else float(scale),
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


def _rope(x, theta, rotary_dim):
    """x (L, H, D), position = row: rotate-half on the first ``rotary_dim``
    dimensions, the rest untouched."""
    l = x.shape[0]
    r = rotary_dim
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    xr = x[..., :r]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., : r // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., r:]], -1)


def _swiglu(x, p, mode):
    return _mm(_round(jax.nn.silu(_mm(x, p["gate"], mode)) * _mm(x, p["up"], mode),
                      mode), p["down"], mode)


def _attention(p, x, window, spec, mode, variant):
    """One layer's attention over the sequence ``x`` (L, d); ``window``: the
    layer's kind."""
    l = x.shape[0]
    h, hd, dv = spec["heads"], spec["head_dim"], spec["v_head_dim"]
    kv = spec["kv_window"] if window else spec["kv_full"]
    theta = spec["theta_window"] if window else spec["theta_full"]
    if variant == "thetas_swapped":
        theta = spec["theta_full"] if window else spec["theta_window"]
    rotary = hd if variant == "rope_whole_head" else spec["rotary_dim"]
    span = spec["window"] + {"window_minus_1": -1, "window_plus_1": 1}.get(variant, 0)
    q = _mm(x, p["wq"], mode).reshape(l, h, hd)
    k = _mm(x, p["wk"], mode).reshape(l, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(l, kv, dv)
    if variant != "no_value_scale":
        v = _round(v * spec["value_scale"], mode)
    q = _round(_rope(q, theta, rotary), mode)
    k = _round(_rope(k, theta, rotary), mode)
    # query head i reads key/value head i // (h / kv)
    reads = jnp.arange(h) // (h // kv)
    if variant == "full_groups_of_window" and not window:
        reads = (jnp.arange(h) // (h // spec["kv_window"])) % kv
    k, v = k[:, reads], v[:, reads]                           # (L, H, .)
    sink = None
    if window and "sink" in p and variant != "no_sink":
        sink = jnp.asarray(p["sink"], jnp.float32)            # (H,)
    blocks = -(-l // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - l), (0, 0), (0, 0)))
    j = jnp.arange(l)[None, :]

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        mask = j <= i
        if window:
            mask &= i - j < span
        s = jnp.einsum("ihd,jhd->hij", qb, k) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, None, None])
        e = jnp.exp(s - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sink[:, None, None] - m)
        o = jnp.einsum("hij,jhd->ihd", _round(e / den, mode), v)
        if sink is not None and variant == "sink_value":
            p_sink = (jnp.exp(sink[:, None, None] - m) / den)[..., 0]   # (H, q)
            o = o + _round(p_sink, mode).T[..., None] * v[0][None]
        return _round(o, mode)

    o = jax.lax.map(one, (qp.reshape(blocks, QUERY_BLOCK, h, hd),
                          jnp.arange(blocks) * QUERY_BLOCK))
    return _mm(o.reshape(blocks * QUERY_BLOCK, h * dv)[:l], p["wo"], mode)


def _attention_half(layer, x, window, spec, mode, variant):
    """-> (h = x + attn(rms(x, input_layernorm)), rms(h,
    post_attention_layernorm))."""
    xn = _rms(x, layer["ln_in"], spec["eps"], mode)
    h = _round(x + _attention(layer["attn"], xn, window, spec, mode, variant),
               mode)
    return h, _rms(h, layer["ln_post"], spec["eps"], mode)


def _route(x, router, bias, spec, variant):
    s = jax.nn.sigmoid(jnp.dot(x, jnp.asarray(router, jnp.float32)))
    biased = s + jnp.asarray(bias, jnp.float32)
    _, chosen = jax.lax.top_k(biased, spec["top_k"])
    w = jnp.take_along_axis(biased if variant == "bias_in_weights" else s,
                            chosen, axis=-1)
    if spec["normalise"] and variant != "unnormalised_topk":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
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


_attention_half_jit = jax.jit(_attention_half, static_argnums=(2, 3, 4, 5))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_expert_add_jit = jax.jit(_expert_add, static_argnums=(7,))
_dense_tail_jit = jax.jit(_dense_tail, static_argnums=(3,))
_sparse_tail_jit = jax.jit(_sparse_tail, static_argnums=(2,))


def _experts(p, x, spec, mode, variant):
    """-> (the held experts' part of the routed sum (L, d), the experts each
    token chose (L, k)).  Each held expert runs on the tokens that chose it,
    their number padded to the next power of two (EXPERT_ROWS at least; the
    padding rows point past the sequence and are dropped)."""
    chosen, w = _route_jit(x, p["router"], p["bias"], _freeze(spec), variant)
    picked, weights = np.asarray(chosen), np.asarray(w)
    y = jnp.zeros_like(x)
    for e in range(spec["held_experts"]):
        if variant == "expert_zeroed" and e == 0:
            continue
        tok, slot = np.nonzero(picked == spec["first_expert"] + e)
        if not len(tok):
            continue
        room = max(EXPERT_ROWS, 1 << (len(tok) - 1).bit_length())
        rows = np.full((room,), x.shape[0], np.int32)
        rows[:len(tok)] = tok
        w_e = np.zeros((room,), np.float32)
        w_e[:len(tok)] = weights[tok, slot]
        y = _expert_add_jit(y, x, rows, w_e, p["experts"]["gate"][e],
                            p["experts"]["up"][e], p["experts"]["down"][e], mode)
    return y, chosen


def _block(layer, x, window, spec, mode, variant):
    """One block over the sequence: -> (y, chosen or None)."""
    h, hn = _attention_half_jit(layer, x, window, _freeze(spec), mode, variant)
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
        for layer, window in zip(params["layers"], spec["window_layers"]):
            x, c = _block(layer, x, window, spec, mode, variant)
            if c is not None:
                chosen.append(c)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head_jit(params["final_norm"], params["head"], x[at],
                           spec["eps"], mode)
    return {"logits": logits, "hidden": x, "chosen": [c[at] for c in chosen]}


def _head(norm, head, x, eps, mode):
    return jnp.dot(_round(_rms(x, norm, eps, mode), mode), _weight(head, mode))


_head_jit = jax.jit(_head, static_argnums=(3, 4))


def expert_layer(p, x, spec: dict, mode: str = "f32"):
    """One sparse feed-forward on ``x`` (L, d) alone: the held experts' part
    of the routed sum (the CPU test that ties the share to the model adds
    the ranks' parts up)."""
    with jax.default_matmul_precision("highest"):
        return _experts(p, jnp.asarray(x, jnp.float32), spec, mode, None)[0]


def attention(p, x, window: bool, spec: dict, mode: str = "f32", variant=None):
    """One layer's attention on ``x`` (L, d) alone (the CPU tests hold
    ``ops/attention.py``'s forms to it)."""
    with jax.default_matmul_precision("highest"):
        return _attention(p, jnp.asarray(x, jnp.float32), window, spec, mode,
                          variant)


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
