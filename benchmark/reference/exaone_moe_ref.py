"""The benchmark's copy of the plain reference of K-EXAONE's forward pass
(one chip's share of it): the yardstick of ``correct`` in the ``lm_serve``
cells.  The same text below this docstring as
``can_tpu/testing/exaone_moe_ref.py`` (``tests/test_exaone_moe.py`` compares
them); kept here so that no change to the program moves the yardstick.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"), a Python loop over the held
# experts (every expert sees every token, its result masked by the routing
# weight), a full L x L mask, no cache, no batching, no kernels.
#
# The description (K-EXAONE-236B-A23B's config.json; EXAONE 4.0,
# arXiv:2507.11407, for what the config does not say):
#   block   h = x + Attn(RMSNorm(x)); y = h + F(RMSNorm(h)); F is a SwiGLU in
#           "dense" layers and the expert layer in "sparse" ones; a final
#           RMSNorm and an untied head.
#   attn    q, k RMS-normalised per head; rotary embedding (rotate-half over
#           the whole head) on sliding_attention layers only; scores
#           q.k / sqrt(head_dim) for j <= i, and i - j < window on
#           sliding_attention layers; each key/value head serves
#           heads / kv_heads consecutive query heads.
#   experts s = sigmoid(x W_r) over ALL experts; chosen = top-k of s + bias;
#           w_i = scale * s_i / sum of the chosen s; E(x) = W_down(silu(W_gate
#           x) * (W_up x)); one shared expert added for every token.
#   MTP     h' = W_p [RMSNorm(h_t); RMSNorm(Emb(x_{t+1}))], one full-attention
#           block with an expert layer, a norm, the shared head (DeepSeek-V3).
#
# Departures from the published model, each the cut the configuration states:
#   * only the experts first .. first + held - 1 are held: the routed sum
#     runs over the chosen experts that are held, the others' part is left
#     out, and that partial result goes on to the next layer;
#   * the vocabulary is a slice: embedding and head have `held` rows, ids and
#     logits are over the slice;
#   * only the first num_hidden_layers layers exist;
#   * the MTP module's own norm before the shared head is DeepSeek-V3's
#     (`shared_head.norm`); the config does not say.
#
# `mode` computes the same mathematics in a lower precision, in the
# program's place, for the yardstick and the controls of `correct`:
#   "f32"   float32, matmuls at "highest" (the reference proper)
#   "bf16"  weights and activations rounded to bfloat16, float32 router,
#           softmax and norm statistics: what a sound program computes
#   "int8"  as bf16 with every matrix rounded to 8 bits per output column
# `variant` breaks one piece of the mathematics (controls only):
#   "window+1", "rope_on_full", "unnormalised_topk", "expert_zeroed".

WINDOW = "sliding_attention"


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied; `published` the uncut counts; `deployment.rank`)."""
    n = int(d["num_hidden_layers"])
    rank = int(d.get("deployment", {}).get("rank", 0))
    held = int(d["num_experts"])
    assumed = d.get("assumed", {})
    return {
        "heads": int(d["num_attention_heads"]),
        "kv_heads": int(d["num_key_value_heads"]),
        "head_dim": int(d["head_dim"]),
        "eps": float(d["rms_norm_eps"]),
        "theta": float(d["rope_parameters"]["rope_theta"]),
        "window": int(d["sliding_window"]),
        "layer_types": list(d["layer_types"][:n]),
        "top_k": int(d["num_experts_per_tok"]),
        "scale": float(d["routed_scaling_factor"]),
        "normalise": bool(d["norm_topk_prob"]),
        "first_expert": rank * held,
        "held_experts": held,
        "qk_norm": bool(assumed.get("qk_norm", True)),
        "rope_layers": list(assumed.get("rope_layers", [WINDOW])),
        "pre_norm": bool(assumed.get("pre_norm", True)),
    }


def _round(x, mode):
    return x if mode == "f32" else x.astype(jnp.bfloat16).astype(jnp.float32)


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


def _attention(p, x, layer_type, spec, mode, variant):
    l = x.shape[0]
    h, kv, d = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = _mm(x, p["wq"], mode).reshape(l, h, d)
    k = _mm(x, p["wk"], mode).reshape(l, kv, d)
    v = _mm(x, p["wv"], mode).reshape(l, kv, d)
    if spec["qk_norm"]:
        q = _rms(q, p["q_norm"], spec["eps"], mode)
        k = _rms(k, p["k_norm"], spec["eps"], mode)
    if layer_type in spec["rope_layers"] or variant == "rope_on_full":
        q, k = _round(_rope(q, spec["theta"]), mode), _round(_rope(k, spec["theta"]), mode)
    k = jnp.repeat(k, h // kv, axis=1)        # kv head j serves q heads j*g ..
    v = jnp.repeat(v, h // kv, axis=1)
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    mask = j <= i
    if layer_type == WINDOW:
        mask &= i - j < spec["window"] + (1 if variant == "window+1" else 0)
    s = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(d)
    pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = _round(jnp.einsum("hij,jhd->ihd", _round(pr, mode), v), mode)
    return _mm(o.reshape(l, h * d), p["wo"], mode)


def _experts(p, x, spec, mode, variant):
    """-> (the held experts' part of the routed sum + the shared expert,
    the experts each token chose (L, k))."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.dot(x, jnp.asarray(p["router"], jnp.float32)))
    _, chosen = jax.lax.top_k(s + jnp.asarray(p["bias"], jnp.float32), spec["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if spec["normalise"] and variant != "unnormalised_topk":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * spec["scale"]
    y = jnp.zeros_like(x)
    for e in range(spec["held_experts"]):
        if variant == "expert_zeroed" and e == 0:
            continue
        one = {n: p["experts"][n][e] for n in ("gate", "up", "down")}
        w_e = jnp.sum(jnp.where(chosen == spec["first_expert"] + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * _swiglu(x, one, mode)
    return _round(y, mode) + _swiglu(x, p["shared"], mode), chosen


def _block(layer, layer_type, x, spec, mode, variant):
    xn = _rms(x, layer["ln_in"], spec["eps"], mode) if spec["pre_norm"] else x
    h = _round(x + _attention(layer["attn"], xn, layer_type, spec, mode, variant), mode)
    hn = _rms(h, layer["ln_post"], spec["eps"], mode) if spec["pre_norm"] else h
    if "mlp" in layer:
        return _round(h + _swiglu(hn, layer["mlp"], mode), mode), None
    y, chosen = _experts(layer["moe"], hn, spec, mode, variant)
    return _round(h + y, mode), chosen


_block_jit = jax.jit(_block, static_argnums=(1, 3, 4, 5))


def forward(params, tokens, spec: dict, mode: str = "f32", variant=None) -> dict:
    """One sequence ``tokens`` (L,) -> {"logits" (L, V) float32, "hidden"
    (L, d) before the final norm, "chosen": [(L, k) per expert layer]}.
    The blocks run one jitted call each, fed that layer of the tree the
    program serves from, so that only one layer is ever upcast at a time."""
    frozen = _freeze(spec)
    with jax.default_matmul_precision("highest"):
        x = _round(jnp.asarray(params["embed"], jnp.float32)[tokens], mode)
        chosen = []
        for layer, lt in zip(params["layers"], spec["layer_types"]):
            x, c = _block_jit(layer, lt, x, frozen, mode, variant)
            if c is not None:
                chosen.append(c)
        logits = _head(params, x, spec["eps"], mode)
    return {"logits": logits, "hidden": x, "chosen": chosen}


@jax.jit
def _head_f32(norm, head, x, eps):
    return jnp.dot(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                   * jnp.asarray(norm, jnp.float32), jnp.asarray(head, jnp.float32))


def _head(params, x, eps, mode, norm="final_norm"):
    if mode == "f32":
        return _head_f32(params[norm], params["head"], x, eps)
    return jnp.dot(_round(_rms(x, params[norm], eps, mode), mode),
                   _weight(params["head"], mode))


def mtp_forward(params, hidden, next_tokens, spec: dict, mode: str = "f32"):
    """The MTP module over one sequence: ``hidden`` (L, d) from ``forward``,
    ``next_tokens`` (L,) the ids at t + 1 -> logits (L, V) for t + 2."""
    m = params["mtp"]
    with jax.default_matmul_precision("highest"):
        emb = _round(jnp.asarray(params["embed"], jnp.float32)[next_tokens], mode)
        x = jnp.concatenate([_rms(hidden, m["ln_hidden"], spec["eps"], mode),
                             _rms(emb, m["ln_embed"], spec["eps"], mode)], axis=-1)
        x = _mm(x, m["proj"], mode)
        x, _ = _block(m["block"], "full_attention", x, spec, mode, None)
        return _head({"final_norm": m["final_norm"], "head": params["head"]},
                     x, spec["eps"], mode)


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                                 for k, v in self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
