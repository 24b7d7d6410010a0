"""The benchmark's copy of the plain reference of GLM-4.7-Flash's forward
pass (latent attention in its expanded form, sparse experts, MTP): the
yardstick of ``correct`` in the ``glm_serve`` cell.  The same text below this
docstring as ``can_tpu/testing/glm_moe_lite_ref.py``
(``tests/test_glm_moe_lite.py`` compares them); kept here so that no change
to the program moves the yardstick.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Plain jax.numpy over ONE whole sequence: float32 under
# jax.default_matmul_precision("highest"), latent attention in its EXPANDED
# form only (keys and values rebuilt per head from the latent; no cache, no
# absorbed product), a Python loop over the held experts (each on the tokens
# that chose it), no batching, no kernels.  Imports nothing of the program.
#
# The description (GLM-4.7-Flash's config.json, model_type glm4_moe_lite;
# the layer is DeepSeek-V2/V3's MLA, arXiv:2405.04434, 2412.19437):
#   block   h = x + Attn(RMSNorm(x)); y = h + F(RMSNorm(h)); F is a SwiGLU in
#           the first_k_dense_replace leading layers and the expert layer
#           after; a final RMSNorm and an untied head.
#   attn    c_q = RMSNorm(x W_qa); q = c_q W_qb, per head [q_nope | q_rope];
#           x W_kva = [c_kv | k_rope]; c_kv <- RMSNorm(c_kv); rotary embedding
#           (rotate-half over qk_rope_head_dim, theta, position = row) on
#           q_rope and on k_rope, which all heads share; per head
#           [k_nope | v] = c_kv W_kvb; k = [k_nope | k_rope]; scores
#           q.k / sqrt(qk_nope + qk_rope) for j <= i; o = concat_h(p v) W_o.
#   experts s = sigmoid(x W_r) over ALL experts; chosen = top-k of s + bias;
#           w_i = scale * s_i / sum of the chosen s; E(x) = W_down(silu(W_gate
#           x) * (W_up x)); one shared expert added for every token.
#   MTP     h' = W_p [RMSNorm(h_t); RMSNorm(Emb(x_{t+1}))], one block of the
#           same kind (latent attention + expert layer), a norm, the shared
#           head (DeepSeek-V3).
#
# Departures from the published model, each the cut the configuration states:
#   * only the first num_hidden_layers layers exist;
#   * only the experts first .. first + held - 1 are held (in the benchmark's
#     cell: all of them): the routed sum runs over the chosen experts that
#     are held, and that partial result goes on to the next layer;
#   * the config has no scoring_func: sigmoid is what noaux_tc implies; the
#     rotary pairing is rotate-half; the MTP module's own norm before the
#     shared head is DeepSeek-V3's.
#
# So that a sequence of 16,512 positions fits, attention runs a block of
# query positions at a time against all keys (a full mask would be 21 GB),
# and logits are computed only for the rows asked for.
#
# `mode` computes the same mathematics in a lower precision, in the
# program's place, for the yardstick and the controls of `correct`:
#   "f32"   float32, matmuls at "highest" (the reference proper)
#   "bf16"  weights and activations rounded to bfloat16, float32 router,
#           softmax and norm statistics: what a sound program computes
#   "int8"  as bf16 with every matrix rounded to 8 bits per output column
# `variant` breaks one piece of the mathematics (controls only):
#   "scale_nope" (softmax scale 1/sqrt(qk_nope)), "no_kv_norm", "rope_on_nope",
#   "unnormalised_topk", "expert_zeroed".

QUERY_BLOCK = 256
EXPERT_ROWS = 512     # an expert's tokens are padded to a multiple of this


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied; `published` the uncut counts; `deployment.rank`)."""
    rank = int(d.get("deployment", {}).get("rank", 0))
    held = int(d["n_routed_experts"])
    return {
        "heads": int(d["num_attention_heads"]),
        "kv_rank": int(d["kv_lora_rank"]),
        "nope": int(d["qk_nope_head_dim"]),
        "rope": int(d["qk_rope_head_dim"]),
        "v_dim": int(d["v_head_dim"]),
        "eps": float(d["rms_norm_eps"]),
        "theta": float(d["rope_theta"]),
        "layers": int(d["num_hidden_layers"]),
        "top_k": int(d["num_experts_per_tok"]),
        "scale": float(d["routed_scaling_factor"]),
        "normalise": bool(d["norm_topk_prob"]),
        "first_expert": rank * held,
        "held_experts": held,
    }


def _round(x, mode):
    # reduce_precision, not astype(bfloat16).astype(float32): inside a fusion
    # XLA:TPU may keep the excess precision of such a pair, and the yardstick
    # then rounds less than bfloat16 does (my chip run, PR 30: the attention
    # half's own gap read 0.008 where rounding its output alone gives 0.05)
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


def _swiglu(x, p, mode):
    return _mm(_round(jax.nn.silu(_mm(x, p["gate"], mode)) * _mm(x, p["up"], mode),
                      mode), p["down"], mode)


def _attention(p, x, spec, mode, variant):
    l = x.shape[0]
    h, r = spec["heads"], spec["kv_rank"]
    nope, rope, dv = spec["nope"], spec["rope"], spec["v_dim"]
    cq = _rms(_mm(x, p["wq_a"], mode), p["q_norm"], spec["eps"], mode)
    q = _mm(cq, p["wq_b"], mode).reshape(l, h, nope + rope)
    kv = _mm(x, p["wkv_a"], mode)
    ckv = kv[:, :r]
    if variant != "no_kv_norm":
        ckv = _rms(ckv, p["kv_norm"], spec["eps"], mode)
    up = _mm(ckv, p["wkv_b"], mode).reshape(l, h, nope + dv)
    q_nope, k_nope, v = q[..., :nope], up[..., :nope], up[..., nope:]
    q_rope = _round(_rope(q[..., nope:], spec["theta"]), mode)
    k_rope = _round(_rope(kv[:, None, r:], spec["theta"]), mode)   # one for all heads
    if variant == "rope_on_nope":
        q_nope = _round(_rope(q_nope, spec["theta"]), mode)
        k_nope = _round(_rope(k_nope, spec["theta"]), mode)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (l, h, rope))], -1)
    scale = 1.0 / math.sqrt(nope if variant == "scale_nope" else nope + rope)
    # a block of query positions at a time, each against all keys
    blocks = -(-l // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - l), (0, 0), (0, 0)))
    j = jnp.arange(l)[None, :]

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        s = jnp.einsum("ihd,jhd->hij", qb, k) * scale
        pr = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return _round(jnp.einsum("hij,jhd->ihd", _round(pr, mode), v), mode)

    o = jax.lax.map(one, (qp.reshape(blocks, QUERY_BLOCK, h, nope + rope),
                          jnp.arange(blocks) * QUERY_BLOCK))
    return _mm(o.reshape(blocks * QUERY_BLOCK, h * dv)[:l], p["wo"], mode)


def _attn_half(layer, x, spec, mode, variant):
    """-> (h = x + Attn(RMSNorm(x)), RMSNorm(h))."""
    xn = _rms(x, layer["ln_in"], spec["eps"], mode)
    h = _round(x + _attention(layer["attn"], xn, spec, mode, variant), mode)
    return h, _rms(h, layer["ln_post"], spec["eps"], mode)


def _route(x, router, bias, spec, variant):
    s = jax.nn.sigmoid(jnp.dot(x, jnp.asarray(router, jnp.float32)))
    _, chosen = jax.lax.top_k(s + jnp.asarray(bias, jnp.float32), spec["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
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


def _shared_tail(h, y, hn, shared, mode):
    return _round(h + _round(y, mode) + _swiglu(hn, shared, mode), mode)


_attn_half_jit = jax.jit(_attn_half, static_argnums=(2, 3, 4))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_expert_add_jit = jax.jit(_expert_add, static_argnums=(7,))
_dense_tail_jit = jax.jit(_dense_tail, static_argnums=(3,))
_shared_tail_jit = jax.jit(_shared_tail, static_argnums=(4,))


def _experts(p, x, spec, mode, variant):
    """-> (the held experts' part of the routed sum (L, d), the experts each
    token chose (L, k)).  Each held expert runs on the tokens that chose it,
    their number padded to a multiple of EXPERT_ROWS."""
    with jax.default_matmul_precision("highest"):
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


def _block(layer, x, spec, mode, variant):
    """One block over the sequence: -> (y, chosen or None)."""
    h, hn = _attn_half_jit(layer, x, _freeze(spec), mode, variant)
    if "mlp" in layer:
        return _dense_tail_jit(h, hn, layer["mlp"], mode), None
    y, chosen = _experts(layer["moe"], hn, spec, mode, variant)
    return _shared_tail_jit(h, y, hn, layer["moe"]["shared"], mode), chosen


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
        for layer in params["layers"][:spec["layers"]]:
            x, c = _block(layer, x, spec, mode, variant)
            if c is not None:
                chosen.append(c)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head(params, x[at], spec["eps"], mode)
    return {"logits": logits, "hidden": x, "chosen": [c[at] for c in chosen]}


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
        emb = _round(jnp.asarray(params["embed"][next_tokens], jnp.float32), mode)
        x = jnp.concatenate([_rms(hidden, m["ln_hidden"], spec["eps"], mode),
                             _rms(emb, m["ln_embed"], spec["eps"], mode)], axis=-1)
        x = _mm(x, m["proj"], mode)
        x, _ = _block(m["block"], x, spec, mode, None)
        return _head({"final_norm": m["final_norm"], "head": params["head"]},
                     x, spec["eps"], mode)


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
