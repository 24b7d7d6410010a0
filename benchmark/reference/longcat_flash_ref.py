"""The plain reference of LongCat-Flash-Omni's language model (LongCat-Flash,
arXiv:2509.01322): the yardstick of ``correct`` in the ``longcat`` cell and of
``tests/test_longcat_flash.py``.  It lives in the benchmark alone, so that no
change to the program moves the yardstick.
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
# The description (the published config.json's keys; the layer is the
# shortcut-connected MoE block of the LongCat-Flash report, the attention
# DeepSeek-V2's MLA with the two factors of the family's published code):
#   layer   a0 = x  + MLA_0(RMSNorm(x))
#           n0 = RMSNorm(a0)
#           m  = MoE(n0)              the shortcut: leaves the stream here,
#                                     joins at the layer's end
#           b0 = a0 + SwiGLU_0(n0)    dense, width ffn_hidden_size
#           a1 = b0 + MLA_1(RMSNorm(b0))
#           y  = a1 + SwiGLU_1(RMSNorm(a1)) + m
#           a final RMSNorm and an untied head.
#   MLA     c_q = RMSNorm(xn W_qa) * sqrt(hidden / q_lora_rank)
#           (mla_scale_q_lora); q = c_q W_qb, per head [q_nope | q_rope];
#           xn W_kva = [c | k_r]; c_kv = RMSNorm(c) * sqrt(hidden /
#           kv_lora_rank) (mla_scale_kv_lora); rotary embedding (rotate-half
#           over qk_rope_head_dim, theta, position = row) on q_rope and on
#           k_r, which all heads share; per head [k_nope | v] = c_kv W_kvb;
#           scores q.k / sqrt(qk_nope + qk_rope) for j <= i; o = concat_h(p v)
#           W_o.
#   MoE     s = softmax(n W_r) in float32 over ALL the router's outputs:
#           n_routed_experts experts, then zero_expert_num identity experts;
#           chosen = top-k of s + bias; w_i = routed_scaling_factor * s_i, NOT
#           normalised; m = sum over the chosen routed experts of w_i E_i(n)
#           + (sum of the chosen identity experts' w_i) n;  E(n) = W_down(
#           silu(W_gate n) * (W_up n)).
#
# Departures from the published model, each the cut the configuration states:
#   * only the first num_layers layers exist;
#   * only the routed experts first .. first + held - 1 are held: the routed
#     sum runs over the chosen experts that are held, and that partial result
#     goes on to the next layer; the identity experts' term is whole (every
#     rank computes it alike: counted once);
#   * the audio and vision encoders and the codec decoder are not here;
#   * what config.json does not say (softmax scoring, no renormalisation,
#     rotate-half pairing, no long-context factor in the softmax scale, the
#     factors on the normed latents) is the family's published code's, named
#     in the configuration file under `assumed`.
#
# Attention runs a block of query positions at a time against all keys, and
# logits are computed only for the rows asked for.
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
#   "no_zero_term" (the identity experts' term left out), "normalised_topk"
#   (the twelve weights renormalised to sum to one before the factor),
#   "sigmoid_scoring" (each output's own sigmoid in place of the softmax),
#   "no_scale_factor" (routed_scaling_factor left out), "no_q_scale" /
#   "no_kv_scale" (a latent's factor left out), "sequential_block" (the expert
#   layer fed the SECOND sublayer's normed stream: a plain sequential block,
#   no shortcut), "expert_zeroed" (the first held expert returns nothing).

QUERY_BLOCK = 256
EXPERT_ROWS = 64      # an expert's tokens are padded to a multiple of this


def spec_from_config(d: dict) -> dict:
    """What the reference needs of a configuration file (the published keys
    with the cut applied; `published` the uncut counts; `deployment.rank`)."""
    rank = int(d.get("deployment", {}).get("rank", 0))
    held = int(d["n_routed_experts"])
    hidden = int(d["hidden_size"])
    return {
        "heads": int(d["num_attention_heads"]),
        "kv_rank": int(d["kv_lora_rank"]),
        "nope": int(d["qk_nope_head_dim"]),
        "rope": int(d["qk_rope_head_dim"]),
        "v_dim": int(d["v_head_dim"]),
        "q_scale": (math.sqrt(hidden / int(d["q_lora_rank"]))
                    if d.get("mla_scale_q_lora") else 1.0),
        "kv_scale": (math.sqrt(hidden / int(d["kv_lora_rank"]))
                     if d.get("mla_scale_kv_lora") else 1.0),
        "eps": float(d["rms_norm_eps"]),
        "theta": float(d["rope_theta"]),
        "layers": int(d["num_layers"]),
        "top_k": int(d["moe_topk"]),
        "scale": float(d["routed_scaling_factor"]),
        "routed": int(d.get("published", {}).get("n_routed_experts", held)),
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


def _rms(x, g, eps, mode, factor=1.0):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _round(y * jnp.asarray(g, jnp.float32) * factor, mode)


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
    q_scale = 1.0 if variant == "no_q_scale" else spec["q_scale"]
    kv_scale = 1.0 if variant == "no_kv_scale" else spec["kv_scale"]
    cq = _rms(_mm(x, p["wq_a"], mode), p["q_norm"], spec["eps"], mode, q_scale)
    q = _mm(cq, p["wq_b"], mode).reshape(l, h, nope + rope)
    kv = _mm(x, p["wkv_a"], mode)
    ckv = _rms(kv[:, :r], p["kv_norm"], spec["eps"], mode, kv_scale)
    up = _mm(ckv, p["wkv_b"], mode).reshape(l, h, nope + dv)
    q_nope, k_nope, v = q[..., :nope], up[..., :nope], up[..., nope:]
    q_rope = _round(_rope(q[..., nope:], spec["theta"]), mode)
    k_rope = _round(_rope(kv[:, None, r:], spec["theta"]), mode)   # one for all heads
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (l, h, rope))], -1)
    scale = 1.0 / math.sqrt(nope + rope)
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


def _attn_half(sub, x, spec, mode, variant):
    """One sublayer's attention: -> (a = x + MLA(RMSNorm(x)), RMSNorm(a))."""
    xn = _rms(x, sub["ln_in"], spec["eps"], mode)
    a = _round(x + _attention(sub["attn"], xn, spec, mode, variant), mode)
    return a, _rms(a, sub["ln_post"], spec["eps"], mode)


def _route(x, router, bias, spec, variant):
    """-> (chosen (L, k) over ALL the router's outputs, their weights)."""
    logits = jnp.dot(x, jnp.asarray(router, jnp.float32))
    s = (jax.nn.sigmoid(logits) if variant == "sigmoid_scoring"
         else jax.nn.softmax(logits, axis=-1))
    _, chosen = jax.lax.top_k(s + jnp.asarray(bias, jnp.float32), spec["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if variant == "normalised_topk":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * (1.0 if variant == "no_scale_factor" else spec["scale"])


def _expert_add(y, x, rows, w, gate, up, down, mode):
    """y with w * E(x[rows]) added at ``rows`` (rows == len(x): padding)."""
    take = jnp.minimum(rows, x.shape[0] - 1)
    out = w[:, None] * _swiglu(x[take], {"gate": gate, "up": up, "down": down}, mode)
    return y.at[rows].add(out, mode="drop")


def _dense_tail(a, n, mlp, mode):
    return _round(a + _swiglu(n, mlp, mode), mode)


def _layer_end(a, n, mlp, m, mode):
    """y = a1 + SwiGLU_1(n1) + m: the shortcut joins the stream here."""
    return _round(_dense_tail(a, n, mlp, mode) + m, mode)


_attn_half_jit = jax.jit(_attn_half, static_argnums=(2, 3, 4))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_expert_add_jit = jax.jit(_expert_add, static_argnums=(7,))
_dense_tail_jit = jax.jit(_dense_tail, static_argnums=(3,))
_layer_end_jit = jax.jit(_layer_end, static_argnums=(4,))


def _experts(p, x, spec, mode, variant):
    """The expert layer over ``x`` (L, d): -> (m: the held experts' part of
    the routed sum + the identity experts' term (L, d), the router's outputs
    each token chose (L, k)).  Each held expert runs on the tokens that chose
    it, their number padded to a multiple of EXPERT_ROWS."""
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
    if variant != "no_zero_term":
        # an identity expert returns its input: the chosen ones' weights, summed
        w0 = np.where(picked >= spec["routed"], weights, 0.0).sum(-1)
        y = y + jnp.asarray(w0, jnp.float32)[:, None] * x
    return _round(y, mode), chosen


def _block(layer, x, spec, mode, variant):
    """One layer over the sequence: -> (y, the router's outputs chosen)."""
    first, second = layer["sub"]
    frozen = _freeze(spec)
    a0, n0 = _attn_half_jit(first, x, frozen, mode, variant)
    if variant != "sequential_block":
        m, chosen = _experts(layer["moe"], n0, spec, mode, variant)
    b0 = _dense_tail_jit(a0, n0, first["mlp"], mode)
    a1, n1 = _attn_half_jit(second, b0, frozen, mode, variant)
    if variant == "sequential_block":
        m, chosen = _experts(layer["moe"], n1, spec, mode, variant)
    return _layer_end_jit(a1, n1, second["mlp"], m, mode), chosen


def forward(params, tokens, spec: dict, mode: str = "f32", variant=None,
            rows=None) -> dict:
    """One sequence ``tokens`` (L,) -> {"logits" float32 (L, V), or (len(rows),
    V) for the positions ``rows`` alone, "hidden" (L, d) before the final
    norm, "chosen": [(L, k) or (len(rows), k) per layer]}.  The layers run a few jitted calls each, fed that
    layer of the tree the program serves from, so that only one matrix is
    ever upcast at a time."""
    with jax.default_matmul_precision("highest"):
        x = _round(jnp.asarray(params["embed"][tokens], jnp.float32), mode)
        chosen = []
        for layer in params["layers"][:spec["layers"]]:
            x, c = _block(layer, x, spec, mode, variant)
            chosen.append(c)
        at = slice(None) if rows is None else np.asarray(rows)
        logits = _head(params, x[at], spec["eps"], mode)
    return {"logits": logits, "hidden": x, "chosen": [c[at] for c in chosen]}


@jax.jit
def _head_f32(norm, head, x, eps):
    return jnp.dot(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                   * jnp.asarray(norm, jnp.float32), jnp.asarray(head, jnp.float32))


def _head(params, x, eps, mode):
    if mode == "f32":
        return _head_f32(params["final_norm"], params["head"], x, eps)
    return jnp.dot(_round(_rms(x, params["final_norm"], eps, mode), mode),
                   _weight(params["head"], mode))


class _Frozen(dict):
    """A spec that ``jax.jit`` can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(spec: dict) -> _Frozen:
    return _Frozen(spec)
