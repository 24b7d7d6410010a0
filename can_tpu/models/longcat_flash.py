"""LongCat-Flash-Omni's language model (``model_type`` ``longcat_flash``;
LongCat-Flash, arXiv:2509.01322): a decoder whose every layer holds TWO
latent-attention sublayers, each followed by a dense SwiGLU, and ONE expert
layer on a shortcut around the second of them (shortcut-connected MoE), with
zero-compute experts beside the routed ones.  The audio and vision encoders
and the codec decoder of the Omni model are not here.

Pure functions over a parameter tree, through ``models/lm_blocks.py``'s
skeleton as the other language models; the latent attention layer itself is
``models/glm_moe_lite.py``'s (one place for both models: ``attention_
expanded`` over a prompt, ``attention_absorbed`` over a step against the
cache), here with the two factors on the normed latents.

The layer (``d`` = ``hidden_size``; every norm an RMSNorm, statistics in
float32)::

    a0 = x  + MLA_0(RMSNorm(x))
    n0 = RMSNorm(a0)
    m  = MoE(n0)                       # the shortcut: leaves the stream here, joins at the end
    b0 = a0 + SwiGLU_0(n0)             # dense, width ffn_hidden_size
    a1 = b0 + MLA_1(RMSNorm(b0))
    y  = a1 + SwiGLU_1(RMSNorm(a1)) + m

    MLA(xn):  c_q = RMSNorm(xn W_qa) * sqrt(d / q_lora_rank)      (mla_scale_q_lora)
              q   = c_q W_qb -> heads of [q_nope | q_rope];  q_rope = RoPE(q_rope)
              [c | k_r] = xn W_kva
              c_kv = RMSNorm(c) * sqrt(d / kv_lora_rank)           (mla_scale_kv_lora)
              k_rope = RoPE(k_r), one for all heads
              [k_nope_h | v_h] = c_kv W_kvb
              p = causal softmax((q_nope . k_nope + q_rope . k_rope) / sqrt(qk_nope + qk_rope))
              out = concat_h(p v_h) W_o
              cached a position and sublayer: c_kv and k_rope

    MoE(n):   s = softmax_f32(n W_r) over n_routed_experts + zero_expert_num outputs
              chosen = top_k(s + bias)          (the bias moves the choice, never the weight)
              w_i = routed_scaling_factor s_i   (NOT normalised)
              m = sum_{i chosen, i < n_routed} w_i E_i(n) + (sum_{i chosen, i >= n_routed} w_i) n
              E_i(n) = (silu(n G_i) * (n U_i)) D_i

One chip holds ``share.held`` of the routed experts (``ops/moe.py``): what
the absent ones would add is left out of ``m``; the identity experts' term
is computed whole (every rank computes it alike).

A layer is ONE layer of the stack: its cache entry holds both sublayers'
latents under distinct leaves (``ckv0`` / ``krope0``, ``ckv1`` / ``krope1``;
``cache_layout`` gives a tuple of two ``latent`` specs), its block runs both
sublayers, and its one ``Routed`` is the expert layer's.

What the published ``config.json`` does not say is ONE choice each here,
named in ``ASSUMED`` (a configuration file states them under ``assumed``, and
``from_dict`` refuses another value), each the family's published code's:
``scoring_func`` (softmax over all the router's outputs), ``norm_topk_prob``
(false: the chosen scores are not renormalised), ``rope_pairing``
(rotate-half; with seeded weights interleaved pairs are a permutation of
columns), ``softmax_scale`` (``1 / sqrt(qk_nope + qk_rope)``, no long-context
factor) and ``mla_scale_on`` (the factors multiply the normed latents; on
``q`` after ``W_qb`` and on the keys and values after ``W_kvb`` they are the
same numbers up to rounding).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp

from can_tpu.models.lm_blocks import experts_form  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models import glm_moe_lite as latent_attn
from can_tpu.models import lm_blocks
from can_tpu.models.glm_moe_lite import latent_traced  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes, expert_layer,
                                      init_from_shapes, last_hidden, lm_head,
                                      rms_norm, swiglu)
from can_tpu.ops import cache_layout as layout
from can_tpu.ops.moe import ExpertShare

SUBLAYERS = 2     # latent attention + dense SwiGLU, twice a layer

# what config.json leaves open, and the one value of each this module
# implements (module docstring)
ASSUMED = {"scoring_func": "softmax", "norm_topk_prob": False,
           "rope_pairing": "rotate_half",
           "softmax_scale": "1/sqrt(qk_head_dim)",
           "mla_scale_on": "normed_latents"}

# what the published model's switches have to say for this module to be it
_PUBLISHED = {"attention_bias": False, "attention_method": "MLA",
              "zero_expert_type": "identity", "rope_scaling": None,
              "tie_word_embeddings": False, "router_bias": False}


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_scale: Optional[float]       # None: mla_scale_q_lora false
    kv_lora_scale: Optional[float]
    intermediate_size: int              # ffn_hidden_size: a dense SwiGLU
    moe_intermediate_size: int          # expert_ffn_hidden_size
    num_experts_per_tok: int            # moe_topk
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    num_layers: int                     # of the layers held
    share: ExpertShare                  # with the identity experts: ``zero``
    vocab: VocabSlice
    norm_topk_prob: bool = ASSUMED["norm_topk_prob"]
    scoring_func: str = ASSUMED["scoring_func"]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @classmethod
    def from_dict(cls, d: dict) -> "LongcatFlashConfig":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied (``num_layers`` kept, ``n_routed_experts`` and
        ``vocab_size`` HELD; ``zero_expert_num`` whole), ``published`` for the
        uncut counts, ``deployment`` for the rank, ``assumed`` for what the
        config leaves open (``ASSUMED``)."""
        for name, only in _PUBLISHED.items():
            if d.get(name, only) != only:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only {only!r})")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        pub = d.get("published", {})
        rank = int(d.get("deployment", {}).get("rank", 0))
        hidden = int(d["hidden_size"])
        rq, r = int(d["q_lora_rank"]), int(d["kv_lora_rank"])
        held_e = int(d["n_routed_experts"])
        held_v = int(d["vocab_size"])
        tot_v = int(pub.get("vocab_size", held_v))
        return cls(
            hidden_size=hidden,
            num_attention_heads=int(d["num_attention_heads"]),
            q_lora_rank=rq, kv_lora_rank=r,
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            q_lora_scale=((hidden / rq) ** 0.5 if d.get("mla_scale_q_lora")
                          else None),
            kv_lora_scale=((hidden / r) ** 0.5 if d.get("mla_scale_kv_lora")
                           else None),
            intermediate_size=int(d["ffn_hidden_size"]),
            moe_intermediate_size=int(d["expert_ffn_hidden_size"]),
            num_experts_per_tok=int(d["moe_topk"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            num_layers=int(d["num_layers"]),
            share=ExpertShare(rank * held_e, held_e,
                              int(pub.get("n_routed_experts", held_e)),
                              int(d.get("zero_expert_num", 0))),
            # the vocabulary's slices go round the layer's ranks (32 chips
            # hold four copies of its eighths)
            vocab=VocabSlice(rank % max(tot_v // held_v, 1) * held_v, held_v,
                             tot_v),
        )

    @classmethod
    def from_file(cls, path: str) -> "LongcatFlashConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: LongcatFlashConfig) -> dict:
    """The tree of shapes (tuples).  ``x @ w`` everywhere.  A layer:
    ``sub`` [two of {``ln_in``, ``attn`` (the latent attention's seven
    leaves, as GLM's), ``ln_post``, ``mlp`` {gate, up, down}}] and ``moe``
    {``router`` (d, routed + zero experts), ``bias`` (the same,) a float32
    buffer, ``experts`` {gate, up (held, d, f), down (held, f, d)}}."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    f, e = cfg.moe_intermediate_size, cfg.share.held

    def sub():
        w = cfg.intermediate_size
        return {"ln_in": (d,), "ln_post": (d,),
                "attn": {"wq_a": (d, cfg.q_lora_rank),
                         "q_norm": (cfg.q_lora_rank,),
                         "wq_b": (cfg.q_lora_rank, h * cfg.qk_head_dim),
                         "wkv_a": (d, r + dr), "kv_norm": (r,),
                         "wkv_b": (r, h * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)),
                         "wo": (h * cfg.v_head_dim, d)},
                "mlp": {"gate": (d, w), "up": (d, w), "down": (w, d)}}

    def block():
        return {"sub": [sub() for _ in range(SUBLAYERS)],
                "moe": {"router": (d, cfg.share.width),
                        "bias": (cfg.share.width,),
                        "experts": {"gate": (e, d, f), "up": (e, d, f),
                                    "down": (e, f, d)}}}

    return {"embed": (cfg.vocab.held, d),
            "layers": [block() for _ in range(cfg.num_layers)],
            "final_norm": (d,), "head": (d, cfg.vocab.held)}


def param_count(cfg: LongcatFlashConfig) -> int:
    return count_shapes(param_shapes(cfg))


# the seeded router's scale: ``router`` N(0, ROUTER_GAIN^2 / fan_in), so a
# token's 768 logits are N(0, ROUTER_GAIN^2).  At the projections' unit
# scale a softmax over 768 gives the twelve chosen 0.12 together and the
# expert layer's ``m`` is a small part of the stream; at 2 they hold 0.41
# (the largest 0.12, the twelfth 0.014), so a token's weights sum to about
# 2.5 and each of the twelve matters; from 4 on one or two experts take it
# all (benchmark/harness/weights_longcat_flash.py says how that was chosen
# and draws the same)
ROUTER_GAIN = 2.0


def init_params(key, cfg: LongcatFlashConfig, dtype=jnp.bfloat16):
    """Parameters from a key, leaf by leaf on the device
    (``lm_blocks.init_from_shapes``: projections N(0, 1 / fan_in), norms
    near one, embedding N(0, 1)); the routers then times ``ROUTER_GAIN`` and
    their correction biases a tenth of the sigmoid routers' (N(0, 0.005): a
    softmax score is a hundredth of a sigmoid's, and the bias is to move a
    choice now and then, not to make it)."""
    params = init_from_shapes(key, param_shapes(cfg), dtype)
    for layer in params["layers"]:
        moe = layer["moe"]
        moe["router"] = moe["router"] * jnp.asarray(ROUTER_GAIN, dtype)
        moe["bias"] = moe["bias"] * 0.1
    return params


def cache_layout(cfg: LongcatFlashConfig) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``): the latent and the shared rotary key of every
    position of BOTH sublayers, as a tuple of two ``latent`` specs whose
    leaves share the layer's entry under their sublayer's index."""
    return (tuple(layout.latent_layer(rank=cfg.kv_lora_rank,
                                      rope_dim=cfg.qk_rope_head_dim, index=i)
                  for i in range(SUBLAYERS)),) * cfg.num_layers


# -- the block ------------------------------------------------------------
def _block(layer, x, attend, cfg: LongcatFlashConfig):
    """The layer's residual path over ``x`` (B, L, d), whatever the form of
    its attention: ``attend(i, p, xn)`` -> sublayer ``i``'s output before
    the residual.  -> (y, the expert layer's ``Routed`` with ``idx`` (B, L,
    k))."""
    b, l, d = x.shape
    first, second = layer["sub"]
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, first["ln_in"], cfg.rms_norm_eps)
    o = attend(0, first["attn"], xn)
    with jax.named_scope("attn.out"):
        a0 = x + o
    with jax.named_scope("moe.router"):
        n0 = rms_norm(a0, first["ln_post"], cfg.rms_norm_eps)
    # the shortcut: the expert layer reads the FIRST sublayer's normed
    # stream and joins the residual at the block's end, so that nothing
    # between here and there waits for it (across chips: its exchange)
    m, routed = expert_layer(layer["moe"], n0.reshape(b * l, d), cfg)
    with jax.named_scope("dense_mlp"):
        b0 = a0 + swiglu(n0, first["mlp"])
    with jax.named_scope("attn.proj"):
        xn = rms_norm(b0, second["ln_in"], cfg.rms_norm_eps)
    o = attend(1, second["attn"], xn)
    with jax.named_scope("attn.out"):
        a1 = b0 + o
    with jax.named_scope("dense_mlp"):
        y = a1 + swiglu(rms_norm(a1, second["ln_post"], cfg.rms_norm_eps),
                        second["mlp"])
    with jax.named_scope("moe.shared"):
        return y + m.reshape(b, l, d), routed._replace(
            idx=routed.idx.reshape(b, l, -1))


# ``lm_blocks``' stacks hand a block its layer's label (here its two
# ``LayerSpec``) and a step's positions as a column too: both sublayers are
# the one latent kind and ``attention_absorbed`` takes ``positions`` as they
# come, so neither is read below
def _prefill_block(layer, kind, x, positions, lengths, cfg,
                   cache_len: Optional[int]):
    """One layer over whole prompts; -> (y, cache entry or None, chosen)."""
    entry = {}

    def attend(i, p, xn):
        o, ckv, krope = latent_attn.attention_expanded(p, xn, positions,
                                                       lengths, cfg)
        if cache_len is not None:
            with jax.named_scope("attn.cache"):
                pad = ((0, 0), (0, cache_len - x.shape[1]), (0, 0))
                ckv_leaf, krope_leaf = layout.latent_leaves(i)
                entry[ckv_leaf] = jnp.pad(ckv, pad)
                entry[krope_leaf] = jnp.pad(krope, pad)
        return o

    y, chosen = _block(layer, x, attend, cfg)
    return y, (entry if cache_len is not None else None), chosen


def prefill(params, tokens, lengths, cfg: LongcatFlashConfig, cache_len: int,
            active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (float32
    logits (B, V) at each sequence's last position, a cache of ``cache_len``
    positions, routing).  Padded positions compute garbage that no valid
    position ever sees."""
    h, cache, routing = lm_blocks.prefill_stack(
        params, tokens, lengths, cache_layout(cfg), _prefill_block, cfg,
        cache_len, active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


def _decode_block(layer, kind, x, entry, positions, column, cfg):
    """One layer over one token a sequence, each sublayer's latent written
    into ITS leaves of ``entry`` before it attends; -> (y, the entry,
    chosen)."""
    written = {}

    def attend(i, p, xn):
        o, leaves = latent_attn.attention_absorbed(
            p, xn, positions, entry, cfg, leaves=layout.latent_leaves(i))
        written.update(leaves)
        return o

    y, chosen = _block(layer, x, attend, cfg)
    return y, written, chosen


def decode_step(params, cache, tokens, positions, cfg: LongcatFlashConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing)."""
    return lm_blocks.decode_stack(params, cache, tokens, positions,
                                  cache_layout(cfg), _decode_block, cfg, active)
