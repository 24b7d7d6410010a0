"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a decoder with
multi-head latent attention (MLA; DeepSeek-V2/V3, arXiv:2405.04434,
2412.19437) in every layer, a dense first layer, sparse experts with one
shared expert in the others, and a multi-token-prediction (MTP) module.

Pure functions over a parameter tree, as ``models/exaone_moe.py``; the block
around the attention (norm, SwiGLU, expert layer, head, routing report,
initialiser) is ``models/lm_blocks.py``'s, which both models import:

* ``prefill(params, tokens, lengths, cfg, cache_len)`` -> (logits at each
  sequence's last position, cache, routing);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache,
  routing);
* ``mtp_logits(params, hidden, next_tokens, cfg)`` -> logits for ``t + 2``.

The attention layer has ONE set of weights and two forms:

* queries: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``; rotary embedding on ``q_rope``;
* the latent: ``x W_kva`` -> ``[c_kv | k_rope]``; ``c_kv <- RMSNorm(c_kv)``;
  rotary on ``k_rope``, one for all heads.  ``kv_lora_rank +
  qk_rope_head_dim`` numbers a position: all the cache keeps;
* expanded (prefill): per head ``[k_nope | v] = c_kv W_kvb``, ``k = [k_nope
  | k_rope]``, causal softmax of ``q.k * scale``, ``o = concat_h(p v) W_o``;
* absorbed (decode): with ``W_kvb`` split per head into ``W_uk`` and
  ``W_uv``: ``q_lat = q_nope W_uk^T``; score ``= (q_lat . c_kv + q_rope .
  k_rope) * scale``; ``o_lat = sum_j p_j c_kv_j``; ``o_h = o_lat W_uv``.  The
  same mathematics, the cache read as it is stored, once for all heads.

What the published ``config.json`` does not say is ONE choice each here,
named in ``ASSUMED`` (a configuration file states the four under
``assumed``, and ``from_dict`` refuses another value): ``scoring_func``
(sigmoid: what ``topk_method`` ``noaux_tc`` implies), ``rope_pairing``
(rotate-half; with seeded weights interleaved pairs are a permutation of
columns), ``softmax_scale`` (``1 / sqrt(qk_nope + qk_rope)``, no
long-context factor: ``rope_scaling`` is null) and ``mtp_layout``
(DeepSeek-V3's).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from can_tpu.models.lm_blocks import experts_form  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models import lm_blocks
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes, ffn,
                                      init_from_shapes, last_hidden, lm_head,
                                      rms_norm)
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import pallas_attention as fused_attn
from can_tpu.ops import pallas_latent as fused_latent
from can_tpu.ops.moe import ExpertShare

# queries and keys of a prefill in the SCANNED form meet in blocks of this
# many positions (``ops/attention.py::prefill_causal``): a float32 score
# block of 20 heads is 84 MB, and a 16,384-token prompt is 136 block pairs.
# The fused form has its own, timed, beside the kernel
# (``ops/pallas_attention.py``)
PREFILL_BLOCK = 1024

# what config.json leaves open, and the one value of each this module
# implements (module docstring)
ASSUMED = {"scoring_func": "sigmoid", "rope_pairing": "rotate_half",
           "softmax_scale": "1/sqrt(qk_head_dim)",
           "mtp_layout": "deepseek_v3"}


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    num_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    mlp_layer_types: Tuple[str, ...]    # of the layers held
    share: ExpertShare
    vocab: VocabSlice
    mtp_layers: int = 0

    @property
    def num_layers(self) -> int:
        return len(self.mlp_layer_types)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @classmethod
    def from_dict(cls, d: dict) -> "Glm4MoeLiteConfig":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied (``num_hidden_layers`` kept, ``n_routed_experts``
        and ``vocab_size`` HELD), ``published`` for the uncut counts,
        ``deployment`` for the rank, ``assumed`` for what the config leaves
        open (``ASSUMED``)."""
        pub = d.get("published", {})
        rank = int(d.get("deployment", {}).get("rank", 0))
        n = int(d["num_hidden_layers"])
        dense = int(d["first_k_dense_replace"])
        held_e = int(d["n_routed_experts"])
        tot_e = int(pub.get("n_routed_experts", held_e))
        held_v = int(d["vocab_size"])
        tot_v = int(pub.get("vocab_size", held_v))
        if int(d.get("n_group", 1)) != 1 or int(d.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing is not implemented "
                             "(n_group and topk_group must be 1)")
        if d.get("rope_scaling") is not None:
            raise ValueError("rotary scaling is not implemented "
                             "(rope_scaling must be null)")
        if float(d.get("partial_rotary_factor", 1)) != 1:
            raise ValueError("partial_rotary_factor must be 1: the rotary "
                             "embedding covers the whole of qk_rope_head_dim")
        if d.get("attention_bias") or d.get("tie_word_embeddings"):
            raise ValueError("attention biases and tied embeddings are not "
                             "implemented")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["n_shared_experts"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            mlp_layer_types=tuple(["dense"] * min(dense, n)
                                  + ["sparse"] * max(n - dense, 0)),
            share=ExpertShare(rank * held_e, held_e, tot_e),
            vocab=VocabSlice(rank * held_v, held_v, tot_v),
            mtp_layers=int(d.get("num_nextn_predict_layers", 0)),
        )

    @classmethod
    def from_file(cls, path: str) -> "Glm4MoeLiteConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: Glm4MoeLiteConfig) -> dict:
    """The tree of shapes (tuples); ``bias`` leaves are float32 buffers.
    ``x @ w`` everywhere; ``wkv_b``'s columns are head by head ``[k_nope |
    v]``, ``wq_b``'s ``[q_nope | q_rope]``."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim

    def mlp(width):
        return {"gate": (d, width), "up": (d, width), "down": (width, d)}

    def block(mlp_type):
        out = {"ln_in": (d,), "ln_post": (d,),
               "attn": {"wq_a": (d, cfg.q_lora_rank),
                        "q_norm": (cfg.q_lora_rank,),
                        "wq_b": (cfg.q_lora_rank, h * cfg.qk_head_dim),
                        "wkv_a": (d, r + dr), "kv_norm": (r,),
                        "wkv_b": (r, h * (cfg.qk_nope_head_dim
                                          + cfg.v_head_dim)),
                        "wo": (h * cfg.v_head_dim, d)}}
        if mlp_type == "dense":
            out["mlp"] = mlp(cfg.intermediate_size)
        else:
            f, e = cfg.moe_intermediate_size, cfg.share.held
            out["moe"] = {"router": (d, cfg.share.total),
                          "bias": (cfg.share.total,),
                          "experts": {"gate": (e, d, f), "up": (e, d, f),
                                      "down": (e, f, d)},
                          "shared": mlp(f * cfg.num_shared_experts)}
        return out

    tree = {"embed": (cfg.vocab.held, d),
            "layers": [block(t) for t in cfg.mlp_layer_types],
            "final_norm": (d,), "head": (d, cfg.vocab.held)}
    if cfg.mtp_layers:
        tree["mtp"] = {"ln_hidden": (d,), "ln_embed": (d,), "proj": (2 * d, d),
                       "block": block("sparse"), "final_norm": (d,)}
    return tree


def param_count(cfg: Glm4MoeLiteConfig) -> int:
    return count_shapes(param_shapes(cfg))


def init_params(key, cfg: Glm4MoeLiteConfig, dtype=jnp.bfloat16):
    """Parameters from a key, leaf by leaf on the device
    (``lm_blocks.init_from_shapes``: projections N(0, 1 / fan_in), norms
    near one, embedding N(0, 1))."""
    return init_from_shapes(key, param_shapes(cfg), dtype)


def cache_layout(cfg: Glm4MoeLiteConfig) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``): the latent and the shared rotary key of every
    position, no heads.  Two arrays and not one of ``rank + rope_dim``: 576
    is not a whole number of the 128 lanes, so that array would reach a
    program with its positions minor, which the decode kernel cannot read
    as rows of latent (the 64-wide ``krope`` does arrive so, and is read and
    written so: ``ops/pallas_latent.py``, ``attn_ops.write_row``)."""
    return (layout.latent_layer(rank=cfg.kv_lora_rank,
                                rope_dim=cfg.qk_rope_head_dim),
            ) * cfg.num_layers


# -- latent attention ---------------------------------------------------
# The layer below is every latent-attention model's (``longcat_flash.py``
# runs two of it a block): ``cfg`` is any configuration with the widths it
# reads and, where the model scales its normed latents (LongCat-Flash's
# ``mla_scale_q_lora`` / ``mla_scale_kv_lora``), ``q_lora_scale`` /
# ``kv_lora_scale``; ``leaves`` names the sublayer's two leaves in its
# layer's cache entry (``cache_layout.latent_leaves``).
def _gain(g, cfg, name: str):
    """A latent's norm weight, times the configuration's factor ``name``
    where it has one (in float32: one rounding, the norm's own)."""
    scale = getattr(cfg, name, None)
    return g if scale is None else g.astype(jnp.float32) * scale


def _queries(p, xn, positions, cfg: Glm4MoeLiteConfig):
    """``xn`` (B, L, d) -> q_nope (B, L, H, nope), q_rope (B, L, H, rope)."""
    b, l, _ = xn.shape
    cq = rms_norm(jnp.dot(xn, p["wq_a"]), _gain(p["q_norm"], cfg, "q_lora_scale"),
                  cfg.rms_norm_eps)
    q = jnp.dot(cq, p["wq_b"]).reshape(b, l, cfg.num_attention_heads,
                                       cfg.qk_head_dim)
    return (q[..., :cfg.qk_nope_head_dim],
            attn_ops.rope(q[..., cfg.qk_nope_head_dim:], positions,
                          cfg.rope_theta))


def _latent(p, xn, positions, cfg: Glm4MoeLiteConfig):
    """``xn`` (B, L, d) -> c_kv (B, L, rank) normalised, k_rope (B, L, rope)
    rotated: what the cache keeps of these positions."""
    kv = jnp.dot(xn, p["wkv_a"])
    return (rms_norm(kv[..., :cfg.kv_lora_rank],
                     _gain(p["kv_norm"], cfg, "kv_lora_scale"),
                     cfg.rms_norm_eps),
            attn_ops.rope(kv[..., cfg.kv_lora_rank:], positions,
                          cfg.rope_theta))


def _up_projections(p, cfg: Glm4MoeLiteConfig):
    """``W_kvb`` per head: W_uk (rank, H, nope), W_uv (rank, H, v)."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# (B, L) of a prefill's prompts -> the form the newest trace of
# ``attention_expanded`` on such prompts ran its attention in.  Written while
# a program is traced, read after its launch by whoever reports what the
# program does (as ``models/cannet.py::stage1_traced``).
_ATTENTION_TRACED: dict = {}


def attention_traced(tokens_shape) -> Optional[str]:
    """``"fused"`` / ``"scanned"`` as the prefill traced in this process for
    prompts of this (B, L) has it; None where none was traced."""
    return _ATTENTION_TRACED.get(tuple(tokens_shape))


# (B, 1) of a decode step -> the form the newest trace of
# ``attention_absorbed`` for B sequences read the latent cache in
_LATENT_TRACED: dict = {}


def latent_traced(tokens_shape) -> Optional[str]:
    """``"fused"`` / ``"plain"`` as the decode step traced in this process
    for tokens of this (B, 1) has it; None where none was traced."""
    return _LATENT_TRACED.get(tuple(tokens_shape))


def attention_expanded(p, xn, positions, lengths, cfg: Glm4MoeLiteConfig):
    """Whole prompts, keys and values rebuilt per head from the latent:
    -> (the layer's output (B, L, d) before the residual, c_kv, k_rope).

    The causal attention itself has two forms, one algorithm: the fused
    kernel (``ops/pallas_attention.py``) where its ``supports`` says it can
    run (a TPU, head widths of whole lanes, a bucket of whole blocks), the
    scanned ``prefill_causal`` everywhere else.  Nothing else chooses."""
    b, l, _ = xn.shape
    h = cfg.num_attention_heads
    with jax.named_scope("attn.proj"):
        q_nope, q_rope = _queries(p, xn, positions, cfg)
        ckv, krope = _latent(p, xn, positions, cfg)
        # keys and values each from their own columns of ``W_kvb``: the
        # products land where they are used, no slice of a joint result
        w_uk, w_uv = _up_projections(p, cfg)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate(
            [jnp.einsum("blr,rhn->blhn", ckv, w_uk),
             jnp.broadcast_to(krope[:, :, None],
                              (b, l, h, krope.shape[-1]))], -1)
        v = jnp.einsum("blr,rhv->blhv", ckv, w_uv)
    fused = fused_attn.supports(q.shape, v.shape, q.dtype)
    _ATTENTION_TRACED[(b, l)] = "fused" if fused else "scanned"
    with jax.named_scope("attn.core"):
        if fused:
            o = fused_attn.fused_causal(q, k, v, lengths, scale=cfg.scale)
        else:
            o = attn_ops.prefill_causal(q, k, v, lengths, scale=cfg.scale,
                                        block=PREFILL_BLOCK)
    with jax.named_scope("attn.out"):
        return jnp.dot(o.reshape(b, l, -1), p["wo"]), ckv, krope


def attention_absorbed(p, xn, positions, entry, cfg: Glm4MoeLiteConfig,
                       leaves=layout.latent_leaves()):
    """One token a sequence, in the latent space: ``xn`` (B, 1, d) at
    ``positions`` (B,), its latent written into ``entry``'s two ``leaves``
    before it attends -> (the layer's output (B, 1, d), those leaves
    written).  The cache is read in one
    of two forms of one algorithm: the fused kernel over each sequence's own
    context (``ops/pallas_latent.py``) where its ``supports`` says it can run
    (a TPU, a rank of whole lanes, a cache of at least one block), the plain
    ``decode_latent`` over every allocated position everywhere else.  Nothing
    else chooses."""
    b = xn.shape[0]
    ckv_leaf, krope_leaf = leaves
    with jax.named_scope("attn.proj"):
        q_nope, q_rope = _queries(p, xn, positions[:, None], cfg)
        ckv, krope = _latent(p, xn, positions[:, None], cfg)
    with jax.named_scope("attn.cache"):
        ckv_c = attn_ops.write_row(entry[ckv_leaf], ckv[:, 0], positions)
        krope_c = attn_ops.write_row(entry[krope_leaf], krope[:, 0], positions)
    with jax.named_scope("attn.proj"):
        w_uk, w_uv = _up_projections(p, cfg)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    fused = fused_latent.supports(q_lat.shape, ckv_c.shape, krope_c.shape[-1],
                                  ckv_c.dtype)
    _LATENT_TRACED[(b, 1)] = "fused" if fused else "plain"
    with jax.named_scope("attn.core"):
        if fused:
            o_lat = fused_latent.fused_latent_decode(
                q_lat, q_rope[:, 0], ckv_c, krope_c, positions,
                scale=cfg.scale)
        else:
            valid = jnp.arange(ckv_c.shape[1])[None, :] <= positions[:, None]
            o_lat = attn_ops.decode_latent(q_lat, q_rope[:, 0], ckv_c,
                                           krope_c, valid, scale=cfg.scale)
    with jax.named_scope("attn.out"):
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
        return (jnp.dot(o.reshape(b, 1, -1), p["wo"]),
                {ckv_leaf: ckv_c, krope_leaf: krope_c})


# -- prefill ------------------------------------------------------------
# ``lm_blocks``' stacks hand a block its layer's label (here its
# ``LayerSpec``) and a step's positions as a column too: every layer is the
# one latent kind and ``attention_absorbed`` takes ``positions`` as they
# come, so neither is read below
def _prefill_block(layer, kind, x, positions, lengths, cfg,
                   cache_len: Optional[int]):
    """One block over whole prompts; -> (y, cache entry or None, chosen)."""
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
    o, ckv, krope = attention_expanded(layer["attn"], xn, positions, lengths,
                                       cfg)
    with jax.named_scope("attn.out"):
        h = x + o
    entry = None
    if cache_len is not None:
        with jax.named_scope("attn.cache"):
            pad = ((0, 0), (0, cache_len - x.shape[1]), (0, 0))
            entry = {"ckv": jnp.pad(ckv, pad), "krope": jnp.pad(krope, pad)}
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def prefill_hidden(params, tokens, lengths, cfg: Glm4MoeLiteConfig,
                   cache_len: Optional[int] = None, active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (hidden (B,
    L, d) before the final norm, cache or None, routing).  Padded positions
    compute garbage (or nothing: attention skips whole blocks of them) that
    no valid position ever sees."""
    return lm_blocks.prefill_stack(params, tokens, lengths, cache_layout(cfg),
                                   _prefill_block, cfg, cache_len, active)


def prefill(params, tokens, lengths, cfg: Glm4MoeLiteConfig, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, a cache
    of ``cache_len`` positions, routing)."""
    h, cache, routing = prefill_hidden(params, tokens, lengths, cfg, cache_len,
                                       active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


# -- decode -------------------------------------------------------------
def _decode_block(layer, kind, x, entry, positions, column, cfg):
    """One block over one token a sequence, its latent written into
    ``entry`` before it attends; -> (y, the entry, chosen)."""
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
    o, entry = attention_absorbed(layer["attn"], xn, positions, entry, cfg)
    with jax.named_scope("attn.out"):
        h = x + o
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def decode_step(params, cache, tokens, positions, cfg: Glm4MoeLiteConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing)."""
    return lm_blocks.decode_stack(params, cache, tokens, positions,
                                  cache_layout(cfg), _decode_block, cfg, active)


# -- multi-token prediction -----------------------------------------------
def mtp_logits(params, hidden, next_tokens, cfg: Glm4MoeLiteConfig):
    """``lm_blocks.mtp_logits`` with one block of the model's own kind
    (latent attention + expert layer): float32 logits (B, L, V) for
    position ``t + 2``."""
    return lm_blocks.mtp_logits(params, hidden, next_tokens, cfg,
                                _prefill_block, None)
