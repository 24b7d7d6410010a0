"""K-EXAONE (``model_type`` ``exaone_moe``): a decoder with window and
full attention layers mixed, a dense first layer, sparse experts with one
shared expert in the others, and a multi-token-prediction (MTP) module.

Pure functions over a parameter tree made from a seed:

* ``prefill(params, tokens, lengths, cfg)`` -> (logits at each sequence's
  last position, cache);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache);
* ``mtp_logits(params, hidden, next_tokens, cfg)`` -> logits for ``t + 2``.

One chip's share: ``cfg.share`` says which experts of each layer live here
(``ops/moe.py``) and ``cfg.vocab`` which slice of the vocabulary; ids,
logits and the argmax are over the slice.  Weights and activations follow
the parameter tree's dtype (bfloat16 as served; the CPU tests also run
float32); the router, the softmax and the norms' statistics are float32.

The block: ``h = x + Attn(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``, a final
RMSNorm and an untied head.  Three conventions are the family's (EXAONE
4.0, arXiv:2507.11407, and the K-EXAONE model card) and not keys of the
published ``config.json``; each sits behind one field of the config:
``qk_norm`` (q and k RMS-normalised per head), ``rope_layers`` (rotary
embedding on window layers only) and ``pre_norm`` (norms before the
sublayers).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from can_tpu.models import lm_blocks
from can_tpu.models.lm_blocks import experts_form  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes,
                                      init_from_shapes, kv_decode, kv_entry,
                                      last_hidden, lm_head, qkv_heads,
                                      rms_norm)
from can_tpu.models.lm_blocks import ffn as _ffn
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops.moe import ExpertShare

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    num_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    sliding_window: int
    layer_types: Tuple[str, ...]        # of the layers held
    mlp_layer_types: Tuple[str, ...]
    share: ExpertShare
    vocab: VocabSlice
    mtp_layers: int = 0
    # the family's conventions (module docstring)
    qk_norm: bool = True
    rope_layers: Tuple[str, ...] = (WINDOW,)
    pre_norm: bool = True

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @classmethod
    def from_dict(cls, d: dict) -> "ExaoneMoeConfig":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied (``num_hidden_layers`` kept, ``num_experts``
        and ``vocab_size`` HELD), ``published`` for the uncut counts,
        ``deployment`` for the rank, ``assumed`` for the conventions."""
        pub = d.get("published", {})
        dep = d.get("deployment", {})
        ass = d.get("assumed", {})
        n = int(d["num_hidden_layers"])
        rank = int(dep.get("rank", 0))
        held_e, tot_e = int(d["num_experts"]), int(pub.get("num_experts", d["num_experts"]))
        held_v, tot_v = int(d["vocab_size"]), int(pub.get("vocab_size", d["vocab_size"]))
        if int(d.get("n_group", 1)) != 1 or int(d.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing is not implemented "
                             "(n_group and topk_group must be 1)")
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("only sigmoid router scores are implemented")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["num_shared_experts"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_parameters"]["rope_theta"]),
            sliding_window=int(d["sliding_window"]),
            layer_types=tuple(d["layer_types"][:n]),
            mlp_layer_types=tuple(d["mlp_layer_types"][:n]),
            share=ExpertShare(rank * held_e, held_e, tot_e),
            vocab=VocabSlice(rank * held_v, held_v, tot_v),
            mtp_layers=int(d.get("num_nextn_predict_layers", 0)),
            qk_norm=bool(ass.get("qk_norm", True)),
            rope_layers=tuple(ass.get("rope_layers", (WINDOW,))),
            pre_norm=bool(ass.get("pre_norm", True)),
        )

    @classmethod
    def from_file(cls, path: str) -> "ExaoneMoeConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: ExaoneMoeConfig) -> dict:
    """The tree of shapes (tuples); ``bias`` leaves are float32 buffers."""
    d, hd = cfg.hidden_size, cfg.head_dim
    qd, kd = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd

    def mlp(width):
        return {"gate": (d, width), "up": (d, width), "down": (width, d)}

    def block(mlp_type):
        out = {"ln_in": (d,), "ln_post": (d,),
               "attn": {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd),
                        "wo": (qd, d), "q_norm": (hd,), "k_norm": (hd,)}}
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


def param_count(cfg: ExaoneMoeConfig) -> int:
    return count_shapes(param_shapes(cfg))


def init_params(key, cfg: ExaoneMoeConfig, dtype=jnp.bfloat16):
    """Parameters from a key, leaf by leaf on the device
    (``lm_blocks.init_from_shapes``: projections N(0, 1 / fan_in), norms
    near one, embedding N(0, 1))."""
    return init_from_shapes(key, param_shapes(cfg), dtype)


def _kv_spec(cfg: ExaoneMoeConfig, layer_type: str) -> layout.LayerSpec:
    return layout.kv_layer(
        layout.RING if layer_type == WINDOW else layout.FULL,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        window=cfg.sliding_window)


def cache_layout(cfg: ExaoneMoeConfig) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``): a ring of ``sliding_window`` positions in
    window layers, the whole context in full ones, keys and values per
    key/value head."""
    return tuple(_kv_spec(cfg, t) for t in cfg.layer_types)


# -- layers -------------------------------------------------------------
def _qkv(p, x, positions, layer_type, cfg: ExaoneMoeConfig):
    """``x`` (B, L, d) -> q (B, L, KV, G, D), k, v (B, L, KV, D)."""
    q, k, v = qkv_heads(p, x, cfg.num_key_value_heads, cfg.groups,
                        cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if layer_type in cfg.rope_layers:
        q = attn_ops.rope(q, positions, cfg.rope_theta)
        k = attn_ops.rope(k, positions, cfg.rope_theta)
    return q, k, v


def ffn(layer, h, cfg: ExaoneMoeConfig):
    """The feed-forward half of a block (``lm_blocks.ffn``), its norm where
    the configuration puts norms before the sublayers."""
    return _ffn(layer, h, cfg, norm=cfg.pre_norm)


# -- prefill ------------------------------------------------------------
def _prefill_block(layer, layer_type, x, positions, lengths, cfg,
                   cache_len: Optional[int]):
    """One block over whole prompts; -> (y, cache entry or None, chosen)."""
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps) if cfg.pre_norm else x
        q, k, v = _qkv(layer["attn"], xn, positions, layer_type, cfg)
    with jax.named_scope("attn.core"):
        if layer_type == WINDOW:
            o = attn_ops.prefill_window(q, k, v, window=cfg.sliding_window)
        else:
            o = attn_ops.prefill_full(q, k, v)
    with jax.named_scope("attn.out"):
        b, l = x.shape[:2]
        h = x + jnp.dot(o.reshape(b, l, -1), layer["attn"]["wo"])
    entry = None
    if cache_len is not None:
        entry = kv_entry(_kv_spec(cfg, layer_type), k, v, lengths, cache_len)
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def prefill_hidden(params, tokens, lengths, cfg: ExaoneMoeConfig,
                   cache_len: Optional[int] = None, active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (hidden (B,
    L, d) before the final norm, cache or None, routing).  Padded positions
    compute garbage no valid position ever sees (attention is causal)."""
    return lm_blocks.prefill_stack(params, tokens, lengths, cfg.layer_types,
                                   _prefill_block, cfg, cache_len, active)


def prefill(params, tokens, lengths, cfg: ExaoneMoeConfig, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, cache,
    routing).  The cache holds ``cache_len`` positions in full
    layers and ``cfg.sliding_window`` in window layers."""
    h, cache, routing = prefill_hidden(params, tokens, lengths, cfg, cache_len,
                                       active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


# -- decode -------------------------------------------------------------
def _decode_block(layer, layer_type, x, entry, positions, column, cfg):
    """One block over one token a sequence, its key and value written into
    ``entry`` before it attends; -> (y, the entry, chosen)."""
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps) if cfg.pre_norm else x
        q, k, v = _qkv(layer["attn"], xn, column, layer_type, cfg)
    o, entry = kv_decode(_kv_spec(cfg, layer_type), q, k, v, entry, positions,
                         column, ("attn.core",))
    with jax.named_scope("attn.out"):
        h = x + jnp.dot(o.reshape(x.shape[0], 1, -1), layer["attn"]["wo"])
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def decode_step(params, cache, tokens, positions, cfg: ExaoneMoeConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing).  The token's key and value are written at its position
    (its ring slot in window layers) before it attends."""
    return lm_blocks.decode_stack(params, cache, tokens, positions,
                                  cfg.layer_types, _decode_block, cfg, active)


# -- multi-token prediction -----------------------------------------------
def mtp_logits(params, hidden, next_tokens, cfg: ExaoneMoeConfig):
    """``lm_blocks.mtp_logits`` with one full-attention block with an expert
    layer: float32 logits (B, L, V) for position ``t + 2``."""
    return lm_blocks.mtp_logits(params, hidden, next_tokens, cfg,
                                _prefill_block, FULL)
