"""LFM2-MoE (``model_type`` ``lfm2_moe``; LFM2-24B-A2B): a decoder whose layers
differ in the KIND OF MIXER: most are a gated short convolution (``conv``),
some grouped-query attention (``full_attention``), as ``layer_types`` says
layer by layer; the first ``num_dense_layers`` feed-forwards are a dense
SwiGLU, the others sparse experts WITHOUT a shared one; the head is tied to
the embedding.

Pure functions over a parameter tree, as ``models/exaone_moe.py``; norm,
SwiGLU, expert layer, routing report, embedding, head and initialiser are
``models/lm_blocks.py``'s, the convolution ``ops/ssm.py``'s, attention
``ops/attention.py``'s:

* ``prefill(params, tokens, lengths, cfg, cache_len)`` -> (logits at each
  sequence's last position, cache, routing);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache,
  routing).

The block, as the published ``config.json`` and Hugging Face's ``Lfm2Moe*``
state it (``h`` (L, d), ``rms`` with ``norm_eps`` in float32):

    h = h + mixer_i(rms(h, operator_norm));   h = h + ffn_i(rms(h, ffn_norm))
    logits = rms(h, embedding_norm) embed^T

* ``conv`` mixer: ``[B | C | X] = x W_in`` (d -> 3 d, in that order), ``u =
  B * X``, ``v[t] = sum_j w[:, j] u[t - K + 1 + j]`` per channel (depthwise,
  causal, width ``conv_L_cache``, no bias, no activation), ``y = (C * v)
  W_out``.  Two forms of one layer: over whole prompts (prefill), one step
  from the cached tail (decode);
* ``full_attention`` mixer: grouped-query, no bias, q and k RMS-normalised
  per head, rotary embedding over the whole head, scale ``1 /
  sqrt(head_dim)``, causal;
* sparse ``ffn``: sigmoid scores over ALL experts in float32, the top
  ``num_experts_per_tok`` of ``score + expert_bias`` chosen, their scores
  normalised and scaled (``ops/moe.py::route``, which divides by the sum
  where the published code adds 1e-6 to it: 5e-7 relative), the sum over the
  chosen experts HELD HERE (``cfg.share``).

A launch's cache (``ops/cache_layout.py``) holds ONE kind a layer, and which
depends on the layer: a ``conv`` layer a ``state`` without positions (``u``
at the last ``conv_L_cache - 1`` positions of each sequence's OWN length,
(slots, d, K - 1) in the cache's dtype: a copy of activations, nothing
accumulates), a ``full_attention`` layer ``full`` keys and values, the heads
of 64 two to a row of 128 lanes (``ops/cache_layout.py::kv_pack``: the
prefill hands its entry over in that shape, ``write_slot`` and ``decode``
read it from the leaf's).

What the published config leaves open is ONE choice each, named in
``ASSUMED`` (a configuration file states them under ``assumed``;
``from_dict`` refuses another value); ``head_dim``, which the config does not
give, is ``assumed.head_dim`` or ``hidden_size / num_attention_heads``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from can_tpu.models import lm_blocks
from can_tpu.models.lm_blocks import experts_form  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes, ffn,
                                      init_from_shapes, kv_decode, kv_entry,
                                      last_hidden, lm_head, qkv_heads,
                                      rms_norm)
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import ssm as ssm_ops
from can_tpu.ops.moe import ExpertShare

CONV, FULL = "conv", "full_attention"

ASSUMED = {"tie_word_embeddings": True, "rope_pairing": "rotate_half",
           "conv_tail_dtype": "activations"}

# what the published model's switches have to say for this module to be it
_PUBLISHED = {"conv_bias": False, "use_expert_bias": True}


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    conv_L_cache: int
    layer_types: Tuple[str, ...]        # of the layers held
    num_dense_layers: int
    share: ExpertShare
    vocab: VocabSlice

    @property
    def groups(self) -> int:
        """Query heads to a key/value head."""
        return self.num_attention_heads // self.num_key_value_heads

    @classmethod
    def from_dict(cls, d: dict) -> "Lfm2MoeConfig":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied (``num_experts`` HELD), ``published`` for the
        uncut count, ``deployment`` for the rank, ``assumed`` for what the
        config leaves open."""
        for name, only in _PUBLISHED.items():
            if bool(d.get(name, only)) != only:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only {only!r})")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        kinds = tuple(d["layer_types"])
        if len(kinds) != int(d["num_hidden_layers"]):
            raise ValueError(f"layer_types names {len(kinds)} layers, "
                             f"num_hidden_layers is {d['num_hidden_layers']}")
        if set(kinds) - {CONV, FULL}:
            raise ValueError(f"layer types {sorted(set(kinds) - {CONV, FULL})} "
                             f"are not implemented (only {CONV}, {FULL})")
        heads, kv = int(d["num_attention_heads"]), int(d["num_key_value_heads"])
        if heads % kv:
            raise ValueError("heads do not divide into their groups")
        held = int(d["num_experts"])
        total = int(d.get("published", {}).get("num_experts", held))
        rank = int(d.get("deployment", {}).get("rank", 0))
        vocab = int(d["vocab_size"])
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=heads, num_key_value_heads=kv,
            head_dim=int(ass.get("head_dim", int(d["hidden_size"]) // heads)),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            rms_norm_eps=float(d["norm_eps"]),
            rope_theta=float(d["rope_parameters"]["rope_theta"]),
            conv_L_cache=int(d["conv_L_cache"]),
            layer_types=kinds,
            num_dense_layers=int(d["num_dense_layers"]),
            share=ExpertShare(rank * held, held, total),
            vocab=VocabSlice(0, vocab, vocab),
        )


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: Lfm2MoeConfig) -> dict:
    """The tree of shapes (tuples).  ``x @ w`` everywhere; ``in_proj``'s
    columns are ``[B | C | X]``, the convolution's weight (channels, taps)
    with the current position last; ``bias`` leaves are float32 buffers; no
    ``head`` (tied to ``embed``)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    qd, kd = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd

    def mlp(width):
        return {"gate": (d, width), "up": (d, width), "down": (width, d)}

    def block(i, kind):
        out = {"ln_in": (d,), "ln_post": (d,)}
        if kind == CONV:
            out["conv"] = {"in_proj": (d, 3 * d),
                           "conv_w": (d, cfg.conv_L_cache),
                           "out_proj": (d, d)}
        else:
            out["attn"] = {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd),
                           "wo": (qd, d), "q_norm": (hd,), "k_norm": (hd,)}
        if i < cfg.num_dense_layers:
            out["mlp"] = mlp(cfg.intermediate_size)
        else:
            f, e = cfg.moe_intermediate_size, cfg.share.held
            out["moe"] = {"router": (d, cfg.share.total),
                          "bias": (cfg.share.total,),
                          "experts": {"gate": (e, d, f), "up": (e, d, f),
                                      "down": (e, f, d)}}
        return out

    return {"embed": (cfg.vocab.held, d),
            "layers": [block(i, k) for i, k in enumerate(cfg.layer_types)],
            "final_norm": (d,)}


def param_count(cfg: Lfm2MoeConfig) -> int:
    return count_shapes(param_shapes(cfg))


def init_params(key, cfg: Lfm2MoeConfig, dtype=jnp.bfloat16):
    """Parameters from a key, leaf by leaf on the device
    (``lm_blocks.init_from_shapes``)."""
    return init_from_shapes(key, param_shapes(cfg), dtype)


def _kv_spec(cfg: Lfm2MoeConfig) -> layout.LayerSpec:
    """A ``full_attention`` layer's keys and values: heads of 64 lie two to
    a row of 128 lanes (``layout.kv_pack``)."""
    return layout.kv_layer(layout.FULL, kv_heads=cfg.num_key_value_heads,
                           head_dim=cfg.head_dim)


def cache_layout(cfg: Lfm2MoeConfig) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``), ONE kind a layer: a ``conv`` layer the
    convolution's last ``conv_L_cache - 1`` inputs (no positions, the
    cache's dtype), a ``full_attention`` layer keys and values of every
    position, in rows of whole lanes."""
    tail = layout.state_layer(
        conv=((cfg.hidden_size, cfg.conv_L_cache - 1), None))
    full = _kv_spec(cfg)
    return tuple(tail if kind == CONV else full for kind in cfg.layer_types)


# (B, L) of a program's tokens (L = 1: a decode step) -> the form the newest
# trace of a ``conv`` mixer on such tokens ran in (as
# ``falcon_h1.ssm_traced``); no entry for a model without such a layer.
_CONV_TRACED: dict = {}


def conv_traced(tokens_shape) -> Optional[str]:
    """``"causal"`` / ``"step"`` as the program traced in this process for
    tokens of this (B, L) has it; None where none was traced."""
    return _CONV_TRACED.get(tuple(tokens_shape))


# -- layers -------------------------------------------------------------
def _qkv(p, x, positions, cfg: Lfm2MoeConfig):
    """``x`` (B, L, d) -> q (B, L, KV, G, D), k, v (B, L, KV, D)."""
    q, k, v = qkv_heads(p, x, cfg.num_key_value_heads, cfg.groups,
                        cfg.head_dim)
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    return (attn_ops.rope(q, positions, cfg.rope_theta),
            attn_ops.rope(k, positions, cfg.rope_theta), v)


def conv_mixer_causal(layer, x, lengths, cfg: Lfm2MoeConfig):
    """A ``conv`` layer's first half over whole prompts: ``x`` (B, L, d) ->
    (``x + mixer(rms(x))``, the tail (B, d, K - 1) at each prompt's own
    length)."""
    p = layer["conv"]
    with jax.named_scope("conv.proj"):
        bcx = jnp.dot(rms_norm(x, layer["ln_in"], cfg.rms_norm_eps),
                      p["in_proj"])
    _CONV_TRACED[x.shape[:2]] = "causal"
    with jax.named_scope("conv.mix"):
        y, tail = ssm_ops.gated_conv_causal(bcx, p["conv_w"], lengths)
    with jax.named_scope("conv.out"):
        return x + jnp.dot(y, p["out_proj"]), tail


def conv_mixer_step(layer, x, tail, cfg: Lfm2MoeConfig):
    """One token a sequence: ``x`` (B, 1, d), the layer's ``conv`` leaf ->
    (``x + mixer(rms(x))``, the tail moved on by one)."""
    p = layer["conv"]
    with jax.named_scope("conv.proj"):
        bcx = jnp.dot(rms_norm(x[:, 0], layer["ln_in"], cfg.rms_norm_eps),
                      p["in_proj"])
    _CONV_TRACED[(x.shape[0], 1)] = "step"
    with jax.named_scope("conv.mix"):
        y, tail = ssm_ops.gated_conv_step(tail, bcx, p["conv_w"])
    with jax.named_scope("conv.out"):
        return x + jnp.dot(y, p["out_proj"])[:, None], tail


# -- prefill ------------------------------------------------------------
def _prefill_block(layer, kind, x, positions, lengths, cfg,
                   cache_len: Optional[int]):
    """One block over whole prompts, its mixer a gated short convolution or
    attention; -> (y, cache entry, chosen)."""
    if kind == CONV:
        h, tail = conv_mixer_causal(layer, x, lengths, cfg)
        entry = {"conv": tail}
    else:
        b, l = x.shape[:2]
        with jax.named_scope("attn.proj"):
            q, k, v = _qkv(layer["attn"],
                           rms_norm(x, layer["ln_in"], cfg.rms_norm_eps),
                           positions, cfg)
        with jax.named_scope("attn.core"):
            o = attn_ops.prefill_full(q, k, v)
        with jax.named_scope("attn.out"):
            h = x + jnp.dot(o.reshape(b, l, -1), layer["attn"]["wo"])
        entry = None
        if cache_len is not None:
            entry = kv_entry(_kv_spec(cfg), k, v, lengths, cache_len)
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def prefill_hidden(params, tokens, lengths, cfg: Lfm2MoeConfig,
                   cache_len: Optional[int] = None, active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (hidden (B,
    L, d) before the final norm, cache or None, routing).  Padded positions
    compute garbage no valid position ever sees: attention and the
    convolution are causal, and the tail is taken at ``lengths``."""
    return lm_blocks.prefill_stack(params, tokens, lengths, cfg.layer_types,
                                   _prefill_block, cfg, cache_len, active)


def prefill(params, tokens, lengths, cfg: Lfm2MoeConfig, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, a cache
    of ``cache_len`` positions in the attention layers and the convolution's
    tail AT EACH PROMPT'S OWN LENGTH in the others, routing)."""
    h, cache, routing = prefill_hidden(params, tokens, lengths, cfg, cache_len,
                                       active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


# -- decode -------------------------------------------------------------
def _decode_block(layer, kind, x, entry, positions, column, cfg):
    """One block over one token a sequence: an attention layer writes the
    token's key and value at its position before it attends; a ``conv``
    layer reads its tail, writes the token's ``u`` behind it.  -> (y, the
    entry, chosen)."""
    if kind == CONV:
        h, tail = conv_mixer_step(layer, x, entry["conv"], cfg)
        entry = {"conv": tail}
    else:
        with jax.named_scope("attn.proj"):
            q, k, v = _qkv(layer["attn"],
                           rms_norm(x, layer["ln_in"], cfg.rms_norm_eps),
                           column, cfg)
        o, entry = kv_decode(_kv_spec(cfg), q, k, v, entry, positions, column,
                             ("attn.core",))
        with jax.named_scope("attn.out"):
            h = x + jnp.dot(o.reshape(x.shape[0], 1, -1), layer["attn"]["wo"])
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def decode_step(params, cache, tokens, positions, cfg: Lfm2MoeConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing)."""
    return lm_blocks.decode_stack(params, cache, tokens, positions,
                                  cfg.layer_types, _decode_block, cfg, active)
