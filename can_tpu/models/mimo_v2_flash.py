"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``): a decoder whose WINDOW and
FULL attention layers differ in more than their mask.  As
``hybrid_layer_pattern`` says layer by layer (0 full, 1 window), a layer has
its own number of key/value heads (``num_key_value_heads`` /
``swa_num_key_value_heads``), its own rotary base (``rope_theta`` /
``swa_rope_theta``) and, in window layers, a learned SINK in the softmax;
every layer's keys are ``head_dim`` wide beside values of ``v_head_dim``,
rotary turns the first ``rotary_dim`` dimensions of a head only, and the
values carry a scale.  As ``moe_layer_freq`` says, a layer's feed-forward is
a dense SwiGLU or sparse experts WITHOUT a shared one; the head is untied.

Pure functions over a parameter tree, as the other language models; norm,
SwiGLU, the three projections by head, expert layer, routing report,
embedding, head and initialiser are ``models/lm_blocks.py``'s, attention
``ops/attention.py``'s:

* ``prefill(params, tokens, lengths, cfg, cache_len)`` -> (logits at each
  sequence's last position, cache, routing);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache,
  routing).

The block (``h`` (L, d), ``rms`` with ``layernorm_epsilon`` in float32):

    h = h + attn_i(rms(h, input_layernorm));  h = h + ffn_i(rms(h, post_attention_layernorm))
    logits = rms(h, norm) head

* attention of kind ``c``: ``q = x W_q`` (H heads of D), ``k = x W_k`` (KV_c
  heads of D), ``v = attention_value_scale * (x W_v)`` (KV_c heads of Dv);
  rotary (rotate-half, ``theta_c``) on dimensions ``0 .. rotary_dim - 1`` of
  every q and k head, the rest passed through; scores ``q . k / sqrt(D)``,
  causal, in a window layer also ``i - j < sliding_window``; a window
  layer's softmax has the head's sink in its denominator
  (``ops/attention.py::_softmax_av``); ``attn = concat_h(o) W_o`` (H x Dv ->
  d).  No bias, no q/k norm;
* sparse ``ffn``: sigmoid scores over ALL experts in float32, the top
  ``num_experts_per_tok`` of ``score + e_score_correction_bias`` chosen,
  their scores normalised (``ops/moe.py::route``), the sum over the chosen
  experts HELD HERE (``cfg.share``).

A launch's cache (``ops/cache_layout.py``): a full layer keeps keys and
values of every position, a window layer a ring of ``sliding_window``; the
number of heads is the layer kind's, and keys 192 wide lie two heads to a row
of 384 = 3 lane rows beside values of 128 a head a row (``kv_pack``: each
leaf's row is whole lanes, no lane is padding; ``write_slot``,
``as_leaf`` / ``ring_entry`` and ``decode`` read it from the leaves' shapes).

A full layer's prefill is one causal attention over groups in two forms of
one algorithm: the fused kernel ``ops/pallas_attention.py::fused_causal``
(keys of 192 beside values of 128, 16 query heads to a key head) where its
``supports`` says it can run (a TPU, a bucket of whole blocks), the scanned
``prefill_causal`` everywhere else (it clamps its block to the bucket).
Nothing else chooses; ``attention_traced`` says which a traced prefill took.

What the published config leaves open is ONE choice each, named in
``ASSUMED`` (a configuration file states them under ``assumed``;
``from_dict`` refuses another value).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from can_tpu.models import lm_blocks
from can_tpu.models.lm_blocks import experts_form  # noqa: F401  (the serving path asks the model for it)
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes, ffn,
                                      init_from_shapes, kv_decode, kv_entry,
                                      last_hidden, lm_head, qkv_heads,
                                      rms_norm, scoped)
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import pallas_attention as fused_attn
from can_tpu.ops.moe import ExpertShare

ASSUMED = {"norm": "rmsnorm", "qk_norm": False, "rope_pairing": "rotate_half",
           "rotary_dims": "first", "softmax_scale": "1/sqrt(head_dim)",
           "sink": "denominator", "value_scale_on": "v",
           "attention_chunk_size": "kernel_tile", "mtp_layers": 0}

# what the published model's switches have to say for this module to be it
_PUBLISHED = {"attention_bias": False, "add_full_attention_sink_bias": False,
              "tie_word_embeddings": False, "hidden_act": "silu",
              "scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "n_group": 1, "topk_group": 1}


@dataclasses.dataclass(frozen=True)
class MimoV2FlashConfig:
    hidden_size: int
    num_attention_heads: int
    kv_heads_full: int
    kv_heads_window: int
    head_dim: int
    v_head_dim: int
    rotary_dim: int
    rope_theta: float
    swa_rope_theta: float
    sliding_window: int
    attention_value_scale: float
    window_sink: bool
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    window_layers: Tuple[bool, ...]     # of the layers held: window (else full)
    sparse_layers: Tuple[bool, ...]     # of the layers held: experts (else dense)
    share: ExpertShare
    vocab: VocabSlice

    def kv_heads(self, window: bool) -> int:
        return self.kv_heads_window if window else self.kv_heads_full

    def theta(self, window: bool) -> float:
        return self.swa_rope_theta if window else self.rope_theta

    @classmethod
    def from_dict(cls, d: dict) -> "MimoV2FlashConfig":
        """From a configuration file: the published ``config.json`` keys with
        the cut applied (``num_hidden_layers`` kept, ``n_routed_experts`` and
        ``vocab_size`` HELD), ``published`` for the uncut counts,
        ``deployment`` for the rank, ``assumed`` for what the config leaves
        open.  ``hybrid_layer_pattern`` and ``moe_layer_freq`` are read layer
        by layer, their first ``num_hidden_layers`` entries: any pattern."""
        for name, only in _PUBLISHED.items():
            if d.get(name, only) != only:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only {only!r})")
        if d.get("n_shared_experts"):
            raise ValueError("a shared expert is not implemented "
                             "(n_shared_experts must be null)")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        for mine, both in (("swa_num_attention_heads", "num_attention_heads"),
                           ("swa_head_dim", "head_dim"),
                           ("swa_v_head_dim", "v_head_dim"),
                           ("sliding_window_size", "sliding_window")):
            if d.get(mine, d[both]) != d[both]:
                raise ValueError(f"{mine} {d[mine]!r} differs from {both} "
                                 f"{d[both]!r}: not implemented")
        n = int(d["num_hidden_layers"])
        pattern, freq = d["hybrid_layer_pattern"], d["moe_layer_freq"]
        if len(pattern) < n or len(freq) < n:
            raise ValueError(f"hybrid_layer_pattern names {len(pattern)} layers "
                             f"and moe_layer_freq {len(freq)}, "
                             f"num_hidden_layers is {n}")
        if set(pattern) - {0, 1} or set(freq) - {0, 1}:
            raise ValueError("hybrid_layer_pattern and moe_layer_freq hold "
                             "0 and 1 only")
        heads, hd = int(d["num_attention_heads"]), int(d["head_dim"])
        kv_full = int(d["num_key_value_heads"])
        kv_window = int(d["swa_num_key_value_heads"])
        if heads % kv_full or heads % kv_window:
            raise ValueError("heads do not divide into their groups")
        rotary = int(ass.get("rotary_dim", int(hd * float(d["partial_rotary_factor"]))))
        if rotary % 2 or not 0 < rotary <= hd:
            raise ValueError(f"rotary_dim {rotary} of a head of {hd}")
        pub = d.get("published", {})
        rank = int(d.get("deployment", {}).get("rank", 0))
        held_e = int(d["n_routed_experts"])
        held_v = int(d["vocab_size"])
        scale = d.get("routed_scaling_factor")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=heads, kv_heads_full=kv_full,
            kv_heads_window=kv_window, head_dim=hd,
            v_head_dim=int(d["v_head_dim"]), rotary_dim=rotary,
            rope_theta=float(d["rope_theta"]),
            swa_rope_theta=float(d["swa_rope_theta"]),
            sliding_window=int(d["sliding_window"]),
            attention_value_scale=float(d["attention_value_scale"]),
            window_sink=bool(d["add_swa_attention_sink_bias"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            routed_scaling_factor=1.0 if scale is None else float(scale),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            rms_norm_eps=float(d["layernorm_epsilon"]),
            window_layers=tuple(bool(x) for x in pattern[:n]),
            sparse_layers=tuple(bool(x) for x in freq[:n]),
            share=ExpertShare(rank * held_e, held_e,
                              int(pub.get("n_routed_experts", held_e))),
            vocab=VocabSlice(rank * held_v, held_v,
                             int(pub.get("vocab_size", held_v))),
        )


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: MimoV2FlashConfig) -> dict:
    """The tree of shapes (tuples).  ``x @ w`` everywhere; ``sink`` (H,) in
    the window layers where the configuration gives them one; ``bias`` leaves
    are float32 buffers."""
    d, h = cfg.hidden_size, cfg.num_attention_heads

    def mlp(width):
        return {"gate": (d, width), "up": (d, width), "down": (width, d)}

    def block(window, sparse):
        kv = cfg.kv_heads(window)
        attn = {"wq": (d, h * cfg.head_dim), "wk": (d, kv * cfg.head_dim),
                "wv": (d, kv * cfg.v_head_dim), "wo": (h * cfg.v_head_dim, d)}
        if window and cfg.window_sink:
            attn["sink"] = (h,)
        out = {"ln_in": (d,), "ln_post": (d,), "attn": attn}
        if sparse:
            f, e = cfg.moe_intermediate_size, cfg.share.held
            out["moe"] = {"router": (d, cfg.share.total),
                          "bias": (cfg.share.total,),
                          "experts": {"gate": (e, d, f), "up": (e, d, f),
                                      "down": (e, f, d)}}
        else:
            out["mlp"] = mlp(cfg.intermediate_size)
        return out

    return {"embed": (cfg.vocab.held, d),
            "layers": [block(w, s) for w, s in zip(cfg.window_layers,
                                                   cfg.sparse_layers)],
            "final_norm": (d,), "head": (d, cfg.vocab.held)}


def param_count(cfg: MimoV2FlashConfig) -> int:
    return count_shapes(param_shapes(cfg))


def init_params(key, cfg: MimoV2FlashConfig, dtype=jnp.bfloat16):
    """Parameters from a key, leaf by leaf on the device
    (``lm_blocks.init_from_shapes``).  A sink is drawn N(ln(sliding_window),
    1), as the benchmark's own weights draw it: beside a full window of keys
    whose seeded scores are N(0, 1) a sink of N(0, 1) takes 1 / window of a
    head's mass and leaving it out moves nothing; about ln(window) it takes
    a third to a half.  The mean is this repo's choice: no published
    checkpoint's sinks were read."""
    params = init_from_shapes(key, param_shapes(cfg), dtype)
    shift = math.log(cfg.sliding_window)
    for layer in params["layers"]:
        if "sink" in layer["attn"]:
            sink = layer["attn"]["sink"].astype(jnp.float32) + shift
            layer["attn"]["sink"] = sink.astype(dtype)
    return params


def _kv_spec(cfg: MimoV2FlashConfig, window: bool) -> layout.LayerSpec:
    return layout.kv_layer(layout.RING if window else layout.FULL,
                           kv_heads=cfg.kv_heads(window),
                           head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim,
                           window=cfg.sliding_window)


def cache_layout(cfg: MimoV2FlashConfig) -> tuple:
    """What each held layer keeps in a launch's cache: a window layer a ring
    of ``sliding_window`` positions of ITS key/value heads, a full layer
    every position of its own (fewer); keys and values each in rows of whole
    lanes (``ops/cache_layout.py::kv_pack``)."""
    return tuple(_kv_spec(cfg, w) for w in cfg.window_layers)


# -- layers -------------------------------------------------------------
def _rotary(x, positions, theta: float, rotary_dim: int):
    """Rotary embedding on the first ``rotary_dim`` dimensions of every
    head, the rest passed through."""
    if rotary_dim == x.shape[-1]:
        return attn_ops.rope(x, positions, theta)
    return jnp.concatenate(
        [attn_ops.rope(x[..., :rotary_dim], positions, theta),
         x[..., rotary_dim:]], axis=-1)


def _qkv(p, x, positions, window: bool, cfg: MimoV2FlashConfig):
    """``x`` (B, L, d) -> q (B, L, KV, G, D), k (B, L, KV, D), v (B, L, KV,
    Dv) of a layer of this kind: its heads, its theta, the scaled values."""
    kv = cfg.kv_heads(window)
    q, k, v = qkv_heads(p, x, kv, cfg.num_attention_heads // kv, cfg.head_dim,
                        cfg.v_head_dim)
    theta = cfg.theta(window)
    v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(v.dtype)
    return (_rotary(q, positions, theta, cfg.rotary_dim),
            _rotary(k, positions, theta, cfg.rotary_dim), v)


def _sink(p, window: bool, cfg: MimoV2FlashConfig):
    """A window layer's sinks as (KV, G), None where the layer has none."""
    if "sink" not in p:
        return None
    kv = cfg.kv_heads(window)
    return p["sink"].reshape(kv, cfg.num_attention_heads // kv)


def _core(window: bool) -> tuple:
    """The scopes of a layer's attention (``lm_blocks.scoped``):
    ``attn.core`` and, for a window layer, ``attn.window`` inside it: the
    family ``attn.`` holds both, ``attn.core`` alone is the full layers'."""
    return ("attn.core", "attn.window") if window else ("attn.core",)


# -- prefill ------------------------------------------------------------
# (B, L) of a prefill's prompts -> the form the newest trace of a full
# layer on such prompts ran its attention in (as
# ``glm_moe_lite._ATTENTION_TRACED``: written while a program is traced, read
# after its launch by whoever reports what the program does)
_ATTENTION_TRACED: dict = {}


def attention_traced(tokens_shape) -> Optional[str]:
    """``"fused"`` / ``"scanned"`` as the prefill traced in this process for
    prompts of this (B, L) has it; None where none was traced."""
    return _ATTENTION_TRACED.get(tuple(tokens_shape))


def _full_prefill(q, k, v, lengths):
    """A full layer's causal attention over whole prompts, a group's queries
    against the key head they share: the fused kernel where its ``supports``
    says it can run, the scanned ``prefill_causal`` elsewhere."""
    b, l, kv, g, d = q.shape
    q = q.reshape(b, l, kv * g, d)
    fused = fused_attn.supports(q.shape, v.shape, q.dtype)
    _ATTENTION_TRACED[(b, l)] = "fused" if fused else "scanned"
    causal = fused_attn.fused_causal if fused else attn_ops.prefill_causal
    return causal(q, k, v, lengths).reshape(b, l, kv, g, v.shape[-1])


def _prefill_block(layer, window, x, positions, lengths, cfg,
                   cache_len: Optional[int]):
    """One block over whole prompts; -> (y, cache entry or None, chosen)."""
    b, l = x.shape[:2]
    p = layer["attn"]
    with jax.named_scope("attn.proj"):
        q, k, v = _qkv(p, rms_norm(x, layer["ln_in"], cfg.rms_norm_eps),
                       positions, window, cfg)
    with scoped(*_core(window)):
        if window:
            o = attn_ops.prefill_window(q, k, v, window=cfg.sliding_window,
                                        sink=_sink(p, window, cfg))
        else:
            o = _full_prefill(q, k, v, lengths)
    with jax.named_scope("attn.out"):
        h = x + jnp.dot(o.reshape(b, l, -1), p["wo"])
    entry = None
    if cache_len is not None:
        entry = kv_entry(_kv_spec(cfg, window), k, v, lengths, cache_len)
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def prefill_hidden(params, tokens, lengths, cfg: MimoV2FlashConfig,
                   cache_len: Optional[int] = None, active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (hidden (B,
    L, d) before the final norm, cache or None, routing).  Padded positions
    compute garbage no valid position ever sees (attention is causal)."""
    return lm_blocks.prefill_stack(params, tokens, lengths, cfg.window_layers,
                                   _prefill_block, cfg, cache_len, active)


def prefill(params, tokens, lengths, cfg: MimoV2FlashConfig, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, cache,
    routing).  The cache holds ``cache_len`` positions in full layers and
    ``cfg.sliding_window`` in window layers."""
    h, cache, routing = prefill_hidden(params, tokens, lengths, cfg, cache_len,
                                       active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


# -- decode -------------------------------------------------------------
def _decode_block(layer, window, x, entry, positions, column, cfg):
    """One block over one token a sequence, its key and value written into
    ``entry`` (its ring slot in a window layer) before it attends; -> (y,
    the entry, chosen)."""
    p = layer["attn"]
    with jax.named_scope("attn.proj"):
        q, k, v = _qkv(p, rms_norm(x, layer["ln_in"], cfg.rms_norm_eps),
                       column, window, cfg)
    o, entry = kv_decode(_kv_spec(cfg, window), q, k, v, entry, positions,
                         column, _core(window), p.get("sink"))
    with jax.named_scope("attn.out"):
        h = x + jnp.dot(o.reshape(x.shape[0], 1, -1), p["wo"])
    y, chosen = ffn(layer, h, cfg)
    return y, entry, chosen


def decode_step(params, cache, tokens, positions, cfg: MimoV2FlashConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing)."""
    return lm_blocks.decode_stack(params, cache, tokens, positions,
                                  cfg.window_layers, _decode_block, cfg,
                                  active)
