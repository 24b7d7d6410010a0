"""Brumby (``model_type`` ``brumby``; Brumby-14B-Base): Qwen3's dense decoder
with every softmax attention replaced by POWER RETENTION (``ops/retention.py``;
Manifest AI, arXiv:2507.04239): a gated linear attention whose kernel is the
square of the scaled dot product.  What a layer keeps of a sequence is a
float32 MATRIX STATE a key head, the same size whatever the context: no
layer of this model has keys, values or positions in its cache.

Pure functions over a parameter tree, as ``models/lfm2_moe.py``; norm, SwiGLU,
embedding, head, the three projections by head, the stack's skeleton and the
initialiser are ``models/lm_blocks.py``'s:

* ``prefill(params, tokens, lengths, cfg, cache_len)`` -> (logits at each
  sequence's last position, cache, routing: none);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache,
  routing: none).

The block (``h`` (L, d), ``rms`` with ``rms_norm_eps`` in float32; key head
``j``, a query head ``h`` of its group):

    x' = rms(x, ln_in)
    q_h = rope(rms_head(x' Wq)_h)    k_j = rope(rms_head(x' Wk)_j)    v_j = (x' Wv)_j
    log g_j = log_sigmoid((x' Wg)_j)           one gate a key head, float32
    y_h = power retention of (q_h, k_j, v_j, log g_j)
    h1 = x + concat_h(y_h) Wo;    out = h1 + swiglu(rms(h1, ln_post))

Two forms of one layer: chunked over whole prompts (prefill), one step from
the cached state (decode; on a TPU at the published head width the step is
ONE Pallas kernel a layer, ``ops/pallas_retention.py``, that reads and writes
each (slot, key head) state once, where it lies; elsewhere the plain form,
its oracle: ``ops/retention.py::step_form`` says which, from the backend and
the shapes, and ``retention_traced`` reports it).  A launch's cache
(``ops/cache_layout.py``) holds ONE kind, ``state``, in every layer: ``S``
(slots, key heads, head_dim, rows) and ``z`` (slots, key heads, rows), both
float32, ``rows`` = ``retention.state_rows(head_dim)`` (8,320 for heads of
128) in the LANES (the layout the step's kernel streams: ``ops/retention.py``),
written as they stand at each prompt's own length.

The published ``config.json`` carries Qwen3's keys only.  What it leaves
open is ONE choice each, named in ``ASSUMED`` (a configuration file states
them under ``assumed``; ``from_dict`` refuses another value).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from can_tpu.models import lm_blocks
from can_tpu.models.lm_blocks import (VocabSlice, count_shapes,
                                      init_from_shapes, last_hidden, lm_head,
                                      qkv_heads, rms_norm, swiglu)
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import retention as ret_ops

ASSUMED = {
    "power_degree": 2,                       # the kernel is the SQUARE
    "gate": "log_sigmoid_per_key_head",      # bias-free, one number a key head
    "qk_norm": "rms_per_head",               # Qwen3's
    "rope_pairing": "rotate_half",           # over the whole head
    "score_scale": "inv_sqrt_head_dim_inside_power",
    "normaliser": "sum_of_weights",
    "state_dtype": "float32",                # in the cache and in both forms
    "state_rows": "symmetric_by_offset",     # (head_dim / 2 + 1) head_dim rows
}

# what the published model's switches have to say for this module to be it
_PUBLISHED = {"attention_bias": False, "tie_word_embeddings": False,
              "use_sliding_window": False}

# where a prefill slice's states are placed into the launch's cache
# (``serve/programs.py`` asks; a model that says nothing has ``attn.cache``)
CACHE_PART = "ret.state"

# The constant channel of a seeded model.  The gate has no bias, so a gate
# near one needs a direction that every position's ``x'`` shares, as a
# trained model's residual stream has: ``init_params`` gives channel 0 of
# every embedding row this value and each key head's gate a weight on it
GATE_ANCHOR = 8.0


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rms_norm_eps: float
    rope_theta: float
    vocab: VocabSlice

    @property
    def groups(self) -> int:
        """Query heads to a key head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def layer_kinds(self) -> tuple:
        """One label a layer for ``lm_blocks``' skeleton: all of one kind."""
        return ("retention",) * self.num_hidden_layers

    @classmethod
    def from_dict(cls, d: dict) -> "BrumbyConfig":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied, ``published`` for the uncut counts,
        ``assumed`` for what the config leaves open."""
        for name, only in _PUBLISHED.items():
            if bool(d.get(name, only)) != only:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only {only!r})")
        for name in ("sliding_window", "rope_scaling"):
            if d.get(name) is not None:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only null)")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        heads, kv = int(d["num_attention_heads"]), int(d["num_key_value_heads"])
        if heads % kv:
            raise ValueError("heads do not divide into their groups")
        vocab = int(d["vocab_size"])
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_hidden_layers=int(d["num_hidden_layers"]),
            num_attention_heads=heads, num_key_value_heads=kv,
            head_dim=int(d["head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            vocab=VocabSlice(0, vocab, int(d.get("published", {}).get(
                "vocab_size", vocab))),
        )


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: BrumbyConfig) -> dict:
    """The tree of shapes (tuples).  ``x @ w`` everywhere; ``wg`` is the
    gate's projection, one column a key head."""
    d, hd, f = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    qd, kd = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    block = {"ln_in": (d,), "ln_post": (d,),
             "ret": {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd),
                     "wg": (d, cfg.num_key_value_heads), "wo": (qd, d),
                     "q_norm": (hd,), "k_norm": (hd,)},
             "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}
    return {"embed": (cfg.vocab.held, d),
            "layers": [block] * cfg.num_hidden_layers,
            "final_norm": (d,), "head": (d, cfg.vocab.held)}


def param_count(cfg: BrumbyConfig) -> int:
    return count_shapes(param_shapes(cfg))


def init_params(key, cfg: BrumbyConfig, dtype=jnp.bfloat16):
    """Parameters from a key (``lm_blocks.init_from_shapes``), then the
    gates seeded so that ``g`` lies near one (``GATE_ANCHOR``): channel 0 of
    every embedding row is the anchor, and key head ``j``'s gate weighs it so
    that ``sigmoid`` of the anchor's part is uniform in logit between 0.9
    and 0.999 over the heads; the other rows of ``wg`` a quarter of a
    projection's."""
    params = init_from_shapes(key, param_shapes(cfg), dtype)
    params["embed"] = params["embed"].at[:, 0].set(GATE_ANCHOR)
    kv = cfg.num_key_value_heads
    for i, layer in enumerate(params["layers"]):
        aim = jax.random.uniform(jax.random.fold_in(key, 1000 + i), (kv,),
                                 jnp.float32, 2.2, 6.9)
        wg = (0.25 * layer["ret"]["wg"].astype(jnp.float32)
              ).at[0].set(aim / GATE_ANCHOR)
        params["layers"][i] = dict(layer, ret=dict(layer["ret"],
                                                   wg=wg.astype(dtype)))
    return params


def cache_layout(cfg: BrumbyConfig) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``): the retention's state alone, no positions."""
    rows = ret_ops.state_rows(cfg.head_dim)
    kv = cfg.num_key_value_heads
    return (layout.state_layer(
        S=((kv, cfg.head_dim, rows), ASSUMED["state_dtype"]),
        z=((kv, rows), ASSUMED["state_dtype"])),) * cfg.num_hidden_layers


# (B, L) of a program's tokens (L = 1: a decode step) -> the form the newest
# trace of a retention layer on such tokens ran in (as
# ``falcon_h1.ssm_traced``).
_RETENTION_TRACED: dict = {}


def retention_traced(tokens_shape) -> Optional[str]:
    """``"chunked"`` / ``"step"`` / ``"fused"`` (the step as one kernel:
    ``ops/retention.py::step_form``) as the program traced in this process
    for tokens of this (B, L) has it; None where none was traced."""
    return _RETENTION_TRACED.get(tuple(tokens_shape))


# -- layers -------------------------------------------------------------
def _inputs(layer, x, positions, cfg: BrumbyConfig):
    """``x`` (B, L, d) -> q (B, L, KV, G, D), k, v (B, L, KV, D), log g
    (B, L, KV) float32."""
    p = layer["ret"]
    u = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
    q, k, v = qkv_heads(p, u, cfg.num_key_value_heads, cfg.groups,
                        cfg.head_dim)
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    log_g = jax.nn.log_sigmoid(jnp.dot(u, p["wg"],
                                       preferred_element_type=jnp.float32))
    return (attn_ops.rope(q, positions, cfg.rope_theta),
            attn_ops.rope(k, positions, cfg.rope_theta), v, log_g)


def _mlp(layer, h, cfg: BrumbyConfig):
    with jax.named_scope("dense_mlp"):
        return h + swiglu(rms_norm(h, layer["ln_post"], cfg.rms_norm_eps),
                          layer["mlp"])


def _prefill_block(layer, kind, x, positions, lengths, cfg, cache_len):
    """One block over whole prompts; -> (y, the state at each prompt's own
    length, no routing)."""
    b, l = x.shape[:2]
    with jax.named_scope("ret.proj"):
        q, k, v, log_g = _inputs(layer, x, positions, cfg)
    _RETENTION_TRACED[(b, l)] = "chunked"
    y, S, z = ret_ops.power_retention_chunked(q, k, v, log_g, lengths,
                                              chunk=ret_ops.CHUNK)
    with jax.named_scope("ret.out"):
        h = x + jnp.dot(y.reshape(b, l, -1), layer["ret"]["wo"])
    entry = None if cache_len is None else {"S": S, "z": z}
    return _mlp(layer, h, cfg), entry, None


def _decode_block(layer, kind, x, entry, positions, column, cfg, *, active):
    """One block over one token a sequence: the state read, moved on by the
    token and written; -> (y, the entry, no routing)."""
    b = x.shape[0]
    with jax.named_scope("ret.proj"):
        q, k, v, log_g = _inputs(layer, x, column, cfg)
    _RETENTION_TRACED[(b, 1)] = ret_ops.step_form(entry["S"], q[:, 0])
    y, S, z = ret_ops.power_retention_step(entry["S"], entry["z"], q[:, 0],
                                           k[:, 0], v[:, 0], log_g[:, 0],
                                           active)
    with jax.named_scope("ret.out"):
        h = x + jnp.dot(y.reshape(b, 1, -1), layer["ret"]["wo"])
    return _mlp(layer, h, cfg), {"S": S, "z": z}, None


# -- prefill ------------------------------------------------------------
def prefill_hidden(params, tokens, lengths, cfg: BrumbyConfig,
                   cache_len: Optional[int] = None, active=None):
    """``lm_blocks.prefill_stack`` over ``_prefill_block``: -> (hidden (B,
    L, d) before the final norm, cache or None, no routing).  Padded
    positions compute garbage no valid position ever sees: retention is
    causal, and the state does not advance over them."""
    return lm_blocks.prefill_stack(params, tokens, lengths, cfg.layer_kinds,
                                   _prefill_block, cfg, cache_len, active)


def prefill(params, tokens, lengths, cfg: BrumbyConfig, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, every
    layer's state AT EACH PROMPT'S OWN LENGTH, no routing).  ``cache_len``
    is the serving programs' (positions of context): a state has none."""
    h, cache, routing = prefill_hidden(params, tokens, lengths, cfg, cache_len,
                                       active)
    return lm_head(params, last_hidden(h, lengths), cfg), cache, routing


# -- decode -------------------------------------------------------------
def decode_step(params, cache, tokens, positions, cfg: BrumbyConfig,
                active=None):
    """``lm_blocks.decode_stack`` over ``_decode_block``: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, no routing).  The slots ``active`` (B,) marks False keep their
    state."""
    return lm_blocks.decode_stack(
        params, cache, tokens, positions, cfg.layer_kinds,
        functools.partial(_decode_block, active=active), cfg, active)
