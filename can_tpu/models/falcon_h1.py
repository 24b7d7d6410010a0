"""Falcon-H1 (``model_type`` ``falcon_h1``): a dense decoder whose every block
runs a Mamba-2 mixer and grouped-query attention SIDE BY SIDE on the same
normed input, then a SwiGLU; every branch carries a multiplier of the
configuration (maximal-update parametrisation).

Pure functions over a parameter tree, as ``models/exaone_moe.py``; norm,
SwiGLU, head and initialiser are ``models/lm_blocks.py``'s:

* ``prefill(params, tokens, lengths, cfg, cache_len)`` -> (logits at each
  sequence's last position, cache, routing: none);
* ``decode_step(params, cache, tokens, positions, cfg)`` -> (logits, cache,
  routing: none).

The block, as the published ``config.json`` and model code state it (``h``
(L, d); every multiplier is a key of the config):

    u = RMSNorm_in(h)
    h = h + ssm_out_multiplier * Mixer(u * ssm_in_multiplier)
          + attention_out_multiplier * Attn(u * attention_in_multiplier)
    h = h + MLP(RMSNorm_ff(h))

* attention: grouped-query, no bias, ``k = k * key_multiplier``, rotary
  embedding over the whole head, scale ``1 / sqrt(head_dim)``, causal;
* MLP: ``down(up(x) * silu(gate(x) * mlp_multipliers[0])) *
  mlp_multipliers[1]``;
* mixer (``ops/ssm.py``): ``[z | xBC | dt] = (x W_in) * mup`` (``mup`` scales
  the column groups z, x, B, C, dt by ``ssm_multipliers``); ``xBC =
  silu(conv1d_causal(xBC))``, split into ``x`` (heads), ``B``, ``C``
  (groups); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
  recurrence; ``y = y * silu(z)`` RMS-normalised within each group, times
  its weight; ``W_out``.  Two forms of one layer: chunked over whole prompts
  (prefill), one step from the cached state (decode).

A layer's cache entry holds two kinds (``ops/cache_layout.py``): ``full``
keys and values per position, and a ``state`` without positions: the
mixer's state ``ssm`` (slots, heads, head_dim, d_state) in FLOAT32 and the
convolution's last ``d_conv - 1`` inputs ``conv`` (slots, channels, d_conv -
1).  Prompts are right-padded; both are written as they stand at each
prompt's own length.

What the published config leaves open is ONE choice each, named in
``ASSUMED`` (a configuration file states them under ``assumed``;
``from_dict`` refuses another value): the gated norm is per group
(Mamba-2's ``RMSNormGated`` with ``group_size = d_ssm / n_groups``), the
rotary pairing is rotate-half, ``dt`` is not clamped, and the recurrent
state is float32 in the cache and in both forms (the published code lets
the cache take the model's dtype: a bfloat16 state is rounded at every
decode step, an accumulator in a lower precision than the one stated).
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from can_tpu.models.lm_blocks import (VocabSlice, count_shapes, embed,
                                      init_from_shapes, kv_decode, kv_entry,
                                      last_hidden, lm_head, no_routing,
                                      rms_norm, swiglu)
from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import ssm as ssm_ops

ASSUMED = {"gated_norm": "per_group", "rope_pairing": "rotate_half",
           "dt_limit": "none", "state_dtype": "float32",
           "conv_tail_dtype": "activations"}

# what the published model's booleans have to say for this module to be it
_PUBLISHED = {"attention_bias": False, "mlp_bias": False,
              "projectors_bias": False, "mamba_proj_bias": False,
              "mamba_conv_bias": True, "mamba_rms_norm": True,
              "mamba_norm_before_gate": False, "mamba_use_mlp": True,
              "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_chunk_size: int
    rms_norm_eps: float
    rope_theta: float
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Tuple[float, ...]     # of the columns z, x, B, C, dt
    mlp_multipliers: Tuple[float, float]   # of the gate, of the output
    vocab: VocabSlice

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def groups(self) -> int:
        """Query heads to a key/value head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    @classmethod
    def from_dict(cls, d: dict) -> "FalconH1Config":
        """From a configuration file: the published ``config.json`` keys
        with the cut applied (``num_hidden_layers`` kept), ``published`` for
        the uncut counts, ``assumed`` for what the config leaves open."""
        for name, only in _PUBLISHED.items():
            if bool(d.get(name, only)) != only:
                raise ValueError(f"{name} {d[name]!r} is not implemented "
                                 f"(only {only!r})")
        if d.get("attn_layer_indices") is not None:
            raise ValueError("attention in some layers only is not "
                             "implemented (attn_layer_indices must be null)")
        if d.get("rope_scaling") is not None:
            raise ValueError("rotary scaling is not implemented "
                             "(rope_scaling must be null)")
        ass = d.get("assumed", {})
        for name, only in ASSUMED.items():
            if ass.get(name, only) != only:
                raise ValueError(f"{name} {ass[name]!r} is not implemented "
                                 f"(only {only!r})")
        heads, head = int(d["mamba_n_heads"]), int(d["mamba_d_head"])
        if heads * head != int(d["mamba_d_ssm"]):
            raise ValueError(f"mamba_d_ssm {d['mamba_d_ssm']} is not "
                             f"mamba_n_heads x mamba_d_head ({heads} x {head})")
        if heads % int(d["mamba_n_groups"]) or (
                int(d["num_attention_heads"]) % int(d["num_key_value_heads"])):
            raise ValueError("heads do not divide into their groups")
        held_v = int(d["vocab_size"])
        tot_v = int(d.get("published", {}).get("vocab_size", held_v))
        rank = int(d.get("deployment", {}).get("rank", 0))
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_hidden_layers=int(d["num_hidden_layers"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            mamba_d_ssm=int(d["mamba_d_ssm"]), mamba_n_heads=heads,
            mamba_d_head=head, mamba_d_state=int(d["mamba_d_state"]),
            mamba_n_groups=int(d["mamba_n_groups"]),
            mamba_d_conv=int(d["mamba_d_conv"]),
            mamba_chunk_size=int(d["mamba_chunk_size"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            rope_theta=float(d["rope_theta"]),
            embedding_multiplier=float(d["embedding_multiplier"]),
            lm_head_multiplier=float(d["lm_head_multiplier"]),
            attention_in_multiplier=float(d["attention_in_multiplier"]),
            attention_out_multiplier=float(d["attention_out_multiplier"]),
            key_multiplier=float(d["key_multiplier"]),
            ssm_in_multiplier=float(d["ssm_in_multiplier"]),
            ssm_out_multiplier=float(d["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(m) for m in d["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in d["mlp_multipliers"]),
            vocab=VocabSlice(rank * held_v, held_v, tot_v),
        )

    @classmethod
    def from_file(cls, path: str) -> "FalconH1Config":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# -- parameters ---------------------------------------------------------
def param_shapes(cfg: FalconH1Config) -> dict:
    """The tree of shapes (tuples).  ``x @ w`` everywhere; ``in_proj``'s
    columns are ``[z | x | B | C | dt]``, the convolution's channels ``[x | B
    | C]``, its weight (channels, d_conv) with the current position last."""
    d = cfg.hidden_size
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    hm = cfg.mamba_n_heads
    block = {
        "ln_in": (d,), "ln_post": (d,),
        "attn": {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                 "wo": (h * hd, d)},
        "mixer": {"in_proj": (d, cfg.in_proj_dim),
                  "conv_w": (cfg.conv_dim, cfg.mamba_d_conv),
                  "conv_b": (cfg.conv_dim,),
                  "A_log": (hm,), "D": (hm,), "dt_bias": (hm,),
                  "gate_norm": (cfg.mamba_d_ssm,),
                  "out_proj": (cfg.mamba_d_ssm, d)},
        "mlp": {"gate": (d, cfg.intermediate_size),
                "up": (d, cfg.intermediate_size),
                "down": (cfg.intermediate_size, d)},
    }
    return {"embed": (cfg.vocab.held, d),
            "layers": [block] * cfg.num_layers,
            "final_norm": (d,), "head": (d, cfg.vocab.held)}


def param_count(cfg: FalconH1Config) -> int:
    return count_shapes(param_shapes(cfg))


def mup_vector(cfg: FalconH1Config):
    """``ssm_multipliers`` spread over ``in_proj``'s columns (float32)."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


def init_params(key, cfg: FalconH1Config, dtype=jnp.bfloat16):
    """Parameters from a key (``lm_blocks.init_from_shapes``), then every
    projection divided by the multiplier that follows it, so that each
    branch is of order one AFTER its multiplier (the published ones would
    otherwise leave mixer, attention and logits vanishing beside the
    residual), and the mixer's ``A_log``, ``dt_bias``, ``D``, convolution
    from Mamba-2's ranges (A in [1, 16], dt in [1e-3, 1e-1], D near 1)."""
    shapes = param_shapes(cfg)
    own = ("conv_w", "conv_b", "A_log", "D", "dt_bias")   # drawn below
    shapes["layers"] = [
        dict(block, mixer={k: v for k, v in block["mixer"].items()
                           if k not in own}) for block in shapes["layers"]]
    params = init_from_shapes(key, shapes, dtype)

    def over(w, m):
        return (w.astype(jnp.float32) / m).astype(dtype)

    mg, md = cfg.mlp_multipliers
    mup = cfg.ssm_in_multiplier * mup_vector(cfg)
    params["embed"] = over(params["embed"], cfg.embedding_multiplier)
    params["head"] = over(params["head"], cfg.lm_head_multiplier)
    hm = cfg.mamba_n_heads
    for i, layer in enumerate(params["layers"]):
        layer = dict(layer)
        a, m, f = dict(layer["attn"]), dict(layer["mixer"]), dict(layer["mlp"])
        a["wq"] = over(a["wq"], cfg.attention_in_multiplier)
        a["wk"] = over(a["wk"], cfg.attention_in_multiplier * cfg.key_multiplier)
        a["wv"] = over(a["wv"], cfg.attention_in_multiplier)
        a["wo"] = over(a["wo"], cfg.attention_out_multiplier)
        f["gate"], f["down"] = over(f["gate"], mg), over(f["down"], md)
        m["in_proj"] = over(m["in_proj"], mup)
        m["out_proj"] = over(m["out_proj"], cfg.ssm_out_multiplier)
        ka, kd, kD, kb, kw = jax.random.split(
            jax.random.fold_in(key, 1000 + i), 5)
        m["conv_w"] = (jax.random.normal(kw, (cfg.conv_dim, cfg.mamba_d_conv),
                                         jnp.float32)
                       * cfg.mamba_d_conv ** -0.5).astype(dtype)
        m["A_log"] = jnp.log(jax.random.uniform(ka, (hm,), jnp.float32, 1.0,
                                                16.0)).astype(dtype)
        dt = jnp.exp(jax.random.uniform(kd, (hm,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        m["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        m["D"] = (1.0 + 0.1 * jax.random.normal(kD, (hm,), jnp.float32)
                  ).astype(dtype)
        m["conv_b"] = (0.1 * jax.random.normal(kb, (cfg.conv_dim,),
                                               jnp.float32)).astype(dtype)
        layer["attn"], layer["mixer"], layer["mlp"] = a, m, f
        params["layers"][i] = layer
    return params


def _kv_spec(cfg: FalconH1Config) -> layout.LayerSpec:
    return layout.kv_layer(layout.FULL, kv_heads=cfg.num_key_value_heads,
                           head_dim=cfg.head_dim)


def cache_layout(cfg: FalconH1Config) -> tuple:
    """What each held layer keeps in a launch's cache
    (``ops/cache_layout.py``), two kinds in ONE layer: keys and values of
    every position, and the mixer's state (float32) with the convolution's
    last inputs (the cache's dtype), neither of which has positions."""
    block = (_kv_spec(cfg),
             layout.state_layer(
                 ssm=((cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state), ASSUMED["state_dtype"]),
                 conv=((cfg.conv_dim, cfg.mamba_d_conv - 1), None)))
    return (block,) * cfg.num_layers


# -- layers -------------------------------------------------------------
def _scaled(x, m: float):
    """``x * m`` with the product in float32: a multiplier rounded to
    bfloat16 would be the same error on every number of its branch."""
    return x if m == 1 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _qkv(p, u, positions, cfg: FalconH1Config):
    """``u`` (B, L, d) -> q (B, L, KV, G, D), k, v (B, L, KV, D)."""
    b, l, _ = u.shape
    kv, g, hd = cfg.num_key_value_heads, cfg.groups, cfg.head_dim
    q = jnp.dot(u, p["wq"]).reshape(b, l, kv, g, hd)
    k = _scaled(jnp.dot(u, p["wk"]), cfg.key_multiplier).reshape(b, l, kv, hd)
    v = jnp.dot(u, p["wv"]).reshape(b, l, kv, hd)
    return (attn_ops.rope(q, positions, cfg.rope_theta),
            attn_ops.rope(k, positions, cfg.rope_theta), v)


def _mixer_inputs(p, u, cfg: FalconH1Config):
    """``u`` (..., d) -> gate z (..., d_ssm), xBC (..., conv_dim) before the
    convolution, dt (..., heads) float32 before the softplus' bias."""
    zxbcdt = jnp.dot(u, p["in_proj"])
    zxbcdt = (zxbcdt.astype(jnp.float32) * mup_vector(cfg)).astype(u.dtype)
    ds = cfg.mamba_d_ssm
    return (zxbcdt[..., :ds], zxbcdt[..., ds:ds + cfg.conv_dim],
            zxbcdt[..., ds + cfg.conv_dim:].astype(jnp.float32))


def _recurrence_inputs(p, xbc, dt, cfg: FalconH1Config):
    """After the convolution: ``xbc`` (..., conv_dim), ``dt`` (..., heads)
    -> x (..., heads, head_dim), B, C (..., groups, d_state), dt after its
    softplus, A (heads,)."""
    lead = xbc.shape[:-1]
    ds, gn = cfg.mamba_d_ssm, cfg.mamba_n_groups * cfg.mamba_d_state
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :ds].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
    bm = xbc[..., ds:ds + gn].reshape(*lead, cfg.mamba_n_groups,
                                      cfg.mamba_d_state)
    cm = xbc[..., ds + gn:].reshape(*lead, cfg.mamba_n_groups,
                                    cfg.mamba_d_state)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return x, bm, cm, dt, -jnp.exp(p["A_log"].astype(jnp.float32))


def gated_norm(y, z, g, cfg: FalconH1Config):
    """``y * silu(z)`` RMS-normalised within each of the ``n_groups`` groups
    of channels, times the weight; float32 statistics."""
    lead = y.shape[:-1]
    y32 = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y32 = y32.reshape(*lead, cfg.mamba_n_groups, -1)
    y32 = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, -1, keepdims=True)
                              + cfg.rms_norm_eps)
    return (y32.reshape(*lead, -1) * g.astype(jnp.float32)).astype(y.dtype)


# (B, L) of a program's tokens (L = 1: a decode step) -> the form the newest
# trace of the mixer on such tokens ran the recurrence in.  Written while a
# program is traced, read after its launch by whoever reports what the
# program does (as ``glm_moe_lite.attention_traced``).
_SSM_TRACED: dict = {}


def ssm_traced(tokens_shape) -> Optional[str]:
    """``"chunked"`` / ``"step"`` as the program traced in this process for
    tokens of this (B, L) has it; None where none was traced."""
    return _SSM_TRACED.get(tuple(tokens_shape))


def mixer_chunked(p, u, lengths, cfg: FalconH1Config):
    """Whole prompts: ``u`` (B, L, d) the normed input times its multiplier
    -> (the mixer's output (B, L, d) before its multiplier, the state
    (B, heads, head_dim, d_state) float32 and the convolution's tail
    (B, conv_dim, d_conv - 1), both at each prompt's own length)."""
    b, l, _ = u.shape
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _mixer_inputs(p, u, cfg)
        tail = ssm_ops.conv_tail(xbc, lengths, cfg.mamba_d_conv)
        xbc = ssm_ops.conv1d_causal(xbc, p["conv_w"], p["conv_b"])
        x, bm, cm, dt, a = _recurrence_inputs(p, xbc, dt, cfg)
    _SSM_TRACED[(b, l)] = "chunked"
    with jax.named_scope("ssm.scan"):
        y, state = ssm_ops.ssd_chunked(x, dt, a, bm, cm, p["D"], lengths,
                                       chunk=cfg.mamba_chunk_size)
    with jax.named_scope("ssm.out"):
        y = gated_norm(y.reshape(b, l, -1), z, p["gate_norm"], cfg)
        return jnp.dot(y, p["out_proj"]), state, tail


def mixer_step(p, u, entry, active, cfg: FalconH1Config):
    """One token a sequence: ``u`` (B, d), the layer's ``ssm`` and ``conv``
    leaves -> (the mixer's output (B, d), the two leaves moved on by one)."""
    b = u.shape[0]
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _mixer_inputs(p, u, cfg)
        xbc, tail = ssm_ops.conv1d_step(entry["conv"], xbc, p["conv_w"],
                                        p["conv_b"])
        x, bm, cm, dt, a = _recurrence_inputs(p, xbc, dt, cfg)
    _SSM_TRACED[(b, 1)] = "step"
    with jax.named_scope("ssm.scan"):
        y, state = ssm_ops.ssd_step(entry["ssm"], x, dt, a, bm, cm, p["D"],
                                    active)
    with jax.named_scope("ssm.out"):
        y = gated_norm(y.reshape(b, -1), z, p["gate_norm"], cfg)
        return jnp.dot(y, p["out_proj"]), state, tail


def _mixed(x, m, o, cfg: FalconH1Config):
    """The block's first half: the residual plus the mixer's and the
    attention's outputs, each times its multiplier (the sum in float32)."""
    return (x.astype(jnp.float32)
            + m.astype(jnp.float32) * cfg.ssm_out_multiplier
            + o.astype(jnp.float32) * cfg.attention_out_multiplier
            ).astype(x.dtype)


def ffn(layer, h, cfg: FalconH1Config):
    with jax.named_scope("dense_mlp"):
        x = rms_norm(h, layer["ln_post"], cfg.rms_norm_eps)
        return h + swiglu(x, layer["mlp"], cfg.mlp_multipliers)


# -- prefill ------------------------------------------------------------
def _prefill_block(layer, x, positions, lengths, cfg, cache_len):
    """One block over whole prompts; -> (y, cache entry or None)."""
    b, l = x.shape[:2]
    with jax.named_scope("attn.proj"):
        # the block's one input norm: the mixer reads it too
        u = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
        q, k, v = _qkv(layer["attn"], _scaled(u, cfg.attention_in_multiplier),
                       positions, cfg)
    with jax.named_scope("attn.core"):
        o = attn_ops.prefill_full(q, k, v)
    with jax.named_scope("attn.out"):
        o = jnp.dot(o.reshape(b, l, -1), layer["attn"]["wo"])
    with jax.named_scope("ssm.proj"):
        u_ssm = _scaled(u, cfg.ssm_in_multiplier)
    m, state, tail = mixer_chunked(layer["mixer"], u_ssm, lengths, cfg)
    with jax.named_scope("attn.out"):
        h = _mixed(x, m, o, cfg)   # the residual and both branches' sum
    entry = None
    if cache_len is not None:
        entry = {**kv_entry(_kv_spec(cfg), k, v, lengths, cache_len),
                 "ssm": state, "conv": tail}
    return ffn(layer, h, cfg), entry


def prefill_hidden(params, tokens, lengths, cfg: FalconH1Config,
                   cache_len: Optional[int] = None):
    """Whole prompts through the blocks: -> (hidden (B, L, d) before the
    final norm, cache or None).  ``tokens`` (B, L) right-padded, ``lengths``
    (B,).  Padded positions compute garbage no valid position ever sees:
    attention is causal, and the recurrence does not advance over them."""
    b, l = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    with jax.named_scope("embed"):
        x = _scaled(embed(params, tokens), cfg.embedding_multiplier)
    entries = []
    for layer in params["layers"]:
        x, entry = _prefill_block(layer, x, positions, lengths, cfg, cache_len)
        entries.append(entry)
    return x, (None if cache_len is None else {"layers": entries})


def prefill(params, tokens, lengths, cfg: FalconH1Config, cache_len: int,
            active=None):
    """-> (float32 logits (B, V) at each sequence's last position, a cache
    of ``cache_len`` positions with the recurrent state and the
    convolution's tail AT EACH PROMPT'S OWN LENGTH, no routing).  ``active``
    is the serving programs' (which slots hold a request): a dense model
    counts nothing by it."""
    h, cache = prefill_hidden(params, tokens, lengths, cfg, cache_len)
    return (lm_head(params, last_hidden(h, lengths), cfg,
                    cfg.lm_head_multiplier), cache,
            no_routing(tokens.shape[0]))


# -- decode -------------------------------------------------------------
def decode_step(params, cache, tokens, positions, cfg: FalconH1Config,
                active=None):
    """One token per sequence: ``tokens`` (B,) at ``positions`` (B,) ->
    (float32 logits (B, V) for the next position, cache, no routing).  The
    token's key and value are written at its position before it attends;
    the state and the convolution's tail move on by one, but for the slots
    ``active`` (B,) marks False, which keep their state."""
    b = tokens.shape[0]
    pos2 = positions[:, None]
    with jax.named_scope("embed"):
        x = _scaled(embed(params, tokens), cfg.embedding_multiplier)[:, None]
    entries = []
    for layer, entry in zip(params["layers"], cache["layers"]):
        with jax.named_scope("attn.proj"):
            # the block's one input norm: the mixer reads it too
            u = rms_norm(x, layer["ln_in"], cfg.rms_norm_eps)
            q, k, v = _qkv(layer["attn"],
                           _scaled(u, cfg.attention_in_multiplier), pos2, cfg)
        o, kv = kv_decode(_kv_spec(cfg), q, k, v, entry, positions, pos2,
                          ("attn.core",))
        with jax.named_scope("attn.out"):
            o = jnp.dot(o.reshape(b, 1, -1), layer["attn"]["wo"])
        with jax.named_scope("ssm.proj"):
            u_ssm = _scaled(u[:, 0], cfg.ssm_in_multiplier)
        m, state, tail = mixer_step(layer["mixer"], u_ssm, entry, active, cfg)
        with jax.named_scope("attn.out"):
            h = _mixed(x, m[:, None], o, cfg)   # the residual, both branches
        entries.append({**kv, "ssm": state, "conv": tail})
        x = ffn(layer, h, cfg)
    return (lm_head(params, x[:, 0], cfg, cfg.lm_head_multiplier),
            {"layers": entries}, no_routing(b))
