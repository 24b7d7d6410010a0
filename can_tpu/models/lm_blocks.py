"""What the language models' decoder blocks have in common, as pure
functions over a parameter tree: the norm, SwiGLU, the expert layer with
its shared expert, the feed-forward half of a block, the head, the routing
report a serving program returns, the seeded initialiser, and the two
things every model's stack does the same way: **how a key/value layer
writes and reads its cache** (``kv_entry``, ``kv_decode``: from the
``LayerSpec`` the model states in its ``cache_layout``, nothing else) and
**the stack's skeleton** (``prefill_stack``, ``decode_stack``,
``mtp_logits``: positions and mask, the embedding, the loop over the layers,
the cache, the head, the routing report).  A model's module
(``exaone_moe.py``, ``glm_moe_lite.py``, ``lfm2_moe.py``,
``mimo_v2_flash.py``, ``brumby.py``, ``longcat_flash.py``) brings its config class, its own mixers as one function
a layer for a prompt and one for a step, and three thin entry points over
the skeleton (the grouped-query ones share ``qkv_heads``, the three
projections by head); the config offers ``rms_norm_eps`` and, where the
model has an expert layer, ``num_experts_per_tok``,
``routed_scaling_factor``, ``norm_topk_prob`` and ``share``
(``ops.moe.ExpertShare``).  An expert layer has a shared expert where its
parameters hold one (``shared``; LFM2's hold none), and the head is the
embedding's transpose where the tree has no ``head`` (tied).  A dense model
(``falcon_h1.py``) takes the norm, SwiGLU, the head with its configuration's
multipliers, ``kv_entry`` / ``kv_decode`` and ``no_routing``; its embedding
and head carry multipliers, so its shell is its own.

Weights and activations follow the parameter tree's dtype; the router, the
norms' statistics and the logits are float32.
"""

from __future__ import annotations

import contextlib
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from can_tpu.ops import attention as attn_ops
from can_tpu.ops import cache_layout as layout
from can_tpu.ops import moe as moe_ops
from can_tpu.ops.moe import ExpertShare


# The parts of a language model, ONE vocabulary for the seven models: every
# ``jax.named_scope`` that the serving programs pass through (the models,
# ``ops/moe.py``, ``serve/programs.py``) is one of these names, whole (the MTP
# modules' ``mtp``, outside those programs, wraps them).  A scope is metadata: it names no op and adds
# none (``tests/test_program_scopes.py`` holds the programs' text equal with
# and without).  The compiled program carries the scopes as each
# instruction's ``op_name``; ``obs/trace.py::part_of`` gives a device op the
# INNERMOST name of its path, and ``LMEngine`` records the map when a tracer
# is active (the span ``program.scopes``).
PARTS = (
    "embed",         # the token gather (and Falcon-H1's multiplier)
    "attn.proj",     # input norm, query / key-value / latent projections, rotary, GLM's q_nope x W_uk
    "attn.cache",    # the row written into the cache; a prefill slice's rows placed into the launch's cache
    "attn.core",     # scores, softmax, values: against the cache (decode) or over the prompt (prefill)
    "attn.window",   # the same of a WINDOW layer (the ring; two blocks of a prompt; its sink), where a model opens it inside attn.core: attn.core is then its full layers' alone
    "attn.out",      # GLM's W_uv, ``wo``, the residual
    "moe.router",    # post-norm, router product, top-k, the weights by held expert
    "moe.dispatch",  # the sorted form's sort, gather, scatter and combine (no other form has any)
    "moe.experts",   # the routed experts' products, in all three forms
    "moe.shared",    # the shared expert and the layer's residual
    "dense_mlp",     # a dense layer's norm, SwiGLU and residual
    "ssm.proj",      # ``in_proj``, the convolution, the recurrence's inputs
    "ssm.scan",      # ``ssd_chunked`` / ``ssd_step``: the state
    "ssm.out",       # the gated norm and ``out_proj``
    "conv.proj",     # LFM2's gated short convolution: input norm and ``in_proj``
    "conv.mix",      # the gates ``B * X`` and ``C * v`` around the convolution (a prompt / one step), the tail
    "conv.out",      # ``out_proj`` and the residual
    "ret.proj",      # power retention (Brumby): input norm, ``wq`` / ``wk`` / ``wv`` / ``wg``, the head norms, rotary, ``log_sigmoid``
    "ret.core",      # a prompt's scores inside a chunk: the power, the decay, the weighted values
    "ret.state",     # ``phi``; the state decayed, updated, queried, its read and write; a prefill slice's states placed into the launch's cache
    "ret.out",       # the division by the normaliser, ``wo``, the residual
    "head",          # final norm and the head's product
    "sample",        # argmax, the ``ids`` update, the decode state moved on
    "routing",       # ``routing_report``'s counts and choices
)

# What the compiler renames: XLA:TPU rewrites ``jax.lax.ragged_dot`` into its
# grouped-matmul kernels and stamps their ``op_name`` anew, dropping the scope
# they were traced in (read in the compiled text of both sparse models'
# prefill, PR 35).  ``obs.trace.part_of`` takes these names for the part too.
RENAMED_BY_COMPILER = {
    "ragged-dot-none": "moe.experts",      # the grouped product itself
    "ragged-dot-metadata": "moe.experts",  # its groups' tiling, from the sizes
}


class VocabSlice(NamedTuple):
    """Rows ``first .. first + held - 1`` of the ``total`` vocabulary."""

    first: int
    held: int
    total: int


# -- parameters ---------------------------------------------------------
def count_shapes(shapes: dict) -> int:
    """Numbers in a tree of shapes (tuples)."""
    return sum(math.prod(s) for s in
               jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)))


def _leaf(key, name: str, shape, dtype):
    if name.startswith("ln_") or name.endswith("_norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name == "bias":      # the router's correction bias: a float32 buffer
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    if name in ("embed", "sink"):   # a sink: one logit a head; its model moves the mean
        return jax.random.normal(key, shape, dtype)
    if name == "conv_w":    # (channels, taps): N(0, 1 / taps)
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[-1] ** -0.5).astype(dtype)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, dtype) * jnp.asarray(fan_in ** -0.5, dtype)


def init_from_shapes(key, shapes: dict, dtype=jnp.bfloat16):
    """Parameters from a key for a tree of shapes, leaf by leaf on the
    device (one jitted call a leaf: no float32 copy of the whole tree is
    ever alive).  By the leaf's name: ``ln_*`` / ``*_norm`` norms near one,
    ``bias`` a float32 buffer, ``embed`` and ``sink`` N(0, 1) (the model that
    has sinks moves their mean: ``mimo_v2_flash.init_params``), ``conv_w`` a
    depthwise convolution N(0, 1 / taps), every other a projection N(0, 1 /
    fan_in) so that activations stay of order one."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    make = jax.jit(_leaf, static_argnums=(1, 2, 3))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        leaves.append(make(jax.random.fold_in(key, i), str(path[-1].key), shape,
                           dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- layers -------------------------------------------------------------
def rms_norm(x, g, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, p, multipliers=None):
    """``down(silu(gate(x)) * up(x))``; with ``multipliers`` (gate's, down's;
    maximal-update parametrisation) ``down(up(x) * silu(gate(x) * m0)) *
    m1``."""
    if multipliers is None:
        return jnp.dot(jax.nn.silu(jnp.dot(x, p["gate"])) * jnp.dot(x, p["up"]),
                       p["down"])
    # the products with a multiplier in float32: one rounded to bfloat16
    # would be the same error on every number of the branch
    m_gate, m_down = multipliers
    gate = (jnp.dot(x, p["gate"]).astype(jnp.float32) * m_gate).astype(x.dtype)
    y = jnp.dot(jax.nn.silu(gate) * jnp.dot(x, p["up"]), p["down"])
    return (y.astype(jnp.float32) * m_down).astype(x.dtype)


def qkv_heads(p, x, kv_heads: int, groups: int, head_dim: int,
              v_head_dim: Optional[int] = None):
    """Grouped-query projections: ``x`` (B, L, d) -> q (B, L, KV, G, D), k
    (B, L, KV, D), v (B, L, KV, Dv), from ``p``'s ``wq``, ``wk``, ``wv``
    (``Dv`` = ``D`` where the model gives the values no width of their
    own).  Norms, rotary and scales are the model's."""
    b, l, _ = x.shape
    q = jnp.dot(x, p["wq"]).reshape(b, l, kv_heads, groups, head_dim)
    k = jnp.dot(x, p["wk"]).reshape(b, l, kv_heads, head_dim)
    v = jnp.dot(x, p["wv"]).reshape(b, l, kv_heads, v_head_dim or head_dim)
    return q, k, v


def scoped(*names: str):
    """``jax.named_scope`` of each of ``names``, the first outermost: a
    window layer's core is ``("attn.core", "attn.window")`` where its model
    tells the two kinds of layer apart, ``("attn.core",)`` elsewhere."""
    stack = contextlib.ExitStack()
    for name in names:
        stack.enter_context(jax.named_scope(name))
    return stack


# -- a key/value layer's cache ------------------------------------------
# THE place where keys and values meet the leaves ``cache_layout.kv_layer``
# describes: what a model states there (full or a ring, the heads' widths)
# is all these two read.  ``ops/attention.py``'s writes and reads are called
# through the module: the benchmark's calibration breaks the timed path by
# replacing them there (``write_slot``, ``ring_entry``).
def kv_entry(spec: layout.LayerSpec, k, v, lengths, cache_len: int) -> dict:
    """A prompt's keys and values (B, L, KV, D / Dv) as the layer's entry
    ``{"k", "v"}`` in ``spec.shapes(B, cache_len)``: every position of a
    ``full`` layer (those past ``L`` zero), the newest ``spec.window`` before
    each prompt's own length in a ``ring``."""
    with jax.named_scope("attn.cache"):
        shapes = spec.shapes(k.shape[0], cache_len)
        if spec.kind == layout.RING:
            return attn_ops.ring_entry(k, v, lengths, spec.window, shapes)
        return {"k": attn_ops.as_leaf(k, shapes["k"]),
                "v": attn_ops.as_leaf(v, shapes["v"])}


def kv_decode(spec: layout.LayerSpec, q, k, v, entry, positions, column,
              core, sink=None):
    """One token a sequence against the layer's cache: ``q`` (B, 1, KV, G,
    D) and the token's own ``k`` (B, 1, KV, D), ``v`` (B, 1, KV, Dv) at
    ``positions`` (B,) (``column``: the same as (B, 1), made once a step),
    written into ``entry`` before the token attends: at its position in a
    ``full`` layer, at slot ``position % spec.window`` in a ``ring`` -> (the
    attended values (B, KV, G, Dv), the entry).  ``core``: the scope names
    of the layer's attention (``scoped``), which the per-layer metrics read
    from the compiled program; ``sink`` (H,): a learned logit a query head
    in the softmax's denominator, as the layer's parameters hold it."""
    with scoped(*core):
        if spec.kind == layout.RING:
            slot = jnp.mod(positions, spec.window)
            valid = attn_ops.ring_positions(positions, spec.window) >= 0
        else:
            slot = positions
            valid = jnp.arange(entry["k"].shape[2])[None, :] <= column
    with jax.named_scope("attn.cache"):
        kc = attn_ops.write_slot(entry["k"], k[:, 0], slot)
        vc = attn_ops.write_slot(entry["v"], v[:, 0], slot)
    with scoped(*core):
        o = attn_ops.decode(q[:, 0], kc, vc, valid,
                            None if sink is None else sink.reshape(q.shape[2:4]))
    return o, {"k": kc, "v": vc}


class Routed(NamedTuple):
    """What an expert layer says of one call: ``idx`` the experts each token
    chose (..., k), ``read`` the number of held experts whose weights the
    call read, () int32, where its form counts them, else None (every held
    expert), ``passes`` the passes it took over its buffer, () int32, where
    its form has one, else None (``ops.moe.share_apply``)."""

    idx: jax.Array
    read: Optional[jax.Array]
    passes: Optional[jax.Array]


def experts_form(cfg, tokens: int, dtype) -> str:
    """The form (``ops.moe.share_form``) in which the expert layers of a
    model of ``cfg`` run a program of ``tokens`` tokens."""
    return moe_ops.share_form(tokens, cfg.num_experts_per_tok, cfg.share,
                              cfg.hidden_size, cfg.moe_intermediate_size,
                              dtype)


def expert_layer(p, x, cfg):
    """``x`` (T, d) -> (this chip's part of the routed sum + the shared
    expert where the layer has one (T, d), ``Routed``: the experts each
    token chose (T, k)).  Where the router has identity experts behind the
    routed ones (``cfg.share.zero``) their term, ``(sum of a token's weights
    on them) x``, is added whole: every chip computes it alike.  The
    router's scores are ``cfg.scoring_func`` where the configuration names
    one, sigmoid where it does not."""
    with jax.named_scope("moe.router"):
        idx, w = moe_ops.route(x, p["router"], p["bias"],
                               top_k=cfg.num_experts_per_tok,
                               scale=cfg.routed_scaling_factor,
                               normalize=cfg.norm_topk_prob,
                               scoring=getattr(cfg, "scoring_func", "sigmoid"))
    routed, read, passes = moe_ops.share_apply(x, idx, w, p["experts"],
                                               cfg.share)
    if cfg.share.zero:
        with jax.named_scope("moe.router"):
            w0 = moe_ops.zero_weight(idx, w, cfg.share)
        with jax.named_scope("moe.experts"):
            routed = (routed.astype(jnp.float32)
                      + w0[:, None] * x.astype(jnp.float32)).astype(x.dtype)
    if "shared" not in p:
        return routed, Routed(idx, read, passes)
    with jax.named_scope("moe.shared"):
        return routed + swiglu(x, p["shared"]), Routed(idx, read, passes)


def ffn(layer, h, cfg, *, norm: bool = True):
    """The feed-forward half of a block on (B, L, d), ``h + F(RMSNorm(h))``
    (``norm`` False: ``h + F(h)``): -> (y, ``Routed`` with the experts each
    token chose (B, L, k), or None for the dense layer)."""
    b, l, d = h.shape
    dense = "mlp" in layer
    with jax.named_scope("dense_mlp" if dense else "moe.router"):
        x = rms_norm(h, layer["ln_post"], cfg.rms_norm_eps) if norm else h
        if dense:
            return h + swiglu(x, layer["mlp"]), None
    y, routed = expert_layer(layer["moe"], x.reshape(b * l, d), cfg)
    with jax.named_scope("moe.shared"):
        return h + y.reshape(b, l, d), routed._replace(
            idx=routed.idx.reshape(b, l, -1))


def routing_report(chosen, mask, pick, cfg) -> dict:
    """What a program reports of its routing: ``counts`` (expert layers,
    held) assignments of the tokens ``mask`` (B, L) marks that landed on
    each held expert, and ``choices`` (expert layers, B, k) the experts
    chosen at position ``pick`` (B,) of each sequence (``no_routing`` where no
    layer is an expert layer); where the layers'
    form counts the experts it read (``Routed.read``), also
    ``experts_read`` () int32, their sum over the layers (every row of the
    batch reads, marked or not), and where it counts its passes
    (``Routed.passes``), ``dispatch_passes`` () int32, theirs.  ``chosen``:
    each layer's ``Routed``, None for a dense layer."""
    chosen = [c for c in chosen if c is not None]
    if not chosen:      # no expert layer among them: no rows, of any config
        return no_routing(pick.shape[0])
    with jax.named_scope("routing"):
        counts = [moe_ops.held_counts(jnp.where(mask[..., None], c.idx, -1),
                                      cfg.share) for c in chosen]
        at = [jnp.take_along_axis(c.idx, pick[:, None, None], axis=1)[:, 0]
              for c in chosen]
        report = {"counts": jnp.stack(counts), "choices": jnp.stack(at)}
        if cfg.share.zero:
            report["zero"] = jnp.stack([moe_ops.zero_counts(
                jnp.where(mask[..., None], c.idx, -1), cfg.share)
                for c in chosen])
        read = [c.read for c in chosen if c.read is not None]
        if read:
            report["experts_read"] = sum(read)
        passes = [c.passes for c in chosen if c.passes is not None]
        if passes:
            report["dispatch_passes"] = sum(passes)
        return report


def no_routing(batch: int) -> dict:
    """``routing_report`` of a model without an expert layer: no rows."""
    return {"counts": jnp.zeros((0, 0), jnp.int32),
            "choices": jnp.zeros((0, batch, 0), jnp.int32)}


def embed(params, tokens):
    with jax.named_scope("embed"):
        return params["embed"][tokens]


def last_hidden(h, lengths):
    """``h`` (B, L, d) -> (B, d) at each sequence's last position: what a
    prefill hands the head."""
    with jax.named_scope("head"):
        return jnp.take_along_axis(h, (lengths - 1)[:, None, None],
                                   axis=1)[:, 0]


def lm_head(params, h, cfg, multiplier=None):
    """Float32 logits of ``RMSNorm(h)``, times ``multiplier`` where the
    configuration has one.  A tree without ``head`` ties it to the
    embedding: the product is against ``embed`` (V, d) as it is stored."""
    with jax.named_scope("head"):
        x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        if "head" in params:
            logits = jnp.dot(x, params["head"],
                             preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("...d,vd->...v", x, params["embed"],
                                preferred_element_type=jnp.float32)
        return logits if multiplier is None else logits * multiplier


# -- the stack's skeleton -------------------------------------------------
# What a model's ``prefill_hidden`` / ``decode_step`` / ``mtp_logits`` run
# around its own layers.  ``kinds``: one label a layer, the model's own (its
# layer types, or its ``cache_layout``'s specs), handed to the layer function
# with the layer's parameters.
def prefill_stack(params, tokens, lengths, kinds, block, cfg,
                  cache_len: Optional[int], active):
    """Whole prompts through the layers: -> (hidden (B, L, d) before the
    final norm, cache or None, ``routing_report`` of the valid tokens).
    ``tokens`` (B, L) right-padded, ``lengths`` (B,); ``active`` (B,) marks
    the sequences whose routing is counted (all when None).  ``block(layer,
    kind, x, positions, lengths, cfg, cache_len)`` -> (y, the layer's cache
    entry or None, its ``Routed`` or None) is the model's layer over ``x``
    (B, L, d) at ``positions`` (B, L)."""
    b, l = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    mask = positions < lengths[:, None]
    if active is not None:
        mask &= active[:, None]
    x = embed(params, tokens)
    entries, chosen = [], []
    for layer, kind in zip(params["layers"], kinds):
        x, entry, c = block(layer, kind, x, positions, lengths, cfg, cache_len)
        entries.append(entry)
        chosen.append(c)
    cache = None if cache_len is None else {"layers": entries}
    return x, cache, routing_report(chosen, mask, lengths - 1, cfg)


def decode_stack(params, cache, tokens, positions, kinds, block, cfg, active):
    """One token per sequence through the layers: ``tokens`` (B,) at
    ``positions`` (B,) -> (float32 logits (B, V) for the next position,
    cache, routing).  ``active`` (B,) marks the slots whose routing is
    counted (all when None).  ``block(layer, kind, x, entry, positions,
    column, cfg)`` -> (y, the entry written, its ``Routed`` or None) is the
    model's layer over ``x`` (B, 1, d); ``column`` is ``positions`` as (B,
    1), made once a step for the rotary and the masks of all layers."""
    b = tokens.shape[0]
    column = positions[:, None]
    x = embed(params, tokens)[:, None]                       # (B, 1, d)
    entries, chosen = [], []
    for layer, kind, entry in zip(params["layers"], kinds, cache["layers"]):
        x, entry, c = block(layer, kind, x, entry, positions, column, cfg)
        entries.append(entry)
        chosen.append(c)
    mask = jnp.ones((b, 1), bool) if active is None else active[:, None]
    return (lm_head(params, x[:, 0], cfg), {"layers": entries},
            routing_report(chosen, mask, jnp.zeros((b,), jnp.int32), cfg))


def mtp_logits(params, hidden, next_tokens, cfg, block, kind):
    """The MTP module in DeepSeek-V3's form, over whole sequences:
    ``h' = W_p [RMSNorm(h_t); RMSNorm(Emb(x_{t+1}))]``, one block of the
    model's own (``block`` as ``prefill_stack`` calls it, of ``kind``: its
    attention + an expert layer, nothing cached), the module's norm and the
    SHARED head: float32 logits (B, L, V) for position ``t + 2``.
    ``hidden`` (B, L, d) is ``prefill_hidden``'s, ``next_tokens`` (B, L) the
    ids at ``t + 1``."""
    m = params["mtp"]
    b, l, _ = hidden.shape
    with jax.named_scope("mtp"):
        x = jnp.concatenate(
            [rms_norm(hidden, m["ln_hidden"], cfg.rms_norm_eps),
             rms_norm(embed(params, next_tokens), m["ln_embed"],
                      cfg.rms_norm_eps)], axis=-1)
        x = jnp.dot(x, m["proj"])
        positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
        x = block(m["block"], kind, x, positions, None, cfg, None)[0]
        x = rms_norm(x, m["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)
