"""MiMo-V2-Flash's weights from ``--seed``, made by the benchmark itself (as
``weights_glm.py`` makes GLM's), leaf by leaf on the device in bfloat16.  The
shapes are written here from the configuration file's published keys and its
stated cut; nothing of the program is imported, so a wrong shape, layout or
buffer in the program's own initialiser cannot reach both sides of the
comparison: the program refuses this tree, or computes with it what the
reference (``reference/mimo_v2_flash_ref.py``, which reads the same names)
does not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary held, hidden), ``head`` (hidden, vocabulary held),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``ln_in`` (the published ``input_layernorm``), ``ln_post``
  (``post_attention_layernorm``) (hidden,); ``attn``, by
  ``hybrid_layer_pattern[i]`` (0 full, 1 window) with ``KV`` =
  ``num_key_value_heads`` / ``swa_num_key_value_heads``: ``wq`` (hidden,
  heads x head_dim), ``wk`` (hidden, KV x head_dim), ``wv`` (hidden, KV x
  v_head_dim), ``wo`` (heads x v_head_dim, hidden) and, in a window layer
  where ``add_swa_attention_sink_bias``, ``sink`` (heads,); then by
  ``moe_layer_freq[i]``: 0 ``mlp`` {gate, up (hidden, intermediate), down}, 1
  ``moe``: ``router`` (hidden, ALL experts), ``bias`` (all experts,) float32,
  ``experts`` {gate, up (held, hidden, moe width), down (held, moe width,
  hidden)}; no shared expert.

Projections N(0, 1 / fan_in) so that activations stay of order one, norm
weights 1 + N(0, 0.1), the embedding N(0, 1), the router's correction bias
N(0, 0.05), **a sink N(ln(sliding_window), 1)**: scores of seeded q and k are
N(0, 1), so the keys of a full window weigh about ``1.65 x sliding_window``
together and a sink near ``ln(sliding_window)`` takes a third to a half of a
head's mass.  THE MEAN IS THE BENCHMARK'S OWN CHOICE AND HAS NO SOURCE: no
published checkpoint's sinks were read, and nothing says a trained sink
takes that share; it is set where a control can see the sink at all.  A sink
of N(0, 1), ISSUE 40's, takes 1% of a window of 128, and leaving it out read
``logit_gap_ratio`` 1.30, like a sound run (my chip run, PR 40, seed
4000000101): the mean was moved AFTER that control passed, and every limit
was read with the moved mean.  The program's own initialiser draws the same
(``mimo_v2_flash.init_params``), so the CPU tests and the cell exercise one
regime.  The same
seed gives the same weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm")


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    hd, dv = int(config["head_dim"]), int(config["v_head_dim"])
    kv = {0: int(config["num_key_value_heads"]),
          1: int(config["swa_num_key_value_heads"])}
    held = int(config["n_routed_experts"])
    routed = int(config.get("published", {}).get("n_routed_experts", held))
    vocab = int(config["vocab_size"])
    width = int(config["moe_intermediate_size"])
    n = int(config["num_hidden_layers"])

    def block(window, sparse):
        attn = {"wq": (d, h * hd), "wk": (d, kv[window] * hd),
                "wv": (d, kv[window] * dv), "wo": (h * dv, d)}
        if window and config["add_swa_attention_sink_bias"]:
            attn["sink"] = (h,)
        out = {"ln_in": (d,), "ln_post": (d,), "attn": attn}
        if sparse:
            out["moe"] = {"router": (d, routed), "bias": (routed,),
                          "experts": {"gate": (held, d, width),
                                      "up": (held, d, width),
                                      "down": (held, width, d)}}
        else:
            f = int(config["intermediate_size"])
            out["mlp"] = {"gate": (d, f), "up": (d, f), "down": (f, d)}
        return out

    return {"embed": (vocab, d),
            "layers": [block(int(w), int(s)) for w, s in zip(
                config["hybrid_layer_pattern"][:n], config["moe_layer_freq"][:n])],
            "final_norm": (d,), "head": (d, vocab)}


def _leaf(key, name, shape, sink_mean):
    import jax
    import jax.numpy as jnp

    if name == "sink":
        return (sink_mean + jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name == "bias":       # a float32 buffer, as published
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    std = 1.0 if name == "embed" else shape[-2] ** -0.5
    return jax.random.normal(key, shape, jnp.bfloat16) * jnp.bfloat16(std)


def make_params(config: dict, seed: int):
    import math

    import jax

    from benchmark.harness import weights

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    make = jax.jit(_leaf, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    sink_mean = math.log(int(config["sliding_window"]))
    # one jitted call a leaf: no float32 copy of the whole tree is ever alive
    return jax.tree_util.tree_unflatten(treedef, [
        make(jax.random.fold_in(key, i), str(path[-1].key), shape, sink_mean)
        for i, (path, shape) in enumerate(flat)])
