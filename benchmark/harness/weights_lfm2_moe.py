"""LFM2-MoE's weights from ``--seed``, made by the benchmark itself (as
``weights_glm.py`` makes GLM's), leaf by leaf on the device in bfloat16.  The
shapes are written here from the configuration file's published keys and its
stated cut; nothing of the program is imported, so a wrong shape, layout or
buffer in the program's own initialiser cannot reach both sides of the
comparison: the program refuses this tree, or computes with it what the
reference (``reference/lfm2_moe_ref.py``, which reads the same names) does
not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary, hidden), ``final_norm`` (hidden,); NO ``head``: it
  is tied to ``embed`` (the configuration's ``assumed``);
* ``layers[i]``: ``ln_in`` (the published ``operator_norm``), ``ln_post``
  (``ffn_norm``) (hidden,); then by ``layer_types[i]`` either ``conv``:
  ``in_proj`` (hidden, 3 x hidden), columns ``[B | C | X]``, ``conv_w``
  (hidden, conv_L_cache), the current position last, ``out_proj`` (hidden,
  hidden); or ``attn``: ``wq`` (hidden, heads x head_dim), ``wk``, ``wv``
  (hidden, kv heads x head_dim), ``wo``, ``q_norm``, ``k_norm`` (head_dim,);
  then ``mlp`` {gate, up (hidden, intermediate), down} in the
  ``num_dense_layers`` leading layers, else ``moe``: ``router`` (hidden, ALL
  experts), ``bias`` (all experts,) float32, ``experts`` {gate, up (held,
  hidden, moe width), down (held, moe width, hidden)}; no shared expert.

Projections N(0, 1 / fan_in) so that activations stay of order one (the two
gates are products of two such: of order one too), the convolution N(0, 1 /
taps), norm weights 1 + N(0, 0.1), the router's correction bias N(0, 0.05).
The embedding is N(0, 1 / sqrt(hidden)), between its two uses: as the first
layer's input it is normalised whatever its scale, and as the tied head it
gives logits of rms hidden ** 0.25 (6.7 at 2,048: N(0, 1) rows would give
45, a softmax no trained model has).  The same seed gives the same weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm", "q_norm", "k_norm")
CONV = "conv"


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    hd = int(config.get("assumed", {}).get("head_dim", d // h))
    held = int(config["num_experts"])
    routed = int(config.get("published", {}).get("num_experts", held))
    width = int(config["moe_intermediate_size"])

    def mlp(f):
        return {"gate": (d, f), "up": (d, f), "down": (f, d)}

    def block(i, kind):
        out = {"ln_in": (d,), "ln_post": (d,)}
        if kind == CONV:
            out["conv"] = {"in_proj": (d, 3 * d),
                           "conv_w": (d, int(config["conv_L_cache"])),
                           "out_proj": (d, d)}
        else:
            out["attn"] = {"wq": (d, h * hd), "wk": (d, kv * hd),
                           "wv": (d, kv * hd), "wo": (h * hd, d),
                           "q_norm": (hd,), "k_norm": (hd,)}
        if i < int(config["num_dense_layers"]):
            out["mlp"] = mlp(int(config["intermediate_size"]))
        else:
            out["moe"] = {"router": (d, routed), "bias": (routed,),
                          "experts": {"gate": (held, d, width),
                                      "up": (held, d, width),
                                      "down": (held, width, d)}}
        return out

    return {"embed": (int(config["vocab_size"]), d),
            "layers": [block(i, k) for i, k in enumerate(config["layer_types"])],
            "final_norm": (d,)}


def _leaf(key, name, shape):
    import jax
    import jax.numpy as jnp

    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name == "bias":       # a float32 buffer, as published
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        std = shape[-1] ** -0.25
    elif name == "conv_w":
        std = shape[-1] ** -0.5
    else:
        std = shape[-2] ** -0.5
    return jax.random.normal(key, shape, jnp.bfloat16) * jnp.bfloat16(std)


def make_params(config: dict, seed: int):
    import jax

    from benchmark.harness import weights

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    make = jax.jit(_leaf, static_argnums=(1, 2))
    key = weights.seed_key(seed)
    # one jitted call a leaf: no float32 copy of the whole tree is ever alive
    return jax.tree_util.tree_unflatten(treedef, [
        make(jax.random.fold_in(key, i), str(path[-1].key), shape)
        for i, (path, shape) in enumerate(flat)])
