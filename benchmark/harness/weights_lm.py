"""Language-model weights from ``--seed``, made by the benchmark itself (as
``weights.py`` makes CANNet's), leaf by leaf on the device in bfloat16.
The shapes are written here from the configuration file's published keys
and its stated cut; nothing of the program is imported, so a wrong shape,
layout or buffer in the program's own initialiser cannot reach both sides of
the comparison: the program refuses this tree, or computes with it what the
reference (``reference/exaone_moe_ref.py``, which reads the same names)
does not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary held, hidden), ``head`` (hidden, vocabulary held),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``ln_in``, ``ln_post`` (hidden,); ``attn``: ``wq`` (hidden,
  heads x head_dim), ``wk``, ``wv`` (hidden, kv heads x head_dim), ``wo``
  (heads x head_dim, hidden), ``q_norm``, ``k_norm`` (head_dim,); then
  ``mlp`` {gate, up (hidden, intermediate), down} where ``mlp_layer_types``
  says dense, else ``moe``: ``router`` (hidden, ALL experts), ``bias`` (all
  experts,) float32, ``experts`` {gate, up (held, hidden, moe width), down
  (held, moe width, hidden)}, ``shared`` {gate, up, down} of ``moe width x
  num_shared_experts``;
* ``mtp`` where the configuration holds the prediction layer.

Projections N(0, 1 / fan_in) so that activations stay of order one, norm
weights 1 + N(0, 0.1), the embedding N(0, 1), the router's correction bias
N(0, 0.05).  The same seed gives the same weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm", "q_norm", "k_norm", "ln_hidden",
         "ln_embed")


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    qd = int(config["num_attention_heads"]) * hd
    kd = int(config["num_key_value_heads"]) * hd
    held = int(config["num_experts"])
    routed = int(config.get("published", {}).get("num_experts", held))
    vocab = int(config["vocab_size"])
    width = int(config["moe_intermediate_size"])

    def mlp(f):
        return {"gate": (d, f), "up": (d, f), "down": (f, d)}

    def block(kind):
        out = {"ln_in": (d,), "ln_post": (d,),
               "attn": {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd),
                        "wo": (qd, d), "q_norm": (hd,), "k_norm": (hd,)}}
        if kind == "dense":
            out["mlp"] = mlp(int(config["intermediate_size"]))
        else:
            out["moe"] = {"router": (d, routed), "bias": (routed,),
                          "experts": {"gate": (held, d, width),
                                      "up": (held, d, width),
                                      "down": (held, width, d)},
                          "shared": mlp(width * int(config["num_shared_experts"]))}
        return out

    kinds = config["mlp_layer_types"][:int(config["num_hidden_layers"])]
    tree = {"embed": (vocab, d), "layers": [block(k) for k in kinds],
            "final_norm": (d,), "head": (d, vocab)}
    if int(config.get("num_nextn_predict_layers", 0)):
        tree["mtp"] = {"ln_hidden": (d,), "ln_embed": (d,), "proj": (2 * d, d),
                       "block": block("sparse"), "final_norm": (d,)}
    return tree


def _leaf(key, name, shape):
    import jax
    import jax.numpy as jnp

    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name == "bias":       # a float32 buffer, as published
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    std = 1.0 if name == "embed" else shape[-2] ** -0.5
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
