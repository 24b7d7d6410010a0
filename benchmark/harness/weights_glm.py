"""GLM-4.7-Flash's weights from ``--seed``, made by the benchmark itself (as
``weights_lm.py`` makes K-EXAONE's), leaf by leaf on the device in bfloat16.
The shapes are written here from the configuration file's published keys
and its stated cut; nothing of the program is imported, so a wrong shape,
layout or buffer in the program's own initialiser cannot reach both sides of
the comparison: the program refuses this tree, or computes with it what the
reference (``reference/glm_moe_lite_ref.py``, which reads the same names)
does not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary, hidden), ``head`` (hidden, vocabulary),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``ln_in``, ``ln_post`` (hidden,); ``attn``: ``wq_a`` (hidden,
  q_lora_rank), ``q_norm`` (q_lora_rank,), ``wq_b`` (q_lora_rank, heads x
  (qk_nope + qk_rope)), ``wkv_a`` (hidden, kv_lora_rank + qk_rope), ``kv_norm``
  (kv_lora_rank,), ``wkv_b`` (kv_lora_rank, heads x (qk_nope + v)), head by
  head ``[k_nope | v]``, ``wo`` (heads x v, hidden); then ``mlp`` {gate, up
  (hidden, intermediate), down} in the ``first_k_dense_replace`` leading
  layers, else ``moe``: ``router`` (hidden, ALL experts), ``bias`` (all
  experts,) float32, ``experts`` {gate, up (held, hidden, moe width), down
  (held, moe width, hidden)}, ``shared`` {gate, up, down} of ``moe width x
  n_shared_experts``;
* ``mtp`` where the configuration holds the prediction layer.

Projections N(0, 1 / fan_in) so that activations stay of order one, norm
weights 1 + N(0, 0.1), the embedding N(0, 1), the router's correction bias
N(0, 0.05).  The same seed gives the same weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm", "q_norm", "kv_norm", "ln_hidden",
         "ln_embed")


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    rq, r = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    held = int(config["n_routed_experts"])
    routed = int(config.get("published", {}).get("n_routed_experts", held))
    vocab = int(config["vocab_size"])
    width = int(config["moe_intermediate_size"])

    def mlp(f):
        return {"gate": (d, f), "up": (d, f), "down": (f, d)}

    def block(dense):
        out = {"ln_in": (d,), "ln_post": (d,),
               "attn": {"wq_a": (d, rq), "q_norm": (rq,),
                        "wq_b": (rq, h * (nope + rope)),
                        "wkv_a": (d, r + rope), "kv_norm": (r,),
                        "wkv_b": (r, h * (nope + dv)), "wo": (h * dv, d)}}
        if dense:
            out["mlp"] = mlp(int(config["intermediate_size"]))
        else:
            out["moe"] = {"router": (d, routed), "bias": (routed,),
                          "experts": {"gate": (held, d, width),
                                      "up": (held, d, width),
                                      "down": (held, width, d)},
                          "shared": mlp(width * int(config["n_shared_experts"]))}
        return out

    n, lead = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    tree = {"embed": (vocab, d), "layers": [block(i < lead) for i in range(n)],
            "final_norm": (d,), "head": (d, vocab)}
    if int(config.get("num_nextn_predict_layers", 0)):
        tree["mtp"] = {"ln_hidden": (d,), "ln_embed": (d,), "proj": (2 * d, d),
                       "block": block(False), "final_norm": (d,)}
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
