"""LongCat-Flash-Omni's language-model weights from ``--seed``, made by the
benchmark itself (as ``weights_glm.py`` makes GLM's), leaf by leaf on the
device in bfloat16.  The shapes are written here from the configuration
file's published keys and its stated cut; nothing of the program is imported,
so a wrong shape, layout or buffer in the program's own initialiser cannot
reach both sides of the comparison: the program refuses this tree, or
computes with it what the reference (``reference/longcat_flash_ref.py``,
which reads the same names) does not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary held, hidden), ``head`` (hidden, vocabulary held),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``sub`` [two sublayers, each ``ln_in`` (the published
  ``input_layernorm[j]``), ``ln_post`` (``post_attention_layernorm[j]``)
  (hidden,); ``attn`` (``self_attn[j]``): ``wq_a`` (hidden, q_lora_rank),
  ``q_norm`` (q_lora_rank,), ``wq_b`` (q_lora_rank, heads x (qk_nope +
  qk_rope)), ``wkv_a`` (hidden, kv_lora_rank + qk_rope), ``kv_norm``
  (kv_lora_rank,), ``wkv_b`` (kv_lora_rank, heads x (qk_nope + v)), head by
  head ``[k_nope | v]``, ``wo`` (heads x v, hidden); ``mlp`` (``mlps[j]``)
  {gate, up (hidden, ffn_hidden_size), down}]; ``moe`` (the layer's one
  expert layer): ``router`` (hidden, ALL routed experts + zero experts),
  ``bias`` (the same,) float32, ``experts`` {gate, up (held, hidden,
  expert_ffn_hidden_size), down (held, expert_ffn_hidden_size, hidden)}; an
  identity expert has no weight.

Projections N(0, 1 / fan_in) so that activations stay of order one, norm
weights 1 + N(0, 0.1), the embedding N(0, 1), the router's correction bias
N(0, 0.005) (a tenth of the other routers': a softmax score is a hundredth of
a sigmoid's), **the router N(0, ROUTER_GAIN^2 / fan_in) with ROUTER_GAIN 2**.
THE GAIN IS THE BENCHMARK'S OWN CHOICE AND HAS NO SOURCE: no published
checkpoint's router was read.  A token's 768 logits are N(0, gain^2); over
2,000 drawn tokens the twelve chosen softmax scores hold together (the
largest | the twelfth): gain 1 0.12 (0.020 | 0.0070), 2 0.41 (0.12 | 0.014),
3 0.71 (0.30 | 0.014), 4 0.87 (0.44 | 0.010), 6 0.97 (0.62 | 0.004).  At 1 a
token's weights sum to 0.74 and the expert layer's ``m`` is a small part of
the stream, so that a broken expert layer would read like a sound run; from
3 on one expert takes most of the mass and the other eleven stop mattering;
at 2 the weights sum to about 2.5 (``routed_scaling_factor`` 6 x 0.41), the
twelfth still carries 0.085, and every choice moves the stream.  Seeded
router columns are alike, so a third of a token's twelve choices (256 / 768)
fall on identity experts, which is the published operating point (about 8 of
12 activated experts compute).  The program's own initialiser draws the same
(``longcat_flash.init_params``), so the CPU tests and the cell exercise one
regime.  The same seed gives the same weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm", "q_norm", "kv_norm")
ROUTER_GAIN = 2.0
SUBLAYERS = 2


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    rq, r = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    held = int(config["n_routed_experts"])
    routed = int(config.get("published", {}).get("n_routed_experts", held))
    outputs = routed + int(config["zero_expert_num"])
    vocab = int(config["vocab_size"])
    width = int(config["expert_ffn_hidden_size"])
    f = int(config["ffn_hidden_size"])

    def sub():
        return {"ln_in": (d,), "ln_post": (d,),
                "attn": {"wq_a": (d, rq), "q_norm": (rq,),
                         "wq_b": (rq, h * (nope + rope)),
                         "wkv_a": (d, r + rope), "kv_norm": (r,),
                         "wkv_b": (r, h * (nope + dv)), "wo": (h * dv, d)},
                "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}

    def block():
        return {"sub": [sub() for _ in range(SUBLAYERS)],
                "moe": {"router": (d, outputs), "bias": (outputs,),
                        "experts": {"gate": (held, d, width),
                                    "up": (held, d, width),
                                    "down": (held, width, d)}}}

    return {"embed": (vocab, d),
            "layers": [block() for _ in range(int(config["num_layers"]))],
            "final_norm": (d,), "head": (d, vocab)}


def _leaf(key, name, shape):
    import jax
    import jax.numpy as jnp

    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name == "bias":       # a float32 buffer, as published
        return 0.005 * jax.random.normal(key, shape, jnp.float32)
    std = 1.0 if name == "embed" else shape[-2] ** -0.5
    if name == "router":
        std *= ROUTER_GAIN
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
