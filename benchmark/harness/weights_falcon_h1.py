"""Falcon-H1's weights from ``--seed``, made by the benchmark itself (as
``weights_glm.py`` makes GLM's), leaf by leaf on the device in bfloat16.  The
shapes are written here from the configuration file's published keys and its
stated cut; nothing of the program is imported, so a wrong shape, layout or
buffer in the program's own initialiser cannot reach both sides of the
comparison: the program refuses this tree, or computes with it what the
reference (``reference/falcon_h1_ref.py``, which reads the same names) does
not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary, hidden), ``head`` (hidden, vocabulary),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``ln_in``, ``ln_post`` (hidden,); ``attn``: ``wq`` (hidden,
  heads x head_dim), ``wk``, ``wv`` (hidden, kv heads x head_dim), ``wo``
  (heads x head_dim, hidden); ``mixer``: ``in_proj`` (hidden, d_ssm + d_ssm + 2
  groups x d_state + mamba heads), columns ``[z | x | B | C | dt]``; ``conv_w``
  (d_ssm + 2 groups x d_state, d_conv), the current position last; ``conv_b``;
  ``A_log``, ``D``, ``dt_bias`` (mamba heads,); ``gate_norm`` (d_ssm,);
  ``out_proj`` (d_ssm, hidden); ``mlp``: ``gate``, ``up`` (hidden,
  intermediate), ``down``.

**Every branch of order one AFTER its published multiplier.**  The published
multipliers belong to trained weights (maximal-update parametrisation):
``attention_out_multiplier`` 0.0375, ``ssm_out_multiplier`` 0.088,
``lm_head_multiplier`` 0.0078, ``key_multiplier`` 0.011 and the rest.  With
projections drawn N(0, 1 / fan_in) they would leave the mixer's and the
attention's outputs a few hundredths of the residual, every key near zero (a
uniform softmax) and every logit near zero: ``correct`` would be blind to
all three.  So each projection is drawn N(0, 1 / fan_in) and then DIVIDED by
the multiplier (or the product of multipliers) that scales its output:

* ``embed`` / ``embedding_multiplier``; ``head`` / ``lm_head_multiplier``;
* ``wq``, ``wv`` / ``attention_in_multiplier``; ``wk`` /
  (``attention_in_multiplier`` x ``key_multiplier``); ``wo`` /
  ``attention_out_multiplier``;
* ``in_proj``'s column groups z, x, B, C, dt / (``ssm_in_multiplier`` x
  ``ssm_multipliers[0..4]``); ``out_proj`` / ``ssm_out_multiplier``;
* ``gate`` / ``mlp_multipliers[0]``; ``down`` / ``mlp_multipliers[1]``.

Then the residual stream, queries, keys, values, gate pre-activations, B, C,
the step sizes' inputs and the logits are all of order one, as a trained
model's are.  Norm weights 1 + N(0, 0.1); the convolution N(0, 1 / d_conv),
its bias N(0, 0.1); Mamba-2's own ranges for the recurrence: ``A`` uniform in
[1, 16] (``A_log`` its logarithm), ``dt_bias`` the inverse softplus of a step
log-uniform in [1e-3, 1e-1], ``D`` 1 + N(0, 0.1).  The same seed gives the
same weights."""

from __future__ import annotations

import math

NORMS = ("ln_in", "ln_post", "final_norm", "gate_norm")


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d = int(config["hidden_size"])
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    ds, hm = int(config["mamba_d_ssm"]), int(config["mamba_n_heads"])
    gn = int(config["mamba_n_groups"]) * int(config["mamba_d_state"])
    f, vocab = int(config["intermediate_size"]), int(config["vocab_size"])

    def block():
        return {"ln_in": (d,), "ln_post": (d,),
                "attn": {"wq": (d, h * hd), "wk": (d, kv * hd),
                         "wv": (d, kv * hd), "wo": (h * hd, d)},
                "mixer": {"in_proj": (d, 2 * ds + 2 * gn + hm),
                          "conv_w": (ds + 2 * gn, int(config["mamba_d_conv"])),
                          "conv_b": (ds + 2 * gn,),
                          "A_log": (hm,), "D": (hm,), "dt_bias": (hm,),
                          "gate_norm": (ds,), "out_proj": (ds, d)},
                "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}

    return {"embed": (vocab, d),
            "layers": [block() for _ in range(int(config["num_hidden_layers"]))],
            "final_norm": (d,), "head": (d, vocab)}


def divisors(config: dict) -> dict:
    """Leaf name -> what its N(0, 1 / fan_in) draw is divided by: a number,
    or for ``in_proj`` one number for each of its five column groups with
    the groups' widths."""
    a_in, s_in = (float(config["attention_in_multiplier"]),
                  float(config["ssm_in_multiplier"]))
    ds, hm = int(config["mamba_d_ssm"]), int(config["mamba_n_heads"])
    gn = int(config["mamba_n_groups"]) * int(config["mamba_d_state"])
    m_gate, m_down = (float(m) for m in config["mlp_multipliers"])
    return {
        "embed": float(config["embedding_multiplier"]),
        "head": float(config["lm_head_multiplier"]),
        "wq": a_in, "wv": a_in, "wk": a_in * float(config["key_multiplier"]),
        "wo": float(config["attention_out_multiplier"]),
        "in_proj": ((ds, ds, gn, gn, hm),
                    tuple(s_in * float(m) for m in config["ssm_multipliers"])),
        "out_proj": float(config["ssm_out_multiplier"]),
        "gate": m_gate, "down": m_down, "up": 1.0,
    }


def _leaf(key, name, shape, over):
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    if name in NORMS or name == "D":
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(bf16)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, f32)).astype(bf16)
    if name == "conv_w":
        return (jax.random.normal(key, shape, f32) * shape[-1] ** -0.5).astype(bf16)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)).astype(bf16)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(bf16)   # softplus^-1
    std = 1.0 if name == "embed" else shape[-2] ** -0.5
    if isinstance(over, tuple):            # in_proj: a divisor a column group
        widths, by_group = over
        scale = jnp.concatenate([jnp.full((w,), std / m, f32)
                                 for w, m in zip(widths, by_group)])
        return (jax.random.normal(key, shape, bf16).astype(f32) * scale).astype(bf16)
    return jax.random.normal(key, shape, bf16) * jnp.bfloat16(std / over)


def make_params(config: dict, seed: int):
    import jax

    from benchmark.harness import weights

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    over = divisors(config)
    make = jax.jit(_leaf, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    # one jitted call a leaf: no float32 copy of the whole tree is ever alive
    return jax.tree_util.tree_unflatten(treedef, [
        make(jax.random.fold_in(key, i), str(path[-1].key), shape,
             over.get(str(path[-1].key)))
        for i, (path, shape) in enumerate(flat)])
