"""Brumby's weights from ``--seed``, made by the benchmark itself (as
``weights_falcon_h1.py`` makes Falcon-H1's), leaf by leaf on the device in
bfloat16.  The shapes are written here from the configuration file's
published keys and its stated cut; nothing of the program is imported, so a
wrong shape, layout or buffer in the program's own initialiser cannot reach
both sides of the comparison: the program refuses this tree, or computes
with it what the reference (``reference/brumby_ref.py``, which reads the same
names) does not.

The tree (the names are the program's checkpoint layout; ``x @ w``
everywhere, so a projection is (in, out)):

* ``embed`` (vocabulary, hidden), ``head`` (hidden, vocabulary),
  ``final_norm`` (hidden,);
* ``layers[i]``: ``ln_in``, ``ln_post`` (hidden,); ``ret``: ``wq`` (hidden,
  heads x head_dim), ``wk``, ``wv`` (hidden, kv heads x head_dim), ``wg``
  (hidden, kv heads: the gate, one column a key head), ``wo`` (heads x
  head_dim, hidden), ``q_norm``, ``k_norm`` (head_dim,); ``mlp``: ``gate``,
  ``up`` (hidden, intermediate), ``down``.

Projections N(0, 1 / fan_in), the embedding N(0, 1), norm weights 1 + N(0,
0.1), so that activations stay of order one.

**The gates** (the configuration's ``assumed.gate_seeding``).  The published
gate has no bias: ``log g = log_sigmoid(x' W_g)``.  Drawn like a projection,
``x' W_g`` would be N(0, 1) and ``g`` about a half: a state that forgets in
two positions, behind which a state kept in a lower precision, taken after
the padding or queried a position late could not be seen.  A trained model's
gate finds a direction that every position's ``x'`` shares; a seeded one is
given it: CHANNEL 0 of every embedding row is the constant ``ANCHOR`` (8), no
layer writes that channel (column 0 of every ``wo`` and ``down`` is zero), so
the residual stream carries exactly 8 there at every depth, and layer ``i``'s
gate of key head ``j`` weighs channel 0 by ``c_ij sqrt(1 + GROWTH i) /
ANCHOR`` with ``c_ij`` uniform in [2.2, 6.9] (``sigmoid``: 0.9 to 0.999) from
the seed; the root undoes the RMS norm's division by a stream that has grown
(each layer adds about ``GROWTH`` = 0.7 to the other channels' mean square: a
SwiGLU of unit inputs 0.36, the retention's average about as much; read at a
width of 512 on the CPU: 1.0, 1.5, 2.1, 2.8, 3.5, 4.2, 5.0, 5.7 before layers
0 to 7).  The other 5,119 rows of ``wg`` are a quarter of a projection's (N(0,
1 / 16 fan_in)): each position moves its gate by about a quarter in the
logit.  So ``g`` spreads over about 0.9 to 0.999 by head and layer, and a
head's memory over about 10 to 1,000 positions.  The same seed gives the same
weights."""

from __future__ import annotations

NORMS = ("ln_in", "ln_post", "final_norm", "q_norm", "k_norm")
ANCHOR = 8.0
GROWTH = 0.7
GATE_LOGIT = (2.2, 6.9)     # sigmoid: 0.9 .. 0.999


def shapes(config: dict) -> dict:
    """The tree of shapes (tuples) for a configuration file."""
    d = int(config["hidden_size"])
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    f, vocab = int(config["intermediate_size"]), int(config["vocab_size"])
    block = {"ln_in": (d,), "ln_post": (d,),
             "ret": {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                     "wg": (d, kv), "wo": (h * hd, d),
                     "q_norm": (hd,), "k_norm": (hd,)},
             "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}
    return {"embed": (vocab, d),
            "layers": [block] * int(config["num_hidden_layers"]),
            "final_norm": (d,), "head": (d, vocab)}


def _leaf(key, name, shape, layer):
    import jax
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(bf16)
    if name == "embed":
        return jax.random.normal(key, shape, bf16).at[:, 0].set(ANCHOR)
    if name == "wg":
        k_aim, k_rest = jax.random.split(key)
        aim = jax.random.uniform(k_aim, shape[-1:], f32, *GATE_LOGIT)
        rest = jax.random.normal(k_rest, shape, f32) * (0.25 * shape[0] ** -0.5)
        return rest.at[0].set(aim * (1.0 + GROWTH * layer) ** 0.5 / ANCHOR
                              ).astype(bf16)
    out = jax.random.normal(key, shape, bf16) * jnp.bfloat16(shape[-2] ** -0.5)
    # no layer writes the constant channel
    return out.at[:, 0].set(0) if name in ("wo", "down") else out


def make_params(config: dict, seed: int):
    import jax

    from benchmark.harness import weights

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    make = jax.jit(_leaf, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    # one jitted call a leaf: no float32 copy of the whole tree is ever alive
    return jax.tree_util.tree_unflatten(treedef, [
        # (the layer's index is the gate's alone: every other leaf of a name
        # and shape is one compiled program for all the layers)
        make(jax.random.fold_in(key, i), str(path[-1].key), shape,
             path[1].idx if str(path[-1].key) == "wg" else 0)
        for i, (path, shape) in enumerate(flat)])
