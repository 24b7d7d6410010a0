"""The one cached key/value layer (``models/lm_blocks.py::kv_entry`` /
``kv_decode``): what a prompt hands over is what ``serve/cache.py``
allocates from the model's ``cache_layout``, also where the heads PACK
(several side by side in a row of whole lanes), a step decodes from it what
plain attention gives, and no model's module writes a leaf itself."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.models import lm_blocks as lb
from can_tpu.ops import cache_layout as layout
from can_tpu.serve import cache as kv_cache

import lm_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the four models at a preset whose heads pack ----------------------
def _exaone():
    from benchmark.reference import exaone_moe_ref as ref
    from can_tpu.models import exaone_moe as model

    d = lm_tiny.tiny_config(mtp=0)
    d.update(num_attention_heads=8, num_key_value_heads=8,    # 8 heads of 16
             num_hidden_layers=2, mlp_layer_types=["dense", "sparse"],
             layer_types=["full_attention", "sliding_attention"])
    return model, model.ExaoneMoeConfig.from_dict(d), ref, d


def _lfm2():
    from benchmark.reference import lfm2_moe_ref as ref
    from can_tpu.models import lfm2_moe as model

    d = lm_tiny.tiny_lfm2_config(
        head_dim=16, layer_types=["conv", "full_attention", "conv"])
    d.update(num_attention_heads=8, num_key_value_heads=8)
    return model, model.Lfm2MoeConfig.from_dict(d), ref, d


def _mimo():
    from benchmark.reference import mimo_v2_flash_ref as ref
    from can_tpu.models import mimo_v2_flash as model

    # keys of 32 lie four to a row, values of 16 eight: they pack apart
    d = lm_tiny.tiny_mimo_config(
        layers=3, head_dim=32, swa_head_dim=32, num_key_value_heads=8,
        swa_num_key_value_heads=8)
    return model, model.MimoV2FlashConfig.from_dict(d), ref, d


def _falcon():
    from benchmark.reference import falcon_h1_ref as ref
    from can_tpu.models import falcon_h1 as model

    d = lm_tiny.tiny_falcon_config()
    d.update(head_dim=16, num_attention_heads=8, num_key_value_heads=8)
    return model, model.FalconH1Config.from_dict(d), ref, d


PACKED = {"k-exaone": _exaone, "lfm2": _lfm2, "mimo-v2-flash": _mimo,
          "falcon-h1": _falcon}


@pytest.mark.parametrize("name", sorted(PACKED))
def test_a_prefill_hands_over_the_layout_s_leaves_where_heads_pack(name):
    """8 key/value heads of 16 lie eight to a row of 128 lanes
    (``kv_pack``): every layer's entry from ``prefill`` has exactly the
    shapes and dtypes ``serve/cache.py::allocate`` gives from
    ``cache_layout(cfg)``, and prefill followed by two decode steps (the
    second ring slot, the rows past the prompt) gives the reference's
    logits over prompt + tokens."""
    model, cfg, ref, d = PACKED[name]()
    params = model.init_params(jax.random.key(3), cfg, jnp.float32)
    specs = model.cache_layout(cfg)
    kv = [s for layer in specs for s in layout.parts(layer)
          if s.kind in (layout.FULL, layout.RING)]
    assert kv and all(rows[0] < 8 for s in kv for _, rows, _ in s.leaves)
    bucket, cache_len = 16, 24
    rng = np.random.default_rng(4)
    lengths = np.asarray([16, 11], np.int32)
    tokens = np.zeros((2, bucket), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, 256, n)
    logits, cache, _ = jax.jit(model.prefill, static_argnums=(3, 4))(
        params, jnp.asarray(tokens), jnp.asarray(lengths), cfg, cache_len)
    step = jax.jit(model.decode_step, static_argnums=(4,))
    want = kv_cache.allocate(specs, slots=2, positions=cache_len,
                             dtype=jnp.float32)
    form = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert form(cache) == form(want)
    seqs = [list(tokens[i, :n]) for i, n in enumerate(lengths)]
    steps, pos = [np.asarray(logits)], jnp.asarray(lengths)
    for _ in range(2):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for s, t in zip(seqs, np.asarray(tok)):
            s.append(int(t))
        logits, cache, _ = step(params, cache, tok, pos, cfg)
        steps.append(np.asarray(logits))
        pos = pos + 1
    assert form(cache) == form(want)
    spec = ref.spec_from_config(d)
    for i, n in enumerate(lengths):
        full = np.asarray(ref.forward(params, np.asarray(seqs[i], np.int32),
                                      spec)["logits"])
        for s, got in enumerate(steps):
            np.testing.assert_allclose(got[i], full[n - 1 + s], atol=3e-5,
                                       rtol=3e-5)


# -- (b) the specs the cells use --------------------------------------------
CELL_SPECS = {
    # K-EXAONE's and Falcon-H1's full layers: a head of 128 a row
    "full-128": (dict(kind=layout.FULL, kv_heads=8, head_dim=128), False),
    # LFM2's: heads of 64 in pairs
    "full-64-in-pairs": (dict(kind=layout.FULL, kv_heads=8, head_dim=64),
                         False),
    # MiMo's full layers: keys 192 two to a row of 384, values 128 a row
    "full-192-beside-128": (dict(kind=layout.FULL, kv_heads=4, head_dim=192,
                                 v_head_dim=128), False),
    # K-EXAONE's window layers, and MiMo's (8 heads, a learned sink)
    "ring-128": (dict(kind=layout.RING, kv_heads=8, head_dim=128,
                      window=128), False),
    "ring-128-sink": (dict(kind=layout.RING, kv_heads=8, head_dim=192,
                           v_head_dim=128, window=128), True),
}


def _plain(q, k, v, lo, sink):
    """Softmax attention of one query (KV, G, D) over positions ``lo ..``
    of ``k`` (S, KV, D), ``v`` (S, KV, Dv), in float64; ``sink`` (KV, G)
    joins the denominator."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k[lo:], v[lo:]))
    s = np.einsum("kgd,skd->kgs", q, k) * q.shape[-1] ** -0.5
    m = s.max(-1, keepdims=True)
    if sink is not None:
        m = np.maximum(m, np.asarray(sink, np.float64)[..., None])
    e = np.exp(s - m)
    den = e.sum(-1, keepdims=True)
    if sink is not None:
        den = den + np.exp(np.asarray(sink, np.float64)[..., None] - m)
    return np.einsum("kgs,skd->kgd", e / den, v)


@pytest.mark.parametrize("name", sorted(CELL_SPECS))
def test_a_step_over_a_prompt_s_entry_is_plain_attention(name):
    """``kv_decode`` over the entry ``kv_entry`` wrote equals plain softmax
    attention over the same prompt plus the token, for two prompts of
    unequal length in one batch: a full layer sees every position up to the
    token's, a ring the newest ``window`` (the longer prompt has wrapped it,
    the shorter has not filled it)."""
    kw, with_sink = CELL_SPECS[name]
    kw = dict(kw)
    spec = layout.kv_layer(kw.pop("kind"), **kw)
    kv, d, dv = kw["kv_heads"], kw["head_dim"], kw.get("v_head_dim",
                                                       kw["head_dim"])
    g, b, l, cache_len = 2, 2, 256, 384
    lengths = jnp.asarray([200, 77], jnp.int32)
    rng = np.random.default_rng(7)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    k, v = normal(b, l, kv, d), normal(b, l, kv, dv)
    q, nk, nv = normal(b, 1, kv, g, d), normal(b, 1, kv, d), normal(b, 1, kv, dv)
    sink = normal(kv * g) + 2.0 if with_sink else None

    @jax.jit
    def prompt_then_step(k, v, q, nk, nv, lengths, sink):
        entry = lb.kv_entry(spec, k, v, lengths, cache_len)
        return entry, lb.kv_decode(spec, q, nk, nv, entry, lengths,
                                   lengths[:, None], ("attn.core",), sink)

    entry, (o, written) = prompt_then_step(k, v, q, nk, nv, lengths, sink)
    for leaves in (entry, written):
        assert {n: a.shape for n, a in leaves.items()} == spec.shapes(
            b, cache_len)
    for i, n in enumerate(np.asarray(lengths)):
        ks = np.concatenate([k[i, :n], nk[i]])
        vs = np.concatenate([v[i, :n], nv[i]])
        lo = max(0, n + 1 - spec.window) if spec.kind == layout.RING else 0
        want = _plain(q[i, 0], ks, vs, lo,
                      None if sink is None else sink.reshape(kv, g))
        np.testing.assert_allclose(np.asarray(o[i]), want, atol=2e-5,
                                   rtol=2e-5)


# -- (c) the fence ----------------------------------------------------------
MODEL_FILES = sorted(f for f in os.listdir(
    os.path.join(ROOT, "can_tpu", "models")) if f.endswith(".py"))
LEAF_WRITERS = {"write_slot", "ring_entry", "as_leaf"}


@pytest.mark.parametrize("name", MODEL_FILES)
def test_no_model_s_module_fills_a_key_value_leaf_itself(name):
    """Outside ``ops/attention.py`` and ``lm_blocks.kv_entry`` / ``kv_decode``
    no module under ``can_tpu/models/`` names ``write_slot``, ``ring_entry``
    or ``as_leaf`` (GLM's ``write_row`` is its latent layer's own); the
    shared functions reach them through the module, never bound by ``from
    ... import`` (the benchmark's calibration replaces the attributes)."""
    with open(os.path.join(ROOT, "can_tpu", "models", name)) as f:
        tree = ast.parse(f.read())
    named = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in LEAF_WRITERS:
            named.append((node.attr, node.lineno, node.value))
        elif isinstance(node, ast.Name) and node.id in LEAF_WRITERS:
            named.append((node.id, node.lineno, None))
        elif isinstance(node, ast.ImportFrom):
            assert not LEAF_WRITERS & {a.name for a in node.names}, node.lineno
    if name != "lm_blocks.py":
        assert named == []
        return
    inside = {n.name: (n.lineno, n.end_lineno) for n in tree.body
              if isinstance(n, ast.FunctionDef)}
    spans = [inside["kv_entry"], inside["kv_decode"]]
    assert {n for n, _, _ in named} == LEAF_WRITERS
    for attr, line, via in named:
        assert any(lo <= line <= hi for lo, hi in spans), (attr, line)
        assert isinstance(via, ast.Name) and via.id == "attn_ops", (attr, line)
