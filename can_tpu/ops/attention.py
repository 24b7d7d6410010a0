"""Attention for a decoder: prefill over a whole prompt in blocks, and
one-token decode over a cache.  Grouped-query attention first; latent
attention (one compressed key/value latent a position for all heads) at the
end of the module.

Shapes: ``q`` (B, L, KV, G, D) with G query heads to each of the KV
key/value heads; ``k`` (B, L, KV, D), ``v`` (B, L, KV, Dv): the values' width
is their own (MiMo-V2-Flash: keys 192, values 128), the output's is ``Dv``.
Scores and the softmax are float32 whatever the inputs are; the output has
``q``'s dtype.  A layer may carry a learned SINK, one logit a query head
that joins the softmax's denominator and adds no value (``_softmax_av``).

Two kinds of layer: *full* (every earlier position) and *window*
(``i - j < window``).  Prefill never forms an L x L score matrix for a window
layer (a query block of ``window`` rows sees its own block and the one
before), and for a full layer forms it a block of queries at a time, each
against the keys up to its own end.  Decode reads a cache laid out
(B, KV, S, D): the whole context for a full layer, a ring of ``window``
slots for a window layer, in which slot ``r`` holds the newest position
``p <= pos`` with ``p % window == r``.  A position's row is a whole number
of the 128 lanes: heads whose width is not lie ``pack`` side by side in one
((B, KV / pack, S, pack * D), ``ops/cache_layout.py::kv_pack``: two heads of
64 in 128 lanes, two of 192 in 384), because a donated leaf whose last axis
is not whole lane rows is not written in place (two whole-leaf copies a
write); ``write_slot``, ``as_leaf``, ``ring_entry`` and ``decode`` read
``pack`` from the shapes they are given, keys and values each their own, 1
for every head of 128.  ONE caller fills and reads a layer's leaves with
them, for every model: ``models/lm_blocks.py::kv_entry`` (a prompt) and
``kv_decode`` (a step), from the ``LayerSpec`` the model states.

Latent attention keeps (B, S, rank) latents and (B, S, rope_dim) rotary keys,
no heads.  Its prefill rebuilds keys and values per head and runs a causal
attention with a running softmax over blocks of queries and, inside, the
blocks of keys up to the diagonal, with no work for blocks past a
sequence's own length.  That attention has two forms of one algorithm:
``prefill_causal`` here, the SCANNED form (ONE compiled body whatever the
prompt's length, where ``prefill_full`` unrolls one pair of products per
query block, each of another shape: 64 a layer at 16,384 positions; no
score block larger than ``block x block`` a head), which runs anywhere and
is the other's oracle; and the FUSED Pallas kernel of
``ops/pallas_attention.py``, in which a score block never leaves the chip.
``pallas_attention.supports`` chooses from the backend and the shapes;
nothing else does.  Two models ask it: ``models/glm_moe_lite.py::
attention_expanded`` (a key a head, 256 wide) and ``models/mimo_v2_flash.py::
_full_prefill`` (the full layers of a grouped-query model: 64 query heads of
192 over 4 key heads, values 128: ``prefill_causal`` over GROUPS is that
kernel's oracle too).  K-EXAONE's, Falcon-H1's and LFM2's full layers and
every window layer run ``prefill_full`` / ``prefill_window`` (plain
``jax.numpy``).  Latent attention's decode is the absorbed form: queries
carried into the latent space, the cache read as it is stored.  It has two
forms of one algorithm too: ``decode_latent`` here (two products around a
softmax, every allocated position), which runs anywhere and is the other's
oracle, and ``ops/pallas_latent.py::fused_latent_decode``, one launch that
reads each sequence's own context once; ``pallas_latent.supports`` chooses
(``models/glm_moe_lite.py::attention_absorbed`` asks it).  ``write_row``
writes both of its leaves, each where it lies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30  # finite: a fully masked row (a padded slot) must not make NaN
_LANES = 128   # the minor axis of a TPU tile


def rope(x, positions, theta: float):
    """Rotary embedding, rotate-half over the whole last dimension.
    ``x`` (B, L, ..., D), ``positions`` (B, L)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv      # (B, L, D/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def _softmax_av(scores, mask, v, eq, dtype, sink=None):
    """``softmax(scores) v`` over the last axis of ``scores``.  ``sink``
    (broadcast against ``scores`` with a last axis of 1): a learned logit
    per head that joins the softmax's DENOMINATOR and nothing else (it takes
    probability mass and adds no value): ``p_j = exp(s_j - m) / (sum_j'
    exp(s_j' - m) + exp(sink - m))``, ``m = max(max_j s_j, sink)``."""
    scores = jnp.where(mask, scores, NEG)
    if sink is None:
        p = jax.nn.softmax(scores, axis=-1)
    else:
        sink = sink.astype(jnp.float32)
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
        e = jnp.exp(scores - m)
        p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))
    return jnp.einsum(eq, p.astype(dtype), v,
                      preferred_element_type=jnp.float32).astype(dtype)


def prefill_full(q, k, v, *, block: int = 256):
    """Causal attention over the whole prompt, a block of queries at a time."""
    b, l, kv, g, d = q.shape
    block = min(block, l)
    if l % block:
        raise ValueError(f"prompt bucket {l} is not a multiple of the query "
                         f"block {block}")
    scale = d ** -0.5
    outs = []
    for i in range(l // block):
        hi = (i + 1) * block
        qb = q[:, i * block:hi]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k[:, :hi],
                       preferred_element_type=jnp.float32) * scale
        mask = (jnp.arange(hi)[None, :]
                <= (i * block + jnp.arange(block))[:, None])
        outs.append(_softmax_av(s, mask, v[:, :hi], "bkgqs,bskd->bqkgd",
                                q.dtype))
    return jnp.concatenate(outs, axis=1)


def prefill_window(q, k, v, *, window: int, sink=None):
    """Causal attention with ``i - j < window``: blocks of ``window``
    queries, each against its own block of keys and the one before.
    ``sink`` (KV, G): a learned logit a query head in the softmax's
    denominator (``_softmax_av``); None: a plain softmax."""
    b, l, kv, g, d = q.shape
    if l % window:
        raise ValueError(f"prompt bucket {l} is not a multiple of the "
                         f"window {window}")
    nb = l // window
    qb = q.reshape(b, nb, window, kv, g, d)

    def two_blocks(x):
        xb = x.reshape(b, nb, window, kv, x.shape[-1])
        prev = jnp.pad(xb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
        return jnp.concatenate([prev, xb], axis=2)   # (B, nb, 2W, KV, D)

    kk, vv = two_blocks(k), two_blocks(v)
    s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, kk,
                   preferred_element_type=jnp.float32) * d ** -0.5
    qi = jnp.arange(window)[:, None] + window         # in two-block frame
    kj = jnp.arange(2 * window)[None, :]
    mask = (kj <= qi) & (qi - kj < window)
    first = mask & (kj >= window)                     # block 0 has no "before"
    mask = jnp.where((jnp.arange(nb) == 0)[:, None, None], first, mask)
    out = _softmax_av(s, mask[None, :, None, None], vv,
                      "bnkgqs,bnskd->bnqkgd", q.dtype,
                      None if sink is None else sink[:, :, None, None])
    return out.reshape(b, l, kv, g, v.shape[-1])


def decode(q, k_cache, v_cache, valid, sink=None):
    """One query per sequence against its cache.  ``q`` (B, KV, G, D);
    keys (B, KV / pack, S, pack * D), ``pack`` heads side by side in a row
    (``cache_layout.kv_pack``; read here from the shapes: 1 for heads of
    whole lanes); values the same with their OWN width ``Dv`` and their own
    ``pack`` (keys 192 wide lie two to a row of 384, their values of 128 a
    head a row); ``valid`` (B, S): which slots hold a position this query
    may see; ``sink`` (KV, G): a learned logit a query head in the
    softmax's denominator, or None.

    Over packed rows the group axis takes the queries of all the row's
    heads (``pack * G`` a row), each zero outside its own head's ``D``
    lanes, and each keeps its own head's ``Dv`` lanes of the result.  Exact:
    the other heads' lanes add ``key * 0`` to a float32 score and their
    half of the values is dropped; ``pack`` times the arithmetic of two
    small products, in a step the cache's bytes bound."""
    b, kv, g, d = q.shape
    rows = k_cache.shape[1]
    pack = kv // rows
    if pack > 1:
        own = jnp.eye(pack, dtype=q.dtype)     # head p of a row: lanes p
        q = jnp.einsum("brpgd,pl->brpgld", q.reshape(b, rows, pack, g, d),
                       own).reshape(b, rows, pack * g, pack * d)
    s = jnp.einsum("bkgd,bksd->bkgs", q, k_cache,
                   preferred_element_type=jnp.float32) * d ** -0.5
    rows_v = v_cache.shape[1]
    pack_v = kv // rows_v
    dv = v_cache.shape[-1] // pack_v
    # the scores by the VALUES' rows (head = row * pack + p on both sides:
    # nothing moves; the same shape where keys and values pack alike)
    s = s.reshape(b, rows_v, pack_v * g, s.shape[-1])
    if sink is not None:
        sink = sink.reshape(rows_v, pack_v * g, 1)
    o = _softmax_av(s, valid[:, None, None, :], v_cache,
                    "bkgs,bksd->bkgd", q.dtype, sink)
    if pack_v > 1:
        own_v = own if pack_v == pack else jnp.eye(pack_v, dtype=q.dtype)
        o = jnp.einsum("brpgld,pl->brpgd",
                       o.reshape(b, rows_v, pack_v, g, pack_v, dv),
                       own_v).reshape(b, kv, g, dv)
    return o


def ring_positions(pos, window: int):
    """(B, W): the position each ring slot holds once the token at ``pos``
    (B,) has been written; negative where the slot is still empty."""
    r = jnp.arange(window)[None, :]
    return pos[:, None] - jnp.mod(pos[:, None] - r, window)


def as_leaf(x, shape):
    """A prompt's keys or values ``x`` (B, L, KV, D) as a cache leaf of
    ``shape`` (B, rows, positions, width) (``LayerSpec.shapes``): the heads
    of a row side by side as ``write_slot`` writes them, the positions past
    ``L`` zero."""
    b, rows, positions, width = shape
    l = x.shape[1]
    x = x.reshape(b, l, rows, width).transpose(0, 2, 1, 3)
    return jnp.pad(x, ((0, 0), (0, 0), (0, positions - l), (0, 0)))


def ring_entry(k, v, lengths, window: int, shapes) -> dict:
    """A prompt's keys and values (B, L, KV, D / Dv) as a window layer's
    ring: slot ``r`` takes the newest position ``p < length`` with ``p %
    window == r`` (a slot no position has reached yet holds position 0's
    row, which ``decode``'s ``valid`` never admits).  ``shapes``
    (``LayerSpec.shapes``): the leaves' (B, rows, window, width), the heads
    of a row side by side as ``write_slot`` writes them."""
    b, l = k.shape[:2]
    k = k.reshape(b, l, *shapes["k"][1::2])
    v = v.reshape(b, l, *shapes["v"][1::2])
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    held = ring_positions(lengths - 1, window)
    take = jnp.clip(held, 0, l - 1)[:, None, :, None]
    return {"k": jnp.take_along_axis(kt, take, axis=2),
            "v": jnp.take_along_axis(vt, take, axis=2)}


def write_slot(cache, new, slot):
    """``cache`` (B, KV, S, D) with ``new`` (B, KV, D) written at ``slot``
    (B,), one slot per sequence.  Over packed rows (``cache`` (B, KV /
    pack, S, pack * D), ``cache_layout.kv_pack``) ``new`` is the same
    (B, KV, D): the heads of a row are adjacent in it, so the reshape to the
    cache's rows below places them side by side and moves nothing.

    The rows are written by a scatter over the MERGED (sequence x head)
    axis, one indexed axis in front of the positions.  Indexed as
    ``cache.at[arange(B), :, slot]`` the head axis is a window dimension
    between two indexed ones, and XLA:TPU re-lays the whole cache with the
    positions above the heads, scatters there and copies it back to the
    layout the donated argument and ``decode`` have: two copies of every
    layer's whole cache a step (PERF.md section 6, PR 37).  Merged, the
    write is ``bitcast -> scatter -> bitcast`` on the argument's own buffer.
    The merge is a bitcast where the positions are a whole number of the
    leaf's tile rows (8 of float32, 16 of bfloat16: 1,280 and a window of
    128 are); elsewhere it is a correct write that may cost a copy."""
    b, kv, s, d = cache.shape
    rows = cache.reshape(b * kv, s, d)
    rows = rows.at[jnp.arange(b * kv), jnp.repeat(slot, kv)].set(
        new.reshape(b * kv, d).astype(cache.dtype), unique_indices=True)
    return rows.reshape(b, kv, s, d)


# -- latent attention -------------------------------------------------------
def prefill_causal(q, k, v, lengths=None, *, scale=None, block: int = 1024):
    """Causal attention over whole prompts with a running softmax.

    ``q`` (B, L, H, D), ``k`` (B, L, KV, D), ``v`` (B, L, KV, Dv) -> (B, L,
    H, Dv); query heads ``c * G .. c * G + G - 1`` read key/value head ``c``
    (G = H / KV; latent attention rebuilds a key a head: KV = H).  One
    sequence at a time (``lax.map``); its queries in blocks of ``block``,
    each against the key blocks before it (no mask) and its own (the causal
    mask).  ``lengths`` (B,): blocks of queries wholly past a sequence's
    length are not computed and stay zero (no valid position reads them)."""
    b, l, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    block = min(block, l)
    if l % block:
        raise ValueError(f"prompt bucket {l} is not a multiple of the "
                         f"attention block {block}")
    scale = d ** -0.5 if scale is None else scale
    dv = v.shape[-1]
    if lengths is None:
        lengths = jnp.full((b,), l, jnp.int32)
    causal = jnp.arange(block)[None, :] <= jnp.arange(block)[:, None]

    def one(args):
        qs, ks, vs, n = args                       # (L, H, D) of one sequence

        def q_block(i, out):
            qb = jax.lax.dynamic_slice_in_dim(qs, i * block, block, 0)

            def k_block(j, carry, mask=None):
                m, den, acc = carry
                kb = jax.lax.dynamic_slice_in_dim(ks, j * block, block, 0)
                vb = jax.lax.dynamic_slice_in_dim(vs, j * block, block, 0)
                if g == 1:
                    s = jnp.einsum("qhd,khd->hqk", qb, kb,
                                   preferred_element_type=jnp.float32) * scale
                else:   # a group's queries against the one key head they share
                    s = jnp.einsum(
                        "qcgd,kcd->cgqk", qb.reshape(block, kv, g, d), kb,
                        preferred_element_type=jnp.float32,
                    ).reshape(h, block, block) * scale
                if mask is not None:
                    s = jnp.where(mask, s, NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                fade = jnp.exp(m - m_new)
                if g == 1:
                    pv = jnp.einsum("hqk,khd->hqd", p.astype(vs.dtype), vb,
                                    preferred_element_type=jnp.float32)
                else:
                    pv = jnp.einsum(
                        "cgqk,kcd->cgqd",
                        p.astype(vs.dtype).reshape(kv, g, block, block), vb,
                        preferred_element_type=jnp.float32,
                    ).reshape(h, block, dv)
                return (m_new, den * fade + jnp.sum(p, axis=-1),
                        acc * fade[..., None] + pv)

            carry = (jnp.full((h, block), NEG, jnp.float32),
                     jnp.zeros((h, block), jnp.float32),
                     jnp.zeros((h, block, dv), jnp.float32))
            carry = jax.lax.fori_loop(0, i, k_block, carry)
            _, den, acc = k_block(i, carry, causal)
            o = (acc / den[..., None]).astype(qs.dtype).transpose(1, 0, 2)
            return jax.lax.dynamic_update_slice_in_dim(out, o, i * block, 0)

        blocks = jnp.clip((n + block - 1) // block, 1, l // block)
        return jax.lax.fori_loop(0, blocks, q_block,
                                 jnp.zeros((l, h, dv), qs.dtype))

    return jax.lax.map(one, (q, k, v, lengths.astype(jnp.int32)))


def decode_latent(q_lat, q_rope, ckv, krope, valid, *, scale: float):
    """One query per sequence against a latent cache, in the latent space:
    the PLAIN form (two products around a softmax whose float32 scores go
    through HBM, every allocated position read twice), which runs anywhere
    and is the oracle of the fused kernel (``ops/pallas_latent.py``: one
    launch that reads each sequence's own context once, where its
    ``supports`` says it can run).
    ``q_lat`` (B, H, R): the queries' no-rotary part carried through the key
    up-projection; ``q_rope`` (B, H, Dr); ``ckv`` (B, S, R), ``krope``
    (B, S, Dr); ``valid`` (B, S) -> the attended latents (B, H, R), which the
    value up-projection turns into the heads' outputs."""
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, krope,
                      preferred_element_type=jnp.float32)) * scale
    return _softmax_av(s, valid[:, None, :], ckv, "bhs,bsr->bhr", q_lat.dtype)


# A narrow leaf of at most this many positions a slot is written by a select
# over the whole leaf, a longer one by an update a slot (``write_row``): an
# update is about 1.04 us whatever the leaf (PR 49's reading), the select
# 2 x positions x 64 x 2 B a slot at an elementwise pass's 590-750 GB/s, so
# they cross near 2,400-3,000 positions.  TIMED at the two cells' shapes by
# ``tools/latent_decode_forms.py --shape glm|longcat`` (one layer's two writes
# + attention, ms, updates | select; my chip run, PR 50): 256 slots x 1,280
# positions 1.503 | 1.210; 16 slots x 16,512 positions 0.418 | 0.565.  In
# LongCat's whole step the 2,048 updates hid behind the weights' prefetches
# (``req_per_s`` the same); what they cost was a compiled step of 3,994
# instructions against 1,065 and a device trace that held a third of three
# launches (PERF.md section 6, PR 50)
SELECT_MAX_POSITIONS = 2048


def write_row(cache, new, pos):
    """``cache`` (B, S, D) with ``new`` (B, D) written at position ``pos``
    (B,), one row per sequence, where the leaf lies.

    A leaf of whole lane rows (the latent, 512) is row-major on the chip and
    one scatter over its two leading axes writes it in place.  A leaf
    NARROWER than the 128 lanes (the rotary keys, 64) XLA:TPU keeps with the
    POSITIONS minor (``bf16[16,16512,64]{1,2,0}``: how a donated argument
    arrives and has to leave, and how ``ops/pallas_latent.py`` reads it); the
    scatter wants it row-major and re-laid the whole leaf on the way in and
    back on the way out, twice a layer and step (PERF.md section 6, PR 49).
    A ``dynamic_update_slice`` takes a leaf in whatever layout it has: one a
    sequence there, no copy.  Each is a small program of its own, about a
    microsecond (96 a GLM step read 0.10 ms, PR 49), so their cost is the
    SLOTS' count; a select over the whole leaf (one elementwise pass in the
    leaf's own layout, in place where it is donated) costs the leaf's bytes
    twice.  Both grow with the slots, so what chooses is the positions a
    slot holds: at or under ``SELECT_MAX_POSITIONS`` the select (LongCat's
    256 slots of 1,280: 8 leaves x 256 updates would be 2,048 programs a
    step), over it an update a slot (GLM's 16 of 16,512: 34 MB a leaf to
    rewrite for 16 rows).  Off a TPU no layout is at stake and the scatter
    stands."""
    b, s, d = cache.shape
    new = new.astype(cache.dtype)
    if d % _LANES == 0 or jax.default_backend() != "tpu":
        return cache.at[jnp.arange(b), pos].set(new)
    if s <= SELECT_MAX_POSITIONS:
        # (a position the leaf does not hold writes nothing, as the scatter)
        hit = jnp.arange(s)[None, :, None] == pos[:, None, None]
        return jnp.where(hit, new[:, None, :], cache)
    # (a position the leaf does not hold: the scatter drops it, this clamps
    # it to the leaf's last row; a step never asks for one)
    for i in range(b):
        cache = jax.lax.dynamic_update_slice(cache, new[i][None, None],
                                             (i, pos[i], 0))
    return cache
