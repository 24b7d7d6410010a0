"""How one layer's entry of a decoding cache is laid out: the description a
model gives of each of its layers (``cache_layout(cfg)``) and
``serve/cache.py`` allocates from.  It sits beside ``ops/attention.py``,
whose writes and reads (``write_slot``, ``as_leaf``, ``write_row``,
``decode``, ``decode_latent``; ``ops/pallas_latent.py``'s fused form of the
last) assume these leaves; the models and the
serving path both import it, and neither imports the other for it.  A
key/value layer's leaves are filled and read in one place for every model,
``models/lm_blocks.py::kv_entry`` / ``kv_decode``, which ask the layer's
``LayerSpec`` for a leaf's shape and its window, never a config.

* ``full``: keys and values of every position of the context,
  ``{"k", "v"}`` of (slots, rows, positions, width), each leaf with its own
  width (keys ``head_dim``, values ``v_head_dim`` where the model gives
  one).  **A position's row is a whole number of lanes:** heads of 128 are
  a row each (rows = kv_heads, width = head_dim); heads of another width lie
  ``pack`` side by side, the FEWEST whose row is whole lanes (two of 64 in
  128, two of 192 in 384), heads ``pack * j .. pack * j + pack - 1`` in row
  ``j`` (rows = kv_heads / pack, width = pack * head_dim: the key
  projection's own output (B, L, kv_heads * head_dim) reshaped, no data
  moves, no lane is padding).  ``kv_pack`` says how many: 1 where ``pack``
  does not divide ``kv_heads``.  Why: XLA:TPU hands a leaf whose last axis
  is not whole lane rows on with the POSITIONS minor, so a donated leaf of
  64-wide heads was copied whole into another layout and whole back around
  every one-row write (PERF.md section 6, PR 39); a leaf of whole lane rows
  is written where it lies.  The bytes are the same;
* ``ring`` (window attention): the newest ``window`` positions, slot
  ``p % window`` for position ``p``, the same two leaves with ``window``
  positions;
* ``latent`` (latent attention): no heads; per position the compressed
  key/value latent and the one rotary key all heads share, ``{"ckv"
  (slots, positions, rank), "krope" (slots, positions, rope_dim)}``.  The
  latent's rows are whole lanes and row-major on the chip.  The rotary keys
  (64 wide) are NOT, and have no second head to lie beside: XLA:TPU keeps
  that leaf with the positions minor, and both its users take it so
  (``attention.write_row`` writes it a ``dynamic_update_slice`` a slot, not
  a scatter; ``ops/pallas_latent.py`` reads it as (slots, rope_dim,
  positions), a bitcast: PERF.md section 6, PR 49);
* ``state`` (a recurrence): NO position axis, the size fixed whatever the
  context: each leaf ``(slots, *dimensions)`` of whatever the layer carries
  from one position to the next (a state-space mixer's state, its
  convolution's last inputs).  A leaf may state its dtype (an accumulator
  in float32 beside bfloat16 neighbours); the others take the cache's.

A layer whose block keeps two kinds (attention and a recurrence side by
side), or one kind twice (two latent attention sublayers: ``latent_layer``'s
``index``), is described by a tuple of ``LayerSpec``, one a part; its leaves
share the layer's one entry, so their names differ.  ``parts`` reads either
form.

A decode step writes ONE position of a leaf that has positions, in place:
``write_slot`` merges the axes in front of the positions (slots x heads)
into one before it scatters, because XLA:TPU's scatter takes its indexed
axes adjacent and major, and with the heads between two indexed axes it
copies the whole leaf into another layout and back around the row (PERF.md
section 6, PR 37).  ``positioned_leaves`` names the arrays this holds for.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

FULL, RING, LATENT, STATE = "full", "ring", "latent", "state"
_LANES = 128   # the minor axis of a TPU tile: a row a leaf is stored in


class LayerSpec(NamedTuple):
    """One kind's part of a layer's entry: its kind, its leaves as (name,
    dimensions before the positions, width of a position) and, for a ring,
    how many positions it holds (None: the whole context).  A ``state``
    leaf is (name, its dimensions but the last, the last) and has no
    positions; ``dtypes`` names the leaves that do not take the cache's
    dtype, as (name, dtype name)."""

    kind: str
    leaves: Tuple[Tuple[str, Tuple[int, ...], int], ...]
    window: Optional[int] = None
    dtypes: Tuple[Tuple[str, str], ...] = ()

    def shapes(self, slots: int, positions: int) -> Dict[str, tuple]:
        if self.kind == STATE:
            return {name: (slots, *lead, width)
                    for name, lead, width in self.leaves}
        held = positions if self.window is None else self.window
        return {name: (slots, *lead, held, width)
                for name, lead, width in self.leaves}


def parts(layer) -> Tuple[LayerSpec, ...]:
    """A layer's description, one ``LayerSpec`` or a tuple of them, as the
    tuple."""
    return (layer,) if isinstance(layer, LayerSpec) else tuple(layer)


def _leaves(specs, cache: dict, keep) -> list:
    return [entry[name] for layer, entry in zip(specs, cache["layers"])
            for spec in parts(layer) if keep(spec)
            for name, _, _ in spec.leaves]


def positioned_leaves(specs, cache: dict) -> list:
    """The arrays of ``cache`` (``serve/cache.py::allocate``'s, of the
    layers ``specs``) that have a position axis: every kind's but
    ``state``'s, which a step rewrites whole."""
    return _leaves(specs, cache, lambda spec: spec.kind != STATE)


def state_leaves(specs, cache: dict) -> list:
    """The arrays of ``cache`` that have NO position axis (the ``state``
    kind's): a step reads and rewrites each whole, where it lies if the
    program is sound (PERF.md section 6, PR 47)."""
    return _leaves(specs, cache, lambda spec: spec.kind == STATE)


def kv_pack(kv_heads: int, head_dim: int) -> int:
    """How many heads share a row of a key/value leaf: the fewest whose row
    is a whole number of the 128 lanes (1 of 128, 2 of 64 or of 192, 4 of
    32), where the heads come out in whole rows; else 1 (whole lane rows or
    none: no partial packing)."""
    pack = _LANES // math.gcd(int(head_dim), _LANES)
    return pack if int(kv_heads) % pack == 0 else 1


def kv_layer(kind: str, *, kv_heads: int, head_dim: int,
             window: Optional[int] = None,
             v_head_dim: Optional[int] = None) -> LayerSpec:
    """Keys and values per head: ``full``, or ``ring`` over ``window``; keys
    ``head_dim`` wide, values ``v_head_dim`` (None: as the keys); a
    position's row of either leaf holds ITS ``kv_pack`` heads side by
    side."""
    if kind not in (FULL, RING):
        raise ValueError(f"unknown cache layer kind {kind!r}")
    if kind == RING and not window:
        raise ValueError("a ring layer needs its window")
    def leaf(name, width):
        pack = kv_pack(kv_heads, width)
        return name, (int(kv_heads) // pack,), pack * int(width)

    leaves = (leaf("k", head_dim), leaf("v", v_head_dim or head_dim))
    return LayerSpec(kind, leaves, int(window) if kind == RING else None)


def latent_layer(*, rank: int, rope_dim: int,
                 index: Optional[int] = None) -> LayerSpec:
    """``rank + rope_dim`` numbers a position, whatever the number of heads.
    ``index``: which of a layer's latent sublayers this is, where a block
    has more than one: its leaves are ``ckv<index>`` / ``krope<index>``
    (``latent_leaves``), so that two specs share one entry."""
    ckv, krope = latent_leaves(index)
    return LayerSpec(LATENT, ((ckv, (), int(rank)),
                              (krope, (), int(rope_dim))))


def latent_leaves(index: Optional[int] = None) -> Tuple[str, str]:
    """The names of a latent sublayer's two leaves in its layer's entry."""
    tail = "" if index is None else str(int(index))
    return "ckv" + tail, "krope" + tail


def state_layer(**leaves) -> LayerSpec:
    """What a recurrence carries, no positions: ``name=(dimensions after the
    slots, dtype name or None for the cache's own)``."""
    return LayerSpec(
        STATE,
        tuple((n, tuple(int(d) for d in dims[:-1]), int(dims[-1]))
              for n, (dims, _) in leaves.items()),
        dtypes=tuple((n, str(dt)) for n, (_, dt) in leaves.items()
                     if dt is not None))
