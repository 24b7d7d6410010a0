"""How one layer's entry of a decoding cache is laid out: the description a
model gives of each of its layers (``cache_layout(cfg)``) and
``serve/cache.py`` allocates from.  It sits beside ``ops/attention.py``,
whose writes and reads (``write_slot``, ``write_row``, ``decode``,
``decode_latent``) assume these leaves; the models and the serving path
both import it, and neither imports the other for it.

* ``full``: keys and values of every position of the context,
  ``{"k", "v"}`` of (slots, kv_heads, positions, head_dim);
* ``ring`` (window attention): the newest ``window`` positions, slot
  ``p % window`` for position ``p``, the same two leaves with ``window``
  positions;
* ``latent`` (latent attention): no heads; per position the compressed
  key/value latent and the one rotary key all heads share, ``{"ckv"
  (slots, positions, rank), "krope" (slots, positions, rope_dim)}``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

FULL, RING, LATENT = "full", "ring", "latent"


class LayerSpec(NamedTuple):
    """One layer's entry: its kind, its leaves as (name, dimensions before
    the positions, width of a position) and, for a ring, how many positions
    it holds (None: the whole context)."""

    kind: str
    leaves: Tuple[Tuple[str, Tuple[int, ...], int], ...]
    window: Optional[int] = None

    def shapes(self, slots: int, positions: int) -> Dict[str, tuple]:
        held = positions if self.window is None else self.window
        return {name: (slots, *lead, held, width)
                for name, lead, width in self.leaves}


def kv_layer(kind: str, *, kv_heads: int, head_dim: int,
             window: Optional[int] = None) -> LayerSpec:
    """Keys and values per head: ``full``, or ``ring`` over ``window``."""
    if kind not in (FULL, RING):
        raise ValueError(f"unknown cache layer kind {kind!r}")
    if kind == RING and not window:
        raise ValueError("a ring layer needs its window")
    leaves = tuple((n, (int(kv_heads),), int(head_dim)) for n in ("k", "v"))
    return LayerSpec(kind, leaves, int(window) if kind == RING else None)


def latent_layer(*, rank: int, rope_dim: int) -> LayerSpec:
    """``rank + rope_dim`` numbers a position, whatever the number of heads."""
    return LayerSpec(LATENT, (("ckv", (), int(rank)),
                              ("krope", (), int(rope_dim))))
