"""A launch's decoding cache: keys and values of every layer, on the
device between the programs of one launch (prefill writes it, every decode
step reads it and writes one position).

Two kinds of layer live in it side by side: a ``full`` layer keeps every
position of the context, a ``ring`` layer (window attention) the newest
``window`` positions, slot ``p % window`` for position ``p``.  Entries are
laid out (slots, kv_heads, positions, head_dim): one sequence's positions
of one head are contiguous for the decode step's product.

Static batches: the cache is allocated per launch and dropped with it.  A
cache that outlives its launch (prefix reuse, sessions) is not built yet
(ROADMAP, Reach B).
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax.numpy as jnp

FULL, RING = "full", "ring"


def entry_shape(kind: str, *, slots: int, kv_heads: int, head_dim: int,
                positions: int, window: int):
    if kind not in (FULL, RING):
        raise ValueError(f"unknown cache layer kind {kind!r}")
    return (slots, kv_heads, positions if kind == FULL else window, head_dim)


def allocate(layer_kinds: Sequence[str], *, slots: int, kv_heads: int,
             head_dim: int, positions: int, window: int,
             dtype=jnp.bfloat16) -> dict:
    """Zeros: ``{"layers": [{"k", "v"} per layer]}``.  Call it under
    ``jax.jit`` (static arguments) to make the cache on the device."""
    out = []
    for kind in layer_kinds:
        shape = entry_shape(kind, slots=slots, kv_heads=kv_heads,
                            head_dim=head_dim, positions=positions,
                            window=window)
        out.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)})
    return {"layers": out}


def nbytes_by_kind(cache: dict, layer_kinds: Sequence[str]) -> Dict[str, int]:
    """Bytes the cache holds, by kind of layer."""
    out = {FULL: 0, RING: 0}
    for kind, entry in zip(layer_kinds, cache["layers"]):
        out[kind] += sum(int(a.size) * a.dtype.itemsize for a in entry.values())
    return out
