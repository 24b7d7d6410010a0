"""A launch's decoding cache: what every layer keeps of the positions it
has seen, on the device between the programs of one launch (prefill writes
it, every decode step reads it and writes one position).

A layer's entry is described by its kind (``ops/cache_layout.py``'s
``LayerSpec``, which the model states for each of its layers:
``serve/programs.py`` asks it): ``full`` and ``ring`` keep keys and values
per head, ``latent`` the compressed latent and the one rotary key of a
position, no heads, ``state`` what a recurrence carries from one position to
the next: no position axis, a size fixed whatever the context, a leaf in
float32 where the model says so.  One sequence's positions (of one head,
where there are heads) are contiguous for the decode step's product.  Kinds
live side by side in one cache, and in one LAYER where the model's block
keeps two (a tuple of specs: their leaves share the layer's entry).

What this module asks of a model: ``cache_layout(cfg)``, for each layer
held one ``LayerSpec`` or a tuple of them with distinct leaf names; and that
every leaf has the slots as its leading axis (a prefill slice is written at
its first slot, whatever the leaf's kind).  Nothing outside this module and
the model that reads its own entries names a leaf: the engine asks
``signature_leaves`` and ``nbytes_by_kind``.

Static batches: the cache is allocated per launch and dropped with it.  A
cache that outlives its launch (prefix reuse, sessions) is not built yet
(ROADMAP, Reach B).
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax.numpy as jnp

from can_tpu.ops.cache_layout import STATE, LayerSpec, parts


def allocate(specs: Sequence, *, slots: int, positions: int,
             dtype=jnp.bfloat16) -> dict:
    """Zeros: ``{"layers": [one entry per layer]}``, every leaf in ``dtype``
    but those whose spec states their own.  Call it under ``jax.jit``
    (static arguments) to make the cache on the device."""
    layers = []
    for layer in specs:
        entry = {}
        for spec in parts(layer):
            own = dict(spec.dtypes)
            for name, shape in spec.shapes(slots, positions).items():
                if name in entry:
                    raise ValueError(f"two leaves named {name!r} in one layer")
                entry[name] = jnp.zeros(shape, own.get(name, dtype))
        layers.append(entry)
    return {"layers": layers}


def nbytes_by_kind(cache: dict, specs: Sequence) -> Dict[str, int]:
    """Bytes the cache holds, by kind, for every kind present."""
    out: Dict[str, int] = {}
    for layer, entry in zip(specs, cache["layers"]):
        for spec in parts(layer):
            out[spec.kind] = out.get(spec.kind, 0) + sum(
                int(entry[name].size) * entry[name].dtype.itemsize
                for name, _, _ in spec.leaves)
    return out


def signature_leaves(cache: dict, specs: Sequence) -> list:
    """One array per layer whose shape and dtype tell two caches apart (the
    launch size and the context both show in it): for a compile signature.
    The first leaf by name of the layer's first part that has positions; of
    a layer that keeps a state alone, its first leaf."""
    out = []
    for layer, entry in zip(specs, cache["layers"]):
        kinds = parts(layer)
        spec = next((s for s in kinds if s.kind != STATE), kinds[0])
        out.append(entry[min(name for name, _, _ in spec.leaves)])
    return out
