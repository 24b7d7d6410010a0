"""A launch's decoding cache: what every layer keeps of the positions it
has seen, on the device between the programs of one launch (prefill writes
it, every decode step reads it and writes one position).

A layer's entry is described by its kind (``ops/cache_layout.py``'s
``LayerSpec``, which the model states for each of its layers:
``serve/programs.py`` asks it): ``full`` and ``ring`` keep keys and values
per head, ``latent`` the compressed latent and the one rotary key of a
position, no heads.  One sequence's positions (of one head, where there are
heads) are contiguous for the decode step's product.  Kinds live side by
side in one cache.  Nothing outside this module and the model that reads
its own entries names a leaf: the engine asks ``signature_leaves`` and
``nbytes_by_kind``.

Static batches: the cache is allocated per launch and dropped with it.  A
cache that outlives its launch (prefix reuse, sessions) is not built yet
(ROADMAP, Reach B).
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax.numpy as jnp

from can_tpu.ops.cache_layout import LayerSpec


def allocate(specs: Sequence[LayerSpec], *, slots: int, positions: int,
             dtype=jnp.bfloat16) -> dict:
    """Zeros: ``{"layers": [one entry per spec]}``.  Call it under
    ``jax.jit`` (static arguments) to make the cache on the device."""
    return {"layers": [{name: jnp.zeros(shape, dtype) for name, shape
                        in spec.shapes(slots, positions).items()}
                       for spec in specs]}


def nbytes_by_kind(cache: dict, specs: Sequence[LayerSpec]) -> Dict[str, int]:
    """Bytes the cache holds, by kind of layer, for every kind present."""
    out: Dict[str, int] = {}
    for spec, entry in zip(specs, cache["layers"]):
        out[spec.kind] = out.get(spec.kind, 0) + sum(
            int(a.size) * a.dtype.itemsize for a in entry.values())
    return out


def signature_leaves(cache: dict) -> list:
    """One array per layer whose shape and dtype tell two caches apart (the
    launch size and the context both show in it): for a compile signature."""
    return [entry[min(entry)] for entry in cache["layers"]]
