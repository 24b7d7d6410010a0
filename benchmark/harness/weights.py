"""Weights from ``--seed``, made on the device in one jitted call, in the
tree the program's checkpoints use (``frontend``/``backend``: lists of
``{"w": HWIO, "b"}``; ``context``: ``s1,s2,s3,s6`` -> ``{"ave","weight"}``
(Cin, Cout); ``output``).  The front end is He-normal with biases N(0, 0.1),
standing for the pretrained VGG-16 the reference loads (activations of order
one at 1/8 resolution); everything behind it is N(0, 0.01) with zero biases,
as the reference initialises it, so the first predictions are near zero as
in a real run's first epoch.  (Biases of N(0, 0.01) behind the front end
swamped the signal, which shrinks ~0.5x per layer there: the output was the
last bias plus a little, and its relative error swung 20x from seed to seed
with the size of that one number; my chip runs, PR 23.)"""

from __future__ import annotations

import math

FRONTEND = (64, 64, 128, 128, 256, 256, 256, 512, 512, 512)
BACKEND = (512, 512, 512, 256, 128, 64)
SCALES = (1, 2, 3, 6)


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _build(key):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 64))

    def conv(cin, cout, k, std, bias_std=0.0):
        return {"w": jax.random.normal(next(keys), (k, k, cin, cout), jnp.float32) * std,
                "b": jax.random.normal(next(keys), (cout,), jnp.float32) * bias_std}

    params = {"frontend": [], "context": {}, "backend": []}
    cin = 3
    for v in FRONTEND:
        params["frontend"].append(conv(cin, v, 3, math.sqrt(2.0 / (9 * cin)), 0.1))
        cin = v
    for s in SCALES:
        params["context"][f"s{s}"] = {
            "ave": jax.random.normal(next(keys), (512, 512), jnp.float32) * 0.01,
            "weight": jax.random.normal(next(keys), (512, 512), jnp.float32) * 0.01}
    cin = 1024
    for v in BACKEND:
        params["backend"].append(conv(cin, v, 3, 0.01))
        cin = v
    params["output"] = conv(cin, 1, 1, 0.01)
    return params


def make_params(seed: int):
    import jax

    return jax.jit(_build)(seed_key(seed))
