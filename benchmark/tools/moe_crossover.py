"""Time ops/moe.py's two forms of the held experts' product at the
published widths (16 of 128 experts held, hidden 6144, width 2048, top-8,
bfloat16), by token count: where does sorting by expert begin to win?
``DENSE_MAX_TOKENS`` in ``can_tpu/ops/moe.py`` is set from this script's
output (PERF.md section 6, PR 26).

    chiprun --chips 1 -- python3 -m benchmark.tools.moe_crossover
"""
import json
import time

import jax
import jax.numpy as jnp

from can_tpu.ops import moe

share = moe.ExpertShare(0, 16, 128)
d, f, k = 6144, 2048, 8
key = jax.random.key(0)
ks = jax.random.split(key, 6)
experts = {"gate": jax.random.normal(ks[0], (16, d, f), jnp.bfloat16) * d ** -0.5,
           "up": jax.random.normal(ks[1], (16, d, f), jnp.bfloat16) * d ** -0.5,
           "down": jax.random.normal(ks[2], (16, f, d), jnp.bfloat16) * f ** -0.5}
rows = []
for t in (64, 128, 256, 384, 512, 768, 1024, 2048):
    x = jax.random.normal(ks[3], (t, d), jnp.bfloat16)
    # every token's 8 distinct experts of 128, uniform
    idx = jnp.argsort(jax.random.uniform(jax.random.fold_in(ks[4], t), (t, 128)),
                      axis=-1)[:, :k].astype(jnp.int32)
    w = jnp.full((t, k), 2.5 / k, jnp.float32)
    row = {"tokens": t}
    for name, fn in (("batched", moe._share_apply_batched),
                     ("sorted", moe._share_apply_sorted)):
        run = jax.jit(lambda x, idx, w, e, fn=fn: fn(x, idx, w, e, share))
        run(x, idx, w, experts).block_until_ready()
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                out = run(x, idx, w, experts)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / 10)
        row[name + "_ms"] = round(best * 1e3, 4)
    rows.append(row)
    print("[moe]", json.dumps(row), flush=True)
print("[moe] device", jax.devices()[0].device_kind)
