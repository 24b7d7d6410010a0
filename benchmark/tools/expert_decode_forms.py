"""Time the two few-tokens forms of ops/moe.py's held experts' product:
``_share_apply_batched`` (every held expert on every token) and
``_share_apply_skipping`` (the Pallas kernel of ``ops/pallas_experts.py``,
which reads only the experts a token chose), ms a layer and the GB/s of
weights each streams, at

* GLM-4.7-Flash's shape (64 of 64 experts held, 2048 x 1536, top-4,
  bfloat16) for T = 8, 16, 32, 64, 128 tokens, and
* K-EXAONE's (16 of 128 held, 6144 x 2048, top-8) for T = 64.

``SKIP_MIN_IDLE`` in ``can_tpu/ops/moe.py`` and ``TILE_F`` in
``can_tpu/ops/pallas_experts.py`` are set from this script's output (PERF.md
section 6, PR 33).  Routing is uniform: every token's experts are distinct
and drawn from all of the layer's.  ``--tiles`` times the kernel at several
tiles of ``f``; both forms' answers are compared on every row.

    chiprun --chips 1 -- python3 -m benchmark.tools.expert_decode_forms
    JAX_PLATFORMS=cpu python3 -m benchmark.tools.expert_decode_forms --rehearse
"""
import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from can_tpu.ops import moe
from can_tpu.ops import pallas_experts

CALLS = 20


def timed(run, args, reps: int) -> float:
    """ms a call: ``CALLS`` calls in a row, the best of ``reps`` rows."""
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = run(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / CALLS)
    return best * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default=str(pallas_experts.TILE_F),
                    help="tiles of f to time the kernel at, comma-separated")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, the kernel interpreted: finds wrong "
                         "paths on the CPU, times nothing worth reading")
    args = ap.parse_args()
    tiles = [int(t) for t in args.tiles.split(",")]
    # (name, held, total, d, f, top_k, scale, token counts)
    shapes = [("glm", 64, 64, 2048, 1536, 4, 1.8, (8, 16, 32, 64, 128)),
              ("k-exaone", 16, 128, 6144, 2048, 8, 2.5, (64,))]
    if args.rehearse:
        shapes = [(n, h, tot, d // 16, f // 4, k, s, ts[:2])
                  for n, h, tot, d, f, k, s, ts in shapes]
        tiles = [128]
    print("[experts] device", jax.devices()[0].device_kind, flush=True)
    for name, held, total, d, f, k, scale, token_counts in shapes:
        share = moe.ExpertShare(0, held, total)
        ks = jax.random.split(jax.random.key(0), 5)
        experts = {
            "gate": jax.random.normal(ks[0], (held, d, f), jnp.bfloat16) * d ** -0.5,
            "up": jax.random.normal(ks[1], (held, d, f), jnp.bfloat16) * d ** -0.5,
            "down": jax.random.normal(ks[2], (held, f, d), jnp.bfloat16) * f ** -0.5}
        expert_bytes = 3 * d * f * 2
        for t in token_counts:
            x = jax.random.normal(ks[3], (t, d), jnp.bfloat16)
            idx = jnp.argsort(jax.random.uniform(jax.random.fold_in(ks[4], t),
                                                 (t, total)),
                              axis=-1)[:, :k].astype(jnp.int32)
            w = jnp.full((t, k), scale / k, jnp.float32)
            row = {"shape": name, "tokens": t, "held": held,
                   "idle_expected": round((1 - k / total) ** t, 4),
                   "form": moe.share_form(t, k, share, d, f, x.dtype)}
            batched = jax.jit(lambda x, idx, w, e: moe._share_apply_batched(
                x, idx, w, e, share))
            want = np.asarray(batched(x, idx, w, experts), np.float32)
            ms = timed(batched, (x, idx, w, experts), args.reps)
            row["batched_ms"] = round(ms, 4)
            row["batched_gb_s"] = round(held * expert_bytes / ms / 1e6, 1)
            for tile in tiles:
                if not pallas_experts.supports(t, d, f, x.dtype, tile_f=tile,
                                               interpret=args.rehearse):
                    continue          # this tile's blocks are over the budget
                kernel = functools.partial(pallas_experts.skipping_experts,
                                           tile_f=tile,
                                           interpret=args.rehearse)

                def skipping(x, idx, w, e):
                    return moe._share_apply_skipping(x, idx, w, e, share,
                                                     kernel=kernel)

                run = jax.jit(skipping)
                got, read = run(x, idx, w, experts)
                ms = timed(run, (x, idx, w, experts), args.reps)
                tag = f"kernel_f{tile}"
                row["experts_read"] = int(read)
                row[f"{tag}_ms"] = round(ms, 4)
                row[f"{tag}_gb_s"] = round(int(read) * expert_bytes / ms / 1e6, 1)
                row[f"{tag}_max_gap"] = round(float(np.max(np.abs(
                    np.asarray(got, np.float32) - want))), 5)
            row["answer_max_abs"] = round(float(np.max(np.abs(want))), 4)
            print("[experts]", json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
