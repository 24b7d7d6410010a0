"""Time the sorted form of ops/moe.py's held experts' product (a prefill
slice's: ``_share_apply_sorted``) by the size of its buffer, one layer, ms a
call, at the three cells' widths and slice sizes where a rank holds a share
of the experts, and at the one that holds them all (the control: its buffer
is the whole at any margin, one row):

* LFM2-24B-A2B's (8 of 64 experts held, 2048 x 1536, top-4, bfloat16),
  8 prompts of 1,024 tokens;
* MiMo-V2-Flash's (16 of 256 held, 4096 x 2048, top-8), 4 prompts of 8,192;
* K-EXAONE's (16 of 128 held, 6144 x 2048, top-8), 8 prompts of 1,024;
* GLM-4.7-Flash's (64 of 64 held, 2048 x 1536, top-4), 2 prompts of 16,384.

``SORTED_MARGIN`` in ``can_tpu/ops/moe.py`` is set from this script's output
(PERF.md section 6, PR 43).  For each margin of ``--margins`` (``whole``: the
buffer holds every assignment that CAN land here, one pass and no loop) the
constant is set and the function traced anew.  Routing is a seeded router's,
as the cells' weights are made (``models/lm_blocks.py::_leaf``): a product
N(0, 1 / d) and a bias 0.05 N(0, 1) over unit-variance rows.  ``rows_over_even``
is the most rows in use over ``--routers`` such routers against what even
routing lands here, ``past_one_pass`` the share of them that need a second
pass at the margin.  ``skewed_ms`` times a call whose router's bias puts
1.4 to 1.9 times the even share here: two passes at 1.25, one at 2.
Every answer is compared with the batched form's on the first 512 tokens'
rows.  A program from before the constant is timed as it stands.

    chiprun --chips 1 -- python3 -m benchmark.tools.expert_prefill_forms
    JAX_PLATFORMS=cpu python3 -m benchmark.tools.expert_prefill_forms --rehearse
"""
import argparse
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.tools.expert_decode_forms import timed
from can_tpu.ops import moe


def routed(key, tokens, d, total, k, scale, lift, share):
    """-> (x, idx, w) of a seeded router; ``lift`` is added to the held
    experts' bias (0: the cells' own kind of router)."""
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (tokens, d), jnp.bfloat16)
    router = jax.random.normal(kw, (d, total), jnp.float32) * d ** -0.5
    bias = 0.05 * jax.random.normal(kb, (total,), jnp.float32)
    bias = bias.at[share.first:share.first + share.held].add(lift)
    idx, w = jax.jit(moe.route, static_argnames=("top_k", "scale"))(
        x, router, bias, top_k=k, scale=scale)
    return x, idx, w


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--margins", default="whole,1.25,1.5,2")
    ap.add_argument("--routers", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--shapes", default="lfm2,mimo,k-exaone,glm")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths: finds wrong paths on the CPU, times "
                         "nothing worth reading")
    args = ap.parse_args()
    # (name, held, total, d, f, top_k, scale, tokens of a slice)
    shapes = [("lfm2", 8, 64, 2048, 1536, 4, 1.0, 8 * 1024),
              ("mimo", 16, 256, 4096, 2048, 8, 1.0, 4 * 8192),
              ("k-exaone", 16, 128, 6144, 2048, 8, 2.5, 8 * 1024),
              ("glm", 64, 64, 2048, 1536, 4, 1.8, 2 * 16384)]
    shapes = [s for s in shapes if s[0] in args.shapes.split(",")]
    if args.rehearse:
        shapes = [(n, h, tot, d // 64, f // 64, k, s, t // 4)
                  for n, h, tot, d, f, k, s, t in shapes]
    has_margin = hasattr(moe, "SORTED_MARGIN")
    margins = args.margins.split(",") if has_margin else ["as it stands"]
    print("[experts] device", jax.devices()[0].device_kind, flush=True)
    for name, held, total, d, f, k, scale, tokens in shapes:
        share = moe.ExpertShare(0, held, total)
        ks = jax.random.split(jax.random.key(0), 4)
        experts = {
            "gate": jax.random.normal(ks[0], (held, d, f), jnp.bfloat16) * d ** -0.5,
            "up": jax.random.normal(ks[1], (held, d, f), jnp.bfloat16) * d ** -0.5,
            "down": jax.random.normal(ks[2], (held, f, d), jnp.bfloat16) * f ** -0.5}
        even = tokens * k * held / total
        in_use = [int(moe.held_counts(routed(jax.random.fold_in(ks[3], i), tokens,
                                             d, total, k, scale, 0.0, share)[1],
                                      share).sum())
                  for i in range(args.routers)]
        plain = routed(ks[3], tokens, d, total, k, scale, 0.0, share)
        # a lift of the held experts' bias that lands about 1.6 x even here
        skewed = routed(ks[3], tokens, d, total, k, scale, 0.035, share)
        batched = jax.jit(lambda x, idx, w, e: moe._share_apply_batched(
            x, idx, w, e, share))
        timed_at = set()
        for margin in margins:
            row = {"shape": name, "tokens": tokens, "held": held,
                   "total": total, "top_k": k, "margin": margin}
            if has_margin:
                # total / held times the even share is every assignment
                moe.SORTED_MARGIN = (total / held if margin == "whole"
                                     else float(margin))
                moe._sorted_in_passes.clear_cache()
                cap = moe.sorted_rows(tokens, k, share)
                if cap in timed_at:
                    continue        # the same buffer as an earlier margin's
                timed_at.add(cap)
                row["rows"] = cap
                row["rows_over_even"] = round(max(in_use) / even, 4)
                row["past_one_pass"] = round(
                    sum(n > cap for n in in_use) / len(in_use), 4)
            run = jax.jit(lambda x, idx, w, e: moe._share_apply_sorted(
                x, idx, w, e, share))
            for tag, (x, idx, w) in (("plain", plain), ("skewed", skewed)):
                n = int(moe.held_counts(idx, share).sum())
                row[f"{tag}_rows_in_use"] = n
                if has_margin:
                    row[f"{tag}_passes"] = max(1, math.ceil(n / cap))
                try:
                    got = np.asarray(run(x, idx, w, experts)[:512], np.float32)
                    row[f"{tag}_ms"] = round(
                        timed(run, (x, idx, w, experts), args.reps), 4)
                except Exception as e:  # noqa: BLE001 (a buffer over the chip's memory is a reading)
                    row[f"{tag}_failed"] = f"{type(e).__name__}: {str(e)[:160]}"
                    continue
                want = np.asarray(batched(x[:512], idx[:512], w[:512], experts),
                                  np.float32)
                row[f"{tag}_max_gap"] = round(float(np.max(np.abs(got - want))), 5)
            print("[experts]", json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
