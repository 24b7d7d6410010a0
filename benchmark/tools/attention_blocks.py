"""Time the two forms of the GLM prefill's causal attention at the cell's
shape (a slice of 2 sequences x 16,384 positions x 20 heads of 256,
bfloat16, the traffic's own 16 lengths in its 8 slices): the scanned
``ops/attention.py::prefill_causal`` and the fused kernel
``ops/pallas_attention.py::fused_causal`` at several block sizes.
``BLOCK_Q`` / ``BLOCK_K`` beside the kernel are set from this script's
output (PERF.md section 6, PR 31).  Also: the two forms' answers side by
side, and the kernel's time at lengths 8,192 against 16,384 (36 block
pairs of 1,024 a head against 136: a kernel that masked and did not skip
would take the same time for both).

    chiprun --chips 1 -- python3 -m benchmark.tools.attention_blocks
    JAX_PLATFORMS=cpu python3 -m benchmark.tools.attention_blocks --rehearse
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from can_tpu.ops import attention as attn_ops
from can_tpu.ops import pallas_attention as fused_attn

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS, PEAK_TFLOPS = 6, 197.0


def slices_of_the_traffic():
    with open(os.path.join(HERE, "..", "traffic",
                           "agent-16k-128-closed.json")) as f:
        traffic = json.load(f)
    lo, hi = traffic["prompt_tokens"]
    lengths = np.random.default_rng(int(traffic["length_seed"])).integers(
        lo, hi + 1, int(traffic["distinct_prompts"]))
    return [tuple(int(n) for n in lengths[i:i + 2])
            for i in range(0, len(lengths), 2)]


def block_pairs(lengths, block: int) -> int:
    """Pairs of (query block, key block) at or under the diagonal and inside
    each sequence's length."""
    n = [-(-int(x) // block) for x in lengths]
    return sum(x * (x + 1) // 2 for x in n)


def timed(run, args, reps: int) -> float:
    run(*args).block_until_ready()
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="1024x1024,512x1024,1024x512,"
                                        "512x512,2048x1024,2048x512")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, the kernel interpreted: finds wrong "
                         "paths on the CPU, times nothing worth reading")
    args = ap.parse_args()
    b, l, h, d = (2, 256, 2, 128) if args.rehearse else (2, 16384, 20, 256)
    unit = 64 if args.rehearse else 1024
    blocks = [tuple(int(x) // (1024 // unit) for x in s.split("x"))
              for s in args.blocks.split(",")]
    slices = slices_of_the_traffic()
    if args.rehearse:
        slices = [tuple(max(1, n * l // 16384) for n in s) for s in slices[:2]]
    print("[attention] device", jax.devices()[0].device_kind, "shape",
          (b, l, h, d), "slices", slices, flush=True)
    ks = jax.random.split(jax.random.key(0), 3)
    # heads by positions: what the kernel reads, and what XLA hands over
    # in the prefill program (positions in the lanes)
    qt, kt, vt = (jax.random.normal(kk, (b, h * d, l), jnp.bfloat16)
                  for kk in ks)

    def heads(x):
        return jnp.swapaxes(x, 1, 2).reshape(b, l, h, d)

    flops_pair = 4 * unit * unit * d * h          # both products, all heads
    scale = d ** -0.5
    scanned = jax.jit(lambda q, k, v, n: attn_ops.prefill_causal(
        q, k, v, n, scale=scale, block=unit))
    q4, k4, v4 = heads(qt), heads(kt), heads(vt)
    ms = [timed(scanned, (q4, k4, v4, jnp.asarray(s, jnp.int32)), args.reps)
          for s in slices]
    pairs = sum(block_pairs(s, unit) for s in slices)
    row = {"form": "scanned", "block": unit, "ms_by_slice": np.round(ms, 3).tolist(),
           "launch_s": round(sum(ms) * LAYERS / 1e3, 4),
           "tflops": round(pairs * flops_pair / sum(ms) / 1e9, 2)}
    print("[attention]", json.dumps(row), flush=True)
    want = scanned(q4, k4, v4, jnp.asarray(slices[0], jnp.int32))
    del q4, k4, v4

    for bq, bk in blocks:
        # in and out as the kernel itself has them (the reshapes cancel),
        # so that the kernel alone is timed
        run = jax.jit(lambda qt, kt, vt, n, bq=bq, bk=bk: fused_attn.fused_causal(
            heads(qt), heads(kt), heads(vt), n, scale=scale, block_q=bq,
            block_k=bk, interpret=args.rehearse).reshape(b, l, h * d))
        try:
            ms = [timed(run, (qt, kt, vt, jnp.asarray(s, jnp.int32)), args.reps)
                  for s in slices]
        except Exception as e:  # noqa: BLE001 — a block the compiler refuses
            print("[attention]", json.dumps(
                {"form": "fused", "block_q": bq, "block_k": bk,
                 "refused": str(e).splitlines()[0][:200]}), flush=True)
            continue
        got = run(qt, kt, vt, jnp.asarray(slices[0], jnp.int32)).reshape(want.shape)
        # rows past a length are zeros or garbage by the block size
        valid = (jnp.arange(l)[None] < jnp.asarray(slices[0])[:, None])
        gap = jnp.where(valid[:, :, None, None], jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)), 0.0)
        full = timed(run, (qt, kt, vt, jnp.full((b,), l, jnp.int32)), args.reps)
        half = timed(run, (qt, kt, vt, jnp.full((b,), l // 2, jnp.int32)),
                     args.reps)
        row = {"form": "fused", "block_q": bq, "block_k": bk,
               "ms_by_slice": np.round(ms, 3).tolist(),
               "launch_s": round(sum(ms) * LAYERS / 1e3, 4),
               # of the work the scanned form's blocks of ``unit`` do
               "tflops": round(pairs * flops_pair / sum(ms) / 1e9, 2),
               "pct_of_peak": round(pairs * flops_pair / sum(ms) / 1e9
                                    / PEAK_TFLOPS * 100, 1),
               "max_gap_to_scanned": float(gap.max()),
               "finite": bool(jnp.isfinite(got.astype(jnp.float32)).all()),
               "ms_full": round(full, 3), "ms_half": round(half, 3),
               "half_over_full": round(half / full, 4)}
        print("[attention]", json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
