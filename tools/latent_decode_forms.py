"""Time the forms of ONE layer's absorbed latent attention in a decode step
at the cell's sizes (``serve-glm-agent16k-closed``: 16 slots over 16,512
positions, 20 heads, rank 512, rotary keys 64 wide, bfloat16, each slot at a
position drawn from 8k-16k + 64 as the traffic's contexts are at a launch's
middle step), THE TWO LEAVES DONATED and handed on from call to call, so
that the write in place is what is timed.  A row is the step's two row
writes and its attention; ms a layer, and GB/s of the VALID bytes (what the
roofline counts: ``rank + rope`` numbers a position up to each slot's own):

* ``plain scatter``: the form until PR 49: ``write_row`` as a scatter on both
  leaves (the rotary keys' leaf re-laid whole on the way in and out) and
  ``ops/attention.py::decode_latent``, two products around a softmax whose
  scores go through HBM, every allocated position read twice;
* ``plain``: ``decode_latent`` with the rotary keys written a
  ``dynamic_update_slice`` a slot, where the leaf lies (``write_row`` on a
  TPU as it stands);
* ``fused scatter``: the kernel (``ops/pallas_latent.py``) with the scatter's
  two copies of the rotary keys' leaf left in;
* ``fused``: the kernel and the writes as they stand, the module's form;
* ``fused updates`` / ``fused select`` (PR 50): the kernel with the rotary
  keys' row written an update a SLOT (16 in GLM's cell, 256 in LongCat's)
  or by a select over the whole leaf (one elementwise pass, in place):
  ``write_row`` takes the first over ``SELECT_MAX_POSITIONS`` positions a
  slot and the second at or under it, so ``fused`` is one of the two
  (``--shape longcat``: 256 slots x 1,280 positions, 64 heads);
* ``fused`` in blocks of 384 / 512 / 1,024 / 1,536 / 2,048 positions.
  (The other orientation of either product, the queries held in the matrix
  unit and the cache block streaming, was a parameter of the kernel in this
  PR's first call and read 15-71% slower: the numbers are beside ``BLOCK`` in
  ``ops/pallas_latent.py``; the parameter went.)

Every row's answer is compared with the first row's (``o_gap``).  With
``--steps`` the rows also run at the cell's first and last positions.

    chiprun --chips 1 -- python3 tools/latent_decode_forms.py
    JAX_PLATFORMS=cpu python3 tools/latent_decode_forms.py --rehearse
"""
import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from can_tpu.ops import attention as attn_ops  # noqa: E402

HBM_GBS = 819.0
SCALE = 192 ** -0.5


def _scatter(cache, new, pos):
    """``write_row`` until PR 49, whatever the leaf's width."""
    return cache.at[jnp.arange(cache.shape[0]), pos].set(new.astype(cache.dtype))


def _updates(cache, new, pos):
    """The row written by a ``dynamic_update_slice`` a slot, whatever the
    leaf's length (``write_row``'s form over ``SELECT_MAX_POSITIONS``)."""
    for i in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, new[i][None, None].astype(cache.dtype), (i, pos[i], 0))
    return cache


def _select(cache, new, pos):
    """The row written by a select over the whole leaf: one elementwise
    pass in whatever layout the leaf has, in place where it is donated; its
    cost is the leaf's bytes, not the slots' count."""
    hit = jnp.arange(cache.shape[1])[None, :, None] == pos[:, None, None]
    return jnp.where(hit, new[:, None, :].astype(cache.dtype), cache)


def _step(write_krope, attend, ckv, krope, q_lat, q_rope, new_ckv, new_krope,
          positions):
    ckv = attn_ops.write_row(ckv, new_ckv, positions)
    krope = write_krope(krope, new_krope, positions)
    return attend(q_lat, q_rope, ckv, krope, positions), ckv, krope


def _plain(q_lat, q_rope, ckv, krope, positions):
    valid = jnp.arange(ckv.shape[1])[None, :] <= positions[:, None]
    return attn_ops.decode_latent(q_lat, q_rope, ckv, krope, valid, scale=SCALE)


def _fused(interpret, **how):
    from can_tpu.ops import pallas_latent

    return functools.partial(pallas_latent.fused_latent_decode, scale=SCALE,
                             interpret=interpret, **how)


def _time(fn, leaves, one, reps):
    """A step with its leaves donated and handed on: -> (ms a step, the
    first step's answer).  The leaves are copied first: the caller keeps its
    own."""
    run = jax.jit(fn, donate_argnums=(0, 1))
    ckv, krope = (x + 0 for x in leaves)
    first, ckv, krope = run(ckv, krope, *one)
    first = np.asarray(first, np.float32)
    jax.block_until_ready(ckv)
    t0 = time.perf_counter()
    for _ in range(reps):
        _, ckv, krope = run(ckv, krope, *one)
    jax.block_until_ready((ckv, krope))
    return 1e3 * (time.perf_counter() - t0) / reps, first


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--steps", action="store_true")
    ap.add_argument("--shape", choices=("glm", "longcat"), default="glm",
                    help="glm: 16 slots x 16,512 positions, 20 heads; longcat: "
                         "256 slots x 1,280 positions, 64 heads, 1,024 new")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("latent_decode_forms: no TPU (use --rehearse on the CPU)",
              file=sys.stderr)
        return 2
    b, h, s, r, dr, reps = ((2, 20, 1152, 128, 16, 1) if args.rehearse
                            else (16, 20, 16512, 512, 64, 50))
    new_tokens = 128   # of the cell: a launch's contexts grow by as many
    if args.shape == "longcat":
        new_tokens = 8 if args.rehearse else 1024
        b, h, s = (8, 20, 1152) if args.rehearse else (256, 64, 1280)
    blocks = (128, 256) if args.rehearse else (384, 512, 1024, 1536, 2048)
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    ks = jax.random.split(jax.random.key(49), 6)
    leaves = (jax.random.normal(ks[0], (b, s, r), dtype),
              jax.random.normal(ks[1], (b, s, dr), dtype))
    q_lat = jax.random.normal(ks[2], (b, h, r), dtype)
    q_rope = jax.random.normal(ks[3], (b, h, dr), dtype)
    new = (jax.random.normal(ks[4], (b, r), dtype),
           jax.random.normal(ks[5], (b, dr), dtype))
    rng = np.random.default_rng(20261004)
    prompts = rng.integers((s - new_tokens) // 2, s - new_tokens + 1, b)
    at = {"middle": prompts + new_tokens // 2}
    if args.steps:
        at.update(first=prompts, last=prompts + new_tokens - 1)
    fused = _fused(args.rehearse)
    forms = [("plain scatter", _scatter, _plain),
             ("plain", attn_ops.write_row, _plain),
             ("fused scatter", _scatter, fused),
             ("fused", attn_ops.write_row, fused),
             ("fused updates", _updates, fused),
             ("fused select", _select, fused)]
    forms += [(f"fused block {n}", attn_ops.write_row,
               _fused(args.rehearse, block=n)) for n in blocks]
    rows = []
    for where, pos in at.items():
        positions = jnp.asarray(pos, jnp.int32)
        valid_gb = float((pos + 1).sum()) * (r + dr) * leaves[0].dtype.itemsize / 1e9
        want = None
        for name, write, attend in forms:
            try:
                ms, o = _time(functools.partial(_step, write, attend), leaves,
                              (q_lat, q_rope, *new, positions), reps)
            except Exception as e:   # a variant the compiler refuses says so
                rows.append({"form": name, "at": where, "error": str(e)[:300]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            row = {"form": name, "at": where, "ms": ms,
                   "valid_GBs": valid_gb / (ms * 1e-3),
                   "of_peak_pct": 100.0 * valid_gb / (ms * 1e-3) / HBM_GBS}
            if want is None:
                want = o
            else:
                row["o_gap"] = float(np.abs(o - want).max())
            rows.append(row)
            print(json.dumps(row), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = ("latent_decode_forms.json" if args.shape == "glm"
            else f"latent_decode_forms.{args.shape}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump({"device": {"platform": dev.platform, "kind": dev.device_kind},
                   "shape": {"slots": b, "heads": h, "positions": s, "rank": r,
                             "rope": dr},
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
