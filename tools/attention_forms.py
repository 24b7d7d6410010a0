"""Time the forms of the MiMo full layers' prefill attention at the cell's
slice (4 sequences x 8,192 positions x 64 query heads of 192 over 4
key/value heads, values 128 wide, bfloat16, the traffic's own 16 lengths in
its 4 slices): the scanned ``ops/attention.py::prefill_causal``, the fused
kernel ``ops/pallas_attention.py::fused_causal`` at several block sizes, and
the two forms of the contraction over a head of 192 that the kernel does NOT
take (kept here, with the kernel's body around them, so that the table
beside ``BLOCK_Q`` can be made again): ``padded`` (queries and each key
block padded with zeros to 256 in VMEM) and ``split`` (two products, 128 +
64 wide).  ``direct`` is the module's own kernel: Mosaic contracts over the
192 that are there.  Also each form's answer beside the scanned form's.

    chiprun --chips 1 -- python3 tools/attention_forms.py
    JAX_PLATFORMS=cpu python3 tools/attention_forms.py --rehearse
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from can_tpu.ops import attention as attn_ops  # noqa: E402
from can_tpu.ops import pallas_attention as fused_attn  # noqa: E402
from can_tpu.ops.pallas_attention import _VMEM_BUDGET, _live_blocks  # noqa: E402

NEG = attn_ops.NEG
LAYERS, PEAK_TFLOPS = 2, 197.0


def _kernel(len_ref, qt_ref, kt_ref, vt_ref, o_ref, q_ref, m_ref, den_ref,
            acc_ref, *, scale, block_k, form, d):
    b, i = pl.program_id(0), pl.program_id(2)
    block_q = o_ref.shape[1]
    live = _live_blocks(len_ref[b], block_q, pl.num_programs(2))

    @pl.when(i >= live)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(i < live)
    def _attend():
        qt = qt_ref[0]
        dp = q_ref.shape[1]
        if dp != d:
            qt = jnp.concatenate([qt, jnp.zeros((dp - d, block_q), qt.dtype)], 0)
        q_ref[...] = qt.T
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        first = i * block_q

        def block(j, masked):
            start = pl.multiple_of(j * block_k, block_k)
            vt = vt_ref[0, :, pl.ds(start, block_k)]
            if form == "split":
                lo = d // 128 * 128
                s = jnp.dot(q_ref[:, :lo], kt_ref[0, :lo, pl.ds(start, block_k)],
                            preferred_element_type=jnp.float32)
                s += jnp.dot(q_ref[:, lo:d], kt_ref[0, lo:d, pl.ds(start, block_k)],
                             preferred_element_type=jnp.float32)
                s = s * scale
            else:
                kt = kt_ref[0, :, pl.ds(start, block_k)]
                if dp != d:
                    kt = jnp.concatenate(
                        [kt, jnp.zeros((dp - d, block_k), kt.dtype)], 0)
                s = jnp.dot(q_ref[...], kt,
                            preferred_element_type=jnp.float32) * scale
            if masked:
                rows = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(cols <= rows, s, NEG)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m - m_new)
            m_ref[...] = m_new
            den_ref[...] = den_ref[...] * fade + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * fade + jax.lax.dot_general(
                p.astype(vt.dtype), vt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        under = (first + 1) // block_k
        reach = (first + block_q - 1) // block_k + 1
        jax.lax.fori_loop(0, under, lambda j, c: block(j, False), None)
        jax.lax.fori_loop(under, reach, lambda j, c: block(j, True), None)
        o_ref[0] = (acc_ref[...] / den_ref[...]).astype(o_ref.dtype)


def fused(qt, kt, vt, lengths, *, h, kv, form, block_q, block_k, interpret):
    """The kernel on (B, H * D, L) / (B, KV * D, L) / (B, KV * Dv, L): the
    module's own for ``direct`` (its reshapes cancel against these, so the
    kernel alone is timed), this file's variant of it otherwise."""
    b, _, l = qt.shape
    d, dv, g = qt.shape[1] // h, vt.shape[1] // kv, h // kv
    if form == "direct":
        def heads(x, n):
            return jnp.swapaxes(x, 1, 2).reshape(b, l, n, -1)

        return fused_attn.fused_causal(
            heads(qt, h), heads(kt, kv), heads(vt, kv), lengths,
            block_q=block_q, block_k=block_k, interpret=interpret
        ).reshape(b, l, h * dv)
    dp = -(-d // 128) * 128 if form == "padded" else d
    blocks = l // block_q

    def q_block(bi, hi, i, lens):
        return bi, hi, jnp.minimum(i, _live_blocks(lens[bi], block_q, blocks) - 1)

    def whole_head(bi, hi, i, lens):
        return bi, hi // g, 0

    return pl.pallas_call(
        functools.partial(_kernel, scale=float(d ** -0.5), block_k=block_k,
                          form=form, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h, blocks),
            in_specs=[pl.BlockSpec((1, d, block_q), q_block),
                      pl.BlockSpec((1, d, l), whole_head),
                      pl.BlockSpec((1, dv, l), whole_head)],
            out_specs=pl.BlockSpec((1, block_q, dv),
                                   lambda bi, hi, i, lens: (bi, i, hi)),
            scratch_shapes=[pltpu.VMEM((block_q, dp), qt.dtype),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, l, h * dv), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="fused_causal_attention", interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)


def slices_of_the_traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "doc-8k-256-probe8-closed.json")) as f:
        traffic = json.load(f)
    lo, hi = traffic["prompt_tokens"]
    lengths = np.random.default_rng(int(traffic["length_seed"])).integers(
        lo, hi + 1, int(traffic["distinct_prompts"]))
    return [tuple(int(n) for n in lengths[i:i + 4])
            for i in range(0, len(lengths), 4)]


def keys_seen(lengths) -> int:
    """Score elements the algorithm needs: n (n + 1) / 2 a sequence."""
    return sum(int(n) * (int(n) + 1) // 2 for n in lengths)


def timed(run, args, reps):
    run(*args).block_until_ready()
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="1024x1024,512x1024,1024x512,2048x1024,512x512")
    ap.add_argument("--forms", default="direct,padded,split")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    b, l, h, kv, d, dv = ((4, 256, 8, 2, 192, 128) if args.rehearse
                          else (4, 8192, 64, 4, 192, 128))
    unit = 32 if args.rehearse else 1024
    blocks = [tuple(int(x) // (1024 // unit) for x in s.split("x"))
              for s in args.blocks.split(",")]
    slices = slices_of_the_traffic()
    if args.rehearse:
        slices = [tuple(max(1, n * l // 8192) for n in s) for s in slices[:2]]
    print("[forms] device", jax.devices()[0].device_kind, "shape",
          (b, l, h, kv, d, dv), "slices", slices, flush=True)
    ks = jax.random.split(jax.random.key(0), 3)
    qt = jax.random.normal(ks[0], (b, h * d, l), jnp.bfloat16)
    kt = jax.random.normal(ks[1], (b, kv * d, l), jnp.bfloat16)
    vt = jax.random.normal(ks[2], (b, kv * dv, l), jnp.bfloat16)

    def heads(x, n):
        return jnp.swapaxes(x, 1, 2).reshape(b, l, n, -1)

    # operations of the algorithm: both products over the keys a position sees
    flops = sum(keys_seen(s) for s in slices) * 2 * (d + dv) * h
    scanned = jax.jit(lambda q, k, v, n: attn_ops.prefill_causal(
        q, k, v, n, block=unit))
    q4, k4, v4 = heads(qt, h), heads(kt, kv), heads(vt, kv)
    ms = [timed(scanned, (q4, k4, v4, jnp.asarray(s, jnp.int32)), args.reps)
          for s in slices]
    print("[forms]", json.dumps(
        {"form": "scanned", "block": unit, "ms_by_slice": np.round(ms, 3).tolist(),
         "launch_s": round(sum(ms) * LAYERS / 1e3, 4),
         "pct_of_peak": round(flops / sum(ms) / 1e9 / PEAK_TFLOPS * 100, 1)}),
          flush=True)
    want = scanned(q4, k4, v4, jnp.asarray(slices[0], jnp.int32))
    del q4, k4, v4

    for form in args.forms.split(","):
        for bq, bk in blocks:
            run = jax.jit(functools.partial(
                fused, h=h, kv=kv, form=form, block_q=bq, block_k=bk,
                interpret=args.rehearse))
            try:
                ms = [timed(run, (qt, kt, vt, jnp.asarray(s, jnp.int32)),
                            args.reps) for s in slices]
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses
                print("[forms]", json.dumps(
                    {"form": form, "block_q": bq, "block_k": bk,
                     "refused": str(e).splitlines()[0][:300]}), flush=True)
                continue
            got = run(qt, kt, vt, jnp.asarray(slices[0], jnp.int32)).reshape(
                want.shape)
            valid = jnp.arange(l)[None] < jnp.asarray(slices[0])[:, None]
            gap = jnp.where(valid[:, :, None, None], jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32)), 0.0)
            print("[forms]", json.dumps(
                {"form": form, "block_q": bq, "block_k": bk,
                 "ms_by_slice": np.round(ms, 3).tolist(),
                 "launch_s": round(sum(ms) * LAYERS / 1e3, 4),
                 "pct_of_peak": round(flops / sum(ms) / 1e9 / PEAK_TFLOPS * 100, 1),
                 "max_gap_to_scanned": float(gap.max()),
                 "finite": bool(jnp.isfinite(got.astype(jnp.float32)).all())}),
                  flush=True)


if __name__ == "__main__":
    main()
