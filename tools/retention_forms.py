"""Time the forms of ONE layer's power retention at the cell's sizes
(``serve-brumby-gen512-closed``: 8 key heads x 5 query heads of 128,
bfloat16 activations, a float32 state of 8,320 rows a key head):

* the prompt form ``ops/retention.py::power_retention_chunked`` at a prefill
  slice (4 prompts in a bucket of 1,024, four of the traffic's own lengths)
  by chunk: 128, 256, 512 and 1,024 (the bucket: the quadratic form plus one
  state at the end), each form's answer beside the last one's;
* the step ``power_retention_step`` at a launch's 16 slots (545 MB of state
  read and written), every form WITH THE STATE DONATED and handed on from
  call to call, so that the write in place is what is timed: ``fused`` (the
  module's form on the chip: ``ops/pallas_retention.py::fused_step``, the
  state in the cache's layout, rows in the lanes, through the chip once),
  ``plain`` (the module's plain form, the kernel's oracle: two XLA fusions of
  the state) and four forms of the layout the state had until PR 48 (rows in
  the sublanes, ``(slots, heads, rows, dv)``), kept here so that the readings
  in the module's comments can be made again: ``product`` (PR 47's module
  form: the state queried after its update by a float32 product at the
  highest precision), ``rows`` (the query as a multiply and a sum over the
  rows on the vector unit), ``old`` (that, on the state BEFORE its update with
  the step's own term added, so that query and update read the same array)
  and ``lanes`` (that, with the group's heads laid side by side in the lanes).

    chiprun --chips 1 -- python3 tools/retention_forms.py
    JAX_PLATFORMS=cpu python3 tools/retention_forms.py --rehearse
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from can_tpu.ops import retention as ret  # noqa: E402
from can_tpu.ops.retention import _normalised, phi  # noqa: E402

HBM_GBS = 819.0


def _update(S, z, k, v, log_g, active):
    """The update on the layout ``(slots, heads, rows, dv)``."""
    fade = jnp.exp(log_g.astype(jnp.float32))
    pk = phi(k)
    new_S = (fade[..., None, None] * S
             + pk[..., None] * v.astype(jnp.float32)[..., None, :])
    new_z = fade[..., None] * z + pk
    return (jnp.where(active[:, None, None, None], new_S, S),
            jnp.where(active[:, None, None], new_z, z), fade, pk)


def step_product(S, z, q, k, v, log_g, active):
    new_S, new_z, _, _ = _update(S, z, k, v, log_g, active)
    pq = phi(q)
    num = jnp.einsum("bkgm,bkmv->bkgv", pq, new_S,
                     precision=jax.lax.Precision.HIGHEST)
    den = jnp.sum(pq * new_z[:, :, None], axis=-1)
    return _normalised(num, den, q.dtype), new_S, new_z


def step_rows(S, z, q, k, v, log_g, active):
    new_S, new_z, _, _ = _update(S, z, k, v, log_g, active)
    pq = phi(q)
    num = jnp.sum(pq[..., None] * new_S[:, :, None], axis=-2)
    den = jnp.sum(pq * new_z[:, :, None], axis=-1)
    return _normalised(num, den, q.dtype), new_S, new_z


def step_old(S, z, q, k, v, log_g, active):
    new_S, new_z, fade, pk = _update(S, z, k, v, log_g, active)
    pq = phi(q)
    own = jnp.sum(pq * pk[:, :, None], -1)                       # (B, KV, G)
    num = (fade[..., None, None] * jnp.sum(pq[..., None] * S[:, :, None], -2)
           + own[..., None] * v.astype(jnp.float32)[:, :, None])
    den = fade[..., None] * jnp.sum(pq * z[:, :, None], -1) + own
    return _normalised(num, den, q.dtype), new_S, new_z


def step_lanes(S, z, q, k, v, log_g, active):
    new_S, new_z, _, _ = _update(S, z, k, v, log_g, active)
    pq = phi(q)                                                  # (B, KV, G, R)
    b, kv, g, r = pq.shape
    dv = S.shape[-1]
    wide = (pq.transpose(0, 1, 3, 2)[..., None]
            * new_S[:, :, :, None, :]).reshape(b, kv, r, g * dv)
    num = jnp.sum(wide, axis=2).reshape(b, kv, g, dv)
    den = jnp.sum(pq * new_z[:, :, None], axis=-1)
    return _normalised(num, den, q.dtype), new_S, new_z


def step_fused(interpret):
    """``fused_step`` as ``power_retention_step`` calls it."""
    from can_tpu.ops import pallas_retention

    def run(S, z, q, k, v, log_g, active):
        num, den, S, z = pallas_retention.fused_step(
            S, z, q, k, v, jnp.exp(log_g.astype(jnp.float32)), active,
            interpret=interpret)
        return _normalised(num, den, q.dtype), S, z

    return run


# the forms of the state's layout until PR 48: rows in the sublanes
ROWS_IN_SUBLANES = {"product": step_product, "rows": step_rows,
                    "old": step_old, "lanes": step_lanes}
def _time(run, args, reps):
    out = run(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps, out


def _time_step(fn, S, z, one, reps):
    """A step with its state donated and handed on: -> (ms a step, the first
    step's ``y``).  ``S`` and ``z`` are copied first: the caller keeps its
    own."""
    run = jax.jit(fn, donate_argnums=(0, 1))
    first, S, z = run(S + 0.0, z + 0.0, *one)
    first = np.asarray(first, np.float32)
    jax.block_until_ready(S)
    t0 = time.perf_counter()
    for _ in range(reps):
        _, S, z = run(S, z, *one)
    jax.block_until_ready(S)
    return 1e3 * (time.perf_counter() - t0) / reps, first


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("retention_forms: no TPU (use --rehearse on the CPU)", file=sys.stderr)
        return 2
    # (the rehearsal keeps heads of 128: the kernel takes no other)
    b, l, kv, g, d, slots, reps = ((2, 16, 2, 3, 128, 2, 1) if args.rehearse
                                   else (4, 1024, 8, 5, 128, 16, 10))
    chunks = (4, 8, 16) if args.rehearse else (128, 256, 512, 1024)
    # (XLA:CPU has no bfloat16 product inside a scan: the rehearsal is float32)
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    ks = jax.random.split(jax.random.key(47), 8)
    q = jax.random.normal(ks[0], (b, l, kv, g, d), dtype)
    k = jax.random.normal(ks[1], (b, l, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, l, kv, d), dtype)
    log_g = jax.nn.log_sigmoid(jax.random.uniform(ks[3], (b, l, kv), jnp.float32,
                                                  2.2, 6.9))
    lengths = jnp.asarray(np.random.default_rng(20261003).integers(
        l // 2, l + 1, 16)[:b], jnp.int32)
    rows = []
    last = None
    for chunk in chunks:
        run = jax.jit(lambda *a, c=chunk: ret.power_retention_chunked(*a, chunk=c))
        try:
            ms, out = _time(run, (q, k, v, log_g, lengths), reps)
        except Exception as e:   # a form that does not fit says so
            rows.append({"form": f"chunked {chunk}", "error": str(e)[:200]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        y, S, z = (np.asarray(x, np.float32) for x in out)
        row = {"form": f"chunked {chunk}", "ms": ms}
        if last is not None:
            live = np.arange(l)[None] < np.asarray(lengths)[:, None]
            row["y_gap"] = float(np.abs(y - last[0])[live].max())
            row["S_gap_rel"] = float(np.abs(S - last[1]).max()
                                     / np.abs(last[1]).max())
        last = (y, S, z)
        rows.append(row)
        print(json.dumps(row), flush=True)
    # a state as a prompt leaves it: the forms' answers can be compared
    _, S, z = jax.jit(ret.power_retention_chunked)(
        *(jnp.tile(x, (slots // b,) + (1,) * (x.ndim - 1))
          for x in (q, k, v, log_g)), jnp.tile(lengths, slots // b))
    active = jnp.ones((slots,), bool)
    state_gb = 2.0 * (S.size + z.size) * 4 / 1e9
    # one position of every slot: the prompts' first, the slice repeated
    one = tuple(jnp.tile(x[:, 0], (slots // b,) + (1,) * (x.ndim - 2))
                for x in (q, k, v, log_g)) + (active,)
    want = None
    # the oracle first: the others' ``y_gap`` is against its answer
    forms = [("plain", ret._step_plain, (S, z)),
             ("fused", step_fused(args.rehearse), (S, z))]
    forms += [(name, fn, (jnp.swapaxes(S, -1, -2), z))
              for name, fn in ROWS_IN_SUBLANES.items()]
    for name, fn, state in forms:
        try:
            ms, y = _time_step(fn, *state, one, 5 * reps)
        except Exception as e:   # a variant the compiler refuses says so
            rows.append({"form": f"step {name}", "error": str(e)[:300]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        row = {"form": f"step {name}", "ms": ms,
               "state_read_and_written_GBs": state_gb / (ms * 1e-3),
               "of_peak_pct": 100.0 * state_gb / (ms * 1e-3) / HBM_GBS}
        if want is not None:
            row["y_gap"] = float(np.abs(y - want).max())
        elif name == "plain":
            want = y
        rows.append(row)
        print(json.dumps(row), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "retention_forms.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform, "kind": dev.device_kind},
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
