#!/usr/bin/env python3
"""Chip smoke: train -> eval -> serve on the TPU, through the CLIs a user types.

    python chip_smoke.py              # one chip: data, train, eval, serve,
                                      #           syncBN-pallas leg, the
                                      #           fused prefill attention,
                                      #           the skipping experts
    python chip_smoke.py --chips 4    # four chips: dp=4 and dp=2 x sp=2 train
                                      #   steps against a one-device reference,
                                      #   cli.train on all four, cli.serve
                                      #   --replicas 4 — and nothing else
    python chip_smoke.py --rehearse-cpu [--chips 4]
                                      # the same phases on (virtual) CPU
                                      #   devices at toy shapes: finds wrong
                                      #   paths and arguments without chip
                                      #   time.  NOT a chip run: exits 3 and
                                      #   prints no result line, pass or fail

The quickest proof that the system still starts on the chip.  Full CANNet
width (VGG-16 widths are fixed) at ShanghaiTech-Part-A-scale resolutions,
bf16, a real per-chip batch; random weights and synthetic data from
``--seed``.  Observations, not benchmark numbers.

One process per chip: THIS process never imports JAX.  Every phase is a
child process, run one after the other — each exits (the server is stopped
by PID and waited for) before the next starts, so no two of them ever want
the chip at once.  The device triple on the last line is what the probe
child reported and every later child's ``[runtime]`` line confirmed.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only when every phase passed; any failure raises straight out (non-zero
exit, no result line).  No TPU -> exit 2 after the probe, before any work.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<repo>/.jax_cache`` (can_tpu/utils/compile_cache.py); the directory and
its entry count before/after are printed, so a second run on the same
machine shows hits: the compile seconds collapse.  (The entry count alone
does not say: under the directory's size cap a cold run can replace old
entries one for one and also print ``cache_entries_added 0``.  And the
checkout's path is part of the cache key — a copy of the same commit at
another path compiles cold.)
"""

from __future__ import annotations

import argparse
import io
import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")                   # data, checkpoints
LOGS = os.path.join(ROOT, "chiprun_out", "chip_smoke")     # child logs
BUDGET_S = 1150.0  # the whole run, compilation included (contract: 1200)
T0 = time.monotonic()

# ShanghaiTech Part A scale on the chip; for --rehearse-cpu toy shapes in
# the same relations (the serve ladder H{768} x W{768,1024} holds every
# data size in two bucket shapes; the mesh bucket's H splits over sp=2)
SHAPES = {
    "tpu": dict(sizes="576x768,768x1024,480x640", bucket=(576, 768),
                serve_buckets="768x768,768x1024"),
    "cpu": dict(sizes="64x96,96x128,48x64", bucket=(64, 96),
                serve_buckets="96x96,96x128"),
}
PLATFORM = "tpu"  # "cpu" only under --rehearse-cpu
CHIPS = 1
SEED = 0


class SmokeFailure(Exception):
    """A phase did not meet its pass condition."""


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def remaining() -> float:
    left = BUDGET_S - (time.monotonic() - T0)
    require(left > 5, f"out of time budget ({BUDGET_S:.0f}s)")
    return left


def child_env() -> dict:
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if PLATFORM == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={CHIPS}"
    return env


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def cache_entries() -> int:
    d = cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def run_child(name: str, argv: list) -> str:
    """Run one child to its end; its stdout+stderr go to LOGS/<name>.log
    and are returned.  A non-zero exit fails the smoke."""
    log_path = os.path.join(LOGS, f"{name}.log")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen([sys.executable] + argv, cwd=ROOT,
                                env=child_env(), stdout=log,
                                stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=remaining())
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    out = open(log_path, errors="replace").read()
    if rc != 0:
        sys.stderr.write(out[-6000:])
        raise SmokeFailure(f"phase {name}: exit code {rc} (log: {log_path})")
    say(f"[{name}] child exit 0 in {time.monotonic() - t0:.1f}s wall")
    return out


def find(pattern: str, text: str, what: str):
    m = re.search(pattern, text, re.M)
    require(m is not None, f"missing in output: {what} (/{pattern}/)")
    return m


def check_runtime(name: str, out: str, device: dict) -> dict:
    """Every phase must have seen the device the probe saw."""
    import ast

    topo = ast.literal_eval(find(r"^\[runtime\] (\{.*\})$", out,
                                 "[runtime] line").group(1))
    say(f"[{name}] device: platform={topo['platform']} "
        f"kind={topo['device_kind']!r} count={topo['global_devices']} "
        f"(process_count {topo['process_count']}, "
        f"local_devices {topo['local_devices']})")
    require(topo["platform"] == PLATFORM,
            f"{name} ran on {topo['platform']}, not {PLATFORM}")
    require(topo["device_kind"] == device["kind"]
            and topo["global_devices"] == device["count"],
            f"{name} saw {topo}, the probe saw {device}")
    require(topo["process_count"] == 1
            and topo["local_devices"] == device["count"],
            f"{name}: expected one process holding every chip, got {topo}")
    m = re.search(r"^\[xla\] persistent compilation cache at (.+)$", out, re.M)
    require(PLATFORM == "cpu"  # the unset-variable default skips the CPU
            or (m is not None and m.group(1) == cache_dir()),
            f"{name}: compile cache not at {cache_dir()}: "
            f"{m.group(1) if m else 'no [xla] line'}")
    return topo


def telemetry(tel_dir: str) -> list:
    path = os.path.join(tel_dir, "telemetry.host0.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def compile_summary(name: str, events: list) -> dict:
    """Compile count + seconds per step name, and the peak device memory,
    from a run's telemetry (the repo's own instrumentation)."""
    by_name: dict = {}
    peak = limit = None
    for e in events:
        p = e["payload"]
        if e["kind"] == "compile":
            n, s = by_name.get(p["name"], (0, 0.0))
            by_name[p["name"]] = (n + 1, s + float(p["seconds"]))
        elif e["kind"] == "memory":
            for d in p["devices"]:
                if "peak_bytes_in_use" in d:
                    peak = max(peak or 0, d["peak_bytes_in_use"])
                if "bytes_limit" in d:
                    limit = d["bytes_limit"]
    for step, (n, s) in sorted(by_name.items()):
        say(f"[{name}] compiles: {step} x{n}, {s:.1f}s")
    say(f"[{name}] memory_stats: peak_bytes_in_use={peak} bytes_limit={limit}")
    require(PLATFORM == "cpu" or (peak is not None and limit is not None),
            f"{name}: memory_stats() reported no peak/limit on the TPU")
    return {k: v[0] for k, v in by_name.items()}


def finite_metrics(name: str, out: str) -> list:
    """Every ``[metrics]`` row a train run printed, all values finite."""
    rows = []
    for line in re.findall(r"^\[metrics\] step \d+ (.+)$", out, re.M):
        row = {k: float(v) for k, v in
               (kv.split("=", 1) for kv in line.split())}
        for k, v in row.items():
            require(math.isfinite(v), f"{name}: non-finite {k}={v}")
        rows.append(row)
    require(rows, f"{name}: no [metrics] rows")
    return rows


# --------------------------------------------------------------- phases --
def phase_probe() -> dict:
    """What JAX finds, asked by a child (this process stays off JAX)."""
    out = run_child("probe", ["-c", (
        "import json, jax; d = jax.devices(); print('PROBE ' + json.dumps("
        "{'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))")])
    device = json.loads(find(r"^PROBE (\{.*\})$", out, "probe line").group(1))
    say(f"[probe] device: {device}")
    say("[probe] environment: " + json.dumps(
        {k: v for k, v in sorted(os.environ.items())
         if k.startswith(("TPU_", "JAX_", "XLA_"))}))
    if device["platform"] != PLATFORM:
        sys.stderr.write(f"chip_smoke: JAX found no {PLATFORM.upper()} "
                         f"(first device: {device}) — nothing to smoke\n")
        raise SystemExit(2)
    require(device["count"] == CHIPS,
            f"--chips {CHIPS} but JAX sees {device['count']} device(s)")
    return device


def phase_data(root: str, train: int, test: int, sizes: str) -> None:
    name = os.path.basename(root)
    out = run_child(name, [
        "tools/make_synthetic_data.py", "--root", root, "--train", str(train),
        "--test", str(test), "--seed", str(SEED), "--sizes", sizes])
    say(f"[{name}] " + find(r"^\[density\] stamping path: (.+)$", out,
                            "stamping path line").group(1))
    say(f"[{name}] {train} train / {test} test at {sizes}")


def phase_train(name: str, device: dict, *, data: str, ckpt: str, batch: int,
                epochs: int, extra=()) -> tuple:
    """Returns ``(child output, the run's best MAE)``."""
    tel = os.path.join(WORK, f"tel_{name}")
    out = run_child(name, [
        "-m", "can_tpu.cli.train", "--data_root", data, "--platform",
        PLATFORM, "--bf16", "--batch-size", str(batch), "--epochs",
        str(epochs), "--checkpoint-dir", ckpt, "--seed", str(SEED),
        "--telemetry-dir", tel, *extra])
    check_runtime(name, out, device)
    say(f"[{name}] " + find(r"^\[hbm\] .+$", out, "[hbm] line").group(0))
    for line in re.findall(r"^\[data\] .+$", out, re.M):
        say(f"[{name}] {line}")
    planned = {tag: int(n) for tag, n in re.findall(
        r"^\[data\] (train|test): .* (\d+) \(shape x size\) programs", out,
        re.M)}
    require(set(planned) == {"train", "test"}, f"{name}: no planner lines")
    compiles = compile_summary(name, telemetry(tel))
    require(compiles.get("train_step") == planned["train"]
            and compiles.get("eval_step") == planned["test"],
            f"{name}: compiled {compiles}, planned {planned}")
    rows = finite_metrics(name, out)
    require(len(rows) == epochs, f"{name}: {len(rows)} epochs of {epochs}")
    say(f"[{name}] train_loss first={rows[0]['train_loss']:.6g} "
        f"last={rows[-1]['train_loss']:.6g}; eval MAE={rows[-1]['mae']:.4f} "
        f"MSE={rows[-1]['mse']:.4f}; {rows[-1]['img_per_s']:.1f} img/s in the "
        f"last epoch (an observation, not a benchmark)")
    best = float(find(r"^\[done\] best MAE ([0-9.]+)$", out,
                      "[done] line").group(1))
    saved = [d for d in os.listdir(ckpt) if d.isdigit()]
    require(saved, f"{name}: no checkpoint step directory in {ckpt}")
    say(f"[{name}] checkpoint epochs on disk: {sorted(map(int, saved))}")
    return out, best


def phase_eval(device: dict, *, data: str, ckpt: str, best_mae: float) -> None:
    tel = os.path.join(WORK, "tel_eval")
    out = run_child("eval", [
        "-m", "can_tpu.cli.test", "--data_root", data, "--checkpoint-dir",
        ckpt, "--platform", PLATFORM, "--bf16", "--batch-size", "8",
        "--seed", str(SEED), "--telemetry-dir", tel])
    check_runtime("eval", out, device)
    say("[eval] " + find(r"^\[load\] epoch \d+ from .+$", out,
                         "[load] line").group(0))
    compile_summary("eval", telemetry(tel))
    m = find(r"^\[result\] images=(\d+) MAE=(\S+) MSE=(\S+)$", out,
             "[result] line")
    mae, mse = float(m.group(2)), float(m.group(3))
    require(math.isfinite(mae) and math.isfinite(mse), f"eval: {m.group(0)}")
    say(f"[eval] {m.group(0)}")
    # the reference for this phase: another process restores the best
    # checkpoint and runs the same eval programs — the train run's own
    # best MAE must come back
    require(abs(mae - best_mae) < 2e-3,
            f"eval MAE {mae} != the train run's best MAE {best_mae}")
    say(f"[eval] reproduces the train run's best MAE ({best_mae})")


def http_json(url: str, body: bytes = None, timeout: float = 120.0):
    req = urllib.request.Request(url, data=body)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:  # say what the server said
        raise SmokeFailure(f"{url}: HTTP {e.code}: "
                           f"{e.read()[:500]!r}") from e


def npy_bytes(path: str) -> bytes:
    import numpy as np
    from PIL import Image

    buf = io.BytesIO()
    np.save(buf, np.asarray(Image.open(path).convert("RGB")))
    return buf.getvalue()


def phase_serve(name: str, device: dict, *, data: str, ckpt: str,
                buckets: str, replicas: int, port: int) -> None:
    img_dir = os.path.join(data, "test_data", "images")
    bodies = [npy_bytes(os.path.join(img_dir, f))
              for f in sorted(os.listdir(img_dir))]
    if replicas > 1:
        bodies = bodies * 2  # enough concurrent batches to reach every replica
    log_path = os.path.join(LOGS, f"{name}.log")
    argv = [sys.executable, "-m", "can_tpu.cli.serve", "--checkpoint-dir",
            ckpt, "--platform", PLATFORM, "--serve-dtype", "bf16",
            "--bucket-shapes", buckets, "--max-batch", "4", "--max-wait-ms",
            "50", "--port", str(port), "--seed", str(SEED),
            "--telemetry-dir", os.path.join(WORK, f"tel_{name}")]
    if replicas > 1:
        # every program of the priced menu compiles once PER DEVICE
        argv += ["--replicas", str(replicas)]
    t0 = time.monotonic()
    log = open(log_path, "wb")
    proc = subprocess.Popen(argv, cwd=ROOT, env=child_env(), stdout=log,
                            stderr=subprocess.STDOUT)
    try:
        while True:  # wait for the server to come up, or die
            out = open(log_path, errors="replace").read()
            if "[serve] listening" in out:
                break
            if proc.poll() is not None:
                sys.stderr.write(out[-6000:])
                raise SmokeFailure(f"{name}: server exited {proc.returncode} "
                                   f"before listening")
            remaining()
            time.sleep(1.0)
        say(f"[{name}] listening after {time.monotonic() - t0:.1f}s wall")
        check_runtime(name, out, device)
        say(f"[{name}] " + find(r"^\[serve\] (warmup: .+)$", out,
                                "warmup line").group(1))
        base = f"http://127.0.0.1:{port}"
        require(http_json(f"{base}/healthz")["ok"] is True, "healthz not ok")
        warm = http_json(f"{base}/stats")
        predict = f"{base}/predict?deadline_ms=120000"

        # the same image twice, alone: same program, identical count
        first = http_json(predict, bodies[0])
        again = http_json(predict, bodies[0])
        require(first["count"] == again["count"],
                f"{name}: repeated image {first['count']} != "
                f"{again['count']}")
        answers = [first, again]

        # concurrent bursts until a batch > 1 has formed (and, in a fleet,
        # every replica has answered): which requests share a launch, and
        # which replica wakes first, is timing — bounded retries, then fail
        for burst in range(8):
            got, errors = [], []

            def post(body):
                try:
                    got.append(http_json(predict, body))
                except Exception as e:  # surfaced below, never dropped
                    errors.append(e)

            threads = [threading.Thread(target=post, args=(b,))
                       for b in bodies]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180.0)
                require(not t.is_alive(), f"{name}: a request hung")
            require(not errors, f"{name}: request failed: {errors[:1]}")
            answers += got
            stats = http_json(f"{base}/stats")
            if stats["batch_valid"] > stats["batches"] and (
                    replicas == 1 or all(
                        r["batches"] >= 1
                        for r in stats["replicas"].values())):
                break
        for a in answers:
            require(math.isfinite(a["count"]), f"{name}: count {a}")
        say(f"[{name}] {len(answers)} x HTTP 200, counts "
            f"{min(a['count'] for a in answers):.3f}.."
            f"{max(a['count'] for a in answers):.3f}, repeated image "
            f"identical ({first['count']!r}), buckets "
            f"{sorted({tuple(a['bucket']) for a in answers})}")
        say(f"[{name}] /stats: " + json.dumps(
            {k: v for k, v in stats.items() if k != "streams"},
            sort_keys=True))
        require(stats["completed"] == len(answers) and not stats.get(
            "rejected"), f"{name}: completed/rejected off: {stats}")
        require(stats["batch_valid"] > stats["batches"],
                f"{name}: no batch > 1 formed in 8 bursts")
        require(stats["compile_count"] == warm["compile_count"],
                f"{name}: {stats['compile_count'] - warm['compile_count']} "
                f"compile(s) after warm-up")
        say(f"[{name}] compiles: {warm['compile_count']} at warm-up, 0 after")
        if replicas > 1:
            health = http_json(f"{base}/healthz")
            devs = [r["device"] for r in health["replicas"]]
            say(f"[{name}] replica devices: {devs}; batches per replica: "
                f"{ {k: r['batches'] for k, r in stats['replicas'].items()} }")
            require(len(set(devs)) == replicas == health["live"],
                    f"{name}: replicas not on distinct live devices: {devs}")
            require(all(r["batches"] >= 1
                        for r in stats["replicas"].values()),
                    f"{name}: a replica answered no batch")
        require(proc.poll() is None, f"{name}: server died under traffic")
    finally:
        # stopped by PID, and waited for: the next child needs the chip
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        log.close()
    say(f"[{name}] server stopped (exit {proc.returncode})")


def phase_pallas(device: dict, *, data: str) -> None:
    out, _ = phase_train("syncbn_pallas", device, data=data,
                         ckpt=os.path.join(WORK, "ckpt_bn"), batch=8,
                         epochs=1, extra=["--syncBN", "--bn-impl", "pallas"])
    mode = find(r"^\[model\] pallas BN-moments kernel: (.+), platform (\w+)$",
                out, "kernel mode line")
    say(f"[syncbn_pallas] {mode.group(0)}")
    require(mode.group(2) == PLATFORM and mode.group(1) == (
        "compiled (not interpreted)" if PLATFORM == "tpu" else "INTERPRETED"),
        f"pallas kernel mode: {mode.group(0)}")
    routes = re.findall(r"^\[model\] bucket \d+x\d+: (\d+) BN layers -> "
                        r"pallas kernel, (\d+) -> jnp onepass twin", out, re.M)
    require(routes, "no BN routing line")
    for line in re.findall(r"^\[model\] bucket .+$", out, re.M):
        say(f"[syncbn_pallas] {line}")
    require(all(int(k) > 0 for k, _ in routes),
            f"a bucket routed no BN layer to the kernel: {routes}")


# --------------------------------------------- fused prefill attention --
def attention_worker() -> None:
    """CHILD process (``--attention-worker``): the fused causal attention
    kernel (``ops/pallas_attention.py``) COMPILED for the chip (interpreted
    only under ``--rehearse-cpu``) against the scanned ``prefill_causal`` at
    two small shapes (a key a head of whole lanes; groups of query heads over
    keys of 192 beside values of 128), the kernel's own blocks, uneven
    lengths: one sequence ends inside its last block, the other leaves a
    block of queries wholly past its length."""
    import jax
    import jax.numpy as jnp

    from can_tpu.ops import attention as attn_ops
    from can_tpu.ops import pallas_attention as fused_attn
    from can_tpu.parallel import init_runtime
    from can_tpu.utils import enable_compilation_cache

    print(f"[runtime] {init_runtime()}")
    print(f"[xla] persistent compilation cache at "
          f"{enable_compilation_cache()}")
    on_chip = jax.default_backend() == "tpu"
    l = 3 * max(fused_attn.BLOCK_Q, fused_attn.BLOCK_K)
    lengths = jnp.asarray([l - 37, fused_attn.BLOCK_Q + 5], jnp.int32)
    # a key a head of whole lanes (GLM's kind), then groups of four query
    # heads over keys of 192 beside values of 128 (MiMo's full layers' kind)
    for b, h, kv, d, dv in ((2, 3, 3, 256, 128), (2, 8, 2, 192, 128)):
        ks = jax.random.split(jax.random.key(SEED), 3)
        q = jax.random.normal(ks[0], (b, l, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, l, kv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, l, kv, dv), jnp.bfloat16)
        assert fused_attn.supports(q.shape, v.shape, q.dtype,
                                   interpret=not on_chip), "supports() refused"
        fused = jax.jit(lambda *a: fused_attn.fused_causal(
            *a, interpret=not on_chip))(q, k, v, lengths)
        plain = jax.jit(attn_ops.prefill_causal)(q, k, v, lengths)
        valid = jnp.arange(l)[None] < lengths[:, None]
        gap = float(jnp.where(valid[:, :, None, None], jnp.abs(
            fused.astype(jnp.float32) - plain.astype(jnp.float32)), 0).max())
        finite = bool(jnp.isfinite(fused.astype(jnp.float32)).all())
        skipped = not bool(fused[1, 2 * fused_attn.BLOCK_Q:].any())
        print(f"[attention] kernel {'compiled (not interpreted)' if on_chip else 'INTERPRETED'}"
              f", platform {jax.default_backend()}; q {q.shape} k {k.shape} "
              f"v {v.shape} lengths {lengths.tolist()}: largest gap to "
              f"prefill_causal over valid rows {gap:.3e}, every row finite "
              f"{finite}, the block past a length left zero {skipped}")
        # one bfloat16 step of an output of size about 1 is 2^-8; the two
        # forms round the same products, summed in another order
        assert gap < 2e-2 and finite and skipped
    print("ATTENTION OK")


def phase_attention(device: dict) -> None:
    out = run_child("attention", [os.path.abspath(__file__),
                                  "--attention-worker", "--seed", str(SEED)])
    check_runtime("attention", out, device)
    say(find(r"^\[attention\] .+$", out, "kernel line").group(0))
    find(r"^ATTENTION OK$", out, "attention worker verdict")


# ------------------------------------------------- skipping experts --
def experts_worker() -> None:
    """CHILD process (``--experts-worker``): the skipping experts kernel
    (``ops/pallas_experts.py``) COMPILED for the chip (interpreted only
    where JAX is held to the CPU, with 2 tokens and a quarter of the
    experts) against ``_share_apply_batched``, one layer at GLM-4.7-Flash's
    published widths: 16 tokens, 64 of 64 experts held, 2048 x 1536, top-4,
    bfloat16."""
    import functools

    import jax
    import jax.numpy as jnp

    from can_tpu.ops import moe as moe_ops
    from can_tpu.ops import pallas_experts
    from can_tpu.parallel import init_runtime
    from can_tpu.utils import enable_compilation_cache

    print(f"[runtime] {init_runtime()}")
    print(f"[xla] persistent compilation cache at "
          f"{enable_compilation_cache()}")
    on_chip = jax.default_backend() == "tpu"
    d, f, k = 2048, 1536, 4
    tokens, held = (16, 64) if on_chip else (2, 16)
    share = moe_ops.ExpertShare(0, held, held)
    ks = jax.random.split(jax.random.key(SEED), 6)
    experts = {
        "gate": jax.random.normal(ks[0], (held, d, f), jnp.bfloat16) * d ** -0.5,
        "up": jax.random.normal(ks[1], (held, d, f), jnp.bfloat16) * d ** -0.5,
        "down": jax.random.normal(ks[2], (held, f, d), jnp.bfloat16) * f ** -0.5}
    x = jax.random.normal(ks[3], (tokens, d), jnp.bfloat16)
    idx = jnp.argsort(jax.random.uniform(ks[4], (tokens, held)),
                      axis=-1)[:, :k].astype(jnp.int32)
    w = 1.8 * jax.nn.softmax(jax.random.normal(ks[5], (tokens, k)), axis=-1)
    assert pallas_experts.supports(tokens, d, f, x.dtype,
                                   interpret=not on_chip), "supports() refused"
    if on_chip:
        form = moe_ops.share_form(tokens, k, share, d, f, x.dtype)
        assert form == "skipping", f"share_form says {form} on the chip"
    kernel = functools.partial(pallas_experts.skipping_experts,
                               interpret=not on_chip)
    got, read = jax.jit(lambda *a: moe_ops._share_apply_skipping(
        *a, share, kernel=kernel))(x, idx, w, experts)
    plain = jax.jit(lambda *a: moe_ops._share_apply_batched(*a, share))(
        x, idx, w, experts)
    chosen = int((moe_ops.held_counts(idx, share) > 0).sum())
    size = float(jnp.abs(plain.astype(jnp.float32)).max())
    gap = float(jnp.abs(got.astype(jnp.float32)
                        - plain.astype(jnp.float32)).max())
    print(f"[experts] kernel {'compiled (not interpreted)' if on_chip else 'INTERPRETED'}"
          f", platform {jax.default_backend()}; x {x.shape} experts "
          f"{experts['gate'].shape}: read {int(read)} of {held} experts "
          f"({chosen} have a token), largest gap to _share_apply_batched "
          f"{gap:.3e} on answers up to {size:.3f}")
    # the two forms round the same products; a bfloat16 step of the largest
    # answer is size * 2^-8, and the sum over 4 experts is float32 in both
    assert int(read) == chosen < held and gap <= size * 2 ** -6
    print("EXPERTS OK")


def phase_experts(device: dict) -> None:
    out = run_child("experts", [os.path.abspath(__file__),
                                "--experts-worker", "--seed", str(SEED)])
    check_runtime("experts", out, device)
    say(find(r"^\[experts\] .+$", out, "kernel line").group(0))
    find(r"^EXPERTS OK$", out, "experts worker verdict")


# ------------------------------------------------- four-chip mesh worker --
def mesh_worker() -> None:
    """CHILD process (``--mesh-worker``): the only code here that imports
    JAX.  dp=4 and dp=2 x sp=2 bf16 train steps on the same global batch
    and initial state as a one-device reference, stepped in this process;
    placement asserted, not assumed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from can_tpu.cli.common import build_mesh_and_batch
    from can_tpu.data.batching import Batch
    from can_tpu.models import cannet_apply, cannet_init
    from can_tpu.parallel import (
        init_runtime,
        make_dp_train_step,
        make_global_batch,
        make_mesh,
    )
    from can_tpu.parallel.spatial import make_sp_train_step
    from can_tpu.serve import FleetEngine
    from can_tpu.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
    )
    from can_tpu.utils import enable_compilation_cache

    print(f"TPU_WORKER_HOSTNAMES={os.environ.get('TPU_WORKER_HOSTNAMES')!r}")
    topo = init_runtime()
    print(f"[runtime] {topo}")
    print(f"[xla] persistent compilation cache at "
          f"{enable_compilation_cache()}")
    devices = jax.devices()
    ndev = len(devices)
    assert topo["process_count"] == 1 and topo["local_devices"] == ndev == 4

    h, w = SHAPES[devices[0].platform]["bucket"]
    # global batch 16, not b8-16 PER chip: the one-device reference steps
    # the SAME global batch, and b16 x 576x768 is what one 16 GB chip holds
    gb, steps = 16, 3
    rng = np.random.default_rng(SEED)
    batch = Batch(
        image=rng.normal(size=(gb, h, w, 3)).astype(np.float32),
        dmap=rng.uniform(size=(gb, h // 8, w // 8, 1)).astype(np.float32),
        pixel_mask=np.ones((gb, h // 8, w // 8, 1), np.float32),
        sample_mask=np.ones((gb,), np.float32))

    def run(name, mesh, dp, sp):
        # each mesh's own lr x dp / grad / dp pairing (DDP parity): the
        # parameter trajectory is the same on every mesh
        opt = make_optimizer(make_lr_schedule(1e-7, world_size=dp))
        state = create_train_state(cannet_init(jax.random.key(SEED)), opt)
        if sp > 1:
            step = make_sp_train_step(opt, mesh, (h, w),
                                      compute_dtype=jnp.bfloat16)
        else:
            step = make_dp_train_step(cannet_apply, opt, mesh,
                                      compute_dtype=jnp.bfloat16)
        gbatch = make_global_batch(batch, mesh, spatial=sp > 1)
        want = dp * sp
        shards = gbatch["image"].addressable_shards
        on = {s.device for s in shards}
        assert len(shards) == len(on) == want == len(
            gbatch["image"].sharding.device_set), (name, on)
        assert all(s.data.shape == (gb // dp, h // sp, w, 3)
                   for s in shards), [s.data.shape for s in shards]
        losses, t0 = [], time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, gbatch)
            losses.append(float(jax.device_get(metrics["loss"])))
        dt = time.perf_counter() - t0
        pdevs = {d for leaf in jax.tree.leaves(state.params)
                 for d in leaf.sharding.device_set}
        assert len(pdevs) == want, (name, pdevs)
        assert all(np.isfinite(losses)), (name, losses)
        print(f"[mesh4] {name}: batch shards on {sorted(d.id for d in on)} "
              f"{shards[0].data.shape} each, params on "
              f"{sorted(d.id for d in pdevs)}; losses "
              f"{[round(x, 4) for x in losses]} ({dt:.1f}s incl. compile)",
              flush=True)
        return losses

    ref = run("one-device reference", make_mesh(devices[:1], dp=1, sp=1), 1, 1)
    mesh_dp, host_batch, dp = build_mesh_and_batch(gb // ndev, 1)
    assert (dp, host_batch) == (ndev, gb)
    legs = {"dp=4": run("dp=4", mesh_dp, dp, 1)}
    mesh_sp, host_batch, dp = build_mesh_and_batch(gb // 2, 2)
    assert (dp, host_batch) == (2, gb)
    legs["dp=2 x sp=2"] = run("dp=2 x sp=2", mesh_sp, 2, 2)
    disagree = []  # judged after every leg has reported
    for name, losses in legs.items():
        rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
        # the loss barely moves in 3 steps at the recipe's lr, so its
        # DECREASE is what shows a wrong update scale (lr x dp, grad / dp)
        moved = (losses[0] - losses[-1]) / (ref[0] - ref[-1])
        print(f"[mesh4] {name} vs reference: max relative loss difference "
              f"{rel:.3e} over {steps} steps; loss decrease = {moved:.3f} x "
              f"the reference's")
        # bf16 tolerance on the loss: one bf16 ulp is 2^-8 = 3.9e-3.  On
        # the decrease: the MXU accumulates bf16 products in f32, so on
        # the chip the trajectories agree; XLA:CPU accumulates the b16
        # reference's weight gradients in bf16 and loses most of them
        # (the rehearsal prints 2-3 x here), so the CPU rehearsal judges
        # the plumbing only
        if rel >= 1e-2 or (devices[0].platform == "tpu"
                           and not 0.9 < moved < 1.1):
            disagree.append((name, losses, ref))

    # each fleet replica's params on ITS device (fleet.py slices
    # jax.devices()[:replicas] and re-keys a replicated tree per device)
    fleet = FleetEngine(cannet_init(jax.random.key(SEED)), replicas=ndev,
                        serve_dtype="bf16")
    homes = []
    for r in fleet.replicas:
        devs = {d for leaf in jax.tree.leaves(r.engine.params)
                for d in leaf.devices()}
        assert devs == {r.device}, (r.index, devs, r.device)
        homes.append(r.device.id)
    assert len(set(homes)) == ndev, homes
    print(f"[mesh4] fleet replica params on devices {homes} (distinct)")
    assert not disagree, disagree
    print("MESH4 OK")


def phase_mesh_worker(device: dict) -> None:
    out = run_child("mesh4", [os.path.abspath(__file__), "--mesh-worker",
                              "--seed", str(SEED)])
    check_runtime("mesh4", out, device)
    for line in re.findall(r"^(?:TPU_WORKER_HOSTNAMES=|\[mesh4\] ).+$", out,
                           re.M):
        say(f"[mesh4] {line}")
    find(r"^MESH4 OK$", out, "mesh worker verdict")


# ----------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phases; 4: ONLY the "
                         "four-chip legs and their one-device reference")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the synthetic data and the random weights")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the phases on (virtual) CPU devices at toy "
                         "shapes; exits 3 and prints no result line")
    ap.add_argument("--mesh-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--attention-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--experts-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    global PLATFORM, CHIPS, SEED
    CHIPS, SEED = args.chips, args.seed
    if args.mesh_worker:
        mesh_worker()
        return 0
    if args.attention_worker:
        attention_worker()
        return 0
    if args.experts_worker:
        experts_worker()
        return 0
    if args.rehearse_cpu:
        PLATFORM = "cpu"
    shapes = SHAPES[PLATFORM]

    for needed in ("can_tpu/cli/train.py", "tools/make_synthetic_data.py",
                   "tools/build_native.py"):
        if not os.path.isfile(os.path.join(ROOT, needed)):
            sys.stderr.write(f"chip_smoke: {needed} not found next to this "
                             f"script — run it from a checkout of the repo\n")
            return 2
    os.makedirs(LOGS, exist_ok=True)
    device = phase_probe()

    import shutil

    shutil.rmtree(WORK, ignore_errors=True)  # data is made anew every run
    os.makedirs(WORK)
    entries0 = cache_entries()
    say(f"[cache] dir in effect: {cache_dir()} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed in-checkout default'}); "
        f"entries before: {entries0}")
    say("[native] " + run_child("native", ["tools/build_native.py"])
        .strip().splitlines()[-1])

    data, ckpt = os.path.join(WORK, "data"), os.path.join(WORK, "ckpt")
    one_size = os.path.join(WORK, "data1")
    bucket = "%dx%d" % shapes["bucket"]
    if args.chips == 1:
        # 20 images in 3 sizes at b8 plan to <= 4 (shape x size) train
        # programs at seed 0 — each a ~35 s compile cold
        phase_data(data, 20, 8, shapes["sizes"])
        phase_data(one_size, 24, 8, bucket)
        _, best = phase_train("train", device, data=data, ckpt=ckpt,
                              batch=8, epochs=2)
        phase_eval(device, data=data, ckpt=ckpt, best_mae=best)
        phase_serve("serve", device, data=data, ckpt=ckpt,
                    buckets=shapes["serve_buckets"], replicas=1, port=8731)
        phase_pallas(device, data=one_size)
        phase_attention(device)
        phase_experts(device)
    else:
        phase_data(one_size, 32, 8, bucket)
        phase_mesh_worker(device)
        out, _ = phase_train("train4", device, data=one_size, ckpt=ckpt,
                             batch=8, epochs=1)
        find(r"^\[data\] train=\d+ test=\d+ host_batch=32 dp=4 sp=1 ", out,
             "[data] line showing dp=4")
        phase_serve("serve4", device, data=one_size, ckpt=ckpt,
                    buckets=bucket, replicas=4, port=8734)

    entries1 = cache_entries()
    say(f"[cache] entries after: {entries1} (cache_entries_added "
        f"{entries1 - entries0})")
    say(f"[done] every phase passed in {time.monotonic() - T0:.0f}s wall")
    if PLATFORM != "tpu":
        say("[rehearsal] passed on the CPU — not a chip run: no result line")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
