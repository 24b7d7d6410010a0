"""Driver of a language-model serving cell whose model has NO expert layer
(Falcon-H1: a state-space mixer and attention in every block, a dense MLP).
``drive_glm_serve.py`` calls itself the driver "for any model", but reads the
expert counters of every launch (``decode_expert_tokens``, and takes its
``max()``), and may not be edited by the PR that adds a dense model; so this
is its text without them.  What is the loop's (``EngineProbe``,
``_closed_loop``, ``make_prompts``, ``prompt_of``, ``token_sample``,
``wants_logits`` inside ``_closed_loop``) is ``drive_lm_serve``'s, imported
unchanged, so the rate, the segments and the numbers of ``correct`` keep
their definitions, and the traffic file ``chat-1k-256-closed`` means here
what it means in ``serve-exaone-chat-closed``: one request in
``logits_every`` asks for its logits, at a slot that moves on by 17.  The
model's own pieces are found BY NAME from the configuration file
(``reference``, ``weights``, ``work``: module paths); the trace is reduced
by ``drive_glm_serve._reduce`` (``trace_lm_any`` with the work functions
handed in).

The reference returns logits only for the rows that are compared (a
sequence's last prompt position and its generated positions: 257 rows of
261,120 logits), so ``correct_lm.lm_numbers`` is handed those rows with a
prompt of length 1 in front of them: its row arithmetic is then the same.
With no expert layer the reference's ``chosen`` is empty and
``routing_diff_share`` reads 0.

A program without serving programs for the configuration's ``model_type``
(the parent commit of the PR that added it) fails with ``SpecError`` before
a device is opened or a weight is made."""

from __future__ import annotations

import collections
import importlib
import time

from benchmark.harness.drive_glm_serve import _reduce
from benchmark.harness.drive_lm_serve import (EngineProbe, _closed_loop,
                                              make_prompts, prompt_of,
                                              token_sample)


def run(cell, seed, seconds, trace, env):
    from benchmark.harness import spec

    cfg, traffic = cell.config, cell.traffic
    try:
        from can_tpu.serve import build_model_service
        from can_tpu.serve.programs import MODEL_TYPES
    except ImportError as e:
        raise spec.SpecError(
            f"cell {cell.name}: the program under test has no language-model "
            f"serving path (can_tpu.serve.build_model_service): {e}") from None
    if cfg["model_type"] not in MODEL_TYPES:
        raise spec.SpecError(
            f"cell {cell.name}: the program under test has no serving "
            f"programs for model_type {cfg['model_type']!r} (it serves "
            f"{sorted(MODEL_TYPES)})")
    import numpy as np

    ref, weights, work = (importlib.import_module(cfg[k])
                          for k in ("reference", "weights", "work"))
    devices, _ = env.open_devices(cell.chips)
    from can_tpu.obs import Telemetry

    from benchmark.harness import correct, correct_lm, estimator

    if traffic["generator"] != "closed_loop":
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    prompts = make_prompts(traffic, int(cfg["vocab_size"]), seed)
    new_tokens = int(traffic["max_new_tokens"])
    sink = env.ListSink()
    telemetry = Telemetry([sink])
    t0 = time.perf_counter()
    params = weights.make_params(cfg, seed)
    service = build_model_service(cfg, params=params, telemetry=telemetry,
                                  break_programs=env.break_path)
    engine = service.engine = EngineProbe(service.engine)
    print(f"[setup] weights on the device in {time.perf_counter() - t0:.1f}s",
          flush=True)
    t0 = time.perf_counter()
    report = service.warmup()
    print(f"[setup] {report['compiles']} programs warm in "
          f"{time.perf_counter() - t0:.1f}s ({report['sizes']} launch size(s))",
          flush=True)
    service.start()
    max_batch = int(cfg["max_batch"])
    segment = int(traffic["segment_requests"])
    outstanding = int(traffic["clients"])
    logits_every = int(traffic["logits_every"])
    compared_n = int(traffic["logits_compared"])
    tokens_n = int(traffic["tokens_compared"])
    counters, end_to_end = {}, {}
    compiles0 = env.compiles.count
    stats0 = service.stats()
    sink.events.clear()
    del engine.launches[:], engine.spans[:]
    env.setup_done(time.perf_counter())
    if env.first_steps_only:
        # calibration: one launch and the comparison, no window
        t_start, log, failed = _closed_loop(
            service, prompts, max_batch, max_batch, new_tokens,
            max(1, max_batch // compared_n), 0.0, 1, limit=max_batch)
    else:
        t_start, log, failed = _closed_loop(
            service, prompts, outstanding, max_batch, new_tokens, logits_every,
            seconds, segment)
        est = estimator.summarise(
            t_start, estimator.boundaries_from_log([r[0] for r in log], segment),
            float(segment))
        print("[segments] req/s per %d requests: " % segment
              + " ".join(f"{r:.3f}" for r in est["segments"])
              + f" | median {est['segment_median']:.3f} | completed / wall "
              f"{est['rate']:.3f}", flush=True)
        end_to_end["req_per_s"] = est["rate"]
        counters["rate"] = est
    compiled = env.compiles.count - compiles0
    stats1 = service.stats()
    slots = stats1["batch_slots"] - stats0["batch_slots"]
    counters["batch_fill_pct"] = (100.0 * (stats1["batch_valid"] - stats0["batch_valid"])
                                  / max(slots, 1))
    window_launches = list(engine.launches)
    counters["compiles_in_window"] = compiled
    counters["lm"] = stats1["lm"]
    sizes = collections.Counter((l["slots"], l["valid"]) for l in window_launches)
    print("[launches] (slots, valid): count  " + "  ".join(
        f"({s},{v}): {n}" for (s, v), n in sorted(sizes.items(), reverse=True))
        + f" | cache bytes {stats1['lm']['cache_bytes']}"
        + f" | staging {stats1['staging']}", flush=True)

    reduced = None
    if trace:
        del engine.launches[:], engine.spans[:]
        n_traced = int(traffic.get("traced_launches", 3))
        tdir = env.start_trace()
        _closed_loop(service, prompts, outstanding, max_batch, new_tokens, 0,
                     0.0, 1, limit=n_traced * max_batch)
        env.stop_trace()
        # (a test's CPU Env hands no trace directory: nothing to reduce)
        reduced = _reduce(tdir, engine, cfg, env.peaks, work) if tdir else None

    t0 = time.perf_counter()
    service.close()
    dev = env.device_report(devices)
    engine.release_buffers()
    del service, engine
    print(f"[close] service closed in {time.perf_counter() - t0:.1f}s", flush=True)

    # -- the reference: each compared request's whole sequence, float32 ----
    ref_spec = ref.spec_from_config(cfg)
    compared = [{"prompt": prompt_of(prompts, i), "tokens": res.tokens,
                 "logits": res.logits, "routing": res.routing}
                for _, i, res in log if res.logits is not None][:compared_n]
    if not compared:
        raise RuntimeError("no request of the window asked for its logits")
    # the generated ids of more requests than asked for their logits
    others = token_sample(log, prompts, (id(c["prompt"]) for c in compared),
                          tokens_n - len(compared), max_batch)
    n_cmp = len(compared)
    everyone = compared + others
    # one shape for every sequence, seed and run: the largest bucket and its
    # new tokens (attention is causal and the reference's recurrence runs
    # forward: no valid position sees the padding behind it)
    longest = int(cfg["length_ladder"][-1]) + int(cfg["max_new_tokens"])

    def forward(mode, variant=None, upto=None):
        """The reference over each compared sequence, right-padded; only
        the rows that are compared come back: the last prompt position and
        one for each generated token."""
        out = []
        for c in everyone[:upto]:
            n, new = len(c["prompt"]), len(c["tokens"])
            seq = np.concatenate([c["prompt"], c["tokens"]])
            r = ref.forward(params, np.pad(seq, (0, longest - len(seq))),
                            ref_spec, mode, variant,
                            rows=np.arange(n - 1, n + new))
            out.append({"logits": np.asarray(r["logits"]),
                        "chosen": [np.asarray(ch) for ch in r["chosen"]]})
        return out

    def rows_only(c):
        """``c`` as ``lm_numbers`` is to see it: a prompt of length 1 in
        front of the rows the reference returned."""
        return dict(c, prompt=c["prompt"][-1:])

    t0 = time.perf_counter()
    f32, yard = forward("f32"), forward("bf16", upto=n_cmp)
    print(f"[reference] {len(everyone)} sequences of up to "
          f"{max(len(c['prompt']) for c in everyone) + new_tokens} tokens "
          f"(padded to {longest}) in float32, {n_cmp} in bfloat16, in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    margin = float(cfg["correct"]["token_margin_rms"])
    numbers = correct_lm.lm_numbers([rows_only(c) for c in everyone], f32, yard,
                                    token_margin_rms=margin)
    numbers["compiles_in_window"] = float(compiled)
    for mode in env.control_modes:
        # the control: the reference in a lower precision, or with one piece
        # of the mathematics broken, in the program's place on the same
        # sequences (its own argmax tokens)
        out = forward(*(("bf16", mode.split(":", 1)[1]) if mode.startswith("variant:")
                        else (mode,)))
        stand_in = []
        for c, o in zip(everyone, out):
            s = {"prompt": c["prompt"][-1:],
                 "tokens": o["logits"][:len(c["tokens"])].argmax(-1)}
            if "logits" in c:
                rows = correct_lm.probe_positions(1, c["logits"])
                s["logits"] = {k: o["logits"][r] for k, r in rows.items()}
                s["routing"] = {k: np.zeros((0, 0), np.int32) for k in rows}
            stand_in.append(s)
        control = correct_lm.lm_numbers(stand_in, f32, yard, token_margin_rms=margin)
        print(f"[control {mode}] " + " ".join(f"{k}={v:.6g}" for k, v in control.items()),
              flush=True)
        env.control_numbers[mode] = control
    ok = correct.judge(numbers, cfg["limits"])
    return env.Result(correct=ok and failed == 0, attempted=len(log) + failed,
                      failed=failed, end_to_end=end_to_end, counters=counters,
                      reduced=reduced, device=dev, numbers=numbers)
