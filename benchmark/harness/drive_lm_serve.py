"""Driver of the language-model serving cells: the service
``can_tpu.serve.build_model_service`` builds (the construction
``can_tpu.cli.serve --model-config`` uses: queue, batcher, engine, service),
in process, under a closed loop kept in flight by one thread
(``drive_serve.py`` says why one).  The rate is all completed requests over
the whole window; the segments of equal work are printed beside it.
``correct`` is decided outside the window from what the timed path
produced (``correct_lm.py``)."""

from __future__ import annotations

import collections
import time


class EngineProbe:
    """The benchmark's own call boundary around ``generate_batch``."""

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "launches", [])
        object.__setattr__(self, "spans", [])

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)

    def generate_batch(self, batch, **kw):
        t0 = time.perf_counter()
        out = self._engine.generate_batch(batch, **kw)
        live = batch.sample_mask > 0
        last = self._engine.last_launch
        self.spans.append(("bench:launch", t0, time.perf_counter()))
        self.launches.append({
            "slots": int(batch.tokens.shape[0]), "bucket": int(batch.tokens.shape[1]),
            "valid": int(live.sum()),
            "lengths": [int(n) for n in batch.lengths[live]],
            "slices": len(self._engine._slices(batch.tokens.shape[0])),
            "steps": int(last["steps"]),
            "held_prefill": float(sum(map(sum, last["prefill_expert_tokens"]))),
            "decode_expert_tokens": last["decode_expert_tokens"],
            "assignments_held": last["assignments_held"],
            "assignments_all": last["assignments_all"]})
        return out


def prompt_of(prompts, i: int):
    """Request ``i``'s prompt: the distinct prompts round after round, each
    round starting one further on.  Every full round holds every prompt
    once (a launch's work is the same set of lengths whatever the round),
    and the requests that ask for their logits carry different prompts:
    their probes are independent readings, not one reading repeated."""
    n = len(prompts)
    return prompts[(i + i // n) % n]


def wants_logits(i: int, every: int) -> bool:
    """Whether request ``i`` asks for its logits: one request in ``every``,
    at a place that moves on by 17 from one group of ``every`` to the next
    (with 64 to a launch: slots 0, 17, 34, 51, ...), so that the compared
    probes come from different slots, prefill slices and cache offsets."""
    return bool(every) and i % every == (17 * (i // every)) % every


def token_sample(log, prompts, taken, want: int, launch: int):
    """The requests whose generated ids alone are compared: ``want`` of
    them, at every ``launch // want``-th place of the window's first
    requests (with 64 to a launch and 12 wanted: slots 2, 7, ..., 62, every
    prefill slice among them), each with a prompt no compared request has."""
    if want <= 0:
        return []
    stride = max(1, launch // want)
    seen, out = set(taken), []
    for _, i, res in sorted(log, key=lambda r: r[1]):
        p = prompt_of(prompts, i)
        if i % stride == stride // 2 and id(p) not in seen and len(out) < want:
            seen.add(id(p))
            out.append({"prompt": p, "tokens": res.tokens})
    return out


def _closed_loop(service, prompts, outstanding, group, new_tokens, logits_every,
                 seconds, segment, limit=None):
    """``drive_serve._closed_loop`` for prompts: ``outstanding`` requests in
    flight, the oldest ``group`` answers awaited and replaced together.
    Ends at the first segment boundary at or after ``seconds`` (or after
    ``limit`` requests).  -> (start, completion log, failed)."""
    from can_tpu.serve import RejectedError

    log, failed, tickets = [], 0, collections.deque()
    target = limit
    t_start = time.perf_counter()

    def submit(n):
        for _ in range(n):
            i = submit.next
            if target is not None and i >= target:
                return
            submit.next = i + 1
            tickets.append((i, service.submit(
                prompt_of(prompts, i), max_new_tokens=new_tokens,
                want_logits=wants_logits(i, logits_every))))
    submit.next = 0

    submit(outstanding)
    while tickets:
        n = min(group, len(tickets))
        for _ in range(n):
            i, ticket = tickets.popleft()
            try:
                res = ticket.result(600.0)
                log.append((time.perf_counter(), i, res))
            except RejectedError:
                failed += 1
        if target is None and time.perf_counter() - t_start >= seconds:
            target = -(-submit.next // segment) * segment
        submit(n)
    return t_start, log, failed


def make_prompts(traffic: dict, vocab: int, seed: int):
    """``distinct_prompts`` prompts: lengths uniform in ``prompt_tokens``
    from the traffic file's OWN seed (the same schedule of shapes for every
    ``--seed``), ids from ``--seed`` inside the vocabulary slice."""
    import numpy as np

    lo, hi = traffic["prompt_tokens"]
    lengths = np.random.default_rng(int(traffic["length_seed"])).integers(
        lo, hi + 1, int(traffic["distinct_prompts"]))
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lengths]


def _reduce(tdir, engine, cfg, peaks):
    import shutil

    from benchmark.harness import program_spans, trace, trace_lm

    events = trace.load(trace.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    ring = program_spans.read()
    marks = (ring.as_marks(engine.spans[0][1], engine.spans[-1][2])
             if ring is not None else engine.spans)
    # generate_batch returns once the ids are fetched: its last return is
    # the end of the last decode step, give or take the fetch
    trace.place_spans(events, marks, engine.spans[-1][2], trace_lm.DECODE)
    reduced = trace_lm.reduce(events, list(engine.launches), cfg=cfg, peaks=peaks)
    print(f"[trace] read {reduced['launches']} of {len(engine.launches)} "
          f"traced launches", flush=True)
    return reduced


def run(cell, seed, seconds, trace, env):
    from benchmark.harness import spec

    try:
        from can_tpu.serve import build_model_service
    except ImportError as e:
        raise spec.SpecError(
            f"cell {cell.name}: the program under test has no language-model "
            f"serving path (can_tpu.serve.build_model_service): {e}") from None
    import numpy as np

    cfg, traffic = cell.config, cell.traffic
    devices, _ = env.open_devices(cell.chips)
    from can_tpu.obs import Telemetry

    from benchmark.harness import correct, correct_lm, estimator, weights_lm

    if traffic["generator"] != "closed_loop":
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    prompts = make_prompts(traffic, int(cfg["vocab_size"]), seed)
    new_tokens = int(traffic["max_new_tokens"])
    sink = env.ListSink()
    telemetry = Telemetry([sink])
    t0 = time.perf_counter()
    params = weights_lm.make_params(cfg, seed)
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
    held = sum(l["assignments_held"] for l in window_launches)
    every = sum(l["assignments_all"] for l in window_launches)
    counters["expert_local_share_pct"] = 100.0 * held / max(every, 1)
    loads = [np.asarray(l["decode_expert_tokens"], np.float64)
             for l in window_launches if l["valid"]]
    counters["expert_load_max_over_mean"] = float(np.mean(
        [x.max() / max(x.mean(), 1e-30) for x in loads])) if loads else None
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
        reduced = _reduce(tdir, engine, cfg, env.peaks) if tdir else None

    t0 = time.perf_counter()
    service.close()
    dev = env.device_report(devices)
    engine.release_buffers()
    del service, engine
    print(f"[close] service closed in {time.perf_counter() - t0:.1f}s", flush=True)

    # -- the reference: each compared request's whole sequence, float32 ----
    from benchmark.reference import exaone_moe_ref as ref

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
    seqs = [np.concatenate([c["prompt"], c["tokens"]]) for c in compared + others]
    longest = max(len(s) for s in seqs)

    def forward(mode, variant=None, seqs=seqs):
        """The reference over each compared sequence, right-padded to the
        longest so that one compiled block serves them all (the masks are
        causal: no valid position sees the padding); the rows read are the
        sequence's own."""
        out = []
        for s in seqs:
            r = ref.forward(params, np.pad(s, (0, longest - len(s))), ref_spec,
                            mode, variant)
            out.append({"logits": np.asarray(r["logits"])[:len(s)],
                        "chosen": [np.asarray(c)[:len(s)] for c in r["chosen"]]})
        return out

    t0 = time.perf_counter()
    f32, yard = forward("f32"), forward("bf16", seqs=seqs[:n_cmp])
    print(f"[reference] {len(seqs)} sequences of up to {longest} tokens in float32, "
          f"{n_cmp} in bfloat16, in {time.perf_counter() - t0:.1f}s", flush=True)
    margin = float(cfg["correct"]["token_margin_rms"])
    numbers = correct_lm.lm_numbers(compared + others, f32, yard,
                                    token_margin_rms=margin)
    numbers["compiles_in_window"] = float(compiled)
    for mode in env.control_modes:
        # the control: the reference in a lower precision, or with one piece
        # of the mathematics broken, in the program's place on the same
        # sequences (its own argmax tokens, its own routing)
        out = forward(*(("bf16", mode.split(":", 1)[1]) if mode.startswith("variant:")
                        else (mode,)))
        stand_in = []
        for c, o in zip(compared + others, out):
            n = len(c["prompt"])
            s = {"prompt": c["prompt"],
                 "tokens": o["logits"][n - 1:n - 1 + len(c["tokens"])].argmax(-1)}
            if "logits" in c:
                rows = correct_lm.probe_positions(n, c["logits"])
                s["logits"] = {k: o["logits"][r] for k, r in rows.items()}
                s["routing"] = {k: np.stack([ch[r] for ch in o["chosen"]])
                                for k, r in rows.items()}
            stand_in.append(s)
        control = correct_lm.lm_numbers(stand_in, f32, yard, token_margin_rms=margin)
        print(f"[control {mode}] " + " ".join(f"{k}={v:.6g}" for k, v in control.items()),
              flush=True)
        env.control_numbers[mode] = control
    ok = correct.judge(numbers, cfg["limits"])
    return env.Result(correct=ok and failed == 0, attempted=len(log) + failed,
                      failed=failed, end_to_end=end_to_end, counters=counters,
                      reduced=reduced, device=dev, numbers=numbers)
