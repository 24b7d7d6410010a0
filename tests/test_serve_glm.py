"""The latent-attention model through the ONE serving path (queue,
``MicroBatcher``, ``LMEngine``, ``GenerateService``), built by the
construction ``can_tpu.cli.serve --model-config`` uses: the seam of
``serve/programs.py`` given a second model.  Tiny preset, CPU; sibling of
``tests/test_serve_lm.py``."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_moe_lite_ref as ref
from can_tpu.models import glm_moe_lite as gm
from can_tpu.obs import Telemetry, spans
from can_tpu.serve import GenerateService, build_model_service, lm_probe_steps
from can_tpu.serve import programs as serve_programs

from lm_tiny import (interpret_fused_latent, interpret_skipping_experts,
                     tiny_glm_config)

NEW = 6


def glm_config(**kw) -> dict:
    d = tiny_glm_config(mtp=0)
    d.update(max_new_tokens=NEW, prefill_slice=2, length_ladder=[16, 32],
             max_batch=4, queue_capacity=16, max_wait_ms=5.0)
    d.update(kw)
    return d


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def service():
    block, gm.PREFILL_BLOCK = gm.PREFILL_BLOCK, 8   # buckets of 2 and 4 blocks
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = gm.Glm4MoeLiteConfig.from_dict(glm_config())
    params = gm.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(glm_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()
    gm.PREFILL_BLOCK = block


def test_the_table_builds_the_same_programs_class_for_both_models():
    entry = serve_programs.serving_model("glm4_moe_lite")
    made, params = entry.programs(glm_config(), None, 3)
    assert isinstance(made, serve_programs.LMPrograms)
    assert made.vocab_size == 256 and made.ssm_traced is None
    assert [s.kind for s in made.cache_layout] == ["latent"] * 3
    assert params["layers"][1]["attn"]["wkv_a"].shape == (64, 24)
    assert params["embed"].dtype == jnp.bfloat16 == made.dtype


def test_warmup_compiles_every_bucket(service):
    svc, report, _ = service
    assert isinstance(svc, GenerateService) and svc.sched is None
    assert report["compiles"] == 2 * 2 == svc.engine.compile_count


def test_generated_ids_and_probe_logits_match_the_reference(service):
    """Prefill in the expanded form, then decode through the latent cache,
    from another slot than 0 and a bucket of four blocks."""
    svc, _, _ = service
    before = svc.engine.compile_count
    p = _prompt(27, 99)
    other = svc.submit(_prompt(11, 98))
    r = svc.submit(p, want_logits=True).result(120)
    assert other.result(120).logits is None
    assert svc.engine.compile_count == before
    assert r.tokens.shape == (NEW,) and r.bucket_hw == (1, 32)
    steps = lm_probe_steps(NEW)
    assert sorted(r.logits) == sorted(["prefill"] + [f"step{s}" for s in steps])
    spec = ref.spec_from_config(glm_config())
    seq = np.concatenate([p, r.tokens])
    full = ref.forward(svc.engine.params, seq, spec)
    for name, at in [("prefill", len(p) - 1)] + [
            (f"step{s}", len(p) - 1 + s) for s in steps]:
        np.testing.assert_allclose(r.logits[name], np.asarray(full["logits"][at]),
                                   atol=3e-5, rtol=3e-5)
        for layer, chosen in enumerate(full["chosen"]):
            assert (np.sort(r.routing[name][layer])
                    == np.sort(np.asarray(chosen[at]))).all()
    want = np.asarray(full["logits"])[len(p) - 1:len(p) - 1 + NEW]
    margin = want[np.arange(NEW), r.tokens] - want.max(-1)
    assert (margin > -1e-4).all(), margin


def test_counters_say_the_cache_is_latent(service):
    from can_tpu.obs.exporter import render_stats

    svc, _, _ = service
    svc.submit(_prompt(8, 1)).result(120)
    lm = svc.stats()["lm"]
    # 4 slots x (16 + 6) positions x 3 layers x (16 + 8) numbers x 4 bytes
    assert lm["cache_bytes"] == {"latent": 4 * 22 * 3 * 24 * 4}
    assert lm["assignments_held"] == lm["assignments_all"] > 0
    last = svc.engine.last_launch
    assert np.sum(last["decode_expert_tokens"]) == last["valid"] * last["steps"] * 4 * 2
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert 'can_tpu_serve_lm_cache_bytes{kind="latent"}' in text


def _launch_spans(tracer, ticket):
    """-> (the ring, {name: span} directly under the ticket's launch)."""
    ticket.result(120)
    want = ticket._request.batch_span.span_id
    for _ in range(500):
        ring = tracer.snapshot()
        if any(s["span_id"] == want for s in ring):
            break
        time.sleep(0.01)
    launch = next(s for s in ring if s["name"] == "serve.dispatch"
                  and s.get("parent_id") == want)
    return ring, {s["name"]: s for s in ring
                  if s.get("parent_id") == launch["span_id"]}


def test_prefill_spans_name_every_slice(service):
    svc, _, tracer = service
    ring, inner = _launch_spans(tracer, svc.submit(_prompt(12, 5)))
    assert set(inner) == {"lm.prefill", "lm.decode"}
    pre = inner["lm.prefill"]
    assert pre["slices"] == 2
    assert (pre["tokens"], pre["valid_tokens"]) == (64, 12)
    slices = sorted((s for s in ring if s["name"] == "lm.prefill.dispatch"
                     and s.get("parent_id") == pre["span_id"]),
                    key=lambda s: s["slice"])
    assert [(s["slice"], s["start_slot"]) for s in slices] == [(0, 0), (1, 2)]
    steps = [s for s in ring if s["name"] == "lm.decode.dispatch"
             and s.get("parent_id") == inner["lm.decode"]["span_id"]]
    assert len(steps) == NEW


def test_the_prefill_says_which_form_its_attention_ran_in(service):
    """On the CPU (and at heads of 16) ``supports`` refuses the fused
    kernel: the span, the engine and the counters all say ``scanned``; the
    warm-up's launches are not traffic and are not counted."""
    from can_tpu.obs.exporter import render_stats

    svc, _, tracer = service
    before = dict(svc.stats()["lm"]["prefill_attention"])
    pre = _launch_spans(tracer, svc.submit(_prompt(9, 7)))[1]["lm.prefill"]
    assert pre["attention"] == "scanned"
    # one program a (prompts in a slice, bucket): two buckets warmed up
    assert svc.engine.prefill_attention == {(2, 16): "scanned",
                                            (2, 32): "scanned"}
    lm = svc.stats()["lm"]
    assert set(lm["prefill_attention"]) == {"scanned"}
    assert (lm["prefill_attention"]["scanned"]
            == before.get("scanned", 0) + 1 <= lm["launches"])
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert ('can_tpu_serve_lm_prefill_launches_total{attention="scanned"} '
            f'{lm["prefill_attention"]["scanned"]}') in text


def test_the_span_says_fused_where_the_program_traced_the_kernel(service,
                                                                 monkeypatch):
    """What the span carries is what the program's trace noted, not what
    the engine assumes: a program noted as ``fused`` reads so."""
    svc, _, tracer = service
    monkeypatch.setitem(svc.engine.prefill_attention, (2, 16), "fused")
    pre = _launch_spans(tracer, svc.submit(_prompt(10, 8)))[1]["lm.prefill"]
    assert pre["attention"] == "fused"
    assert svc.stats()["lm"]["prefill_attention"]["fused"] == 1


@pytest.fixture
def kernel_service(monkeypatch):
    """A service of its own with a latent of whole lanes (rank 128, rotary
    keys 16 wide) over a context of 134 positions, the decode step's kernel
    interpreted in blocks of 128: what a TPU backend turns on."""
    interpret_fused_latent(monkeypatch)
    config = glm_config(kv_lora_rank=128, qk_rope_head_dim=16,
                        length_ladder=[128], max_batch=2)
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = gm.Glm4MoeLiteConfig.from_dict(config)
    params = gm.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(config, params=params, telemetry=tel)
    svc.warmup()
    svc.start()
    yield svc, None, tracer
    svc.close()


@pytest.mark.parametrize("which,slots,form", [("service", 4, "plain"),
                                              ("kernel_service", 2, "fused")])
def test_the_decode_says_which_form_read_the_latent_cache(request, which,
                                                          slots, form):
    """``lm.decode`` carries ``latent`` = what the step's trace noted
    (``glm_moe_lite.latent_traced``): ``plain`` on the CPU and at a rank of
    16, ``fused`` where ``pallas_latent.supports`` says yes; the program's
    map says how many whole copies of a cache leaf the step makes."""
    svc, _, tracer = request.getfixturevalue(which)
    ring, inner = _launch_spans(tracer, svc.submit(_prompt(11, 9)))
    assert inner["lm.decode"]["latent"] == form
    assert "latent" not in inner["lm.prefill"]
    assert "retention" not in inner["lm.decode"]
    assert svc.engine.latent_forms == {(slots, 1): form}
    scopes = {s["program"]: s for s in ring if s["name"] == "program.scopes"}
    # (recorded at the program's first launch under the tracer: the warm-up's)
    assert scopes["jit_decode"]["cache_copies"] == 0
    assert "attn.core" in scopes["jit_decode"]["parts"].values()


def test_cli_serves_the_model_from_its_configuration_file(tmp_path, capsys):
    from can_tpu.cli import serve as cli

    path = tmp_path / "tiny-glm.json"
    path.write_text(json.dumps(glm_config(length_ladder=[16], max_batch=2)))
    svc = cli.build_service(cli.parse_args(["--model-config", str(path),
                                            "--seed", "4"]))
    try:
        assert isinstance(svc.engine.programs.cfg, gm.Glm4MoeLiteConfig)
        assert "[serve] warmup:" in capsys.readouterr().out
        with svc:
            r = svc.generate(_prompt(7), timeout=120)
        assert r.tokens.shape == (NEW,)
    finally:
        svc.close()


# -- the decode step's expert layers: which form, and what it read ----------
def test_the_batched_form_reads_every_held_expert(service):
    """On the CPU the decode step's expert layers take the batched form:
    the ``lm.decode`` span says so, and read = held = expert layers x held
    experts x steps on the launch's ``serve.fetch`` span and in the
    counters (the warm-up's launches are not traffic)."""
    from can_tpu.obs.exporter import render_stats

    svc, _, tracer = service
    assert svc.engine.programs.decode_experts(4) == "batched"
    before = svc.stats()["lm"]
    ticket = svc.submit(_prompt(9, 21))
    ring, inner = _launch_spans(tracer, ticket)
    assert inner["lm.decode"]["experts"] == "batched"
    batch = ticket._request.batch_span.span_id
    fetch = next(s for s in ring if s["name"] == "serve.fetch"
                 and s.get("parent_id") == batch)
    assert fetch["experts_read"] == fetch["experts_held"] == 2 * 16 * NEW
    lm = svc.stats()["lm"]
    assert (lm["decode_experts_held"] - before["decode_experts_held"]
            == lm["decode_experts_read"] - before["decode_experts_read"]
            == 2 * 16 * NEW)
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert (f'can_tpu_serve_lm_decode_experts_read_total '
            f'{lm["decode_experts_read"]}') in text
    assert (f'can_tpu_serve_lm_decode_experts_held_total '
            f'{lm["decode_experts_held"]}') in text


@pytest.fixture()
def skipping(monkeypatch):
    interpret_skipping_experts(monkeypatch)


def test_the_skipping_form_counts_the_experts_it_read(skipping):
    """Widths of whole lanes (hidden 128, experts 128 wide), 4 slots of 16
    experts top-4: the decode step's expert layers take the kernel; the
    state carries the counter, the answers are the batched program's, and
    ``experts_read`` is the number of distinct experts the step's rows chose,
    layer by layer (every row reads, live or not)."""
    from can_tpu.serve.engine import LMEngine
    from can_tpu.serve.kinds import TokenBatch

    config = glm_config(hidden_size=128, moe_intermediate_size=128)
    cfg = gm.Glm4MoeLiteConfig.from_dict(config)
    params = gm.init_params(jax.random.key(5), cfg, jnp.float32)
    programs = serve_programs.LMPrograms(gm, cfg, max_new_tokens=NEW,
                                         dtype=jnp.float32)
    assert programs.decode_experts(4) == "skipping"
    assert programs.decode_experts(64) == "batched"     # nobody idle
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    engine = LMEngine(params, programs, prefill_slice=2, telemetry=tel)
    tokens = np.stack([np.pad(_prompt(n, n), (0, 16 - n)) for n in (9, 16, 5, 1)])
    batch = TokenBatch(tokens, np.asarray([9, 16, 5, 1], np.int32),
                       np.asarray([1, 1, 1, 0], np.float32))
    ids, probes = engine.generate_batch(batch, steps=1, want_logits=True)
    chosen = probes["step1"]["choices"]              # (expert layers, slots, k)
    read = sum(len(np.unique(layer)) for layer in chosen)
    assert engine.counters["decode_experts_read"] == read < 2 * 16
    assert engine.counters["decode_experts_held"] == 2 * 16
    ring = tracer.snapshot()
    assert next(s for s in ring if s["name"] == "lm.decode")["experts"] == "skipping"
    fetch = next(s for s in ring if s["name"] == "serve.fetch")
    assert (fetch["experts_read"], fetch["experts_held"]) == (read, 32)
    # more steps: one program for all of them (the counter is in the state
    # from the first), and the plain form's tokens
    compiled = engine.compile_count
    ids, _ = engine.generate_batch(batch, steps=NEW)
    assert engine.compile_count == compiled
    assert engine.counters["decode_experts_held"] == 32 * (1 + NEW)


def test_the_skipping_program_answers_as_the_batched_one(skipping, monkeypatch):
    from can_tpu.ops import moe as moe_ops
    from can_tpu.serve.engine import LMEngine
    from can_tpu.serve.kinds import TokenBatch

    config = glm_config(hidden_size=128, moe_intermediate_size=128)
    cfg = gm.Glm4MoeLiteConfig.from_dict(config)
    params = gm.init_params(jax.random.key(6), cfg, jnp.float32)
    tokens = np.stack([np.pad(_prompt(n, n), (0, 16 - n)) for n in (12, 16, 7, 3)])
    batch = TokenBatch(tokens, np.asarray([12, 16, 7, 3], np.int32),
                       np.ones((4,), np.float32))

    def launch():
        programs = serve_programs.LMPrograms(gm, cfg, max_new_tokens=NEW,
                                             dtype=jnp.float32)
        engine = LMEngine(params, programs, prefill_slice=2)
        return engine, engine.generate_batch(batch, want_logits=True)

    kernel, (ids, probes) = launch()
    assert kernel.counters["decode_experts_read"] < kernel.counters["decode_experts_held"]
    monkeypatch.setattr(moe_ops, "SKIP_MIN_IDLE", 2.0)   # nothing is that idle
    plain, (want_ids, want) = launch()
    assert plain.counters["decode_experts_read"] == plain.counters["decode_experts_held"]
    assert (ids == want_ids).all()
    for name in want:
        np.testing.assert_allclose(probes[name]["logits"], want[name]["logits"],
                                   atol=2e-5, rtol=2e-5)
        assert (probes[name]["choices"] == want[name]["choices"]).all()
