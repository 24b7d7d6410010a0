"""The state-space hybrid through the ONE serving path (queue,
``MicroBatcher``, ``LMEngine``, ``GenerateService``), built by the
construction ``can_tpu.cli.serve --model-config`` uses: the seam of
``serve/programs.py`` given a model WITHOUT an expert layer, and the cache
given a fourth kind, ``state``, beside keys and values in one layer.  Tiny
preset, CPU; sibling of ``tests/test_serve_glm.py``."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1_ref as ref
from can_tpu.models import falcon_h1 as fh
from can_tpu.obs import Telemetry, spans
from can_tpu.ops import cache_layout as layout
from can_tpu.serve import GenerateService, build_model_service, lm_probe_steps
from can_tpu.serve import cache as kv_cache
from can_tpu.serve import programs as serve_programs

from lm_tiny import tiny_falcon_config

NEW = 12


def falcon_config(**kw) -> dict:
    d = tiny_falcon_config()
    d.update(max_new_tokens=NEW, prefill_slice=2, length_ladder=[16, 32],
             max_batch=4, queue_capacity=16, max_wait_ms=5.0)
    d.update(kw)
    return d


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def service():
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = fh.FalconH1Config.from_dict(falcon_config())
    params = fh.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(falcon_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


def test_the_table_builds_the_same_programs_class_for_a_dense_model():
    entry = serve_programs.serving_model("falcon_h1")
    made, params = entry.programs(falcon_config(), None, 3)
    assert isinstance(made, serve_programs.LMPrograms)
    assert made.vocab_size == 256 and made.attention_traced is None
    assert made.ssm_traced is fh.ssm_traced
    assert [[s.kind for s in layer] for layer in made.cache_layout] == [
        ["full", "state"]] * 2
    assert params["layers"][1]["mixer"]["out_proj"].shape == (48, 64)
    assert params["embed"].dtype == jnp.bfloat16 == made.dtype
    # what LMPrograms asks of a configuration is what every model has
    assert not hasattr(made.cfg, "mlp_layer_types")
    assert not hasattr(made.cfg, "share")


def test_warmup_compiles_every_bucket(service):
    svc, report, _ = service
    assert isinstance(svc, GenerateService) and svc.sched is None
    assert report["compiles"] == 2 * 2 == svc.engine.compile_count


def test_greedy_ids_and_probe_logits_match_the_reference_through_the_queue(service):
    """Two prompts of different lengths in one launch, right-padded into a
    bucket of four chunks: each request's 12 greedy ids and its probed
    logits are the reference's over ITS OWN sequence, so the state handed to
    decode was the one at its own length."""
    svc, _, _ = service
    before = svc.engine.compile_count
    prompts = [_prompt(27, 99), _prompt(18, 98), _prompt(32, 97)]
    tickets = [svc.submit(p, want_logits=(i != 1)) for i, p in enumerate(prompts)]
    results = [t.result(120) for t in tickets]
    assert svc.engine.compile_count == before
    assert results[1].logits is None
    spec = ref.spec_from_config(falcon_config())
    steps = lm_probe_steps(NEW)
    for p, r in zip(prompts, results):
        assert r.tokens.shape == (NEW,) and r.bucket_hw == (1, 32)
        full = np.asarray(ref.forward(svc.engine.params,
                                      np.concatenate([p, r.tokens]), spec)["logits"])
        want = full[len(p) - 1:len(p) - 1 + NEW]
        margin = want[np.arange(NEW), r.tokens] - want.max(-1)
        assert (margin > -1e-4).all(), margin            # the greedy ids
        if r.logits is None:
            continue
        assert sorted(r.logits) == sorted(["prefill"] + [f"step{s}" for s in steps])
        for name, at in [("prefill", len(p) - 1)] + [
                (f"step{s}", len(p) - 1 + s) for s in steps]:
            np.testing.assert_allclose(r.logits[name], full[at], atol=3e-5,
                                       rtol=3e-5)


def test_an_expert_less_model_s_counters_read_zero_and_its_routing_is_empty(service):
    from can_tpu.obs.exporter import render_stats

    svc, _, _ = service
    r = svc.submit(_prompt(8, 1), want_logits=True).result(120)
    assert all(v.shape == (0, 0) for v in r.routing.values())
    assert set(r.routing) == set(r.logits)
    lm = svc.stats()["lm"]
    assert lm["assignments_held"] == lm["assignments_all"] == 0
    assert lm["expert_tokens_max"] == 0
    assert lm["generated_tokens"] > 0 and lm["launches"] > 0
    last = svc.engine.last_launch
    # the keys the benchmark's probe reads, with empty lists
    assert last["prefill_expert_tokens"] == [] == last["decode_expert_tokens"]
    assert float(sum(map(sum, last["prefill_expert_tokens"]))) == 0.0
    assert (last["valid"], last["steps"]) == (1, NEW)
    # 4 slots x (16 + 12) positions x 2 layers x 2 x 2 heads x 8 x 4 bytes of
    # keys and values; 4 slots x 2 layers x (6 x 8 x 16 + 112 x 3) x 4 bytes
    assert lm["cache_bytes"] == {"full": 4 * 28 * 2 * 2 * 2 * 8 * 4,
                                 "state": 4 * 2 * (768 + 336) * 4}
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert 'can_tpu_serve_lm_cache_bytes{kind="state"}' in text
    assert 'can_tpu_serve_lm_cache_bytes{kind="full"}' in text
    assert "can_tpu_serve_lm_assignments_all_total 0" in text


def _launch_spans(tracer, ticket):
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


def test_the_spans_say_which_form_the_recurrence_ran_in(service):
    svc, _, tracer = service
    _, inner = _launch_spans(tracer, svc.submit(_prompt(9, 7)))
    assert inner["lm.prefill"]["ssm"] == "chunked"
    assert inner["lm.decode"]["ssm"] == "step"
    assert "attention" not in inner["lm.prefill"]    # one form: none noted
    assert svc.engine.ssm_forms[(2, 16)] == "chunked"
    assert svc.engine.ssm_forms[(4, 1)] == "step"
    assert svc.stats()["lm"]["prefill_attention"] == {}


def test_a_model_without_experts_says_nothing_of_them(service):
    svc, _, tracer = service
    _launch_spans(tracer, svc.submit(_prompt(5, 9)))
    assert svc.engine.programs.decode_experts(4) is None
    ring = tracer.snapshot()
    assert all("experts" not in s for s in ring if s["name"] == "lm.decode")
    assert all("experts_read" not in s and "experts_held" not in s
               for s in ring if s["name"] == "serve.fetch")
    lm = svc.stats()["lm"]
    assert lm["decode_experts_read"] == lm["decode_experts_held"] == 0


def test_cli_builds_the_same_service(tmp_path, capsys):
    """``can_tpu.cli.serve --model-config`` takes a third language model."""
    from can_tpu.cli import serve as cli

    path = tmp_path / "tiny-falcon.json"
    path.write_text(json.dumps(falcon_config(length_ladder=[16], max_batch=2)))
    args = cli.parse_args(["--model-config", str(path), "--seed", "4"])
    svc = cli.build_service(args)
    try:
        assert isinstance(svc, GenerateService)
        assert "[serve] warmup:" in capsys.readouterr().out
        with svc:
            r = svc.generate(_prompt(7), timeout=120)
        assert r.tokens.shape == (NEW,)
    finally:
        svc.close()


# -- the fourth kind ------------------------------------------------------
class TestStateKind:
    KV = dict(kv_heads=2, head_dim=8)

    def _block(self):
        return (layout.kv_layer("full", **self.KV),
                layout.state_layer(ssm=((6, 8, 16), "float32"),
                                   conv=((112, 3), None)))

    def test_no_position_axis_and_its_own_dtype(self):
        spec = self._block()[1]
        assert spec.kind == layout.STATE == "state"
        assert spec.shapes(4, 40) == spec.shapes(4, 4000) == {
            "ssm": (4, 6, 8, 16), "conv": (4, 112, 3)}
        assert spec.dtypes == (("ssm", "float32"),)

    def test_two_kinds_in_one_layer_float32_beside_bfloat16(self):
        specs = (self._block(),) * 2
        c = jax.jit(lambda: kv_cache.allocate(specs, slots=4, positions=40))()
        entry = c["layers"][1]
        assert sorted(entry) == ["conv", "k", "ssm", "v"]
        assert {n: (a.shape, a.dtype.name) for n, a in entry.items()} == {
            "k": ((4, 2, 40, 8), "bfloat16"), "v": ((4, 2, 40, 8), "bfloat16"),
            "ssm": ((4, 6, 8, 16), "float32"), "conv": ((4, 112, 3), "bfloat16")}
        f32 = kv_cache.allocate(specs, slots=1, positions=2, dtype=jnp.float32)
        assert f32["layers"][0]["conv"].dtype == jnp.float32 == \
            f32["layers"][0]["ssm"].dtype

    def test_bytes_by_kind(self):
        specs = (self._block(),) * 2
        c = kv_cache.allocate(specs, slots=4, positions=40)
        assert kv_cache.nbytes_by_kind(c, specs) == {
            "full": 2 * 2 * 4 * 2 * 40 * 8 * 2,
            "state": 2 * 4 * (6 * 8 * 16 * 4 + 112 * 3 * 2)}

    def test_the_signature_shows_launch_size_and_context(self):
        """The leaf a compile signature reads has positions: of a layer
        with keys and a state, the keys (``min(entry)`` would be ``conv``,
        which no context shows in)."""
        specs = (self._block(), layout.latent_layer(rank=16, rope_dim=8),
                 layout.state_layer(ssm=((6, 8, 16), "float32")))
        c = kv_cache.allocate(specs, slots=4, positions=40)
        sig = [a.shape for a in kv_cache.signature_leaves(c, specs)]
        assert sig == [(4, 2, 40, 8), (4, 40, 16), (4, 6, 8, 16)]
        longer = kv_cache.allocate(specs, slots=4, positions=48)
        assert [a.shape for a in kv_cache.signature_leaves(longer, specs)][:2] \
            != sig[:2]

    def test_two_leaves_of_one_name_in_a_layer_are_refused(self):
        twice = (layout.kv_layer("full", **self.KV),
                 layout.state_layer(k=((3,), None)))
        with pytest.raises(ValueError, match="two leaves named 'k'"):
            kv_cache.allocate((twice,), slots=1, positions=2)

    def test_a_slice_is_written_at_start_whatever_the_kind(self):
        """``LMPrograms.prefill_slice``'s slot write: slots are every leaf's
        leading axis, so the state of a slice lands at ``start`` as its keys
        do, and the other slots keep theirs."""
        d = tiny_falcon_config()
        cfg = fh.FalconH1Config.from_dict(d)
        params = fh.init_params(jax.random.key(1), cfg, jnp.float32)
        programs = serve_programs.LMPrograms(fh, cfg, max_new_tokens=4,
                                             dtype=jnp.float32)
        cache = programs.new_cache(6, 16)
        cache = jax.tree.map(lambda a: a + 7, cache)
        batch = {"tokens": jnp.asarray(np.stack([_prompt(16, 1), _prompt(16, 2)])),
                 "lengths": jnp.asarray([16, 11]), "active": jnp.asarray([True, True])}
        out, written = programs.prefill_slice(params, batch, cache, jnp.int32(2))
        _, alone, _ = fh.prefill(params, batch["tokens"], batch["lengths"], cfg, 20)
        for mine, theirs in zip(written["layers"], alone["layers"]):
            for name in ("ssm", "conv", "k", "v"):
                np.testing.assert_allclose(np.asarray(mine[name])[2:4],
                                           np.asarray(theirs[name]), atol=1e-6)
                assert (np.asarray(mine[name])[[0, 1, 4, 5]] == 7).all()
        assert out["choices"].shape == (0, 2, 0) and out["counts"].shape == (0, 0)

    def test_decode_leaves_an_inactive_slot_s_state_untouched(self):
        d = tiny_falcon_config()
        cfg = fh.FalconH1Config.from_dict(d)
        params = fh.init_params(jax.random.key(1), cfg, jnp.float32)
        programs = serve_programs.LMPrograms(fh, cfg, max_new_tokens=4,
                                             dtype=jnp.float32)
        batch = {"tokens": jnp.asarray(np.stack([_prompt(16, 1), _prompt(16, 2)])),
                 "lengths": jnp.asarray([16, 11]),
                 "active": jnp.asarray([True, False])}
        out, cache = programs.prefill_slice(params, batch,
                                            programs.new_cache(2, 16), jnp.int32(0))
        state, _ = programs.new_state([out], batch["lengths"], batch["active"])
        assert state["counts"].shape == (0, 0)
        before = [np.asarray(e["ssm"]).copy() for e in cache["layers"]]
        state, cache, step = programs.decode(params, state, cache)
        for was, entry in zip(before, cache["layers"]):
            assert (np.asarray(entry["ssm"])[1] == was[1]).all()
            assert (np.asarray(entry["ssm"])[0] != was[0]).any()
        assert step["choices"].shape == (0, 2, 0)
        assert int(state["step"]) == 2


def test_the_other_models_programs_lower_to_the_text_they_had():
    """K-EXAONE's and GLM's programs do not pass through anything this
    model added: no ``state`` leaf, no multiplier, no new operand."""
    from lm_tiny import tiny_glm_model, tiny_model

    from can_tpu.models import exaone_moe as em
    from can_tpu.models import glm_moe_lite as gm

    for module, (d, cfg, params) in ((em, tiny_model(mtp=0)),
                                     (gm, tiny_glm_model(mtp=0))):
        programs = serve_programs.LMPrograms(module, cfg, max_new_tokens=4,
                                             dtype=jnp.float32)
        cache = programs.new_cache(2, 16)
        assert all(isinstance(s, layout.LayerSpec) for s in programs.cache_layout)
        assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(cache))
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "lengths": jnp.asarray([16, 9]), "active": jnp.asarray([True, True])}
        text = jax.jit(programs.prefill_slice).lower(
            params, batch, cache, jnp.int32(0)).as_text()
        assert "multiply" in text and "exponential" in text
        assert "cumsum" not in text and "log_plus_one" not in text   # no recurrence
