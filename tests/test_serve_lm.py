"""The serving seam: request kinds through the one ``MicroBatcher``, the
language model's engine and service (prefill, then decode steps over a
cache on the device), built by the construction ``can_tpu.cli.serve
--model-config`` uses.  Tiny preset, CPU."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import exaone_moe_ref as ref
from can_tpu.data.batching import pad_batch
from can_tpu.models import exaone_moe as em
from can_tpu.obs import Telemetry, spans
from can_tpu.sched import ServeSched
from can_tpu.serve import (
    BoundedRequestQueue,
    GenerateService,
    MicroBatcher,
    ServeRequest,
    TokenRequest,
    build_model_service,
    lm_probe_steps,
)
from can_tpu.ops import cache_layout as layout
from can_tpu.serve import cache as kv_cache
from can_tpu.serve.kinds import TOKENS, ImageKind, TokenKind

from lm_tiny import tiny_config

NEW = 6


def lm_config(**kw) -> dict:
    d = tiny_config(mtp=0)
    d.update(model_type="exaone_moe", max_new_tokens=NEW, prefill_slice=2,
             length_ladder=[16, 32], max_batch=4, queue_capacity=16,
             max_wait_ms=5.0)
    d.update(kw)
    return d


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


class _Sink:
    """A dispatch that records copies of what it was handed."""

    def __init__(self):
        self.calls = []

    def __call__(self, bucket, batch, requests):
        arrays = (batch if isinstance(batch, tuple)
                  else [getattr(batch, f.name) for f in dataclasses.fields(batch)])
        self.calls.append((bucket, type(batch)(*[np.array(a) for a in arrays]),
                           list(requests)))
        for r in requests:
            r.resolve(None)


def _token_batcher(sink, *, max_batch=4, sched=None, free=True):
    q = BoundedRequestQueue(32)
    b = MicroBatcher(q, sink, max_batch=max_batch, max_wait_ms=1.0,
                     kinds={TOKENS: TokenKind([16, 32])}, sched=sched,
                     batch_free_on_return=free)
    return q, b


class TestTokenKindThroughMicroBatcher:
    def test_bucket_and_assembly(self):
        sink = _Sink()
        q, b = _token_batcher(sink)
        reqs = [TokenRequest(_prompt(n, n), max_new_tokens=4, deadline_s=None)
                for n in (9, 16, 3, 12)]
        for r in reqs:
            q.offer(r)
        assert b.intake() == 1            # four prompts of one bucket: full
        bucket, batch, group = sink.calls[0]
        assert bucket == (1, 16) and group == reqs
        assert batch.tokens.shape == (4, 16) and batch.tokens.dtype == np.int32
        assert batch.lengths.tolist() == [9, 16, 3, 12]
        assert batch.sample_mask.tolist() == [1, 1, 1, 1]
        for slot, r in enumerate(reqs):
            n = r.shape[1]
            assert (batch.tokens[slot, :n] == r.tokens).all()
            assert (batch.tokens[slot, n:] == 0).all()

    def test_two_buckets_never_share_a_launch(self):
        sink = _Sink()
        q, b = _token_batcher(sink)
        for n in (9, 20, 16, 32):
            q.offer(TokenRequest(_prompt(n), max_new_tokens=4, deadline_s=None))
        b.intake()
        b.flush_all()
        assert sorted(c[0] for c in sink.calls) == [(1, 16), (1, 32)]
        assert all(len(c[2]) == 2 for c in sink.calls)

    def test_staging_buffer_reused_and_stale_tail_zeroed(self):
        sink = _Sink()
        q, b = _token_batcher(sink)
        for round_, lengths in enumerate([(16, 16, 16, 16), (5, 7, 2, 9)]):
            for n in lengths:
                q.offer(TokenRequest(_prompt(n, 10 * round_ + n),
                                     max_new_tokens=4, deadline_s=None))
            b.intake()
        assert b.staging["fresh"] == 1 and b.staging["reused"] == 1
        assert b.staging["bytes_held"] == 4 * 16 * 4 + 4 * 4 + 4 * 4
        second = sink.calls[1][1]
        for slot, r in enumerate(sink.calls[1][2]):
            n = r.shape[1]
            assert (second.tokens[slot, :n] == r.tokens).all()
            assert (second.tokens[slot, n:] == 0).all()   # the 16s are gone

    def test_partial_launch_through_the_menu(self):
        sink = _Sink()
        sched = ServeSched(4, max_wait_s=1e-3, menu=(4, 2, 1))
        q, b = _token_batcher(sink, sched=sched)
        for n in (9, 4, 11):
            q.offer(TokenRequest(_prompt(n), max_new_tokens=4, deadline_s=None))
        b.intake()
        b.flush_all()
        sizes = sorted(c[1].tokens.shape[0] for c in sink.calls)
        assert sizes == [1, 2]            # 3 = 2 + 1, every size a menu size
        assert sum(len(c[2]) for c in sink.calls) == 3

    def test_dead_slots_are_masked(self):
        kind = TokenKind([16])
        reqs = [TokenRequest(_prompt(5), max_new_tokens=2, deadline_s=None)]
        batch = kind.assemble((1, 16, "i32"), reqs, 4, None)
        assert batch.sample_mask.tolist() == [1, 0, 0, 0]
        assert batch.lengths.tolist() == [5, 1, 1, 1]
        assert (batch.tokens[1:] == 0).all()

    def test_request_past_the_ladder_is_refused(self):
        kind = TokenKind([16, 32])
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            kind.bucket_of(33)
        assert kind.bucket_of(17) == 32 and kind.bucket_of(16) == 16


class TestImageKindIsWhatItWas:
    """The seam changed nothing for CANNet: an image launch is byte for byte
    ``snap_to_bucket`` + ``pad_batch`` with zero density targets."""

    def _requests(self, dtype):
        rng = np.random.default_rng(0)
        shapes = [(40, 56), (64, 64), (24, 64)]
        imgs = [(rng.standard_normal((h, w, 3)) * 50).astype(dtype)
                for h, w in shapes]
        return [ServeRequest(im, deadline_s=None) for im in imgs]

    @pytest.mark.parametrize("dtype", [np.float32, np.uint8])
    @pytest.mark.parametrize("staged", [False, True])
    def test_image_batch_byte_for_byte(self, dtype, staged):
        sink = _Sink()
        q = BoundedRequestQueue(16)
        b = MicroBatcher(q, sink, max_batch=4, max_wait_ms=1.0,
                         bucket_ladder=((64,), (64,)),
                         batch_free_on_return=staged)
        for round_ in range(2):           # the second launch reuses the buffer
            reqs = self._requests(dtype)[round_:]
            for r in reqs:
                q.offer(r)
            b.intake()
            b.flush_all()
            bucket, batch, group = sink.calls[-1]
            assert bucket == (64, 64) and group == reqs
            items = [(r.image, np.zeros((r.shape[0] // 8, r.shape[1] // 8, 1),
                                        np.float32)) for r in reqs]
            want = pad_batch(items, (64, 64), 4, [True] * len(items), 8)
            for f in dataclasses.fields(want):
                got, ref_ = getattr(batch, f.name), getattr(want, f.name)
                assert got.dtype == ref_.dtype and got.shape == ref_.shape
                assert got.tobytes() == ref_.tobytes()
        assert b.staging == ({"reused": 1, "fresh": 1,
                              "bytes_held": b.staging["bytes_held"]}
                             if staged else
                             {"reused": 0, "fresh": 2, "bytes_held": 0})

    def test_group_key_is_bucket_and_dtype(self):
        kind = ImageKind(bucket_ladder=((64, 128), (64, 128)))
        r = ServeRequest(np.zeros((72, 40, 3), np.uint8), deadline_s=None)
        assert r.kind == "image"
        assert kind.group_key(r) == (128, 64, "uint8")

    def test_batcher_bucket_of_is_the_image_kinds(self):
        b = MicroBatcher(BoundedRequestQueue(4), lambda *a: None,
                         bucket_ladder=((64, 128), (96,)))
        assert b.bucket_of((70, 80)) == (128, 96)


class TestCache:
    KV = dict(kv_heads=2, head_dim=16)

    def _exaone_layout(self):
        ring = layout.kv_layer("ring", window=8, **self.KV)
        return (ring, ring, ring, layout.kv_layer("full", **self.KV), ring)

    def test_allocation_by_kind_and_bytes(self):
        specs = self._exaone_layout()
        c = jax.jit(lambda: kv_cache.allocate(specs, slots=4, positions=40))()
        shapes = [e["k"].shape for e in c["layers"]]
        assert shapes == [(4, 2, 8, 16)] * 3 + [(4, 2, 40, 16), (4, 2, 8, 16)]
        assert all(sorted(e) == ["k", "v"] for e in c["layers"])
        assert kv_cache.nbytes_by_kind(c, specs) == {
            "full": 2 * 4 * 2 * 40 * 16 * 2, "ring": 4 * 2 * 4 * 2 * 8 * 16 * 2}

    def test_published_cell_cache_bytes(self):
        """64 sequences of 1,280 positions: 0.34 GB in the full layer, 0.13
        in the four rings (ISSUE 26's arithmetic)."""
        kv = dict(kv_heads=8, head_dim=128)
        nbytes = lambda spec: sum(  # noqa: E731
            int(np.prod(s)) * 2 for s in spec.shapes(64, 1280).values())
        full = nbytes(layout.kv_layer("full", **kv))
        ring = nbytes(layout.kv_layer("ring", window=128, **kv)) * 4
        assert (full, ring) == (335_544_320, 134_217_728)

    def test_unknown_kind_refused(self):
        with pytest.raises(ValueError, match="unknown cache layer kind"):
            layout.kv_layer("paged", kv_heads=1, head_dim=1)
        with pytest.raises(ValueError, match="needs its window"):
            layout.kv_layer("ring", kv_heads=1, head_dim=1)

    def test_three_kinds_live_in_one_cache(self):
        """A ring, a full and a latent layer side by side: each entry has
        its kind's leaves, the bytes are reported for every kind present,
        and a compile signature tells two such caches apart."""
        specs = (layout.kv_layer("ring", window=8, **self.KV),
                 layout.kv_layer("full", **self.KV),
                 layout.latent_layer(rank=16, rope_dim=8))
        c = jax.jit(lambda: kv_cache.allocate(specs, slots=4, positions=40))()
        assert {k: v.shape for k, v in c["layers"][2].items()} == {
            "ckv": (4, 40, 16), "krope": (4, 40, 8)}
        assert c["layers"][2]["ckv"].dtype == jnp.bfloat16
        assert kv_cache.nbytes_by_kind(c, specs) == {
            "ring": 2 * 4 * 2 * 8 * 16 * 2, "full": 2 * 4 * 2 * 40 * 16 * 2,
            "latent": 4 * 40 * (16 + 8) * 2}
        sig = [a.shape for a in kv_cache.signature_leaves(c, specs)]
        assert sig == [(4, 2, 8, 16), (4, 2, 40, 16), (4, 40, 16)]
        other = kv_cache.allocate(specs, slots=2, positions=40)
        assert [a.shape for a in kv_cache.signature_leaves(other, specs)] != sig

    def test_the_published_latent_cache(self):
        """16 slots x 16,512 positions x 6 layers of 512 + 64 numbers: 6,912
        B a position, 1.83 GB; per-head keys and values would be 32 GB."""
        spec = layout.latent_layer(rank=512, rope_dim=64)
        one = sum(int(np.prod(s)) * 2 for s in spec.shapes(16, 16512).values())
        assert 6 * one == 16 * 16512 * 6912 == 1_826_095_104
        assert spec.window is None and spec.kind == "latent"


@pytest.fixture(scope="module")
def service():
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    # float32 weights: tight enough against the reference to see a wrong
    # index (the chip serves bfloat16; build_model_service's default)
    cfg = em.ExaoneMoeConfig.from_dict(lm_config())
    params = em.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(lm_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


class TestGenerateService:
    def test_warmup_compiles_every_bucket_and_menu_size(self, service):
        svc, report, _ = service
        # one launch size and the plain timer (build_model_service says why)
        assert svc.sched is None
        # per bucket: the decode step at 4 slots and a prefill slice of 2
        assert report["compiles"] == 2 * 2 == svc.engine.compile_count
        assert report["sizes"] == 1 and report["shapes"] == 2

    def test_generated_ids_are_the_models_greedy_ids(self, service):
        svc, _, _ = service
        before = svc.engine.compile_count
        prompts = [_prompt(n, n) for n in (9, 30, 16, 17, 5)]
        tickets = [svc.submit(p) for p in prompts]
        results = [t.result(120) for t in tickets]
        assert svc.engine.compile_count == before      # nothing new compiled
        params, cfg = svc.engine.params, svc.engine.programs.cfg
        spec = ref.spec_from_config(lm_config())
        for p, r in zip(prompts, results):
            assert r.tokens.shape == (NEW,) and r.tokens.dtype == np.int32
            assert r.bucket_hw == (1, 16 if len(p) <= 16 else 32)
            assert r.logits is None
            # each id is the float32 reference's argmax given the ids before
            seq = np.concatenate([p, r.tokens])
            logits = np.asarray(ref.forward(params, seq, spec)["logits"])
            want = logits[len(p) - 1:len(p) - 1 + NEW]
            top = np.sort(want, axis=-1)
            margin = want[np.arange(NEW), r.tokens] - top[:, -1]
            assert (margin > -1e-4).all(), margin

    def test_probe_logits_only_for_who_asked_and_match_reference(self, service):
        svc, _, _ = service
        p = _prompt(13, 99)
        other = svc.submit(_prompt(11, 98))
        r = svc.submit(p, want_logits=True).result(120)
        assert other.result(120).logits is None
        steps = lm_probe_steps(NEW)
        assert steps == (1, 3, 6)
        assert sorted(r.logits) == sorted(["prefill"] + [f"step{s}" for s in steps])
        spec = ref.spec_from_config(lm_config())
        seq = np.concatenate([p, r.tokens])
        full = ref.forward(svc.engine.params, seq, spec)
        for name, at in [("prefill", len(p) - 1)] + [
                (f"step{s}", len(p) - 1 + s) for s in steps]:
            np.testing.assert_allclose(r.logits[name], np.asarray(full["logits"][at]),
                                       atol=3e-5, rtol=3e-5)
            for layer, chosen in enumerate(full["chosen"]):
                assert (np.sort(r.routing[name][layer])
                        == np.sort(np.asarray(chosen[at]))).all()

    def test_fewer_new_tokens_than_the_cap(self, service):
        svc, _, _ = service
        p = _prompt(10, 7)
        short = svc.submit(p, max_new_tokens=2).result(120)
        whole = svc.submit(p).result(120)
        assert short.tokens.tolist() == whole.tokens[:2].tolist()

    def test_refused_at_the_door(self, service):
        svc, _, _ = service
        with pytest.raises(ValueError, match="largest bucket"):
            svc.submit(_prompt(33))
        with pytest.raises(ValueError, match="vocabulary slice"):
            svc.submit(np.asarray([1, 2, 256], np.int32))
        with pytest.raises(ValueError, match="max_new_tokens"):
            svc.submit(_prompt(4), max_new_tokens=NEW + 1)
        with pytest.raises(ValueError, match="1-D"):
            svc.submit(np.zeros((2, 3), np.int32))

    def test_counters_and_stats(self, service):
        svc, _, _ = service
        for seed in (1, 2):   # the second launch reuses the staging buffer
            svc.submit(_prompt(8, seed)).result(120)
        s = svc.stats()
        lm = s["lm"]
        assert lm["generated_tokens"] > 0 and lm["launches"] > 0
        # the tiny preset holds all 8 experts: every assignment lands here
        assert lm["assignments_held"] == lm["assignments_all"] > 0
        assert lm["cache_bytes"]["ring"] > 0 and lm["cache_bytes"]["full"] > 0
        assert s["staging"]["reused"] >= 1 and s["batch_valid"] >= 1
        assert s["compile_count"] == svc.engine.compile_count
        last = svc.engine.last_launch
        assert np.sum(last["decode_expert_tokens"]) == last["valid"] * last["steps"] * 2 * 4

    def test_a_model_with_one_attention_form_notes_none(self, service):
        """K-EXAONE's prefill has one form: no ``attention`` on its spans,
        no launches by form in its counters."""
        svc, _, tracer = service
        svc.submit(_prompt(6, 3)).result(120)
        assert svc.engine.programs.attention_traced is None
        assert svc.engine.prefill_attention == {}
        assert svc.stats()["lm"]["prefill_attention"] == {}
        assert all("attention" not in s for s in tracer.snapshot()
                   if s["name"] == "lm.prefill")

    def test_the_batched_decode_reads_every_held_expert(self, service):
        """K-EXAONE's decode on the CPU: the batched form, so ``experts`` on
        the ``lm.decode`` span says ``batched``, the state carries no
        counter, and read = held = 4 expert layers x 8 held x steps."""
        svc, _, tracer = service
        assert svc.engine.programs.decode_experts(4) == "batched"
        before = dict(svc.stats()["lm"])
        svc.submit(_prompt(7, 4), max_new_tokens=3).result(120)
        lm = svc.stats()["lm"]
        assert (lm["decode_experts_read"] - before["decode_experts_read"]
                == lm["decode_experts_held"] - before["decode_experts_held"]
                == 4 * 8 * 3)
        ring = tracer.snapshot()
        assert {s["experts"] for s in ring if s["name"] == "lm.decode"} == {"batched"}
        fetch = [s for s in ring if s["name"] == "serve.fetch"][-1]
        assert fetch["experts_read"] == fetch["experts_held"] == 4 * 8 * 3

    def test_spans_under_serve_batch(self, service):
        svc, _, tracer = service
        import time

        ticket = svc.submit(_prompt(12, 5))
        ticket.result(120)
        # the request resolves inside serve.complete, before its
        # serve.batch span is recorded (and an earlier test's batch may
        # still be on its way into the ring): find the batch through the
        # request's own link and wait for that id
        want = ticket._request.batch_span.span_id
        for _ in range(500):
            ring = tracer.snapshot()
            new = [s for s in ring if s["span_id"] == want]
            if new:
                break
            time.sleep(0.01)
        batch, = new
        assert batch["name"] == "serve.batch" and batch["valid"] == 1
        # the engine states one launch in flight: in line, on the
        # batcher's thread, nothing beside it
        assert batch["in_flight"] == 0
        assert batch["thread"] == "can-tpu-serve-batcher"
        assert {s["thread"] for s in ring
                if s.get("parent_id") == want} == {"can-tpu-serve-batcher"}
        cycle = next(s for s in ring if s["span_id"] == batch["parent_id"])
        assert cycle["name"] in ("serve.intake", "serve.poll")
        request = next(s for s in ring if s["name"] == "request"
                       and s["trace_id"] == ticket._request.trace_id)
        assert request["batch"] == want
        kids = {}
        for s in ring:
            if s.get("parent_id") == batch["span_id"]:
                kids.setdefault(s["name"], []).append(s)
        # the engine's phases under the names they have for every model;
        # the model's own spans nest in serve.dispatch
        assert {"serve.pad", "serve.dispatch", "serve.fetch",
                "serve.complete"} <= set(kids)
        launch = kids["serve.dispatch"][0]
        assert launch["compiled"] is False
        inner = {s["name"]: s for s in ring
                 if s.get("parent_id") == launch["span_id"]}
        assert set(inner) == {"lm.prefill", "lm.decode"}
        pre = inner["lm.prefill"]
        assert (pre["bucket"], pre["slots"], pre["valid"]) == (16, 4, 1)
        assert pre["valid_tokens"] == 12 and pre["tokens"] == 64
        assert pre["slices"] == 2 and pre["compiled"] is False
        dec = inner["lm.decode"]
        assert dec["steps"] == NEW and dec["slots"] == 4
        steps = [s for s in ring if s["name"] == "lm.decode.dispatch"
                 and s.get("parent_id") == dec["span_id"]]
        assert [s["decode_step"] for s in steps] == list(range(1, NEW + 1))

    def test_metrics_scrape_carries_the_lm_counters(self, service):
        from can_tpu.obs.exporter import render_stats

        svc, _, _ = service
        text = render_stats(svc.stats(), prefix="can_tpu_serve")
        assert "can_tpu_serve_lm_generated_tokens_total" in text
        assert 'can_tpu_serve_lm_cache_bytes{kind="ring"}' in text
        assert "can_tpu_serve_lm_assignments_held_total" in text


def test_cli_builds_the_same_service(tmp_path, capsys):
    """``can_tpu.cli.serve --model-config`` goes through
    ``build_model_service`` and warms every launch."""
    from can_tpu.cli import serve as cli

    path = tmp_path / "tiny-lm.json"
    path.write_text(json.dumps(lm_config(length_ladder=[16], max_batch=2)))
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


def test_unknown_model_type_refused():
    with pytest.raises(ValueError, match="no serving programs.*exaone_moe.*"
                                         "glm4_moe_lite"):
        build_model_service({"model_type": "resnet"})


def _imports(module) -> set:
    import ast
    import inspect

    imported = set()
    for node in ast.walk(ast.parse(inspect.getsource(module))):
        if isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module)
        elif isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
    return imported


@pytest.mark.parametrize("name", ["engine", "service", "batcher", "queue",
                                  "kinds", "cache"])
def test_serving_layers_import_no_network(name):
    """The model seam: an engine is given its programs and the service its
    model's entry of ``serve/programs.py``'s table; only that module names
    a network."""
    import importlib

    imported = _imports(importlib.import_module(f"can_tpu.serve.{name}"))
    assert not any(m.startswith("can_tpu.models") for m in imported), imported


@pytest.mark.parametrize("name", ["exaone_moe", "glm_moe_lite", "falcon_h1",
                                  "lfm2_moe", "mimo_v2_flash", "brumby",
                                  "lm_blocks"])
def test_a_model_imports_neither_the_serving_path_nor_another_model(name):
    """The other side of the seam: a model describes its cache with
    ``ops/cache_layout.py`` and shares its block with ``models/lm_blocks.py``;
    a change to one model's module cannot move another's programs."""
    import importlib

    imported = _imports(importlib.import_module(f"can_tpu.models.{name}"))
    assert not any(m.startswith("can_tpu.serve") for m in imported), imported
    others = {f"can_tpu.models.{m}" for m in ("exaone_moe", "glm_moe_lite",
                                              "falcon_h1", "lfm2_moe",
                                              "mimo_v2_flash", "brumby")}
    assert not (imported & others - {f"can_tpu.models.{name}"}), imported


def test_the_second_latent_model_takes_the_layer_from_the_first():
    """The one exception to the rule above, on purpose: LongCat-Flash's two
    sublayers ARE GLM's latent attention layer (``attention_expanded``,
    ``attention_absorbed``), used from one place; it imports no other model
    and nothing of the serving path."""
    import importlib

    imported = _imports(importlib.import_module("can_tpu.models.longcat_flash"))
    assert not any(m.startswith("can_tpu.serve") for m in imported), imported
    assert {m for m in imported if m.startswith("can_tpu.models.")} == {
        "can_tpu.models.glm_moe_lite", "can_tpu.models.lm_blocks"}


def test_the_model_table_holds_what_a_configuration_file_may_name():
    from can_tpu.serve import programs

    entry = programs.serving_model("exaone_moe")
    assert set(programs.MODEL_TYPES) == {"exaone_moe", "glm4_moe_lite",
                                         "falcon_h1", "lfm2_moe",
                                         "mimo_v2_flash", "brumby",
                                         "longcat_flash"}
    made, params = entry.programs(lm_config(), None, 3)
    assert isinstance(made, programs.LMPrograms) and made.vocab_size == 256
    assert params["embed"].shape == (256, 64)
    assert "can_tpu.models" in _imports(programs)   # the one place


# -- the scheduling core and a kind it cannot price -------------------------
def test_the_core_refuses_a_kind_it_cannot_price():
    """A token launch does not cost slots x pixels; priced as if it did,
    groups of 5 and 8 were flushed alone (ServeSched's docstring)."""
    assert ImageKind.cost_unit == "px" and TokenKind.cost_unit is None
    ServeSched(4, max_wait_s=0.1, kinds=(ImageKind(),))
    with pytest.raises(ValueError, match="cannot price request kind.*tokens"):
        ServeSched(4, max_wait_s=0.1, kinds=(TokenKind([16]),))


def test_a_service_decides_its_scheduler_from_its_kinds(service):
    """Nobody switches the core off for the token service: left to itself it
    runs one launch size under the timer; asked for a menu or a priced flush
    it refuses."""
    svc, _, _ = service
    assert svc.sched is None and svc.batcher.sched is None
    for kw in ({"menu_budget": 3}, {"flush_policy": "priced"}):
        with pytest.raises(ValueError, match="cannot price"):
            GenerateService(svc.engine, length_ladder=[16], **kw)


def test_an_image_service_keeps_the_priced_core_by_default():
    from can_tpu.serve import CountService

    class Engine:
        ds, telemetry = 8, Telemetry()

    svc = CountService(Engine(), max_batch=8)
    assert svc.sched is not None and svc.sched.priced_flush
    assert len(svc.sched.menu) == 3
    assert CountService(Engine(), max_batch=8, menu_budget=1,
                        flush_policy="timer").sched is None


def test_http_generate_and_the_other_models_route(service):
    """``POST /generate`` answers with the generated ids; ``/predict`` on a
    language-model server is refused, not crashed."""
    import threading
    import urllib.error
    import urllib.request

    from can_tpu.serve import serve_http

    svc, _, _ = service
    httpd = serve_http(svc, port=0)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        body = json.dumps({"tokens": _prompt(9, 3).tolist(),
                           "max_new_tokens": 3}).encode()
        req = urllib.request.Request(f"http://127.0.0.1:{port}/generate",
                                     data=body, method="POST")
        out = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert len(out["tokens"]) == 3 and out["bucket"] == [1, 16]
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/predict", data=b"x", method="POST"),
                timeout=30)
        assert e.value.code == 501
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps({"tokens": [1] * 40}).encode(), method="POST"),
                timeout=30)
        assert e.value.code == 400
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_arrivals_during_a_launch_join_their_group_before_the_timer():
    """A drain that straddles a full launch leaves a rest; while that
    launch blocked the thread, the rest's companions were queued.  The
    timer must not flush the rest alone (launches of 3 and 1 for a group
    of 4): ``intake`` sorts the arrivals in before ``poll`` looks, and the
    group that filled is the next cycle's launch."""
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    queue = BoundedRequestQueue(64, clock=clock)
    kind = TokenKind([16])
    launches, late = [], []

    def dispatch(bucket, batch, requests):
        launches.append(len(requests))
        clock.t += 5.0                     # the launch blocks the thread
        while late:                        # ... and meanwhile these arrive
            queue.offer(late.pop())
        for r in requests:
            r.resolve(None)

    b = MicroBatcher(queue, dispatch, max_batch=4, max_wait_ms=100.0,
                     clock=clock, kinds={TOKENS: kind},
                     batch_free_on_return=True)

    def request():
        return TokenRequest(np.arange(1, 9), max_new_tokens=1,
                            deadline_s=None, clock=clock)

    for _ in range(7):                     # one full launch and a rest of 3
        queue.offer(request())
    late.append(request())                 # the rest's fourth
    assert b.run_once(0.0) == 1 and launches == [4]
    assert b.pending_count() == 4 and b.next_wake_s() == 0.0
    assert b.run_once(0.0) == 1 and launches == [4, 4]
    assert b.flush_reasons["full"] == 2 and b.flush_reasons["due"] == 0
    # a rest nobody joins is still flushed by the timer, on its own clock
    for _ in range(5):
        queue.offer(request())
    assert b.run_once(0.0) == 2 and launches == [4, 4, 4, 1]


# -- the sorted expert form's passes, counted by the engine ------------------
@pytest.mark.parametrize("held,loop", [(8, False), (2, True)],
                         ids=["every-expert-held", "a-quarter-held"])
def test_the_engine_counts_the_prefill_s_passes_over_the_sorted_buffer(held,
                                                                       loop):
    """A prefill slice of 4 x 256 tokens takes the sorted form: each of the
    4 expert layers of each slice is one call and says how many passes over
    its buffer it took (``ops.moe.share_apply``); the launch's sum comes
    back with its answers, into ``stats()["lm"]`` and onto ``serve.fetch``.
    With every expert held the buffer is the whole: one pass a call, no
    loop.  A launch whose prefill is in another form (the module's
    ``service``: slices of 32 tokens) counts 0 of 0."""
    from can_tpu.ops import moe as moe_ops

    config = lm_config(num_experts=held, prefill_slice=4, max_batch=8,
                       length_ladder=[256], max_new_tokens=2)
    cfg = em.ExaoneMoeConfig.from_dict(config)
    assert (moe_ops.sorted_rows(1024, 2, cfg.share) < 1024 * 2) == loop
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    svc = build_model_service(config, telemetry=tel, seed=5)
    svc.warmup()
    assert svc.stats()["lm"]["dispatch_calls"] == 0   # warm-up is no traffic
    svc.start()
    try:
        for t in [svc.submit(_prompt(200 + n, n)) for n in range(8)]:
            t.result(300)
        lm = svc.stats()["lm"]
    finally:
        svc.close()
    calls = 4 * 2 * lm["launches"]         # expert layers x slices of 4 of 8
    assert lm["dispatch_calls"] == calls > 0
    if loop:
        assert calls <= lm["dispatch_passes"] <= 2 * calls
    else:
        assert lm["dispatch_passes"] == calls
    fetches = [s for s in tracer.snapshot() if s["name"] == "serve.fetch"
               and "dispatch_calls" in s]
    assert sum(s["dispatch_passes"] for s in fetches[-lm["launches"]:]) \
        == lm["dispatch_passes"]


def test_a_prefill_in_another_form_counts_no_passes(service):
    svc, _, tracer = service
    svc.submit(_prompt(9, 11)).result(120)
    lm = svc.stats()["lm"]
    assert lm["dispatch_calls"] == lm["dispatch_passes"] == 0
    assert not [s for s in tracer.snapshot() if "dispatch_passes" in s]


# -- a launch of 256 slots, a router with identity experts ---------------------
def _longcat_config(**kw) -> dict:
    from lm_tiny import tiny_longcat_config

    d = tiny_longcat_config()
    d.update(max_new_tokens=3, prefill_slice=32, length_ladder=[16],
             max_batch=256, queue_capacity=1024, max_wait_ms=20.0)
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def wide_service():
    """The tiny LongCat-Flash preset behind the cell's own launch: 256 slots
    a launch in slices of 32, a queue of 1,024."""
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    svc = build_model_service(_longcat_config(), telemetry=tel, seed=11)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


def test_a_launch_of_256_slots_fills_runs_and_answers_everyone(wide_service):
    """600 requests at once (the queue takes 1,024): two full launches of 256
    and one of 88, eight prefill slices a full launch; every request is
    answered with its own tokens (the same prompt twice: the same ids,
    whatever slot and launch it sat in)."""
    svc, report, _ = wide_service
    assert report["compiles"] == 2 == svc.engine.compile_count
    prompts = [_prompt(4 + i % 12, i % 40) for i in range(600)]
    tickets = [svc.submit(p) for p in prompts]
    results = [t.result(600) for t in tickets]
    assert all(r.tokens.shape == (3,) for r in results)
    for i in (0, 7, 39):
        for j in range(i + 40, 600, 40):
            if len(prompts[i]) == len(prompts[j]):
                np.testing.assert_array_equal(results[i].tokens,
                                              results[j].tokens)
    stats = svc.stats()
    assert stats["completed"] == 600 and stats["rejected"] == 0
    assert svc.engine.compile_count == 2       # nothing compiled in traffic
    assert max(r.batch_fill for r in results) == 1.0     # a launch of 256 of 256
    assert stats["lm"]["launches"] >= 3


def test_the_engine_counts_the_choices_that_were_identity_experts(wide_service):
    """``assignments_zero`` beside ``assignments_held`` / ``_all``: every
    choice of a valid token (prefill and decode, 2 layers x top-3) is held
    here, held elsewhere or an identity expert's; a third of the router's 12
    outputs are identity experts; the counter is on ``/metrics``."""
    from can_tpu.obs.exporter import render_stats

    svc, _, tracer = wide_service
    before = svc.stats()["lm"]
    for t in [svc.submit(_prompt(9, 100 + n)) for n in range(40)]:
        t.result(600)
    lm = svc.stats()["lm"]
    tokens = (lm["prompt_tokens"] - before["prompt_tokens"]
              + lm["generated_tokens"] - before["generated_tokens"])
    every = lm["assignments_all"] - before["assignments_all"]
    zero = lm["assignments_zero"] - before["assignments_zero"]
    held = lm["assignments_held"] - before["assignments_held"]
    assert every == tokens * 2 * 3 and tokens == 40 * (9 + 3)
    assert 0 < zero < every and 0 < held and held + zero < every
    assert 0.15 < zero / every < 0.55          # 4 of the router's 12 outputs
    # 256 slots x (16 + 3) positions x 2 layers x 2 sublayers x 24 numbers x 2 B
    assert lm["cache_bytes"] == {"latent": 256 * 19 * 2 * 2 * 24 * 2}
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert f"can_tpu_serve_lm_assignments_zero_total {lm['assignments_zero']}" in text
    decode = [s for s in tracer.snapshot() if s["name"] == "lm.decode"][-1]
    assert decode["experts"] == "batched" and decode["latent"] == "plain"


def test_a_model_without_identity_experts_counts_none(service):
    svc, _, _ = service
    svc.submit(_prompt(9, 5)).result(120)
    lm = svc.stats()["lm"]
    assert lm["assignments_zero"] == 0 < lm["assignments_all"]
