"""``models/brumby.py`` (power retention in every layer: a matrix state, not
keys and values, is the whole cache) against the plain reference
(``benchmark/reference/brumby_ref.py``: the quadratic form over all pairs,
no state, no cache) on seeded weights, and through the ONE serving path
(queue, ``MicroBatcher``, ``LMEngine``, ``GenerateService``).  Tiny preset,
groups of 3 query heads to a key head, float32, CPU."""

import inspect
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import brumby_ref as ref
from can_tpu.models import brumby as bm
from can_tpu.obs import Telemetry, spans
from can_tpu.ops import retention as ret_ops
from can_tpu.serve import GenerateService, build_model_service, lm_probe_steps
from can_tpu.serve import cache as kv_cache
from can_tpu.serve import programs as serve_programs

from lm_tiny import (interpret_fused_retention, tiny_brumby_config,
                     tiny_brumby_model)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_FILE = os.path.join(REPO, "benchmark", "configs",
                         "brumby-14b-pp5-serve-bf16.json")
TOL = dict(atol=5e-5, rtol=5e-5)
NEW = 12


@pytest.fixture(scope="module")
def tiny():
    d, cfg, params = tiny_brumby_model(seed=5)
    return d, cfg, params, ref.spec_from_config(d)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _padded(prompts, bucket):
    toks = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) for p in prompts], jnp.int32)


def test_the_tiny_preset_has_every_mechanism(tiny):
    d, cfg, params, _ = tiny
    assert cfg.groups == 3 and cfg.num_key_value_heads == 2
    layer = params["layers"][0]["ret"]
    assert layer["wg"].shape == (64, 2) and layer["q_norm"].shape == (8,)
    assert "head" in params                      # untied
    (spec,) = set(bm.cache_layout(cfg))
    assert spec.kind == "state" and spec.shapes(4, 99) == {
        "S": (4, 2, 8, 40), "z": (4, 2, 40)}     # no positions; rows in the lanes
    assert dict(spec.dtypes) == {"S": "float32", "z": "float32"}
    # the seeded gates lie near one: a state that forgets in two positions
    # would hide every fault of the state from the comparison
    x = params["embed"][_tokens(64, 1)]
    u = bm.rms_norm(x, params["layers"][0]["ln_in"], cfg.rms_norm_eps)
    g = np.asarray(jax.nn.sigmoid(u @ layer["wg"]))
    assert 0.8 < g.min() and np.median(g) > 0.9 and g.max() < 1


@pytest.mark.parametrize("chunk", [1024, 8])
def test_prefill_hidden_matches_the_reference_s_full_forward(tiny, chunk,
                                                             monkeypatch):
    """Whole prompts of different lengths right-padded into one bucket: every
    valid position's hidden state is the reference's over the prompt alone,
    with the bucket one chunk (the quadratic form) and with four (the
    carried state)."""
    _, cfg, params, spec = tiny
    monkeypatch.setattr(ret_ops, "CHUNK", chunk)
    prompts = [_tokens(29, 1), _tokens(17, 2), _tokens(32, 3)]
    toks, lengths = _padded(prompts, 32)
    hidden, _, _ = bm.prefill_hidden(params, toks, lengths, cfg)
    for i, p in enumerate(prompts):
        want = ref.forward(params, p, spec)["hidden"]
        np.testing.assert_allclose(np.asarray(hidden)[i, :len(p)],
                                   np.asarray(want), **TOL)


def test_two_padded_prompts_hand_decode_what_each_would_have_alone(tiny):
    """The state written to the cache is the one AT EACH PROMPT'S OWN
    LENGTH."""
    _, cfg, params, _ = tiny
    prompts = [_tokens(13, 4), _tokens(22, 5)]
    toks, lengths = _padded(prompts, 24)
    _, cache, _ = bm.prefill(params, toks, lengths, cfg, 40)
    for i, p in enumerate(prompts):
        _, alone, _ = bm.prefill(params, jnp.asarray(p)[None],
                                 jnp.asarray([len(p)]), cfg, 40)
        for layer, mine in zip(alone["layers"], cache["layers"]):
            assert sorted(mine) == ["S", "z"]
            for leaf in ("S", "z"):
                np.testing.assert_allclose(np.asarray(mine[leaf])[i],
                                           np.asarray(layer[leaf])[0], **TOL)
    assert cache["layers"][0]["S"].dtype == jnp.float32


@pytest.mark.parametrize("chunk", [1024, 8])
def test_prefill_then_12_decode_steps_match_the_reference(tiny, chunk,
                                                          monkeypatch):
    """Prefill, then 12 greedy steps through the launch's cache (the state
    alone), against the reference's ONE full forward over prompt + generated
    tokens: logits at every position."""
    _, cfg, params, spec = tiny
    monkeypatch.setattr(ret_ops, "CHUNK", chunk)
    prompts = [_tokens(21, 6), _tokens(9, 7), _tokens(32, 8)]
    toks, lengths = _padded(prompts, 32)
    specs = bm.cache_layout(cfg)
    cache = kv_cache.allocate(specs, slots=3, positions=32 + 12,
                              dtype=jnp.float32)
    logits, part, routing = bm.prefill(params, toks, lengths, cfg, 32 + 12)
    assert routing["counts"].shape == (0, 0)
    assert routing["choices"].shape == (0, 3, 0)
    cache = jax.tree.map(lambda c, p: c.at[:].set(p), cache, part)
    got, seqs = [np.asarray(logits)], [list(p) for p in prompts]
    tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), lengths
    step = jax.jit(bm.decode_step, static_argnums=(4,))
    for _ in range(12):
        for s, t in zip(seqs, np.asarray(tok)):
            s.append(int(t))
        logits, cache, _ = step(params, cache, tok, pos, cfg)
        got.append(np.asarray(logits))
        tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), pos + 1
    got = np.stack(got, 1)                                   # (B, 13, V)
    for i, p in enumerate(prompts):
        want = np.asarray(ref.forward(params, np.asarray(seqs[i], np.int32),
                                      spec)["logits"])[len(p) - 1:]
        np.testing.assert_allclose(got[i], want, **TOL)
    assert np.abs(want).max() > 0.5


def test_an_inactive_slot_keeps_its_state(tiny):
    _, cfg, params, _ = tiny
    toks, lengths = _padded([_tokens(8, 9), _tokens(8, 10)], 8)
    _, cache, _ = bm.prefill(params, toks, lengths, cfg, 16)
    _, moved, _ = bm.decode_step(params, cache, jnp.asarray([3, 4], jnp.int32),
                                 lengths, cfg,
                                 active=jnp.asarray([True, False]))
    for before, after in zip(cache["layers"], moved["layers"]):
        for leaf in ("S", "z"):
            assert (np.asarray(after[leaf])[1] == np.asarray(before[leaf])[1]).all()
            assert (np.asarray(after[leaf])[0] != np.asarray(before[leaf])[0]).any()


def test_heads_of_128_decode_in_the_fused_kernel(monkeypatch):
    """The published head width at a toy depth: prefill, then 4 greedy steps
    with the step as ONE kernel a layer (``ops/pallas_retention.py``,
    interpreted here; what a TPU backend turns on), one slot inactive, against
    the reference's full forward; and the same steps in the plain form."""
    d, cfg, params = tiny_brumby_model(seed=6, head_dim=128,
                                       num_hidden_layers=2)
    spec = ref.spec_from_config(d)
    prompts = [_tokens(11, 1), _tokens(5, 2), _tokens(16, 3)]
    toks, lengths = _padded(prompts, 16)
    logits, cache, _ = bm.prefill(params, toks, lengths, cfg, 16 + 4)
    assert cache["layers"][0]["S"].shape == (3, 2, 128, 8320)
    active = jnp.asarray([True, False, True])
    first = jnp.argmax(logits, -1).astype(jnp.int32)

    def steps(form):
        # (a function of its own: a second ``jit`` of ``bm.decode_step``
        # itself would find the first one's trace)
        step = jax.jit(lambda c, tok, pos: bm.decode_step(
            params, c, tok, pos, cfg, active))
        got, c, tok, pos = [], cache, first, lengths
        for _ in range(4):
            out, c, _ = step(c, tok, pos)
            got.append(np.asarray(out))
            tok, pos = jnp.argmax(out, -1).astype(jnp.int32), pos + 1
        assert bm.retention_traced((3, 1)) == form
        return np.stack(got, 1), c

    plain, plain_cache = steps("step")
    interpret_fused_retention(monkeypatch)
    fused, fused_cache = steps("fused")
    for i in (0, 2):
        seq = list(prompts[i]) + [int(first[i])] + [
            int(t) for t in fused[i, :3].argmax(-1)]
        want = np.asarray(ref.forward(params, np.asarray(seq, np.int32),
                                      spec)["logits"])[len(prompts[i]):]
        np.testing.assert_allclose(fused[i], want, **TOL)
        np.testing.assert_allclose(fused[i], plain[i], **TOL)
    for kept, a, b in zip(cache["layers"], fused_cache["layers"],
                          plain_cache["layers"]):
        for leaf in ("S", "z"):
            assert np.array_equal(np.asarray(a[leaf])[1], np.asarray(kept[leaf])[1])
            np.testing.assert_allclose(np.asarray(a[leaf]), np.asarray(b[leaf]),
                                       rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("variant,moves", [
    ("no_gate", True), ("no_normaliser", True), ("no_head_norm", True),
    ("no_rope", True), ("no_scale", False)])
def test_a_broken_piece_of_the_mathematics_moves_the_reference(tiny, variant,
                                                               moves):
    """Each control of the calibration is a different computation, but the
    scale left out of the power: the normaliser divides it out again."""
    _, _, params, spec = tiny
    p = _tokens(24, 11)
    sound = np.asarray(ref.forward(params, p, spec)["logits"])
    broken = np.asarray(ref.forward(params, p, spec, "f32", variant)["logits"])
    assert (np.abs(broken - sound).max() > 0.05) == moves
    if not moves:
        np.testing.assert_allclose(broken, sound, atol=1e-4)


def test_the_yardstick_rounds_and_stays_near(tiny):
    _, _, params, spec = tiny
    p = _tokens(24, 12)
    sound = np.asarray(ref.forward(params, p, spec)["logits"])
    bf16 = np.asarray(ref.forward(params, p, spec, "bf16")["logits"])
    assert 1e-3 < np.abs(bf16 - sound).max() < 0.3
    int8 = np.asarray(ref.forward(params, p, spec, "int8")["logits"])
    assert np.abs(int8 - sound).max() > np.abs(bf16 - sound).max()
    code = [l for l in inspect.getsource(ref).splitlines()
            if not l.strip().startswith("#")]
    assert not any(".astype(" in l for l in code)
    assert "reduce_precision" in inspect.getsource(ref._round)
    assert "can_tpu" not in "".join(
        l for l in inspect.getsource(ref).splitlines()
        if l.startswith(("import", "from")))
    # the quadratic form alone: nothing here has a state, a chunk or a phi
    assert not any(w in "".join(code) for w in ("phi(", "scan(", "chunk"))


def test_rows_returns_the_rows_asked_for(tiny):
    _, _, params, spec = tiny
    p = _tokens(20, 13)
    full = np.asarray(ref.forward(params, p, spec)["logits"])
    some = np.asarray(ref.forward(params, p, spec, rows=np.arange(15, 20))["logits"])
    np.testing.assert_allclose(some, full[15:], atol=1e-6)


# -- the configuration --------------------------------------------------
PUBLISHED = dict(hidden_size=5120, num_attention_heads=40,
                 num_key_value_heads=8, head_dim=128, intermediate_size=17408,
                 vocab_size=151936)


def test_a_layer_at_the_published_widths_counts_the_issue_s_parameters():
    """Shapes only: nothing of this size is made."""
    one, none = (bm.BrumbyConfig.from_dict(tiny_brumby_config(
        **PUBLISHED, num_hidden_layers=n)) for n in (1, 0))
    assert bm.param_count(one) - bm.param_count(none) == 330_352_896
    ret = bm.param_shapes(one)["layers"][0]["ret"]
    assert [ret[k] for k in ("wq", "wk", "wv", "wg", "wo")] == [
        (5120, 5120), (5120, 1024), (5120, 1024), (5120, 8), (5120, 5120)]
    cell = bm.BrumbyConfig.from_dict(tiny_brumby_config(**PUBLISHED,
                                                        num_hidden_layers=8))
    assert bm.param_count(cell) == 4_198_652_928


def test_the_cell_s_cache_is_state_alone_at_the_issue_s_size():
    cfg = bm.BrumbyConfig.from_dict(tiny_brumby_config(**PUBLISHED,
                                                       num_hidden_layers=8))
    made = jax.eval_shape(lambda: kv_cache.allocate(
        bm.cache_layout(cfg), slots=16, positions=1536))
    rows = ret_ops.state_rows(128)
    assert rows == 8320        # 65 whole rows of lanes; 8,256 distinct monomials
    per_slot = 8 * (8 * 128 * rows * 4 + 8 * rows * 4)
    assert per_slot == 274_759_680
    # the rows in the lanes (the layout the step's kernel streams): the bytes
    # a slot keeps are the same
    assert made["layers"][0]["S"].shape == (16, 8, 128, rows)
    assert kv_cache.nbytes_by_kind(made, bm.cache_layout(cfg)) == {
        "state": 16 * per_slot}
    # the same whatever the context: the context bounds nothing
    assert jax.eval_shape(lambda: kv_cache.allocate(
        bm.cache_layout(cfg), slots=16, positions=32768)) == made


@pytest.mark.parametrize("key,value,match", [
    ("sliding_window", 4096, "sliding_window"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("num_key_value_heads", 4, "heads do not divide"),
])
def test_what_is_not_implemented_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        bm.BrumbyConfig.from_dict(tiny_brumby_config(**{key: value}))


@pytest.mark.parametrize("name,other", [
    ("power_degree", 4), ("gate", "log_sigmoid_with_bias"),
    ("qk_norm", "none"), ("rope_pairing", "interleaved"),
    ("score_scale", "outside_power"), ("normaliser", "none"),
    ("state_dtype", "bfloat16"), ("state_rows", "outer_product")])
def test_an_assumed_value_other_than_the_one_is_refused(name, other):
    """The state's float32 among them: a bfloat16 state is another result,
    not a configuration of this one."""
    assert sorted(bm.ASSUMED) == sorted(
        ["power_degree", "gate", "qk_norm", "rope_pairing", "score_scale",
         "normaliser", "state_dtype", "state_rows"])
    assert bm.BrumbyConfig.from_dict(tiny_brumby_config(
        assumed={name: bm.ASSUMED[name]})).num_hidden_layers == 3
    with pytest.raises(ValueError, match=f"{name} .* is not implemented"):
        bm.BrumbyConfig.from_dict(tiny_brumby_config(assumed={name: other}))


def test_the_cell_s_file_states_its_cut_and_its_assumptions():
    with open(CELL_FILE) as f:
        d = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == d["name"])
    assert d["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert d["source"] == entry["source"]
    assert d["published"] == {"num_hidden_layers": 40, "vocab_size": 151936}
    assert {k: d["assumed"][k] for k in bm.ASSUMED} == bm.ASSUMED
    assert d["deployment"]["pipeline_stages"] == 5 and d["deployment"]["stage"] == 0
    assert {k: d[k] for k in PUBLISHED} == PUBLISHED
    assert d["num_hidden_layers"] == 8 and d["driver"] == "hybrid_serve"
    cfg = bm.BrumbyConfig.from_dict(d)
    assert bm.param_count(cfg) == 4_198_652_928
    assert d["assumed"]["state_bytes_per_slot"] == 274_759_680
    assert set(d["limits_why"]) >= set(d["limits"])


# -- through the serving path -----------------------------------------------
def brumby_config(**kw) -> dict:
    d = tiny_brumby_config()
    d.update(max_new_tokens=NEW, prefill_slice=2, length_ladder=[16, 32],
             max_batch=4, queue_capacity=16, max_wait_ms=5.0)
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def service():
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = bm.BrumbyConfig.from_dict(brumby_config())
    params = bm.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(brumby_config(), params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


def test_the_table_builds_the_same_programs_class():
    entry = serve_programs.serving_model("brumby")
    made, params = entry.programs(brumby_config(), None, 3)
    assert isinstance(made, serve_programs.LMPrograms)
    assert made.vocab_size == 256 and made.attention_traced is None
    assert made.ssm_traced is None and made.conv_traced is None
    assert made.retention_traced is bm.retention_traced
    assert [spec.kind for spec in made.cache_layout] == ["state"] * 3
    assert params["embed"].dtype == jnp.bfloat16 == made.dtype
    assert made.decode_experts(4) is None and not hasattr(made.cfg, "share")


def test_the_queue_the_batcher_and_the_engine_answer_as_the_reference(service):
    """Prompts of different lengths in one launch, two prefill slices: each
    request's 12 greedy ids and its probed logits are the reference's over
    ITS OWN sequence."""
    svc, report, _ = service
    assert isinstance(svc, GenerateService)
    # a prefill a bucket and ONE decode program: nothing in this model's
    # cache has the context in its shape
    assert report["compiles"] == 2 + 1 == svc.engine.compile_count
    prompts = [_tokens(27, 99), _tokens(18, 98), _tokens(32, 97)]
    tickets = [svc.submit(p, want_logits=(i != 1)) for i, p in enumerate(prompts)]
    results = [t.result(120) for t in tickets]
    assert svc.engine.compile_count == 3 and results[1].logits is None
    spec = ref.spec_from_config(brumby_config())
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
        for name, at in [("prefill", len(p) - 1)] + [
                (f"step{s}", len(p) - 1 + s) for s in steps]:
            np.testing.assert_allclose(r.logits[name], full[at], **TOL)
            assert r.routing[name].shape == (0, 0)


def test_the_cache_s_bytes_hold_state_alone(service):
    from can_tpu.obs.exporter import render_stats

    svc, _, _ = service
    svc.submit(_tokens(8, 1)).result(120)
    lm = svc.stats()["lm"]
    # 4 slots x 3 layers x 2 key heads x 40 rows x (8 + 1) float32
    assert lm["cache_bytes"] == {"state": 4 * 3 * 2 * 40 * 9 * 4}
    assert lm["assignments_all"] == 0 and lm["generated_tokens"] > 0
    text = render_stats(svc.stats(), prefix="can_tpu_serve")
    assert 'can_tpu_serve_lm_cache_bytes{kind="state"}' in text
    assert 'kind="full"' not in text


@pytest.fixture
def kernel_service(monkeypatch):
    """A service of its own at the published head width (one layer), the
    step's kernel interpreted: what a TPU backend turns on."""
    interpret_fused_retention(monkeypatch)
    config = brumby_config(head_dim=128, num_hidden_layers=1, max_batch=2,
                           published={"num_hidden_layers": 1,
                                      "vocab_size": 256})
    tracer = spans.SpanTracer()
    tel = Telemetry()
    tel.spans = tracer
    cfg = bm.BrumbyConfig.from_dict(config)
    params = bm.init_params(jax.random.key(3), cfg, jnp.float32)
    svc = build_model_service(config, params=params, telemetry=tel)
    report = svc.warmup()
    svc.start()
    yield svc, report, tracer
    svc.close()


@pytest.mark.parametrize("which,slots,form", [("service", 4, "step"),
                                              ("kernel_service", 2, "fused")])
def test_the_spans_say_which_form_the_retention_ran_in(request, which, slots,
                                                       form):
    svc, _, tracer = request.getfixturevalue(which)
    ticket = svc.submit(_tokens(9, 7))
    ticket.result(120)
    want = ticket._request.batch_span.span_id
    for _ in range(500):
        ring = tracer.snapshot()
        if any(s["span_id"] == want for s in ring):
            break
        time.sleep(0.01)
    launch = next(s for s in ring if s["name"] == "serve.dispatch"
                  and s.get("parent_id") == want)
    inner = {s["name"]: s for s in ring if s.get("parent_id") == launch["span_id"]}
    assert inner["lm.prefill"]["retention"] == "chunked"
    assert inner["lm.decode"]["retention"] == form
    assert "ssm" not in inner["lm.prefill"] and "conv" not in inner["lm.decode"]
    assert svc.engine.retention_forms[(2, 16)] == "chunked"
    assert svc.engine.retention_forms[(slots, 1)] == form
    # the programs' maps were recorded with both counts of whole copies
    scopes = [s for s in ring if s["name"] == "program.scopes"]
    assert scopes and all(s["cache_copies"] == 0 and s["state_copies"] >= 0
                          for s in scopes)
    assert {p for s in scopes for p in s["parts"].values()} >= {
        "ret.proj", "ret.state", "ret.out"}


def test_cli_builds_the_same_service(tmp_path, capsys):
    """``can_tpu.cli.serve --model-config`` takes a sixth language model."""
    from can_tpu.cli import serve as cli

    path = tmp_path / "tiny-brumby.json"
    path.write_text(json.dumps(brumby_config(length_ladder=[16], max_batch=2)))
    args = cli.parse_args(["--model-config", str(path), "--seed", "4"])
    svc = cli.build_service(args)
    try:
        assert isinstance(svc, GenerateService)
        assert "[serve] warmup:" in capsys.readouterr().out
        with svc:
            r = svc.generate(_tokens(7), timeout=120)
        assert r.tokens.shape == (NEW,)
    finally:
        svc.close()


def test_the_benchmark_s_weights_are_the_program_s_tree():
    """``weights_brumby.shapes`` (written from the configuration file's keys,
    nothing of the program imported) against ``param_shapes``."""
    from benchmark.harness import weights_brumby

    d = tiny_brumby_config()
    cfg = bm.BrumbyConfig.from_dict(d)
    assert weights_brumby.shapes(d) == bm.param_shapes(cfg)
    params = weights_brumby.make_params(d, 4000000123)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda s: s, bm.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    again = weights_brumby.make_params(d, 4000000123)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(params),
                                                    jax.tree.leaves(again)))
