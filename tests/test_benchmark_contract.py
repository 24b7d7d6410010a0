"""What ``benchmark/`` takes from the package, checked on the CPU.

The benchmark (``BENCHMARK.json``, ``python3 -m benchmark.run``) is run on the
chip by the driver after a PR is handed in, and most PRs may not edit it.  It
imports names from ``can_tpu``, passes fixed keywords to constructors and
functions, and calls methods on the objects it is handed.  A refactor that
renames one of those is otherwise found on the chip, as a refused PR.

Everything here is read from the benchmark's own source with ``ast`` at
import time (``benchmark/tests/`` left out: those are its tests, not what the
driver runs) and resolved against the package with ``importlib`` /
``inspect``: one case per imported name, per keyword of a call to an imported
name, per attribute used on a service or an engine, per key read from
``stats()``.  Nothing of the benchmark is imported or run.
"""

import ast
import importlib
import inspect
import os
import re
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SERVE_DRIVER = "harness/drive_serve.py"
LM_DRIVER = "harness/drive_lm_serve.py"
GLM_DRIVER = "harness/drive_glm_serve.py"   # EngineProbe is LM_DRIVER's
HYBRID_DRIVER = "harness/drive_hybrid_serve.py"   # a model without experts


def _sources():
    """{path relative to benchmark/: tree} of every file the driver can run."""
    out = {}
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__" and not (
            root == BENCH and d == "tests"))
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    out[os.path.relpath(path, BENCH)] = ast.parse(fh.read(),
                                                                  path)
    return out


SOURCES = _sources()


def _package_imports(tree):
    """{local name: (module, name)} of a file's ``from can_tpu... import``."""
    return {a.asname or a.name: (n.module, a.name)
            for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
            and n.module and n.module.split(".")[0] == "can_tpu"
            for a in n.names}


def _imported():
    return sorted({pair for tree in SOURCES.values()
                   for pair in _package_imports(tree).values()})


def _call_keywords():
    """(module, name, keyword) of every keyword a benchmark file passes to a
    name it imported from the package."""
    out = set()
    for tree in SOURCES.values():
        names = _package_imports(tree)
        for n in ast.walk(tree):
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id in names):
                out.update((*names[n.func.id], k.arg)
                           for k in n.keywords if k.arg)
    return sorted(out)


def _uses(rel, holder):
    """Attribute names a driver reads or calls on ``holder`` (a dotted
    expression such as ``service`` or ``self._engine``)."""
    return {n.attr for n in ast.walk(SOURCES[rel])
            if isinstance(n, ast.Attribute) and ast.unparse(n.value) == holder}


def _probe_own(rel):
    """What a driver's ``EngineProbe`` defines itself (``engine.launches``
    and the like are the probe's, not the engine's)."""
    cls = next((n for n in ast.walk(SOURCES[rel])
                if isinstance(n, ast.ClassDef) and n.name == "EngineProbe"),
               None)
    if cls is None:          # imported from the driver that defines it
        return _probe_own(LM_DRIVER)
    own = {m.name for m in cls.body if isinstance(m, ast.FunctionDef)}
    own |= {c.args[1].value for c in ast.walk(cls)
            if isinstance(c, ast.Call)
            and ast.unparse(c.func) == "object.__setattr__"
            and isinstance(c.args[1], ast.Constant)}
    return own


def _method_keywords(rel, holder):
    return {(n.func.attr, k.arg) for n in ast.walk(SOURCES[rel])
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and ast.unparse(n.func.value) == holder
            for k in n.keywords if k.arg}


def _stats_keys(rel):
    return {n.slice.value for n in ast.walk(SOURCES[rel])
            if isinstance(n, ast.Subscript) and isinstance(n.value, ast.Name)
            and n.value.id in ("stats0", "stats1")
            and isinstance(n.slice, ast.Constant)}


# which classes a driver's ``service`` and ``engine`` are: the CANNet driver
# names its own; the language-model driver is handed what
# ``build_model_service`` builds for ``exaone_moe``
ROLES = {SERVE_DRIVER: ("CountService", "ServeEngine"),
         LM_DRIVER: ("GenerateService", "LMEngine"),
         GLM_DRIVER: ("GenerateService", "LMEngine"),
         HYBRID_DRIVER: ("GenerateService", "LMEngine")}
# read THROUGH the probe by the service (``EngineProbe.__getattr__`` forwards
# to the engine), so no benchmark file spells them
FORWARDED = ("last_batch_compiled", "launches_in_flight")


def _attribute_cases():
    out = set()
    for rel, (service, engine) in ROLES.items():
        out |= {(service, a) for a in _uses(rel, "service")}
        on_engine = _uses(rel, "self._engine") | (
            _uses(rel, "engine") - _probe_own(rel))
        out |= {(engine, a) for a in on_engine | set(FORWARDED)}
    return sorted(out)


def _method_keyword_cases():
    # ``self._service``: the door ``drive_glm_serve`` puts before the service
    return sorted({(ROLES[rel][0], m, k) for rel in ROLES
                   for holder in ("service", "self._service")
                   for m, k in _method_keywords(rel, holder)})


def _stats_cases():
    return sorted({(ROLES[rel][0], k) for rel in ROLES
                   for k in _stats_keys(rel)})


def _resolve(module, name):
    mod = importlib.import_module(module)
    if hasattr(mod, name):
        return getattr(mod, name)
    return importlib.import_module(f"{module}.{name}")


def _serve_class(name):
    from can_tpu.serve import engine, service

    return getattr(service if name.endswith("Service") else engine, name)


def _has(cls, attr):
    """A method, property or class attribute, or an attribute an
    ``__init__`` of the class sets."""
    if hasattr(cls, attr):
        return True
    assigns = re.compile(rf"\bself\.{re.escape(attr)}\b[^=\n]*=(?!=)")
    return any(assigns.search(inspect.getsource(c))
               for c in cls.__mro__ if c is not object)


def _accepts(fn, keyword):
    return keyword in inspect.signature(fn).parameters


def test_the_collection_found_the_benchmark():
    # an empty collection would pass vacuously: the drivers are there and
    # each list holds what the drivers are known to use
    assert {SERVE_DRIVER, LM_DRIVER, GLM_DRIVER, HYBRID_DRIVER,
            "harness/drive_train.py", "run.py"} <= set(SOURCES)
    assert "warmup" in _uses(HYBRID_DRIVER, "service")
    assert "release_buffers" in _uses(HYBRID_DRIVER, "engine")
    assert {"batch_slots", "batch_valid", "lm"} <= _stats_keys(HYBRID_DRIVER)
    assert ("can_tpu.serve.programs", "MODEL_TYPES") in _imported()
    assert ("GenerateService", "submit", "want_logits") in _method_keyword_cases()
    assert ("can_tpu.serve", "RejectedError") in _imported()
    assert ("can_tpu.serve", "CountService", "max_batch") in _call_keywords()
    assert ("CountService", "warmup") in _attribute_cases()
    assert ("ServeEngine", "predict_batch") in _attribute_cases()
    assert ("CountService", "batch_slots") in _stats_cases()
    assert not any(rel.startswith("tests") for rel in SOURCES)


@pytest.mark.parametrize("module,name", _imported(),
                         ids=lambda v: v.replace("can_tpu.", ""))
def test_an_imported_name_resolves(module, name):
    assert _resolve(module, name) is not None


@pytest.mark.parametrize("module,name,keyword", _call_keywords(),
                         ids=lambda v: v.replace("can_tpu.", ""))
def test_a_call_s_keyword_is_accepted(module, name, keyword):
    assert _accepts(_resolve(module, name), keyword), (
        f"benchmark/ calls {module}.{name}({keyword}=...)")


@pytest.mark.parametrize("cls,attr", _attribute_cases())
def test_an_attribute_the_drivers_use_exists(cls, attr):
    assert _has(_serve_class(cls), attr), f"{cls}.{attr}"


def test_the_menu_the_serve_driver_prints_exists():
    from can_tpu.sched import ServeSched

    assert "menu" in _uses(SERVE_DRIVER, "service.sched")
    assert ServeSched(4, max_wait_s=0.005).menu[0] == 4


@pytest.mark.parametrize("cls,method,keyword", _method_keyword_cases())
def test_a_method_s_keyword_is_accepted(cls, method, keyword):
    assert _accepts(getattr(_serve_class(cls), method), keyword), (
        f"{cls}.{method}({keyword}=...)")


@pytest.fixture(scope="module")
def stats_of():
    """``stats()`` of each service class over a stub engine: building a
    service runs no program."""
    from can_tpu.obs import Telemetry
    from can_tpu.serve.service import CountService, GenerateService

    engine = types.SimpleNamespace(telemetry=Telemetry(), compile_count=0,
                                   ds=8, counters={})
    services = {
        "CountService": CountService(engine, max_batch=4),
        "GenerateService": GenerateService(engine, length_ladder=(16,),
                                           max_batch=4)}
    yield {name: s.stats() for name, s in services.items()}
    for s in services.values():
        s.close()


@pytest.mark.parametrize("cls,key", _stats_cases())
def test_a_stats_key_the_drivers_read_is_there(stats_of, cls, key):
    assert key in stats_of[cls]


# -- what the language-model cells take beyond names and keywords ----------
def _lm_configs():
    import json

    out = []
    for f in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", f)) as fh:
            config = json.load(fh)
        if "model_type" in config:
            out.append((f[:-len(".json")], config))
    return out


@pytest.mark.parametrize("name,config", _lm_configs(),
                         ids=[n for n, _ in _lm_configs()])
def test_a_configuration_s_model_type_is_served(name, config):
    """``drive_glm_serve`` asks ``MODEL_TYPES`` before it opens a device; a
    configuration file also names its driver's modules."""
    from can_tpu.serve.programs import MODEL_TYPES, serving_model

    assert config["model_type"] in MODEL_TYPES
    assert callable(serving_model(config["model_type"]).programs)
    for key in ("reference", "weights", "work"):
        if key in config:
            assert os.path.isfile(os.path.join(
                REPO, *config[key].split(".")) + ".py"), config[key]


@pytest.mark.parametrize("module,name", [
    ("can_tpu.ops.attention", "write_slot"),   # calibrate_lm.late_write
    ("can_tpu.ops.attention", "write_row"),    # calibrate_glm.late_write
    ("can_tpu.ops.ssm", "ssd_chunked"),   # calibrate_falcon_h1.state_after_padding
    ("can_tpu.ops.ssm", "conv_tail"),     # calibrate_falcon_h1.tail_late
])
def test_a_function_the_calibration_breaks_is_called_through_its_module(
        module, name):
    """The calibration replaces the module's attribute: the model has to
    reach the function through the module, not through a name of its own."""
    assert callable(_resolve(module, name))
    models = os.path.join(REPO, "can_tpu", "models")
    alias = {"attention": "attn_ops", "ssm": "ssm_ops"}[module.rsplit(".", 1)[1]]
    callers = [f for f in sorted(os.listdir(models)) if f.endswith(".py")
               and f"{alias}.{name}(" in open(os.path.join(models, f)).read()]
    assert callers, f"no model calls {alias}.{name}"


@pytest.mark.parametrize("span,attrs", [
    ("lm.prefill", ("tokens", "valid_tokens")),   # prefill_pad_token_pct.lm
    ("lm.prefill.dispatch", ("slice", "start_slot")),      # the idle gaps' names
    ("lm.decode.dispatch", ("decode_step",)),
])
def test_a_span_the_lm_readers_read_is_recorded(span, attrs):
    from can_tpu.serve.engine import LMEngine

    source = inspect.getsource(LMEngine.generate_batch)
    call = source[source.index(f'span("{span}"'):]
    call = call[:call.index(" as ")] if " as " in call[:400] else call[:400]
    for a in attrs:
        assert f"{a}=" in call, f"{span} lacks {a}"


def test_the_prefill_span_s_attention_is_written_where_its_reader_reads_it():
    """``prefill_fused_attention_pct.lm`` reads ``attention`` off the
    ``lm.prefill`` spans: the engine sets it inside that span, once the
    slices' programs have run (and so have been traced)."""
    from can_tpu.serve.engine import LMEngine

    source = inspect.getsource(LMEngine.generate_batch)
    inside = source[source.index('span("lm.prefill"'):
                    source.index('span("lm.decode"')]
    assert 'sp.attrs["attention"]' in inside
    with open(os.path.join(BENCH, "metrics",
                           "prefill_fused_attention_pct.lm.py")) as f:
        reader = f.read()
    assert '"lm.prefill"' in reader and 's["attention"] == "fused"' in reader


def test_the_spans_ssm_is_written_where_its_reader_reads_it():
    """``prefill_ssm_chunked_pct.lm`` reads ``ssm`` off the ``lm.prefill``
    spans; the decode span carries its form too."""
    from can_tpu.serve.engine import LMEngine

    source = inspect.getsource(LMEngine.generate_batch)
    pre = source[source.index('span("lm.prefill"'):source.index('span("lm.decode"')]
    assert 'sp.attrs["ssm"]' in pre
    assert 'sp.attrs["ssm"]' in source[source.index('span("lm.decode"'):]
    with open(os.path.join(BENCH, "metrics", "prefill_ssm_chunked_pct.lm.py")) as f:
        reader = f.read()
    assert '"lm.prefill"' in reader and 's["ssm"] == "chunked"' in reader


def test_the_fetch_span_s_expert_counters_are_written_where_their_reader_reads_them():
    """``decode_experts_read_pct.lm`` reads ``experts_read`` / ``experts_held``
    off the launches' ``serve.fetch`` spans: the engine sets both inside
    that span, where the counters arrive from the device."""
    from can_tpu.serve.engine import LMEngine

    source = inspect.getsource(LMEngine.generate_batch)
    inside = source[source.index('span("serve.fetch"'):]
    assert 'sp.attrs["experts_read"]' in inside
    assert 'sp.attrs["experts_held"]' in inside
    with open(os.path.join(BENCH, "metrics",
                           "decode_experts_read_pct.lm.py")) as f:
        reader = f.read()
    assert '"serve.fetch"' in reader
    assert 'fetch["experts_read"]' in reader and 'fetch["experts_held"]' in reader


def test_the_experts_reader_reads_a_recorded_window(monkeypatch):
    """The reader over a ring as the program records it: two launches that
    read 60 and 68 of 100 read 64; a ring whose fetch spans carry no
    counter (the parent's program) reads nothing."""
    import importlib.util

    from benchmark.harness import program_spans
    from can_tpu.obs import spans as recorder

    spec = importlib.util.spec_from_file_location(
        "decode_experts_read_pct_lm",
        os.path.join(BENCH, "metrics", "decode_experts_read_pct.lm.py"))
    reader = importlib.util.module_from_spec(spec)
    # a reader's module arms a ring-only tracer when it is loaded: put back
    # what was installed
    monkeypatch.setattr(recorder, "_installed", recorder._installed)
    spec.loader.exec_module(reader)

    def ring(counters):
        spans, sid = [], iter(range(1, 100))
        for n, attrs in enumerate(counters):
            batch = next(sid)
            spans.append({"name": "serve.batch", "span_id": batch,
                          "parent_id": 0, "trace_id": "lane", "valid": 16,
                          "start_s": float(n), "duration_s": 0.9})
            spans.append({"name": "serve.dispatch", "span_id": next(sid),
                          "parent_id": batch, "trace_id": "lane",
                          "compiled": False, "start_s": float(n),
                          "duration_s": 0.5})
            spans.append({"name": "serve.fetch", "span_id": next(sid),
                          "parent_id": batch, "trace_id": "lane",
                          "start_s": n + 0.5, "duration_s": 0.1, **attrs})
        return program_spans.Ring(spans, lambda span, kids: 0.0)

    ctx = {"counters": {"rate": {"rate": 32.0, "window_s": 1.0}}}
    for counters, want in (
            ([{"experts_read": 60, "experts_held": 100},
              {"experts_read": 68, "experts_held": 100}], 64.0),
            ([{}, {}], None)):
        monkeypatch.setattr(program_spans, "read", lambda c=counters: ring(c))
        assert reader.read(ctx) == want


def test_the_state_counter_its_reader_reads_is_kept_by_kind():
    """``state_cache_bytes_per_slot.lm`` reads ``cache_bytes["state"]``."""
    from can_tpu.ops import cache_layout as layout
    from can_tpu.serve import cache as kv_cache

    spec = layout.state_layer(ssm=((2, 3, 4), "float32"), conv=((5, 3), None))
    made = kv_cache.allocate((spec,), slots=2, positions=9)
    assert kv_cache.nbytes_by_kind(made, (spec,)) == {
        "state": 2 * (24 * 4 + 15 * 2)}
    with open(os.path.join(BENCH, "metrics",
                           "state_cache_bytes_per_slot.lm.py")) as f:
        assert '"state"' in f.read()


@pytest.mark.parametrize("key", ["cache_bytes", "generated_tokens", "launches"])
def test_a_counter_the_lm_readers_read_is_kept(key):
    """``stats()["lm"]`` is the engine's counters: ``latent_cache_bytes_per_pos.lm``
    reads ``cache_bytes`` (by kind, from ``serve/cache.py``)."""
    from can_tpu.ops import cache_layout as layout
    from can_tpu.serve import cache as kv_cache
    from can_tpu.serve.engine import LMEngine

    assert f'"{key}"' in inspect.getsource(LMEngine)
    spec = layout.latent_layer(rank=4, rope_dim=2)
    made = kv_cache.allocate((spec,), slots=1, positions=3)
    assert kv_cache.nbytes_by_kind(made, (spec,)) == {"latent": 3 * 6 * 2}
