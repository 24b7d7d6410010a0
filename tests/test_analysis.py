"""Static-analysis subsystem tests (can_tpu/analysis/).

Two layers, mirrored here:

* ``hlo_audit`` — facts extraction from StableHLO text, contract
  checking/diff rendering, the canonical program registry vs the
  committed ``PROGRAM_CONTRACTS.json``, and the seeded MUTATION pins:
  deleting a psum, upcasting an accumulator to f64, and hoisting the
  int8 dequant out of the jit must each turn the audit red with the
  violated invariant named.
* ``source_lint`` — one fixture per rule (caught AND the nearby pattern
  that must NOT be caught), pragma parsing (unknown rule / missing
  reason rejected), baseline round trip incl. STALENESS (a baselined
  finding that no longer fires is an error), and the acceptance pin:
  the real tree lints clean with zero unbaselined findings.

Plus the CLIs: ``tools/can_tpu_lint.py`` exit codes, the audit module
CLI's torn/absent-contract failure modes (failure, never a vacuous
pass), and ``tools/ci_lint.sh``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from can_tpu.analysis import hlo_audit as ha
from can_tpu.analysis import source_lint as sl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTRACT = os.path.join(REPO, "PROGRAM_CONTRACTS.json")


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra)


# =========================== hlo_audit ===================================
SYNTH_HLO = textwrap.dedent("""\
    module @jit_step {
      func.func public @main(%arg0: tensor<4xi8>, %arg1: tensor<129xf32>,
          %arg2: tensor<2x2xi8>, %arg3: tensor<8x8xf32>)
          -> (tensor<129xf32> {jax.result_info = ""}) {
        %0 = "stablehlo.all_reduce"(%arg1) <{replica_groups = dense<0>
             : tensor<1x1xi64>}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<129xf32>) -> tensor<129xf32>
        %1 = "stablehlo.all_reduce"(%arg3) <{replica_groups = dense<0>
             : tensor<1x1xi64>}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<8x8xf32>) -> tensor<8x8xf32>
        %2 = "stablehlo.collective_permute"(%arg3) <{}> : (tensor<8x8xf32>)
             -> tensor<8x8xf32>
        %3 = stablehlo.custom_call @xla_python_cpu_callback(%arg1) :
             (tensor<129xf32>) -> tensor<129xf32>
        %4 = stablehlo.convert %arg0 : (tensor<4xi8>) -> tensor<4xf64>
        return %0 : tensor<129xf32>
      }
    }
""")


class TestFactsExtraction:
    def test_synthetic_text_facts(self):
        f = ha.facts_from_text("synth", SYNTH_HLO)
        assert f.collectives["all_reduce"] == 2
        assert f.collectives["collective_permute"] == 1
        assert f.collectives["all_gather"] == 0
        assert f.all_reduce_shapes == sorted(["129xf32", "8x8xf32"])
        assert f.f64_ops == 1
        assert f.host_calls == 1
        # %arg0 (1-D) and %arg2 (2-D) are i8 params; f32 args are not
        assert f.int8_params == 2

    def test_sharding_custom_call_is_not_a_host_call(self):
        txt = ('%0 = stablehlo.custom_call @Sharding(%arg0) : '
               '(tensor<4xf32>) -> tensor<4xf32>')
        assert ha.count_host_calls(txt) == 0
        assert ha.count_host_calls(
            "stablehlo.infeed %tok : tensor<f32>") == 1

    def test_packed_bn_reduce_count(self):
        shapes = ["129xf32", "1025xf32", "129xf32", "128xf32",
                  "129xi32", "2x129xf32"]
        # only 1-D f32 of size 2C+1 for a real BN width count as packed
        assert ha.packed_bn_reduce_count(shapes, [64, 512]) == 3


def _entry(**kw):
    base = {"collectives": {"all_reduce": 2},
            "all_reduce_shapes": ["129xf32", "8x8xf32"],
            "forbid_f64": True, "forbid_host_calls": True}
    base.update(kw)
    return base


def _facts(**kw):
    base = dict(name="p", collectives={"all_reduce": 2},
                all_reduce_shapes=["129xf32", "8x8xf32"], f64_ops=0,
                host_calls=0, int8_params=0)
    base.update(kw)
    return ha.ProgramFacts(**base)


class TestCheckFacts:
    def test_clean_pass(self):
        assert ha.check_facts(_entry(), _facts()) == []

    def test_deleted_collective_named(self):
        v = ha.check_facts(_entry(), _facts(
            collectives={"all_reduce": 1},
            all_reduce_shapes=["8x8xf32"]))
        names = {x.invariant for x in v}
        assert "collectives.all_reduce" in names
        assert "all_reduce_shapes" in names
        ar = next(x for x in v if x.invariant == "collectives.all_reduce")
        assert ar.expected == 2 and ar.actual == 1
        assert "deleted" in ar.detail

    def test_packed_bn_invariant(self):
        entry = _entry(bn_channels=[64], packed_bn_reduces=1)
        assert ha.check_facts(entry, _facts()) == []
        v = ha.check_facts(entry, _facts(
            all_reduce_shapes=["128xf32", "8x8xf32"]))
        names = [x.invariant for x in v]
        assert "packed_bn_reduces" in names
        # default expectation = one per BN layer when not given explicitly
        entry2 = _entry(bn_channels=[64])
        assert not any(x.invariant == "packed_bn_reduces"
                       for x in ha.check_facts(entry2, _facts()))

    def test_f64_host_int8_invariants(self):
        v = ha.check_facts(_entry(), _facts(f64_ops=3, host_calls=1))
        assert {x.invariant for x in v} == {"forbid_f64",
                                            "forbid_host_calls"}
        v = ha.check_facts(_entry(require_int8_params=True), _facts())
        assert [x.invariant for x in v] == ["require_int8_params"]
        v = ha.check_facts(_entry(require_int8_params=True,
                                  int8_params=24),
                           _facts(int8_params=20))
        assert [x.invariant for x in v] == ["int8_params"]

    def test_cost_band_two_sided_with_noise(self):
        entry = _entry(flops=100.0, bytes_accessed=1000.0,
                       cost_noise_pct=10)
        ok = ha.check_facts(entry, _facts(flops=109.0,
                                          bytes_accessed=905.0))
        assert ok == []
        up = ha.check_facts(entry, _facts(flops=120.0,
                                          bytes_accessed=1000.0))
        assert [x.invariant for x in up] == ["cost.flops"]
        down = ha.check_facts(entry, _facts(flops=100.0,
                                            bytes_accessed=800.0))
        assert [x.invariant for x in down] == ["cost.bytes_accessed"]

    def test_fast_mode_skips_cost_never_fails_it(self):
        entry = _entry(flops=100.0, bytes_accessed=1000.0)
        # facts without cost (structure-only lowering): no violation
        assert ha.check_facts(entry, _facts()) == []

    def test_render_diff_names_program_and_update_path(self):
        v = ha.check_facts(_entry(), _facts(f64_ops=1))
        txt = ha.render_diff(v)
        assert "p: forbid_f64" in txt and "--update" in txt
        assert ha.render_diff([]) == "program-contract audit: OK"


class TestContractIO:
    def test_absent_contract_is_failure(self, tmp_path):
        with pytest.raises(ha.AuditError, match="does not exist"):
            ha.load_contract(str(tmp_path / "nope.json"))

    def test_torn_contract_is_failure(self, tmp_path):
        p = tmp_path / "torn.json"
        p.write_text('{"version": 1, "programs": {"a": {"colle')
        with pytest.raises(ha.AuditError, match="torn"):
            ha.load_contract(str(p))

    def test_wrong_version_or_empty_is_failure(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"version": 99, "programs": {"a": {}}}))
        with pytest.raises(ha.AuditError, match="expected"):
            ha.load_contract(str(p))
        p.write_text(json.dumps({"version": 1, "programs": {}}))
        with pytest.raises(ha.AuditError):
            ha.load_contract(str(p))

    def test_audit_cli_absent_contract_exits_2_fast(self, tmp_path):
        r = subprocess.run(
            [sys.executable, "-m", "can_tpu.analysis.hlo_audit",
             "--contract", str(tmp_path / "gone.json")],
            capture_output=True, text=True, cwd=REPO, env=_env())
        assert r.returncode == 2
        assert "does not exist" in r.stdout

    def test_audit_cli_refuses_self_overwrite(self):
        r = subprocess.run(
            [sys.executable, "-m", "can_tpu.analysis.hlo_audit",
             "--contract", "PROGRAM_CONTRACTS.json",
             "--update", "PROGRAM_CONTRACTS.json"],
            capture_output=True, text=True, cwd=REPO, env=_env())
        assert r.returncode == 2
        assert "refusing" in r.stdout
        # and the committed contract was not touched
        assert ha.load_contract(CONTRACT)["programs"]


class TestProgramContracts:
    """The committed artifact + the live registry."""

    def test_committed_contract_covers_canonical_programs(self):
        doc = ha.load_contract(CONTRACT)
        names = set(doc["programs"])
        assert {"train_step_default", "train_step_bf16",
                "train_step_syncbn_onepass", "train_step_syncbn_twopass",
                "eval_step_f32", "serve_predict_int8"} <= names
        assert len(names) >= 6
        for name, entry in doc["programs"].items():
            assert entry["forbid_f64"] and entry["forbid_host_calls"]
            assert entry["flops"] and entry["bytes_accessed"], (
                f"{name}: committed contract must carry cost budgets")
        assert doc["programs"]["serve_predict_int8"]["require_int8_params"]
        onepass = doc["programs"]["train_step_syncbn_onepass"]
        # one packed (2C+1,) psum per BN layer per pass (fwd + transpose)
        assert (onepass["packed_bn_reduces"]
                == 2 * len(onepass["bn_channels"]))
        assert (doc["programs"]["train_step_syncbn_twopass"]
                ["packed_bn_reduces"] == 0)
        # the PR-7 headline, now a committed structural fact
        assert (onepass["collectives"]["all_reduce"]
                < doc["programs"]["train_step_syncbn_twopass"]
                ["collectives"]["all_reduce"])

    def test_fresh_lowerings_match_committed_contract(self):
        doc = ha.load_contract(CONTRACT)
        violations = ha.audit_programs(doc)  # structure mode, all 8
        assert violations == [], ha.render_diff(violations)

    def test_eval_program_cost_band_matches_committed(self):
        """One real compile through cost_analysis: the budget path is
        exercised end-to-end, not just on synthetic facts."""
        doc = ha.load_contract(CONTRACT)
        v = ha.audit_programs(doc, ["eval_step_f32"], with_cost=True)
        assert v == [], ha.render_diff(v)
        facts = ha.program_facts("eval_step_f32", with_cost=True)
        assert facts.flops and facts.bytes_accessed

    def test_unknown_program_and_rotted_contract_entry(self):
        doc = ha.load_contract(CONTRACT)
        with pytest.raises(ha.AuditError, match="not in the contract"):
            ha.audit_programs(doc, ["no_such_program"])
        with pytest.raises(ha.AuditError, match="unknown program"):
            ha.lower_program("no_such_program")
        rotted = {"version": 1,
                  "programs": {"retired_step": dict(
                      doc["programs"]["eval_step_f32"])}}
        v = ha.audit_programs(rotted)
        invs = {x.invariant for x in v}
        assert "program_exists" in invs
        # ...and the registry programs the rotted contract dropped are
        # themselves flagged: a program family must not ship unguarded
        assert "program_contracted" in invs
        uncontracted = {x.program for x in v
                        if x.invariant == "program_contracted"}
        assert uncontracted == set(ha.PROGRAM_BUILDERS)

    def test_uncontracted_registry_program_flagged_on_full_audit(self):
        doc = ha.load_contract(CONTRACT)
        pruned = {"version": 1, "programs": dict(doc["programs"])}
        pruned["programs"].pop("eval_step_f32")
        v = ha.audit_programs(pruned)
        assert [(x.program, x.invariant) for x in v] == [
            ("eval_step_f32", "program_contracted")]
        # an explicit subset audit is exempt (it names what it checks)
        assert ha.audit_programs(pruned, ["train_step_default"]) == []

    # --- the seeded mutations: the audit must have TEETH ---------------
    def test_mutation_deleted_psum_turns_audit_red(self):
        doc = ha.load_contract(CONTRACT)
        txt = ha.lower_program("train_step_syncbn_onepass").as_text()
        mutated = txt.replace('"stablehlo.all_reduce"',
                              '"stablehlo.all_reduce_deleted"', 1)
        facts = ha.facts_from_text("train_step_syncbn_onepass", mutated)
        v = ha.check_facts(doc["programs"]["train_step_syncbn_onepass"],
                           facts)
        names = {x.invariant for x in v}
        assert "collectives.all_reduce" in names, ha.render_diff(v)
        ar = next(x for x in v
                  if x.invariant == "collectives.all_reduce")
        assert "deleted" in ar.detail

    def test_mutation_f64_accumulator_turns_audit_red(self):
        import jax

        from can_tpu.models import cannet_apply
        from can_tpu.train import make_train_step

        doc = ha.load_contract(CONTRACT)
        _, opt, state = ha._train_setup(batch_norm=False)

        def apply_f64(params, image, **kw):
            # the seeded bug: an accumulator silently upcast to f64
            import jax.numpy as jnp

            pred = cannet_apply(params, image, **kw)
            return (pred.astype(jnp.float64) * 1.0).astype(jnp.float32)

        with jax.enable_x64(True):
            low = jax.jit(make_train_step(apply_f64, opt)).lower(
                state, ha._audit_batch(1))
            facts = ha.facts_from_text("train_step_default",
                                       low.as_text())
        assert facts.f64_ops > 0
        v = ha.check_facts(doc["programs"]["train_step_default"], facts)
        assert any(x.invariant == "forbid_f64" for x in v), (
            ha.render_diff(v))

    def test_mutation_hoisted_int8_dequant_turns_audit_red(self):
        from can_tpu.serve.quant import dequantize_tree

        doc = ha.load_contract(CONTRACT)
        fn, (params, batch, stats) = ha.serve_predict_lowerable("int8")
        # the seeded bug: dequantize on host, jit sees f32 weights —
        # HBM holds 4x the bytes and the int8 mode is quietly a lie
        low = fn.lower(dequantize_tree(params, "int8"), batch, stats)
        facts = ha.facts_from_text("serve_predict_int8", low.as_text())
        v = ha.check_facts(doc["programs"]["serve_predict_int8"], facts)
        assert [x.invariant for x in v] == ["require_int8_params"]
        assert "hoisted" in v[0].detail


# =========================== source_lint =================================
def run_lint(rel, src):
    """Single-source lint with pragmas applied (the engine's own rules;
    EMITKIND needs a tree and is tested via lint_paths below)."""
    pragmas = sl.parse_pragmas(src, rel)
    findings, _ = sl.lint_source(rel, src)
    return [f for f in findings
            if f.rule not in (pragmas.get(f.line, set())
                              | pragmas.get(f.line - 1, set()))]


HOT = "can_tpu/ops/fixture.py"       # hot-path AND device scope
COLD = "can_tpu/cli/fixture.py"      # neither


class TestHostSyncRule:
    def test_each_sync_shape_caught(self):
        src = textwrap.dedent("""\
            def f(x, metrics, np):
                a = x.item()
                x.block_until_ready()
                b = np.asarray(x)
                c = float(metrics["loss"])
                return a, b, c
        """)
        rules = [f.rule for f in run_lint(HOT, src)]
        assert rules == ["HOSTSYNC"] * 4
        assert run_lint(COLD, src) == []  # scope: hot modules only

    def test_benign_float_and_jnp_asarray_not_flagged(self):
        src = textwrap.dedent("""\
            def f(ms, jnp, x):
                a = float(ms)          # bare config scalar coercion
                b = jnp.asarray(x)     # stays on device
                return a, b
        """)
        assert run_lint(HOT, src) == []


class TestTimeTimeRule:
    def test_time_time_flagged_perf_counter_not(self):
        src = ("import time\n"
               "t0 = time.time()\n"
               "t1 = time.perf_counter()\n")
        assert [f.rule for f in run_lint(HOT, src)] == ["TIMETIME"]
        assert run_lint(COLD, src) == []


class TestSwallowRule:
    def test_silent_swallow_flagged(self):
        src = textwrap.dedent("""\
            try:
                x = 1
            except Exception:
                pass
        """)
        (f,) = run_lint(COLD, src)
        assert f.rule == "SWALLOW" and f.line == 3

    def test_bare_except_flagged_narrow_not(self):
        bare = "try:\n    x = 1\nexcept:\n    x = 2\n"
        assert [f.rule for f in run_lint(COLD, bare)] == ["SWALLOW"]
        narrow = "try:\n    x = 1\nexcept ValueError:\n    x = 2\n"
        assert run_lint(COLD, narrow) == []

    def test_raise_use_or_log_is_handled(self):
        for body in ("    raise",
                     "    print('fell back')",
                     "    log.warning('x')",
                     "    tel.emit('bad')"):
            src = f"try:\n    x = 1\nexcept Exception:\n{body}\n"
            assert run_lint(COLD, src) == [], body
        uses = ("try:\n    x = 1\nexcept Exception as e:\n"
                "    x = handle(e)\n")
        assert run_lint(COLD, uses) == []


LOCKED_CLS = textwrap.dedent("""\
    import threading

    class Svc:
        def __init__(self):
            self._lock = threading.Lock()
            self._stats = {}
            self.closed = False

        def good(self):
            with self._lock:
                self._stats["n"] = 1
                self.closed = True

        def bad(self):
            self._stats["n"] += 1
            self.closed = True
""")


class TestLockHeldRule:
    def test_unlocked_writes_flagged_locked_and_init_not(self):
        findings = run_lint("can_tpu/serve/fixture.py", LOCKED_CLS)
        assert [f.rule for f in findings] == ["LOCKHELD"] * 2
        assert {f.line for f in findings} == {15, 16}

    def test_scope_and_lockless_class_exempt(self):
        # same class outside serve/: out of scope
        assert run_lint("can_tpu/obs/fixture.py", LOCKED_CLS) == []
        lockless = ("class P:\n"
                    "    def set(self):\n"
                    "        self.x = 1\n")
        assert run_lint("can_tpu/serve/fixture.py", lockless) == []

    def test_condition_counts_as_lock(self):
        src = textwrap.dedent("""\
            import threading

            class Q:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.n = 0

                def ok(self):
                    with self._cond:
                        self.n += 1
        """)
        assert run_lint("can_tpu/serve/fixture.py", src) == []


class TestF64Rule:
    def test_f64_literals_flagged_in_device_scope_only(self):
        src = ("import numpy as np\n"
               "A = np.float64\n"
               "B = 'float64'\n")
        assert [f.rule for f in run_lint(HOT, src)] == ["F64LIT"] * 2
        # host-side density generation legitimately uses f64
        assert run_lint("can_tpu/data/density.py", src) == []


class TestPragmas:
    def test_same_line_and_line_above_suppress(self):
        inline = ("def f(x):\n"
                  "    return x.item()  "
                  "# can-tpu-lint: disable=HOSTSYNC(fetch is the API)\n")
        assert run_lint(HOT, inline) == []
        above = ("def f(x):\n"
                 "    # can-tpu-lint: disable=HOSTSYNC(fetch is the API)\n"
                 "    return x.item()\n")
        assert run_lint(HOT, above) == []
        other_rule = ("def f(x):\n"
                      "    # can-tpu-lint: disable=TIMETIME(wrong rule)\n"
                      "    return x.item()\n")
        assert [f.rule for f in run_lint(HOT, other_rule)] == ["HOSTSYNC"]

    def test_unknown_rule_pragma_rejected(self):
        src = "x = 1  # can-tpu-lint: disable=NOTARULE(because)\n"
        with pytest.raises(sl.LintUsageError, match="unknown rule"):
            sl.parse_pragmas(src, "f.py")

    def test_missing_reason_rejected(self):
        for frag in ("disable=HOSTSYNC", "disable=HOSTSYNC()",
                     "disable=HOSTSYNC(  )"):
            src = f"x = 1  # can-tpu-lint: {frag}\n"
            with pytest.raises(sl.LintUsageError, match="no reason"):
                sl.parse_pragmas(src, "f.py")

    def test_reason_may_contain_calls(self):
        src = ("x = 1  "
               "# can-tpu-lint: disable=SWALLOW(close() is best-effort)\n")
        assert sl.parse_pragmas(src, "f.py") == {1: {"SWALLOW"}}

    def test_pragma_in_string_literal_is_not_a_pragma(self):
        src = 's = "# can-tpu-lint: disable=NOTARULE(nope)"\n'
        assert sl.parse_pragmas(src, "f.py") == {}


def _mini_tree(tmp_path, kinds, emit_kinds):
    (tmp_path / "can_tpu" / "obs").mkdir(parents=True)
    (tmp_path / "can_tpu" / "__init__.py").write_text("")
    (tmp_path / "can_tpu" / "obs" / "__init__.py").write_text("")
    (tmp_path / "can_tpu" / "obs" / "bus.py").write_text(
        f"EVENT_KINDS = {tuple(kinds)!r}\n")
    body = "def go(tel):\n" + "".join(
        f"    tel.emit({k!r}, x=1)\n" for k in emit_kinds)
    (tmp_path / "can_tpu" / "obs" / "emitter.py").write_text(body)
    return str(tmp_path)


class TestEmitKindRule:
    def test_undeclared_kind_flagged_at_site(self, tmp_path):
        root = _mini_tree(tmp_path, ["a"], ["a", "b"])
        findings, _ = sl.lint_paths(root)
        (f,) = [x for x in findings if x.rule == "EMITKIND"]
        assert '"b"' in f.message and f.path.endswith("emitter.py")

    def test_declared_never_emitted_flagged_at_declaration(self, tmp_path):
        root = _mini_tree(tmp_path, ["a", "ghost"], ["a"])
        findings, _ = sl.lint_paths(root)
        (f,) = [x for x in findings if x.rule == "EMITKIND"]
        assert '"ghost"' in f.message
        assert f.path == sl.EVENT_KINDS_FILE

    def test_drift_api_both_directions(self, tmp_path):
        root = _mini_tree(tmp_path, ["a", "ghost"], ["a", "b"])
        undeclared, unemitted = sl.emit_kind_drift(root)
        assert set(undeclared) == {"b"} and unemitted == ["ghost"]


class TestBaseline:
    def _findings(self, n=2):
        return [sl.Finding("p.py", 10 + i, "SWALLOW", "m", "except: pass")
                for i in range(n)]

    def test_matching_baseline_is_clean_and_stale_is_error(self):
        fs = self._findings(2)
        base = {fs[0].fingerprint(): 2}
        new, stale = sl.check_baseline(fs, base)
        assert new == [] and stale == []
        # one fixed: the same baseline is now stale — it must FAIL
        new, stale = sl.check_baseline(fs[:1], base)
        assert new == [] and stale == [fs[0].fingerprint()]
        # one more than baselined: the extra one is new
        new, stale = sl.check_baseline(self._findings(3),
                                       {fs[0].fingerprint(): 2})
        assert len(new) == 1 and stale == []

    def test_fingerprint_is_line_shift_invariant(self):
        a = sl.Finding("p.py", 10, "SWALLOW", "m", "except: pass")
        b = sl.Finding("p.py", 99, "SWALLOW", "m", "except: pass")
        assert a.fingerprint() == b.fingerprint()

    def test_absent_or_torn_baseline_is_usage_error(self, tmp_path):
        with pytest.raises(sl.LintUsageError, match="does not exist"):
            sl.load_baseline(str(tmp_path / "nope.json"))
        p = tmp_path / "torn.json"
        p.write_text('{"version": 1, "findings": [{"pa')
        with pytest.raises(sl.LintUsageError, match="torn"):
            sl.load_baseline(str(p))
        p.write_text(json.dumps({"version": 1, "findings": [
            {"path": "p.py", "rule": "NOTARULE", "snippet": "x"}]}))
        with pytest.raises(sl.LintUsageError, match="unknown rule"):
            sl.load_baseline(str(p))

    def test_committed_baseline_loads(self):
        base = sl.load_baseline(
            os.path.join(REPO, "tools", "lint_baseline.json"))
        assert isinstance(base, dict)


class TestTreeIsClean:
    def test_real_tree_zero_unbaselined_findings(self):
        """THE acceptance pin: the library + tools lint clean
        (in-source pragmas carry their reasons; the committed baseline
        covers the rest — currently nothing)."""
        findings, suppressed = sl.lint_paths(REPO)
        baseline = sl.load_baseline(
            os.path.join(REPO, "tools", "lint_baseline.json"))
        new, stale = sl.check_baseline(findings, baseline)
        assert new == [], "\n".join(f.render() for f in new)
        assert stale == [], stale
        assert suppressed > 10  # the pragmas are real and load-bearing


class TestLintCLI:
    TOOL = os.path.join(REPO, "tools", "can_tpu_lint.py")

    def test_exit_0_on_tree(self):
        r = subprocess.run([sys.executable, self.TOOL],
                           capture_output=True, text=True, cwd=REPO,
                           env=_env())
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout

    def test_exit_1_on_violating_fixture(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
        r = subprocess.run(
            [sys.executable, self.TOOL, str(bad), "--no-baseline"],
            capture_output=True, text=True, cwd=REPO, env=_env())
        assert r.returncode == 1
        assert "SWALLOW" in r.stdout

    def test_json_output_and_list_rules(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
        r = subprocess.run(
            [sys.executable, self.TOOL, str(bad), "--no-baseline",
             "--json"],
            capture_output=True, text=True, cwd=REPO, env=_env())
        doc = json.loads(r.stdout)
        assert doc["findings"][0]["rule"] == "SWALLOW"
        r = subprocess.run([sys.executable, self.TOOL, "--list-rules"],
                           capture_output=True, text=True, cwd=REPO,
                           env=_env())
        assert r.returncode == 0
        for rule in sl.RULES:
            assert rule in r.stdout

    def test_subset_path_run_is_clean_no_false_emitkind(self):
        """A scoped run (the documented `can_tpu_lint.py can_tpu/serve`
        usage) must not fail with 'declared kind has no emitter' for
        kinds whose emitters live in files it didn't scan, nor report
        baseline staleness for entries outside its scope."""
        r = subprocess.run(
            [sys.executable, self.TOOL,
             os.path.join(REPO, "can_tpu", "serve")],
            capture_output=True, text=True, cwd=REPO, env=_env())
        assert r.returncode == 0, r.stdout + r.stderr
        # in-process twin: subset scan yields no EMITKIND findings at all
        serve = [p for p in sl.default_paths(REPO)
                 if "can_tpu/serve/" in p.replace(os.sep, "/")]
        findings, _ = sl.lint_paths(REPO, serve)
        assert [f for f in findings if f.rule == "EMITKIND"] == []

    def test_exit_2_on_unknown_rule_pragma(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("x = 1  # can-tpu-lint: disable=NOPE(reason)\n")
        r = subprocess.run(
            [sys.executable, self.TOOL, str(bad), "--no-baseline"],
            capture_output=True, text=True, cwd=REPO, env=_env())
        assert r.returncode == 2
        assert "unknown rule" in r.stderr


class TestCiLintGate:
    GATE = os.path.join(REPO, "tools", "ci_lint.sh")

    def test_lint_stage_green(self):
        r = subprocess.run(["sh", self.GATE], capture_output=True,
                           text=True, cwd=REPO,
                           env=_env(CI_LINT_ONLY="lint"))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_audit_stage_fails_on_absent_contract(self, tmp_path):
        r = subprocess.run(
            ["sh", self.GATE], capture_output=True, text=True, cwd=REPO,
            env=_env(CI_LINT_ONLY="audit",
                     CI_LINT_CONTRACT=str(tmp_path / "gone.json")))
        assert r.returncode == 1
        assert "does not exist" in r.stdout

    def test_lint_stage_fails_on_stale_baseline(self, tmp_path):
        stale = tmp_path / "stale_baseline.json"
        stale.write_text(json.dumps({"version": 1, "findings": [
            {"path": "can_tpu/zz.py", "rule": "SWALLOW",
             "snippet": "except Exception: pass", "count": 1}]}))
        r = subprocess.run(
            ["sh", self.GATE], capture_output=True, text=True, cwd=REPO,
            env=_env(CI_LINT_ONLY="lint", CI_LINT_BASELINE=str(stale)))
        assert r.returncode == 1
        assert "stale" in r.stdout
