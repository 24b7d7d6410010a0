"""Program-contract auditor: structured invariants over lowered StableHLO.

The stack ships ten compiled program families (default / bf16 / syncBN
train steps — the syncBN pair on both the full 2x4 mesh and the elastic
dp′=1 shrunk mesh — the eval step, and the f32/bf16/int8 serve predicts)
whose
correctness-critical STRUCTURE — how many collectives, what operand
shapes, which dtypes, whether params live quantized in HBM — used to be
guarded by scattered per-test regexes.  This module lowers each canonical
program once (through the same ``jit_for`` hooks the cost ledger uses,
``obs.costs.resolve_jit``) and checks machine-readable facts against the
committed ``PROGRAM_CONTRACTS.json``:

* **collective counts** per op (``all_reduce`` / ``all_gather`` /
  ``reduce_scatter`` / ``collective_permute`` / ``all_to_all``) — a
  deleted or duplicated psum changes program semantics silently;
* **all_reduce operand shapes** (exact multiset) and the packed-moments
  invariant: one-pass syncBN issues exactly ONE ``(2C+1,)`` packed
  all_reduce per BN layer (ops/bn_moments.py) — the PR-7 win the old
  test could only state as "strictly fewer";
* **dtype discipline** — zero f64 ops in any bf16/f32 program (an f64
  accumulator sneaking in runs ~10x slow on TPU and doubles HBM);
* **no host round-trips** — zero host callbacks / infeed / outfeed;
* **int8 placement** — the int8 predict must take int8 parameter tensors
  (dequant INSIDE the program, HBM holds int8; a hoisted dequant would
  quietly quadruple parameter traffic);
* **flop/byte budgets** — XLA ``cost_analysis()`` within a per-program
  noise band of the contract (cost is deterministic, so both
  directions trip — up is bloat, down is lost work).

Facts come from text because text is what XLA was actually given: the
byte-identity pin (tests/test_perf.py) already proves lowering is
deterministic, so exact structural counts are stable, not flaky.

Contract updates are intentional: ``--update`` writes a FRESH contract
to a separate path (``PROGRAM_CONTRACTS_local.json`` by default — the
PR-6/7/8 no-self-overwrite rule), which a human diffs and commits.  A
missing or torn contract is an audit FAILURE, never a pass.

CLI::

    python -m can_tpu.analysis.hlo_audit                  # fast: structure
    python -m can_tpu.analysis.hlo_audit --full           # + cost bands
    python -m can_tpu.analysis.hlo_audit --update OUT     # regenerate

Needs >= 8 devices for the syncBN programs (CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as conftest.py
and tools/ci_lint.sh set up).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Sequence

COLLECTIVE_OPS = ("all_reduce", "all_gather", "reduce_scatter",
                  "collective_permute", "all_to_all")
CONTRACT_VERSION = 1
DEFAULT_CONTRACT = "PROGRAM_CONTRACTS.json"
DEFAULT_UPDATE_OUT = "PROGRAM_CONTRACTS_local.json"

# the canonical audit configuration: small but REAL — the full CANNet
# model at the smallest (h, w) the dp=2 x sp=4 mesh legally shards
# (h % (8*sp) == 0 and >= 2 feature rows per shard)
AUDIT_HW = (64, 64)
AUDIT_DP, AUDIT_SP = 2, 4
# the RE-FORMED mesh after an elastic shrink loses half the pod
# (parallel/elastic.py): dp 2 -> 1 at the same sp.  The dp′ programs are
# contracted exactly like the full-mesh ones, so an elastic transition
# cannot silently change the compiled program's collective structure —
# the re-formed world's psums/packing are pinned, not assumed.
AUDIT_DP_SHRUNK = 1
# the serve sub-batch menu programs are pinned from ONE registry
# (can_tpu/sched.default_serve_menu — the same call warmup, the AOT bake,
# and the batcher's covers derive from): for each serve dtype, one
# contracted program per menu size at this max_batch.  A menu changed
# outside the registry shows up as a registry/contract mismatch and
# turns the audit red (the r14 mutation test).
AUDIT_SERVE_MAX_BATCH = 2
# ceiling on the total contracted program count (enforced when the
# committed contract carries "program_budget"): program families — and
# the serve menu especially — must grow by DECISION, not accretion
DEFAULT_PROGRAM_BUDGET = 16


class AuditError(Exception):
    """The AUDIT RUN is invalid (absent/torn contract, no devices) —
    distinct from 'a program violates its contract'."""


# -- facts ----------------------------------------------------------------
@dataclasses.dataclass
class ProgramFacts:
    """What one lowered program structurally IS."""

    name: str
    collectives: Dict[str, int]
    all_reduce_shapes: List[str]   # sorted operand types, e.g. "129xf32"
    f64_ops: int
    host_calls: int
    int8_params: int
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# one all_reduce op: quoted form with a reduction region, closed by
# `}) : (input types) -> ...`; regions hold only the tiny combiner, so
# the non-greedy span is safe
_AR_RE = re.compile(
    r'"stablehlo\.all_reduce"\(.*?\}\)\s*:\s*\(([^)]*)\)', re.S)
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_MAIN_RE = re.compile(r"func\.func public @main\((.*?)\)\s*->", re.S)
_I8_ARG_RE = re.compile(r"%arg\d+: tensor<(?:\d+x)*i8>")
_HOST_RE = re.compile(
    r"custom_call\s*@\w*(?:callback|infeed|outfeed|host_)\w*"
    r"|stablehlo\.(?:infeed|outfeed)\b")
_PACKED_RE = re.compile(r"^(\d+)xf32$")


def collective_counts(text: str) -> Dict[str, int]:
    """Per-collective op counts in a StableHLO module text.  (Each op
    instance names its kind exactly once — combiner regions contain only
    ``add``/``max`` arithmetic.)"""
    return {op: len(re.findall(rf"stablehlo\.{op}\b", text))
            for op in COLLECTIVE_OPS}


def all_reduce_operand_shapes(text: str) -> List[str]:
    """Sorted operand types of every all_reduce (a packed one-pass BN
    moment round shows up here as its ``(2C+1,)`` f32 vector)."""
    shapes: List[str] = []
    for m in _AR_RE.finditer(text):
        shapes.extend(_TENSOR_RE.findall(m.group(1)))
    return sorted(shapes)


def count_f64_ops(text: str) -> int:
    return len(re.findall(r"f64", text))


def count_host_calls(text: str) -> int:
    return len(_HOST_RE.findall(text))


def count_int8_params(text: str) -> int:
    """int8 tensors among @main's parameters — the 'int8 weights live in
    HBM, dequant runs in-program' placement receipt."""
    m = _MAIN_RE.search(text)
    sig = m.group(1) if m else text
    return len(_I8_ARG_RE.findall(sig))


def facts_from_text(name: str, text: str, *,
                    cost: Optional[tuple] = None) -> ProgramFacts:
    flops = byts = None
    if cost is not None:
        flops, byts = cost
    return ProgramFacts(
        name=name,
        collectives=collective_counts(text),
        all_reduce_shapes=all_reduce_operand_shapes(text),
        f64_ops=count_f64_ops(text),
        host_calls=count_host_calls(text),
        int8_params=count_int8_params(text),
        flops=flops, bytes_accessed=byts)


def packed_bn_reduce_count(all_reduce_shapes: Sequence[str],
                           bn_channels: Sequence[int]) -> int:
    """How many all_reduce operands are packed one-pass BN moment
    vectors: 1-D f32 of size 2C+1 for one of the model's BN widths."""
    packed_sizes = {2 * int(c) + 1 for c in bn_channels}
    n = 0
    for s in all_reduce_shapes:
        m = _PACKED_RE.match(s)
        if m and int(m.group(1)) in packed_sizes:
            n += 1
    return n


# -- invariant checks -----------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Violation:
    program: str
    invariant: str   # e.g. "collectives.all_reduce", "forbid_f64"
    expected: object
    actual: object
    detail: str = ""

    def render(self) -> str:
        extra = f" — {self.detail}" if self.detail else ""
        return (f"{self.program}: {self.invariant}: expected "
                f"{self.expected}, got {self.actual}{extra}")


def check_facts(entry: dict, facts: ProgramFacts) -> List[Violation]:
    """One program's contract entry vs its fresh facts."""
    v: List[Violation] = []
    ec = entry.get("collectives")
    if ec is not None:
        for op in sorted(set(ec) | set(facts.collectives)):
            exp = int(ec.get(op, 0))
            got = int(facts.collectives.get(op, 0))
            if exp != got:
                v.append(Violation(
                    facts.name, f"collectives.{op}", exp, got,
                    "a collective was deleted" if got < exp
                    else "a collective was added"))
    es = entry.get("all_reduce_shapes")
    if es is not None:
        exp, got = sorted(es), sorted(facts.all_reduce_shapes)
        if exp != got:
            from collections import Counter

            ce, cg = Counter(exp), Counter(got)
            missing = sorted((ce - cg).elements())
            added = sorted((cg - ce).elements())
            v.append(Violation(
                facts.name, "all_reduce_shapes",
                f"{len(exp)} operands", f"{len(got)} operands",
                f"missing={missing[:6]} added={added[:6]}"))
    if entry.get("bn_channels") is not None:
        exp = int(entry.get("packed_bn_reduces",
                            len(entry["bn_channels"])))
        got = packed_bn_reduce_count(facts.all_reduce_shapes,
                                     entry["bn_channels"])
        if exp != got:
            v.append(Violation(
                facts.name, "packed_bn_reduces", exp, got,
                "one packed (2C+1,) all_reduce per BN layer"))
    if entry.get("forbid_f64") and facts.f64_ops:
        v.append(Violation(facts.name, "forbid_f64", 0, facts.f64_ops,
                           "f64 ops in a bf16/f32 program (accidental "
                           "upcast?)"))
    if entry.get("forbid_host_calls") and facts.host_calls:
        v.append(Violation(facts.name, "forbid_host_calls", 0,
                           facts.host_calls,
                           "host callback/infeed in a compiled program"))
    if entry.get("require_int8_params") and facts.int8_params == 0:
        v.append(Violation(
            facts.name, "require_int8_params", ">= 1", 0,
            "no int8 parameter tensors: the dequant was hoisted out of "
            "the jit — HBM now holds f32 weights"))
    elif (entry.get("int8_params") is not None
          and facts.int8_params != int(entry["int8_params"])):
        v.append(Violation(facts.name, "int8_params",
                           int(entry["int8_params"]), facts.int8_params))
    noise = float(entry.get("cost_noise_pct", 10.0)) / 100.0
    for key in ("flops", "bytes_accessed"):
        exp = entry.get(key)
        got = getattr(facts, key)
        if exp is None or got is None:
            continue  # fast mode / non-reporting backend: no cost check
        if not (exp * (1 - noise) <= got <= exp * (1 + noise)):
            v.append(Violation(
                facts.name, f"cost.{key}",
                f"{exp:.6g} ±{noise:.0%}", f"{got:.6g}",
                "compiled cost is deterministic: up = bloat, down = "
                "lost work"))
    return v


def render_diff(violations: Sequence[Violation]) -> str:
    if not violations:
        return "program-contract audit: OK"
    lines = [f"program-contract audit: {len(violations)} violation(s)"]
    lines += [f"  {v.render()}" for v in violations]
    lines.append("  (intentional change? regenerate with `python -m "
                 "can_tpu.analysis.hlo_audit --update "
                 f"{DEFAULT_UPDATE_OUT}`, diff, and commit)")
    return "\n".join(lines)


# -- the canonical program registry ---------------------------------------
_LOWERED_CACHE: dict = {}
_COST_CACHE: dict = {}


def _ensure_devices(n: int):
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise AuditError(
            f"the syncBN audit programs shard over {n} devices; this "
            f"backend has {len(devs)}.  On CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            f"initialises (conftest.py / tools/ci_lint.sh do)")
    return devs


def _audit_batch(b: int, dtype=None):
    import numpy as np

    h, w = AUDIT_HW
    dtype = np.float32 if dtype is None else dtype
    return {
        "image": np.zeros((b, h, w, 3), dtype),
        "dmap": np.zeros((b, h // 8, w // 8, 1), np.float32),
        "pixel_mask": np.ones((b, h // 8, w // 8, 1), np.float32),
        "sample_mask": np.ones((b,), np.float32),
    }


def _train_setup(batch_norm: bool):
    import jax

    from can_tpu.models import cannet_init
    from can_tpu.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
    )

    params = cannet_init(jax.random.key(0), batch_norm=batch_norm)
    opt = make_optimizer(make_lr_schedule(1e-3))
    if batch_norm:
        from can_tpu.models.cannet import init_batch_stats

        state = create_train_state(params, opt, init_batch_stats(params))
    else:
        state = create_train_state(params, opt)
    return params, opt, state


def _lower_train_default(compute_dtype=None):
    import jax

    from can_tpu.models import cannet_apply
    from can_tpu.train import make_train_step

    _, opt, state = _train_setup(batch_norm=False)
    step = jax.jit(make_train_step(cannet_apply, opt,
                                   compute_dtype=compute_dtype))
    return step.lower(state, _audit_batch(1))


def _lower_sp_syncbn(impl: str, dp: int = AUDIT_DP):
    """The dp x sp syncBN train step.  ``dp=AUDIT_DP_SHRUNK`` lowers the
    program an elastic shrink RE-FORMS (same sp, half the pod, lr peak
    follows the linear rule) — audited under its own contract entry so
    the transition's collective structure is an invariant, not an
    accident."""
    from can_tpu.ops.bn_moments import make_bn_ops
    from can_tpu.parallel.mesh import make_mesh
    from can_tpu.parallel.spatial import make_sp_train_step
    from can_tpu.train import make_lr_schedule, make_optimizer

    devs = _ensure_devices(dp * AUDIT_SP)
    mesh = make_mesh(devs[:dp * AUDIT_SP], dp=dp, sp=AUDIT_SP)
    opt = make_optimizer(make_lr_schedule(1e-3, world_size=dp))
    _, _, state = _train_setup(batch_norm=True)
    step = make_sp_train_step(opt, mesh, AUDIT_HW, donate=False,
                              bn_ops=make_bn_ops(impl))
    return step.lower(state, _audit_batch(dp))


def _lower_eval():
    import jax

    from can_tpu.models import cannet_apply
    from can_tpu.train import make_eval_step

    params, _, _ = _train_setup(batch_norm=False)
    step = jax.jit(make_eval_step(cannet_apply))
    batch = _audit_batch(1)
    return step.lower(params, batch)


def serve_predict_lowerable(serve_dtype: str,
                            batch_size: int = AUDIT_SERVE_MAX_BATCH):
    """(jitted predict, lowering args) for a fresh ServeEngine in this
    mode at one menu batch size — via the same ``jit_for`` hook the cost
    ledger uses, so the audited program IS the one a replica executes.
    Exposed (not just used by the registry) so the mutation tests can
    lower variants — e.g. feeding PRE-dequantized params to simulate a
    hoisted dequant."""
    import jax
    import numpy as np

    from can_tpu.data.batching import pad_batch
    from can_tpu.models import cannet_init
    from can_tpu.obs.costs import resolve_jit
    from can_tpu.serve.engine import ServeEngine, _batch_dict

    params = cannet_init(jax.random.key(0))
    eng = ServeEngine(params, serve_dtype=serve_dtype)
    h, w = AUDIT_HW
    img = np.zeros((h, w, 3), np.float32)
    dm = np.zeros((h // 8, w // 8, 1), np.float32)
    batch = _batch_dict(pad_batch([(img, dm)], (h, w), int(batch_size),
                                  [False], 8))
    args = (eng.params, batch, eng.batch_stats)
    return resolve_jit(eng._predict, args), args


def _lower_serve(serve_dtype: str,
                 batch_size: int = AUDIT_SERVE_MAX_BATCH):
    fn, args = serve_predict_lowerable(serve_dtype, batch_size)
    return fn.lower(*args)


def serve_menu_sizes():
    """The audited serve batch sizes — THE registry call
    (can_tpu/sched.default_serve_menu at the audit's max_batch).  The
    contracted serve program set derives from this at audit time, so a
    menu change anywhere (including after import) diverges from the
    committed contract and fails the audit."""
    from can_tpu.sched import default_serve_menu

    return default_serve_menu(AUDIT_SERVE_MAX_BATCH)


SERVE_DTYPES_AUDITED = ("f32", "bf16", "int8")


def serve_program_name(serve_dtype: str, size: int) -> str:
    """Top menu size keeps the historical name (``serve_predict_f32``);
    the sub-batch menu sizes are suffixed (``serve_predict_f32_b1``)."""
    base = f"serve_predict_{serve_dtype}"
    return base if size == AUDIT_SERVE_MAX_BATCH else f"{base}_b{size}"


def expected_serve_programs() -> Dict[str, object]:
    """name -> builder for every (dtype, menu size) serve program, from
    the LIVE registry menu."""
    return {serve_program_name(dt, s):
            (lambda dt=dt, s=s: _lower_serve(dt, s))
            for dt in SERVE_DTYPES_AUDITED
            for s in serve_menu_sizes()}


PROGRAM_BUILDERS = {
    "train_step_default": lambda: _lower_train_default(None),
    "train_step_bf16": lambda: _lower_train_default("bfloat16"),
    "train_step_syncbn_onepass": lambda: _lower_sp_syncbn("onepass"),
    "train_step_syncbn_twopass": lambda: _lower_sp_syncbn("twopass"),
    # the elastic dp′ mesh (shrink 2x4 -> 1x4): the programs training
    # resumes on after losing half the pod
    "train_step_syncbn_onepass_dp1": lambda: _lower_sp_syncbn(
        "onepass", dp=AUDIT_DP_SHRUNK),
    "train_step_syncbn_twopass_dp1": lambda: _lower_sp_syncbn(
        "twopass", dp=AUDIT_DP_SHRUNK),
    "eval_step_f32": _lower_eval,
    # the serve menu programs, from the one registry
    **expected_serve_programs(),
}


def bn_channels() -> List[int]:
    """Every BN layer's channel width, from the model config — the
    packed-psum sizes are 2C+1 of these."""
    from can_tpu.models.cannet import BACKEND_CFG, FRONTEND_CFG

    return ([int(v) for v in FRONTEND_CFG if v != "M"]
            + [int(v) for v in BACKEND_CFG])


def lower_program(name: str):
    """Lower (and memoise) one canonical program."""
    if name not in PROGRAM_BUILDERS:
        raise AuditError(f"unknown program {name!r} (known: "
                         f"{', '.join(sorted(PROGRAM_BUILDERS))})")
    if name not in _LOWERED_CACHE:
        _LOWERED_CACHE[name] = PROGRAM_BUILDERS[name]()
    return _LOWERED_CACHE[name]


def _cost_of_lowered(lowered) -> Optional[tuple]:
    """(flops, bytes accessed) via compile().cost_analysis(); None when
    the backend doesn't report.  Same key handling as obs/costs.py."""
    try:
        ca = lowered.compile().cost_analysis()
    except Exception as e:  # non-reporting backend: cost checks skip
        print(f"[hlo_audit] cost_analysis unavailable "
              f"({type(e).__name__}: {e}); structure-only", flush=True)
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None
    flops = ca.get("flops")
    byts = ca.get("bytes accessed")
    flops = float(flops) if flops is not None and flops > 0 else None
    byts = float(byts) if byts is not None and byts > 0 else None
    if flops is None and byts is None:
        return None
    return flops, byts


def program_facts(name: str, *, with_cost: bool = False) -> ProgramFacts:
    lowered = lower_program(name)
    cost = None
    if with_cost:
        if name not in _COST_CACHE:
            _COST_CACHE[name] = _cost_of_lowered(lowered)
        cost = _COST_CACHE[name]
    return facts_from_text(name, lowered.as_text(), cost=cost)


# -- contract I/O + audit -------------------------------------------------
def load_contract(path: str) -> dict:
    """A missing, torn, or wrong-version contract is an AUDIT FAILURE:
    'could not read the invariants' must never read as 'no invariants,
    pass'."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError as e:
        raise AuditError(
            f"contract {path} does not exist — the committed "
            f"PROGRAM_CONTRACTS.json is part of the tree; regenerate "
            f"with --update if it was deleted intentionally") from e
    except json.JSONDecodeError as e:
        raise AuditError(f"contract {path} is not valid JSON (torn "
                         f"write?): {e}") from e
    if (not isinstance(doc, dict)
            or doc.get("version") != CONTRACT_VERSION
            or not isinstance(doc.get("programs"), dict)
            or not doc["programs"]):
        raise AuditError(
            f"contract {path}: expected {{'version': {CONTRACT_VERSION}, "
            f"'programs': {{name: entry, ...}}}} with >= 1 program")
    return doc


def audit_programs(contract: dict,
                   names: Optional[Sequence[str]] = None,
                   *, with_cost: bool = False
                   ) -> List[Violation]:
    """Lower every contracted program fresh and check it.  A contract
    entry whose program no longer exists in the registry is itself a
    violation (contracts can't rot), and — on a full audit — so is a
    registry program with NO contract entry (a new program family must
    not ship unguarded)."""
    violations: List[Violation] = []
    if names is None:
        for name in sorted(set(PROGRAM_BUILDERS) - set(contract["programs"])):
            violations.append(Violation(
                name, "program_contracted", "a contract entry", "absent",
                "the registry builds a program the contract does not "
                "guard — add it via --update"))
        # the serve menu is pinned from ONE registry call
        # (sched.default_serve_menu): the LIVE menu's program set must
        # equal both the import-time registry and the contract — a menu
        # changed outside the registry path (or after import) turns the
        # audit red here, with the divergent sizes named
        live = sorted(expected_serve_programs())
        contracted = sorted(n for n in contract["programs"]
                            if n.startswith("serve_predict"))
        registered = sorted(n for n in PROGRAM_BUILDERS
                            if n.startswith("serve_predict"))
        if live != contracted or live != registered:
            violations.append(Violation(
                "<serve menu>", "serve_menu_registry",
                contracted, live,
                "the serve sub-batch menu diverged from the committed "
                "contract — menu changes go through "
                "sched.default_serve_menu + --update, never around them"))
        budget = contract.get("program_budget")
        if budget is not None and len(PROGRAM_BUILDERS) > int(budget):
            violations.append(Violation(
                "<registry>", "program_budget", f"<= {int(budget)}",
                len(PROGRAM_BUILDERS),
                "the registry grew past the committed program-count "
                "budget — raise it intentionally via --update + commit"))
    for name in (sorted(contract["programs"]) if names is None
                 else names):
        entry = contract["programs"].get(name)
        if entry is None:
            raise AuditError(f"program {name!r} is not in the contract")
        if name not in PROGRAM_BUILDERS:
            violations.append(Violation(
                name, "program_exists", "a registry builder", "absent",
                "contract names a program the registry no longer builds"))
            continue
        violations.extend(
            check_facts(entry, program_facts(name, with_cost=with_cost)))
    return violations


def build_contract(names: Optional[Sequence[str]] = None, *,
                   with_cost: bool = True) -> dict:
    """A fresh contract document from the live registry (the --update
    path; a human diffs and commits the result)."""
    import jax

    programs: dict = {}
    chans = bn_channels()
    for name in (sorted(PROGRAM_BUILDERS) if names is None else names):
        facts = program_facts(name, with_cost=with_cost)
        entry: dict = {
            "collectives": facts.collectives,
            "all_reduce_shapes": facts.all_reduce_shapes,
            "forbid_f64": True,
            "forbid_host_calls": True,
            "flops": facts.flops,
            "bytes_accessed": facts.bytes_accessed,
            "cost_noise_pct": 10,
        }
        if "syncbn" in name:
            entry["bn_channels"] = chans
            entry["packed_bn_reduces"] = packed_bn_reduce_count(
                facts.all_reduce_shapes, chans)
        if "int8" in name:
            entry["require_int8_params"] = True
            entry["int8_params"] = facts.int8_params
        programs[name] = entry
    return {
        "version": CONTRACT_VERSION,
        "program_budget": DEFAULT_PROGRAM_BUDGET,
        "generated": {
            "jax": jax.__version__,
            "backend": jax.devices()[0].platform,
            "image_hw": list(AUDIT_HW),
            "mesh": {"dp": AUDIT_DP, "sp": AUDIT_SP},
            "serve_menu": list(serve_menu_sizes()),
            "with_cost": bool(with_cost),
        },
        "programs": programs,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Audit the canonical compiled programs against "
                    "PROGRAM_CONTRACTS.json")
    ap.add_argument("--contract", default=DEFAULT_CONTRACT)
    ap.add_argument("--full", action="store_true",
                    help="also compile each program and check the "
                         "flop/byte bands (slower)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset")
    ap.add_argument("--update", nargs="?", const=DEFAULT_UPDATE_OUT,
                    default=None, metavar="OUT",
                    help=f"write a FRESH contract to OUT (default "
                         f"{DEFAULT_UPDATE_OUT}) instead of auditing")
    ap.add_argument("--force", action="store_true",
                    help="allow --update to overwrite the --contract "
                         "path itself")
    args = ap.parse_args(argv)
    names = (args.programs.split(",") if args.programs else None)

    if args.update is not None:
        if (os.path.abspath(args.update) == os.path.abspath(args.contract)
                and not args.force):
            print(f"refusing to overwrite the committed contract "
                  f"{args.contract} in place (the gate would then "
                  f"compare the fresh run against itself and pass "
                  f"vacuously) — write to {DEFAULT_UPDATE_OUT}, diff, "
                  f"and commit; or pass --force")
            return 2
        doc = build_contract(names, with_cost=True)
        with open(args.update, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(doc['programs'])} program contracts to "
              f"{args.update}")
        return 0

    try:
        contract = load_contract(args.contract)
        violations = audit_programs(contract, names,
                                    with_cost=args.full)
    except AuditError as e:
        print(f"hlo_audit error: {e}")
        return 2
    print(render_diff(violations))
    n = len(contract["programs"] if names is None else names)
    if not violations:
        print(f"{n} program(s) match {args.contract}"
              f" ({'structure+cost' if args.full else 'structure'})")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
