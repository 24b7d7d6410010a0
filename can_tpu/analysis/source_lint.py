"""JAX/concurrency-aware AST linter for the can_tpu source tree.

Generic linters know nothing about the failure modes that actually bite
this stack: a stray ``.item()`` in the step loop serialises the pipeline
per batch, an ``except Exception: pass`` turns a dead telemetry sink into
a silent data loss, an ``.emit("kind")`` literal that skips ``EVENT_KINDS``
drops a whole event family from the report/gauge layer, and an attribute
write outside the owning lock is a race the tests only catch when the
scheduler feels like it.  Each PR-7/8 review round re-found one of these
by hand; this module makes them a machine check.

Rules (each finding carries its rule id):

* ``HOSTSYNC``  — host-sync calls in HOT-PATH modules: ``.item()``,
  ``.block_until_ready()``, ``np.asarray(...)``, ``float(<expr>)``.
  Every one forces a device→host fetch (or hints one); on the step/serve
  path that is a pipeline stall.  Deliberate fences carry a pragma.
* ``TIMETIME``  — ``time.time()`` in hot-path modules: device timing
  without a fence measures dispatch, not execution (and wall clocks
  step); hot paths use ``perf_counter`` around a fenced fetch.
* ``SWALLOW``   — ``except Exception`` / bare ``except`` whose handler
  neither re-raises, nor uses the bound exception, nor logs (print /
  ``log``/``warn``/``error``/``exception``/``debug``/``info`` /
  ``.emit``): the error evaporates.  Tree-wide.
* ``EMITKIND``  — ``.emit("<literal>")`` kinds vs ``obs/bus.py
  EVENT_KINDS``, BOTH directions (an undeclared kind silently misses
  report/gauge coverage; a declared-never-emitted kind is dead weight).
* ``LOCKHELD``  — in ``serve/`` classes that declare a lock attribute
  (``threading.Lock/RLock/Condition`` assigned in ``__init__``, or an
  attribute literally named ``lock``/``_lock``), every ``self.<attr>``
  write outside ``__init__`` must happen under ``with self.<some
  declared lock>``.  Single-writer lifecycle flags carry a pragma
  stating the invariant that makes them safe.
* ``F64LIT``    — ``float64`` literals (``np/jnp.float64`` or the string
  ``"float64"``) in DEVICE modules: f64 runs at 1/10+ rate on TPU and
  usually means an accidental upcast.  (Host-side density generation in
  ``data/`` legitimately uses f64 and is out of scope.)

Suppression: ``# can-tpu-lint: disable=RULE(reason)`` on the finding's
line or the line above.  The reason is REQUIRED — a pragma without one,
or naming an unknown rule, is a usage error, not a suppression.  A
committed baseline (``tools/lint_baseline.json``) may carry findings the
tree accepts without touching the source; a baselined finding that no
longer fires is an ERROR (baselines can't rot into dead weight).

This module deliberately imports neither jax nor anything that does —
linting the tree must cost milliseconds and run anywhere.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

RULES: Dict[str, str] = {
    "HOSTSYNC": "host-sync call (.item/.block_until_ready/np.asarray/"
                "float) in a hot-path module",
    "TIMETIME": "time.time() in a hot-path module (unfenced device "
                "timing; use perf_counter around a fenced fetch)",
    "SWALLOW": "except Exception swallowed: no raise, no use of the "
               "exception, no logging",
    "EMITKIND": ".emit(kind) literal not declared in EVENT_KINDS (or a "
                "declared kind with no emitter)",
    "LOCKHELD": "attribute write outside `with self.<lock>` in a "
                "lock-declaring serve class",
    "F64LIT": "float64 literal in a device-code module",
}

# Module scopes, as repo-relative posix prefixes (a trailing "/" scopes a
# directory).  Hot path = code on the per-step / per-request critical
# path, where one stray sync costs throughput.
HOT_PATH_MODULES: Tuple[str, ...] = (
    "can_tpu/train/loop.py",
    "can_tpu/train/steps.py",
    "can_tpu/data/prefetch.py",
    "can_tpu/serve/engine.py",
    "can_tpu/serve/batcher.py",
    "can_tpu/serve/fleet.py",
    "can_tpu/parallel/spatial.py",
    "can_tpu/parallel/data_parallel.py",
    "can_tpu/models/cannet.py",
    "can_tpu/ops/",
)
# Device modules: code that traces into compiled programs (plus the quant
# storage layer whose dtypes land in HBM).
DEVICE_MODULES: Tuple[str, ...] = (
    "can_tpu/ops/",
    "can_tpu/models/",
    "can_tpu/train/",
    "can_tpu/parallel/",
    "can_tpu/serve/engine.py",
    "can_tpu/serve/quant.py",
)
LOCK_MODULES: Tuple[str, ...] = ("can_tpu/serve/",)

EVENT_KINDS_FILE = "can_tpu/obs/bus.py"

_LOG_ATTRS = frozenset({"emit", "warning", "warn", "error", "exception",
                        "log", "info", "debug", "print_exc"})
_LOCK_FACTORY_ATTRS = frozenset({"Lock", "RLock", "Condition"})
_LOCK_NAME_RE = re.compile(r"^_?lock$")

# one pragma per comment; the reason runs to the comment's final ")" so
# it may itself contain calls/parens
PRAGMA_RE = re.compile(
    r"#\s*can-tpu-lint:\s*disable=([A-Za-z0-9_]+)\s*(?:\((.*)\))?\s*$")


class LintUsageError(Exception):
    """Bad pragma / unreadable baseline / unparsable source: the LINT RUN
    is invalid — distinct from 'the tree has findings'."""


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str       # repo-relative posix path
    line: int       # 1-indexed
    rule: str
    message: str
    snippet: str    # stripped source line — the baseline fingerprint key

    def fingerprint(self) -> Tuple[str, str, str]:
        # line numbers rot on unrelated edits; (path, rule, code text)
        # survives them and still pins the finding to a real site
        return (self.path, self.rule, self.snippet)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


def _in_scope(rel: str, prefixes: Sequence[str]) -> bool:
    return any(rel == p or (p.endswith("/") and rel.startswith(p))
               for p in prefixes)


def parse_pragmas(src: str, rel: str) -> Dict[int, set]:
    """Line -> set of disabled rule ids, parsed from COMMENT tokens only
    (a pragma quoted inside a string — this module's own docstring, a
    test fixture literal — is not a pragma).  Unknown rules and missing
    reasons raise ``LintUsageError`` — a typo'd pragma must not silently
    suppress nothing (or worse, look like it suppressed something)."""
    import io
    import tokenize

    out: Dict[int, set] = {}
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except tokenize.TokenError as e:
        raise LintUsageError(f"{rel}: untokenizable source: {e}") from e
    for tok in tokens:
        if tok.type != tokenize.COMMENT or "can-tpu-lint" not in tok.string:
            continue
        lineno = tok.start[0]
        m = PRAGMA_RE.search(tok.string)
        if m is None:
            raise LintUsageError(
                f"{rel}:{lineno}: malformed can-tpu-lint pragma (expected "
                f"`# can-tpu-lint: disable=RULE(reason)`): "
                f"{tok.string.strip()}")
        rule, reason = m.group(1), m.group(2)
        if rule not in RULES:
            raise LintUsageError(
                f"{rel}:{lineno}: pragma disables unknown rule "
                f"{rule!r} (known: {', '.join(sorted(RULES))})")
        if not reason or not reason.strip():
            raise LintUsageError(
                f"{rel}:{lineno}: pragma for {rule} has no reason — "
                f"write `disable={rule}(why this is safe)`")
        out.setdefault(lineno, set()).add(rule)
    return out


def _snippet(lines: List[str], lineno: int) -> str:
    return lines[lineno - 1].strip() if 0 < lineno <= len(lines) else ""


# -- per-node rule helpers ------------------------------------------------
def _is_np_asarray(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "asarray"
            and isinstance(f.value, ast.Name)
            and f.value.id in ("np", "numpy"))


def _is_time_time(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "time"
            and isinstance(f.value, ast.Name) and f.value.id == "time")


def _is_f64_attr(node: ast.Attribute) -> bool:
    if node.attr != "float64":
        return False
    v = node.value
    if isinstance(v, ast.Name) and v.id in ("np", "numpy", "jnp"):
        return True
    # jax.numpy.float64
    return (isinstance(v, ast.Attribute) and v.attr == "numpy"
            and isinstance(v.value, ast.Name) and v.value.id == "jax")


def _broad_except(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Name):
        names = [t.id]
    elif isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    return any(n in ("Exception", "BaseException") for n in names)


def _handler_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler neither raises, nor touches the bound
    exception, nor calls anything that looks like logging."""
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return False
        if (handler.name and isinstance(node, ast.Name)
                and node.id == handler.name):
            return False
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                return False
            if isinstance(f, ast.Attribute) and f.attr in _LOG_ATTRS:
                return False
    return True


def _self_attr_root(target: ast.expr) -> Optional[str]:
    """The attribute name X for a write whose target roots at ``self.X``
    (through any Subscript/Attribute chain), else None."""
    node = target
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        parent = node
        node = node.value
        if (isinstance(node, ast.Name) and node.id == "self"
                and isinstance(parent, ast.Attribute)):
            return parent.attr
    return None


def _lock_attrs_of(cls: ast.ClassDef) -> set:
    """Lock-like attributes this class declares in ``__init__``:
    ``self.X = threading.Lock()/RLock()/Condition(...)`` or an attribute
    literally named ``lock``/``_lock``."""
    locks: set = set()
    for fn in cls.body:
        if not (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                and fn.name == "__init__"):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                v = node.value
                if (isinstance(v, ast.Call)
                        and isinstance(v.func, ast.Attribute)
                        and v.func.attr in _LOCK_FACTORY_ATTRS):
                    locks.add(tgt.attr)
                elif _LOCK_NAME_RE.match(tgt.attr):
                    locks.add(tgt.attr)
    return locks


def _with_holds_lock(node: ast.With, locks: set) -> bool:
    for item in node.items:
        ctx = item.context_expr
        if (isinstance(ctx, ast.Attribute) and ctx.attr in locks
                and isinstance(ctx.value, ast.Name)
                and ctx.value.id == "self"):
            return True
    return False


class _LockVisitor(ast.NodeVisitor):
    """Flags self-attribute writes outside ``with self.<lock>`` within
    one lock-declaring class's non-__init__ methods."""

    def __init__(self, rel: str, lines: List[str], locks: set,
                 findings: List[Finding]):
        self.rel = rel
        self.lines = lines
        self.locks = locks
        self.findings = findings
        self.depth = 0  # with-lock nesting

    def visit_With(self, node: ast.With) -> None:
        held = _with_holds_lock(node, self.locks)
        self.depth += 1 if held else 0
        self.generic_visit(node)
        self.depth -= 1 if held else 0

    def _check_write(self, node, targets) -> None:
        if self.depth > 0:
            return
        for tgt in targets:
            attr = _self_attr_root(tgt)
            if attr is not None:
                self.findings.append(Finding(
                    self.rel, node.lineno, "LOCKHELD",
                    f"write to self.{attr} outside `with self.<lock>` in "
                    f"a class declaring {sorted(self.locks)}",
                    _snippet(self.lines, node.lineno)))

    def visit_Assign(self, node: ast.Assign) -> None:
        self._check_write(node, node.targets)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_write(node, [node.target])
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_write(node, [node.target])
        self.generic_visit(node)


def _lint_locks(tree: ast.AST, rel: str, lines: List[str],
                findings: List[Finding]) -> None:
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        locks = _lock_attrs_of(cls)
        if not locks:
            continue
        for fn in cls.body:
            if (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn.name != "__init__"):
                _LockVisitor(rel, lines, locks, findings).visit(fn)


def lint_source(rel: str, src: str
                ) -> Tuple[List[Finding], List[Tuple[int, str, str]]]:
    """Lint one file's source.  Returns (raw findings — pragmas NOT yet
    applied, emit-kind literals as (line, kind, snippet))."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        raise LintUsageError(f"{rel}:{e.lineno}: unparsable source: "
                             f"{e.msg}") from e
    lines = src.splitlines()
    findings: List[Finding] = []
    emits: List[Tuple[int, str, str]] = []
    hot = _in_scope(rel, HOT_PATH_MODULES)
    dev = _in_scope(rel, DEVICE_MODULES)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "emit"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                emits.append((node.lineno, node.args[0].value,
                              _snippet(lines, node.lineno)))
            if hot:
                if (isinstance(f, ast.Attribute)
                        and f.attr in ("item", "block_until_ready")
                        and not node.args):
                    findings.append(Finding(
                        rel, node.lineno, "HOSTSYNC",
                        f".{f.attr}() forces a device->host sync on the "
                        f"hot path", _snippet(lines, node.lineno)))
                elif _is_np_asarray(node):
                    findings.append(Finding(
                        rel, node.lineno, "HOSTSYNC",
                        "np.asarray on the hot path fetches device data "
                        "to host", _snippet(lines, node.lineno)))
                elif (isinstance(f, ast.Name) and f.id == "float"
                      and len(node.args) == 1
                      and isinstance(node.args[0],
                                     (ast.Subscript, ast.Call))):
                    # float(metrics["loss"]) / float(x.mean()) — the
                    # array-access shapes that block on a device value;
                    # bare float(name) config coercions are host scalars
                    findings.append(Finding(
                        rel, node.lineno, "HOSTSYNC",
                        "float(...) on the hot path blocks on the value "
                        "it converts", _snippet(lines, node.lineno)))
                if _is_time_time(node):
                    findings.append(Finding(
                        rel, node.lineno, "TIMETIME",
                        "time.time() around device work measures "
                        "dispatch, not execution (and wall clocks step)",
                        _snippet(lines, node.lineno)))
        elif isinstance(node, ast.ExceptHandler):
            if _broad_except(node) and _handler_swallows(node):
                findings.append(Finding(
                    rel, node.lineno, "SWALLOW",
                    "broad except neither raises, uses the exception, "
                    "nor logs — the error evaporates",
                    _snippet(lines, node.lineno)))
        elif dev and isinstance(node, ast.Attribute) and _is_f64_attr(node):
            findings.append(Finding(
                rel, node.lineno, "F64LIT",
                "float64 literal in device code (f64 is ~10x slow on "
                "TPU and usually an accidental upcast)",
                _snippet(lines, node.lineno)))
        elif (dev and isinstance(node, ast.Constant)
              and node.value == "float64"):
            findings.append(Finding(
                rel, node.lineno, "F64LIT",
                '"float64" dtype string in device code',
                _snippet(lines, node.lineno)))

    if _in_scope(rel, LOCK_MODULES):
        _lint_locks(tree, rel, lines, findings)
    return findings, emits


# -- EVENT_KINDS ----------------------------------------------------------
def declared_event_kinds(root: str) -> Tuple[List[str], int]:
    """(kinds, lineno of the declaration) parsed from obs/bus.py's AST —
    no import, so the linter stays jax-free."""
    path = os.path.join(root, EVENT_KINDS_FILE)
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "EVENT_KINDS"
                        for t in node.targets)
                and isinstance(node.value, (ast.Tuple, ast.List))):
            kinds = [e.value for e in node.value.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str)]
            return kinds, node.lineno
    raise LintUsageError(f"{EVENT_KINDS_FILE}: EVENT_KINDS tuple not found")


def default_paths(root: str) -> List[str]:
    """The lint scope: the library and the tools — the universe the
    EVENT_KINDS drift test scans."""
    import glob

    paths = sorted(
        glob.glob(os.path.join(root, "can_tpu", "**", "*.py"),
                  recursive=True)
        + glob.glob(os.path.join(root, "tools", "*.py")))
    return paths


def emit_kind_drift(root: str, paths: Optional[Sequence[str]] = None
                    ) -> Tuple[Dict[str, list], List[str]]:
    """The two drift directions, as data (tests assert on this directly):
    (undeclared: kind -> [(path, line)], declared-but-never-emitted)."""
    kinds, _ = declared_event_kinds(root)
    declared = set(kinds)
    emitted: Dict[str, list] = {}
    for path in (default_paths(root) if paths is None else paths):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path) as f:
            _, emits = lint_source(rel, f.read())
        for line, kind, _snip in emits:
            emitted.setdefault(kind, []).append((rel, line))
    undeclared = {k: v for k, v in emitted.items() if k not in declared}
    unemitted = sorted(declared - set(emitted))
    return undeclared, unemitted


# -- tree-level run -------------------------------------------------------
def lint_paths(root: str, paths: Optional[Sequence[str]] = None,
               *, rules: Optional[Sequence[str]] = None
               ) -> Tuple[List[Finding], int]:
    """Lint the tree.  Returns (findings with pragmas applied, number of
    pragma-suppressed findings).  ``rules`` restricts to a subset."""
    full_scan = paths is None
    paths = default_paths(root) if paths is None else list(paths)
    selected = set(RULES) if rules is None else set(rules)
    unknown = selected - set(RULES)
    if unknown:
        raise LintUsageError(f"unknown rule(s): {sorted(unknown)}")
    all_findings: List[Finding] = []
    pragmas_by_rel: Dict[str, Dict[int, set]] = {}
    emits_by_rel: Dict[str, List[Tuple[int, str, str]]] = {}
    for path in paths:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path) as f:
            src = f.read()
        pragmas_by_rel[rel] = parse_pragmas(src, rel)
        findings, emits = lint_source(rel, src)
        emits_by_rel[rel] = emits
        all_findings.extend(findings)

    if "EMITKIND" in selected:
        kinds, decl_line = declared_event_kinds(root)
        declared = set(kinds)
        seen: set = set()
        for rel, emits in emits_by_rel.items():
            for line, kind, snip in emits:
                seen.add(kind)
                if kind not in declared:
                    all_findings.append(Finding(
                        rel, line, "EMITKIND",
                        f'emitted kind "{kind}" is not declared in '
                        f"EVENT_KINDS ({EVENT_KINDS_FILE})", snip))
        # the reverse direction ("declared but never emitted") is only
        # meaningful over the FULL tree: a subset-path run hasn't seen
        # the other files' emitters and would report false drift
        if full_scan:
            for kind in sorted(declared - seen):
                all_findings.append(Finding(
                    EVENT_KINDS_FILE, decl_line, "EMITKIND",
                    f'declared kind "{kind}" has no emitter in the tree',
                    f'EVENT_KINDS entry "{kind}"'))

    kept: List[Finding] = []
    suppressed = 0
    for f in all_findings:
        if f.rule not in selected:
            continue
        pragmas = pragmas_by_rel.get(f.path, {})
        if (f.rule in pragmas.get(f.line, ())
                or f.rule in pragmas.get(f.line - 1, ())):
            suppressed += 1
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    return kept, suppressed


# -- baseline -------------------------------------------------------------
def load_baseline(path: str) -> Dict[Tuple[str, str, str], int]:
    """Committed-baseline fingerprints -> accepted count.  An unreadable
    or torn baseline is a usage error — it must never read as 'empty
    baseline, everything is new' OR 'nothing to check, pass'."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError as e:
        raise LintUsageError(f"baseline {path} does not exist") from e
    except json.JSONDecodeError as e:
        raise LintUsageError(f"baseline {path} is not valid JSON "
                             f"(torn write?): {e}") from e
    if not isinstance(doc, dict) or doc.get("version") != 1:
        raise LintUsageError(f"baseline {path}: expected "
                             '{"version": 1, "findings": [...]}')
    out: Dict[Tuple[str, str, str], int] = {}
    for rec in doc.get("findings", []):
        if rec.get("rule") not in RULES:
            raise LintUsageError(
                f"baseline {path}: unknown rule {rec.get('rule')!r}")
        fp = (rec["path"], rec["rule"], rec["snippet"])
        out[fp] = out.get(fp, 0) + int(rec.get("count", 1))
    return out


def check_baseline(findings: Sequence[Finding],
                   baseline: Dict[Tuple[str, str, str], int]
                   ) -> Tuple[List[Finding], List[Tuple[str, str, str]]]:
    """(new findings beyond the baseline, stale baseline entries).  Both
    must be empty for a clean run: new = the tree regressed, stale = the
    finding was fixed but the baseline still carries it (rot)."""
    counts: Dict[Tuple[str, str, str], int] = {}
    for f in findings:
        counts[f.fingerprint()] = counts.get(f.fingerprint(), 0) + 1
    new: List[Finding] = []
    seen_over: Dict[Tuple[str, str, str], int] = {}
    for f in findings:
        fp = f.fingerprint()
        seen_over[fp] = seen_over.get(fp, 0) + 1
        if seen_over[fp] > baseline.get(fp, 0):
            new.append(f)
    stale = [fp for fp, n in sorted(baseline.items())
             if counts.get(fp, 0) < n]
    return new, stale
