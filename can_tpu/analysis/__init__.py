"""can_tpu.analysis — static analysis over the compiled programs and the
source tree.

Two passes, two failure classes:

* ``hlo_audit`` — lowers each canonical compiled program (the eight
  program families the stack ships: default/bf16/syncBN train steps, the
  eval step, and the quantized serve predicts) and checks STRUCTURED
  invariants over the StableHLO text and XLA ``cost_analysis()`` against
  the committed ``PROGRAM_CONTRACTS.json``: collective counts and operand
  shapes, dtype discipline (no f64), no host callbacks, int8 params held
  in HBM, flop/byte budgets.  The invariants the repo used to guard with
  per-test regexes (the ``all_reduce`` count in tests/test_batchnorm.py)
  now live here once.

* ``source_lint`` — a JAX/concurrency-aware AST linter for the hazards
  type checkers don't see: host-sync calls in hot-path modules, unfenced
  ``time.time()`` device timing, swallowed ``except Exception``,
  ``.emit(kind)`` literals drifting from ``EVENT_KINDS``, unlocked
  attribute writes in lock-declaring serve classes, and f64 literals in
  device code.  ``# can-tpu-lint: disable=RULE(reason)`` pragmas and a
  committed baseline keep the tree clean without hiding the exceptions.

Entry points: ``tools/can_tpu_lint.py`` (lint CLI),
``python -m can_tpu.analysis.hlo_audit`` (audit CLI), ``tools/ci_lint.sh``
(both, as a CI gate), and ``tests/test_analysis.py`` (tier-1).
"""

from can_tpu.analysis.source_lint import (  # noqa: F401
    Finding,
    LintUsageError,
    check_baseline,
    emit_kind_drift,
    lint_paths,
)

__all__ = [
    "Finding",
    "LintUsageError",
    "check_baseline",
    "emit_kind_drift",
    "lint_paths",
]
