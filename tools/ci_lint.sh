#!/usr/bin/env sh
# CI static-analysis gate: the source linter + the program-contract audit,
# in the tier-1 flow.  Exit 0 iff BOTH pass.
#
#   tools/ci_lint.sh                 # lint + structure audit (fast, ~30s)
#   CI_LINT_FULL=1 tools/ci_lint.sh  # + compile each program and check
#                                    #   the flop/byte bands
#   CI_LINT_ONLY=lint  tools/ci_lint.sh   # linter only (milliseconds)
#   CI_LINT_ONLY=audit tools/ci_lint.sh   # contract audit only
#
# Environment knobs:
#   CI_LINT_CONTRACT   contract path (default PROGRAM_CONTRACTS.json —
#                      the committed baseline).  A missing or torn
#                      contract FAILS the gate, never passes it.
#   CI_LINT_BASELINE   lint baseline (default tools/lint_baseline.json)
#
# Updating the contract intentionally (the PR-6/7/8 no-self-overwrite
# rule: the fresh run lands ASIDE the committed baseline, a human diffs
# and commits):
#   python -m can_tpu.analysis.hlo_audit --update PROGRAM_CONTRACTS_local.json
#   diff PROGRAM_CONTRACTS.json PROGRAM_CONTRACTS_local.json
#   mv PROGRAM_CONTRACTS_local.json PROGRAM_CONTRACTS.json  # if intended
set -eu

cd "$(dirname "$0")/.."

ONLY=${CI_LINT_ONLY:-}
rc=0

if [ "$ONLY" != "audit" ]; then
    python tools/can_tpu_lint.py \
        --baseline "${CI_LINT_BASELINE:-tools/lint_baseline.json}" || rc=1
fi

if [ "$ONLY" != "lint" ]; then
    # the syncBN audit programs shard over 8 devices; force the CPU
    # host-platform split exactly like tests/conftest.py does
    FULL_FLAG=""
    if [ -n "${CI_LINT_FULL:-}" ]; then
        FULL_FLAG="--full"
    fi
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
        XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}" \
        python -m can_tpu.analysis.hlo_audit \
        --contract "${CI_LINT_CONTRACT:-PROGRAM_CONTRACTS.json}" \
        $FULL_FLAG || rc=1
fi

exit $rc
